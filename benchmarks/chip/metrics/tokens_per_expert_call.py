"""Rows a grouped product's group gets: token-expert assignments a held
expert got per call of an expert layer, mean over the window (every
program call: chunks, decode and verify rounds) — the engine's
``moe_assignments_held`` over ``moe_expert_calls`` x the experts held
(``num_experts``). 4 a plain round, 20 a verify round and 8 a chunk at 64
lanes where EVERY expert is held (the deployment's own load); the three
EP-16 cells read 2-10 where their deployment's is 32
(``moe_tokens_per_held_expert``, whose key this configuration lacks)."""


def read(obs):
    c, m = obs.get("counters") or {}, obs.get("model") or {}
    calls = c.get("moe_expert_calls")
    if obs.get("job") != "serve" or not calls or "num_experts" not in m:
        return None
    return c["moe_assignments_held"] / (calls * m["num_experts"])

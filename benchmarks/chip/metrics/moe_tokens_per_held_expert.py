"""Token-expert assignments a held expert got per call of an expert
layer, mean over the window (every program call: chunks, decode and
verify rounds): the engine's ``moe_assignments_held`` over
``moe_expert_calls`` x the experts held. The deployment this chip
stands for sees 16x as many (the configuration's ``expert_load``)."""


def read(obs):
    c = obs.get("counters") or {}
    calls = c.get("moe_expert_calls")
    if obs.get("job") != "serve" or not calls:
        return None
    return c["moe_assignments_held"] / (calls
                                        * obs["model"]["n_routed_experts"])

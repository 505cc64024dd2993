"""Prefill time per 1000 prompt tokens actually pushed through the
prefill program (Request.attribution over the miss-token counter)."""


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "open":
        return None
    miss = obs["counters"]["prefix_miss_tokens"]
    ms = sum(r["prefill_ms"] for r in obs["requests"])
    return 1000.0 * ms / miss if miss else None

"""Prompt tokens served from cached blocks over all prompt tokens
admitted in the window (engine counters)."""


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "open":
        return None
    c = obs["counters"]
    total = c["prefix_hit_tokens"] + c["prefix_miss_tokens"]
    return 100.0 * c["prefix_hit_tokens"] / total if total else None

"""Tokens emitted per decode or verify round, all lanes together (engine
counters over the window)."""


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "backlog":
        return None
    c = obs["counters"]
    rounds = c["decode_steps"] + c["verify_steps"]
    return c["decoded_tokens"] / rounds if rounds else None

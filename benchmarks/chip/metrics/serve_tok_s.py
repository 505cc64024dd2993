"""Tokens emitted in the window over the window's time, all requests."""


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "backlog":
        return None
    return obs["tokens_out"] / obs["window_s"]

"""Tokens of the fenced steps over fenced time (never --seconds)."""


def read(obs):
    if obs["job"] != "train":
        return None
    return obs["tokens"] / obs["window_s"]

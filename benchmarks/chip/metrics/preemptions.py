"""Requests preempted in the window (engine counter)."""


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "open":
        return None
    return float(obs["counters"]["preemptions"])

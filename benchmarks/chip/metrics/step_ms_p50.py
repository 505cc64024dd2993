"""Median fenced step time of the traced run (one fence a step)."""
import statistics


def read(obs):
    if obs["job"] != "train" or not obs["step_ms"]:
        return None
    return statistics.median(obs["step_ms"])

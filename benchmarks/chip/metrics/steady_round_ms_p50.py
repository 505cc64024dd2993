"""Median span around ``engine.step`` over pure decode / verify rounds,
open-loop cells (its own name: the end-to-end metric it moves differs)."""
from chiplib.common import pure_round_ms


def read(obs):
    return pure_round_ms(obs, "open")

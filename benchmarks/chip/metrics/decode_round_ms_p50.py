"""Median span around ``engine.step`` over pure decode / verify rounds,
backlog cells."""
from chiplib.common import pure_round_ms


def read(obs):
    return pure_round_ms(obs, "backlog")

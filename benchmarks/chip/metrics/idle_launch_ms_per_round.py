"""Device-idle time inside ``serving/pack`` + ``serving/dispatch`` (the
numpy tables, their uploads and the executable call until it returns) a
traced round, backlog cells."""
from chiplib import progspans


def read(obs):
    return progspans.idle_ms_per_round(
        obs, "backlog", ("serving/pack", "serving/dispatch"))

"""Device-idle time inside ``serving/step``, all phases, a traced round,
open-loop cells."""
from chiplib import progspans


def read(obs):
    return progspans.idle_ms_per_round(obs, "open")

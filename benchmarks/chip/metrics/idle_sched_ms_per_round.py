"""Device-idle time inside ``serving/admit`` + ``serving/grow`` +
``serving/emit`` (admission with its prefix lookup, block growth, the
accept / emit / finish loop) a traced round, backlog cells."""
from chiplib import progspans


def read(obs):
    return progspans.idle_ms_per_round(
        obs, "backlog",
        ("serving/admit", "serving/grow", "serving/emit"))

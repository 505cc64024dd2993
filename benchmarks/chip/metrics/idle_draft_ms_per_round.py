"""Device-idle time inside ``serving/draft`` (the host-side n-gram
drafter, every lane's context scanned) a traced round, backlog cells."""
from chiplib import progspans


def read(obs):
    return progspans.idle_ms_per_round(obs, "backlog", ("serving/draft",))

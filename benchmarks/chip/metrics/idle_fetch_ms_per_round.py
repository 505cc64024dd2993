"""Device-idle time inside ``serving/token_fetch`` (the round's one host
sync: launch latency before the first op, the tail after the last) a
traced round, backlog cells."""
from chiplib import progspans


def read(obs):
    return progspans.idle_ms_per_round(obs, "backlog",
                                       ("serving/token_fetch",))

"""Device-idle time inside ``serving/prefill`` (chunk build, uploads,
gaps between chunks, the first-token fetch) a traced round, backlog
cells."""
from chiplib import progspans


def read(obs):
    return progspans.idle_ms_per_round(
        obs, "backlog",
        ("serving/prefill", "serving/first_token_fetch"))

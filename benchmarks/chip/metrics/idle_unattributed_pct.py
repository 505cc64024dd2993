"""Share of the traced window's device-idle time in no phase span:
``serving/step``'s own statements and the benchmark's loop between
steps. A check on the tracing itself; backlog cells."""
from chiplib import progspans


def read(obs):
    return progspans.unattributed_pct(obs, "backlog")

"""Share of the decode and verify programs' device op time that falls
under no declared scope (or to an instruction the program's scope map
does not know), backlog cells. A check on the tracing itself, like
``idle_unattributed_pct``: the five ``dev_*_ms_per_round`` groups account
for the rest."""
from chiplib import devscopes


def read(obs):
    return devscopes.unscoped_pct(obs, "backlog")

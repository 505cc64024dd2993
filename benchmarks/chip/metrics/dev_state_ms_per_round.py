"""Device time of the decode and verify programs' operations issued by the
recurrent mixers (the ``ssm/*`` and ``kda/*`` scopes, whole: projections,
conv, gates, state update, gate-norm, out-proj), a traced round, backlog
cells: op events joined to ``jax.named_scope`` names by instruction name
within module (``chiplib/devscopes.py``)."""
from chiplib import devscopes


def read(obs):
    return devscopes.group_ms_per_round(obs, "backlog", "state")

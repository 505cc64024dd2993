"""Device time of the decode and verify programs' operations issued by the
layers' RMS norms (the ``norm`` scope of the shared ``_rms``; a norm nested
in another scope falls to that scope's group), a traced round, backlog
cells: op events joined to ``jax.named_scope`` names by instruction name
within module (``chiplib/devscopes.py``)."""
from chiplib import devscopes


def read(obs):
    return devscopes.group_ms_per_round(obs, "backlog", "norm")

"""Device time of the decode and verify programs' operations issued by
embedding, final norm, head product, greedy pick, the verify program's
acceptance and the int32 accumulators (the ``embed``, ``head``, ``sample``,
``spec`` and ``acc`` scopes), a traced round, backlog cells: op events
joined to ``jax.named_scope`` names by instruction name within module
(``chiplib/devscopes.py``)."""
from chiplib import devscopes


def read(obs):
    return devscopes.group_ms_per_round(obs, "backlog", "head")

"""Device time of the decode and verify programs' operations issued by
attention (the ``attn/*`` and ``mla/*`` scopes: projections, cache write,
the K/V or latent read, scores, softmax, output product), a traced round,
backlog cells: op events joined to ``jax.named_scope`` names by instruction
name within module (``chiplib/devscopes.py``)."""
from chiplib import devscopes


def read(obs):
    return devscopes.group_ms_per_round(obs, "backlog", "attn")

"""Device time of the decode and verify programs' operations issued by the
feed-forward layers (the ``mlp`` and ``moe/*`` scopes: dense SwiGLU,
routing, dispatch, the held and shared experts, combine), a traced round,
backlog cells: op events joined to ``jax.named_scope`` names by instruction
name within module (``chiplib/devscopes.py``)."""
from chiplib import devscopes


def read(obs):
    return devscopes.group_ms_per_round(obs, "backlog", "ffn")

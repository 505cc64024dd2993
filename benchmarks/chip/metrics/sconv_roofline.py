"""The short convolutions against their roofline: the least time the chip
could take for everything the conv layers do under ``sconv`` in a decode
or verify round (the architecture's ``sconv_flops_bytes``: the
in-projections, taps and out-projections of the conv layers read once and
every running lane's tails read once and written once, at the HBM rate; or
2 FLOP a fed position a weight at the MXU rate, if larger — at 5 positions
a lane it is), over the device seconds of the round programs' operations
under that scope (in-projection, the tail read, the three-tap sum, the two
gates, the tail write, out-projection). The operations are picked BY SCOPE
(``arch/swa_gqa_moe.py:scope_roofline``). Tokens fed:
``optext.tokens_fed``."""
from chiplib import optext


def read(obs):
    arch, m = obs.get("arch"), obs.get("model") or {}
    if not hasattr(arch, "sconv_flops_bytes"):
        return None
    return arch.scope_roofline(
        obs, "sconv", lambda r: arch.sconv_flops_bytes(
            m, obs["layers"], r["lanes"], optext.tokens_fed(obs, r)))

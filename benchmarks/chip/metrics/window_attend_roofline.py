"""The window layers' attention against its roofline: the least time the
chip could take for what ``attn/window`` does in a decode or verify round
(the architecture's ``window_attend_flops_bytes``: every running lane's
LIVE ring slots read once — at most ``sliding_window`` of the ring's
``window_ring_len`` — and the fed positions written once, at the HBM
rate; or the scores' and the weighted sum's FLOP at the MXU rate, if
larger), over the device seconds of the round programs' operations under
that scope: the ring write, the band read, the sink softmax. The
operations are picked BY SCOPE (``arch/swa_gqa_moe.py:scope_roofline``).
Tokens fed: ``optext.tokens_fed``."""
from chiplib import optext


def read(obs):
    arch, m = obs.get("arch"), obs.get("model") or {}
    if not hasattr(arch, "window_attend_flops_bytes"):
        return None
    return arch.scope_roofline(
        obs, "attn/window", lambda r: arch.window_attend_flops_bytes(
            m, obs["layers"], r["live_kv_tokens"], r["lanes"],
            optext.tokens_fed(obs, r)))

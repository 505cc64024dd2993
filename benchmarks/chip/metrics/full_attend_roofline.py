"""The full layers' attention against its roofline: the least time the
chip could take for what ``attn/rows`` does in a decode or verify round
(the architecture's ``full_attend_flops_bytes``: every running lane's
live K/V of the full layers read once from the paged pool, at the HBM
rate; or the scores' and the weighted sum's FLOP at the MXU rate, if
larger), over the device seconds of the round programs' operations under
that scope (the live-rows read: gather, scores, softmax fold). The
operations are picked BY SCOPE (``arch/swa_gqa_moe.py:scope_roofline``).
Tokens fed: ``optext.tokens_fed``."""
from chiplib import optext


def read(obs):
    arch, m = obs.get("arch"), obs.get("model") or {}
    if not hasattr(arch, "full_attend_flops_bytes"):
        return None
    return arch.scope_roofline(
        obs, "attn/rows", lambda r: arch.full_attend_flops_bytes(
            m, obs["layers"], r["live_kv_tokens"],
            optext.tokens_fed(obs, r) / max(r["lanes"], 1)))

"""The expert products' share of their roofline in a window-attention
cell: ``linear_expert_mm_roofline``'s definition with this family's key
names, its count of expert layers and its operations picked BY SCOPE
(``moe/experts``: ``arch/swa_gqa_moe.py:scope_roofline``) instead of by
the weights' shapes — the least time the chip could take for the held
experts' grouped products of the traced decode / verify rounds (the
architecture's ``expert_mm_flops_bytes``: the larger of FLOP / peak and
bytes / HBM rate), over the device time under that scope. The
token-expert pairs a round sent to held experts: its tokens fed x experts
a token x the window's own share of assignments that went to held
experts; the experts whose weights a call has to read: those that were
HIT, the engine's own count (``round_experts_hit``). All engine
counters."""
from chiplib import optext


def read(obs):
    arch, m = obs.get("arch"), obs.get("model") or {}
    c = obs.get("counters") or {}
    if not hasattr(arch, "scope_roofline") or not c.get("moe_assignments"):
        return None
    layers = arch.expert_layers(m, obs["layers"])
    hit = arch.round_experts_hit(m, obs["layers"], c)
    if hit is None:
        return None
    share = c["moe_assignments_held"] / c["moe_assignments"]

    def need(r):
        pairs = optext.tokens_fed(obs, r) * m["num_experts_per_tok"] * share
        flops, nbytes = arch.expert_mm_flops_bytes(m, 1, pairs, hit)
        return flops * layers, nbytes * layers

    return arch.scope_roofline(obs, "moe/experts", need)

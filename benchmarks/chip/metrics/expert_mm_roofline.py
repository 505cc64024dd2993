"""The expert products' share of their roofline: the least time the chip
could take for the held experts' grouped products of the traced pure
decode / verify rounds (the architecture's ``expert_mm_flops_bytes``: the
larger of FLOP / peak and bytes / HBM rate; at a few tokens an expert it
is the bytes of the experts that were hit), over the device time of the
operations that read a stacked expert weight (picked by the weights'
shapes in the event's text: ``chiplib/optext.py`` says why not by
scope). The token-expert pairs a round sent to held experts: its tokens
fed (``optext.tokens_fed``) x experts a token x the window's own share of
assignments that went to held experts (engine counters)."""
from chiplib import optext


def read(obs):
    m = obs.get("model") or {}
    c = obs.get("counters") or {}
    if "moe_intermediate_size" not in m or not c.get("moe_assignments"):
        return None
    held, h, w = (m["n_routed_experts"], m["hidden_size"],
                  m["moe_intermediate_size"])
    got = optext.seconds_in_pure_rounds(
        obs, rf"\[{held},{h},{2 * w}\]|\[{held},{w},{h}\]")
    if got is None:
        return None
    seconds, rounds = got
    expert_layers = obs["layers"] - m["first_k_dense_replace"]
    share = c["moe_assignments_held"] / c["moe_assignments"]
    flops = nbytes = 0.0
    for r in rounds:
        pairs = optext.tokens_fed(obs, r) * m["num_experts_per_tok"] * share
        f, b = obs["arch"].expert_mm_flops_bytes(m, 1, pairs)
        flops += f * expert_layers
        nbytes += b * expert_layers
    least = max(flops / obs["peaks"]["bf16_flops"],
                nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds

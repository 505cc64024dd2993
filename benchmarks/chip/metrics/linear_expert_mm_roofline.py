"""The expert products' share of their roofline in a linear-attention /
latent-attention cell: ``expert_mm_roofline``'s definition with this
family's key names and its count of expert layers (every layer past the
dense ones, whatever its mixer) — the least time the chip could take for
the held experts' grouped products of the traced pure decode / verify
rounds (the architecture's ``expert_mm_flops_bytes``: the larger of FLOP
/ peak and bytes / HBM rate), over the device time of the operations that
read a stacked expert weight (picked by the weights' shapes in the
event's text: ``chiplib/optext.py``). The token-expert pairs a round sent
to held experts: its tokens fed x experts a token x the window's own
share of assignments that went to held experts; the experts whose
weights a call has to read: those that were HIT, the engine's own count
(``round_experts_hit``: with seeded weights the outputs repeat and route
alike, and an even spread over-stated them — the first traced run read
107% that way). All engine counters."""
from chiplib import optext


def read(obs):
    m = obs.get("model") or {}
    c = obs.get("counters") or {}
    arch = obs.get("arch")
    if "linear_attn_config" not in m or not c.get("moe_assignments") \
            or not hasattr(arch, "expert_layers"):
        return None
    held, h, w = (m["num_experts"], m["hidden_size"],
                  m["moe_intermediate_size"])
    got = optext.seconds_in_pure_rounds(
        obs, rf"\[{held},{h},{2 * w}\]|\[{held},{w},{h}\]")
    if got is None:
        return None
    seconds, rounds = got
    layers = arch.expert_layers(m, obs["layers"])
    hit = arch.round_experts_hit(m, obs["layers"], c)
    if hit is None:
        return None
    share = c["moe_assignments_held"] / c["moe_assignments"]
    flops = nbytes = 0.0
    for r in rounds:
        pairs = optext.tokens_fed(obs, r) * m["num_experts_per_token"] \
            * share
        f, b = arch.expert_mm_flops_bytes(m, 1, pairs, hit)
        flops += f * layers
        nbytes += b * layers
    least = max(flops / obs["peaks"]["bf16_flops"],
                nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds

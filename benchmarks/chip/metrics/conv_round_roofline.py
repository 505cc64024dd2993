"""A short-convolution cell's decode round against its roofline — this
cell's ``decode_step_roofline``, in ``swa_round_roofline``'s form: the
least time the chip could take to read the weights a round needs once (of
the held experts those that were hit: the engine's count,
``round_experts_hit``), every running lane's live K/V of the attention
layers once and every running lane's tails once (the architecture's
``conv_round_bytes`` at the published HBM rate), over the seconds in which
an operation ran on the DEVICE inside that round's ``bench/engine_step``
annotation. Pure decode / verify rounds only; median over rounds. The
lanes are those still running when the round ended (a lane that finished
in it is not counted: the reading can only be low)."""
import statistics

from chiplib import trace


def read(obs):
    arch = obs.get("arch")
    if obs["job"] != "serve" or obs["loop"] != "backlog" \
            or not obs.get("trace") or not hasattr(arch, "conv_round_bytes"):
        return None
    hit = arch.round_experts_hit(obs["model"], obs["layers"],
                                 obs.get("counters") or {})
    if hit is None:
        return None
    rounds = [r for r in obs["rounds"] if r["traced"]]
    busy = trace.busy_in_spans(obs["trace"], "bench/engine_step")
    # every traced round wrote one annotation; where the profiler lost
    # some at its start, the last ones still pair up
    n = min(len(rounds), len(busy))
    shares = []
    for r, b in zip(rounds[len(rounds) - n:], busy[len(busy) - n:]):
        if r["prefill_chunks"] or b <= 0 or not (r["decode_steps"]
                                                 + r["verify_steps"]):
            continue
        least = arch.conv_round_bytes(
            obs["model"], obs["layers"], r["live_kv_tokens"], r["lanes"],
            hit) / obs["peaks"]["hbm_bytes_per_s"]
        shares.append(100.0 * least / b)
    return statistics.median(shares) if shares else None

"""The decode round's share of its roofline: the least time the chip
could take to read the weights once and every running lane's live K/V
once (the architecture's ``decode_round_bytes`` at the published HBM
rate: ``obs["arch"]``, the configuration's adapter), over the
seconds in which an operation ran on the DEVICE during that round: the
device events of the trace that fall inside the round's
``bench/engine_step`` annotation. Pure decode / verify rounds only;
median over rounds. Host time between and inside rounds (drafting,
scheduling, the token fetch) is not in it: that is ``decode_round_ms_p50``
and the idle share."""
import statistics

from chiplib import trace


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "backlog" \
            or not obs.get("trace"):
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
        least = obs["arch"].decode_round_bytes(
            obs["model"], obs["layers"], r["live_kv_tokens"]) \
            / obs["peaks"]["hbm_bytes_per_s"]
        shares.append(100.0 * least / b)
    return statistics.median(shares) if shares else None

"""A short-convolution cell's PREFILL CHUNK against its roofline: the call
that takes most of its window. The least time the chip could take for one
chunk (the architecture's ``conv_chunk_flops_bytes``: the weights with the
experts such a chunk hits, its lane's live K/V, its tail, at the HBM rate;
or its FLOPs at the MXU rate, if larger) times the executions of the
prefill program that start in the traced window, over the device seconds of
those executions' operations (``chiplib/devscopes.py``: every operation of
the program whose compile site is ``serving/prefill``). An engine step that
prefills also runs a round, so the chunks cannot be timed by the step's
span: they are told apart by PROGRAM. What a chunk holds is the window's
mean: real tokens a chunk from the engine's counters (prompt tokens fed
over chunks; a prompt's last chunk is padded), the lane's live tokens from
the window's prompts (a prompt of ``n`` tokens runs its chunks at a mean
context of ``n / 2``, and ``n / chunk`` of them)."""
from chiplib import devscopes


def read(obs):
    arch, c = obs.get("arch"), obs.get("counters") or {}
    if not hasattr(arch, "conv_chunk_flops_bytes") \
            or not c.get("prefill_chunks") or obs.get("loop") != "backlog":
        return None
    red = devscopes.table(obs)
    prompts = [f["prompt_len"] for f in obs.get("requests", ())]
    if red is None or not red["prefill_calls"] or not prompts:
        return None
    seconds = red["seconds"].get("prefill", 0.0)
    if seconds <= 0:
        return None
    flops, nbytes = arch.conv_chunk_flops_bytes(
        obs["model"], obs["layers"],
        c["prefix_miss_tokens"] / c["prefill_chunks"],
        sum(n * n for n in prompts) / (2.0 * sum(prompts)))
    least = max(flops / obs["peaks"]["bf16_flops"],
                nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * red["prefill_calls"] / seconds

"""The latent read and attention's share of their roofline: the least
time the chip could take to read every running lane's live latent cache
once and do the absorbed attention over it, in the traced pure decode /
verify rounds (the architecture's ``mla_attend_flops_bytes``, the larger of
FLOP / peak and bytes / HBM rate), over the device time of the
operations that read or make a lane's gathered table or the attention's
own tensors (``pattern``). The program gathers every table slot, live or
not; what is REQUIRED counts the live ones."""
import re

from chiplib import optext

TILE = 128  # the TPU's lane tile: a stored cache entry may be padded to it


def pattern(names, lanes, m):
    """The regular expression that picks the latent read and attention out
    of whole instruction names, or None where no gathered cache is among
    them. The gathered cache is ``[lanes, slots, stored]``: ``stored`` the
    entry's width (latent + rotary key) as it is or padded to whole lane
    tiles (576 -> 640), ``slots`` the LARGEST such middle dimension — a
    round's new entries are ``[lanes, k + 1, width]`` too, and reading
    them for the cache picked the write path (review of PR 27). Picked:
    that cache (the gather makes it in blocks, ``[lanes x blocks a lane,
    block, stored]``), every ``[lanes, .., slots]`` tensor (scores,
    softmax, mask) and the per-head latent queries and outputs ``[lanes,
    .., heads, latent or stored]`` (``kv_b`` absorbed into the query and
    out of the output)."""
    dc, nh = m["kv_lora_rank"], m["num_attention_heads"]
    width = dc + m["qk_rope_head_dim"]
    slots, stored = 0, None
    for w in sorted({width, -(-width // TILE) * TILE}):
        rx = re.compile(rf"\[{lanes},(\d+),{w}[\],]")
        for name in names:
            for g in rx.finditer(name):
                if int(g.group(1)) > slots:
                    slots, stored = int(g.group(1)), w
    if stored is None:
        return None
    in_blocks = set()
    rx = re.compile(rf"\[(\d+),(\d+),{stored}\]")
    for name in names:
        for g in rx.finditer(name):
            if int(g.group(1)) * int(g.group(2)) == lanes * slots:
                in_blocks.add(g.group(0))
    return "|".join(
        [rf"\[{lanes},{slots},{stored}(,1)?\]",
         rf"\[{lanes},(\d+,)*{slots}\]",
         rf"\[{lanes},(\d+,)*{nh},({dc}|{stored})\]"]
        + [re.escape(x) for x in sorted(in_blocks)])


def read(obs):
    m = obs.get("model") or {}
    if "kv_lora_rank" not in m or not obs.get("trace") \
            or "spec_proposed_tokens" not in (obs.get("counters") or {}):
        return None
    events = optext.device_events(obs)
    if not events:
        return None
    layers = obs["layers"]
    picked = pattern([name for name, _, _ in events], obs["lanes"], m)
    if picked is None:
        return None
    got = optext.seconds_in_pure_rounds(obs, picked)
    if got is None:
        return None
    seconds, rounds = got
    flops = nbytes = 0.0
    for r in rounds:
        fed = optext.tokens_fed(obs, r)
        # every token fed sees its lane's cache (and itself)
        seen = fed * (r["live_kv_tokens"] / max(r["lanes"], 1) + 1)
        f, b = obs["arch"].mla_attend_flops_bytes(
            m, fed, seen, r["live_kv_tokens"] + fed)
        flops += f * layers
        nbytes += b * layers
    least = max(flops / obs["peaks"]["bf16_flops"],
                nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds

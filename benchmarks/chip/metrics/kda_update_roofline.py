"""The delta rule's state update against its roofline: the least time the
chip could take to read every running lane's matrix state once and write
it once (the architecture's ``kda_update_bytes`` at the published HBM
rate; the update's FLOP, a few a state element, are far below that), over
the device time of the operations that touch a state array, in the traced
pure decode / verify rounds. The operations are picked by the state's
SHAPE in their instruction text (``chiplib/optext.py`` says why not by
scope): any float32 array with as many elements as one layer's ``[lanes,
heads, d, d]`` array, whatever reshape the compiler made of it. A plain
round goes through the state twice (one read for the correction and the
output, one read and write for the update: the correction depends on a
reduction over the whole state) and a verify round twice (once to read it
for the round's outputs, once to apply what was accepted): both count as
time, not as bytes required."""
import math
import re

from chiplib import optext

_F32 = re.compile(r"f32\[([\d,]+)\]")


def pattern(names, lanes, m):
    """The regular expression that picks the state arrays' operations out
    of whole instruction names, or None where none is among them."""
    la = m["linear_attn_config"]
    slab = lanes * la["num_heads"] * la["head_dim"] ** 2
    found = set()
    for name in names:
        for g in _F32.finditer(name):
            if math.prod(int(x) for x in g.group(1).split(",")) == slab:
                found.add(g.group(0))
    return "|".join(re.escape(x) for x in sorted(found)) or None


def read(obs):
    m = obs.get("model") or {}
    arch = obs.get("arch")
    if "linear_attn_config" not in m or not obs.get("trace") \
            or not hasattr(arch, "kda_update_bytes"):
        return None
    events = optext.device_events(obs)
    if not events:
        return None
    picked = pattern([name for name, _, _ in events], obs["lanes"], m)
    if picked is None:
        return None
    got = optext.seconds_in_pure_rounds(obs, picked)
    if got is None:
        return None
    seconds, rounds = got
    nbytes = sum(arch.kda_update_bytes(m, obs["layers"], r["lanes"])
                 for r in rounds)
    return 100.0 * nbytes / obs["peaks"]["hbm_bytes_per_s"] / seconds

"""The recurrent-state update's share of its roofline: the least time the
chip could take to read every running lane's state once and write it once
(the architecture's ``ssm_update_bytes`` at the published HBM rate; the
update's FLOP, 4 a state element, are 1/800 of that), over the device time
of the operations that touch the state pool, in the traced pure decode /
verify rounds. The operations are picked by the pool's SHAPE in their
instruction text (``chiplib/optext.py`` says why not by scope): any
float32 array with as many elements as one layer's ``[lanes, heads,
d_head, d_state]`` slab or as the whole pool, whatever reshape the
compiler made of it. A verify round goes through the state twice (once to
read it for the round's outputs, once to apply what was accepted) and a
plain round may read a layer's new state back for its output: both count
as time, not as bytes required."""
import math
import re

from chiplib import optext

_F32 = re.compile(r"f32\[([\d,]+)\]")


def pattern(names, lanes, m, n_ssm):
    """The regular expression that picks the state pool's operations out
    of whole instruction names, or None where none is among them."""
    slab = lanes * m["mamba_n_heads"] * m["mamba_d_head"] \
        * m["mamba_d_state"]
    found = set()
    for name in names:
        for g in _F32.finditer(name):
            if math.prod(int(x) for x in g.group(1).split(",")) \
                    in (slab, n_ssm * slab):
                found.add(g.group(0))
    return "|".join(re.escape(x) for x in sorted(found)) or None


def read(obs):
    m = obs.get("model") or {}
    arch = obs.get("arch")
    if "mamba_d_state" not in m or not obs.get("trace") \
            or not hasattr(arch, "ssm_update_bytes"):
        return None
    events = optext.device_events(obs)
    if not events:
        return None
    picked = pattern([name for name, _, _ in events], obs["lanes"], m,
                     arch.ssm_layers(m, obs["layers"]))
    if picked is None:
        return None
    got = optext.seconds_in_pure_rounds(obs, picked)
    if got is None:
        return None
    seconds, rounds = got
    nbytes = sum(arch.ssm_update_bytes(m, obs["layers"], r["lanes"])
                 for r in rounds)
    return 100.0 * nbytes / obs["peaks"]["hbm_bytes_per_s"] / seconds

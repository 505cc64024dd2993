"""The flash-attention kernel's share of its roofline: the least time the
chip could take for the calls' required FLOPs and bytes (the
architecture's ``flash_fwd_bwd_flops`` / ``_bytes``, ``obs["arch"]``; the
larger of the two bounds), over the kernel events' device time in the
trace. Per device: each chip runs its own shard's calls."""
from chiplib import trace

KERNEL = r"^\S+ custom-call( |$)"  # the op's own opcode, never an operand


def read(obs):
    if obs["job"] != "train" or not obs.get("trace"):
        return None
    k = trace.kernel_seconds(obs["trace"], KERNEL)
    if not k or k["seconds"] <= 0:
        return None
    steps = obs["traced_steps"]
    rows_per_chip = obs["rows"] / obs["chips"]
    arch = obs["arch"]
    flops = (arch.flash_fwd_bwd_flops(obs["model"], obs["seq"],
                                      rows_per_chip)
             * obs["layers"] * steps)
    nbytes = (arch.flash_fwd_bwd_bytes(obs["model"], obs["seq"],
                                       rows_per_chip)
              * obs["layers"] * steps)
    least = max(flops / obs["peaks"]["bf16_flops"],
                nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / k["seconds"]

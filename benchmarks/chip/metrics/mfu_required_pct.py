"""Required FLOPs (causal attention, no gather, no recompute: the
architecture's ``train_flops_per_token``, ``obs["arch"]``)
times tokens/s (a step's tokens over the traced run's median fenced step)
over chips times the published bf16 peak."""


def read(obs):
    if obs["job"] != "train":
        return None
    per_tok = obs["arch"].train_flops_per_token(obs["model"], obs["layers"],
                                                obs["seq"])
    # the traced run fences every step and starts and stops the profiler
    # inside its window: its rate is tokens of a step over the median step
    import statistics

    rate = obs["rows"] * obs["seq"] / (statistics.median(obs["step_ms"])
                                       / 1e3)
    return 100.0 * per_tok * rate / (obs["chips"]
                                     * obs["peaks"]["bf16_flops"])

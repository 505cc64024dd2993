"""Records ``data/serving_rounds.xplane.pb``: a few steps of the
program's ``ServingEngine`` at a tiny size on the chip, under the
profiler as the benchmark sets it, each step inside the benchmark's
``bench/engine_step`` annotation. ``tests/test_progspans.py`` reads the
file; record it again when the engine's phase spans change:

    chiprun -- python3 benchmarks/chip/tests/record_serving_trace.py

writes ``chiprun_out/serving_rounds.xplane.pb`` and, beside it,
``serving_rounds.json`` with what the engine's counters say the traced
steps did (copy both over the data files). ``--rehearse`` runs the same
on the CPU, where the trace has no device plane."""
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


class EveryThird:
    """A drafter that proposes (the last token again) whenever a lane's
    context length divides by three: verify rounds and plain decode
    rounds both occur, whatever the random-weight model emits."""

    def propose(self, ctx, k):
        return ctx[-1:].repeat(k) if len(ctx) % 3 == 0 else ctx[:0]


def main(argv):
    import jax
    import numpy as np

    import paddle_tpu as pt
    from chiplib import trace
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    if "--rehearse" not in argv and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: nothing recorded")
    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    model.eval()
    engine = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=4, prefill_chunk=8, max_seq_len=48),
        drafter=EveryThird())
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size

    def submit(tag):
        for i, n in enumerate((16, 5, 11)):
            engine.submit(rng.randint(0, vocab, (n,)).astype(np.int32),
                          max_new_tokens=6 - i, request_id=f"{tag}{i}")

    submit("warm")  # every program compiles before the trace
    engine.run()
    before = dict(engine.counters)
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    submit("t")
    while engine.has_work():
        with jax.profiler.TraceAnnotation("bench/engine_step"):
            engine.step()
    jax.profiler.stop_trace()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, "serving_rounds.xplane.pb")
    shutil.copy(trace.find_xplane(logdir), dst)
    did = {k: engine.counters[k] - before[k] for k in (
        "admits", "prefill_chunks", "decode_steps", "verify_steps",
        "decoded_tokens", "prefix_miss_tokens")}
    did["device"] = jax.devices()[0].device_kind
    with open(os.path.join(out, "serving_rounds.json"), "w") as f:
        json.dump(did, f)
    print(did, os.path.getsize(dst), "bytes ->", dst)


if __name__ == "__main__":
    main(sys.argv[1:])

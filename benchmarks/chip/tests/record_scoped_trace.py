"""Records ``data/serving_scoped.xplane.pb`` with the program's scope map
beside it: ``record_serving_trace.py``'s session (the program's
``ServingEngine`` at a tiny size on the chip, every step inside
``bench/engine_step``), then ``paddle_tpu.monitor.scopes.dump`` of what
the session compiled and ``chiplib/devscopes.py``'s reduction of the two,
as the session read it. ``tests/test_devscopes.py`` holds the reader to
those figures; record again when the step programs' scopes change:

    chiprun -- python3 benchmarks/chip/tests/record_scoped_trace.py

writes, under ``chiprun_out/``, ``serving_scoped.xplane.pb``,
``serving_scoped_map.json`` (the map) and ``serving_scoped.json`` (the
figures; copy the three into ``data/``). ``--rehearse`` runs the same on
the CPU, where the trace has no device plane and nothing is reduced."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import record_serving_trace as rec  # noqa: E402 — puts the roots on the path


def figures(red):
    """What a test can hold a later reading to, as plain JSON."""
    return {"rounds": red["rounds"], "prefill_calls": red["prefill_calls"],
            "executions": red["executions"], "seconds": red["seconds"],
            "by_group": red["by_group"], "mixed_s": red["mixed_s"],
            "unknown_s": red["unknown_s"],
            "host_device_skew_ms": red["host_device_skew_ms"],
            "by_path": sorted([k[0], k[1], v, red["calls"][k]]
                              for k, v in red["by_path"].items())}


def main(argv):
    from chiplib import devscopes, progspans
    from paddle_tpu.monitor import scopes

    rec.main(argv)
    out = os.path.join(rec.ROOT, "chiprun_out")
    trace = os.path.join(out, "serving_scoped.xplane.pb")
    os.replace(os.path.join(out, "serving_rounds.xplane.pb"), trace)
    os.remove(os.path.join(out, "serving_rounds.json"))
    scopes.dump(os.path.join(out, "serving_scoped_map.json"))
    registry = scopes.compiled()
    print({m: (p["label"], len(p["instructions"]), p["scoped"])
           for m, p in registry.items()},
          "stale_programs", scopes.stale_programs())
    modules, ops = devscopes.read_events(trace)
    red = devscopes.reduce(
        modules, ops, registry, None,
        [s["start"] for s in progspans.load_spans(trace)
         if s["name"] == progspans.ROUND])
    if red is None:
        print("no device plane: nothing reduced")
        return
    with open(os.path.join(out, "serving_scoped.json"), "w") as f:
        json.dump(figures(red), f)
    print(json.dumps(figures(red)))


if __name__ == "__main__":
    main(sys.argv[1:])

"""How ``benchmark/testdata/tiny_lm_trace.json`` was recorded (PR 23): a
few steps of the tiny LM preset on the chip under the profiler, loaded by
``lib.trace.load_xplane`` and cut to the events of a short stretch.

    chiprun -- python3 benchmark/tests/record_testdata.py chiprun_out/tiny_lm_trace.json

Not a test; run by hand on a machine with a TPU.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main(out_path, steps=3, keep_ns=40_000_000):
    import jax
    import numpy as np

    import run as bench_run
    from lib import trace
    from lib.profile import TracedWindow

    config = bench_run.load_json(HERE, "configs", "tiny-lm.json")
    traffic = bench_run.load_json(HERE, "traffic", "tiny-lm-train.json")
    family = bench_run.load_module("families", config["family"])
    trainer = family.Trainer(config, traffic, 1)
    next_batch = family.batch_source(config, traffic, np.random.default_rng(1))
    trainer.step(next_batch())
    log_dir = os.path.join(os.path.dirname(HERE), ".bench_trace", "testdata")
    with TracedWindow(log_dir):
        for _ in range(steps):
            with jax.profiler.TraceAnnotation("bench.step"):
                trainer.step(next_batch())
    planes = trace.load_xplane(trace.find_xplane(log_dir))
    summary = [{"plane": p["name"], "lines": [[ln["name"], len(ln["events"]), ln["events"][:3]]
                                               for ln in p["lines"]]} for p in planes]
    print(json.dumps(summary, indent=1)[:6000])
    # keep the device planes and the host lines that carry the benchmark's
    # spans, cut to the first keep_ns nanoseconds after the window opens
    start = min(e[1] for p in planes if p["name"] == trace.HOST_PLANE
                for ln in p["lines"] for e in ln["events"] if e[0] == "bench.window")
    kept = []
    for p in planes:
        if not (trace.DEVICE_PLANE.match(p["name"]) or p["name"] == trace.HOST_PLANE):
            continue
        lines = []
        for ln in p["lines"]:
            evs = [e for e in ln["events"] if start <= e[1] < start + keep_ns]
            if p["name"] == trace.HOST_PLANE and not any(e[0].startswith("bench.") for e in evs):
                continue
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        kept.append({"name": p["name"], "lines": lines})
    with open(out_path, "w") as f:
        json.dump(kept, f)
    print("reduced:", json.dumps(trace.reduce(planes))[:3000])


if __name__ == "__main__":
    main(sys.argv[1])

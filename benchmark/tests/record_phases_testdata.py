"""How ``benchmark/testdata/tiny_lm_phases.json`` was recorded (PR 24): a few
steps of a training preset on the chip under the profiler, the trace
loaded by ``lib.phases.load`` (operations with their JAX names), the
program's ring of phases of the same steps beside it, both cut to a short
stretch.

    chiprun -- python3 benchmark/tests/record_phases_testdata.py tiny-lm:tiny-lm-train chiprun_out/tiny_lm_phases.json

``--all`` keeps every event of the window (to study a real cell's trace:
``gpt2-small:lm-train-1024``, ``resnet50:image-train-b128``); the mix's own
``host_tracer_level`` applies. Not a test; run by hand on a machine with a
TPU.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("pair", help="<config>:<traffic> of a training mix")
    ap.add_argument("out")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--keep-ns", type=float, default=40e6)
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    import run as bench_run
    from lib import phases, trace
    from lib.profile import TracedWindow

    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    config_name, traffic_name = args.pair.split(":")
    config = bench_run.load_json(HERE, "configs", config_name + ".json")
    traffic = bench_run.load_json(HERE, "traffic", traffic_name + ".json")
    family = bench_run.load_module("families", config["family"])
    trainer = family.Trainer(config, traffic, 1)
    next_batch = family.batch_source(config, traffic, np.random.default_rng(1))
    for _ in range(2):
        trainer.step(next_batch())
    log_dir = os.path.join(os.path.dirname(HERE), ".bench_trace", "testdata")
    with TracedWindow(log_dir, traffic.get("host_tracer_level", 2)):
        for _ in range(args.steps):
            with jax.profiler.TraceAnnotation("bench.make_batch"):
                batch = next_batch()
            with jax.profiler.TraceAnnotation("bench.step"):
                trainer.step(batch)
    path = trace.find_xplane(log_dir)
    xplane, ring = phases.load(path), phases.program_ring()

    lo, hi = phases.window_of(xplane["planes"])
    if not args.all:
        hi = lo + args.keep_ns
    names = {e[0] for e in ring} | {"bench.window", "bench.make_batch", "bench.step"}
    kept = []
    for p in xplane["planes"]:
        device = trace.DEVICE_PLANE.match(p["name"])
        if not (device or p["name"] == trace.HOST_PLANE):
            continue
        lines = []
        for ln in p["lines"]:
            evs = [e for e in ln["events"] if lo <= e[1] < hi and (device or e[0] in names)]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        kept.append({"name": p["name"], "lines": lines})
    t0 = xplane["start_ns"]
    cut = {"start_ns": t0, "planes": kept,
           "ring": [e for e in ring if t0 is None or lo <= e[1] - t0 < hi]}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(cut, f)
    whole = {"start_ns": t0, "planes": xplane["planes"]}
    print("clock_check:", json.dumps(phases.clock_check(whole, ring)))
    print("device_scopes:", json.dumps(phases.device_scopes(whole)))
    print("idle_by_phase:", json.dumps(phases.idle_by_phase(whole, ring)))
    print("phase_stats:", json.dumps(phases.phase_stats(ring, *phases.window_on_ring_clock(whole))))


if __name__ == "__main__":
    main()

"""What the profiler costs a training cell while it is on: steps per second
untraced, then traced at each host tracer level, and whether the trace
still carries the benchmark's window span.

    python3 benchmark/tools/trace_overhead.py --workload resnet50.train --seconds 4
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    import numpy as np

    import run as bench_run
    from lib import trace
    from lib.profile import WINDOW_SPAN, TracedWindow

    cell, config, traffic = bench_run.load_cell(
        bench_run.load_json(bench_run.ROOT, "BENCHMARK.json"), args.workload)
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    family = bench_run.load_module("families", config["family"])
    trainer = family.Trainer(config, traffic, 1)
    next_batch = family.batch_source(config, traffic, np.random.default_rng(1))
    trainer.step(next_batch())

    def steps_for(seconds):
        t0, n = time.monotonic(), 0
        while time.monotonic() < t0 + seconds:
            trainer.step(next_batch())
            n += 1
        return n / (time.monotonic() - t0)

    print(json.dumps({"untraced_steps_per_s": steps_for(args.seconds)}), flush=True)
    for level in (0, 1, 2):
        tw = TracedWindow(os.path.join(os.path.dirname(HERE), ".bench_trace", "overhead"), level)
        with tw:
            rate = steps_for(args.seconds)
        planes = trace.load_xplane(trace.find_xplane(tw.log_dir))
        spans = sum(1 for p in planes if p["name"] == trace.HOST_PLANE
                    for ln in p["lines"] for e in ln["events"] if e[0] == WINDOW_SPAN)
        host_events = sum(len(ln["events"]) for p in planes if p["name"] == trace.HOST_PLANE
                          for ln in p["lines"])
        r = trace.reduce(planes)
        print(json.dumps({"host_tracer_level": level, "traced_steps_per_s": rate,
                          "window_span_found": bool(spans), "host_events": host_events,
                          "idle_share": r["idle_share"], "idle_gaps": r["idle_gaps"][:4]}), flush=True)
    print(json.dumps({"untraced_again_steps_per_s": steps_for(args.seconds)}), flush=True)


if __name__ == "__main__":
    main()

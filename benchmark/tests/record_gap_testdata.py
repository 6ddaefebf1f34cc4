"""How ``benchmark/testdata/tiny_serve_gaps.json`` was recorded (PR 37): a
``GenerationEngine`` over a small ``TransformerLM`` on the chip under the
profiler: two requests decode side by side, a third is claimed between two
of their steps, so the stretch holds plain pairs of decode executions and
a pair with a prefill between. Kept: the first device's ``XLA Modules``
line, the host span ``bench.window``, ``profile_start_time``, and the
program's four-wide ring of the same stretch
(``deeplearning4j_tpu.obs.trace.caused_phases``).

    chiprun -- python3 benchmark/tests/record_gap_testdata.py chiprun_out/tiny_serve_gaps.json

The model is sized so that a decode step takes some milliseconds (24
layers of 1024, 16 slots of 512): the join of ``lib/gap_read.py`` tells
neighbouring steps apart by a quarter of a step at most. Not a test; run
by hand on a machine with a TPU.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

PROGRAM = "jit__decode"


def main():
    out_path = sys.argv[1]
    import jax
    import numpy as np

    from lib import gap_read, phases, trace
    from lib.profile import TracedWindow

    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.runtime import enable_compile_cache
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    lm = TransformerLM(vocab_size=8192, d_model=1024, n_heads=8, n_layers=24, max_length=512,
                       compute_dtype="bfloat16", seed=37).init()
    engine = GenerationEngine(lm, n_slots=16, prefill_buckets=[32])
    engine.warmup()
    rng = np.random.default_rng(37)

    def ask(max_new):
        return engine.submit(rng.integers(0, 8192, (24,)).astype(np.int32), max_new=max_new, timeout=120)

    ask(4).result(timeout=120)
    log_dir = os.path.join(os.path.dirname(HERE), ".bench_trace", "testdata")
    with TracedWindow(log_dir, 2):
        first = [ask(14), ask(14)]
        time.sleep(0.03)
        first.append(ask(6))
        for r in first:
            r.result(timeout=120)
        time.sleep(0.01)
    engine.shutdown()
    xplane, ring = phases.load(trace.find_xplane(log_dir)), gap_read.program_caused_ring()

    planes = xplane["planes"]
    lo, hi = phases.window_of(planes)
    device = phases._first_device(planes)
    kept = [{"name": device["name"],
             "lines": [{"name": trace.MODULES_LINE,
                        "events": [e for e in phases._line(device, trace.MODULES_LINE) if lo <= e[1] <= hi]}]},
            {"name": trace.HOST_PLANE,
             "lines": [{"name": "main", "events": [["bench.window", lo, hi - lo]]}]}]
    t0 = xplane["start_ns"]
    cut = {"start_ns": t0, "planes": kept, "ring": [e for e in ring if lo <= e[1] - t0 <= hi]}
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(cut, f)
    print("summary:", json.dumps(gap_read.summary(cut, cut["ring"], PROGRAM)))
    print("clock_check:", json.dumps(phases.clock_check(xplane, [e[:3] for e in ring])))
    for pair in gap_read.joined_pairs(cut, cut["ring"], PROGRAM) or gap_read.device_pairs(cut, PROGRAM)[1]:
        print("pair:", json.dumps(pair))


if __name__ == "__main__":
    main()

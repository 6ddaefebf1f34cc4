"""Read, on the chip and at a cell's own size, the two numbers every limit
of ``correct`` is set from: the largest that sound runs of the program give
over a dozen seeds, and the smallest that the control gives (the plain
reference computed in the precision below the one the configuration
states: int8 for bfloat16).

    python3 benchmark/tools/calibrate.py --workload gpt2-small.train \\
        --seeds 12 --control-seeds 3 [--first-seed 1000]

One process per seed; prints one line per seed and a summary. The limits then go
into ``benchmark/limits/<config>.<traffic>.json`` by hand, with these readings
beside them and in PERF.md.
"""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

#: the precision below the bfloat16 that every configuration states today
CONTROL = "int8"


def one_seed(args, seed, control):
    import run as bench_run

    cell, config, traffic = bench_run.load_cell(
        bench_run.load_json(bench_run.ROOT, "BENCHMARK.json"), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: needs the TPU")
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    family = bench_run.load_module("families", config["family"])
    kind = bench_run.load_module("kinds", traffic["kind"])
    print(json.dumps({"seed": seed, **kind.calibrate(family, config, traffic, seed, control)}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--single", type=int, help="(internal) read this one seed in this process")
    ap.add_argument("--with-control", action="store_true")
    args = ap.parse_args()
    if args.single is not None:
        return one_seed(args, args.single, CONTROL if args.with_control else None)

    # one process per seed: each owns the chip in turn and gives all of its
    # memory back, whatever the program leaves allocated; this parent stays off JAX
    program, control = {}, {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        cmd = [sys.executable, os.path.abspath(__file__), "--workload", args.workload,
               "--single", str(seed)]
        if i < args.control_seeds:
            cmd.append("--with-control")
        done = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        if done.returncode != 0:
            raise SystemExit(f"calibrate: seed {seed} failed")
        out = json.loads(done.stdout.strip().splitlines()[-1])
        print(json.dumps(out), flush=True)
        for k, v in out["program"].items():
            program.setdefault(k, []).append(v)
        for k, v in out.get("control", {}).items():
            control.setdefault(k, []).append(v)
    print(json.dumps({"summary": {
        k: {"program_largest": max(v), "control_smallest": min(control[k]) if k in control else None,
            "seeds": len(v), "control_seeds": len(control.get(k, []))}
        for k, v in program.items()}}))


if __name__ == "__main__":
    main()

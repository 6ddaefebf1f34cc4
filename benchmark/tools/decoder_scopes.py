"""Device time by scope of a ``decoder_lm`` cell's programs, from the trace
a ``--trace 1`` run of the cell left under ``.bench_trace/<cell>``:

    python3 benchmark/run.py --workload <cell> --seed 1 --trace 1
    python3 benchmark/tools/decoder_scopes.py --workload <cell>

prints, for the decode and the prefill program, milliseconds an execution
under each scope of ``lib/decoder_read.SCOPES`` and the largest operations
with the scope each fell under. Needs no chip.
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
    ap.add_argument("--workload", required=True)  # lib/phases.py finds the trace by it
    ap.add_argument("--top", type=int, default=14)
    args = ap.parse_args()
    from lib import decoder_read, phases, trace

    xplane, _ = phases.current()
    if not xplane:
        raise SystemExit(f"decoder_scopes: no trace under .bench_trace/{args.workload}")
    for program in ("jit__decode", "jit__prefill"):
        read = decoder_read.scope_seconds(program)
        if read is None:
            print(json.dumps({"program": program, "executions": 0}))
            continue
        by_scope, runs = read
        print(json.dumps({"program": program, "executions": runs,
                          "ms_per_execution": {k: round(1e3 * v / runs, 4) for k, v in
                                               sorted(by_scope.items(), key=lambda kv: -kv[1])}}))
    planes = xplane["planes"]
    ops, names = phases._clipped_ops(planes, *phases.window_of(planes))
    rows = sorted(trace._self_times(ops).items(), key=lambda kv: -kv[1])[: args.top]
    for name, secs in rows:
        print(json.dumps({"op": trace.op_label(name), "scope": decoder_read.scope_of(names.get(name, "")),
                          "window_ms": round(1e3 * secs, 3), "jax_name": names.get(name, "")[-90:]}))


if __name__ == "__main__":
    main()

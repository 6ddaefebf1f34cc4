"""Device time by scope of a ``looped_decoder_lm`` cell's programs, from the
trace a ``--trace 1`` run of the cell left under ``.bench_trace/<cell>``:
``tools/decoder_scopes.py`` run with the scopes of ``lib/looped_read.py``
(``pass_close`` beside the others), then the decode step by PASS: the
passes the configuration runs a token, and what one of them costs under
``attn_full`` + ``mlp`` + ``pass_close``.

    python3 benchmark/run.py --workload <cell> --seed 1 --trace 1
    python3 benchmark/tools/looped_scopes.py --workload <cell> [--top 14]

Needs no chip.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def by_pass(by_scope, runs, passes):
    """The decode step by pass, from its seconds by scope over ``runs``
    executions: milliseconds a step, a pass, and under each pass scope."""
    from lib import looped_read

    ms = {s: 1e3 * by_scope.get(s, 0.0) / runs for s in looped_read.PASS_SCOPES}
    return {"program": "jit__decode", "passes_per_step": passes,
            "ms_per_step": round(1e3 * sum(by_scope.values()) / runs, 4),
            "ms_per_pass": round(sum(ms.values()) / passes, 4),
            "ms_per_step_by_pass_scope": {k: round(v, 4) for k, v in ms.items()}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--top", type=int, default=14)
    args = ap.parse_args()
    import run as bench_run
    from lib import looped_read
    from tools import decoder_scopes

    _cell, config, _traffic = bench_run.load_cell(
        bench_run.load_json(bench_run.ROOT, "BENCHMARK.json"), args.workload)
    looped_read.with_scopes(decoder_scopes.main)
    read = looped_read.scope_seconds("jit__decode")
    if read is not None:
        print(json.dumps(by_pass(*read, config["total_ut_steps"])))


if __name__ == "__main__":
    main()

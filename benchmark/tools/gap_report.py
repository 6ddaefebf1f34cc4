"""One traced run of a serving cell, and everything ``lib/gap_read.py``
reads of it written out for a person: the five numbers, the table of the
traced span's pairs of decode executions (the device's wait, what ran
between, and what the loop did between the same two steps), and the
account that reconciles the waits with the span's idle time. ``PERF.md``'s
section 5 is written from it.

    python3 benchmark/tools/gap_report.py --workload gpt2-large.chat --seed 7 --out chiprun_out/gaps.json

It takes ``run.py``'s options (``--trace 1`` is added) and prints ``run.py``'s
lines; ``tools/phases_report.py`` stays the report of the phases. The
traced run's end-to-end numbers, which ``run.py``'s traced line leaves out,
are kept under ``traced_end_to_end`` (what the profiler costs a run: against
an untraced run of the same seed).
"""

import bisect
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def idle_account(xplane, program):
    """Where the traced window's idle seconds lie, from the device plane:
    ``plain_wait_s`` / ``claim_wait_s`` / ``other_wait_s`` (the waits of
    ``gap_read.device_pairs`` by kind, less the programs that ran in them),
    ``inside_decode_s`` / ``inside_others_s`` (idle between the operations
    of one execution), ``edges_s`` (before the first whole execution of the
    decode program and after the last), and ``idle_s``, which they sum to."""
    from lib import gap_read, phases, trace

    device = gap_read.device_pairs(xplane, program)
    if not device or not device[0]:
        return None
    runs, pairs = device
    lo, hi = phases.window_of(xplane["planes"])
    ops, _ = phases._clipped_ops(xplane["planes"], lo, hi)
    merged = trace._union([(a, b) for _, a, b in ops])
    ends = [y for _, y in merged]

    def idle(a, b):
        busy = 0.0
        for x, y in merged[bisect.bisect_right(ends, a):]:
            if x >= b:
                break
            busy += min(y, b) - max(x, a)
        return (b - a) - busy

    out = {"window_s": (hi - lo) * 1e-9, "idle_s": idle(lo, hi) * 1e-9,
           "inside_decode_s": sum(idle(a, b) for a, b in runs) * 1e-9,
           "edges_s": (idle(lo, runs[0][0]) + idle(runs[-1][1], hi)) * 1e-9}
    inside_others = 0.0
    for kind in ("plain", "claim", "other"):
        out[kind + "_wait_s"] = 0.0
    for (_, end), (start, _), pair in zip(runs, runs[1:], pairs):
        programs = sum(ns for _, ns in pair["between"])
        out[pair["kind"] + "_wait_s"] += (pair["wait_ns"] - programs) * 1e-9
        inside_others += idle(end, start) - (pair["wait_ns"] - programs)
    out["inside_others_s"] = inside_others * 1e-9
    return out


def report(xplane, ring, program):
    from lib import gap_read, phases

    def ms(pair, key):
        return None if pair.get(key) is None else pair[key] * 1e-6

    out = dict(gap_read.summary(xplane, ring, program), program=program,
               idle_account=idle_account(xplane, program), pairs_table=[])
    bounds = phases.window_on_ring_clock(xplane)
    if ring and bounds:
        # the ring is bounded: it must still hold the traced span when the run's drain has ended
        out["ring"] = {"entries": len(ring), "oldest_before_the_window_s": (bounds[0] - ring[0][1]) * 1e-9}
    device = gap_read.device_pairs(xplane, program)
    table = gap_read.joined_pairs(xplane, ring, program) or (device[1] if device else [])
    for pair in table:
        row = {"kind": pair["kind"], "wait_ms": ms(pair, "wait_ns"),
               "between": [[n, ns * 1e-6] for n, ns in pair["between"]], "causes": pair.get("causes")}
        for key in ("host_turn_ns", "emit_ns", "turn_ns", "put_ns"):
            row[key[:-2] + "ms"] = ms(pair, key)
        row["admits"] = pair.get("admits")
        if pair.get("host_turn_ns") is not None:
            row["runtime_ms"] = (pair["wait_ns"] - pair["host_turn_ns"]) * 1e-6
        out["pairs_table"].append(row)
    return out


def main():
    argv, out_path = sys.argv[1:], None
    if "--out" in argv:
        i = argv.index("--out")
        out_path = argv[i + 1]
        del argv[i:i + 2]
    import run as bench_run
    from lib import gap_read

    # run.py's traced line carries per-layer metrics only: keep what the kind measured end to end
    end_to_end, load = {}, bench_run.load_module

    def load_and_keep(folder, name):
        module = load(folder, name)
        if folder == "kinds":
            run = module.run

            def run_and_keep(ctx):
                out = run(ctx)
                end_to_end.update(out["end_to_end"])
                return out

            module.run = run_and_keep
        return module

    bench_run.load_module = load_and_keep
    rc = bench_run.main(argv + ["--trace", "1"])
    xplane, ring = gap_read.current()
    if xplane is None:
        raise SystemExit("gap_report: the run left no trace that lib/phases.py can read")
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell, config, traffic = bench_run.load_cell(bench, argv[argv.index("--workload") + 1])
    work = bench_run.load_module("families", config["family"]).work_model(config, traffic)
    out = dict(report(xplane, ring, work.get("decode_program")), traced_end_to_end=end_to_end,
               workload=cell["name"])
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "pairs_table"}))
    return rc


if __name__ == "__main__":
    sys.exit(main())

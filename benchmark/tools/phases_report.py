"""One traced run of a cell, and everything ``lib/phases.py`` reads of it
written out for a person: the clock check and the device plane's lead,
idle by program phase, the phases' statistics and the share of the window
they cover, and for each program that took 1 % of the busy time or more
the device time by scope with the largest operations and the scope each
fell in. ``PERF.md``'s section 5 is written from it.

    python3 benchmark/tools/phases_report.py --workload gpt2-large.chat --seed 7 --out chiprun_out/report.json

It takes ``run.py``'s options (``--trace 1`` is added) and prints ``run.py``'s
lines. ``--raw FILE`` also keeps the device plane, the host spans named
like a phase and the ring of the traced window, gzipped, so that the
readers can be run again on this trace without the chip
(``phases.load``'s form plus ``"ring"``).
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def report(xplane, ring, top=12):
    from lib import phases, trace

    out = {"clock_check": phases.clock_check(xplane, ring),
           "launch_leads_ns": phases.launch_leads(xplane, ring) if ring else None,
           "window_s": phases.window_seconds(xplane),
           "idle_by_phase": phases.idle_by_phase(xplane, ring),
           "device_scopes": phases.device_scopes(xplane)}
    bounds = phases.window_on_ring_clock(xplane)
    if ring and bounds:
        out["phase_stats"] = phases.phase_stats(ring, *bounds)
        lo, hi = bounds
        inside = [(a, a + d) for n, a, d in ring
                  if n not in phases.NOT_HOST_WORK and a + d > lo and a < hi]
        covered = sum(min(b, hi) - max(a, lo) for a, b in trace._union(inside))
        out["window_covered_by_phases"] = covered / (hi - lo)
    if not out["device_scopes"]:
        return out
    planes = xplane["planes"]
    busy = sum(out["device_scopes"].values())
    programs = {}
    for n, a, b in trace._clip(phases._line(phases._first_device(planes), trace.MODULES_LINE),
                               *phases.window_of(planes)):
        entry = programs.setdefault(trace.program_name(n), [0.0, 0])
        entry[0] += (b - a) * 1e-9
        entry[1] += 1
    out["programs"] = {}
    for name, (secs, runs) in programs.items():
        if secs < 0.01 * busy:
            continue
        rows = phases.scoped_ops(xplane, name)
        out["programs"][name] = {
            "seconds": secs, "executions": runs, "scopes": phases.device_scopes(xplane, name),
            "largest_ops": rows[:top],
            "largest_unscoped": [r for r in rows if r[1] == phases.UNSCOPED][:top],
            "copies": [r for r in rows if r[0].split(" ")[-1] == "copy"][:top]}
    return out


def raw(xplane, ring):
    """The traced window alone: the first device's plane, the host spans
    that bear a phase's or the benchmark's name; and the whole ring."""
    from lib import phases, trace

    lo, hi = phases.window_of(xplane["planes"])
    names = {e[0] for e in ring or ()} | {"bench.window", "bench.make_batch", "bench.step"}
    kept = []
    for p in xplane["planes"]:
        device = trace.DEVICE_PLANE.match(p["name"])
        if not (device or p["name"] == trace.HOST_PLANE):
            continue
        lines = [{"name": ln["name"], "events": [e for e in ln["events"] if lo <= e[1] <= hi
                                                 and (device or e[0] in names)]} for ln in p["lines"]]
        kept.append({"name": p["name"], "lines": [ln for ln in lines if ln["events"]]})
    return {"start_ns": xplane["start_ns"], "planes": kept, "ring": ring}


def main():
    argv, paths = sys.argv[1:], {}
    for option in ("--out", "--raw"):
        if option in argv:
            i = argv.index(option)
            paths[option] = argv[i + 1]
            del argv[i:i + 2]
    import run as bench_run
    from lib import phases

    rc = bench_run.main(argv + ["--trace", "1"])
    xplane, ring = phases.current()
    if xplane is None:
        raise SystemExit("phases_report: the run left no trace that lib/phases.py can read")
    for option, build in (("--out", report), ("--raw", raw)):
        if option in paths:
            os.makedirs(os.path.dirname(os.path.abspath(paths[option])), exist_ok=True)
            opener = gzip.open if paths[option].endswith(".gz") else open
            with opener(paths[option], "wt") as f:
                json.dump(build(xplane, ring), f, indent=1 if option == "--out" else None)
    if "--out" not in paths:
        print(json.dumps(report(xplane, ring)))
    return rc


if __name__ == "__main__":
    sys.exit(main())

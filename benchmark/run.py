#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

finds ``workloads[<cell>]`` in ``BENCHMARK.json``, then
``benchmark/configs/<config>.json`` (which names its ``family``),
``benchmark/traffic/<traffic>.json`` (which names its ``kind``) and
``benchmark/limits/<config>.<traffic>.json`` (the limits of ``correct`` for
that pair), loads
``benchmark/families/<family>.py`` and ``benchmark/kinds/<kind>.py``, runs
the cell on the TPU this process finds, and prints the contract's one
JSON object as the last line of its output. With ``--trace 1`` the metrics
are the cell's per-layer metrics, each read by
``benchmark/metrics/<metric>.py``. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

    python3 benchmark/run.py --rehearse <config>:<traffic> --seed 1 --seconds 2

is the CPU rehearsal of the same control flow at a tiny preset. Its last
line says ``rehearsal_only`` and carries counts, never a metric's name.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_age_s():
    """Seconds since this process started, from the kernel's own record."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


STARTED = time.monotonic() - process_age_s()


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(folder, name):
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no {folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(bench, workload):
    """(cell, configuration, traffic mix) of a ``workloads`` entry, by name."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json")
    return cell, *load_cell_files(cell)


def load_cell_files(cell):
    return (load_json(HERE, "configs", cell["config"] + ".json"),
            load_json(HERE, "traffic", cell["traffic"] + ".json"))


def load_limits(cell):
    """The limits of ``correct`` belong to the pair of configuration and
    mix and sit in a file of that pair's own, so that a new mix on a
    configuration that exists edits nothing."""
    path = os.path.join(HERE, "limits", f"{cell['config']}.{cell['traffic']}.json")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no limits/{os.path.basename(path)}: "
                         "without limits nothing can be judged correct")
    return load_json(path)["limits"]


class Context:
    """What a kind gets: the cell's files, the seed, the window's length,
    and the hooks that mark the window and read the device."""

    def __init__(self, args, cell, config, traffic, family, meter, chips):
        self.seed, self.seconds, self.trace = args.seed, args.seconds, bool(args.trace)
        self.cell, self.config, self.traffic, self.family = cell, config, traffic, family
        self.meter, self.chips = meter, chips
        self.rehearsal = bool(args.rehearse)
        self.trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
        self.setup_s = None
        self._at_begin = self._at_end = None
        self.marks = []

    def mark(self, name):
        """A named instant of set-up, in seconds since the process started."""
        self.marks.append([name, round(time.monotonic() - STARTED, 3)])

    def reduce_trace(self, traced):
        """The traced window's numbers; None in a CPU rehearsal, whose
        trace has no device plane to reduce."""
        if self.rehearsal:
            return None
        return traced.reduce(self.chips)[0]

    def begin_window(self):
        self.setup_s = time.monotonic() - STARTED
        self._at_begin = self.meter.read()

    def end_window(self):
        self._at_end = self.meter.read()

    def setup_compile_s(self):
        return self._at_begin[0]

    def window_compiles(self):
        return self._at_end[1] - self._at_begin[1]

    def memory_peak(self):
        """Peak bytes on the fullest chip. ``peak_bytes_in_use`` of this
        runtime counts live buffers and not a program's temporaries (PR 21),
        so beside it stands the plan: the bytes live now plus the largest
        (temporaries + outputs not aliased to arguments) of the programs
        this process has loaded, from each executable's own memory
        statistics (what ``memory_analysis()`` reads)."""
        import jax

        peaks = []
        for dev in jax.local_devices()[: self.chips]:
            stats = dev.memory_stats() or {}
            planned = 0
            for exe in dev.client.live_executables():
                try:
                    m = exe.get_compiled_memory_stats()
                except Exception:  # noqa: BLE001 - an executable without statistics plans nothing
                    continue
                planned = max(planned, m.temp_size_in_bytes + m.output_size_in_bytes
                              - m.alias_size_in_bytes)
            peaks.append(max(int(stats.get("peak_bytes_in_use", 0)),
                             int(stats.get("bytes_in_use", 0)) + int(planned)))
            self.memory_detail = {"bytes_in_use": stats.get("bytes_in_use"),
                                  "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                                  "largest_program_plan": int(planned)}
        return max(peaks)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC",
                    help="CPU rehearsal of a tiny preset; prints no metric")
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.rehearse):
        raise SystemExit("benchmark: give --workload <cell> or --rehearse <config>:<traffic>")
    if not os.path.isdir(os.path.join(ROOT, "deeplearning4j_tpu")):
        raise SystemExit("benchmark: the system under test (deeplearning4j_tpu/) is not in this checkout")

    bench = load_json(ROOT, "BENCHMARK.json")
    if args.rehearse:
        config_name, traffic_name = args.rehearse.split(":")
        cell = {"name": "rehearsal", "config": config_name, "traffic": traffic_name, "chips": 1}
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
        config, traffic = load_cell_files(cell)
    else:
        cell, config, traffic = load_cell(bench, args.workload)
    limits = load_limits(cell)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])

    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import jax

    imported = round(time.monotonic() - STARTED, 3)
    devices = jax.devices()
    device_at = round(time.monotonic() - STARTED, 3)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell["chips"]}
    if not args.rehearse and (device["platform"] != "tpu" or len(devices) < cell["chips"]):
        raise SystemExit(f"benchmark: the cell needs {cell['chips']} TPU chip(s); JAX reports "
                         f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})")
    peaks = load_json(HERE, "peaks.json").get(device["kind"])
    if peaks is None and not args.rehearse:
        raise SystemExit(f"benchmark: no peaks for device kind {device['kind']!r} in peaks.json")

    from deeplearning4j_tpu.runtime import enable_compile_cache
    from lib.meter import CompileMeter

    enable_compile_cache()  # <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR places it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    meter = CompileMeter()
    family = load_module("families", config["family"])
    kind = load_module("kinds", traffic["kind"])
    ctx = Context(args, cell, config, traffic, family, meter, cell["chips"])
    ctx.marks = [["imports", imported], ["device", device_at]]
    out = kind.run(ctx)

    from lib import compare

    correct, lines = compare.judge(out["numbers"], limits)
    for line in lines:
        line["where"] = out["where"].get(line["compared"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"detail": out["detail"], "counters": out["counters"],
                      "setup": {"setup_s": ctx.setup_s, "compile_s": ctx.setup_compile_s(),
                                "cache_hits": meter.hits, "cache_misses": meter.misses,
                                "marks": ctx.marks},
                      "window_compiles": ctx.window_compiles(),
                      "memory": getattr(ctx, "memory_detail", None)}), flush=True)

    if args.rehearse:
        print(json.dumps({"rehearsal_only": True, "correct": correct,
                          "attempted": out["attempted"], "failed": out["failed"],
                          "platform": device["platform"]}))
        return 0 if correct else 1

    def declared(group):
        return [m for m in bench[group]
                if "workloads" not in m or cell["name"] in m["workloads"]]

    metrics = {}
    if args.trace:
        run = {"counters": out["counters"], "trace": out["trace"], "peaks": peaks,
               "work": family.work_model(config, traffic), "chips": cell["chips"],
               "setup": {"compile_s": ctx.setup_compile_s()},
               "window": {"compiles": ctx.window_compiles()}}
        for m in declared("per_layer"):
            value = load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for m in declared("end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": correct, "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                               "idle_gaps": out["trace"]["idle_gaps"]}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""From a profiler trace (``.xplane.pb``) to device-busy time, operation
and program totals, and idle gaps named by what the host was doing.

Two stages, so that the reduction can be checked on a recorded trace
without the profiler: ``load_xplane`` turns the file into plain lists
(``benchmark/testdata/*.json`` holds such lists, cut from a chip trace),
and ``reduce`` turns the lists into numbers.

What a v5e trace looks like (PR 23, first traced chip run): one plane per
chip named ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per
executed HLO operation (a ``while`` wraps the events of its body) and whose
line ``XLA Modules`` has one event per program execution; the plane
``/host:CPU`` has one line per host thread with the runtime's and the
benchmark's ``TraceAnnotation`` spans. All planes share one clock.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: gaps shorter than this are summed under one name and not attributed:
#: they are the spaces between the operations of one program
SHORT_GAP_NS = 50_000.0


def find_xplane(log_dir):
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path):
    """[{name, lines: [{name, events: [[name, start_ns, duration_ns], ...]}]}]"""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1][1] = hi
        else:
            merged.append([lo, hi])
    return merged


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _self_times(events):
    """{name: seconds} where an event that encloses others (a ``while``
    around its body) keeps only the time its children leave uncovered."""
    totals = {}
    stack = []  # [name, end, covered_by_children, start]

    def close(item):
        name, end, covered, start = item
        totals[name] = totals.get(name, 0.0) + max(end - start - covered, 0.0)

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(b, stack[-1][1]) - a
        stack.append([name, b, 0.0, a])
    while stack:
        close(stack.pop())
    return {k: v * 1e-9 for k, v in totals.items()}


def program_name(event_name):
    """``jit_step(1234567)`` -> ``jit_step``"""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_label(event_name):
    """An ``XLA Ops`` event is named by its whole HLO instruction:
    ``%closed_call.82 = (bf16[...]) custom-call(...), custom_call_target="tpu_custom_call"``
    -> ``closed_call.82 custom-call:tpu_custom_call``"""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name.lstrip("%")[:80]
    label = head.lstrip("%")
    opcode = re.search(r"\s([a-z][\w\-]*)\(", " " + rest)
    if opcode:
        label += " " + opcode.group(1)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    if target:
        label += ":" + target.group(1)
    return label[:80]


def op_group(event_name):
    """The instruction's name without its number: XLA names a fusion by
    what it fuses (``convolution_add_fusion.12`` -> ``convolution_add_fusion``)."""
    head = event_name.partition(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


class _HostSpans:
    """Innermost host span that covers an instant, over all host threads."""

    def __init__(self, planes, skip):
        self.lines = []
        for plane in planes:
            if plane["name"] != HOST_PLANE:
                continue
            for line in plane["lines"]:
                evs = sorted((s, s + d, n) for n, s, d in line["events"]
                             if d > 0 and n not in skip)
                if evs:
                    self.lines.append(([e[0] for e in evs], evs))

    def covering(self, t):
        best = None
        for starts, evs in self.lines:
            i = bisect.bisect_right(starts, t) - 1
            for j in range(i, max(i - 64, -1), -1):
                s, e, n = evs[j]
                if e >= t:
                    if best is None or e - s < best[0]:
                        best = (e - s, n)
                    break
        return best[1] if best else "unattributed"


def reduce(planes, chips=1, window_span="bench.window"):
    """Numbers of one traced window.

    The window is the host span named ``window_span`` where the trace has
    one, else the extent of the device events. Returns ``window_s``,
    ``busy_s`` (union of device-operation intervals, averaged over the
    chips used), ``idle_share`` (1 - busy/window), ``programs``
    ({name: [seconds, executions]}, first chip), ``ops`` ({group: self
    seconds}, first chip) and ``gaps`` ({host span: idle seconds}, first
    chip), plus ``device_ops`` and ``idle_gaps``, the ten largest of the
    last two as [name, seconds] lists.
    """
    devices = sorted((int(DEVICE_PLANE.match(p["name"]).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p["name"]))
    devices = [p for _, p in devices][:chips]
    if not devices:
        raise ValueError("the trace has no /device:TPU:<n> plane")

    def line(plane, name):
        for ln in plane["lines"]:
            if ln["name"] == name:
                return ln["events"]
        return []

    window = None
    for plane in planes:
        if plane["name"] == HOST_PLANE:
            for ln in plane["lines"]:
                for name, start, dur in ln["events"]:
                    if name == window_span:
                        window = (start, start + dur)
    if window is None:
        every = [e for p in devices for e in line(p, OPS_LINE)]
        if not every:
            raise ValueError("no operation ran on the device in the trace")
        window = (min(e[1] for e in every), max(e[1] + e[2] for e in every))
    lo, hi = window
    window_ns = hi - lo

    busy_ns = []
    for plane in devices:
        merged = _union([(a, b) for _, a, b in _clip(line(plane, OPS_LINE), lo, hi)])
        busy_ns.append(sum(b - a for a, b in merged))
    first = devices[0]
    ops = _clip(line(first, OPS_LINE), lo, hi)
    merged = _union([(a, b) for _, a, b in ops])

    by_group = {}
    for name, secs in _self_times(ops).items():
        g = op_group(name)
        by_group[g] = by_group.get(g, 0.0) + secs
    by_op = {}
    for name, secs in _self_times(ops).items():
        by_op[op_label(name)] = by_op.get(op_label(name), 0.0) + secs

    programs = {}
    for name, a, b in _clip(line(first, MODULES_LINE), lo, hi):
        entry = programs.setdefault(program_name(name), [0.0, 0])
        entry[0] += (b - a) * 1e-9
        entry[1] += 1

    host = _HostSpans(planes, skip={window_span})
    gaps = {}
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        name = ("between operations (<50us)" if b - a < SHORT_GAP_NS
                else host.covering((a + b) / 2))
        gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    return {"window_s": window_ns * 1e-9, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / (window_ns * 1e-9),
            "programs": programs, "ops": by_group, "gaps": gaps,
            "device_ops": top(by_op), "idle_gaps": top(gaps)}

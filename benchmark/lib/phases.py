"""The program's own phases and scopes, read beside the device trace.

``lib/trace.py`` names device time by HLO instruction and idle time by
whatever host span the profiler recorded. This module reads what the
program says of itself:

- **scopes**: the ``jax.named_scope`` names of ``models/transformer_lm.py``
  (``SCOPES``). On a v5e trace every ``XLA Ops`` event carries the stat
  ``tf_op`` with the JAX operation's name,
  ``jit(step)/transpose(jvp())/while/body/closed_call/attn/dot_general:``,
  not on its events but in the plane's table of event metadata, which
  jax's ``ProfileData`` does not hand out (:func:`op_names` parses that
  table out of the file); :func:`device_scopes` groups self time by the
  innermost scope in it.
- **phases**: the ring of ``deeplearning4j_tpu.obs.trace`` (``(name,
  start_ns, duration_ns)`` on ``time.time_ns()``). A plane's times count
  from the session's ``profile_start_time`` (a stat of the plane ``Task
  Environment``), which is on the same wall clock, so a ring entry can be
  laid over the device plane with the host tracer off. :func:`idle_by_phase`
  divides every idle gap of ``lib/trace.reduce``'s definition among the
  innermost program phases that cover it.

Two things are checked in every run before a gap is put down to a phase
(:func:`clock_check`), and the ``idle_*`` readers return nothing where
either fails: the ring against the ``TraceAnnotation`` of the same name
(where the host tracer is on), and the device plane against the ring's
dispatch phases, by what launched what. The second also gives the device
plane's own lead over the host's clock, up to a millisecond a session,
which is taken out. A program without the ring or the scopes (the parent
of the PR that added them) reads as nothing to read: every function
returns ``None`` or an empty result and none raises. The readers only
read; ``tools/phases_report.py`` writes out everything for a person.
"""

import bisect
import os
import re
import statistics
import sys

from lib import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the names ``models/transformer_lm.py`` wraps its model phases in
SCOPES = ("embed", "attn", "kv_write", "mlp", "head", "loss", "sample", "update")
UNSCOPED = "unscoped"
OUTSIDE = "outside_program"
SHORT_GAPS = "between operations (<50us)"
#: the stat of an ``XLA Ops`` event that carries the JAX operation's name
OP_NAME_STAT = "tf_op"
TASK_PLANE = "Task Environment"
#: ring entries that are no host work of the loop that made them: a
#: request's wait spans other threads' work and covers whole decode steps
NOT_HOST_WORK = frozenset({"gen.queue_wait"})
#: the ring and the host plane must agree to this, or nothing is laid over
CLOCK_LIMIT_NS = 1_000_000
#: a dispatch phase is matched with the program execution it launched
DISPATCH = re.compile(r"\.dispatch$")
#: how long before a dispatch phase's start its execution is looked for:
#: a device plane that leads by more is not corrected but refused
LEAD_SEARCH_NS = 3_000_000
#: the leads of one session's dispatches must lie this close together
#: (first to third quartile), or the lead is not known well enough to
#: decide between neighbouring phases of a millisecond. Read on the v5e
#: (PR 24): 146,000 over 31 dispatches of gpt2-small.train, 263,000 over
#: 24 of gpt2-large.chat, whose launches wait for the GIL among 48
#: streaming threads
LEAD_SPREAD_LIMIT_NS = 500_000

_WRAPPER = re.compile(r"^(?:transpose|jvp|vmap|remat|checkpoint|custom_jvp|custom_vjp)\((.*)\)$")


def _say(message):
    print(f"benchmark: lib/phases.py: {message}", file=sys.stderr)


def scope_of(op_name):
    """Innermost of ``SCOPES`` in a JAX operation name, else ``unscoped``.
    ``jit(step)/transpose(jvp(head))/jit(_var)/reduce_sum:`` -> ``head``; a
    fusion of several operations is named after all of them, joined by
    ``;``, and reads as its first."""
    if not op_name:
        return UNSCOPED
    # the stat reads "<name>:<type>", the type empty on a TPU trace
    name = (op_name.rpartition(":")[0] or op_name).split(";")[0]
    for part in reversed(name.split("/")):
        while True:
            m = _WRAPPER.match(part)
            if not m:
                break
            part = m.group(1)
        if part in SCOPES:
            return part
    return UNSCOPED


# ---------------------------------------------------------------------------
# the trace file: events through jax's reader, operation names through
# protobuf's, from the few fields of the xplane schema that hold them
# ---------------------------------------------------------------------------
def _xspace_class():
    """A message class for the part of ``XSpace`` (tsl's ``xplane.proto``)
    that names operations: planes with their name and their tables of
    event and stat metadata. A plane's lines (field 3) are left
    undeclared and so skipped unread. Declared here because the compiled
    schema ships only inside TensorFlow."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    field_type = descriptor_pb2.FieldDescriptorProto
    schema = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="bench_xplane",
                                                syntax="proto3")

    def message(name, *fields):
        m = schema.message_type.add(name=name)
        for field, number, kind, repeated in fields:
            f = m.field.add(name=field, number=number,
                            label=field_type.LABEL_REPEATED if repeated else field_type.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = field_type.TYPE_MESSAGE, ".bench_xplane." + kind
            else:
                f.type = kind

    message("XStat", ("metadata_id", 1, field_type.TYPE_INT64, False),
            ("str_value", 5, field_type.TYPE_BYTES, False), ("ref_value", 7, field_type.TYPE_UINT64, False))
    # XEventMetadata and XStatMetadata: id 1, name 2; an event's stats 5
    message("Metadata", ("name", 2, field_type.TYPE_BYTES, False), ("stats", 5, "XStat", True))
    message("Entry", ("key", 1, field_type.TYPE_INT64, False), ("value", 2, "Metadata", False))
    message("XPlane", ("name", 2, field_type.TYPE_BYTES, False), ("event_metadata", 4, "Entry", True),
            ("stat_metadata", 5, "Entry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(schema)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_names(path):
    """{device plane: {event name: JAX operation name}}: the ``tf_op``
    stat of the planes' event metadata, where an operation's JAX name sits
    once an operation (interned: the stat refers to a stat-metadata entry
    whose name is the string)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())

    def text(raw):
        return raw.decode("utf-8", "replace")

    out = {}
    for plane in space.planes:
        if not trace.DEVICE_PLANE.match(text(plane.name)):
            continue
        strings = {entry.key: text(entry.value.name) for entry in plane.stat_metadata}
        table = out[text(plane.name)] = {}
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                if strings.get(stat.metadata_id) == OP_NAME_STAT:
                    table[text(entry.value.name)] = (strings.get(stat.ref_value, "") if stat.ref_value
                                                     else text(stat.str_value))
    return out


def load(path):
    """``{"start_ns": profile_start_time or None, "planes": [...]}`` with
    the planes as ``lib/trace.load_xplane`` gives them, and on the
    ``XLA Ops`` events a fourth element, the JAX operation's name."""
    from jax.profiler import ProfileData

    names = op_names(path)
    start_ns, planes = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == TASK_PLANE:
            for key, value in plane.stats:
                if key == "profile_start_time":
                    start_ns = int(value)
            continue
        table = names.get(plane.name, {})
        lines = []
        for line in plane.lines:
            with_names = bool(table) and line.name == trace.OPS_LINE
            events = []
            for e in line.events:
                event = [e.name, float(e.start_ns), float(e.duration_ns)]
                if with_names:
                    event.append(table.get(e.name, ""))
                events.append(event)
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"start_ns": start_ns, "planes": planes}


def run_trace_dir(argv=None):
    """``.bench_trace/<cell>``, where ``run.py`` puts this run's trace.
    The harness hands a reader the reduced numbers and no path, so the
    cell is read from where the harness read it: ``--workload`` on the
    command line. ``None`` where the process was not started for a cell."""
    argv = sys.argv if argv is None else argv
    for i, word in enumerate(argv):
        if word == "--workload" and i + 1 < len(argv):
            return os.path.join(ROOT, ".bench_trace", argv[i + 1])
        if word.startswith("--workload="):
            return os.path.join(ROOT, ".bench_trace", word.partition("=")[2])
    return None


def program_ring():
    """The program's ring of phases, or ``None`` where it has none."""
    try:
        from deeplearning4j_tpu.obs.trace import phases
    except ImportError:
        return None
    return [list(e) for e in phases()]


_run = {}


def current():
    """``(xplane, ring)`` of this process's traced run, parsed once; either
    is ``None`` where there is nothing to read (or the trace cannot be
    read: said on stderr, and the metrics are left out)."""
    if "xplane" not in _run:
        _run["xplane"], _run["ring"] = None, program_ring()
        log_dir = run_trace_dir()
        try:
            _run["xplane"] = load(trace.find_xplane(log_dir)) if log_dir else None
        except Exception as e:  # noqa: BLE001 - a trace this module cannot read leaves its metrics out, it fails no run
            _say(f"cannot read the trace under {log_dir}: {type(e).__name__}: {e}")
    return _run["xplane"], _run["ring"]


# ---------------------------------------------------------------------------
# the window and the device's events, as lib/trace.reduce takes them
# ---------------------------------------------------------------------------
def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def _first_device(planes):
    devices = sorted((int(trace.DEVICE_PLANE.match(p["name"]).group(1)), i)
                     for i, p in enumerate(planes) if trace.DEVICE_PLANE.match(p["name"]))
    return planes[devices[0][1]] if devices else None


def window_of(planes, window_span="bench.window"):
    """(lo, hi) of the traced window on the trace's clock: the host span
    where the trace has one, else the extent of the device's operations
    (``lib/trace.reduce``'s rule)."""
    for plane in planes:
        if plane["name"] == trace.HOST_PLANE:
            for ln in plane["lines"]:
                for e in ln["events"]:
                    if e[0] == window_span:
                        return e[1], e[1] + e[2]
    device = _first_device(planes)
    ops = _line(device, trace.OPS_LINE) if device else []
    if not ops:
        return None
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def _clipped_ops(planes, lo, hi):
    """(name, a, b) and the operation name by event name, first chip."""
    device = _first_device(planes)
    events = _line(device, trace.OPS_LINE) if device else []
    names = {e[0]: (e[3] if len(e) > 3 else "") for e in events}
    return trace._clip([e[:3] for e in events], lo, hi), names


def scoped_ops(xplane, program=None):
    """[(operation label, scope, self seconds)] of the traced window's
    operations, first chip, largest first: where each operation fell. With
    ``program`` only the operations that start inside an execution of that
    program (an ``XLA Modules`` event of that name). ``None`` where no
    operation carries a name at all (a trace without the stat)."""
    planes = xplane["planes"]
    window = window_of(planes)
    if window is None:
        return None
    ops, names = _clipped_ops(planes, *window)
    if not any(names.values()):
        return None
    if program is not None:
        runs = sorted((a, b) for n, a, b in trace._clip(
            _line(_first_device(planes), trace.MODULES_LINE), *window)
            if trace.program_name(n) == program)
        starts = [a for a, _ in runs]

        def inside(a):
            i = bisect.bisect_right(starts, a) - 1
            return i >= 0 and a < runs[i][1]

        ops = [o for o in ops if inside(o[1])]
    rows = [(trace.op_label(n), scope_of(names.get(n, "")), s)
            for n, s in trace._self_times(ops).items()]
    return sorted(rows, key=lambda r: -r[2])


def device_scopes(xplane, program=None):
    """{scope: device self seconds} of :func:`scoped_ops`; operations
    without a scope under ``unscoped``."""
    rows = scoped_ops(xplane, program)
    if rows is None:
        return None
    out = {}
    for _, scope, secs in rows:
        out[scope] = out.get(scope, 0.0) + secs
    return out


def program_executions(xplane, program):
    """Executions of ``program`` inside the traced window, first chip."""
    planes = xplane["planes"]
    window = window_of(planes)
    device = _first_device(planes)
    if window is None or device is None:
        return 0
    return sum(1 for n, _, _ in trace._clip(_line(device, trace.MODULES_LINE), *window)
               if trace.program_name(n) == program)


# ---------------------------------------------------------------------------
# the ring laid over the trace
# ---------------------------------------------------------------------------
def on_trace_clock(xplane, ring):
    """The ring's entries as (name, start, end) on the trace's clock."""
    t0 = xplane["start_ns"]
    return [(n, s - t0, s - t0 + d) for n, s, d in ring]


def annotation_offset_ns(xplane, ring):
    """How far the ring lies from the host plane, in nanoseconds (ring
    minus trace): every phase is also a ``TraceAnnotation`` of the same
    name in ``/host:CPU``, and this is the median over those spans of
    (nearest ring entry of that name - the span's start). ``None`` where
    the trace has no such span (the host tracer was off: the mix sets
    ``host_tracer_level`` 0)."""
    starts = {}
    for name, a, _ in on_trace_clock(xplane, ring):
        starts.setdefault(name, []).append(a)
    for v in starts.values():
        v.sort()

    def nearest(name, t):
        v = starts[name]
        i = bisect.bisect_left(v, t)
        return min((abs(x - t), x - t) for x in v[max(i - 1, 0): i + 1])[1]

    offsets = [nearest(e[0], e[1]) for p in xplane["planes"] if p["name"] == trace.HOST_PLANE
               for ln in p["lines"] for e in ln["events"] if e[0] in starts]
    return statistics.median(offsets) if offsets else None


def launch_leads(xplane, ring):
    """For each dispatch phase inside the window, (the phase's start - the
    start of the execution it launched), nanoseconds. The execution a
    phase launched is the longest that starts between 3 ms before the
    phase and its end (beside the step, a dispatch launches casts and rng
    splits of a microsecond). An execution cannot start before its
    launch: a positive lead is the device plane running ahead of the
    host's clock, a negative one the launch's latency."""
    planes = xplane["planes"]
    window, device = window_of(planes), _first_device(planes)
    if window is None or device is None:
        return []
    runs = sorted((a, b) for _, a, b in trace._clip(_line(device, trace.MODULES_LINE), *window))
    starts = [a for a, _ in runs]
    leads = []
    for name, a, b in on_trace_clock(xplane, ring):
        if not DISPATCH.search(name) or a < window[0] or a > window[1]:
            continue
        near = runs[bisect.bisect_left(starts, a - LEAD_SEARCH_NS): bisect.bisect_right(starts, b)]
        if near:
            leads.append(a - max(near, key=lambda r: r[1] - r[0])[0])
    return leads


def clock_check(xplane, ring):
    """Whether the ring may be laid over the device plane, and how far
    the plane leads. The profiler lines the device's clock up with the
    host's once a session, to within a millisecond or so (on the v5e,
    PR 24: 0 to 1.1 ms early, constant within a session), which matters
    where an idle gap of a millisecond lies between two phases. Returns

    - ``check``: ``annotations`` where the host tracer was on (the ring
      against the spans of the same name, refused beyond 1 ms), else
      ``launches``: only the executions vouch for the ring then, and a
      run is refused where no dispatch phase has one within 3 ms before
      it or the executions start over 1 ms late. That catches a clock
      that is milliseconds off, no less: such a run's readings rest on
      ``time.time_ns()`` and the profiler sharing a clock, which the
      runs with annotations show;
    - ``offset_ns``: :func:`annotation_offset_ns`;
    - ``device_lead_ns``: the median of :func:`launch_leads`, at least 0
      (launch latency, tens of microseconds on an idle device, makes it
      an underestimate; a plane that runs LATE cannot be told from a slow
      launch), 0 where no launch is seen; ``lead_spread_ns`` their first
      to third quartile and ``dispatches`` their number: refused where
      they spread over 0.5 ms, since the lead then decides nothing
      between phases of a millisecond;
    - ``ok`` and, where it is not, ``why``.

    ``None`` where there is no ring or the trace has no start time."""
    if not ring or xplane is None or xplane["start_ns"] is None:
        return None
    offset, leads = annotation_offset_ns(xplane, ring), launch_leads(xplane, ring)
    out = {"check": "launches" if offset is None else "annotations", "offset_ns": offset,
           "device_lead_ns": 0.0, "lead_spread_ns": 0.0, "dispatches": len(leads), "ok": True}
    if leads:
        out["device_lead_ns"] = max(statistics.median(leads), 0.0)
    if len(leads) > 1:
        first, _, third = statistics.quantiles(leads, n=4)
        out["lead_spread_ns"] = third - first
    if offset is not None and abs(offset) > CLOCK_LIMIT_NS:
        out["why"] = f"the ring lies {offset:.0f} ns from the annotations of the same name"
    elif offset is None and not leads:
        out["why"] = "no annotation and no launch vouches for the ring's clock"
    elif offset is None and statistics.median(leads) < -CLOCK_LIMIT_NS:
        out["why"] = f"executions start {-statistics.median(leads):.0f} ns after the dispatch phases that launched them"
    elif out["lead_spread_ns"] > LEAD_SPREAD_LIMIT_NS:
        out["why"] = f"the device plane's lead spreads {out['lead_spread_ns']:.0f} ns over {len(leads)} dispatches"
    out["ok"] = "why" not in out
    return out


def innermost_segments(entries):
    """The ring's intervals as disjoint (start, end, name) segments, each
    named after the innermost (shortest) phase that covers it: phases of
    one loop nest or follow one another, and an enclosing phase keeps
    what its children leave uncovered."""
    entries = [(a, b, n) for n, a, b in entries if b > a and n not in NOT_HOST_WORK]
    cuts = sorted({t for a, b, _ in entries for t in (a, b)})
    by_length = sorted(entries, key=lambda e: e[1] - e[0])
    segments = []
    for a, b in zip(cuts, cuts[1:]):
        name = next((n for s, e, n in by_length if s <= a and b <= e), None)
        if name is not None:
            segments.append((a, b, name))
    return segments


def idle_by_phase(xplane, ring, device_lead=None):
    """{phase: idle seconds} over the traced window, first chip: the gaps
    between the device's operations (``lib/trace.reduce``'s: same window,
    gaps under 50 us summed under one name), each divided among the
    innermost program phases that cover it, and what no phase covers put
    down to ``outside_program`` (the benchmark's own batch making and loss
    fetch, the client). A gap is divided and not put down whole to the
    phase at its midpoint: one wait of the device spans a put, a dispatch
    and the caller's fetch. The ring is laid ``device_lead`` earlier, the
    session's (:func:`clock_check`) unless one is given. ``None`` where
    there is no ring or the check refuses the run."""
    check = clock_check(xplane, ring)
    if check is None or (device_lead is None and not check["ok"]):
        return None
    if device_lead is None:
        device_lead = check["device_lead_ns"]
    planes = xplane["planes"]
    window = window_of(planes)
    if window is None:
        return None
    lo, hi = window
    ops, _ = _clipped_ops(planes, lo, hi)
    merged = trace._union([(a, b) for _, a, b in ops])
    segments = innermost_segments(
        [(n, a - device_lead, b - device_lead) for n, a, b in on_trace_clock(xplane, ring)
         if b - device_lead > lo and a - device_lead < hi])
    starts = [seg[0] for seg in segments]
    out = {}

    def add(name, ns):
        out[name] = out.get(name, 0.0) + ns * 1e-9

    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        if b - a < trace.SHORT_GAP_NS:
            add(SHORT_GAPS, b - a)
            continue
        covered = 0.0
        for s, e, name in segments[max(bisect.bisect_right(starts, a) - 1, 0):]:
            if s >= b:
                break
            part = min(e, b) - max(s, a)
            if part > 0:
                add(name, part)
                covered += part
        if b - a > covered:
            add(OUTSIDE, b - a - covered)
    return out


def phase_stats(ring, lo=None, hi=None):
    """{phase: {"count", "median_ms", "p95_ms", "total_s"}} of the ring's
    entries that start inside [lo, hi) (the ring's clock; all of it where
    no bound is given). p95 by nearest rank."""
    by_name = {}
    for name, start, dur in ring or ():
        if (lo is None or start >= lo) and (hi is None or start < hi):
            by_name.setdefault(name, []).append(dur)
    out = {}
    for name, durs in by_name.items():
        durs.sort()
        rank = min(len(durs) - 1, max(0, -(-95 * len(durs) // 100) - 1))
        out[name] = {"count": len(durs), "median_ms": statistics.median(durs) * 1e-6,
                     "p95_ms": durs[rank] * 1e-6, "total_s": sum(durs) * 1e-9}
    return out


def window_on_ring_clock(xplane):
    """(lo, hi) of the traced window on the ring's clock, or ``None``."""
    if xplane is None or xplane["start_ns"] is None:
        return None
    window = window_of(xplane["planes"])
    if window is None:
        return None
    return xplane["start_ns"] + window[0], xplane["start_ns"] + window[1]


def window_seconds(xplane):
    window = window_of(xplane["planes"]) if xplane else None
    return None if window is None else (window[1] - window[0]) * 1e-9


# ---------------------------------------------------------------------------
# what the readers under metrics/ share: each reads this process's run
# ---------------------------------------------------------------------------
def run_phase_ms(names, key="median_ms", whole_run=False):
    """Sum over ``names`` of a phase's ``key`` inside the traced window
    (over the whole ring with ``whole_run``, or where the trace gives no
    window), the phases that the run does not have left out; ``None``
    where it has none of them."""
    xplane, ring = current()
    bounds = None if whole_run else window_on_ring_clock(xplane)
    stats = phase_stats(ring, *(bounds or ()))
    found = [stats[n][key] for n in names if n in stats]
    return sum(found) if found else None


def run_idle_share(names):
    """Device idle seconds under the phases ``names`` as a percentage of
    the traced window; ``None`` where there is no ring or the clock check
    refuses the run. What the check found is said on stderr, once a run."""
    xplane, ring = current()
    check = clock_check(xplane, ring)
    if check is not None and not _run.get("check_said"):
        _run["check_said"] = True
        _say("clock check by {check}: ring - annotation {offset_ns} ns, device plane leads {device_lead_ns:.0f} ns "
             "(spread {lead_spread_ns:.0f} ns over {dispatches} dispatches){verdict}".format(
                 verdict="" if check["ok"] else ": REFUSED, " + check["why"], **check))
    idle = idle_by_phase(xplane, ring)
    if idle is None:
        return None
    return 100.0 * sum(idle.get(n, 0.0) for n in names) / window_seconds(xplane)


def _run_scopes(program=None):
    """:func:`device_scopes` of this run, or ``None`` where no operation
    (of ``program``) carries a scope's name: the program has no scopes
    (the parent of the PR that added them), or the executable was compiled
    before they were added and came out of the persistent cache, whose key
    leaves names out. Such a program reads ``unscoped`` whole, which is a
    perfect reading of a metric where lower is better: it is left out, and
    said on stderr."""
    xplane, _ = current()
    scopes = device_scopes(xplane, program) if xplane else None
    if scopes and set(scopes) <= {UNSCOPED}:
        _say(f"no operation of {program or 'the window'} carries a scope's name (a program without scopes, or an "
             "executable compiled before them and found in the compile cache): its scope metrics are left out")
        return None
    return scopes or None


def run_scope_share(names):
    """Device self time under the scopes ``names`` as a percentage of all
    of it (busy time); ``None`` where the trace shows no scope."""
    scopes = _run_scopes()
    if scopes is None:
        return None
    return 100.0 * sum(scopes.get(n, 0.0) for n in names) / sum(scopes.values())


def run_scope_ms(scope, program):
    """Device self milliseconds under ``scope`` per execution of
    ``program``; ``None`` where the trace shows no scope of the program's
    or the program did not run in the window."""
    scopes = _run_scopes(program)
    runs = program_executions(current()[0], program) if scopes else 0
    if not runs:
        return None
    return 1e3 * scopes.get(scope, 0.0) / runs

"""What the ``decoder_lm`` family's per-layer readers read: the engine's
counters as the kind sampled them, and device time under the scopes of
``models/decoder_lm.py``.

``lib/phases.py`` knows the scopes of ``models/transformer_lm.py`` only,
and the kind hands a reader the deltas of a fixed list of counters; both
files belong to cells that exist. So the family's ``Server.counters()``
leaves every snapshot it returns here (:func:`record`), in the order the
kind took them: window open, [span open, span close,] window close.
"""

import bisect

from lib import phases, trace

#: the scopes of ``models/decoder_lm.py`` beside those of ``lib/phases.py``
SCOPES = phases.SCOPES + ("attn_full", "attn_window", "moe_route", "moe_experts")
#: XLA's grouped-product kernels (``jax.lax.ragged_dot`` on the TPU) are
#: named by the pass that makes them and carry no scope: the expert
#: layer's three products are the only ones in the program
KERNEL_SCOPES = (("ragged-dot", "moe_experts"),)

_snapshots = []


def record(snapshot):
    _snapshots.append(dict(snapshot))
    return snapshot


def counter_delta(name, span=False):
    """``name``'s growth over the window, or over the traced span (the
    middle two of four snapshots); ``None`` where it was not sampled."""
    if span and len(_snapshots) < 4:
        return None
    pair = _snapshots[1:3] if span else _snapshots[:1] + _snapshots[-1:]
    if len(_snapshots) < 2 or any(name not in s for s in pair):
        return None
    return pair[1][name] - pair[0][name]


def scope_of(op_name):
    for prefix, scope in KERNEL_SCOPES:
        if op_name.startswith(prefix):
            return scope
    name = (op_name.rpartition(":")[0] or op_name).split(";")[0]
    for part in reversed(name.split("/")):
        while True:
            m = phases._WRAPPER.match(part)
            if not m:
                break
            part = m.group(1)
        if part in SCOPES:
            return part
    return phases.UNSCOPED


def scope_seconds(program):
    """({scope: device self seconds inside ``program``'s executions of the
    traced window}, executions); ``None`` where there is no trace or no
    operation carries a scope of ``SCOPES``."""
    xplane, _ = phases.current()
    if not xplane:
        return None
    planes = xplane["planes"]
    window = phases.window_of(planes)
    if window is None:
        return None
    ops, names = phases._clipped_ops(planes, *window)
    runs = sorted((a, b) for n, a, b in trace._clip(
        phases._line(phases._first_device(planes), trace.MODULES_LINE), *window)
        if trace.program_name(n) == program)
    starts = [a for a, _ in runs]

    def inside(a):
        i = bisect.bisect_right(starts, a) - 1
        return i >= 0 and a < runs[i][1]

    out = {}
    for n, secs in trace._self_times([o for o in ops if inside(o[1])]).items():
        scope = scope_of(names.get(n, ""))
        out[scope] = out.get(scope, 0.0) + secs
    if not runs or set(out) <= {phases.UNSCOPED}:
        return None
    return out, len(runs)


def scope_ms(scopes, program):
    """Device self milliseconds under ``scopes`` per execution of
    ``program``; ``None`` where there is nothing to read."""
    read = scope_seconds(program) if program else None
    if read is None:
        return None
    by_scope, runs = read
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / runs

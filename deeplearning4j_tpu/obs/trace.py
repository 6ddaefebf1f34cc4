"""Host phases, trace spans and the jit retrace monitor.

Three pieces:

- **Phases**: :func:`phase` names a stretch of host work at a phase
  boundary of a hot path (``train.put_batch``, ``gen.decode.fetch``).
  One call site feeds two sinks from one pair of clock reads: a
  ``jax.profiler.TraceAnnotation`` (xprof and the benchmark's gap
  attribution see it where the host tracer is on) and one process-wide
  bounded ring of ``(name, start_ns, duration_ns, cause)`` plus the per-name
  counters ``host_phase_total`` / ``host_phase_seconds_total`` in the
  default registry. ``start_ns`` is ``time.time_ns()``: the profiler
  stamps its planes from the same wall clock (a plane's times count from
  the session's ``profile_start_time``), so a ring entry can be laid over
  the device plane of the same process with the host tracer off. Phases
  are always counted; there is no switch. :func:`observe` enters an
  interval measured elsewhere (queue wait spans two threads);
  :func:`phases` hands the ring out. Every entry also keeps its **cause**:
  the integer its thread last gave :func:`set_cause` (a decode step's
  id, a training iteration), so that the phases of one unit of work are
  joined by what caused them and not by their order in the ring;
  :func:`caused_phases` hands those four-wide entries out.

- **Spans**: :func:`span` and :func:`step_span` are the bare
  ``jax.profiler`` annotations (``TraceAnnotation``,
  ``StepTraceAnnotation``) for call sites that are not a phase of a step:
  serving dispatches, checkpoint writes, one box per optimizer step.

- **Retrace monitor**: generalizes serving/engine.py's trace-time
  compile-count hook into a registry-backed per-function jit cache-miss
  counter. :func:`count_retraces` wraps a function ABOUT TO BE jitted
  with a Python side effect that runs exactly once per trace (= once per
  distinct XLA program), bumping ``jit_retraces_total{fn=...}`` in the
  metrics registry. A production mesh that recompiles in steady state
  stops being a mystery slowdown and becomes a scrapeable counter; the
  tests arm :class:`RetraceMonitor` around a fit or a serving storm and
  fail on any unexpected delta.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from deeplearning4j_tpu.obs.metrics import MetricsRegistry, default_registry

RETRACE_COUNTER = "jit_retraces_total"
_RETRACE_HELP = ("distinct XLA programs traced per jitted function; "
                 "steady-state growth means shape/dtype churn is "
                 "defeating the jit cache")


def count_retraces(name: str, fn: Callable,
                   registry: Optional[MetricsRegistry] = None) -> Callable:
    """Wrap ``fn`` (about to be ``jax.jit``-ed) so each TRACE bumps
    ``jit_retraces_total{fn=name}``. The bump is a host side effect that
    only runs while jax traces the function — never in the compiled
    program — so steady-state dispatches cost nothing."""
    import functools

    counter = (registry or default_registry()).counter(
        RETRACE_COUNTER, _RETRACE_HELP, labels={"fn": name})

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        counter.inc()
        # same trace-time-only side effect into the flight recorder: a
        # steady-state recompile shows up in the black box ordered
        # against the steps it stalled
        from deeplearning4j_tpu.obs import flight as _flight

        _flight.record("retrace", fn=name)
        return fn(*args, **kwargs)

    return traced


def retrace_counts(registry: Optional[MetricsRegistry] = None
                   ) -> Dict[str, float]:
    """fn-label → trace count over everything instrumented so far."""
    reg = registry or default_registry()
    out: Dict[str, float] = {}
    snap = reg.snapshot().get(RETRACE_COUNTER)
    if isinstance(snap, dict):
        for label, v in snap.items():
            out[label.split("=", 1)[1]] = v
    elif snap is not None:
        out[""] = snap
    return out


class RetraceMonitor:
    """Arm around a region that must not compile: records the per-function
    retrace counters at entry; :meth:`delta` is what compiled since.

        with RetraceMonitor() as mon:
            net.fit(it, epochs=1)      # warm epoch: compiles expected
            mon.rebaseline()
            net.fit(it, epochs=1)      # steady state
        assert mon.total() == 0, mon.delta()
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry or default_registry()
        self._base: Dict[str, float] = {}

    def __enter__(self) -> "RetraceMonitor":
        self.rebaseline()
        return self

    def __exit__(self, *exc) -> None:
        pass

    def rebaseline(self) -> None:
        self._base = retrace_counts(self.registry)

    def delta(self) -> Dict[str, float]:
        """fn → retraces since the last (re)baseline, zero entries
        omitted."""
        now = retrace_counts(self.registry)
        return {k: v - self._base.get(k, 0.0)
                for k, v in now.items() if v - self._base.get(k, 0.0) > 0}

    def total(self) -> float:
        return sum(self.delta().values())


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------
def step_span(name: str, step: int):
    """``jax.profiler.StepTraceAnnotation`` around one training dispatch
    (xprof groups device work per step)."""
    return StepTraceAnnotation(name, step_num=int(step))


#: ``jax.profiler.TraceAnnotation`` around a host-side region that is no
#: phase of a step (serving dispatch, checkpoint write)
span = TraceAnnotation


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
PHASE_COUNTER = "host_phase_total"
PHASE_SECONDS = "host_phase_seconds_total"
RING_SIZE = 65536

#: (name, start_ns on ``time.time_ns()``, duration_ns, cause), oldest first
_ring: deque = deque(maxlen=RING_SIZE)
_sites: Dict[str, "Phase"] = {}
_sites_lock = threading.Lock()


class _Cause(threading.local):
    """What the thread's phases are entered for; ``None`` until set."""

    value: Optional[int] = None


_cause = _Cause()


def set_cause(cause: Optional[int]) -> None:
    """Name the unit of work (a decode step's id, a training iteration)
    that this thread's phases belong to from here on."""
    _cause.value = cause


class _Open(threading.local):
    """The phases a thread has entered and not left, innermost last."""

    def __init__(self):
        self.stack: List[Tuple[TraceAnnotation, int]] = []


class Phase:
    """One named phase; ``with`` it around the work. Re-entrant and
    shared between threads: what is open lives on a per-thread stack.
    Get it from :func:`phase`, once, where the name is known."""

    __slots__ = ("name", "_count", "_seconds", "_open")

    def __init__(self, name: str):
        self.name = name
        reg = default_registry()
        self._count = reg.counter(
            PHASE_COUNTER, "host phases entered, by name",
            labels={"phase": name})
        self._seconds = reg.counter(
            PHASE_SECONDS, "host seconds spent inside a phase, by name",
            labels={"phase": name})
        self._open = _Open()

    def __enter__(self) -> "Phase":
        annotation = TraceAnnotation(self.name)
        annotation.__enter__()
        self._open.stack.append((annotation, time.time_ns()))
        return self

    def __exit__(self, *exc) -> None:
        end = time.time_ns()
        annotation, start = self._open.stack.pop()
        annotation.__exit__(*exc)
        self.record(start, end - start)

    def cancel(self) -> None:
        """Leave without an entry: what was entered turned out to be no
        phase (the ``next()`` that found its iterator exhausted)."""
        annotation, _ = self._open.stack.pop()
        annotation.__exit__(None, None, None)

    def record(self, start_ns: int, duration_ns: int,
               cause: Optional[int] = None) -> None:
        """Enter one interval; ``cause`` where it is not the thread's."""
        _ring.append((self.name, start_ns, duration_ns,
                      _cause.value if cause is None else cause))
        self._count.inc()
        self._seconds.inc(max(duration_ns, 0) * 1e-9)


def phase(name: str) -> Phase:
    """The :class:`Phase` of ``name`` (one object a name, made on first
    use). Hot paths resolve it once, at import or in ``__init__``."""
    site = _sites.get(name)
    if site is None:
        with _sites_lock:
            site = _sites.setdefault(name, Phase(name))
    return site


def observe(name: str, start_ns: int, duration_ns: int,
            cause: Optional[int] = None) -> None:
    """Enter an interval measured elsewhere into the ring and counters
    of ``name``; ``start_ns`` is on ``time.time_ns()``. ``cause`` where
    the interval belongs to another unit of work than the thread's."""
    phase(name).record(int(start_ns), int(duration_ns), cause)


def caused_phases(since_ns: Optional[int] = None
                  ) -> List[Tuple[str, int, int, Optional[int]]]:
    """The ring, oldest first, as ``(name, start_ns, duration_ns,
    cause)``: entries are appended when a phase ENDS, so an enclosing
    phase follows the phases inside it. ``since_ns`` keeps the entries
    that start at or after it."""
    entries = list(_ring)
    if since_ns is None:
        return entries
    return [e for e in entries if e[1] >= since_ns]


def phases(since_ns: Optional[int] = None) -> List[Tuple[str, int, int]]:
    """:func:`caused_phases` without the cause: ``(name, start_ns,
    duration_ns)``, in the same order."""
    return [e[:3] for e in caused_phases(since_ns)]


def each_next(site: Phase, iterable):
    """Yield the items of ``iterable`` with every ``next()`` that returns
    one under ``site``: what an input pipeline costs the loop that
    consumes it, one phase an item."""
    it = iter(iterable)
    while True:
        site.__enter__()
        try:
            item = next(it)
        except BaseException as stop:
            site.cancel()  # exhausted or failed: no item, no phase
            if isinstance(stop, StopIteration):
                return
            raise
        site.__exit__(None, None, None)
        yield item

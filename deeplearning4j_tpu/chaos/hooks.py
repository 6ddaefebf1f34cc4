"""Chaos seam hook points: the one mechanism every injectable fault
rides through.

Production code marks its injectable seams with a single call::

    from deeplearning4j_tpu.chaos import hooks as chaos_hooks
    chaos_hooks.fire("generate.decode_dispatch", role="canary")

With nothing armed (every production process, always) ``fire`` is one
falsy module-flag check — no lock, no allocation. A chaos drill arms
:class:`FaultSpec` entries process-wide (usually through
``chaos.plan.ChaosPlan``, the declarative JSON layer) and the matching
seam then raises a typed error, injects an OSError with a real errno
(ENOSPC/EIO — the filesystem layer wraps those into ``StorageError``),
sleeps (slow/hung-dispatch drills: the delay happens exactly where a
wedged device call would), or hands the spec back for modes only the
seam itself can interpret (``torn`` appends, value overrides).

Determinism: every spec fires on an explicit call count (``at_call``,
1-based over MATCHING calls) and/or a seeded probability — the plan's
seed flows in, so a drill replays identically. Each injection is
appended to an in-process log AND recorded as a ``chaos_inject`` flight
event, so a drill's postmortem dump shows the fault next to the
recovery it triggered.

This module is stdlib-only on purpose: the serving/training hot paths
import it at module top without dragging in anything heavy, and the
chaos package's heavier layers (plans, drills) import the production
stack lazily instead.
"""

from __future__ import annotations

import contextlib
import errno as _errno
import random
import threading
import time
from typing import Dict, List, Optional

SCHEMA = ("point", "mode", "match", "at_call", "prob", "times", "delay_s",
          "value", "message")


class InjectedFaultError(RuntimeError):
    """The generic injected runtime fault (mode ``error``) — stands in
    for 'an arbitrary device/runtime failure at this seam'. Drills
    assert the system converts or contains it; it is part of the typed
    taxonomy the invariant checker accepts precisely because production
    seams are allowed to surface backend errors as-is."""


#: modes that raise at the fire site; everything else returns the spec
#: for the seam to interpret (``torn``, ``value``, ``callback``)
_RAISING_MODES = ("error", "enospc", "eio", "transient_compile")
_MODES = _RAISING_MODES + ("delay", "torn", "value", "callback")


class FaultSpec:
    """One armed fault: where (``point`` + ``match``), when
    (``at_call``/``prob``/``times``), and what (``mode``).

    - ``point``: seam hook-point name (see ``chaos.seams.list_seams``).
    - ``match``: ctx filters — every key must equal the ``fire`` call's
      ctx value; ``path_substr`` substring-matches ``ctx["path"]``.
    - ``at_call``: fire on the Nth MATCHING call (1-based). None = every
      matching call (subject to ``prob``/``times``).
    - ``prob``: fire with this probability (seeded rng — deterministic
      per plan seed). None = always.
    - ``times``: total injection budget (default 1; None = unlimited).
    - ``mode``: ``error`` (raise :class:`InjectedFaultError`),
      ``enospc``/``eio`` (raise OSError with that errno), ``delay``
      (sleep ``delay_s`` — the slow/hung-dispatch fault),
      ``transient_compile`` (raise with the compile-service-crash
      marker so ``probe_with_retry`` retries), ``torn``/``value``/``callback``
      (returned to the seam: torn journal append, score override,
      arbitrary test callback via ``value``).
    """

    def __init__(self, point: str, mode: str = "error",
                 match: Optional[dict] = None, at_call: Optional[int] = None,
                 prob: Optional[float] = None, times: Optional[int] = 1,
                 delay_s: float = 0.0, value=None,
                 message: Optional[str] = None,
                 rng: Optional[random.Random] = None):
        if mode not in _MODES:
            raise ValueError(f"unknown fault mode {mode!r} (known: "
                             f"{sorted(_MODES)})")
        self.point = str(point)
        self.mode = mode
        self.match = dict(match or {})
        self.at_call = None if at_call is None else int(at_call)
        self.prob = None if prob is None else float(prob)
        self.times = None if times is None else int(times)
        self.delay_s = float(delay_s)
        self.value = value
        self.message = message
        self._rng = rng if rng is not None else random.Random(0)
        self.calls = 0   # matching calls seen
        self.fires = 0   # injections performed

    # -- selection ----------------------------------------------------------
    def _matches(self, ctx: dict) -> bool:
        for k, want in self.match.items():
            if k == "path_substr":
                if str(want) not in str(ctx.get("path", "")):
                    return False
            elif ctx.get(k) != want:
                return False
        return True

    def _should_fire(self) -> bool:
        # caller holds the module lock; self.calls was just incremented
        if self.times is not None and self.fires >= self.times:
            return False
        if self.at_call is not None and self.calls != self.at_call:
            return False
        if self.prob is not None and self._rng.random() >= self.prob:
            return False
        return True

    # -- the injection ------------------------------------------------------
    def _act(self):
        msg = self.message or f"chaos-injected {self.mode} at {self.point}"
        if self.mode == "error":
            raise InjectedFaultError(msg)
        if self.mode == "enospc":
            raise OSError(_errno.ENOSPC, msg)
        if self.mode == "eio":
            raise OSError(_errno.EIO, msg)
        if self.mode == "transient_compile":
            # carries the crash signature kernel_compat retries on
            raise RuntimeError(f"{msg} (injected tpu_compile_helper "
                               "subprocess exit code 1)")
        if self.mode == "delay":
            time.sleep(self.delay_s)
            return None
        return self  # torn / value / callback: the seam interprets

    def describe(self) -> dict:
        return {"point": self.point, "mode": self.mode, "match": self.match,
                "at_call": self.at_call, "prob": self.prob,
                "times": self.times, "delay_s": self.delay_s,
                "calls": self.calls, "fires": self.fires}


# --------------------------------------------------------------------------
# process-wide armed state
# --------------------------------------------------------------------------
_lock = threading.RLock()
_armed: Dict[str, List[FaultSpec]] = {}
_fire_log: List[dict] = []
#: lock-free hot-path gate: False ⇒ fire() is a single attribute read
_any_armed = False


def arm(specs) -> None:
    """Arm spec(s) process-wide. Idempotent per object."""
    global _any_armed
    if isinstance(specs, FaultSpec):
        specs = [specs]
    with _lock:
        for s in specs:
            lst = _armed.setdefault(s.point, [])
            if s not in lst:
                lst.append(s)
        _any_armed = bool(_armed)


def disarm(specs=None) -> None:
    """Disarm spec(s); None disarms everything (drill teardown)."""
    global _any_armed
    with _lock:
        if specs is None:
            _armed.clear()
        else:
            if isinstance(specs, FaultSpec):
                specs = [specs]
            for s in specs:
                lst = _armed.get(s.point)
                if lst and s in lst:
                    lst.remove(s)
                    if not lst:
                        _armed.pop(s.point, None)
        _any_armed = bool(_armed)


@contextlib.contextmanager
def armed(specs):
    """Arm for the block, disarm on exit (even on error)."""
    if isinstance(specs, FaultSpec):
        specs = [specs]
    arm(specs)
    try:
        yield specs
    finally:
        disarm(specs)


def armed_points() -> List[str]:
    with _lock:
        return sorted(_armed)


def fire_log(clear: bool = False) -> List[dict]:
    """Injections performed since the last clear — drill forensics."""
    with _lock:
        out = list(_fire_log)
        if clear:
            _fire_log.clear()
        return out


def reset() -> None:
    """Disarm everything and clear the log (test isolation)."""
    with _lock:
        disarm(None)
        _fire_log.clear()


def fire(point: str, **ctx) -> Optional[FaultSpec]:
    """The seam call. No-op (None) unless a matching armed spec fires;
    raising modes raise here, ``delay`` sleeps here, and the remaining
    modes return the spec for the seam to interpret."""
    if not _any_armed:
        return None
    with _lock:
        specs = _armed.get(point)
        if not specs:
            return None
        chosen = None
        for s in specs:
            if not s._matches(ctx):
                continue
            # EVERY matching spec counts the call, even after another
            # spec on this point has fired — at_call determinism for a
            # plan with two faults on one seam must not drift by the
            # number of earlier-spec fires
            s.calls += 1
            if chosen is None and s._should_fire():
                s.fires += 1
                chosen = s
        if chosen is None:
            return None
        _fire_log.append({"point": point, "mode": chosen.mode,
                          "ts": time.time(),
                          "ctx": {k: v for k, v in ctx.items()
                                  if isinstance(v, (str, int, float, bool))
                                  or v is None}})
    # record + act OUTSIDE the lock: flight observers may re-enter fire,
    # and a delay-mode sleep must never serialize unrelated seams
    try:
        from deeplearning4j_tpu.obs import flight as _flight

        _flight.record("chaos_inject", point=point, mode=chosen.mode,
                       fires=chosen.fires)
    except Exception:  # noqa: BLE001 — forensics must not mask the drill
        pass
    return chosen._act()

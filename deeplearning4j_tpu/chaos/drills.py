"""The resilience drill matrix: seam × workload, invariant-checked.

Every drill arms a declarative :class:`~.plan.ChaosPlan` (never an
ad-hoc monkeypatch), runs a real workload — fit, elastic fit, tune
study, registry canary, generation storm — through the injected fault,
and then asserts the cross-cutting contract from :mod:`~.invariants`:
typed errors only, bit-parity where promised, ordered forensics, no
torn artifacts, bounded recovery. Paired drills compose faults no
single-feature test ever did (checkpoint corruption DURING host-dropout
recovery; disk-full mid-publish while a canary window is open; a decode
watchdog trip inside an open canary window).

Run it: ``python -m deeplearning4j_tpu.cli chaos`` (or ``--fast`` for
the single-fault tier-1 subset), ``bench.py chaos`` for the
BENCH_chaos.json scorecard, or ``run_drill(name)`` from tests.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.chaos import hooks, invariants
from deeplearning4j_tpu.chaos.fslayer import StorageError
from deeplearning4j_tpu.chaos.plan import ChaosPlan
from deeplearning4j_tpu.obs import lockwitness

N_IN, N_HID, N_OUT = 4, 6, 3


# --------------------------------------------------------------------------
# workload builders (tiny on purpose: the drills assert contracts, not
# throughput — bench.py owns performance)
# --------------------------------------------------------------------------
def _net(seed: int = 3, policy=None, hidden: int = N_HID):
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.updaters import Adam

    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
    if policy is not None:
        b = b.fault_policy(policy)
    conf = (b.list()
            .layer(DenseLayer(n_out=hidden, activation="tanh"))
            .layer(OutputLayer(n_out=N_OUT, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN))
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(n: int = 4, per: int = 8, seed: int = 0):
    from deeplearning4j_tpu.data import DataSet

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.standard_normal((per, N_IN)).astype(np.float32)
        y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, per)]
        out.append(DataSet(x, y))
    return out


def _lstm(seed: int = 5, classes: int = 12, units: int = 8):
    from deeplearning4j_tpu.models.textgen_lstm import TextGenerationLSTM

    return TextGenerationLSTM(num_classes=classes, units=units,
                              max_length=16, seed=seed).init()


def _fit(model, batches, epochs: int = 1):
    from deeplearning4j_tpu.data import ExistingDataSetIterator

    model.fit(ExistingDataSetIterator(batches), epochs=epochs)
    return model


# --------------------------------------------------------------------------
# drill harness
# --------------------------------------------------------------------------
class DrillContext:
    """Per-drill scratch state: an isolated artifact directory, the
    invariant report, captured caller-visible errors, a flight cursor
    so event-order checks see only this drill's events, and a
    DETECTION evaluator — the default SLO rule pack (obs/slo.py) over
    a fresh registry, watching the flight ring from drill start on an
    injected clock. The harness baseline-ticks it before the drill and
    ticks it twice after (the ≤2-tick detection contract), so a drill
    can assert the injected fault tripped exactly the alert that
    claims to cover it (``expected_alerts``)."""

    #: injected-clock tick spacing the harness uses (fake seconds —
    #: large enough to clear every pack rule's for_s hold)
    ALERT_TICK_S = 60.0

    def __init__(self, name: str):
        from deeplearning4j_tpu.obs import flight, slo
        from deeplearning4j_tpu.obs.alerts import AlertEvaluator

        self.name = name
        self.dir = tempfile.mkdtemp(prefix=f"chaos_{name}_")
        self.report = invariants.InvariantReport()
        self.errors: List[BaseException] = []
        self.recovery_s: Optional[float] = None
        self._flight = flight.default_flight_recorder()
        self._seq0 = self._flight.recorded_total
        self._alert_now = 0.0
        self.alerts = AlertEvaluator(slo.default_rules(),
                                     clock=lambda: self._alert_now,
                                     min_tick_interval=0.0)
        self.alerts.watch_flight(self._flight)
        self.alerts.tick()  # baseline sample: pre-fault counters

    def tick_alerts(self, n: int = 1) -> List[str]:
        """Advance the injected clock ``n`` ticks and evaluate;
        returns the rules that have fired so far."""
        for _ in range(int(n)):
            self._alert_now += self.ALERT_TICK_S
            self.alerts.tick()
        return self.alerts.fired_names()

    def path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def capture(self, fn: Callable, *args, **kwargs):
        """Run ``fn``; a raised exception is captured as a
        caller-visible error (for the typed-errors invariant) instead
        of failing the drill harness. Returns ``(result, error)``."""
        try:
            return fn(*args, **kwargs), None
        except BaseException as e:  # noqa: BLE001 — the drill judges it
            self.errors.append(e)
            return None, e

    def events(self, kinds: Optional[Sequence[str]] = None) -> List[dict]:
        evs = [e for e in self._flight.events()
               if e["seq"] >= self._seq0]
        if kinds is not None:
            evs = [e for e in evs if e["kind"] in kinds]
        return evs

    def expect_error(self, error: Optional[BaseException], *types,
                     name: str = "expected_typed_error") -> bool:
        ok = error is not None and isinstance(error, types)
        return self.report.add(
            name, ok,
            f"got {type(error).__name__ if error else None}: {error}"
            if not ok else type(error).__name__)


class Drill:
    def __init__(self, name: str, fn: Callable, workload: str,
                 seams: Sequence[str], paired: bool, fast: bool,
                 deadline_s: float, description: str,
                 expected_alerts: Sequence[str] = ()):
        self.name = name
        self.fn = fn
        self.workload = workload
        self.seams = list(seams)
        self.paired = paired
        self.fast = fast
        self.deadline_s = float(deadline_s)
        self.description = description
        #: alert names (obs/events.py ALERTS) that MUST fire in the
        #: drill's detection evaluator within 2 post-drill ticks — the
        #: detection half of the invariant contract
        self.expected_alerts = list(expected_alerts)

    def describe(self) -> dict:
        return {"drill": self.name, "workload": self.workload,
                "seams": self.seams, "paired": self.paired,
                "fast": self.fast, "description": self.description,
                "expected_alerts": list(self.expected_alerts)}


DRILLS: "OrderedDict[str, Drill]" = OrderedDict()


def drill(workload: str, seams: Sequence[str], paired: bool = False,
          fast: bool = True, deadline_s: float = 120.0,
          expected_alerts: Sequence[str] = ()):
    def wrap(fn):
        name = fn.__name__.removeprefix("drill_")
        DRILLS[name] = Drill(name, fn, workload, seams, paired, fast,
                             deadline_s,
                             (fn.__doc__ or "").strip().split("\n")[0],
                             expected_alerts=expected_alerts)
        return fn

    return wrap


class DrillResult:
    def __init__(self, name: str, ok: bool, checks: List[dict],
                 wall_s: float, recovery_s: Optional[float] = None,
                 error: Optional[str] = None,
                 skipped: Optional[str] = None,
                 alerts_fired: Optional[List[str]] = None):
        self.name = name
        self.ok = ok
        self.checks = checks
        self.wall_s = wall_s
        self.recovery_s = recovery_s
        self.error = error
        self.skipped = skipped
        self.alerts_fired = list(alerts_fired or [])

    def to_dict(self) -> dict:
        d = DRILLS.get(self.name)
        out = {"drill": self.name,
               "verdict": ("skipped" if self.skipped
                           else "green" if self.ok else "RED"),
               "ok": self.ok, "wall_s": round(self.wall_s, 3),
               "checks": self.checks,
               "alerts_fired": list(self.alerts_fired)}
        if d is not None:
            out.update(workload=d.workload, seams=d.seams,
                       paired=d.paired,
                       expected_alerts=list(d.expected_alerts))
        if self.recovery_s is not None:
            out["recovery_s"] = round(self.recovery_s, 3)
        if self.error:
            out["error"] = self.error
        if self.skipped:
            out["skipped"] = self.skipped
        return out


class DrillSkipped(Exception):
    """Raised by a drill whose environment prerequisite is missing
    (e.g. a multi-device mesh on a 1-device box)."""


def run_drill(name: str) -> DrillResult:
    d = DRILLS.get(name)
    if d is None:
        raise ValueError(f"unknown drill {name!r} (known: "
                         f"{sorted(DRILLS)})")
    ctx = DrillContext(name)
    t0 = time.monotonic()
    error = skipped = None
    # lock witness rides every drill in observe mode: an
    # acquisition-order cycle anywhere under fault pressure is recorded
    # (+ lock_cycle flight event) and fails the drill's invariants
    # below, without turning a latent inversion into a mid-drill crash
    # of an unrelated code path
    cycles0 = len(lockwitness.cycles())
    try:
        with lockwitness.armed(strict=False):
            d.fn(ctx)
    except DrillSkipped as e:
        skipped = str(e)
    except BaseException as e:  # noqa: BLE001 — a crashed drill is RED
        error = f"{type(e).__name__}: {e}"
    finally:
        # belt and braces: a drill that died mid-arm must not leak its
        # faults into the next drill (plans disarm themselves, but the
        # harness guarantees it)
        hooks.disarm(None)
        shutil.rmtree(ctx.dir, ignore_errors=True)
    wall = time.monotonic() - t0
    alerts_fired: List[str] = []
    if skipped is None and error is None:
        invariants.check_deadline(
            ctx.report, ctx.recovery_s if ctx.recovery_s is not None
            else wall, d.deadline_s)
        new_cycles = lockwitness.cycles()[cycles0:]
        ctx.report.add(
            "no_lock_cycles", not new_cycles,
            "; ".join("->".join(c["cycle"]) for c in new_cycles[:3]))
        # detection: two post-drill evaluator ticks (the ≤2-tick
        # contract) — every alert the drill claims covers its fault
        # must have fired
        alerts_fired = ctx.tick_alerts(2)
        if d.expected_alerts:
            invariants.check_expected_alerts(ctx.report, alerts_fired,
                                             d.expected_alerts)
    ctx.alerts.unwatch()
    ok = skipped is None and error is None and ctx.report.ok
    return DrillResult(name, ok, ctx.report.to_dict(), wall,
                       recovery_s=ctx.recovery_s, error=error,
                       skipped=skipped, alerts_fired=alerts_fired)


def run_matrix(fast_only: bool = False,
               names: Optional[Sequence[str]] = None,
               verbose: bool = False) -> dict:
    """Run the drill matrix; returns the scorecard dict (the
    BENCH_chaos.json body). Explicitly named drills always run —
    ``fast_only`` filters only the default full-matrix selection (an
    operator asking for a specific paired drill must not get a vacuous
    '0 green, exit 0'); unknown names fail typed up front."""
    if names:
        unknown = [n for n in names if n not in DRILLS]
        if unknown:
            raise ValueError(f"unknown drill(s) {unknown} "
                             f"(known: {sorted(DRILLS)})")
        chosen = list(names)
    else:
        chosen = [n for n in DRILLS if not fast_only or DRILLS[n].fast]
    # fresh witness state per matrix: the per-inversion-pair dedupe
    # would otherwise suppress a STILL-LIVE inversion already recorded
    # by an earlier armed run in this process, and the scorecard's
    # delta would read a false 0
    lockwitness.reset()
    matrix_cycles0 = len(lockwitness.cycles())
    results = []
    for n in chosen:
        if verbose:
            print(f"chaos drill {n} ...", flush=True)
        r = run_drill(n)
        if verbose:
            mark = ("SKIP" if r.skipped else
                    "green" if r.ok else "RED")
            print(f"chaos drill {n}: {mark} ({r.wall_s:.1f}s)",
                  flush=True)
            if not r.ok and not r.skipped:
                for c in r.checks:
                    if not c["ok"]:
                        print(f"  FAILED {c['name']}: {c['detail']}",
                              flush=True)
                if r.error:
                    print(f"  ERROR {r.error}", flush=True)
        results.append(r)
    n_green = sum(1 for r in results if r.ok)
    n_skipped = sum(1 for r in results if r.skipped)
    silent = [c for r in results if not r.skipped
              for c in r.checks if not c["ok"]]
    return {
        "drills": [r.to_dict() for r in results],
        "n_drills": len(results),
        "n_green": n_green,
        "n_red": len(results) - n_green - n_skipped,
        "n_skipped": n_skipped,
        "n_paired": sum(1 for r in results
                        if not r.skipped and DRILLS[r.name].paired),
        #: drills that declared expected_alerts, ran, and whose every
        #: expected alert FIRED — detection verified, not just recovery
        "alerts_verified": sum(
            1 for r in results
            if not r.skipped and DRILLS[r.name].expected_alerts
            and set(DRILLS[r.name].expected_alerts)
            <= set(r.alerts_fired)),
        "silent_corruption_findings": silent,
        #: acquisition-order cycles the lock witness saw across the
        #: whole matrix (every drill runs under it); the bench gate and
        #: the ISSUE 14 acceptance require 0
        "lock_cycles": len(lockwitness.cycles()) - matrix_cycles0,
        "ok": all(r.ok or r.skipped for r in results),
    }


def _need_devices(n: int) -> list:
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise DrillSkipped(f"needs >= {n} devices, have {len(devs)} "
                           "(run under the 8-device CPU mesh)")
    return devs


# ==========================================================================
# single-fault drills
# ==========================================================================
@drill("fit", ["grad_nan"], expected_alerts=["nan_step_storm"])
def drill_fit_nan_skip_parity(ctx: DrillContext):
    """NaN-gradient storm mid-fit: skipped steps leave params + Adam
    slots equal to the same fit with those batches removed — the
    fault-free-oracle promise (see ``invariants.check_params_match``
    for what "equal" means between two compiled programs)."""
    batches = _batches(4)
    plan = ChaosPlan([{"seam": "grad_nan", "at_iterations": [1]}],
                     name=ctx.name)
    # arm the tripwire far above the storm: its host check is what
    # records nan_skip forensics (and feeds the nan_step_storm alert)
    # without ever tripping — the skip math itself is unchanged, so
    # the parity oracle below still holds
    with plan.armed():
        a = _fit(_net(policy=_policy(max_bad=100)), list(batches))
    oracle = _fit(_net(policy=_policy(max_bad=100)),
                  [batches[0], batches[2], batches[3]])
    invariants.check_params_match(ctx.report, a, oracle)
    ctx.report.add("bad_step_counted", a.bad_step_count == 1,
                   f"bad_step_count={a.bad_step_count}")
    invariants.check_typed_errors(ctx.report, ctx.errors)


def _policy(max_bad: Optional[int] = None):
    from deeplearning4j_tpu.train.faults import FaultPolicy

    return FaultPolicy(skip_nonfinite=True,
                       max_consecutive_bad_steps=max_bad)


@drill("fit", ["grad_nan"],
       expected_alerts=["nan_step_storm", "training_diverged"])
def drill_fit_divergence_trip(ctx: DrillContext):
    """A sustained NaN storm trips the divergence tripwire: typed
    TrainingDivergedError, ordered nan_skip → divergence_trip forensics,
    and a black-box dump on disk."""
    from deeplearning4j_tpu.obs.flight import FlightRecorderListener
    from deeplearning4j_tpu.train.faults import TrainingDivergedError

    batches = _batches(6)
    model = _net(policy=_policy(max_bad=2))
    model.add_listeners(FlightRecorderListener(directory=ctx.path("box"),
                                               dump_every_s=None))
    plan = ChaosPlan(
        [{"seam": "grad_nan", "at_iterations": [0, 1, 2, 3, 4, 5]}],
        name=ctx.name)
    with plan.armed():
        _res, err = ctx.capture(_fit, model, batches)
    ctx.expect_error(err, TrainingDivergedError)
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_event_order(
        ctx.report, ctx.events(),
        ["nan_skip", "divergence_trip", "fit_exception"])
    dumps = [n for n in os.listdir(ctx.path("box"))
             if n.startswith("flight_recorder_")] \
        if os.path.isdir(ctx.path("box")) else []
    ctx.report.add("blackbox_dumped", bool(dumps), str(dumps))


@drill("fit", ["fs.replace"], expected_alerts=["storage_errors"])
def drill_checkpoint_enospc(ctx: DrillContext):
    """Disk full at the atomic checkpoint publish mid-fit: typed
    StorageError, no staging litter, the previous checkpoint still
    loads."""
    from deeplearning4j_tpu.train.listeners import CheckpointListener

    model = _net()
    ck = ctx.path("ckpts")
    model.add_listeners(CheckpointListener(ck, save_every_n_epochs=1,
                                           keep_mode="last", keep_last=3))
    batches = _batches(2)
    _fit(model, batches)  # epoch 1 checkpoint lands clean
    plan = ChaosPlan([{"seam": "fs.replace", "mode": "enospc",
                       "match": {"surface": "checkpoint"}}],
                     name=ctx.name)
    with plan.armed():
        _res, err = ctx.capture(_fit, model, batches)
    ctx.expect_error(err, StorageError)
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_no_tmp_litter(ctx.report, ck)
    invariants.check_checkpoint_loadable(ctx.report, ck)


@drill("fit", ["fs.fsync"], expected_alerts=["storage_errors"])
def drill_checkpoint_fsync_fail(ctx: DrillContext):
    """A failed fsync of the staged checkpoint zip (EIO): typed
    StorageError, clean staging, previous checkpoint intact."""
    from deeplearning4j_tpu.train import faults

    model = _net()
    ck = ctx.path("ckpts")
    faults.save_checkpoint(model, ck, keep_last=3)
    plan = ChaosPlan([{"seam": "fs.fsync", "mode": "eio",
                       "match": {"surface": "checkpoint"}}],
                     name=ctx.name)
    with plan.armed():
        _res, err = ctx.capture(faults.save_checkpoint, model, ck,
                                keep_last=3, stem="second")
    ctx.expect_error(err, StorageError)
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_no_tmp_litter(ctx.report, ck)
    invariants.check_checkpoint_loadable(ctx.report, ck)


@drill("fit", ["checkpoint_truncate"],
       expected_alerts=["checkpoint_fallbacks"])
def drill_checkpoint_torn_fallback(ctx: DrillContext):
    """A truncated newest checkpoint (crash-without-atomic-write state)
    is skipped with a checkpoint_fallback event; the previous one
    serves."""
    from deeplearning4j_tpu.train import faults

    model = _net()
    ck = ctx.path("ckpts")
    first = faults.save_checkpoint(model, ck, stem="first")
    _fit(model, _batches(2))
    newest = faults.save_checkpoint(model, ck, stem="second")
    faults.truncate_file(newest, frac=0.4)
    loaded, err = ctx.capture(faults.load_latest_valid, ck)
    ctx.report.add("fallback_served_previous",
                   err is None and loaded is not None
                   and loaded[1] == first,
                   str(err or (loaded and loaded[1])))
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_event_order(ctx.report, ctx.events(),
                                 ["checkpoint_fallback"])
    evs = ctx.events(["checkpoint_fallback"])
    ctx.report.add("fallback_names_skipped",
                   bool(evs) and evs[-1].get("skipped") == str(newest),
                   str(evs[-1] if evs else None))


@drill("registry_canary", ["registry.validation_score"],
       expected_alerts=["publish_refused"])
def drill_registry_nan_publish_gate(ctx: DrillContext):
    """A NaN-poisoned snapshot is refused typed at publish: journaled
    rejected, publish_refused forensics, never activatable, registry
    consistent on re-open."""
    from deeplearning4j_tpu.serving.registry import (
        ModelRegistry,
        SnapshotValidationError,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    reg = ModelRegistry(ctx.path("reg"))
    p1 = save_checkpoint(_net(seed=1), ctx.path("ck1"))
    reg.publish("m", p1, score=0.5)
    plan = ChaosPlan([{"seam": "registry.validation_score",
                       "mode": "value", "value": float("nan")}],
                     name=ctx.name)
    p2 = save_checkpoint(_net(seed=2), ctx.path("ck2"))
    with plan.armed():
        _res, err = ctx.capture(reg.publish, "m", p2, score=0.4)
    ctx.expect_error(err, SnapshotValidationError)
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_event_order(ctx.report, ctx.events(),
                                 ["publish", "publish_refused"])
    invariants.check_registry_consistent(ctx.report, ctx.path("reg"),
                                         expect_active={"m": 1})
    invariants.check_no_tmp_litter(ctx.report, ctx.path("reg"))


@drill("registry_canary", ["fs.append"],
       expected_alerts=["storage_errors"])
def drill_registry_journal_enospc(ctx: DrillContext):
    """Disk full on the registry's WAL append mid-publish: typed
    StorageError, the copied snapshot bytes are not orphaned, and the
    pre-publish state replays cleanly."""
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.train.faults import save_checkpoint

    reg = ModelRegistry(ctx.path("reg"))
    p1 = save_checkpoint(_net(seed=1), ctx.path("ck1"))
    reg.publish("m", p1, score=0.5)
    plan = ChaosPlan([{"seam": "fs.append", "mode": "enospc",
                       "match": {"surface": "registry_journal"}}],
                     name=ctx.name)
    p2 = save_checkpoint(_net(seed=2), ctx.path("ck2"))
    with plan.armed():
        _res, err = ctx.capture(reg.publish, "m", p2, score=0.4)
    ctx.expect_error(err, StorageError)
    invariants.check_typed_errors(ctx.report, ctx.errors)
    snap_dir = os.path.join(ctx.path("reg"), "snapshots", "m")
    zips = sorted(os.listdir(snap_dir)) if os.path.isdir(snap_dir) else []
    ctx.report.add("no_orphaned_snapshot_bytes", zips == ["v0001.zip"],
                   str(zips))
    invariants.check_registry_consistent(ctx.report, ctx.path("reg"),
                                         expect_active={"m": 1})
    invariants.check_no_tmp_litter(ctx.report, ctx.path("reg"))


@drill("registry_canary", ["registry.version_dispatch"],
       expected_alerts=["canary_rolled_back"])
def drill_registry_canary_dispatch_trip(ctx: DrillContext):
    """Every canary dispatch fails (bad snapshot): the gate trips on the
    FIRST failure — ordered canary_start → regression_trip → rollback,
    outstanding canary requests failed typed, active version untouched."""
    from deeplearning4j_tpu.serving.registry import (
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    reg = ModelRegistry(ctx.path("reg"))
    p1 = save_checkpoint(_net(seed=1), ctx.path("ck1"))
    p2 = save_checkpoint(_net(seed=2), ctx.path("ck2"))
    reg.publish("m", p1, score=0.5)
    router = ModelRouter(reg, canary_fraction=0.5, canary_window_s=30.0,
                         refresh_s=0.0, max_wait_ms=1.0)
    try:
        rows = np.random.default_rng(0).standard_normal(
            (2, N_IN)).astype(np.float32)
        router.predict("m", rows, timeout=30)
        reg.publish("m", p2, score=0.45)
        plan = ChaosPlan([{"seam": "registry.version_dispatch",
                           "mode": "error",
                           "match": {"role": "canary"}, "times": None}],
                         name=ctx.name)
        t0 = time.monotonic()
        with plan.armed():
            for _ in range(8):
                _res, err = ctx.capture(router.predict, "m", rows,
                                        timeout=30)
                state = reg.get("m")
                if (state.get("canary") is None
                        and state["versions"].get("2", {}).get("status")
                        == "rolled_back"):
                    break
        ctx.recovery_s = time.monotonic() - t0
        state = reg.get("m")
        ctx.report.add("rolled_back",
                       state["versions"].get("2", {}).get("status")
                       == "rolled_back", str(state["versions"].get("2")))
        ctx.report.add("active_untouched",
                       state.get("active_version") == 1,
                       f"active={state.get('active_version')}")
        # canary failures surface typed (injected fault or the typed
        # rolled-back error), and the ACTIVE version still serves
        invariants.check_typed_errors(ctx.report, ctx.errors)
        out, err = ctx.capture(router.predict, "m", rows, timeout=30)
        ctx.report.add("active_still_serving",
                       err is None and out is not None
                       and out[1] == 1, str(err))
        invariants.check_event_order(
            ctx.report, ctx.events(),
            ["canary_start", "regression_trip", "rollback"])
    finally:
        router.shutdown()


@drill("tune_study", ["fs.append"], expected_alerts=["storage_errors"])
def drill_tune_journal_torn(ctx: DrillContext):
    """A torn tune-journal append (SIGKILL-mid-append state, injected):
    typed StorageError at the writer, and replay drops exactly the torn
    trailing line — the study reconstructs."""
    from deeplearning4j_tpu.tune.store import TrialStore

    store = TrialStore(ctx.path("study"))
    store.append({"kind": "trial", "id": "t0", "overrides": {},
                  "seed": 1})
    store.append({"kind": "rung", "id": "t0", "rung": 0, "score": 0.5})
    plan = ChaosPlan([{"seam": "fs.append", "mode": "torn",
                       "match": {"surface": "tune_journal"}}],
                     name=ctx.name)
    with plan.armed():
        _res, err = ctx.capture(
            store.append, {"kind": "status", "id": "t0",
                           "status": "COMPLETED"})
    ctx.expect_error(err, StorageError)
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_tune_store_replayable(ctx.report, ctx.path("study"))
    trials, records = TrialStore(ctx.path("study")).reconstruct()
    ctx.report.add("torn_line_dropped", len(records) == 2,
                   f"{len(records)} records")


@drill("tune_study", ["fs.replace"],
       expected_alerts=["storage_errors"])
def drill_tune_study_enospc(ctx: DrillContext):
    """Disk full during a LIVE tune study's store writes: the study
    fails typed (StorageError reaches the driver), and the directory
    still replays for a post-mortem resume."""
    import functools

    from deeplearning4j_tpu.tune import (
        AshaScheduler,
        ContinuousParameterSpace,
        SearchSpace,
        Study,
    )
    from deeplearning4j_tpu.tune.runner import as_objective
    from deeplearning4j_tpu.tune.space import mlp_factory

    space = SearchSpace(
        functools.partial(mlp_factory, N_IN, N_OUT, widths=(8,)),
        {"lr": ContinuousParameterSpace(1e-3, 1e-1, scale="log")})
    batches = _batches(4)
    objective = as_objective(lambda model: float(model.score_))
    plan = ChaosPlan([{"seam": "fs.replace", "mode": "enospc",
                       "match": {"surface": "tune_meta"}}],
                     name=ctx.name)
    with plan.armed():
        _res, err = ctx.capture(
            Study(space, batches, objective,
                  scheduler=AshaScheduler(2, 4, eta=2), num_trials=2,
                  seed=7, engine="pool",
                  store_dir=ctx.path("study")).run)
    ctx.expect_error(err, StorageError)
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_tune_store_replayable(ctx.report, ctx.path("study"))
    invariants.check_no_tmp_litter(ctx.report, ctx.path("study"))


@drill("generation_storm", ["generate.decode_dispatch"],
       expected_alerts=["decode_errors"])
def drill_generate_decode_error(ctx: DrillContext):
    """A decode-dispatch failure mid-storm fails the ACTIVE requests
    typed, leaves decode_error forensics, and the engine keeps serving
    the next request (slab rebuilt)."""
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    engine = GenerationEngine(_lstm(), n_slots=2, max_length=16,
                              default_timeout_s=60.0)
    try:
        prompt = np.array([1, 2, 3], np.int32)
        engine.generate(prompt, max_new=3)  # warm path, no fault
        plan = ChaosPlan([{"seam": "generate.decode_dispatch",
                           "mode": "error"}], name=ctx.name)
        with plan.armed():
            _res, err = ctx.capture(engine.generate, prompt, max_new=4,
                                    timeout=30)
        ctx.expect_error(err, hooks.InjectedFaultError)
        invariants.check_typed_errors(ctx.report, ctx.errors)
        invariants.check_event_order(ctx.report, ctx.events(),
                                     ["decode_error"])
        t0 = time.monotonic()
        out, err = ctx.capture(engine.generate, prompt, max_new=3,
                               timeout=30)
        ctx.recovery_s = time.monotonic() - t0
        ctx.report.add("engine_recovered",
                       err is None and out is not None, str(err))
    finally:
        engine.shutdown(drain=False)


@drill("generation_storm", ["generate.decode_dispatch"],
       deadline_s=30.0, expected_alerts=["decode_stalled"])
def drill_generate_watchdog_stall(ctx: DrillContext):
    """A HUNG decode dispatch (injected delay past the watchdog limit):
    callers are failed typed DecodeStalledError at the limit — never a
    hang — with escalated decode_stall forensics, and the engine
    recovers once the dispatch returns."""
    from deeplearning4j_tpu.serving.generate import (
        DecodeStalledError,
        GenerationEngine,
    )

    engine = GenerationEngine(_lstm(), n_slots=2, max_length=16,
                              default_timeout_s=60.0)
    try:
        prompt = np.array([1, 2, 3], np.int32)
        # compile outside the step clock: a first dispatch that compiles
        # (seconds, on a loaded host) would sit in the step EWMA and
        # lift the watchdog limit above the injected delay
        engine.warmup()
        engine.generate(prompt, max_new=3)  # warm: EWMA is honest
        # tighten the watchdog AFTER warm-up (the first dispatch's XLA
        # compile would otherwise trip a 0.3s limit on its own)
        engine.watchdog_min_s = 0.3
        engine.watchdog_mult = 3.0
        plan = ChaosPlan([{"seam": "generate.decode_dispatch",
                           "mode": "delay", "delay_s": 1.2,
                           "at_call": 2}], name=ctx.name)
        t0 = time.monotonic()
        with plan.armed():
            _res, err = ctx.capture(engine.generate, prompt, max_new=4,
                                    timeout=20)
        ctx.recovery_s = time.monotonic() - t0
        ctx.expect_error(err, DecodeStalledError)
        ctx.report.add("unblocked_before_dispatch_end",
                       ctx.recovery_s < 10.0,
                       f"{ctx.recovery_s:.2f}s")
        invariants.check_typed_errors(ctx.report, ctx.errors)
        escalated = [e for e in ctx.events(["decode_stall"])
                     if e.get("escalated")]
        ctx.report.add("escalated_stall_recorded", bool(escalated),
                       str(ctx.events(["decode_stall"])))
        out, err = ctx.capture(engine.generate, prompt, max_new=3,
                               timeout=30)
        ctx.report.add("engine_recovered",
                       err is None and out is not None, str(err))
    finally:
        engine.shutdown(drain=False)


@drill("generation_storm", ["generate.prefix_cache"],
       expected_alerts=["prefix_hit_rate_low"])
def drill_generate_prefix_poisoned(ctx: DrillContext):
    """A poisoned prefix-cache entry (restore raises at the seam) is
    dropped typed and the request falls back to a real prefill with
    bit-identical output; the collapsing hit rate trips the SLO rule."""
    from deeplearning4j_tpu.serving.generate import GenerationEngine
    from deeplearning4j_tpu.serving.metrics import GenerationMetrics

    # the engine's gauges land in the detection evaluator's registry so
    # the hit-rate rule watches the drill's own engine
    metrics = GenerationMetrics(registry=ctx.alerts.registry)
    engine = GenerationEngine(_lstm(), n_slots=2, max_length=16,
                              default_timeout_s=60.0, metrics=metrics,
                              prefix_cache_mb=1.0)
    try:
        prompt = np.array([1, 2, 3], np.int32)
        ref = engine.generate(prompt, max_new=4)        # miss: captured
        hit = engine.generate(prompt, max_new=4)        # genuine hit
        ctx.report.add("clean_hit_bit_identical",
                       np.array_equal(ref, hit), str(hit))
        plan = ChaosPlan([{"seam": "generate.prefix_cache",
                           "mode": "error", "times": None}], name=ctx.name)
        with plan.armed():
            # every hit is poisoned: entry dropped, real prefill runs;
            # misses re-capture, so hit/drop alternate and the hit rate
            # collapses past the gauge's 8-lookup floor
            outs = []
            for _ in range(8):
                out, err = ctx.capture(engine.generate, prompt,
                                       max_new=4, timeout=30)
                ctx.report.add("poisoned_fallback_no_caller_error",
                               err is None, str(err))
                outs.append(out)
        ctx.report.add(
            "poisoned_fallback_bit_identical",
            all(o is not None and np.array_equal(ref, o) for o in outs),
            str([None if o is None else list(o) for o in outs]))
        invariants.check_typed_errors(ctx.report, ctx.errors)
        invariants.check_event_order(ctx.report, ctx.events(),
                                     ["prefix_hit", "prefix_evict"])
        poisoned = [e for e in ctx.events(["prefix_evict"])
                    if e.get("reason") == "poisoned"]
        ctx.report.add("poisoned_entries_dropped", len(poisoned) >= 3,
                       f"{len(poisoned)} poisoned evictions")
        snap = metrics.snapshot()
        ctx.report.add("hit_rate_collapsed",
                       snap["prefix_lookups"] >= 8 and
                       snap["prefix_hits"] * 5 <= snap["prefix_lookups"],
                       f"{snap['prefix_hits']}/{snap['prefix_lookups']}")
    finally:
        engine.shutdown(drain=False)


@drill("serving", ["serving.batch_dispatch"])
def drill_serving_dispatch_error(ctx: DrillContext):
    """A batched-inference dispatch failure fails exactly that batch
    typed; the batcher worker survives and the next request serves."""
    from deeplearning4j_tpu.serving.batcher import (
        DynamicBatcher,
        make_dispatcher,
    )
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    engine = InferenceEngine(_net())
    batcher = DynamicBatcher(make_dispatcher(engine.infer),
                             batch_limit=8, max_wait_ms=1.0)
    try:
        rows = np.random.default_rng(0).standard_normal(
            (2, N_IN)).astype(np.float32)
        batcher.submit(rows).result(timeout=30)
        plan = ChaosPlan([{"seam": "serving.batch_dispatch",
                           "mode": "error"}], name=ctx.name)
        with plan.armed():
            req = batcher.submit(rows)
            _res, err = ctx.capture(req.result, timeout=30)
        ctx.expect_error(err, hooks.InjectedFaultError)
        invariants.check_typed_errors(ctx.report, ctx.errors)
        out, err = ctx.capture(
            lambda: batcher.submit(rows).result(timeout=30))
        ctx.report.add("batcher_recovered",
                       err is None and out is not None, str(err))
    finally:
        batcher.shutdown(drain=False)


def _serving_mesh():
    """The largest 2-D (batch, model) mesh the host's devices form —
    (2, 4) on the 8-virtual-device test topology; degrades so the drill
    still exercises the seam on smaller hosts."""
    import jax

    from deeplearning4j_tpu.parallel.serving_mesh import ServingMesh

    n = len(jax.devices())
    if n >= 8:
        return ServingMesh(batch=2, model=4,
                           devices=jax.devices()[:8])
    if n >= 2:
        return ServingMesh(batch=1, model=2, devices=jax.devices()[:2])
    return ServingMesh(batch=1, model=1)


def _net_tp(seed: int = 3):
    """Drill net with TP-divisible dims (hidden/out multiples of the
    model axis)."""
    return _net(seed=seed, hidden=8)


@drill("serving", ["serving.sharded_dispatch"],
       expected_alerts=["sharded_serving_fallback"])
def drill_sharded_mesh_loss(ctx: DrillContext):
    """A device subset dies mid-serve on the 2-D (batch, model) mesh:
    the in-flight dispatch fails typed (ShardedMeshError), the engine
    demotes itself to one-device solo serving, the next request gets a
    correct answer, and the sharded_serving_fallback alert fires."""
    from deeplearning4j_tpu.serving.sharded import (
        ShardedInferenceEngine,
        ShardedMeshError,
    )

    mesh = _serving_mesh()
    engine = ShardedInferenceEngine(_net_tp(), mesh=mesh)
    rows = np.random.default_rng(0).standard_normal(
        (2, N_IN)).astype(np.float32)
    healthy = engine.infer(rows)
    plan = ChaosPlan([{"seam": "serving.sharded_dispatch",
                       "mode": "error"}], name=ctx.name)
    t0 = time.monotonic()
    with plan.armed():
        _res, err = ctx.capture(engine.infer, rows)
    ctx.expect_error(err, ShardedMeshError)
    ctx.report.add("fallback_armed", engine.fallback_active,
                   "engine did not demote to solo")
    out, err2 = ctx.capture(engine.infer, rows)
    ctx.recovery_s = time.monotonic() - t0
    ctx.report.add(
        "solo_serves_correctly",
        err2 is None and out is not None
        and np.allclose(healthy, out, rtol=1e-5, atol=1e-6),
        str(err2))
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_event_order(
        ctx.report, ctx.events(),
        ["mesh_build", "shard_load", "sharded_fallback"])


@drill("registry_canary", ["registry.version_dispatch"],
       expected_alerts=["canary_rolled_back"])
def drill_sharded_canary_promote_rollback(ctx: DrillContext):
    """Canary lifecycle with tensor-parallel candidates: on a 2-D
    serving mesh a sharded v2 canary promotes cleanly (no fault), then
    a sharded v3 canary's injected dispatch failures trip the standard
    rollback — the canary state machine is placement-blind."""
    from deeplearning4j_tpu.serving.registry import (
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.serving.sharded import ShardedInferenceEngine
    from deeplearning4j_tpu.train.faults import save_checkpoint

    mesh = _serving_mesh()
    reg = ModelRegistry(ctx.path("reg"))
    paths = [save_checkpoint(_net_tp(seed=s), ctx.path(f"ck{s}"))
             for s in (1, 2, 3)]
    reg.publish("m", paths[0], score=0.5)
    router = ModelRouter(reg, mesh=mesh, canary_fraction=1.0,
                         canary_window_s=0.2, canary_min_requests=1,
                         refresh_s=0.0, max_wait_ms=1.0)
    try:
        rows = np.random.default_rng(0).standard_normal(
            (2, N_IN)).astype(np.float32)
        router.predict("m", rows, timeout=30)
        mm = router._live.get("m")
        ctx.report.add(
            "active_engine_sharded",
            isinstance(mm.active.engine, ShardedInferenceEngine),
            type(mm.active.engine).__name__)
        # leg 1: clean canary promotes
        reg.publish("m", paths[1], score=0.45)
        deadline = time.monotonic() + 30
        promoted = False
        while time.monotonic() < deadline and not promoted:
            ctx.capture(router.predict, "m", rows, timeout=30)
            time.sleep(0.05)
            promoted = reg.get("m").get("active_version") == 2
        ctx.report.add("sharded_canary_promoted", promoted,
                       str(reg.get("m").get("active_version")))
        # leg 2: failing canary rolls back
        reg.publish("m", paths[2], score=0.4)
        plan = ChaosPlan([{"seam": "registry.version_dispatch",
                           "mode": "error",
                           "match": {"role": "canary"}, "times": None}],
                         name=ctx.name)
        t0 = time.monotonic()
        with plan.armed():
            for _ in range(8):
                ctx.capture(router.predict, "m", rows, timeout=30)
                state = reg.get("m")
                if (state.get("canary") is None
                        and state["versions"].get("3", {}).get("status")
                        == "rolled_back"):
                    break
        ctx.recovery_s = time.monotonic() - t0
        state = reg.get("m")
        ctx.report.add("sharded_canary_rolled_back",
                       state["versions"].get("3", {}).get("status")
                       == "rolled_back", str(state["versions"].get("3")))
        ctx.report.add("promoted_version_untouched",
                       state.get("active_version") == 2,
                       f"active={state.get('active_version')}")
        invariants.check_typed_errors(ctx.report, ctx.errors)
        out, err = ctx.capture(router.predict, "m", rows, timeout=30)
        ctx.report.add("active_still_serving",
                       err is None and out is not None and out[1] == 2,
                       str(err))
        invariants.check_event_order(
            ctx.report, ctx.events(),
            ["canary_start", "promote", "canary_start",
             "regression_trip", "rollback"])
    finally:
        router.shutdown()


@drill("kernels", ["kernel.probe"])
def drill_kernel_probe_transient(ctx: DrillContext):
    """A transient remote-compile crash during a kernel probe is
    retried (probe_with_retry) and the kernel still resolves; the crash
    never reaches the caller."""
    from deeplearning4j_tpu.nn.ops.registry import KernelRegistry

    reg = KernelRegistry()
    calls = {"n": 0}

    def probe_fn():
        calls["n"] += 1

    plan = ChaosPlan([{"seam": "kernel.probe",
                       "mode": "transient_compile", "times": 1}],
                     name=ctx.name)
    with plan.armed():
        ok, err = ctx.capture(reg.probe, "chaos_drill_kernel", ("k",),
                              probe_fn)
    ctx.report.add("probe_retried_and_resolved",
                   err is None and ok is True and calls["n"] == 1,
                   f"ok={ok} genuine_probe_calls={calls['n']} err={err}")
    invariants.check_typed_errors(ctx.report, ctx.errors)


@drill("generation_storm", ["generate.decode_dispatch"],
       expected_alerts=["canary_rolled_back"])
def drill_generation_canary_gate(ctx: DrillContext):
    """The PR 11 residue, drilled: a snapshot that only regresses under
    /generate traffic (its canary decode dispatches fail) still trips
    auto-rollback — generation completions feed the per-version gate."""
    from deeplearning4j_tpu.serving.registry import (
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    reg = ModelRegistry(ctx.path("reg"))
    p1 = save_checkpoint(_lstm(seed=1), ctx.path("ck1"))
    p2 = save_checkpoint(_lstm(seed=2), ctx.path("ck2"))
    reg.publish("lm", p1, score=0.5)
    router = ModelRouter(reg, gen_slots=2, gen_max_length=16,
                         canary_fraction=0.5, canary_window_s=30.0,
                         canary_min_requests=1, refresh_s=0.0)
    try:
        prompt = np.array([1, 2, 3], np.int32)
        router.generation_submit("lm", prompt, max_new=3,
                                 timeout=30).result(timeout=30)
        reg.publish("lm", p2, score=0.45)
        plan = ChaosPlan([{"seam": "generate.decode_dispatch",
                           "mode": "error",
                           "match": {"role": "canary"}, "times": None}],
                         name=ctx.name)
        t0 = time.monotonic()
        rolled = False
        with plan.armed():
            for _ in range(16):
                req = router.generation_submit("lm", prompt, max_new=3,
                                               timeout=30)
                ctx.capture(req.result, timeout=30)
                state = reg.get("lm")
                if (state["versions"].get("2", {}).get("status")
                        == "rolled_back"):
                    rolled = True
                    break
        ctx.recovery_s = time.monotonic() - t0
        ctx.report.add("generation_only_regression_rolled_back", rolled,
                       str(reg.get("lm")["versions"].get("2")))
        invariants.check_typed_errors(ctx.report, ctx.errors)
        invariants.check_event_order(
            ctx.report, ctx.events(),
            ["canary_start", "regression_trip", "rollback"])
        out, err = ctx.capture(
            lambda: router.generation_submit(
                "lm", prompt, max_new=3, timeout=30).result(timeout=30))
        ctx.report.add("active_generation_survives",
                       err is None and out is not None, str(err))
    finally:
        router.shutdown()


@drill("elastic_fit", ["host_dropout"], deadline_s=180.0,
       expected_alerts=["mesh_shrunk"])
def drill_elastic_dropout_recovery(ctx: DrillContext):
    """Host dropout mid-fit on the 8-device mesh: survivors re-form,
    reshard, resume in place — ordered mesh_shrink → reshard_start →
    reshard_done → elastic_resume forensics, the fit completes, the
    final model is finite and its checkpoints load."""
    devs = _need_devices(8)
    from deeplearning4j_tpu.train.faults import ElasticFitDriver

    batches = _batches(12, per=8)
    model = _net(policy=_policy())
    driver = ElasticFitDriver(model, ctx.path("ckpts"),
                              devices=devs[:8], max_retries=2)
    plan = ChaosPlan([{"seam": "host_dropout", "at_iteration": 6,
                       "survivors": 4}], name=ctx.name)
    t0 = time.monotonic()
    with plan.armed():
        _res, err = ctx.capture(driver.fit, batches, 1)
    ctx.recovery_s = time.monotonic() - t0
    model = driver.model
    ctx.report.add("fit_completed",
                   err is None and model.iteration == 12,
                   f"err={err} iteration={model.iteration}")
    ctx.report.add("recovered_once", driver.recoveries == 1,
                   f"recoveries={driver.recoveries}")
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_event_order(
        ctx.report, ctx.events(),
        ["mesh_shrink", "reshard_start", "reshard_done",
         "elastic_resume"])
    invariants.check_params_finite(ctx.report, model)
    invariants.check_checkpoint_loadable(ctx.report, ctx.path("ckpts"))
    invariants.check_no_tmp_litter(ctx.report, ctx.path("ckpts"))


# ==========================================================================
# paired-fault drills — compositions no single-feature test exercises
# ==========================================================================
@drill("elastic_fit", ["host_dropout", "on_event"], paired=True,
       fast=False, deadline_s=240.0,
       expected_alerts=["mesh_shrunk", "checkpoint_fallbacks"])
def drill_paired_ckpt_corrupt_during_recovery(ctx: DrillContext):
    """PAIRED: the newest checkpoint is truncated AT THE MOMENT the
    mesh fails (mesh_shrink event) — recovery must fall back to the
    previous checkpoint, replay the longer tail, and still finish:
    mesh_shrink → checkpoint_fallback → elastic_resume, in order."""
    devs = _need_devices(8)
    from deeplearning4j_tpu.train.faults import ElasticFitDriver

    batches = _batches(12, per=8)
    model = _net(policy=_policy())
    ck = ctx.path("ckpts")
    driver = ElasticFitDriver(model, ck, devices=devs[:8], max_retries=2)
    plan = ChaosPlan(
        [{"seam": "host_dropout", "at_iteration": 6, "survivors": 4},
         {"seam": "on_event", "event": "mesh_shrink",
          "action": "truncate_newest_checkpoint", "dir": ck}],
        name=ctx.name)
    t0 = time.monotonic()
    with plan.armed():
        _res, err = ctx.capture(driver.fit, batches, 1)
    ctx.recovery_s = time.monotonic() - t0
    model = driver.model
    ctx.report.add("fit_completed",
                   err is None and model.iteration == 12,
                   f"err={err} iteration={model.iteration}")
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_event_order(
        ctx.report, ctx.events(),
        ["mesh_shrink", "checkpoint_fallback", "elastic_resume"])
    invariants.check_params_finite(ctx.report, model)
    invariants.check_checkpoint_loadable(ctx.report, ck)
    invariants.check_no_tmp_litter(ctx.report, ck)


@drill("registry_canary", ["fs.replace"], paired=True, fast=False,
       deadline_s=120.0, expected_alerts=["storage_errors"])
def drill_paired_enospc_mid_publish_canary_open(ctx: DrillContext):
    """PAIRED: disk fills during a publish WHILE a canary window is
    open — the publish fails typed, the in-flight canary is unaffected
    and still promotes, and the registry replays consistently."""
    from deeplearning4j_tpu.serving.registry import (
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    reg = ModelRegistry(ctx.path("reg"))
    paths = [save_checkpoint(_net(seed=s), ctx.path(f"ck{s}"))
             for s in (1, 2, 3)]
    reg.publish("m", paths[0], score=0.5)
    router = ModelRouter(reg, canary_fraction=0.5, canary_window_s=0.6,
                         canary_min_requests=1, refresh_s=0.0,
                         max_wait_ms=1.0)
    try:
        rows = np.random.default_rng(0).standard_normal(
            (2, N_IN)).astype(np.float32)
        router.predict("m", rows, timeout=30)
        reg.publish("m", paths[1], score=0.45)  # -> canary v2
        router.predict("m", rows, timeout=30)   # window open
        plan = ChaosPlan([{"seam": "fs.replace", "mode": "enospc",
                           "match": {"surface": "registry_publish"}}],
                         name=ctx.name)
        with plan.armed():
            _res, err = ctx.capture(reg.publish, "m", paths[2],
                                    score=0.44)
        ctx.expect_error(err, StorageError)
        # keep traffic flowing until the canary promotes
        t0 = time.monotonic()
        promoted = False
        while time.monotonic() - t0 < 30.0:
            ctx.capture(router.predict, "m", rows, timeout=30)
            if reg.get("m").get("active_version") == 2:
                promoted = True
                break
            time.sleep(0.05)
        ctx.recovery_s = time.monotonic() - t0
        ctx.report.add("canary_promoted_despite_enospc", promoted,
                       str(reg.get("m").get("active_version")))
        invariants.check_typed_errors(ctx.report, ctx.errors)
        invariants.check_event_order(ctx.report, ctx.events(),
                                     ["canary_start", "storage_error",
                                      "promote"])
        invariants.check_registry_consistent(ctx.report, ctx.path("reg"),
                                             expect_active={"m": 2})
        invariants.check_no_tmp_litter(ctx.report, ctx.path("reg"))
    finally:
        router.shutdown()


@drill("generation_storm", ["generate.decode_dispatch"], paired=True,
       fast=False, deadline_s=120.0,
       expected_alerts=["decode_stalled", "canary_rolled_back"])
def drill_paired_watchdog_trip_during_canary(ctx: DrillContext):
    """PAIRED: the decode watchdog trips on the CANARY's hung dispatch
    while its window is open — the stall surfaces typed, the gate rolls
    the candidate back, and active-version generation keeps serving."""
    from deeplearning4j_tpu.serving.registry import (
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    reg = ModelRegistry(ctx.path("reg"))
    p1 = save_checkpoint(_lstm(seed=1), ctx.path("ck1"))
    p2 = save_checkpoint(_lstm(seed=2), ctx.path("ck2"))
    reg.publish("lm", p1, score=0.5)
    router = ModelRouter(reg, gen_slots=2, gen_max_length=16,
                         canary_fraction=1.0, canary_window_s=60.0,
                         canary_min_requests=1, refresh_s=0.0)
    try:
        prompt = np.array([1, 2, 3], np.int32)
        router.generation_submit("lm", prompt, max_new=3,
                                 timeout=30).result(timeout=30)
        reg.publish("lm", p2, score=0.45)
        # hang only the canary engine's decode; shrink its watchdog so
        # the drill is fast
        plan = ChaosPlan([{"seam": "generate.decode_dispatch",
                           "mode": "delay", "delay_s": 1.5,
                           "match": {"role": "canary"}}],
                         name=ctx.name)
        # pre-build the canary's decode engine so its watchdog can be
        # tightened BEFORE the hung dispatch (a production deploy would
        # configure the knobs at build; the drill shrinks them for speed)
        mm = router._managed_for_generation("lm")
        with mm.lock:
            router._maybe_adopt(mm)
            spec = (None if mm.canary is None
                    else (mm.canary.engine.model, mm.canary.version))
            if spec is not None:
                mm.canary_gen_building = True
        # build+warm runs with NO locks held (ISSUE 14: building under
        # mm.lock closed a lock-order cycle against the decode worker)
        if spec is not None:
            router._build_canary_generation(mm, *spec)
        with mm.lock:
            cgen = mm.canary_generation
        ctx.report.add("canary_generation_built", cgen is not None)
        if cgen is not None:
            cgen.watchdog_min_s = 0.3
            cgen.watchdog_mult = 3.0
        t0 = time.monotonic()
        rolled = False
        with plan.armed():
            req = router.generation_submit("lm", prompt, max_new=4,
                                           timeout=20)
            ctx.capture(req.result, timeout=20)
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                if (reg.get("lm")["versions"].get("2", {}).get("status")
                        == "rolled_back"):
                    rolled = True
                    break
                req = router.generation_submit("lm", prompt, max_new=3,
                                               timeout=20)
                ctx.capture(req.result, timeout=20)
        ctx.recovery_s = time.monotonic() - t0
        ctx.report.add("watchdog_trip_rolled_canary_back", rolled,
                       str(reg.get("lm")["versions"].get("2")))
        invariants.check_typed_errors(ctx.report, ctx.errors)
        invariants.check_event_order(
            ctx.report, ctx.events(),
            ["canary_start", "regression_trip", "rollback"])
        out, err = ctx.capture(
            lambda: router.generation_submit(
                "lm", prompt, max_new=3, timeout=30).result(timeout=30))
        ctx.report.add("active_generation_survives",
                       err is None and out is not None, str(err))
    finally:
        router.shutdown()


# ==========================================================================
# cluster drills: the multi-replica tier (serving/cluster.py). All are
# in-process multi-coordinator — to its peers, a SIGKILLed replica is
# indistinguishable from one that stopped appending heartbeats (journal
# silence is the ONLY failure signal); the package-boundary version
# with real processes and real SIGKILL is scripts/drive_cluster.py.
# ==========================================================================
def _cluster_pair(ctx: DrillContext, ttl: float = 0.4,
                  directory: str = "reg"):
    from deeplearning4j_tpu.serving.cluster import ClusterCoordinator

    d = ctx.path(directory)
    os.makedirs(d, exist_ok=True)
    a = ClusterCoordinator(d, "ra", heartbeat_s=0.05, lease_ttl_s=ttl)
    b = ClusterCoordinator(d, "rb", heartbeat_s=0.05, lease_ttl_s=ttl)
    a.heartbeat()
    b.heartbeat()
    return a, b


@drill("cluster", ["registry.version_dispatch"], deadline_s=60.0,
       expected_alerts=["replica_stale", "canary_rolled_back"])
def drill_cluster_replica_loss_mid_canary(ctx: DrillContext):
    """Replica loss mid-canary-window: the lease-holding controller
    dies while a non-holder is watching the canary fail — the survivor
    suspends (its inline trip is fence-refused), steals the lease after
    the TTL, and trips the CLUSTER rollback; active version untouched."""
    from deeplearning4j_tpu.serving.cluster import ClusterCoordinator
    from deeplearning4j_tpu.serving.registry import (
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    regdir = ctx.path("reg")
    reg = ModelRegistry(regdir)
    p1 = save_checkpoint(_net(seed=1), ctx.path("ck1"))
    p2 = save_checkpoint(_net(seed=2), ctx.path("ck2"))
    reg.publish("m", p1, score=0.5)
    # replica A: a bare coordinator standing in for the peer server
    # that owns the canary window; it beats until "SIGKILLed"
    a = ClusterCoordinator(regdir, "ra", heartbeat_s=0.05,
                           lease_ttl_s=0.5)
    a.start()
    b_coord = ClusterCoordinator(regdir, "rb", heartbeat_s=0.05,
                                 lease_ttl_s=0.5)
    b_coord.heartbeat()
    router = ModelRouter(reg, canary_fraction=0.5, canary_window_s=60.0,
                         refresh_s=0.02, max_wait_ms=1.0,
                         cluster=b_coord)
    try:
        ctx.report.add("controller_lease_held_by_peer",
                       a.ensure_lease("m"))
        rows = np.random.default_rng(0).standard_normal(
            (2, N_IN)).astype(np.float32)
        router.predict("m", rows, timeout=30)
        reg.publish("m", p2, score=0.45)
        plan = ChaosPlan([{"seam": "registry.version_dispatch",
                           "mode": "error",
                           "match": {"role": "canary"}, "times": None}],
                         name=ctx.name)
        with plan.armed():
            # phase 1: A alive — B's canary traffic fails, its inline
            # trip is refused by the epoch fence, and it SUSPENDS
            for _ in range(16):
                ctx.capture(router.predict, "m", rows, timeout=30)
                if ctx.events(["canary_suspend"]):
                    break
            suspended = bool(ctx.events(["canary_suspend"]))
            st = reg.get("m")["versions"].get("2", {}).get("status")
            ctx.report.add("nonholder_suspended_not_rolled_back",
                           suspended and st == "canary",
                           f"suspended={suspended} status={st}")
            # phase 2: A dies mid-window (journal silence — to peers,
            # identical to SIGKILL); B steals after the TTL and trips
            a.shutdown(release_leases=False)
            t0 = time.monotonic()
            rolled = False
            while time.monotonic() - t0 < 20.0:
                ctx.capture(router.predict, "m", rows, timeout=30)
                if (reg.get("m")["versions"].get("2", {}).get("status")
                        == "rolled_back"):
                    rolled = True
                    break
                time.sleep(0.05)
            ctx.recovery_s = time.monotonic() - t0
        ctx.report.add("takeover_rolled_back_cluster_wide", rolled,
                       str(reg.get("m")["versions"].get("2")))
        ctx.report.add("active_untouched",
                       reg.get("m").get("active_version") == 1)
        invariants.check_typed_errors(ctx.report, ctx.errors)
        invariants.check_event_order(
            ctx.report, ctx.events(),
            ["lease_acquire", "canary_start", "canary_suspend",
             "replica_lost", "lease_steal", "regression_trip",
             "rollback"])
        invariants.check_registry_consistent(ctx.report, regdir,
                                             expect_active={"m": 1})
        invariants.check_no_tmp_litter(ctx.report, regdir)
    finally:
        router.shutdown()
        b_coord.shutdown(release_leases=False)
        a.shutdown(release_leases=False)


@drill("cluster", ["cluster.decision"],
       expected_alerts=["replica_stale"])
def drill_cluster_lease_expiry_paused_exholder(ctx: DrillContext):
    """Lease expiry + takeover with a PAUSED ex-holder: the holder
    stalls between deciding and fencing (delay on cluster.decision — a
    GC/VM pause), the TTL expires, a peer steals the lease, and the
    resumed holder's late decision is refused typed StaleEpochError."""
    import threading

    from deeplearning4j_tpu.serving.cluster import (
        ClusterCoordinator,
        StaleEpochError,
    )

    a, b = _cluster_pair(ctx, ttl=0.3)
    ctx.report.add("initial_claim", a.ensure_lease("m"))
    plan = ChaosPlan([{"seam": "cluster.decision", "mode": "delay",
                       "delay_s": 0.8, "match": {"replica": "ra"}}],
                     name=ctx.name)
    result = {}

    def late_decision():
        _res, err = ctx.capture(a.release, "m")
        result["err"] = err

    t0 = time.monotonic()
    with plan.armed():
        th = threading.Thread(target=late_decision, daemon=True)
        th.start()
        time.sleep(0.45)  # past the TTL while A is paused at the seam
        b.heartbeat()
        stolen = b.ensure_lease("m")
        th.join(timeout=15)
    ctx.recovery_s = time.monotonic() - t0
    ctx.report.add("lease_stolen_after_expiry", stolen,
                   str(b.lease_state("m")))
    ctx.expect_error(result.get("err"), StaleEpochError,
                     name="late_decision_refused_typed")
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_event_order(
        ctx.report, ctx.events(),
        ["lease_acquire", "replica_lost", "lease_steal",
         "stale_epoch_refused"])
    # a replica joining AFTER the handoff replays the journal to the
    # same holder/epoch — bounded, deterministic recovery
    c = ClusterCoordinator(ctx.path("reg"), "rc", heartbeat_s=0.05,
                           lease_ttl_s=0.3)
    c.refresh()
    lease = c.lease_state("m")
    ctx.report.add("journal_replays_to_stolen_holder",
                   lease["replica"] == "rb" and lease["epoch"] == 2,
                   str(lease))
    for coord in (a, b, c):
        coord.shutdown(release_leases=False)


@drill("cluster", ["cluster.decision"],
       expected_alerts=["replica_stale"])
def drill_cluster_clock_skew_double_claim(ctx: DrillContext):
    """Clock-skewed double-claim: a replica whose clock runs 10s behind
    claims the lease, looks instantly stale to a well-clocked peer, and
    is double-claimed — the epoch fence refuses the skewed replica's
    decisions typed; the two claims are never silently merged."""
    from deeplearning4j_tpu.serving.cluster import (
        ClusterCoordinator,
        StaleEpochError,
    )

    regdir = ctx.path("reg")
    os.makedirs(regdir, exist_ok=True)
    skew = 10.0
    a = ClusterCoordinator(regdir, "ra", heartbeat_s=0.05,
                           lease_ttl_s=0.5,
                           clock=lambda: time.time() - skew)
    b = ClusterCoordinator(regdir, "rb", heartbeat_s=0.05,
                           lease_ttl_s=0.5)
    a.heartbeat()
    b.heartbeat()
    ctx.report.add("skewed_claim", a.ensure_lease("m"))
    # to B, A's heartbeat timestamps are already past the TTL
    b.refresh()
    ctx.report.add("double_claim_steals", b.ensure_lease("m"),
                   str(b.lease_state("m")))
    # both replicas believed they were controller; the fence decides
    _res, err = ctx.capture(a.fence, "m")
    ctx.expect_error(err, StaleEpochError,
                     name="skewed_decision_refused_typed")
    epoch, fence_err = ctx.capture(b.fence, "m")
    ctx.report.add("current_holder_fences_clean",
                   fence_err is None and epoch == 2,
                   f"epoch={epoch} err={fence_err}")
    ctx.report.add("exactly_one_controller",
                   b.is_owner("m") and not a.is_owner("m"))
    invariants.check_typed_errors(ctx.report, ctx.errors)
    # replica_lost fires as soon as ANY fold sees the skewed
    # timestamps — before A even claims — so it is asserted by
    # presence, not position
    ctx.report.add("skew_judged_lost",
                   bool(ctx.events(["replica_lost"])))
    invariants.check_event_order(
        ctx.report, ctx.events(),
        ["lease_acquire", "lease_steal", "stale_epoch_refused"])
    for coord in (a, b):
        coord.shutdown(release_leases=False)


@drill("cluster", ["fs.append"], deadline_s=60.0,
       expected_alerts=["replica_stale", "lease_flap",
                        "storage_errors"])
def drill_cluster_split_brain_appends(ctx: DrillContext):
    """Split-brain concurrent journal appends: two replicas claim the
    same epoch simultaneously — journal append order is the tiebreak
    (exactly one owner, loser refused typed); a torn heartbeat append
    (SIGKILL mid-write) is typed, repaired, and replays clean; repeated
    handoffs fire the lease_flap alert."""
    import threading

    from deeplearning4j_tpu.serving.cluster import (
        ClusterCoordinator,
        StaleEpochError,
    )

    a, b = _cluster_pair(ctx, ttl=0.25)
    barrier = threading.Barrier(2)
    results = {}

    def claim(coord, key):
        barrier.wait()
        results[key] = coord.ensure_lease("m")

    ta = threading.Thread(target=claim, args=(a, "a"), daemon=True)
    tb = threading.Thread(target=claim, args=(b, "b"), daemon=True)
    ta.start()
    tb.start()
    ta.join(timeout=15)
    tb.join(timeout=15)
    a.refresh()
    b.refresh()
    owners = [c for c in (a, b) if c.is_owner("m")]
    ctx.report.add("exactly_one_owner_after_split_brain",
                   len(owners) == 1,
                   f"claims={results} lease={a.lease_state('m')}")
    ctx.report.add("claim_results_agree",
                   sorted(results.values()) == [False, True],
                   str(results))
    loser = b if owners and owners[0] is a else a
    _res, err = ctx.capture(loser.fence, "m")
    ctx.expect_error(err, StaleEpochError,
                     name="split_brain_loser_refused_typed")
    # torn heartbeat append (SIGKILL mid-write): typed StorageError,
    # half the line durably on disk
    plan = ChaosPlan([{"seam": "fs.append", "mode": "torn",
                       "match": {"surface": "cluster_journal"},
                       "times": 1}], name=ctx.name)
    with plan.armed():
        _res, err = ctx.capture(a.heartbeat)
    ctx.expect_error(err, StorageError, name="torn_append_typed")
    # readers leave the fragment unconsumed; the next append repairs it
    b.refresh()
    _res, err = ctx.capture(a.heartbeat)
    ctx.report.add("append_after_torn_repairs", err is None
                   and bool(ctx.events(["journal_repair"])), str(err))
    # repeated stale→steal handoffs: the lease flapping between
    # replicas is an alert, not silence
    winner = owners[0] if owners else a
    loser = b if winner is a else a
    for _ in range(3):
        time.sleep(0.3)  # the holder's heartbeat goes past the TTL
        loser.heartbeat()
        ctx.report.add("flap_steal", loser.ensure_lease("m"),
                       str(loser.lease_state("m")))
        winner, loser = loser, winner
    ctx.report.add("lease_steals_recorded",
                   len(ctx.events(["lease_steal"])) >= 3)
    # a fresh replica replays the whole journal — torn tail, split-
    # brain claims and all — to the same final holder
    c = ClusterCoordinator(ctx.path("reg"), "rc", heartbeat_s=0.05,
                           lease_ttl_s=0.25)
    c.refresh()
    ctx.report.add("journal_replays_final_holder",
                   c.lease_state("m")["replica"]
                   == winner.replica_id, str(c.lease_state("m")))
    invariants.check_typed_errors(ctx.report, ctx.errors)
    for coord in (a, b, c):
        coord.shutdown(release_leases=False)


# ==========================================================================
# loadgen drill: the observe→act loop under oscillating load
# ==========================================================================
@drill("serving", ["controller.act"],
       expected_alerts=["serving_latency_slo_breach"])
def drill_controller_oscillation(ctx: DrillContext):
    """Load flip-flopping across the SLO hysteresis boundary: layered
    flap suppression (alert hold-downs + controller cooldowns) bounds
    controller actions, a demoted tenant is restored once the burn
    stays quiet, and an injected actuator failure is contained by the
    hub — the loop keeps ticking and the knob actuates next tick."""
    from deeplearning4j_tpu.loadgen.controllers import (
        ControllerHub,
        DeadlineTuner,
        TenantDemoter,
    )
    from deeplearning4j_tpu.serving.batcher import (
        DynamicBatcher,
        make_dispatcher,
    )
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    from deeplearning4j_tpu.serving.registry import (
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    reg = ModelRegistry(ctx.path("reg"))
    reg.publish("m", save_checkpoint(_net(), ctx.path("ck")), score=0.5)
    router = ModelRouter(reg, refresh_s=30.0, max_wait_ms=1.0)
    engine = InferenceEngine(_net())
    batcher = DynamicBatcher(make_dispatcher(engine.infer),
                             batch_limit=8, max_wait_ms=8.0)
    # the drill drives the OBSERVED signal directly: this gauge is what
    # the detection evaluator's latency rule reads, so flipping it IS
    # flipping load across the hysteresis boundary, deterministically
    p99 = ctx.alerts.registry.gauge("serving_latency_p99_ms",
                                    "drill-driven latency signal")
    tuner = DeadlineTuner(batcher, cooldown_s=10.0)
    demoter = TenantDemoter(router, restore_after_s=15.0, cooldown_s=5.0)
    hub = ControllerHub(ctx.alerts, [tuner, demoter])
    rows = np.random.default_rng(0).standard_normal(
        (1, N_IN)).astype(np.float32)
    DT = 5.0  # injected-clock seconds per hub tick

    def tick(p99_ms: float, spam: int = 0) -> None:
        for _ in range(spam):
            router.submit("m", rows, timeout=30,
                          tenant="spammy").result(timeout=30)
        router.submit("m", rows, timeout=30,
                      tenant="steady").result(timeout=30)
        p99.set(p99_ms)
        ctx._alert_now += DT
        hub.tick(ctx._alert_now)

    try:
        # phase 1 — sustained burn: the breach fires, the tuner sheds
        # deadline, the demoter pins the dominating tenant
        for _ in range(6):
            tick(400.0, spam=3)
        ctx.report.add("breach_fired",
                       "serving_latency_slo_breach"
                       in ctx.alerts.fired_names())
        ctx.report.add("deadline_shrunk_under_breach",
                       batcher.max_wait_s * 1e3 < tuner.initial_ms,
                       f"max_wait_ms={batcher.max_wait_s * 1e3:.3f}")
        ctx.report.add(
            "abusive_tenant_demoted",
            "spammy" in router.tenant_tiers
            and bool(ctx.events(["controller_tenant_demote"])),
            str(dict(router.tenant_tiers)))
        # phase 2 — oscillation: flip the signal every tick; alert
        # hold-downs + per-controller cooldowns must bound actions
        for i in range(8):
            tick(400.0 if i % 2 == 0 else 100.0, spam=3)
        elapsed = ctx._alert_now
        retunes = len(ctx.events(["controller_retune"]))
        bound = int(elapsed / tuner.cooldown_s) + 1
        ctx.report.add("flap_suppression_bounds_retunes",
                       retunes <= bound,
                       f"retunes={retunes} bound={bound} "
                       f"elapsed_s={elapsed}")
        demotes = len(ctx.events(["controller_tenant_demote"]))
        ctx.report.add("no_demote_storm", demotes == 1,
                       f"demotes={demotes}")
        # phase 3 — the burn stops: the breach resolves, the demoted
        # tenant is restored, the deadline relaxes off its floor
        for _ in range(12):
            tick(100.0)
        ctx.report.add(
            "tenant_restored_after_quiet",
            "spammy" not in router.tenant_tiers
            and bool(ctx.events(["controller_tenant_restore"])),
            str(dict(router.tenant_tiers)))
        ctx.report.add("deadline_relaxes_after_quiet",
                       batcher.max_wait_s * 1e3 > tuner.min_wait_ms,
                       f"max_wait_ms={batcher.max_wait_s * 1e3:.3f}")
        # phase 4 — broken actuator: the injected failure at the
        # actuation seam is contained; the hub keeps ticking and the
        # SAME knob actuates on a later tick
        errors0, actions0 = hub.errors, tuner.actions
        plan = ChaosPlan([{"seam": "controller.act", "mode": "error",
                           "match": {"controller": "deadline_tuner"},
                           "times": 1}], name=ctx.name)
        with plan.armed():
            for _ in range(4):
                tick(400.0, spam=3)
        ctx.report.add("hub_contained_actuator_fault",
                       hub.errors == errors0 + 1
                       and bool(ctx.events(["chaos_inject"])),
                       f"errors={hub.errors}")
        ctx.report.add("loop_alive_after_fault",
                       tuner.actions > actions0,
                       f"actions={tuner.actions} before={actions0}")
        invariants.check_typed_errors(ctx.report, ctx.errors)
        invariants.check_event_order(
            ctx.report, ctx.events(),
            ["controller_retune", "controller_tenant_demote",
             "controller_tenant_restore", "chaos_inject"])
        p99.set(0.0)  # post-drill detection ticks see a quiet signal
    finally:
        batcher.shutdown(drain=False)
        router.shutdown()


@drill("fit", ["data.shard_read"], expected_alerts=["shard_skips"])
def drill_data_torn_shard_skip(ctx: DrillContext):
    """A shard torn mid-epoch: the reader raises typed TornShardError,
    the loader skips the shard with a shard_skip forensic and the fit
    completes on the survivors; resume replay from a mid-stream
    data_state is bit-identical past the skipped shard."""
    from deeplearning4j_tpu.data import (
        ExistingDataSetIterator,
        ShardedLoader,
        TornShardError,
        pack_iterator,
        read_shard,
    )

    batches = _batches(n=12, per=8, seed=5)
    sd = ctx.path("shards")
    pack_iterator(ExistingDataSetIterator(list(batches)), sd,
                  batches_per_shard=3)  # 4 shards x 3 batches
    # tear the shard the epoch-0 plan reads SECOND — genuinely
    # mid-epoch, after the stream has already emitted batches
    probe = ShardedLoader(sd, num_workers=1, seed=11)
    victim = probe.epoch_plan(0)[1]
    victim_name = probe._names[victim]
    # times=None: a torn file stays torn on EVERY read — each loader
    # below must see the same damage (default budget is one injection)
    plan = ChaosPlan([{"seam": "data.shard_read", "mode": "torn",
                       "match": {"path_substr": victim_name},
                       "times": None}],
                     name=ctx.name)
    # 1) the typed contract at the reader itself
    with plan.armed():
        _res, err = ctx.capture(read_shard, os.path.join(sd, victim_name))
    ctx.expect_error(err, TornShardError)
    # 2) the loader under the same injection: fit completes, the torn
    # shard's 3 batches are dropped deterministically
    model = _net(seed=4)
    loader = ShardedLoader(sd, num_workers=2, seed=11)
    with plan.armed():
        _res, err = ctx.capture(model.fit, loader, epochs=1)
    ctx.report.add("fit_completed_past_torn_shard", err is None
                   and model.iteration == len(batches) - 3,
                   f"iteration={model.iteration} err={err}")
    ctx.report.add("shard_skip_forensic",
                   bool(ctx.events(["shard_skip"])),
                   victim_name)
    ctx.report.add("data_state_on_model",
                   (model._data_state or {}).get("batches")
                   == len(batches) - 3,
                   str(model._data_state))
    # 3) resume replay bit-identical past the skip: consume 4 batches,
    # capture the position, resume a FRESH loader from it — suffix
    # streams must match bit for bit (fingerprint chain equality)
    def run(state=None, n=None):
        ld = ShardedLoader(sd, num_workers=2, seed=11)
        if state is not None:
            ld.restore_state(state)
        taken = 0
        while ld.has_next() and (n is None or taken < n):
            ld.next()
            taken += 1
        st = ld.data_state()
        ld.shutdown()
        return st

    with plan.armed():
        oracle = run()
        mid = run(n=4)
        resumed = run(state=mid)
    ctx.report.add("resume_bit_identical_past_skip",
                   resumed["fingerprint"] == oracle["fingerprint"]
                   and resumed["batches"] == oracle["batches"],
                   f"{resumed['fingerprint'][:12]} vs "
                   f"{oracle['fingerprint'][:12]}")
    invariants.check_typed_errors(ctx.report, ctx.errors)
    invariants.check_event_order(ctx.report, ctx.events(),
                                 ["shard_torn", "shard_skip",
                                  "data_resume"])


# ==========================================================================
# custom plans over stock workloads (cli chaos --plan)
# ==========================================================================
WORKLOADS = ("fit", "checkpoint_fit", "generate", "registry", "tune")


def run_custom(plan: ChaosPlan, workload: str) -> DrillResult:
    """Arm an operator-supplied plan around a stock workload and apply
    the generic invariants (typed errors, no litter, artifacts
    loadable). The named drills above are curated compositions; this is
    the escape hatch for probing a new fault idea declaratively."""
    if workload not in WORKLOADS:
        raise ValueError(f"unknown workload {workload!r} "
                         f"(known: {WORKLOADS})")
    ctx = DrillContext(f"custom_{workload}")
    t0 = time.monotonic()
    error = None
    try:
        with plan.armed():
            if workload == "fit":
                ctx.capture(_fit, _net(policy=_policy()), _batches(4))
            elif workload == "checkpoint_fit":
                from deeplearning4j_tpu.train.listeners import (
                    CheckpointListener,
                )

                from deeplearning4j_tpu.train.faults import (
                    checkpoint_files,
                )

                m = _net(policy=_policy())
                m.add_listeners(CheckpointListener(
                    ctx.path("ckpts"), save_every_n_epochs=1,
                    keep_mode="last", keep_last=3))
                ctx.capture(_fit, m, _batches(4), 2)
                # a published checkpoint must load; a plan that failed
                # every write leaves an EMPTY dir, which is consistent
                # (nothing was ever published), not corrupt
                if checkpoint_files(ctx.path("ckpts")):
                    invariants.check_checkpoint_loadable(
                        ctx.report, ctx.path("ckpts"))
            elif workload == "generate":
                from deeplearning4j_tpu.serving.generate import (
                    GenerationEngine,
                )

                engine = GenerationEngine(_lstm(), n_slots=2,
                                          max_length=16,
                                          watchdog_min_s=2.0,
                                          watchdog_mult=5.0)
                try:
                    for _ in range(4):
                        ctx.capture(engine.generate,
                                    np.array([1, 2, 3], np.int32),
                                    max_new=3, timeout=30)
                finally:
                    engine.shutdown(drain=False)
            elif workload == "registry":
                from deeplearning4j_tpu.serving.registry import (
                    ModelRegistry,
                )
                from deeplearning4j_tpu.train.faults import (
                    save_checkpoint,
                )

                reg = ModelRegistry(ctx.path("reg"))
                for s in (1, 2):
                    p = save_checkpoint(_net(seed=s), ctx.path(f"ck{s}"))
                    ctx.capture(reg.publish, "m", p,
                                score=0.5 - 0.01 * s)
                invariants.check_registry_consistent(ctx.report,
                                                     ctx.path("reg"))
            elif workload == "tune":
                from deeplearning4j_tpu.tune.store import TrialStore

                store = TrialStore(ctx.path("study"))
                for i in range(4):
                    ctx.capture(store.append,
                                {"kind": "trial", "id": f"t{i}",
                                 "overrides": {}, "seed": i})
                invariants.check_tune_store_replayable(
                    ctx.report, ctx.path("study"))
        invariants.check_typed_errors(ctx.report, ctx.errors)
        invariants.check_no_tmp_litter(ctx.report, ctx.dir)
    except BaseException as e:  # noqa: BLE001 — a crashed harness is RED
        error = f"{type(e).__name__}: {e}"
    finally:
        hooks.disarm(None)
        shutil.rmtree(ctx.dir, ignore_errors=True)
    alerts_fired = ctx.tick_alerts(2) if error is None else []
    ctx.alerts.unwatch()
    wall = time.monotonic() - t0
    ok = error is None and ctx.report.ok
    res = DrillResult(ctx.name, ok, ctx.report.to_dict(), wall,
                      error=error, alerts_fired=alerts_fired)
    return res


# keep the matrix honest at import time (the acceptance floor)
assert len(DRILLS) >= 12, f"drill matrix shrank to {len(DRILLS)}"
assert sum(1 for d in DRILLS.values() if d.paired) >= 3

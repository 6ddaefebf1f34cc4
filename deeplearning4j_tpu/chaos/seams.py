"""The injectable-seam registry: every fault point in the system,
by name, with one arming protocol.

Until this PR each robustness feature shipped its own ad-hoc injector —
``fault_injection`` for NaN-gradient storms, ``host_dropout_injection``
for mesh loss, ``truncate_file`` for checkpoint corruption, bespoke
monkeypatching for hung dispatches. The registry unifies them: a seam
is a named, documented fault point with ``arm(spec, rng) → disarm``
semantics, and a :class:`~.plan.ChaosPlan` arms any combination of
them declaratively. Three seam kinds:

- **hook** seams delegate to :mod:`~.hooks` fire points living inside
  production code (the FS layer's write/fsync/replace/append, serving
  and decode dispatches, kernel probes, the registry validation score);
- **native** seams wrap the pre-existing deterministic injectors
  (``grad_nan``, ``host_dropout``) so the old drills become plan
  entries instead of special cases;
- **trigger** seams (``on_event``) subscribe to the flight recorder and
  run a named action when a matching event lands — how paired drills
  compose ("truncate the newest checkpoint WHEN ``mesh_shrink`` fires",
  i.e. corruption exactly during recovery).

Adding a seam when a new subsystem lands: put one
``chaos_hooks.fire("<subsystem>.<point>", **ctx)`` at the injectable
boundary, then ``register_hook_seam`` here with a docstring line —
the drill matrix and ``cli chaos --list`` pick it up automatically.
"""

from __future__ import annotations

import os
import random
from typing import Callable, Dict, List, Optional

from deeplearning4j_tpu.chaos import hooks


class Seam:
    """One named injectable fault point."""

    def __init__(self, name: str, subsystem: str, description: str,
                 kind: str, armer: Callable):
        self.name = name
        self.subsystem = subsystem
        self.description = description
        self.kind = kind  # hook | native | trigger
        self._armer = armer

    def arm(self, spec: dict, rng: random.Random) -> Callable[[], None]:
        """Arm this seam with ``spec`` (plan-entry dict minus the
        ``seam`` key); returns the disarm callable."""
        return self._armer(dict(spec), rng)

    def describe(self) -> dict:
        return {"seam": self.name, "subsystem": self.subsystem,
                "kind": self.kind, "description": self.description}


SEAMS: Dict[str, Seam] = {}


def register_seam(name: str, subsystem: str, description: str, kind: str,
                  armer: Callable) -> Seam:
    s = Seam(name, subsystem, description, kind, armer)
    SEAMS[name] = s
    return s


def list_seams() -> List[dict]:
    return [SEAMS[k].describe() for k in sorted(SEAMS)]


def get_seam(name: str) -> Seam:
    s = SEAMS.get(name)
    if s is None:
        raise ValueError(f"unknown seam {name!r} (known: "
                         f"{sorted(SEAMS)}); see cli chaos --list")
    return s


# --------------------------------------------------------------------------
# hook seams (fire points inside production code)
# --------------------------------------------------------------------------
def _hook_armer(point: str):
    def arm(spec: dict, rng: random.Random) -> Callable[[], None]:
        fs = hooks.FaultSpec(
            point,
            mode=spec.pop("mode", "error"),
            match=spec.pop("match", None),
            at_call=spec.pop("at_call", None),
            prob=spec.pop("prob", None),
            times=spec.pop("times", 1),
            delay_s=spec.pop("delay_s", 0.0),
            value=spec.pop("value", None),
            message=spec.pop("message", None),
            rng=rng)
        if spec:
            raise ValueError(f"unknown keys {sorted(spec)} for hook seam "
                             f"{point!r}")
        hooks.arm(fs)
        return lambda: hooks.disarm(fs)

    return arm


def register_hook_seam(point: str, subsystem: str, description: str) -> Seam:
    return register_seam(point, subsystem, description, "hook",
                         _hook_armer(point))


register_hook_seam(
    "fs.write", "storage",
    "staging-file create/copy for checkpoint zips, registry snapshot "
    "copies and JSON artifacts (modes: enospc, eio, error, delay)")
register_hook_seam(
    "fs.fsync", "storage",
    "fsync of a staged artifact or journal append (a durability "
    "barrier that fails on real disks)")
register_hook_seam(
    "fs.replace", "storage",
    "the atomic os.replace publish of checkpoints / registry snapshots "
    "/ tune metadata")
register_hook_seam(
    "fs.append", "storage",
    "durable journal append (registry + tune journals); mode 'torn' "
    "leaves half the line on disk — the SIGKILL-mid-append state")
register_hook_seam(
    "serving.batch_dispatch", "serving",
    "the batched inference dispatch inside make_dispatcher (modes: "
    "error = device failure, delay = slow dispatch)")
register_hook_seam(
    "registry.version_dispatch", "serving",
    "a _VersionedEngine forward, with model/version/role ctx — target "
    "exactly the canary's dispatches (match={'role': 'canary'})")
register_hook_seam(
    "serving.sharded_dispatch", "serving",
    "a tensor-parallel dispatch on the 2-D (batch, model) serving mesh "
    "(error = device subset lost mid-serve; the engine must fail typed "
    "and demote to solo)")
register_hook_seam(
    "generate.decode_dispatch", "generation",
    "the one in-flight jitted decode step (error = decode failure, "
    "delay past the watchdog limit = hung dispatch)")
register_hook_seam(
    "generate.prefix_cache", "generation",
    "a shared-prefix cache hit about to restore cached KV into a slot "
    "(mode 'error' = poisoned entry: the engine must drop it and fall "
    "back to a real prefill, bit-identically)")
register_hook_seam(
    "registry.validation_score", "deployment",
    "the held-out validation score at publish (mode 'value': override "
    "with value=NaN for the poisoned-snapshot drill)")
register_hook_seam(
    "kernel.probe", "kernels",
    "kernel availability probes (mode 'transient_compile' carries the "
    "compile-service-crash signature probe_with_retry retries on)")
register_hook_seam(
    "cluster.decision", "cluster",
    "a canary-controller decision about to be epoch-fence checked "
    "(mode 'delay' = the paused ex-holder: a peer steals the lease "
    "during the pause and the late decision must be refused typed)")
register_hook_seam(
    "controller.act", "loadgen",
    "an adaptive-capacity controller about to actuate its knob "
    "(controller/action ctx; mode 'error' = broken actuator — the "
    "ControllerHub must contain it and keep ticking)")
register_hook_seam(
    "data.shard_read", "data",
    "a record shard about to be opened + decoded by the input "
    "pipeline (mode 'torn' + match={'path_substr': …} = a specific "
    "shard torn mid-epoch — the loader must skip it typed; enospc/eio "
    "= the data volume failing under the reader)")


# --------------------------------------------------------------------------
# native seams (pre-existing deterministic injectors, unified)
# --------------------------------------------------------------------------
def _arm_grad_nan(spec: dict, rng: random.Random) -> Callable[[], None]:
    from deeplearning4j_tpu.train import faults

    steps = spec.get("at_iterations")
    if steps is None:
        raise ValueError("grad_nan seam needs at_iterations=[...]")
    prev = faults.set_fault_injection(steps)
    return lambda: faults.set_fault_injection(prev)


def _arm_host_dropout(spec: dict, rng: random.Random) -> Callable[[], None]:
    from deeplearning4j_tpu.train import faults

    prev = faults.set_host_dropout_injection(
        at_iteration=spec.get("at_iteration"),
        survivors=spec.get("survivors"))
    if prev is None:
        return lambda: faults.set_host_dropout_injection(None)
    return lambda: faults.set_host_dropout_injection(
        at_iteration=prev.get("at_iteration"),
        survivors=prev.get("survivors"))


register_seam(
    "grad_nan", "training",
    "NaN-gradient storm at the given host iterations (the PR-2 "
    "injector: at_iterations=[...])", "native", _arm_grad_nan)
register_seam(
    "host_dropout", "training",
    "one-shot injected mesh failure before at_iteration, leaving "
    "'survivors' devices (the PR-8 elastic drill injector)",
    "native", _arm_host_dropout)


# --------------------------------------------------------------------------
# trigger seam: run an action when a flight event lands
# --------------------------------------------------------------------------
def _action_truncate_newest_checkpoint(params: dict) -> None:
    from deeplearning4j_tpu.train import faults

    directory = params["dir"]
    files = faults.checkpoint_files(directory)
    if files:
        faults.truncate_file(files[-1], frac=float(params.get("frac", 0.5)))


def _action_truncate_file(params: dict) -> None:
    from deeplearning4j_tpu.train import faults

    if os.path.exists(params["path"]):
        faults.truncate_file(params["path"],
                             frac=float(params.get("frac", 0.5)))


#: named, JSON-addressable actions for the on_event seam
ACTIONS: Dict[str, Callable[[dict], None]] = {
    "truncate_newest_checkpoint": _action_truncate_newest_checkpoint,
    "truncate_file": _action_truncate_file,
}


def _arm_on_event(spec: dict, rng: random.Random) -> Callable[[], None]:
    from deeplearning4j_tpu.obs import flight as _flight

    event = spec.get("event")
    if not event:
        raise ValueError("on_event seam needs event=<flight event kind>")
    action_name = spec.get("action")
    action = spec.get("callback")  # test-only: a direct callable
    if action is None:
        if action_name not in ACTIONS:
            raise ValueError(f"unknown on_event action {action_name!r} "
                             f"(known: {sorted(ACTIONS)})")
        action = ACTIONS[action_name]
    times = spec.get("times", 1)
    state = {"fires": 0}

    def observer(ev: dict) -> None:
        if ev.get("kind") != event:
            return
        if times is not None and state["fires"] >= int(times):
            return
        state["fires"] += 1
        _flight.record("chaos_inject", point="on_event", mode="action",
                       event=event, action=str(action_name or "callback"))
        action(dict(spec))

    return _flight.default_flight_recorder().add_observer(observer)


register_seam(
    "on_event", "composition",
    "run an action when a flight event of the given kind lands — how "
    "paired drills compose faults (e.g. event='mesh_shrink', "
    "action='truncate_newest_checkpoint', dir=...)",
    "trigger", _arm_on_event)

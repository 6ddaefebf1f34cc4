"""The cross-cutting resilience contract, as executable checks.

Every drill, whatever seam it poked, must leave the system in a state
where ALL of these hold — this is the system-wide contract (arXiv
1605.08695 §4.3 treats fault handling as a property of the whole
system, not of the feature that first hit the fault):

- **Typed errors**: anything that reached a caller is from the typed
  taxonomy (StorageError, ServingError family, RegistryError family,
  TrainingDivergedError, ElasticRecoveryExhaustedError, …) — never a
  bare KeyError/AttributeError/IndexError leaking an implementation
  detail, and never a hang (drills run under deadlines).
- **Bit-parity where promised**: params + Adam slots bit-identical to
  the fault-free oracle on the paths whose design promises it (the
  NaN-skip ≡ batch-removed contract).
- **Ordered forensics**: the flight recorder's event stream contains
  the documented state-machine sequence as a subsequence
  (mesh_shrink → reshard_start → reshard_done → elastic_resume;
  publish → canary_start → regression_trip → rollback; …).
- **No torn artifacts**: no ``.tmp-`` staging litter survives, the
  newest checkpoint still validates, the registry/tune journals still
  replay.
- **Bounded recovery**: the drill completed (or failed typed) within
  its deadline.

Checks append to an :class:`InvariantReport`; a drill is green iff
every check passed and no silent-corruption finding was recorded.
"""

from __future__ import annotations

import math
import os
from typing import List, Optional, Sequence

_TMP_MARKER = ".tmp-"


def typed_error_bases() -> tuple:
    """The typed-error taxonomy — lazily imported so this module stays
    cheap to import."""
    from deeplearning4j_tpu.chaos.fslayer import StorageError
    from deeplearning4j_tpu.chaos.hooks import InjectedFaultError
    from deeplearning4j_tpu.data.shards import TornShardError
    from deeplearning4j_tpu.serving.batcher import ServingError
    from deeplearning4j_tpu.serving.registry import RegistryError
    from deeplearning4j_tpu.train.faults import (
        ElasticRecoveryExhaustedError,
        MeshFailureError,
        TrainingDivergedError,
    )

    return (StorageError, ServingError, RegistryError,
            TrainingDivergedError, ElasticRecoveryExhaustedError,
            MeshFailureError, InjectedFaultError, TornShardError,
            # deliberate caller-contract errors: a missing checkpoint
            # or an invalid argument is a typed verdict, not a leak
            FileNotFoundError, ValueError)


#: never acceptable at a caller: implementation details leaking
_BARE_LEAKS = (KeyError, AttributeError, IndexError, TypeError,
               ZeroDivisionError, UnboundLocalError)


class Check:
    __slots__ = ("name", "ok", "detail")

    def __init__(self, name: str, ok: bool, detail: str = ""):
        self.name = name
        self.ok = bool(ok)
        self.detail = detail

    def to_dict(self) -> dict:
        return {"name": self.name, "ok": self.ok, "detail": self.detail}


class InvariantReport:
    def __init__(self):
        self.checks: List[Check] = []

    def add(self, name: str, ok: bool, detail: str = "") -> bool:
        self.checks.append(Check(name, ok, detail))
        return bool(ok)

    @property
    def ok(self) -> bool:
        return all(c.ok for c in self.checks)

    def failures(self) -> List[Check]:
        return [c for c in self.checks if not c.ok]

    def to_dict(self) -> List[dict]:
        return [c.to_dict() for c in self.checks]

    def __repr__(self):
        n_bad = len(self.failures())
        return (f"InvariantReport({len(self.checks)} checks, "
                f"{n_bad} failed)")


# --------------------------------------------------------------------------
# the checks
# --------------------------------------------------------------------------
def check_typed_errors(report: InvariantReport,
                       errors: Sequence[BaseException],
                       name: str = "typed_errors") -> bool:
    """Every captured caller-visible error is from the typed taxonomy;
    ValueError subclasses are fine, bare KeyError/AttributeError/… are
    leaks. KeyError needs special care: UnknownModelError deliberately
    subclasses it for dict-compat, so the taxonomy check runs FIRST."""
    bases = typed_error_bases()
    bad = []
    for e in errors:
        if isinstance(e, bases):
            continue
        if isinstance(e, _BARE_LEAKS):
            bad.append(f"{type(e).__name__}: {e}")
            continue
        bad.append(f"untyped {type(e).__name__}: {e}")
    return report.add(name, not bad, "; ".join(bad[:5]))


def check_no_tmp_litter(report: InvariantReport, *directories: str,
                        name: str = "no_tmp_litter") -> bool:
    """No ``.tmp-`` staging file survived anywhere under the drill's
    artifact directories — a failed atomic write must clean up."""
    litter = []
    for d in directories:
        if not os.path.isdir(d):
            continue
        for root, _dirs, files in os.walk(d):
            litter.extend(os.path.join(root, f) for f in files
                          if _TMP_MARKER in f)
    return report.add(name, not litter, "; ".join(litter[:5]))


def check_event_order(report: InvariantReport, events: Sequence[dict],
                      expected: Sequence[str],
                      name: str = "event_order") -> bool:
    """``expected`` event kinds appear in the stream in order (as a
    subsequence — other events may interleave)."""
    kinds = [e.get("kind") for e in events]
    i = 0
    for k in kinds:
        if i < len(expected) and k == expected[i]:
            i += 1
    return report.add(
        name, i == len(expected),
        "" if i == len(expected) else
        f"matched {expected[:i]} but not {expected[i]!r} in {kinds}")


def check_params_match(report: InvariantReport, model_a, model_b,
                       name: str = "params_match") -> bool:
    """params AND optimizer slots equal — the fault-free-oracle promise
    (NaN-skip ≡ batch-removed, resumed ≡ uninterrupted). The two runs
    are two compiled programs (one carries the poison ``where``), and a
    compiler may contract a multiply-add into one rounding in one and
    two in the other, so equal means within one float32 ulp of the
    largest element; a skipped or doubled step is orders above that."""
    import numpy as np

    def same(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape:
            return False
        ulp = np.spacing(np.float32(np.max(np.abs(b)))) if b.size else 0.0
        return bool(np.all(np.abs(a - b) <= ulp))  # NaN compares False

    ok = same(model_a.params_flat(), model_b.params_flat())
    detail = "" if ok else "params differ"
    if ok and model_a.opt_state_ is not None and model_b.opt_state_ is not None:
        ok = same(model_a.opt_state_flat(), model_b.opt_state_flat())
        detail = "" if ok else "optimizer slots differ"
    return report.add(name, ok, detail)


def check_params_finite(report: InvariantReport, model,
                        name: str = "params_finite") -> bool:
    import numpy as np

    ok = bool(np.all(np.isfinite(np.asarray(model.params_flat()))))
    return report.add(name, ok, "" if ok else "non-finite parameters")


def check_checkpoint_loadable(report: InvariantReport, directory: str,
                              name: str = "checkpoint_loadable") -> bool:
    """The newest VALID checkpoint restores — corruption never leaves
    the directory unserviceable."""
    from deeplearning4j_tpu.train import faults

    import numpy as np

    try:
        model, path = faults.load_latest_valid(directory)
    except Exception as e:  # noqa: BLE001 — the verdict IS the check
        return report.add(name, False, f"{type(e).__name__}: {e}")
    ok = bool(np.all(np.isfinite(np.asarray(model.params_flat()))))
    return report.add(name, ok,
                      os.path.basename(path) if ok
                      else f"{path}: non-finite parameters")


def check_registry_consistent(report: InvariantReport, directory: str,
                              expect_active: Optional[dict] = None,
                              name: str = "registry_consistent") -> bool:
    """A fresh process can replay the registry journal, and (when
    given) each model resolves to the expected active version whose
    snapshot file still validates."""
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.train.faults import is_valid_checkpoint

    try:
        reg = ModelRegistry(directory)
    except Exception as e:  # noqa: BLE001 — the verdict IS the check
        return report.add(name, False,
                          f"replay failed: {type(e).__name__}: {e}")
    for model_name, version in (expect_active or {}).items():
        try:
            vrec = reg.resolve(model_name)
        except Exception as e:  # noqa: BLE001
            return report.add(name, False,
                              f"{model_name}: {type(e).__name__}: {e}")
        if int(vrec["version"]) != int(version):
            return report.add(
                name, False, f"{model_name}: active v{vrec['version']} "
                f"!= expected v{version}")
        if not is_valid_checkpoint(vrec["path"]):
            return report.add(name, False,
                              f"{model_name}: active snapshot corrupt")
    return report.add(name, True)


def check_tune_store_replayable(report: InvariantReport, directory: str,
                                name: str = "tune_store_replayable"
                                ) -> bool:
    from deeplearning4j_tpu.tune.store import TrialStore

    try:
        trials, _records = TrialStore(directory).reconstruct()
    except Exception as e:  # noqa: BLE001 — the verdict IS the check
        return report.add(name, False, f"{type(e).__name__}: {e}")
    return report.add(name, True, f"{len(trials)} trials")


def check_expected_alerts(report: InvariantReport,
                          fired: Sequence[str],
                          expected: Sequence[str],
                          name: str = "expected_alerts_fired") -> bool:
    """Every alert the drill claims covers its fault actually FIRED in
    the drill's alert evaluator (obs/alerts.py over the flight ring) —
    the detection half of the resilience contract: the matrix proves
    not just that the system recovers, but that an operator would have
    been told."""
    missing = [a for a in expected if a not in set(fired)]
    return report.add(
        name, not missing,
        "" if not missing else
        f"expected alert(s) {missing} never fired (fired: "
        f"{sorted(fired)})")


def check_deadline(report: InvariantReport, elapsed_s: float,
                   limit_s: float, name: str = "recovery_deadline") -> bool:
    ok = math.isfinite(elapsed_s) and elapsed_s <= limit_s
    return report.add(name, ok,
                      f"{elapsed_s:.2f}s vs limit {limit_s:.2f}s")

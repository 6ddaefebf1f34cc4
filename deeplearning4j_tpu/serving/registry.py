"""Model registry + canary router: the safe train→serve bridge.

The stack already has both halves of a continuous deployment loop —
crash-safe checkpoints on the training side (train/faults.py) and
atomic zero-recompile hot reload on the serving side (serving/engine.py)
— but until now no safe bridge between them: a long ``fit()`` could not
ship snapshots to live traffic without a human, and a bad snapshot
(NaN-poisoned, regressed) that reached ``/reload`` replaced the good one
for 100% of traffic. This module is that bridge, the 1605.08695
train-and-serve pairing taken to its conclusion:

- :class:`ModelRegistry` — a crash-safe store of named models with
  versioned snapshots. Durability mirrors ``tune/store.py`` exactly:
  an append-only fsync'd ``journal.jsonl`` is the source of truth (a
  SIGKILL can lose at most the in-flight line; a torn TRAILING line is
  dropped on replay, a torn middle line refuses), and ``registry.json``
  is an atomically-replaced (tmp + ``os.replace``) snapshot for humans
  and tooling — a crash between journal append and snapshot replace
  loses nothing, the restart replays the journal. Published snapshots
  are COPIED into the registry (``snapshots/<model>/v####.zip``) so a
  trainer's keep-last-k pruning can never delete a version that is
  still serving.

- **Validation-gated publish** — every :meth:`ModelRegistry.publish`
  carries a held-out validation score. A non-finite score (the
  NaN-poisoned snapshot) or a score regressed beyond
  ``regression_tolerance`` against the best validated version is
  REFUSED with a typed :class:`SnapshotValidationError` — journaled as
  ``rejected``, recorded as a ``publish_refused`` flight event, and
  never eligible for activation or canary traffic.

- :class:`ModelRouter` — the multi-model serving front-end the HTTP
  server mounts: routes requests by model name across multiple warmed
  engines (each model keeps its own :class:`InferenceEngine` + batcher,
  so the 1810.09868 fixed-shape zero-recompile discipline holds per
  model), enforces per-tenant queue quotas (typed
  :class:`TenantQuotaExceededError` — one noisy tenant gets 503s, the
  others are untouched), evicts cold models LRU (``model_evict`` /
  ``model_rewarm`` flight events), and runs the **canary state
  machine**:

  ``publish → validate → canary_start → promote | regression_trip →
  rollback``

  A newly validated version never takes 100% of traffic: the router
  builds and warms a SEPARATE engine for it, routes ``canary_fraction``
  of the model's requests there for a bounded ``canary_window_s``, and
  watches per-version error/latency/score counters. A clean window
  auto-promotes (the canary engine becomes the active one — already
  warm, zero recompiles, and the old active batcher drains so in-flight
  old-version requests all complete, PR 3's no-mixing guarantee
  extended to versioned routing). Any canary dispatch failure, a
  latency blow-up, or a regressed score trips ``regression_trip`` →
  ``rollback``: outstanding canary requests are failed typed
  first-wins BEFORE their results could reach a caller, the canary
  engine is retired, and the active version keeps serving untouched.
  Every transition lands in the journal AND the flight recorder, so
  ``cli flight-dump`` renders the whole deployment timeline.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
import warnings
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional

from deeplearning4j_tpu.obs.lockwitness import (
    witnessed_lock,
    witnessed_rlock,
)
from deeplearning4j_tpu.serving.batcher import (
    DynamicBatcher,
    RequestDeadlineExceeded,
    ServerOverloadedError,
    ServingError,
    make_dispatcher,
)
from deeplearning4j_tpu.serving.buckets import BucketPolicy
from deeplearning4j_tpu.serving.metrics import ServingMetrics

JOURNAL_NAME = "journal.jsonl"
SNAPSHOT_NAME = "registry.json"
SNAPSHOTS_SUBDIR = "snapshots"
SCHEMA_VERSION = 1


class RegistryError(RuntimeError):
    """Base of the typed registry failures."""


class SnapshotValidationError(RegistryError):
    """A published snapshot was refused by the validation gate
    (non-finite held-out score, or regressed beyond the tolerance
    against the best validated version). The snapshot is journaled as
    ``rejected`` and can never be activated or canaried."""


class UnknownModelError(RegistryError, KeyError):
    """Request named a model the registry does not hold (HTTP 404)."""

    def __str__(self):  # KeyError.__str__ repr-quotes; keep it readable
        return self.args[0] if self.args else ""


class TenantQuotaExceededError(ServerOverloadedError):
    """One tenant exceeded its per-tenant queue quota — 503 for THAT
    tenant only; other tenants' admission is untouched (a global
    :class:`ServerOverloadedError` would let one noisy tenant starve
    everyone)."""

    def __init__(self, message: str, tenant: str,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.tenant = tenant
        if retry_after_s is not None:
            self.retry_after_s = retry_after_s


class CanaryRolledBackError(ServingError):
    """The request was routed to a canary version that regressed and
    rolled back before the result could be returned. Retryable — the
    active version is serving (HTTP 503)."""


def _now() -> float:
    return time.time()


# --------------------------------------------------------------------------
# the crash-safe registry store
# --------------------------------------------------------------------------
class ModelRegistry:
    """Named models → versioned snapshots, durable across SIGKILL.

    Thread-safe (one RLock) and multi-process friendly: a trainer
    publishing and a server canarying can share one registry directory —
    both append whole fsync'd lines to the journal (O_APPEND), and
    :meth:`refresh` folds in lines another process appended. The journal
    is the source of truth; ``registry.json`` is a convenience snapshot
    rewritten atomically after every append.
    """

    def __init__(self, directory: str, regression_tolerance: float = 0.0,
                 higher_is_better: bool = False,
                 keep_last: Optional[int] = None,
                 refresh_min_interval_s: float = 0.0):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.journal_path = os.path.join(self.directory, JOURNAL_NAME)
        self.snapshot_path = os.path.join(self.directory, SNAPSHOT_NAME)
        #: a new score may be worse than the best validated one by this
        #: relative fraction before the publish gate refuses it
        self.regression_tolerance = float(regression_tolerance)
        self.higher_is_better = bool(higher_is_better)
        #: snapshots retained per model beyond the referenced set
        #: (active / canary / newest validated are never pruned)
        self.keep_last = None if keep_last is None else int(keep_last)
        #: min seconds between :meth:`refresh` stat checks (0 = stat on
        #: every call, the original behavior). A deployment with many
        #: co-located readers raises it; the CLUSTER layer bypasses it
        #: (``refresh(force=True)``) while a canary window is open —
        #: cross-replica rollback latency is bounded by this cadence
        self.refresh_min_interval_s = float(refresh_min_interval_s)
        self._next_refresh_check = 0.0  # monotonic deadline
        self._lock = witnessed_rlock("registry.store")
        self._models: Dict[str, dict] = {}
        self._journal_bytes = 0
        from deeplearning4j_tpu.train.faults import sweep_stale_tmp

        # orphaned staging files from a PRIOR crashed atomic write
        # (snapshot copies, registry.json stages) are swept — and
        # counted in a tmp_sweep flight event — on registry-dir open
        sweep_stale_tmp(self.directory, surface="registry",
                        recursive=True)
        self._load()

    # -- journal / snapshot durability --------------------------------------
    def _append(self, record: dict) -> None:
        """Journal first (fsync'd — the WAL), snapshot second (atomic
        replace). A SIGKILL between the two loses nothing: restart
        replays the journal past the stale snapshot. The record is
        folded into in-memory state only AFTER the journal append
        durably lands — a failed append (disk full: typed StorageError
        out of the fs layer) leaves memory and disk agreeing on the
        pre-append state (at worst disk holds a torn trailing line,
        which replay drops)."""
        from deeplearning4j_tpu.chaos import fslayer as _fs

        with self._lock:
            line = json.dumps(record, sort_keys=True) + "\n"
            _fs.append_line(self.journal_path, line,
                            surface="registry_journal")
            self._fold(record)
            # track the bytes WE have folded, not the file size: the
            # file may already contain another process's un-folded
            # lines (O_APPEND interleaving), and absorbing them into
            # the counter here would make refresh() skip them forever
            self._journal_bytes += len(line.encode())
            try:
                self._write_snapshot()
            except _fs.StorageError as e:
                # the journal (the WAL) committed; registry.json is a
                # convenience mirror — a failed rewrite degrades, never
                # un-publishes (the next successful append refreshes it)
                warnings.warn(f"registry snapshot write failed "
                              f"(journal is authoritative): {e}",
                              stacklevel=2)

    def _write_snapshot(self) -> None:
        from deeplearning4j_tpu.chaos import fslayer as _fs

        body = {"schema_version": SCHEMA_VERSION, "written_at": _now(),
                "models": self._models}
        _fs.write_atomic(self.snapshot_path,
                         json.dumps(body, indent=1, sort_keys=True),
                         surface="registry_snapshot")

    def _replay(self) -> List[dict]:
        """Journal records in append order — the tune/store.py torn-line
        semantics: a torn FINAL line (what a SIGKILL mid-append leaves)
        is dropped with a warning, a torn line with valid records after
        it is external corruption and refuses."""
        if not os.path.exists(self.journal_path):
            return []
        out: List[dict] = []
        torn_at: Optional[int] = None
        with open(self.journal_path) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    torn_at = i
                    continue
                if torn_at is not None:
                    raise RegistryError(
                        f"{self.journal_path}:{torn_at + 1}: corrupt journal "
                        "line with valid records after it — not crash "
                        "truncation; refusing to replay")
                out.append(rec)
        if torn_at is not None:
            warnings.warn(
                f"{self.journal_path}: dropping torn trailing line "
                f"{torn_at + 1} (crash mid-append)", stacklevel=2)
        return out

    def _load(self) -> None:
        with self._lock:
            self._models = {}
            records = self._replay()
            if records:
                for rec in records:
                    self._fold(rec)
            elif os.path.exists(self.snapshot_path):
                # journal gone but a snapshot survives (hand-seeded or
                # archived registry): adopt it as the starting state
                with open(self.snapshot_path) as f:
                    self._models = json.load(f).get("models", {})
            self._journal_bytes = (os.path.getsize(self.journal_path)
                                   if os.path.exists(self.journal_path)
                                   else 0)

    def refresh(self, force: bool = False) -> bool:
        """Fold in journal lines another process appended since the last
        load (the serving router polls this to notice a trainer's
        publishes). Returns True when state changed. Cheap when nothing
        changed: one stat — and, with ``refresh_min_interval_s`` set,
        not even that until the throttle window elapses. ``force=True``
        bypasses the throttle (the cluster layer's canary-window
        tightening)."""
        with self._lock:
            now = time.monotonic()
            if not force and now < self._next_refresh_check:
                return False
            self._next_refresh_check = now + self.refresh_min_interval_s
            size = (os.path.getsize(self.journal_path)
                    if os.path.exists(self.journal_path) else 0)
            if size == self._journal_bytes:
                return False
            # full re-replay: the journal is small (one line per
            # deployment event, not per request) and replay is the one
            # code path crash-recovery already trusts
            self._load()
            return True

    # -- folding (journal record → state machine) ----------------------------
    def _model(self, name: str) -> dict:
        m = self._models.get(name)
        if m is None:
            m = {"name": name, "active_version": None, "canary": None,
                 "next_version": 1, "bucket_policy": None, "versions": {}}
            self._models[name] = m
        return m

    def _fold(self, rec: dict) -> None:
        kind = rec.get("kind")
        if kind == "model":
            m = self._model(rec["name"])
            if rec.get("bucket_policy") is not None:
                m["bucket_policy"] = rec["bucket_policy"]
            return
        m = self._model(rec["name"])
        v = str(rec["version"]) if "version" in rec else None
        if kind == "publish":
            m["versions"][v] = {
                "version": int(rec["version"]),
                "path": rec["path"],
                "fingerprint": rec.get("fingerprint"),
                "source": rec.get("source"),
                "published_at": rec.get("ts"),
                "iteration": rec.get("iteration"),
                "validation": None,
                "status": "published",
            }
            m["next_version"] = max(m["next_version"],
                                    int(rec["version"]) + 1)
        elif kind == "validated":
            vr = m["versions"].get(v)
            if vr is not None:
                vr["validation"] = {"ok": True, "score": rec.get("score"),
                                    "baseline": rec.get("baseline")}
                vr["status"] = "validated"
        elif kind == "rejected":
            vr = m["versions"].get(v)
            if vr is not None:
                vr["validation"] = {"ok": False, "score": rec.get("score"),
                                    "reason": rec.get("reason")}
                vr["status"] = "rejected"
        elif kind == "activate" or kind == "promote":
            old = m.get("active_version")
            if old is not None and str(old) in m["versions"] \
                    and int(old) != int(rec["version"]):
                m["versions"][str(old)]["status"] = "retired"
            m["active_version"] = int(rec["version"])
            if v in m["versions"]:
                m["versions"][v]["status"] = "active"
            if m.get("canary") and int(m["canary"]["version"]) == int(
                    rec["version"]):
                m["canary"] = None
        elif kind == "canary_start":
            m["canary"] = {"version": int(rec["version"]),
                           "fraction": rec.get("fraction"),
                           "window_s": rec.get("window_s"),
                           "started_at": rec.get("ts")}
            if v in m["versions"]:
                m["versions"][v]["status"] = "canary"
        elif kind == "rollback":
            if m.get("canary") and int(m["canary"]["version"]) == int(
                    rec["version"]):
                m["canary"] = None
            if v in m["versions"]:
                m["versions"][v]["status"] = "rolled_back"
        elif kind == "prune":
            m["versions"].pop(v, None)

    # -- reads ---------------------------------------------------------------
    def models(self) -> List[str]:
        with self._lock:
            return sorted(self._models)

    def get(self, name: str) -> dict:
        with self._lock:
            m = self._models.get(name)
            if m is None:
                raise UnknownModelError(
                    f"model {name!r} is not in the registry "
                    f"(have: {sorted(self._models)})")
            return json.loads(json.dumps(m))  # defensive deep copy

    def describe(self) -> dict:
        with self._lock:
            return {"directory": self.directory,
                    "models": json.loads(json.dumps(self._models))}

    def resolve(self, name: str) -> dict:
        """The ACTIVE version record for ``name`` — what a restarted
        server serves. Raises typed when the model has no activated
        (validated) version yet."""
        m = self.get(name)
        av = m.get("active_version")
        if av is None:
            raise UnknownModelError(
                f"model {name!r} has no active version (publish + "
                "validation must succeed at least once)")
        return m["versions"][str(av)]

    def candidate(self, name: str) -> Optional[dict]:
        """Newest VALIDATED version newer than the active one (the one a
        router should canary), or None."""
        with self._lock:
            m = self._models.get(name)
            if m is None:
                return None
            av = m.get("active_version") or 0
            cands = [vr for vr in m["versions"].values()
                     if vr["version"] > av and vr["status"] == "validated"]
            return (dict(max(cands, key=lambda vr: vr["version"]))
                    if cands else None)

    def canary_state(self, name: str) -> Optional[dict]:
        with self._lock:
            m = self._models.get(name)
            return None if m is None else (
                None if m.get("canary") is None else dict(m["canary"]))

    def best_score(self, name: str) -> Optional[float]:
        """Best validated score across the model's versions (direction
        aware) — the baseline the publish regression gate compares new
        snapshots against."""
        with self._lock:
            m = self._models.get(name)
            if m is None:
                return None
            scores = [vr["validation"]["score"]
                      for vr in m["versions"].values()
                      if vr.get("validation") and vr["validation"]["ok"]
                      and vr["validation"].get("score") is not None
                      and vr["status"] != "rolled_back"]
            if not scores:
                return None
            return max(scores) if self.higher_is_better else min(scores)

    def bucket_policy(self, name: str) -> Optional[BucketPolicy]:
        with self._lock:
            m = self._models.get(name)
            bp = None if m is None else m.get("bucket_policy")
        if bp is None:
            return None
        return BucketPolicy(batch_buckets=bp.get("batch_buckets"),
                            max_batch=bp.get("max_batch"),
                            seq_buckets=bp.get("seq_buckets"))

    # -- writes --------------------------------------------------------------
    def define_model(self, name: str,
                     bucket_policy: Optional[dict] = None) -> None:
        """Idempotently declare a model (optionally with its serving
        bucket policy: ``{"batch_buckets": [...], "max_batch": n,
        "seq_buckets": [...]}``)."""
        with self._lock:
            existing = self._models.get(name)
            if existing is not None and (
                    bucket_policy is None
                    or existing.get("bucket_policy") == bucket_policy):
                return
            self._append({"kind": "model", "name": name, "ts": _now(),
                          "bucket_policy": bucket_policy})

    def _snapshot_dest(self, name: str, version: int) -> str:
        d = os.path.join(self.directory, SNAPSHOTS_SUBDIR, name)
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"v{version:04d}.zip")

    def publish(self, name: str, source: str, score: Optional[float] = None,
                iteration: Optional[int] = None,
                allow_unvalidated: bool = False) -> dict:
        """Publish a checkpoint as the next version of ``name``.

        ``source`` is a checkpoint zip or directory; it resolves through
        the serving checkpoint-fallback path (a truncated newest zip
        falls back to its newest valid sibling, with a
        ``checkpoint_fallback`` flight event naming the skipped path and
        error class), then the file is COPIED into the registry
        atomically — the registry owns its snapshots, a trainer's
        retention pruning cannot unpublish one.

        ``score`` is the held-out validation verdict. The gate refuses
        (typed :class:`SnapshotValidationError`, journaled ``rejected``,
        ``publish_refused`` flight event) when the score is non-finite
        or regressed beyond ``regression_tolerance`` against the best
        validated version. ``allow_unvalidated=True`` skips the gate
        (score may be None) — the version lands as ``published`` /
        ``validated``-without-score and the serving-side canary gate is
        the only line of defense; use it for score-free models, never to
        silence a refusal.

        The first validated version of a model auto-activates (there is
        no baseline to canary against); later ones wait for a router to
        canary them.
        """
        from deeplearning4j_tpu.chaos import fslayer as _fs
        from deeplearning4j_tpu.chaos import hooks as _chaos
        from deeplearning4j_tpu.obs import flight as _flight
        from deeplearning4j_tpu.serving.engine import (
            resolve_checkpoint_source,
        )
        from deeplearning4j_tpu.train.faults import (
            atomic_tmp_path,
            checkpoint_fingerprint,
        )

        path = resolve_checkpoint_source(source)
        # chaos seam: the held-out validation verdict (mode 'value'
        # overrides the score — the NaN-poisoned-snapshot drill)
        _score_spec = _chaos.fire("registry.validation_score", model=name)
        if _score_spec is not None and _score_spec.mode == "value":
            score = _score_spec.value
        # stage the copy OUTSIDE the lock: a multi-GB checkpoint copy
        # must not block every registry read (and, through refresh(),
        # every co-located serving submission) for its duration — only
        # the version assignment and the rename need the lock. Disk-full
        # here (fs layer, injectable) is a typed StorageError with the
        # staging file cleaned and the live registry untouched.
        stage_dir = os.path.join(self.directory, SNAPSHOTS_SUBDIR, name)
        os.makedirs(stage_dir, exist_ok=True)
        tmp = atomic_tmp_path(os.path.join(stage_dir, "incoming.zip"))
        try:
            _fs.copy_file(path, tmp, surface="registry_publish")
        except BaseException:
            if os.path.exists(tmp):
                os.remove(tmp)
            raise
        with self._lock:
            # read the next version WITHOUT creating the model entry:
            # in-memory state must only change when the WAL append
            # commits (a first-publish whose append fails must not
            # leave a phantom model that a restart would not replay)
            existing = self._models.get(name)
            version = (int(existing["next_version"])
                       if existing is not None else 1)
            dest = self._snapshot_dest(name, version)
            try:
                _fs.replace(tmp, dest, surface="registry_publish")
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
            fp = checkpoint_fingerprint(dest)
            baseline = self.best_score(name)
            try:
                self._append({"kind": "publish", "name": name,
                              "version": version, "path": dest,
                              "fingerprint": list(fp), "source": str(path),
                              "iteration": iteration, "ts": _now()})
            except _fs.StorageError:
                # the WAL append failed: nothing was folded, so the
                # copied snapshot would be an orphan the journal never
                # names — remove it and surface the typed error (the
                # previously active version keeps serving)
                try:
                    os.remove(dest)
                except OSError:
                    pass
                raise
            m = self._models[name]  # created by the committed fold
            _flight.record("publish", model=name, version=version,
                           source=str(path),
                           score=None if score is None else float(score))
            refusal = self._gate(name, score, baseline, allow_unvalidated)
            if refusal is not None:
                self._append({"kind": "rejected", "name": name,
                              "version": version, "reason": refusal,
                              "score": None if score is None
                              else float(score), "ts": _now()})
                _flight.record("publish_refused", model=name,
                               version=version, reason=refusal,
                               score=None if score is None
                               else float(score))
                # a rejected snapshot can never be activated — keeping
                # its bytes would grow the registry by one checkpoint
                # per refused publish (a long fit whose baseline was a
                # lucky early epoch refuses every later one)
                try:
                    os.remove(dest)
                except OSError:
                    pass
                raise SnapshotValidationError(
                    f"{name} v{version}: {refusal} — snapshot refused, "
                    "never activated (the live version keeps serving)")
            self._append({"kind": "validated", "name": name,
                          "version": version,
                          "score": None if score is None else float(score),
                          "baseline": baseline, "ts": _now()})
            _flight.record("validated", model=name, version=version,
                           score=None if score is None else float(score),
                           baseline=baseline)
            if m.get("active_version") is None:
                self.activate(name, version)
            self._prune(name)
            return dict(m["versions"][str(version)])

    def _gate(self, name: str, score: Optional[float],
              baseline: Optional[float], allow_unvalidated: bool
              ) -> Optional[str]:
        """The validation verdict: None = pass, else the refusal reason."""
        if score is None:
            return (None if allow_unvalidated
                    else "no validation score supplied (pass score=..., or "
                         "allow_unvalidated=True for score-free models)")
        score = float(score)
        if not math.isfinite(score):
            return f"non-finite validation score ({score})"
        if allow_unvalidated or baseline is None:
            return None
        tol = self.regression_tolerance * max(abs(baseline), 1e-12)
        if self.higher_is_better:
            regressed = score < baseline - tol
        else:
            regressed = score > baseline + tol
        if regressed:
            return (f"validation score {score:.6g} regressed vs best "
                    f"validated {baseline:.6g} "
                    f"(tolerance {self.regression_tolerance:g})")
        return None

    def activate(self, name: str, version: int) -> None:
        """Make ``version`` the active one (the first-version bootstrap
        and the explicit-operator override; routed promotion goes
        through :meth:`promote`)."""
        with self._lock:
            vr = self.get(name)["versions"].get(str(int(version)))
            if vr is None:
                raise RegistryError(f"{name} has no version {version}")
            if vr["status"] == "rejected":
                raise SnapshotValidationError(
                    f"{name} v{version} was refused by validation; "
                    "it cannot be activated")
            self._append({"kind": "activate", "name": name,
                          "version": int(version), "ts": _now()})

    def start_canary(self, name: str, version: int, fraction: float,
                     window_s: float) -> None:
        with self._lock:
            self._append({"kind": "canary_start", "name": name,
                          "version": int(version),
                          "fraction": float(fraction),
                          "window_s": float(window_s), "ts": _now()})

    def promote(self, name: str, version: int) -> None:
        with self._lock:
            self._append({"kind": "promote", "name": name,
                          "version": int(version), "ts": _now()})

    def rollback(self, name: str, version: int, reason: str) -> None:
        with self._lock:
            self._append({"kind": "rollback", "name": name,
                          "version": int(version), "reason": str(reason),
                          "ts": _now()})

    def _prune(self, name: str) -> None:
        """keep-last-k snapshot retention: never the active, canary, or
        newest-validated version; journal history is kept (cheap)."""
        if self.keep_last is None:
            return
        m = self._models[name]
        keep = {m.get("active_version")}
        if m.get("canary"):
            keep.add(m["canary"]["version"])
        cand = self.candidate(name)
        if cand is not None:
            keep.add(cand["version"])
        versions = sorted(int(v) for v in m["versions"])
        disposable = [v for v in versions if v not in keep]
        for v in disposable[:max(len(disposable) - self.keep_last, 0)]:
            vr = m["versions"][str(v)]
            try:
                if os.path.exists(vr["path"]):
                    os.remove(vr["path"])
            except OSError:
                continue
            self._append({"kind": "prune", "name": name, "version": v,
                          "ts": _now()})


# --------------------------------------------------------------------------
# per-version serving state (engine + batcher + counters)
# --------------------------------------------------------------------------
class _VersionStats:
    """Per-version serving counters — the canary metric gate's inputs.
    Mirrored into the shared metrics registry as labeled families.
    Generation traffic keeps its own error/latency columns: a decode
    request holds a slot for hundreds of tokens, so folding its wall
    time into the /predict mean would poison the latency comparison —
    the gate compares generation to generation."""

    __slots__ = ("requests", "errors", "latency_sum", "score", "_n_scores",
                 "gen_requests", "gen_errors", "gen_latency_sum")

    def __init__(self):
        self.requests = 0
        self.errors = 0
        self.latency_sum = 0.0
        self.score: Optional[float] = None
        self._n_scores = 0
        self.gen_requests = 0
        self.gen_errors = 0
        self.gen_latency_sum = 0.0

    def mean_latency(self) -> Optional[float]:
        return self.latency_sum / self.requests if self.requests else None

    def mean_gen_latency(self) -> Optional[float]:
        return (self.gen_latency_sum / self.gen_requests
                if self.gen_requests else None)

    def observe_score(self, value: float) -> None:
        # running mean: scores arrive from probes / external evaluators
        self._n_scores += 1
        prev = self.score if self.score is not None else 0.0
        self.score = prev + (float(value) - prev) / self._n_scores


class _VersionedEngine:
    """One live (engine, batcher) pair pinned to one registry version.
    Requests submitted here are computed entirely by this version —
    per-version batchers are what make "a batch is one version" true by
    construction, even while a canary runs next to the active."""

    def __init__(self, router: "ModelRouter", name: str, vrec: dict,
                 role: str):
        self.router = router
        self.name = name
        self.version = int(vrec["version"])
        self.record = dict(vrec)
        self.role = role  # "active" | "canary"
        self.dead = False
        self.stats = _VersionStats()
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        policy = router.registry.bucket_policy(name)
        kwargs = dict(metrics=router.metrics)
        if policy is not None:
            kwargs["buckets"] = policy
        engine_cls = InferenceEngine
        if router.mesh is not None:
            kwargs["mesh"] = router.mesh
            if getattr(router.mesh, "n_model", 1) > 1:
                # a 2-D (batch, model) ServingMesh serves every version
                # — active and canary alike — tensor-parallel; the
                # canary state machine neither knows nor cares (a
                # sharded candidate's dispatch failure trips the same
                # rollback as any other)
                from deeplearning4j_tpu.serving.sharded import (
                    ShardedInferenceEngine,
                )

                engine_cls = ShardedInferenceEngine
        self.engine = engine_cls.from_checkpoint(vrec["path"], **kwargs)
        shape = self.engine.example_shape()
        if shape is not None:
            # warm BEFORE any traffic: canary traffic must never absorb
            # the new version's compiles (PR 3's reload discipline)
            self.engine.warmup(shape)
        self.batcher = DynamicBatcher(
            make_dispatcher(self._infer, metrics=router.metrics,
                            traces=router.traces),
            batch_limit=router.batch_limit,
            max_wait_ms=router.max_wait_ms,
            queue_limit=router.queue_limit, metrics=router.metrics,
            trace_requests=router.trace_requests)

    def _infer(self, x, mask=None):
        from deeplearning4j_tpu.chaos import hooks as _chaos

        t0 = time.monotonic()
        try:
            # chaos seam with deployment identity: drills target exactly
            # the canary's dispatches via match={"role": "canary"}
            _chaos.fire("registry.version_dispatch", model=self.name,
                        version=self.version, role=self.role)
            out, _snap_version = self.engine.infer_versioned(x, mask)
        except BaseException as e:
            self.stats.errors += 1
            self.router._counter("registry_version_errors_total",
                                 self.name, self.version).inc()
            if self.role == "canary":
                # ANY canary dispatch failure trips the rollback — the
                # bad version must not get a second chance at traffic
                self.router._trip(self.name, self,
                                  f"dispatch failure: {type(e).__name__}")
            raise
        if self.dead:
            # rolled back while this batch was in flight: fail instead
            # of finish, so no result computed by the bad version
            # reaches a caller after regression_trip
            raise CanaryRolledBackError(
                f"{self.name} v{self.version} rolled back mid-dispatch")
        dt = time.monotonic() - t0
        self.stats.requests += 1
        self.stats.latency_sum += dt
        self.router._counter("registry_version_requests_total",
                             self.name, self.version).inc()
        self.router._counter("registry_version_latency_seconds_total",
                             self.name, self.version).inc(dt)
        if self.role == "canary":
            self.router._evaluate_canary(self.name)
        # requests carry the REGISTRY version (the deployment-level
        # identity), not the engine's internal snapshot generation
        return out, self.version

    def retire(self, drain: bool) -> None:
        """Shut the batcher down off-thread: retire() is called from
        batcher worker threads (a canary completion promoting, a canary
        dispatch failure tripping) and DynamicBatcher.shutdown joins the
        worker — a same-thread join would deadlock."""
        self.dead = True
        threading.Thread(target=self.batcher.shutdown,
                         kwargs={"drain": drain}, daemon=True,
                         name=f"retire-{self.name}-v{self.version}").start()


class _ManagedModel:
    """Router-side live state of one registry model: the active
    versioned engine, an optional canary one, canary bookkeeping, and
    the per-tenant outstanding-request ledgers."""

    def __init__(self, name: str):
        self.name = name
        self.lock = witnessed_rlock("router.model")
        self.active: Optional[_VersionedEngine] = None
        self.canary: Optional[_VersionedEngine] = None
        self.canary_started: Optional[float] = None  # monotonic
        self.canary_counter = 0
        self.canary_inflight: deque = deque()
        #: cluster mode: this replica observed the canary fail but does
        #: NOT hold the controller lease — local canary routing stops
        #: (no more traffic to a version we saw fail) while the lease
        #: holder's cluster-wide verdict is pending in the journal
        self.canary_suspended = False
        self.generation = None  # lazy GenerationEngine
        #: canary-version GenerationEngine (built lazily at the first
        #: /generate while a canary window is open) — canary_fraction of
        #: generation traffic decodes on the candidate weights so its
        #: errors/latency feed the metric gate (the PR 11 residue:
        #: generation-only regressions must still trip auto-rollback)
        self.canary_generation = None
        self.canary_gen_failed = False  # build failed once: don't retry
        #: a build+warm is in flight OFF the lock (exactly one builder;
        #: traffic keeps routing to the active version meanwhile)
        self.canary_gen_building = False
        #: per-window AlertEvaluator holding the canary gate's rules
        #: (obs/slo.canary_gate_rules) — built at canary start, torn
        #: down on trip/promote/evict; the gate decisions live in the
        #: rules' signals, the engine owns the state machine + forensics
        self.canary_alerts = None
        self.gen_counter = 0
        self.last_used = time.monotonic()
        #: set by LRU eviction. Engines are retired but the references
        #: stay valid, so a thread that grabbed this object before the
        #: eviction fails typed (ServerShutdownError from the drained
        #: batcher) or re-admits — never an AttributeError on None
        self.evicted = False


class ModelRouter:
    """Multi-model request router over a :class:`ModelRegistry`.

    One router per serving process. Models are admitted lazily (first
    request builds + warms the engine — a ``model_rewarm`` flight event
    marks the stall) and evicted LRU beyond ``max_live_models``
    (``model_evict``). The canary state machine runs inside the request
    path: submissions adopt newly validated versions, completions feed
    the metric gate, and the gate promotes or rolls back.

    ``score_probe`` (optional, ``engine → float``, same direction as the
    registry's scores) re-runs the held-out validation against the
    canary's LIVE engine at canary start — the score leg of the gate
    without any external feeder. External evaluators can also post
    scores via :meth:`record_score`.
    """

    def __init__(self, registry: ModelRegistry,
                 batch_limit: int = 32, max_wait_ms: float = 5.0,
                 queue_limit: int = 256, max_live_models: int = 4,
                 tenant_quota: Optional[int] = None,
                 canary_fraction: float = 0.1,
                 canary_window_s: float = 30.0,
                 canary_min_requests: int = 1,
                 latency_trip_mult: float = 5.0,
                 latency_trip_min_samples: int = 8,
                 score_trip_tolerance: float = 0.0,
                 score_probe: Optional[Callable] = None,
                 refresh_s: float = 2.0, mesh=None,
                 gen_slots: int = 0, gen_max_length: Optional[int] = None,
                 gen_spec_decode_k: int = 1, gen_draft_mode: str = "ngram",
                 gen_prefix_cache_mb: float = 0.0,
                 metrics: Optional[ServingMetrics] = None,
                 trace_requests: bool = True, traces=None,
                 cluster=None):
        self.registry = registry
        #: optional serving/cluster.py ClusterCoordinator. When set,
        #: the canary state machine becomes cluster-wide: gate ticks
        #: read CLUSTER-merged per-version stats, only the lease
        #: holder commits trip/promote decisions (epoch-fenced — a
        #: stale ex-holder's decision raises typed StaleEpochError),
        #: and tenant quotas become budget shares of the global quota
        self.cluster = cluster
        self.batch_limit = int(batch_limit)
        self.max_wait_ms = float(max_wait_ms)
        self.queue_limit = int(queue_limit)
        self.max_live_models = max(int(max_live_models), 1)
        self.tenant_quota = (None if tenant_quota is None
                             else max(int(tenant_quota), 1))
        self.canary_fraction = min(max(float(canary_fraction), 0.0), 1.0)
        self.canary_window_s = float(canary_window_s)
        self.canary_min_requests = max(int(canary_min_requests), 1)
        self.latency_trip_mult = float(latency_trip_mult)
        self.latency_trip_min_samples = max(int(latency_trip_min_samples), 1)
        self.score_trip_tolerance = float(score_trip_tolerance)
        self.score_probe = score_probe
        self.refresh_s = float(refresh_s)
        self.mesh = mesh
        self.gen_slots = int(gen_slots)
        self.gen_max_length = gen_max_length
        self.gen_spec_decode_k = int(gen_spec_decode_k)
        self.gen_draft_mode = str(gen_draft_mode)
        self.gen_prefix_cache_mb = float(gen_prefix_cache_mb)
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.trace_requests = bool(trace_requests)
        self.traces = traces
        self._live: "OrderedDict[str, _ManagedModel]" = OrderedDict()
        self._lock = witnessed_rlock("router")
        self._tenants: Dict[str, deque] = {}
        self._tenant_lock = witnessed_lock("router.tenants")
        #: per-tenant quota overrides (demotions): tenant → max
        #: in-flight, applied as a MIN over the configured/cluster
        #: quota in :meth:`_admit_tenant`. Written by
        #: :meth:`demote_tenant` / :meth:`restore_tenant` (the
        #: adaptive-capacity TenantDemoter's knob).
        self.tenant_tiers: Dict[str, int] = {}
        self._last_refresh = time.monotonic()
        self._shutdown = False

    # -- metrics helpers -----------------------------------------------------
    def _counter(self, family: str, name: str, version: int):
        return self.metrics.registry.counter(
            family, "per-version deployment counters",
            labels={"model": name, "version": str(int(version))})

    # -- admission -----------------------------------------------------------
    def _maybe_refresh(self) -> None:
        now = time.monotonic()
        interval = self.refresh_s
        canary_open = False
        if self.cluster is not None:
            with self._lock:
                canary_open = any(mm.canary is not None
                                  for mm in self._live.values())
            if canary_open:
                # tighten the poll while a window is open: a peer's
                # rollback must reach THIS replica within the bench's
                # cross-replica latency bound
                interval = min(interval, self.cluster.canary_refresh_s)
        if now - self._last_refresh < interval:
            return
        self._last_refresh = now
        changed = self.registry.refresh(force=canary_open)
        if self.cluster is not None:
            self.cluster.refresh()
            self._sync_cluster(changed)

    def managed(self, name: str) -> _ManagedModel:
        """The live managed model, admitting (and LRU-evicting) as
        needed. Raises :class:`UnknownModelError` for names the registry
        does not hold. The engine BUILD (checkpoint restore + XLA
        warmup, seconds on a cold model) runs outside the router-wide
        lock so one model's rewarm never stalls traffic to the others;
        a lost build race simply discards the duplicate."""
        from deeplearning4j_tpu.obs import flight as _flight
        from deeplearning4j_tpu.serving.batcher import ServerShutdownError

        with self._lock:
            if self._shutdown:
                raise ServerShutdownError("router is shut down")
            mm = self._live.get(name)
            if mm is not None:
                mm.last_used = time.monotonic()
                self._live.move_to_end(name)
                return mm
            vrec = self.registry.resolve(name)  # typed if unknown/inactive
        t0 = time.monotonic()
        ve = _VersionedEngine(self, name, vrec, role="active")
        with self._lock:
            if self._shutdown:
                ve.retire(drain=False)
                raise ServerShutdownError("router is shut down")
            raced = self._live.get(name)
            if raced is not None:
                ve.retire(drain=False)  # another thread built it first
                return raced
            while len(self._live) >= self.max_live_models:
                evict_name = next(
                    (n for n, m in self._live.items() if m.canary is None),
                    next(iter(self._live)))
                self._evict(evict_name)
            mm = _ManagedModel(name)
            mm.active = ve
            _flight.record("model_rewarm", model=name,
                           version=int(vrec["version"]),
                           wall_ms=round((time.monotonic() - t0) * 1e3, 1))
            self._live[name] = mm
        # a canary that was mid-window when the process died restarts
        # cleanly: the journal kept canary_start, the window restarts
        persisted = self.registry.canary_state(name)
        if persisted is not None:
            with mm.lock:
                cand = self.registry.get(name)["versions"].get(
                    str(persisted["version"]))
                if cand is not None and cand["status"] == "canary":
                    self._start_canary(mm, cand, resumed=True)
        return mm

    def _evict(self, name: str) -> None:
        from deeplearning4j_tpu.obs import flight as _flight

        mm = self._live.pop(name, None)
        if mm is None:
            return
        with mm.lock:
            # retire WITHOUT nulling the references: a thread that
            # grabbed this _ManagedModel before the eviction sees
            # evicted=True (and retries admission) or hits the drained
            # batcher's typed ServerShutdownError — never a None deref
            mm.evicted = True
            if mm.generation is not None:
                gen, mm.generation = mm.generation, None
                threading.Thread(target=gen.shutdown, daemon=True).start()
            if mm.canary_generation is not None:
                cgen, mm.canary_generation = mm.canary_generation, None
                threading.Thread(target=cgen.shutdown,
                                 kwargs={"drain": False},
                                 daemon=True).start()
            if mm.canary is not None:
                # eviction is capacity pressure, not a verdict: the
                # canary record stays in the registry and resumes on
                # rewarm
                mm.canary.retire(drain=True)
                mm.canary = None
            if mm.canary_alerts is not None:
                mm.canary_alerts.shutdown()
                mm.canary_alerts = None
            if mm.active is not None:
                _flight.record("model_evict", model=name,
                               version=mm.active.version)
                mm.active.retire(drain=True)

    # -- capacity surface (the ModelPrewarmer's knobs) -----------------------
    def live_models(self) -> List[str]:
        """Names currently warm, LRU → MRU order."""
        with self._lock:
            return list(self._live)

    def model_idle_s(self, name: str) -> Optional[float]:
        """Seconds since ``name`` last served a request; None when the
        model is not live."""
        with self._lock:
            mm = self._live.get(name)
            return (None if mm is None
                    else max(time.monotonic() - mm.last_used, 0.0))

    def prewarm_model(self, name: str) -> int:
        """Admit (build + warm) ``name`` ahead of predicted load so its
        first real request hits a compiled engine. Returns the active
        version. Typed UnknownModelError when the registry has no such
        model — a forecast must not invent capacity."""
        return self.managed(name).active.version

    def evict_model(self, name: str) -> bool:
        """Release a live model's capacity (predicted-idle eviction).
        Refuses — returns False — while a canary window is open on the
        model (an open verdict outranks a load forecast) or when the
        model is not live. The LRU machinery re-admits on next use."""
        with self._lock:
            mm = self._live.get(name)
            if mm is None or mm.canary is not None:
                return False
            self._evict(name)
            return True

    # -- tenant quotas -------------------------------------------------------
    def tenant_inflight(self) -> Dict[str, int]:
        """Per-tenant in-flight request counts — what this replica's
        cluster heartbeat reports so peers can borrow unused quota."""
        with self._tenant_lock:
            out = {}
            for t, ledger in self._tenants.items():
                n = sum(1 for r in ledger if not r.done())
                if n:
                    out[t] = n
            return out

    def _admit_tenant(self, tenant: str, retry_after: float):
        quota = self.tenant_quota
        if self.cluster is not None:
            # cluster-wide quota: this replica's budget share (fair-
            # share floor + borrow of peers' reported idle capacity)
            budget = self.cluster.tenant_budget(tenant)
            if budget is not None:
                quota = budget if quota is None else min(quota, budget)
        tier = self.tenant_tiers.get(tenant)
        if tier is not None:
            # a demoted tenant's tier binds even when no global quota
            # is configured — demotion must mean something everywhere
            quota = tier if quota is None else min(quota, tier)
        if quota is None:
            return None
        with self._tenant_lock:
            # bound the ledger table: tenant ids come from a
            # client-controlled header, so unique-per-request ids (or
            # natural churn over months) must not grow memory forever
            if len(self._tenants) > 4096:
                self._tenants = {t: d for t, d in self._tenants.items()
                                 if any(not r.done() for r in d)}
            ledger = self._tenants.setdefault(tenant, deque())
            while ledger and ledger[0].done():
                ledger.popleft()
            # opportunistic prune of the middle too (completion order is
            # not FIFO under mixed timeouts)
            if len(ledger) >= quota:
                live = deque(r for r in ledger if not r.done())
                self._tenants[tenant] = ledger = live
            if len(ledger) >= quota:
                from deeplearning4j_tpu.obs import flight as _flight

                self.metrics.registry.counter(
                    "serving_tenant_rejects_total",
                    "per-tenant quota rejections",
                    labels={"tenant": tenant}).inc()
                _flight.record("tenant_reject", tenant=tenant,
                               quota=quota)
                raise TenantQuotaExceededError(
                    f"tenant {tenant!r} has {len(ledger)} requests in "
                    f"flight (quota {quota}); retry with "
                    "backoff — other tenants are unaffected",
                    tenant=tenant, retry_after_s=retry_after)
            return ledger

    def demote_tenant(self, tenant: str, quota: int) -> Optional[int]:
        """Cap ``tenant`` at ``quota`` in-flight requests (a MIN over
        any configured/cluster quota). Returns the previous override
        (None if the tenant was un-demoted). The caller — normally the
        adaptive TenantDemoter — owns recording the controller flight
        event with its triggering verdict."""
        quota = max(int(quota), 1)
        with self._tenant_lock:
            prev = self.tenant_tiers.get(tenant)
            self.tenant_tiers[tenant] = quota
            n = len(self.tenant_tiers)
        self.metrics.registry.gauge(
            "serving_tenants_demoted",
            "tenants currently on a demoted quota tier").set(n)
        return prev

    def restore_tenant(self, tenant: str) -> bool:
        """Lift a tenant's demotion; True if one was in force."""
        with self._tenant_lock:
            had = self.tenant_tiers.pop(tenant, None) is not None
            n = len(self.tenant_tiers)
        self.metrics.registry.gauge(
            "serving_tenants_demoted",
            "tenants currently on a demoted quota tier").set(n)
        return had

    # -- the request path ----------------------------------------------------
    def submit(self, model: str, x, mask=None,
               timeout: Optional[float] = None, tenant: str = "default",
               trace: Optional[bool] = None):
        """Route one request: admit the model, adopt any pending canary,
        pick the version (canary_fraction of traffic to the canary),
        enforce the tenant quota, and submit into that version's
        batcher. Returns the :class:`InferenceRequest` (block on
        ``.result()``; ``.model_version`` is the registry version that
        computed it)."""
        self._maybe_refresh()
        ve = None
        for _ in range(3):
            mm = self.managed(model)
            with mm.lock:
                if mm.evicted:
                    continue  # raced an LRU eviction: re-admit fresh
                self._maybe_adopt(mm)
                self._maybe_promote(mm)
                ve = mm.active
                if mm.canary is not None and self.canary_fraction > 0 \
                        and not mm.canary_suspended:
                    mm.canary_counter += 1
                    every = max(int(round(1.0 / self.canary_fraction)), 1)
                    if mm.canary_counter % every == 0:
                        ve = mm.canary
            break
        if ve is None:
            err = ServerOverloadedError(
                f"model {model!r} kept being evicted under admission "
                "churn; retry")
            err.retry_after_s = 1.0
            raise err
        ledger = self._admit_tenant(tenant, ve.batcher.retry_after_s())
        # per-tenant accepted traffic: the abuse-share signal the
        # TenantDemoter reads (rejects are counted separately above)
        self.metrics.registry.counter(
            "serving_tenant_requests_total",
            "per-tenant accepted requests",
            labels={"tenant": tenant}).inc()
        req = ve.batcher.submit(x, mask, timeout=timeout, trace=trace)
        if ledger is not None:
            with self._tenant_lock:
                ledger.append(req)
        if ve.role == "canary":
            with mm.lock:
                mm.canary_inflight.append(req)
                while mm.canary_inflight and mm.canary_inflight[0].done():
                    mm.canary_inflight.popleft()
        return req

    def predict(self, model: str, x, mask=None,
                timeout: Optional[float] = None, tenant: str = "default",
                trace: Optional[bool] = None):
        """Blocking convenience: ``(outputs, registry_version)``."""
        req = self.submit(model, x, mask, timeout=timeout, tenant=tenant,
                          trace=trace)
        out = req.result(timeout=timeout)
        return out, req.model_version

    def _build_generation(self, base_model, name: str, version: int,
                          role: str, n_slots: Optional[int] = None):
        from deeplearning4j_tpu.serving.generate import GenerationEngine
        from deeplearning4j_tpu.serving.metrics import GenerationMetrics

        gen = GenerationEngine(base_model,
                               n_slots=(self.gen_slots if n_slots is None
                                        else int(n_slots)),
                               max_length=self.gen_max_length,
                               spec_decode_k=self.gen_spec_decode_k,
                               draft_mode=self.gen_draft_mode,
                               prefix_cache_mb=self.gen_prefix_cache_mb,
                               metrics=GenerationMetrics(),
                               traces=self.traces)
        gen.chaos_ctx = {"model": name, "version": int(version),
                         "role": role}
        return gen

    def _managed_for_generation(self, model: str) -> _ManagedModel:
        if self.gen_slots <= 0:
            raise ValueError(
                "router built without generation slots (gen_slots=0)")
        mm = self.managed(model)
        with mm.lock:
            if mm.evicted:
                mm = None
        if mm is None:
            mm = self.managed(model)  # raced an eviction: re-admit
        return mm

    def generation_for(self, model: str):
        """The model's continuous-batching generation engine (lazily
        built over the ACTIVE version's model). Raises TypeError when
        the model has no incremental-decode path, ValueError when the
        router was built with ``gen_slots=0``. Canary-aware generation
        submission goes through :meth:`generation_submit` — this
        accessor always returns the active-version engine."""
        mm = self._managed_for_generation(model)
        with mm.lock:
            return self._ensure_generation(mm)

    def _ensure_generation(self, mm: _ManagedModel):
        # caller holds mm.lock
        if mm.generation is None:
            mm.generation = self._build_generation(
                mm.active.engine.model, mm.name, mm.active.version,
                "active")
        return mm.generation

    def scale_generation_slots(self, model: str, n_slots: int) -> dict:
        """Resize the model's generation slab to ``n_slots`` decode
        slots (the SlotScaler's knob, sized against
        ``generation_memory_report``). The slab's slot count is baked
        into its fixed shapes, so scaling means building and warming a
        FRESH engine — done entirely outside locks (the
        ``_build_canary_generation`` discipline: building under
        ``mm.lock`` would stall the model's traffic for seconds and
        re-close the lock-order cycle the witness flagged), then
        installed under ``mm.lock`` with the old engine drained in the
        background. A lost race (eviction, concurrent scale) discards
        the new engine. Returns ``{slots, previous, changed}``."""
        n_slots = max(int(n_slots), 1)
        mm = self._managed_for_generation(model)
        with mm.lock:
            old = self._ensure_generation(mm)
            if old.n_slots == n_slots:
                return {"slots": n_slots, "previous": n_slots,
                        "changed": False}
            base_model = mm.active.engine.model
            version = mm.active.version
        gen = self._build_generation(base_model, mm.name, version,
                                     "active", n_slots=n_slots)
        gen.warmup()
        stale = None
        with mm.lock:
            if mm.evicted or mm.generation is not old:
                stale = gen  # raced an eviction or another scaler: lose
            else:
                mm.generation = gen
                stale = old
        prev = old.n_slots
        changed = stale is old
        if stale is not None:
            threading.Thread(
                target=stale.shutdown,
                kwargs={"drain": changed},  # drain the replaced engine's
                # in-flight decodes; a discarded NEW engine has none
                daemon=True).start()
        return {"slots": n_slots if changed else prev,
                "previous": prev, "changed": changed}

    def _build_canary_generation(self, mm: _ManagedModel, base_model,
                                 version: int) -> None:
        """Build+warm the canary's generation engine with NO locks
        held, then install it under ``mm.lock`` — the caller set
        ``canary_gen_building`` under the lock, so exactly one builder
        runs. Building under ``mm.lock`` would (a) stall every
        predict/generate for the model behind seconds of slab compiles
        and (b) close a lock-order cycle against the decode worker,
        which holds the engine DEVICE lock when its completion
        observers take ``mm.lock`` — the ABBA pattern the lock witness
        (obs/lockwitness.py) flagged the moment it armed over this
        drill. A model whose candidate cannot decode (arch change)
        records the fact once and serves generation from the active
        version only (the canary then needs /predict traffic to
        promote)."""
        from deeplearning4j_tpu.obs import flight as _flight

        gen = None
        try:
            gen = self._build_generation(base_model, mm.name, version,
                                         "canary")
            gen.warmup()
        except Exception as e:  # noqa: BLE001 — a candidate that
            # cannot even build its decode slab must not take down
            # generation serving; it simply gets no generation
            # traffic (and no generation votes in the gate)
            with mm.lock:
                # poison only the window we were building for: if it
                # already tripped/promoted and a NEW canary opened,
                # this stale failure must not cost the new candidate
                # its generation votes
                if (mm.canary is not None
                        and mm.canary.version == version):
                    mm.canary_gen_failed = True
                mm.canary_gen_building = False
            _flight.record("canary_generation_unavailable",
                           model=mm.name, version=version,
                           error=type(e).__name__,
                           message=str(e)[:200])
            return
        stale = None
        with mm.lock:
            mm.canary_gen_building = False
            if (mm.canary is not None and mm.canary.version == version
                    and mm.canary_generation is None):
                mm.canary_generation = gen
            else:
                # the window closed (trip/promote/evict) while we were
                # warming: discard the engine OUTSIDE the lock
                stale = gen
        if stale is not None:
            stale.shutdown(drain=False, timeout=5.0)

    def generation_submit(self, model: str, prompt_ids, **kwargs):
        """Submit one generation request with canary routing: while a
        canary window is open, ``canary_fraction`` of the model's
        /generate traffic decodes on the candidate version's own
        engine, and EVERY generation completion (either version) feeds
        the per-version ``registry_version_gen_*`` counters the metric
        gate reads — so a snapshot that only regresses under generation
        traffic still trips auto-rollback (the PR 11 residue). Returns
        the :class:`~.generate.GenerationRequest`."""
        mm = self._managed_for_generation(model)
        build_spec = None
        with mm.lock:
            self._maybe_adopt(mm)
            self._maybe_promote(mm)
            gen = self._ensure_generation(mm)
            ve = mm.active
            if mm.canary is not None and self.canary_fraction > 0 \
                    and not mm.canary_suspended:
                cgen = mm.canary_generation
                if (cgen is None and not mm.canary_gen_failed
                        and not mm.canary_gen_building):
                    # first /generate of an open window: claim the
                    # build under the lock, run it AFTER release (see
                    # _build_canary_generation — lock-order + latency)
                    mm.canary_gen_building = True
                    build_spec = (mm.canary.engine.model,
                                  mm.canary.version)
                if cgen is not None:
                    mm.gen_counter += 1
                    every = max(int(round(1.0 / self.canary_fraction)), 1)
                    if mm.gen_counter % every == 0:
                        gen, ve = cgen, mm.canary
        if build_spec is not None:
            # this request still decodes on the active version; the
            # canary starts taking its fraction from the NEXT submit,
            # once the warm engine is installed (the documented
            # lazily-built semantics)
            self._build_canary_generation(mm, *build_spec)
        # the observer rides in through submit so it is installed
        # BEFORE the request is enqueued — a completion racing the
        # submit return (instant canary decode failure, already-expired
        # deadline) must still be counted by the metric gate
        t0 = time.monotonic()
        return gen.submit(prompt_ids,
                          on_done=self._make_gen_observer(model, ve, t0),
                          **kwargs)

    def _make_gen_observer(self, name: str, ve: _VersionedEngine,
                           t0: float):
        from deeplearning4j_tpu.serving.batcher import (
            ServerShutdownError,
        )

        def on_done(req, error):
            dt = time.monotonic() - t0
            if error is None:
                ve.stats.gen_requests += 1
                ve.stats.gen_latency_sum += dt
                self._counter("registry_version_gen_requests_total",
                              name, ve.version).inc()
                self._counter(
                    "registry_version_gen_latency_seconds_total",
                    name, ve.version).inc(dt)
                if ve.role == "canary":
                    # off-thread: on_done runs on the decode worker
                    # UNDER the engine's device lock, and a promotion
                    # here does journal fsyncs + a snapshot rewrite —
                    # disk I/O that must not stall every decode slot.
                    # (The error-path trip below stays inline: it is
                    # terminal for these slots anyway and must be
                    # prompt.)
                    threading.Thread(target=self._evaluate_canary,
                                     args=(name,), daemon=True,
                                     name=f"canary-eval-{name}").start()
                return
            if isinstance(error, (ServerShutdownError,
                                  ServerOverloadedError,
                                  CanaryRolledBackError)):
                return  # admission/lifecycle, not the version's fault
            ve.stats.gen_errors += 1
            self._counter("registry_version_gen_errors_total",
                          name, ve.version).inc()
            if ve.role != "canary" or ve.dead:
                return
            if isinstance(error, RequestDeadlineExceeded):
                # a caller-side deadline is ambiguous (tight client
                # timeout vs slow canary) — count it and let the
                # latency/score legs decide
                self._evaluate_canary(name)
            else:
                # decode failure / watchdog stall on the candidate:
                # the bad version must not get more traffic
                self._trip(name, ve,
                           f"generation dispatch failure: "
                           f"{type(error).__name__}")

        return on_done

    # -- canary state machine ------------------------------------------------
    def _maybe_adopt(self, mm: _ManagedModel) -> None:
        """Start a canary for a newly validated version (the serve-side
        half of the continuous loop: the trainer publishes, the router
        notices here). Adoption is synchronous under ``mm.lock``: the
        ONE request that notices the new version pays the canary
        engine's build+warmup (and concurrent requests for this model
        wait on the lock) — the deliberate trade for a state machine
        with no background thread; canary-ROUTED traffic afterwards
        never absorbs a compile (the engine is warm before the first
        slice of traffic reaches it)."""
        if mm.canary is not None or self._shutdown:
            return
        cand = self.registry.candidate(mm.name)
        if cand is None:
            return
        self._start_canary(mm, cand, resumed=False)

    def _start_canary(self, mm: _ManagedModel, vrec: dict,
                      resumed: bool) -> None:
        from deeplearning4j_tpu.obs import flight as _flight

        try:
            ve = _VersionedEngine(self, mm.name, vrec, role="canary")
        except Exception as e:  # noqa: BLE001 — a snapshot that cannot
            # even build an engine must roll back, not kill serving
            self.registry.rollback(mm.name, int(vrec["version"]),
                                   f"engine build failed: "
                                   f"{type(e).__name__}: {e}")
            _flight.record("regression_trip", model=mm.name,
                           version=int(vrec["version"]),
                           reason=f"engine build failed: {type(e).__name__}")
            _flight.record("rollback", model=mm.name,
                           version=int(vrec["version"]),
                           active_version=mm.active.version)
            return
        mm.canary = ve
        mm.canary_started = time.monotonic()
        mm.canary_counter = 0
        mm.canary_inflight.clear()
        mm.canary_suspended = False
        if self.cluster is not None:
            # bid for the window's controller lease; losing is fine —
            # this replica then serves its canary slice, journals gate
            # snapshots, and the lease holder decides
            self.cluster.ensure_lease(mm.name)
        # the gate as declarative rules in the shared alert engine (ONE
        # evaluation mechanism with the SLO pack): signals close over
        # the live per-version stats and reproduce the PR 11 gate's
        # comparisons and reason strings exactly; the evaluator
        # contributes the state machine, alert_* flight forensics and
        # alert_firing gauges
        from deeplearning4j_tpu.obs.alerts import AlertEvaluator
        from deeplearning4j_tpu.obs.slo import canary_gate_rules

        # cluster mode evaluates the SAME rules over a duck-typed view
        # whose per-version stats are CLUSTER-merged (local live
        # counters + peers' journaled gate snapshots): a regression any
        # replica observes reaches the controller's tick
        gate_subject = (mm if self.cluster is None
                        else self.cluster.gate_view(mm))
        mm.canary_alerts = AlertEvaluator(
            canary_gate_rules(gate_subject,
                              self.registry.higher_is_better,
                              self.latency_trip_mult,
                              self.latency_trip_min_samples,
                              self.score_trip_tolerance),
            registry=self.metrics.registry,
            context={"model": mm.name, "version": ve.version},
            min_tick_interval=0.0)
        if not resumed:
            self.registry.start_canary(mm.name, ve.version,
                                       self.canary_fraction,
                                       self.canary_window_s)
        _flight.record("canary_start", model=mm.name, version=ve.version,
                       fraction=self.canary_fraction,
                       window_s=self.canary_window_s,
                       resumed=bool(resumed))
        if self.score_probe is not None:
            # the held-out validation step re-run against the LIVE
            # canary engine — the score leg of the gate without any
            # external feeder
            try:
                c_score = float(self.score_probe(ve.engine))
                a_score = (mm.active.stats.score
                           if mm.active.stats.score is not None
                           else (self.score_probe(mm.active.engine)
                                 if mm.active is not None else None))
            except Exception as e:  # noqa: BLE001 — a broken probe is a
                # trip, not a crash: refusing to score IS a red flag
                self._trip(mm.name, ve,
                           f"score probe failed: {type(e).__name__}: {e}")
                return
            self.record_score(mm.name, ve.version, c_score)
            if a_score is not None:
                mm.active.stats.observe_score(float(a_score))
            self._evaluate_canary(mm.name)

    def record_score(self, model: str, version: int, value: float) -> None:
        """Post a quality score for a version (probes, external
        evaluators). Feeds the canary score gate; mirrored into the
        shared metrics registry."""
        mm = self._live.get(model)
        if mm is None:
            return
        with mm.lock:
            for ve in (mm.active, mm.canary):
                if ve is not None and ve.version == int(version):
                    ve.stats.observe_score(float(value))
                    self.metrics.registry.gauge(
                        "registry_version_score",
                        "latest quality score per served version",
                        labels={"model": model,
                                "version": str(int(version))}
                    ).set(float(ve.stats.score))
        self._evaluate_canary(model)

    def _evaluate_canary(self, name: str) -> None:
        """The metric gate: called on canary completions, score posts,
        and submissions. One evaluator tick over the window's gate
        rules (score / latency / generation latency, in the original
        evaluation order — obs/slo.canary_gate_rules); the first firing
        rule trips with its rule-rendered reason. Promotes once the
        window has elapsed with enough clean traffic."""
        mm = self._live.get(name)
        if mm is None:
            return
        with mm.lock:
            ve = mm.canary
            if ve is None or ve.dead:
                return
            if self.cluster is not None:
                # fold OUT first: journal this replica's per-version
                # observations so every peer's next tick sees them
                self.cluster.journal_gate(name, ve.version, "canary",
                                          ve.stats)
                if mm.active is not None:
                    self.cluster.journal_gate(name, mm.active.version,
                                              "active", mm.active.stats)
                if not self.cluster.ensure_lease(name):
                    return  # a live peer holds the controller lease
                if mm.canary_suspended:
                    # this replica observed the failure while a peer
                    # held the lease (fence refused its inline trip);
                    # now IT is the controller — the suspended canary
                    # trips immediately
                    self._trip(name, ve,
                               "canary dispatch failures observed "
                               "while a peer held the controller lease")
                    return
                # a dispatch failure a PEER journaled is ground truth
                # (its own inline trip was refused by the fence): the
                # bad version must not get more cluster traffic
                peer_fail = self.cluster.peer_failures(name, ve.version)
                if peer_fail:
                    self._trip(name, ve,
                               f"peer-observed canary dispatch "
                               f"failures ({peer_fail})")
                    return
            ev = mm.canary_alerts
            if ev is not None:
                for st in ev.tick():
                    if st["state"] == "firing":
                        self._trip(name, ve, st["reason"])
                        return
            # promotion: bounded window elapsed, enough canary traffic
            # (predict AND generation requests both count — a model
            # serving only /generate must still be able to promote; in
            # cluster mode the CLUSTER-wide canary traffic counts),
            # nothing tripped
            st = (ve.stats if self.cluster is None
                  else self.cluster.merged_stats(name, ve))
            if (mm.canary_started is not None
                    and time.monotonic() - mm.canary_started
                    >= self.canary_window_s
                    and st.requests + st.gen_requests
                    >= self.canary_min_requests):
                self._promote(mm)

    def _maybe_promote(self, mm: _ManagedModel) -> None:
        """Submission-path promotion poke (completions may have stopped
        exactly at the window edge)."""
        if mm.canary is not None and not mm.canary.dead:
            self._evaluate_canary(mm.name)

    def _promote(self, mm: _ManagedModel) -> None:
        from deeplearning4j_tpu.obs import flight as _flight

        with mm.lock:
            ve, old = mm.canary, mm.active
            if ve is None:
                return
            if self.cluster is not None:
                from deeplearning4j_tpu.serving.cluster import (
                    StaleEpochError,
                )

                try:
                    # the epoch fence: a stale ex-holder (paused,
                    # skewed) must not journal a promote the current
                    # controller did not make
                    self.cluster.fence(mm.name)
                except StaleEpochError:
                    return  # the holder's verdict arrives via the WAL
            mm.canary = None
            mm.canary_started = None
            mm.canary_inflight.clear()
            if mm.canary_alerts is not None:
                mm.canary_alerts.shutdown()
                mm.canary_alerts = None
            mm.active = ve
            ve.role = "active"
            self.registry.promote(mm.name, ve.version)
            _flight.record("promote", model=mm.name, version=ve.version,
                           requests=ve.stats.requests,
                           gen_requests=ve.stats.gen_requests,
                           mean_latency_ms=None
                           if ve.stats.mean_latency() is None
                           else round(ve.stats.mean_latency() * 1e3, 2))
            if old is not None:
                # drain: in-flight old-version requests all complete —
                # the no-mixing/no-dropping guarantee under promotion
                old.retire(drain=True)
            self._adopt_promoted_generation(mm, old)

    def _adopt_promoted_generation(self, mm: _ManagedModel,
                                   old: Optional["_VersionedEngine"]
                                   ) -> None:
        # caller holds mm.lock and has already made mm.active the
        # promoted engine
        if mm.canary_generation is not None:
            # the canary's warmed decode engine IS the promoted
            # version's engine — adopt it (already on the new
            # weights, zero recompiles) and retire the old one
            old_gen, mm.generation = mm.generation, mm.canary_generation
            mm.canary_generation = None
            mm.canary_gen_failed = False
            mm.generation.chaos_ctx["role"] = "active"
            if old_gen is not None:
                threading.Thread(target=old_gen.shutdown,
                                 daemon=True).start()
        else:
            mm.canary_gen_failed = False
            self._sync_generation(mm, old)

    def _sync_generation(self, mm: _ManagedModel,
                         old: Optional[_VersionedEngine]) -> None:
        """Point the model's generation engine at the promoted weights.
        Same architecture → atomic params swap on the bound model object
        (every dispatch looks at ``params_``, so the swap takes effect
        at the next token, zero recompiles; a transformer backend that
        serves a compute-dtype copy of the weights re-makes it then:
        one cast program a swap);
        different architecture → retire and rebuild lazily."""
        gen = mm.generation
        if gen is None:
            return
        new_model = mm.active.engine.model
        old_conf = getattr(getattr(gen.backend.model, "conf", None),
                           "to_json", lambda: None)()
        new_conf = getattr(getattr(new_model, "conf", None),
                           "to_json", lambda: None)()
        if old_conf is not None and old_conf == new_conf:
            gen.backend.model.params_ = new_model.params_
            gen.backend.model.state_ = new_model.state_
        else:
            mm.generation = None
            threading.Thread(target=gen.shutdown, daemon=True).start()

    def _trip(self, name: str, ve: _VersionedEngine, reason: str) -> None:
        """Regression trip → rollback. Outstanding canary requests are
        failed typed FIRST (first-wins — a racing completion of the bad
        version becomes a no-op for any request we fail here), then the
        canary engine is retired and the registry records the rollback.
        The active version is untouched throughout."""
        from deeplearning4j_tpu.obs import flight as _flight

        mm = self._live.get(name)
        if mm is None:
            return
        if self.cluster is not None:
            from deeplearning4j_tpu.serving.cluster import StaleEpochError

            try:
                # same fence as promote: only the current lease holder
                # journals a rollback. A non-holder that observed the
                # failure suspends its local canary routing and journals
                # the failure urgently so the holder's next tick trips.
                self.cluster.fence(name)
            except StaleEpochError:
                self._suspend_canary(mm, ve, reason)
                return
        with mm.lock:
            if mm.canary is not ve or ve.dead:
                return  # already tripped / promoted
            ve.dead = True
            mm.canary = None
            mm.canary_started = None
            if mm.canary_alerts is not None:
                mm.canary_alerts.shutdown()
                mm.canary_alerts = None
            if mm.canary_generation is not None:
                # fail the candidate's in-flight generation requests
                # typed and tear its slab down off-thread (shutdown
                # joins the decode worker)
                cgen, mm.canary_generation = mm.canary_generation, None
                threading.Thread(target=cgen.shutdown,
                                 kwargs={"drain": False},
                                 daemon=True).start()
            mm.canary_gen_failed = False
            _flight.record("regression_trip", model=name,
                           version=ve.version, reason=reason,
                           canary_requests=ve.stats.requests,
                           canary_errors=ve.stats.errors,
                           canary_gen_requests=ve.stats.gen_requests,
                           canary_gen_errors=ve.stats.gen_errors)
            err = CanaryRolledBackError(
                f"{name} v{ve.version} rolled back: {reason}; retry — "
                "the active version is serving")
            while mm.canary_inflight:
                req = mm.canary_inflight.popleft()
                req.fail(err)
            self.registry.rollback(name, ve.version, reason)
            _flight.record("rollback", model=name, version=ve.version,
                           active_version=None if mm.active is None
                           else mm.active.version)
            ve.retire(drain=False)

    # -- cluster sync --------------------------------------------------------
    def _suspend_canary(self, mm: _ManagedModel, ve: _VersionedEngine,
                        reason: str) -> None:
        """Non-holder observed a canary failure but the epoch fence
        refused its trip: stop routing local traffic to the candidate
        and journal the evidence urgently. The lease holder's next gate
        tick sees the peer failures and trips the CLUSTER rollback."""
        from deeplearning4j_tpu.obs import flight as _flight

        with mm.lock:
            if mm.canary is not ve or ve.dead or mm.canary_suspended:
                return
            mm.canary_suspended = True
            _flight.record("canary_suspend", model=mm.name,
                           version=ve.version, reason=reason)
        if self.cluster is not None:
            self.cluster.journal_gate(mm.name, ve.version, "canary",
                                      ve.stats, urgent=True)

    def _sync_cluster(self, registry_changed: bool) -> None:
        """Post-refresh reconciliation against the shared registry +
        cluster journal: apply peers' rollback/promote decisions
        locally, adopt canaries peers opened, and give the lease holder
        its gate tick (liveness-driven — no request traffic needed to
        steal a dead holder's lease)."""
        with self._lock:
            mms = list(self._live.values())
        for mm in mms:
            try:
                self._sync_cluster_model(mm)
            except (RegistryError, OSError):
                continue  # transient — next refresh retries

    def _sync_cluster_model(self, mm: _ManagedModel) -> None:
        try:
            reg = self.registry.get(mm.name)
        except UnknownModelError:
            return
        with mm.lock:
            if mm.evicted:
                return
            ve = mm.canary
            if ve is not None and not ve.dead:
                vr = reg.get("versions", {}).get(str(ve.version))
                status = None if vr is None else vr.get("status")
                if status == "rolled_back":
                    # a peer (the lease holder) tripped: tear down the
                    # local candidate without journaling a second
                    # rollback
                    self._apply_remote_rollback(mm, ve)
                elif (status == "active"
                      and reg.get("active_version") == ve.version):
                    self._apply_remote_promote(mm, ve)
            elif ve is None and not self._shutdown:
                cand = reg.get("canary")
                if (cand is not None
                        and mm.active is not None
                        and int(cand["version"]) != mm.active.version):
                    vrec = reg.get("versions", {}).get(
                        str(int(cand["version"])))
                    if vrec is not None \
                            and vrec.get("status") == "canary":
                        # a peer opened a canary window — adopt it so
                        # this replica's traffic share feeds the
                        # cluster gate
                        self._start_canary(mm, vrec, resumed=True)
        if mm.canary is not None:
            # the holder's poll tick: liveness/steal/peer-failure
            # evaluation must not wait for local canary traffic
            self._evaluate_canary(mm.name)

    def _apply_remote_rollback(self, mm: _ManagedModel,
                               ve: _VersionedEngine) -> None:
        """Caller holds mm.lock. Mirror of _trip's teardown minus the
        registry write and rollback event — the holder already
        journaled both; this replica only applies the verdict."""
        from deeplearning4j_tpu.obs import flight as _flight

        ve.dead = True
        mm.canary = None
        mm.canary_started = None
        mm.canary_suspended = False
        if mm.canary_alerts is not None:
            mm.canary_alerts.shutdown()
            mm.canary_alerts = None
        if mm.canary_generation is not None:
            cgen, mm.canary_generation = mm.canary_generation, None
            threading.Thread(target=cgen.shutdown,
                             kwargs={"drain": False},
                             daemon=True).start()
        mm.canary_gen_failed = False
        _flight.record("cluster_rollback_applied", model=mm.name,
                       version=ve.version)
        err = CanaryRolledBackError(
            f"{mm.name} v{ve.version} rolled back cluster-wide; retry "
            "— the active version is serving")
        while mm.canary_inflight:
            req = mm.canary_inflight.popleft()
            req.fail(err)
        ve.retire(drain=False)

    def _apply_remote_promote(self, mm: _ManagedModel,
                              ve: _VersionedEngine) -> None:
        """Caller holds mm.lock. Mirror of _promote minus the registry
        write and promote event (the holder journaled them)."""
        from deeplearning4j_tpu.obs import flight as _flight

        old = mm.active
        mm.canary = None
        mm.canary_started = None
        mm.canary_suspended = False
        mm.canary_inflight.clear()
        if mm.canary_alerts is not None:
            mm.canary_alerts.shutdown()
            mm.canary_alerts = None
        mm.active = ve
        ve.role = "active"
        _flight.record("cluster_promote_applied", model=mm.name,
                       version=ve.version)
        if old is not None:
            old.retire(drain=True)
        self._adopt_promoted_generation(mm, old)

    # -- introspection -------------------------------------------------------
    def healthz(self, name: str) -> dict:
        """Per-model readiness: active/canary versions, warm state,
        compile counts — the keys rollout tooling watches."""
        self._maybe_refresh()
        reg = self.registry.get(name)
        out = {"model": name,
               "active_version": reg.get("active_version"),
               "canary": reg.get("canary"),
               "live": False, "ready": False}
        mm = self._live.get(name)
        if mm is not None and mm.active is not None:
            info = mm.active.engine.describe()
            out.update(live=True, ready=bool(info.get("warm")),
                       warm=info.get("warm"),
                       checkpoint_fingerprint=info.get(
                           "checkpoint_fingerprint"),
                       compile_count=info.get("compile_count"),
                       queue_depth=mm.active.batcher.queue_depth())
            if mm.canary is not None:
                out["canary_live"] = {
                    "version": mm.canary.version,
                    "requests": mm.canary.stats.requests,
                    "errors": mm.canary.stats.errors,
                    "warm": mm.canary.engine.warm,
                }
        return out

    def describe(self) -> dict:
        with self._lock:
            live = {name: {
                "active_version": None if mm.active is None
                else mm.active.version,
                "canary_version": None if mm.canary is None
                else mm.canary.version,
                "queue_depth": 0 if mm.active is None
                else mm.active.batcher.queue_depth(),
            } for name, mm in self._live.items()}
        out = {"models": self.registry.models(), "live": live,
               "max_live_models": self.max_live_models,
               "tenant_quota": self.tenant_quota,
               "canary_fraction": self.canary_fraction,
               "canary_window_s": self.canary_window_s}
        if self.cluster is not None:
            out["cluster"] = self.cluster.describe()
        return out

    def queue_depth(self) -> int:
        with self._lock:
            depth = 0
            for mm in self._live.values():
                for ve in (mm.active, mm.canary):
                    if ve is not None:
                        depth += ve.batcher.queue_depth()
            return depth

    # -- lifecycle -----------------------------------------------------------
    def shutdown(self) -> None:
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
            names = list(self._live)
        for name in names:
            mm = self._live.get(name)
            if mm is None:
                continue
            # detach under mm.lock, tear down OUTSIDE it: shutdown
            # joins engine workers, and a canary completion observer
            # running ON such a worker takes mm.lock
            # (_evaluate_canary/_trip) — joining it while holding the
            # lock would deadlock. Synchronous drains are fine here
            # (shutdown runs on a caller thread, never a worker).
            with mm.lock:
                gen, mm.generation = mm.generation, None
                cgen, mm.canary_generation = mm.canary_generation, None
                canary, mm.canary = mm.canary, None
                active, mm.active = mm.active, None
                if canary is not None:
                    canary.dead = True
                if mm.canary_alerts is not None:
                    mm.canary_alerts.shutdown()
                    mm.canary_alerts = None
            if cgen is not None:
                cgen.shutdown(drain=False)
            if gen is not None:
                gen.shutdown(drain=True)
            if canary is not None:
                canary.batcher.shutdown(drain=True)
            if active is not None:
                active.batcher.shutdown(drain=True)
        with self._lock:
            self._live.clear()

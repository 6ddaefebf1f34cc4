"""Mesh-sharded serving: tensor-parallel inference + generation on a
2-D (batch, model) ServingMesh.

Both engines here are thin placement layers over the existing serving
stack — **pure-auto GSPMD**, no shard_map, no manual collectives:

- :class:`ShardedInferenceEngine` overrides exactly two seams of
  :class:`InferenceEngine`: snapshot construction (params placed per a
  :class:`ShardingPolicy` instead of replicated) and the raw dispatch
  (batch-sharded input + the ``serving.sharded_dispatch`` chaos seam +
  mesh-loss fallback). Everything else — buckets, warmup, hot reload,
  int8 refusal, registry/canary routing — is inherited unchanged,
  which is the point: the registry's canary machinery promotes and
  rolls back sharded candidates without knowing they are sharded.
- :class:`ShardedGenerationEngine` policy-places the model's params
  *before* the decode backend compiles, then re-places the KV slab
  sharded (slots over "batch", attention heads over "model"). The
  backend's jitted programs read params and slab as *arguments* with
  donation, so the sharded layouts flow through every dispatch and
  steady-state decode never retraces (``trace_counts`` is the
  instrument, same as solo).

Mesh-loss handling: a sharded dispatch that fails (device subset gone,
injected fault) raises a typed :class:`ShardedMeshError` AND arms a
solo fallback — the snapshot's params are gathered onto one surviving
device and every subsequent request serves there (slower, alive). The
``sharded_fallback`` flight event + ``sharded_serving_fallback`` alert
make the degraded mode loud; a canary running sharded trips the normal
rollback on the same failure (ANY canary dispatch error already does).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from deeplearning4j_tpu.parallel import reshard as _reshard
from deeplearning4j_tpu.parallel.serving_mesh import (
    ServingMesh,
    ShardingPolicy,
    ShardingPolicyError,
    policy_for,
    reshard_to_policy,
    validate_policy,
)
from deeplearning4j_tpu.serving.batcher import ServingError
from deeplearning4j_tpu.serving.engine import InferenceEngine, _Snapshot


class ShardedMeshError(ServingError):
    """A sharded dispatch failed mid-serve (device subset lost, runtime
    fault at the mesh seam). The engine has already armed its solo
    fallback when this reaches a caller — retrying the request serves
    degraded instead of failing again."""


class ShardedInferenceEngine(InferenceEngine):
    """:class:`InferenceEngine` whose snapshots live TP-sharded on a
    2-D (batch, model) :class:`ServingMesh`.

    ``mesh`` must be a ServingMesh (the ``n_data`` batch axis drives
    bucket divisibility exactly as before). ``policy`` defaults to the
    model's registry entry (``serving_mesh.policy_for``); validation —
    axis divisibility AND the per-device memory gate — happens at every
    snapshot build, so a reload to an incompatible checkpoint is a
    typed refusal with the old snapshot still serving.
    """

    def __init__(self, model, buckets=None, mesh=None, checkpoint_dir=None,
                 metrics=None, int8_serving: bool = False,
                 policy: Optional[ShardingPolicy] = None,
                 policy_overrides=None):
        if mesh is None or not hasattr(mesh, "n_model"):
            raise ShardingPolicyError(
                "ShardedInferenceEngine needs a ServingMesh (got "
                f"{type(mesh).__name__}); for replicated serving use "
                "InferenceEngine")
        if int8_serving:
            raise ShardingPolicyError(
                "int8_serving composes with replicated snapshots only; "
                "a TP policy would shard per-channel scales — serve "
                "sharded fp32 or solo int8, not both")
        self.policy = (policy if policy is not None
                       else policy_for(model, policy_overrides))
        #: memory-gate report of the LIVE snapshot's placement
        self.shard_report: Optional[dict] = None
        #: (params, state) gathered onto one device after a mesh loss;
        #: None while the mesh serves healthy
        self._solo = None
        super().__init__(model, buckets=buckets, mesh=mesh,
                         checkpoint_dir=checkpoint_dir, metrics=metrics,
                         int8_serving=False)

    # -- construction -------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, source: str, **kwargs):
        """Reshard-on-load: any checkpoint topology → this serving
        mesh. Same resolution/validation/fallback chain as the base
        engine; the reshard event reports N→M with M = the FULL mesh
        device count (a 2x4 mesh is 8 devices, not 2 replicas)."""
        import os

        from deeplearning4j_tpu.serving.engine import (
            resolve_checkpoint_source,
        )
        from deeplearning4j_tpu.train.model_serializer import (
            ModelGuesser,
            ModelSerializer,
        )

        path = resolve_checkpoint_source(source)
        topo = ModelSerializer.checkpoint_meta(path).get("topology") or {}
        n_from = topo.get("n_devices")
        model = ModelGuesser.load_model_guess(path)
        if os.path.isdir(source):
            kwargs.setdefault("checkpoint_dir", source)
        mesh = kwargs.get("mesh")
        n_to = mesh.n_devices if mesh is not None else 1
        with _reshard.reshard_event(n_from, n_to, surface="serving") as st:
            eng = cls(model, **kwargs)
            if eng.reshard_stats is not None:
                st.merge(eng.reshard_stats)
        eng._snap.source = path
        eng._fingerprint = cls._path_fingerprint(path)
        from deeplearning4j_tpu.obs import flight as _flight

        _flight.record("checkpoint_load", path=str(path), surface="serving")
        return eng

    # -- snapshot construction ----------------------------------------------
    def _build_snapshot(self, model, version: int, source) -> _Snapshot:
        from deeplearning4j_tpu.obs import flight as _flight

        conf = getattr(model, "conf", None)
        conf_json = conf.to_json() if hasattr(conf, "to_json") else None
        fn = self._build_fn(model)
        if fn is None:
            raise ShardingPolicyError(
                f"{type(model).__name__} serves through the generic "
                "output path (no functional _forward); tensor-parallel "
                "placement needs params to flow through jit as arguments")
        _flight.record("mesh_build", surface="serving",
                       batch=self.mesh.n_data, model=self.mesh.n_model,
                       n_devices=self.mesh.n_devices,
                       policy=self.policy.name)
        report = validate_policy(model.params_, self.mesh, self.policy,
                                 conf=conf)
        stats = _reshard.TransferStats()
        reshard_to_policy(model, self.mesh, self.policy, stats)
        self.reshard_stats = stats
        self.shard_report = report
        _flight.record("shard_load", surface="serving",
                       policy=self.policy.name, version=int(version),
                       total_bytes=report["total_bytes"],
                       per_device_bytes=report["per_device_bytes"],
                       replicated_bytes=report["replicated_bytes"],
                       device_bytes=int(stats.device_bytes),
                       host_bytes=int(stats.host_bytes))
        # a fresh snapshot serves the full mesh again (a reload is the
        # operator's recovery action after a fallback)
        self._solo = None
        return _Snapshot(model, fn, conf_json, version, source)

    # -- dispatch -----------------------------------------------------------
    @property
    def fallback_active(self) -> bool:
        """True once a mesh loss demoted this engine to one device."""
        return self._solo is not None

    def describe(self) -> dict:
        d = super().describe()
        d["mesh"] = dict(self.mesh.shape)
        d["policy"] = self.policy.describe()
        d["shard_report"] = self.shard_report
        d["fallback_active"] = self.fallback_active
        return d

    def _activate_fallback(self, snap: _Snapshot, reason: str) -> None:
        """Gather the live snapshot onto one device and route every
        later dispatch there. The gather is a device→device copy of
        whatever shards still respond; the first solo dispatch retraces
        (params changed sharding) — loud by design, the retrace event
        sits next to the fallback in the flight recorder."""
        from deeplearning4j_tpu.obs import flight as _flight

        dev = self.mesh.devices_flat()[0]
        sh = jax.sharding.SingleDeviceSharding(dev)
        params = jax.device_put(snap.params, sh)
        state = (jax.device_put(snap.state, sh)
                 if snap.state is not None else None)
        self._solo = (params, state)
        _flight.record("sharded_fallback", surface="serving",
                       reason=reason, batch=self.mesh.n_data,
                       model=self.mesh.n_model,
                       device=str(dev))

    def _forward_raw(self, snap: _Snapshot, xp, mp=None) -> np.ndarray:
        solo = self._solo
        if solo is not None:
            params, state = solo
            return snap.fn(params, state, xp, mp)
        from deeplearning4j_tpu.chaos import hooks as chaos_hooks

        try:
            chaos_hooks.fire("serving.sharded_dispatch",
                             batch=self.mesh.n_data,
                             model=self.mesh.n_model)
            xd = jax.device_put(xp, self.mesh.batch_sharded())
            md = (jax.device_put(mp, self.mesh.batch_sharded())
                  if mp is not None else None)
            return snap.fn(snap.params, snap.state, xd, md)
        except (ShardingPolicyError, TypeError):
            raise
        except Exception as e:  # noqa: BLE001 — any mesh/runtime fault
            self._activate_fallback(snap, reason=type(e).__name__)
            raise ShardedMeshError(
                f"sharded dispatch on mesh {self.mesh.shape} failed "
                f"({type(e).__name__}: {e}); solo fallback armed — "
                "subsequent requests serve on one device") from e


class ShardedGenerationEngine:
    """Factory wrapper: a :class:`GenerationEngine` decoding TP-sharded.

    Construction order matters and is all this class adds: (1) validate
    the mesh divides the model (heads, vocab, feature dim, slots), (2)
    policy-place ``model.params_`` — the backend's jitted decode/prefill
    programs take params per call, so they compile partitioned from the
    first dispatch, (3) build the normal engine, (4) re-place the KV
    slab sharded ``P(None, "batch", "model", None, None)`` — slots over
    the batch axis, attention heads over the model axis — and keep it
    that way across ``backend.reset()`` (decode-failure recovery
    rebuilds the slab; the wrap re-shards it before the next dispatch).

    Use :func:`sharded_generation_engine`; instances ARE
    GenerationEngines (every queue/slot/watchdog/speculation behavior
    inherited by construction, not reimplementation).
    """

    def __new__(cls, *a, **kw):  # pragma: no cover — factory only
        raise TypeError("use sharded_generation_engine(...)")


def _validate_generation_mesh(model, mesh: ServingMesh,
                              n_slots: int) -> None:
    cfg = getattr(model, "cfg", None)
    if cfg is None or not hasattr(cfg, "n_heads"):
        raise ShardingPolicyError(
            f"sharded generation needs a TransformerLM (got "
            f"{type(model).__name__}); recurrent decode backends serve "
            "solo")
    nm, nb = mesh.n_model, mesh.n_data
    checks = [("n_heads", cfg.n_heads, nm), ("d_model", cfg.d_model, nm),
              ("vocab_size", cfg.vocab_size, nm), ("n_slots", n_slots, nb)]
    bad = [f"{name}={val} % {div}" for name, val, div in checks
           if val % div]
    if bad:
        raise ShardingPolicyError(
            f"mesh {mesh.shape} does not divide the model/slab: "
            + ", ".join(bad))


def sharded_generation_engine(model, mesh: ServingMesh,
                              policy: Optional[ShardingPolicy] = None,
                              **kwargs):
    """Build a :class:`GenerationEngine` whose params and KV slab live
    sharded on ``mesh`` (see :class:`ShardedGenerationEngine`).
    Returns the engine with ``serving_mesh``/``shard_policy``/
    ``shard_report`` attached."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.obs import flight as _flight
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    n_slots = int(kwargs.get("n_slots", 8))
    _validate_generation_mesh(model, mesh, n_slots)
    pol = policy if policy is not None else policy_for(model)
    _flight.record("mesh_build", surface="generation",
                   batch=mesh.n_data, model=mesh.n_model,
                   n_devices=mesh.n_devices, policy=pol.name)
    report = validate_policy(model.params_, mesh, pol)
    stats = _reshard.TransferStats()
    reshard_to_policy(model, mesh, pol, stats)
    _flight.record("shard_load", surface="generation", policy=pol.name,
                   total_bytes=report["total_bytes"],
                   per_device_bytes=report["per_device_bytes"],
                   replicated_bytes=report["replicated_bytes"],
                   device_bytes=int(stats.device_bytes),
                   host_bytes=int(stats.host_bytes))
    eng = GenerationEngine(model, **kwargs)
    eng.serving_mesh = mesh
    eng.shard_policy = pol
    eng.shard_report = report
    eng.shard_stats = stats

    # placed exactly as the programs hand the slabs back (this spelling
    # of the spec; an empty draft slab replicated on the mesh), so the
    # first dispatch and every later one see the same input shardings
    # and each program is traced once
    slab_sharding = NamedSharding(mesh.mesh, P(None, "batch", "model"))

    be = eng.backend

    def _place_slab():
        be._kc = jax.device_put(be._kc, slab_sharding)
        be._vc = jax.device_put(be._vc, slab_sharding)
        ld = getattr(be, "draft_layers", 0)
        # draft slabs are L-axis slices of the sharded slab: re-derive
        # so they inherit the placement (zero-size when drafting is off)
        if ld:
            be._dkc, be._dvc = be._kc[:ld], be._vc[:ld]
        else:
            be._dkc = jax.device_put(be._kc[:0], mesh.replicated())
            be._dvc = jax.device_put(be._vc[:0], mesh.replicated())
        if hasattr(be, "_slots_state"):
            # the slots' inputs of a backend that launches ahead: one
            # small array, whole on every device
            be._slots_state = jax.device_put(be._slots_state,
                                             mesh.replicated())

    orig_reset = be.reset

    def reset_sharded():
        orig_reset()
        _place_slab()

    be.reset = reset_sharded
    _place_slab()

    # the programs are traced with the mesh visible, so the routes to a
    # Pallas kernel (flash attention in prefill, the cache's column write
    # in decode) see axes GSPMD would partition over and keep to XLA (a
    # Mosaic call cannot be partitioned automatically)
    def on_mesh(solo):
        def run(*args, **kw):
            with jax.set_mesh(mesh.mesh):
                return solo(*args, **kw)
        return run

    for name in ("prefill", "decode", "launch", "verify", "draft"):
        if hasattr(be, name):
            setattr(be, name, on_mesh(getattr(be, name)))
    return eng

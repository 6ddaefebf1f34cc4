"""Model engine: jitted forward + bucket padding + atomic hot reload.

The engine owns the compiled serving surface for one model:

- a **pure jitted forward** ``fn(params, state, x, mask)`` built once
  per architecture (for :class:`MultiLayerNetwork` it closes over the
  layer graph only — params/state flow through as arguments, which is
  what makes zero-recompile hot reload possible);
- a **compile-count hook**: the traced function bumps a host counter at
  trace time, so ``engine.compile_count`` is exactly the number of
  distinct XLA programs built — the acceptance signal for "warmup
  pre-compiled everything, steady state never compiles";
- ``warmup()``: runs every shape the bucket policy can emit
  (``BucketPolicy.warmup_shapes``) through the forward at startup;
- **atomic hot-swap reload**: a reload builds a complete replacement
  snapshot (params, state, fn) off to the side — re-warming first if
  the architecture changed — and installs it with one reference
  assignment. Serving threads read the snapshot reference once per
  batch, so a batch is always computed entirely under one model:
  serving never observes a half-loaded or mixed model. Checkpoints come
  from ``train.faults.latest_valid_checkpoint`` (crash-safe, falls back
  past truncated newest) or an explicit zip path.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from deeplearning4j_tpu.obs.lockwitness import witnessed_lock
from deeplearning4j_tpu.serving.buckets import BucketPolicy
from deeplearning4j_tpu.serving.metrics import ServingMetrics


class _Snapshot:
    """One immutable serving model version. All fields are set before the
    snapshot becomes visible; after that it is only read."""

    __slots__ = ("model", "params", "state", "fn", "conf_json", "version",
                 "source", "loaded_at")

    def __init__(self, model, fn, conf_json, version, source):
        self.model = model
        self.params = model.params_
        self.state = model.state_
        self.fn = fn  # None → generic model.output fallback
        self.conf_json = conf_json
        self.version = int(version)
        self.source = source
        self.loaded_at = time.time()


def conf_example_shape(conf) -> Optional[Tuple[int, ...]]:
    """Per-example input shape declared by a configuration's input type
    (None when it declares none) — the one derivation shared by engine
    warmup, reload re-warming, and ``ZooModel.serving_input_shape``."""
    itype = getattr(conf, "input_type", None)
    if itype is None:
        # a ComputationGraph conf declares one type per network input
        itypes = getattr(conf, "input_types", None)
        if not itypes or len(itypes) != 1:
            return None
        itype = itypes[0]
    return tuple(itype.shape(1)[1:])


def resolve_checkpoint_source(source: str) -> str:
    """Resolve a checkpoint zip from a path or directory (newest VALID
    one via the fault-tolerance layer). An EXPLICIT zip path that fails
    validation falls back to the newest valid sibling in its directory
    instead of killing server start — a truncated newest checkpoint next
    to keep-last-k valid older snapshots is exactly the crash the
    retention policy exists for. Every fallback (this explicit-path one
    and the directory scan inside ``latest_valid_checkpoint``) emits a
    ``checkpoint_fallback`` flight event naming the SKIPPED path and the
    error class, so a truncated snapshot mid-publish shows up in the
    black box. Shared by engine construction, ``/reload``, and
    ``ModelRegistry.publish``."""
    from deeplearning4j_tpu.train.faults import (
        latest_valid_checkpoint,
        validate_checkpoint,
    )

    if os.path.isdir(source):
        return latest_valid_checkpoint(source)
    if not os.path.exists(source):
        # a missing path is a caller error (409 at the server), not a
        # corrupt checkpoint to route around
        raise FileNotFoundError(f"checkpoint {source!r} does not exist")
    ok, reason = validate_checkpoint(source)
    if ok:
        return source
    parent = os.path.dirname(os.path.abspath(source))
    fallback = (latest_valid_checkpoint(parent, missing_ok=True)
                if os.path.isdir(parent) else None)
    if fallback is None:
        raise ValueError(
            f"checkpoint {source!r} is invalid ({reason}) and no valid "
            f"sibling checkpoint exists in {parent!r}")
    import warnings

    warnings.warn(
        f"checkpoint {source!r} is invalid ({reason}); serving the "
        f"newest valid sibling {fallback!r} instead", stacklevel=3)
    from deeplearning4j_tpu.obs import flight as _flight
    from deeplearning4j_tpu.train.faults import checkpoint_error_class

    _flight.record("checkpoint_fallback", requested=str(source),
                   skipped=str(source), served=str(fallback),
                   error_class=checkpoint_error_class(reason),
                   reason=reason)
    return fallback


class InferenceEngine:
    """Serving engine over one model + bucket policy.

    ``mesh`` (a ``TrainingMesh``) shards each dispatched batch over the
    data axis (GSPMD: replicated params, batch-sharded input); bucket
    sizes must then be multiples of the data-axis size so shards are
    even — the default power-of-two buckets are filtered accordingly.
    """

    def __init__(self, model, buckets: Optional[BucketPolicy] = None,
                 mesh=None, checkpoint_dir: Optional[str] = None,
                 metrics: Optional[ServingMetrics] = None,
                 int8_serving: bool = False):
        # own copy: mesh filtering + oversize growth must never mutate a
        # policy object shared with another engine
        self.buckets = (buckets if buckets is not None
                        else BucketPolicy()).copy()
        self.mesh = mesh
        self.checkpoint_dir = checkpoint_dir
        self.metrics = metrics if metrics is not None else ServingMetrics()
        #: opt-in int8 weight-only quantization of the dense/output
        #: heads (nn/ops/int8_matmul.py): every snapshot this engine
        #: builds — init AND hot reloads — serves int8 weights with
        #: per-channel scales; the MODEL's params stay fp32 (training/
        #: checkpointing never see the quantized form)
        self.int8_serving = bool(int8_serving)
        self.int8_report: Optional[dict] = None
        if self.int8_serving and not hasattr(model, "layers"):
            raise TypeError(
                f"int8_serving needs a layered model with a functional "
                f"forward; {type(model).__name__} serves through the "
                "generic output path")
        self._compile_count = 0
        #: byte ledger of the snapshot placement (parallel/reshard.py);
        #: None for mesh-less engines (placement is implicit at dispatch)
        self.reshard_stats = None
        self._reload_lock = witnessed_lock("serving.reload")
        self._fingerprint: Optional[Tuple[float, int]] = None
        self.warm = False
        if mesh is not None and mesh.n_data > 1:
            # shards must be even: keep only buckets divisible by the
            # data axis (drops the small power-of-two defaults a 1-row
            # request would otherwise pad to)
            keep = [b for b in self.buckets.batch_buckets
                    if b % mesh.n_data == 0]
            dropped = [b for b in self.buckets.batch_buckets
                       if b % mesh.n_data]
            if not keep:
                raise ValueError(
                    f"no batch bucket in {self.buckets.batch_buckets} is "
                    f"divisible by the mesh data axis ({mesh.n_data}); "
                    "raise batch_limit or pass batch_buckets that are "
                    "multiples of it")
            if dropped:
                import warnings

                warnings.warn(
                    f"dropping batch buckets {dropped}: not divisible by "
                    f"the mesh data axis ({mesh.n_data}); serving with "
                    f"{keep}", stacklevel=2)
                self.buckets.batch_buckets = keep
        self._snap = self._build_snapshot(model, version=0, source="init")

    # -- construction -------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, source: str, **kwargs) -> "InferenceEngine":
        """Engine from a checkpoint zip or a checkpoint DIRECTORY (the
        newest valid checkpoint; corrupt/truncated ones are skipped —
        an explicit zip path that fails validation also falls back to
        its newest valid sibling). A directory also becomes the default
        ``/reload`` source.

        Checkpoints are topology-portable: the canonical entries carry
        no device-count assumptions, so a checkpoint written by an
        8-device training mesh serves on 1 device (or any ``mesh``)
        without a host-side re-gather — the train-on-N/serve-on-M leg
        of parallel/reshard.py. The reshard is recorded as
        ``reshard_start``/``reshard_done`` flight events with
        N→M provenance from the checkpoint's ``meta.json``."""
        from deeplearning4j_tpu.parallel import reshard as _reshard
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
        n_to = mesh.n_data if mesh is not None else 1
        with _reshard.reshard_event(n_from, n_to, surface="serving") as st:
            eng = cls(model, **kwargs)
            if eng.reshard_stats is not None:
                st.merge(eng.reshard_stats)
        eng._snap.source = path
        eng._fingerprint = cls._path_fingerprint(path)
        from deeplearning4j_tpu.obs import flight as _flight

        _flight.record("checkpoint_load", path=str(path), surface="serving")
        return eng

    @staticmethod
    def _path_fingerprint(path: str) -> Optional[Tuple[float, int]]:
        from deeplearning4j_tpu.train.faults import checkpoint_fingerprint

        try:
            return checkpoint_fingerprint(path)
        except OSError:
            return None

    def _build_snapshot(self, model, version: int, source) -> "_Snapshot":
        conf = getattr(model, "conf", None)
        conf_json = conf.to_json() if hasattr(conf, "to_json") else None
        fn = self._build_fn(model)
        if self.mesh is not None:
            # replicated placement through the reshard planner: same
            # device_put semantics as before, plus the byte ledger
            # (reshard_stats) the from_checkpoint N→M event reports
            from deeplearning4j_tpu.parallel import reshard as _reshard

            stats = _reshard.TransferStats()
            _reshard.place_model(model, self.mesh, stats)
            self.reshard_stats = stats
        snap = _Snapshot(model, fn, conf_json, version, source)
        if self.int8_serving:
            snap.params = self._quantize_params(model)
        return snap

    def _quantize_params(self, model):
        """Int8-quantize a model's params for a serving snapshot (the
        model object keeps its fp32 params). Mesh engines re-place the
        quantized leaves replicated."""
        if not hasattr(model, "layers"):
            # same guard as __init__ — a hot reload can hand this engine
            # a different-arch checkpoint that loads as a layer-less
            # model, and that must fail typed (reload refused, old
            # snapshot keeps serving), not AttributeError mid-swap
            raise TypeError(
                f"int8_serving needs a layered model with a functional "
                f"forward; {type(model).__name__} serves through the "
                "generic output path")
        from deeplearning4j_tpu.nn.ops.int8_matmul import (
            quantize_model_params,
        )

        qparams, report = quantize_model_params(model)
        self.int8_report = report
        from deeplearning4j_tpu.obs import flight as _flight

        _flight.record("int8_quantize", surface="serving", **report)
        if self.mesh is not None:
            qparams = jax.device_put(qparams, self.mesh.replicated())
        return qparams

    def _build_fn(self, model):
        """Pure jitted forward for models exposing the functional
        ``_forward`` (MultiLayerNetwork, and a ComputationGraph with one
        input and one output). Returns None for other models — they
        serve through ``model.output`` (no compile-count hook, still
        batched/bucketed/hot-swapped)."""
        if not hasattr(model, "_forward"):
            if not hasattr(model, "output"):
                raise TypeError(
                    f"{type(model).__name__} has neither _forward nor "
                    "output; cannot serve it")
            return None
        graph_out = None
        if hasattr(model, "output_single"):  # ComputationGraph surface
            if (len(model.conf.network_inputs) != 1
                    or len(model.conf.network_outputs) != 1):
                return None
            graph_out = model.conf.network_outputs[0]

        retraces = self.metrics.registry.counter(
            "jit_retraces_total",
            "distinct XLA programs traced per jitted function",
            labels={"fn": "serving_forward"})

        def run(params, state, x, fmask):
            # trace-time side effect: one bump per distinct input shape
            # (= per compiled XLA program). Never executes at run time.
            # Mirrored into the metrics registry (obs/trace.py retrace
            # monitor), so steady-state serving recompiles are a
            # scrapeable counter, not just an in-process int — and into
            # the flight recorder, so a recompile storm shows up in the
            # black box ordered against the requests it slowed down.
            self._compile_count += 1
            retraces.inc()
            from deeplearning4j_tpu.obs import flight as _flight

            _flight.record("retrace", fn="serving_forward",
                           shape=str(tuple(x.shape)))
            if graph_out is not None:
                acts, _, _, _ = model._forward(
                    params, state, (x,), train=False, rng=None,
                    fmasks=(fmask,))
                return acts[graph_out]
            y, _, _, _, _ = model._forward(params, state, x, train=False,
                                           rng=None, fmask=fmask)
            return y

        return jax.jit(run)

    def release(self) -> None:
        """Let go of the served snapshot's parameters and state (after
        shutdown): the snapshot holds them beside the model, so a caller
        that frees the model's own keeps the device memory until the last
        of the server's threads has dropped the engine."""
        self._snap.params = self._snap.state = None

    # -- properties ---------------------------------------------------------
    @property
    def compile_count(self) -> int:
        """Distinct XLA programs traced by this engine (all versions)."""
        return self._compile_count

    @property
    def compile_count_supported(self) -> bool:
        return self._snap.fn is not None

    @property
    def model_version(self) -> int:
        return self._snap.version

    @property
    def model(self):
        """The live snapshot's layer graph. NOTE: after a same-arch hot
        reload this is still the ORIGINAL model object (its layer graph
        carries the compiled programs); the weights actually served are
        the snapshot's params — read results through ``infer``, not
        ``model.output``."""
        return self._snap.model

    def describe(self) -> dict:
        snap = self._snap
        return {
            "model_type": type(snap.model).__name__,
            "version": snap.version,
            "source": str(snap.source),
            "loaded_at": snap.loaded_at,
            "num_params": (int(snap.model.num_params())
                           if hasattr(snap.model, "num_params") else None),
            "warm": self.warm,
            "compile_count": self._compile_count,
            "buckets": repr(self.buckets),
            "int8_serving": self.int8_serving,
            "int8_report": self.int8_report,
            # canary/rollback tooling keys on these: WHICH on-disk
            # checkpoint is live (content fingerprint, None for
            # fresh-weights engines) and which snapshot generation
            "checkpoint_fingerprint": (None if self._fingerprint is None
                                       else list(self._fingerprint)),
        }

    # -- inference ----------------------------------------------------------
    def example_shape(self) -> Optional[Tuple[int, ...]]:
        """Per-example input shape from the model conf's input type
        (None when the conf does not declare one — warmup then needs an
        explicit shape)."""
        return conf_example_shape(getattr(self._snap.model, "conf", None))

    def infer(self, x, mask=None) -> np.ndarray:
        """One bucketed forward: pad up to the bucket, run, slice back."""
        return self.infer_versioned(x, mask)[0]

    def infer_versioned(self, x, mask=None) -> Tuple[np.ndarray, int]:
        """:meth:`infer` plus the version of the snapshot that actually
        computed the result. The snapshot reference is read exactly once,
        so concurrent reloads can never mix model versions inside a call
        — and re-reading ``model_version`` after the call would
        misattribute results that raced a hot reload. This is the single
        serving override point: the HTTP server and ``infer`` both route
        through it (wrap THIS method for chaos/test tooling; warmup
        deliberately bypasses it to reach not-yet-published snapshots)."""
        snap = self._snap
        return self._infer_on(snap, x, mask), snap.version

    def _infer_on(self, snap: "_Snapshot", x, mask=None) -> np.ndarray:
        import time as _time

        from deeplearning4j_tpu.obs import trace as _trace
        from deeplearning4j_tpu.serving import rtrace as _rtrace

        x = np.asarray(x)
        t_orig = x.shape[1] if x.ndim >= 3 else None
        xp, mp, n = self.buckets.pad_batch(x, mask)
        t_padded = xp.shape[1] if t_orig is not None else None
        self.metrics.record_dispatch(xp.shape[0], real_rows=n)
        info = _rtrace.current_dispatch()
        if info is not None:
            info.bucket = int(xp.shape[0])
            info.rows_real = int(n)
            info.rows_padded = int(xp.shape[0])
            info.seq_real = t_orig
            info.seq_padded = t_padded
        with _trace.span("serving_dispatch"):
            y = self._forward_raw(snap, xp, mp)
        if info is not None:
            # async backends return from the dispatch before the device
            # finishes; the remaining device wait lands in the "slice"
            # interval (the first host read below blocks on it)
            info.t_forward_done = _time.monotonic()
        from deeplearning4j_tpu.serving.buckets import slice_result

        out = slice_result(y, n, t_orig, t_padded)
        if info is not None:
            info.t_sliced = _time.monotonic()
        return out

    def _forward_raw(self, snap: "_Snapshot", xp, mp=None) -> np.ndarray:
        """The exact-shape forward under ``snap`` — no bucket padding,
        no dispatch metrics. The dispatch core of :meth:`_infer_on`,
        and the primitive :meth:`retune_buckets` uses to pre-compile a
        CANDIDATE bucket set's shapes while the current policy is still
        the one serving traffic."""
        if snap.fn is None:
            m = snap.model
            if hasattr(m, "output_single"):  # ComputationGraph surface
                return m.output_single(xp,
                                       masks=None if mp is None else [mp])
            return m.output(xp, mask=mp)
        xd = xp
        md = mp
        if self.mesh is not None:
            xd = jax.device_put(xp, self.mesh.batch_sharded())
            if mp is not None:
                md = jax.device_put(mp, self.mesh.batch_sharded())
        return snap.fn(snap.params, snap.state, xd, md)

    # -- warmup -------------------------------------------------------------
    def _warm_snapshot(self, snap: "_Snapshot",
                       example_shape: Sequence[int],
                       verbose: bool = False) -> int:
        """Run every bucket shape through ``snap``'s forward; returns
        the shape count. Shared by startup warmup and reload re-warming."""
        shapes = self.buckets.warmup_shapes(tuple(example_shape))
        for full_shape, with_mask in shapes:
            x = np.zeros(full_shape, np.float32)
            mask = (np.ones(full_shape[:2], np.float32)
                    if with_mask else None)
            self._infer_on(snap, x, mask)
            if verbose:
                print(f"warmup {full_shape} mask={with_mask}", flush=True)
        return len(shapes)

    def warmup(self, example_shape: Optional[Sequence[int]] = None,
               verbose: bool = False) -> dict:
        """Pre-compile every bucket shape so steady-state serving never
        recompiles. Returns a report {shapes, compiles, seconds}."""
        shape = tuple(example_shape) if example_shape is not None \
            else self.example_shape()
        if shape is None:
            raise ValueError(
                "cannot infer the per-example input shape from the model "
                "conf; pass warmup(example_shape=...)")
        before = self._compile_count
        t0 = time.perf_counter()
        n_shapes = self._warm_snapshot(self._snap, shape, verbose=verbose)
        self.warm = True
        return {
            "shapes": n_shapes,
            "compiles": self._compile_count - before,
            "seconds": round(time.perf_counter() - t0, 3),
        }

    def retune_buckets(self, new_policy: BucketPolicy,
                       example_shape: Optional[Sequence[int]] = None
                       ) -> dict:
        """Adopt a new bucket set with **zero steady-state retraces**:
        pre-compile-before-switch.

        Under the reload lock (a retune and a hot reload must not
        interleave): copy the candidate policy, apply the same
        mesh-divisibility filter as ``__init__``, run every shape the
        candidate can emit through :meth:`_forward_raw` at its EXACT
        padded shape — jit caches the new programs while ``self.buckets``
        (the old policy) is still the one padding live traffic — then
        atomically ref-assign the new policy. In-flight ``_infer_on``
        calls read ``self.buckets`` once per request, so every request
        pads entirely under one policy or the other, and the first
        request after the swap hits an already-compiled program.

        Returns ``{shapes, compiles, seconds, buckets}`` — ``compiles``
        is the trace-counter delta during the pre-compile (the switch
        itself adds none; the bench asserts that)."""
        shape = tuple(example_shape) if example_shape is not None \
            else self.example_shape()
        if shape is None:
            raise ValueError(
                "cannot infer the per-example input shape from the model "
                "conf; pass retune_buckets(..., example_shape=...)")
        with self._reload_lock:
            pol = new_policy.copy()
            if self.mesh is not None and self.mesh.n_data > 1:
                keep = [b for b in pol.batch_buckets
                        if b % self.mesh.n_data == 0]
                if not keep:
                    raise ValueError(
                        f"no batch bucket in {pol.batch_buckets} is "
                        f"divisible by the mesh data axis "
                        f"({self.mesh.n_data})")
                pol.batch_buckets = keep
            snap = self._snap
            before = self._compile_count
            t0 = time.perf_counter()
            shapes = pol.warmup_shapes(shape)
            for full_shape, with_mask in shapes:
                x = np.zeros(full_shape, np.float32)
                mask = (np.ones(full_shape[:2], np.float32)
                        if with_mask else None)
                self._forward_raw(snap, x, mask)
            self.buckets = pol  # atomic ref swap: old policy until here
            return {
                "shapes": len(shapes),
                "compiles": self._compile_count - before,
                "seconds": round(time.perf_counter() - t0, 3),
                "buckets": list(pol.batch_buckets),
            }

    # -- hardware-efficiency profile ----------------------------------------
    def publish_cost_metrics(self, example_shape: Optional[Sequence[int]]
                             = None, bucket: Optional[int] = None
                             ) -> dict:
        """Static cost sheet of the serving forward (obs/cost.py):
        lower+compile the snapshot's jitted forward at ``bucket``
        (default: the largest batch bucket — the shape a loaded server
        actually runs) and publish FLOPs / bytes-accessed / peak-memory
        gauges plus a serving MFU gauge into this engine's metrics
        registry. The MFU throughput term is the measured
        ``serving_real_samples_total`` rate — REAL dispatched rows, so
        bucket pad waste counts against utilization, exactly as it
        should.
        Call once after ``warmup()`` (re-lowering per request would
        re-trace); returns the analysis dict."""
        from deeplearning4j_tpu.obs import cost as _cost

        snap = self._snap
        if snap.fn is None:
            return {"error": f"{type(snap.model).__name__} serves through "
                             "the generic output path; no compiled "
                             "forward to analyze"}
        shape = (tuple(example_shape) if example_shape is not None
                 else self.example_shape())
        if shape is None:
            return {"error": "cannot infer the per-example input shape; "
                             "pass example_shape=..."}
        b = int(bucket) if bucket is not None else self.buckets.batch_buckets[-1]
        seq = self.buckets.seq_buckets is not None and len(shape) >= 2
        if seq:
            # the time axis pads to a seq bucket at dispatch — analyze
            # the program the server actually runs, not a never-served
            # raw-T shape (which would also compile a fresh executable
            # right after warmup closed the shape set)
            shape = (self.buckets.seq_bucket_for(shape[0]),) + tuple(
                shape[1:])
        full = (b,) + tuple(shape)
        x = np.zeros(full, np.float32)
        mask = np.ones(full[:2], np.float32) if seq else None
        out = _cost.compiled_analysis(snap.fn, snap.params, snap.state,
                                      x, mask)
        out["bucket"] = b
        if "error" in out:
            return out
        reg = self.metrics.registry
        _cost.publish_step_cost(reg, "serving", out,
                                labels={"bucket": str(b)})
        flops_per_example = float(out.get("flops", 0.0)) / b
        bytes_per_example = float(out.get("bytes_accessed", 0.0)) / b
        out["flops_per_example"] = flops_per_example
        _cost.publish_utilization(
            reg, "serving",
            flops_per_unit=flops_per_example,
            bytes_per_unit=bytes_per_example,
            # REAL rows dispatched (all buckets), counted by the engine
            # itself — covers batcher traffic AND direct infer callers,
            # and excludes padding rows from "useful FLOPs"
            units_per_sec=_cost.family_rate_fn(
                reg, "serving_real_samples_total"))
        from deeplearning4j_tpu.obs import flight as _flight

        _flight.record("cost_published", step="serving", bucket=b,
                       flops_per_example=flops_per_example)
        return out

    # -- hot reload ---------------------------------------------------------
    def reload(self, source: Optional[str] = None, force: bool = False
               ) -> dict:
        """Atomically swap in a new model version.

        ``source``: checkpoint zip, checkpoint directory, or None for
        the engine's ``checkpoint_dir``. A reload that resolves to the
        checkpoint already serving is a no-op unless ``force`` (the
        fingerprint check makes a periodic ``/reload`` poll free).

        Same architecture (identical conf JSON) keeps the compiled
        forward — the swap is pure params/state, zero recompiles. A
        different architecture builds and (if the engine was warmed)
        warms a fresh forward BEFORE the swap, so serving latency never
        absorbs the compiles.
        """
        from deeplearning4j_tpu.train.model_serializer import (
            ModelGuesser,
            ModelSerializer,
        )

        src = source or self.checkpoint_dir
        if src is None:
            raise ValueError("no reload source: pass a checkpoint path or "
                             "configure checkpoint_dir")
        with self._reload_lock:
            path = resolve_checkpoint_source(src)
            fp = self._path_fingerprint(path)
            if (not force and fp is not None and fp == self._fingerprint
                    and str(path) == str(self._snap.source)):
                return {"reloaded": False, "version": self._snap.version,
                        "path": path, "reason": "unchanged"}
            # cheap validation + provenance peek before the full restore
            meta = ModelSerializer.checkpoint_meta(path)
            new_model = ModelGuesser.load_model_guess(path)
            old = self._snap
            conf = getattr(new_model, "conf", None)
            conf_json = conf.to_json() if hasattr(conf, "to_json") else None
            same_arch = (conf_json is not None
                         and conf_json == old.conf_json
                         and old.fn is not None)
            if same_arch:
                # pure weight swap: reuse the old layer graph + compiled
                # programs; only the param/state pytrees change (same
                # shapes → jit cache hits, zero recompiles)
                snap = _Snapshot.__new__(_Snapshot)
                snap.model = old.model
                snap.params = (self._quantize_params(new_model)
                               if self.int8_serving else new_model.params_)
                snap.state = new_model.state_
                snap.fn = old.fn
                snap.conf_json = old.conf_json
                snap.version = old.version + 1
                snap.source = path
                snap.loaded_at = time.time()
                if self.mesh is not None:
                    snap.params = jax.device_put(snap.params,
                                                 self.mesh.replicated())
                    snap.state = jax.device_put(snap.state,
                                                self.mesh.replicated())
            else:
                snap = self._build_snapshot(new_model,
                                            version=old.version + 1,
                                            source=path)
                if self.warm:
                    # warm the NEW snapshot before exposing it (its own
                    # input type — the architecture changed)
                    shape = (conf_example_shape(conf)
                             or self.example_shape())
                    if shape is not None:
                        self._warm_snapshot(snap, shape)
            self._snap = snap  # the atomic publish
            self._fingerprint = fp
            self.metrics.record_reload()
            from deeplearning4j_tpu.obs import flight as _flight

            _flight.record("hot_reload", version=snap.version,
                           path=str(path), same_arch=bool(same_arch))
            return {"reloaded": True, "version": snap.version, "path": path,
                    "same_arch": bool(same_arch),
                    "checkpoint_iteration": meta.get("iteration"),
                    "checkpoint_epoch": meta.get("epoch")}

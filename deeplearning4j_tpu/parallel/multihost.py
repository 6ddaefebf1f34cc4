"""Multi-host (multi-process) training: the TPU-native replacement for the
reference's Spark scaleout stack.

Reference surface being replaced (SURVEY.md §2.5, §3.4):
- ``SparkDl4jMultiLayer`` / ``SparkComputationGraph``
  (``spark/impl/multilayer/SparkDl4jMultiLayer.java:214``) — user facade;
- ``TrainingMaster`` SPI (``spark/api/TrainingMaster.java``) with
  ``ParameterAveragingTrainingMaster``
  (``spark/impl/paramavg/ParameterAveragingTrainingMaster.java:62``) and
  ``SharedTrainingMaster``
  (``spark/parameterserver/training/SharedTrainingMaster.java:57``);
- Spark RDD broadcast + tree-aggregate + Aeron parameter server transports.

TPU-native design: ONE process per host, bootstrapped with
``jax.distributed.initialize`` (the PJRT distributed runtime replaces the
Spark driver and the Aeron shard/controller bootstrapping,
``SharedTrainingMaster.java:425-431``). All hosts' devices form one global
``Mesh``; the SAME jitted train step used single-host is compiled with the
batch sharded over the global "data" axis — XLA inserts the gradient
all-reduce, riding ICI within a slice and DCN across hosts. There is no
parameter broadcast, no tree aggregation and no wire codec to write: the
collective IS the communication backend.

Semantics vs the reference masters:
- ParameterAveraging semantics (params equal on every host after each
  sync) hold trivially — SPMD keeps params bit-identical every step, which
  is averaging with frequency 1 and zero staleness. ``averaging_frequency``
  is accepted and documented as subsumed.
- The SharedTraining (compressed gradient) path's intra-slice job is also
  subsumed by ICI all-reduce; its DCN threshold-encoding trick lives in
  ``parallel/compression.py``.

Data plane: each host feeds its own slice of every global batch
(``ShardedDataSetIterator`` — the role of Spark's RDD partitioning,
``ExecuteWorkerFlatMap.java:42``); host-local arrays are assembled into
global sharded arrays with ``multihost_utils.host_local_array_to_global_array``.

Recovery is checkpoint-restart (SURVEY.md §5 failure detection): process 0
writes the standard ModelSerializer zip; every process restores it on
resume. Since PR 8 the restart is ELASTIC: checkpoints are device-count
portable (parallel/reshard.py), so the survivors of a host loss re-form a
smaller world (``reinitialize_for_survivors``), reload the newest valid
checkpoint and resume in place (train/faults.py ``ElasticFitDriver``)
instead of waiting for the lost host to be replaced.
"""

from __future__ import annotations

import os
import socket
import time
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import DataSetIterator
from deeplearning4j_tpu.parallel.mesh import TrainingMesh


# --------------------------------------------------------------------------
# bootstrap
# --------------------------------------------------------------------------
def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> "MultiHostContext":
    """Bootstrap the distributed runtime (one call per host process).

    On a real TPU pod each argument is inferred from the TPU environment
    (plain ``jax.distributed.initialize()``); for CPU-mesh testing or
    bare-metal clusters pass them explicitly. Replaces the Spark
    driver/executor bootstrap + Aeron shard/controller address selection
    (``SharedTrainingMaster.java:425-431``).
    """
    if not jax.distributed.is_initialized():
        if coordinator_address is None:
            jax.distributed.initialize()
        else:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
    return MultiHostContext()


class MultiHostContext:
    """Process-level view of the global device mesh."""

    def __init__(self):
        self.process_id = jax.process_index()
        self.num_processes = jax.process_count()
        self.global_devices = jax.devices()
        self.local_devices = jax.local_devices()

    @property
    def is_chief(self) -> bool:
        return self.process_id == 0

    def barrier(self, name: str = "barrier"):
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)

    def __repr__(self):
        return (
            f"MultiHostContext(process {self.process_id}/{self.num_processes}, "
            f"{len(self.local_devices)} local / {len(self.global_devices)} "
            "global devices)"
        )


# --------------------------------------------------------------------------
# elastic recovery (host loss): survivor roster + world re-formation
# --------------------------------------------------------------------------
def surviving_devices(lost_processes: Iterable[int]) -> list:
    """The global devices NOT owned by ``lost_processes`` — the roster
    an :class:`~deeplearning4j_tpu.train.faults.ElasticFitDriver` hands
    to ``TrainingMesh.shrink`` after a host drops out. (Single-host
    callers simulate host loss by dropping a device range instead; see
    ``train.faults.host_dropout_injection``.)"""
    lost = set(int(p) for p in lost_processes)
    return [d for d in jax.devices()
            if getattr(d, "process_index", 0) not in lost]


def reinitialize_for_survivors(coordinator_address: str,
                               num_processes: int,
                               process_id: int) -> "MultiHostContext":
    """Tear down the distributed runtime and re-bootstrap it as the
    smaller surviving world. Every survivor must call this with its NEW
    process id in the re-numbered [0, num_processes) world and the new
    coordinator (by convention the lowest surviving old id).

    This is the multihost half of elastic recovery; the state half —
    reload ``latest_valid_checkpoint`` and reshard onto the new mesh —
    is topology-independent (parallel/reshard.py), which is exactly why
    the checkpoint format stays canonical. A LIVE world cannot shrink
    (no barrier re-negotiation), so re-forming is shutdown +
    initialize, not an in-place membership change."""
    if jax.distributed.is_initialized():
        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 — the old world is already torn
            pass
    return initialize(coordinator_address=coordinator_address,
                      num_processes=num_processes,
                      process_id=process_id)


def free_port() -> int:
    """A free TCP port for the coordinator (test/laptop convenience)."""
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# --------------------------------------------------------------------------
# per-host data sharding
# --------------------------------------------------------------------------
def host_local_to_global(a, mesh, spec=None):
    """Host-local array → global jax.Array on ``mesh`` (single-process:
    plain device array). ``spec`` defaults to batch-sharded over "data".
    Shared by MultiHostNetwork and SharedTrainingMaster."""
    if a is None:
        return None
    if jax.process_count() == 1:
        import jax.numpy as _jnp

        return _jnp.asarray(a)
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as _P

    if spec is None:
        spec = _P("data")
    return multihost_utils.host_local_array_to_global_array(
        np.asarray(a), mesh, spec
    )


class ShardedDataSetIterator(DataSetIterator):
    """Slices every GLOBAL batch down to this host's shard.

    The base iterator must yield the SAME global batches in the SAME order
    on every host (deterministic seed / shared storage) — the contract
    Spark's partitioner provided by construction
    (``ParameterAveragingTrainingMaster.java:97-98`` repartitioning). Each
    host keeps rows ``[pid*per_host, (pid+1)*per_host)``.

    For genuinely host-partitioned storage (each host owns different
    files), feed each host's own iterator directly to the facade instead —
    the global batch is then the concatenation across hosts.
    """

    def __init__(self, base: DataSetIterator, num_shards: int, shard_index: int):
        if not 0 <= shard_index < num_shards:
            raise ValueError(f"shard_index {shard_index} not in [0, {num_shards})")
        self.base = base
        self.num_shards = num_shards
        self.shard_index = shard_index

    def _shard(self, ds: DataSet) -> DataSet:
        b = ds.features.shape[0]
        if b % self.num_shards:
            raise ValueError(
                f"global batch {b} not divisible by {self.num_shards} hosts"
            )
        per = b // self.num_shards
        lo = self.shard_index * per

        def sl(a):
            return None if a is None else a[lo:lo + per]

        return DataSet(
            sl(ds.features), sl(ds.labels),
            sl(ds.features_mask), sl(ds.labels_mask),
        )

    def has_next(self) -> bool:
        return self.base.has_next()

    def next(self) -> DataSet:
        return self._shard(self.base.next())

    def __iter__(self):
        for ds in self.base:
            yield self._shard(ds)

    def reset(self):
        self.base.reset()

    def async_supported(self) -> bool:
        return False


def host_sharded_loader(shard_dir: str, *, host_index: Optional[int] = None,
                        host_count: Optional[int] = None, **kwargs):
    """This host's :class:`~deeplearning4j_tpu.data.loader.ShardedLoader`
    over a packed shard directory — the genuinely host-partitioned
    alternative to :class:`ShardedDataSetIterator`'s slice-the-global-
    batch contract. Shard ownership is the static disjoint round-robin
    of ``data.shards.assign_host_shards`` (host h owns shards h, h+H,
    …), so every host derives the same partition with no coordination
    and the global batch at step *t* is the concat of each host's
    *t*-th batch, consistent with ``make_sharded_train_step``.

    ``host_index``/``host_count`` default to this process's JAX
    identity (``jax.process_index()`` / ``jax.process_count()``)."""
    from deeplearning4j_tpu.data.loader import ShardedLoader

    if host_index is None:
        host_index = jax.process_index()
    if host_count is None:
        host_count = jax.process_count()
    return ShardedLoader(shard_dir, host_index=int(host_index),
                         host_count=int(host_count),
                         pool=f"shard_loader_host{int(host_index)}",
                         **kwargs)


# --------------------------------------------------------------------------
# TrainingMaster SPI
# --------------------------------------------------------------------------
class TrainingMaster:
    """SPI mirroring ``spark/api/TrainingMaster.java``: owns the strategy
    for turning per-host batches into a globally-synchronized update."""

    def execute_training(self, facade: "MultiHostNetwork", it: DataSetIterator,
                         epochs: int = 1) -> None:
        raise NotImplementedError


class ParameterAveragingTrainingMaster(TrainingMaster):
    """Global-mesh synchronous DP (reference
    ``ParameterAveragingTrainingMaster.java:62``).

    The reference splits the RDD into ``averagingFrequency * batchSize *
    numWorkers`` chunks, fits each partition locally and tree-averages
    parameters. Here every step IS the average: gradients all-reduce over
    the global data axis before the update, so parameters never diverge
    between hosts and ``averaging_frequency``/``aggregation_depth`` have
    nothing left to amortize (kept as documented no-ops for API parity).
    """

    class Builder:
        def __init__(self, batch_size_per_worker: int = 16):
            self._batch = batch_size_per_worker
            self._avg_freq = 1
            self._agg_depth = 2
            self._prefetch = 2
            self._collect_stats = False
            self._sharded = False

        def sharded_update(self, b: bool):
            """ZeRO-1 weight update (parallel/zero.py): each replica
            updates only its 1/N shard of the flat parameter vector and
            keeps 1/N of the updater state; numerically identical to the
            replicated update."""
            self._sharded = bool(b)
            return self

        def batch_size_per_worker(self, n: int):
            self._batch = int(n)
            return self

        def averaging_frequency(self, n: int):
            self._avg_freq = int(n)  # subsumed by every-step all-reduce
            return self

        def aggregation_depth(self, n: int):
            self._agg_depth = int(n)  # XLA picks the reduction topology
            return self

        def worker_prefetch_num_batches(self, n: int):
            self._prefetch = int(n)
            return self

        def collect_training_stats(self, b: bool):
            self._collect_stats = bool(b)
            return self

        def build(self) -> "ParameterAveragingTrainingMaster":
            return ParameterAveragingTrainingMaster(
                self._batch, self._avg_freq, self._agg_depth,
                self._collect_stats, sharded_update=self._sharded,
            )

    def __init__(self, batch_size_per_worker: int = 16,
                 averaging_frequency: int = 1, aggregation_depth: int = 2,
                 collect_stats: bool = False, sharded_update: bool = False):
        self.batch_size_per_worker = batch_size_per_worker
        self.averaging_frequency = averaging_frequency
        self.aggregation_depth = aggregation_depth
        self.collect_stats = collect_stats
        self.sharded_update = bool(sharded_update)
        self.stats: list = []

    def execute_training(self, facade: "MultiHostNetwork", it: DataSetIterator,
                         epochs: int = 1) -> None:
        facade._fit_sharded(it, epochs=epochs, stats=(
            self.stats if self.collect_stats else None))


# --------------------------------------------------------------------------
# facade
# --------------------------------------------------------------------------
class MultiHostNetwork:
    """User facade — the ``SparkDl4jMultiLayer``/``SparkComputationGraph``
    equivalent (``SparkDl4jMultiLayer.java:214`` ``fit`` entry).

    Wraps a MultiLayerNetwork or ComputationGraph; every host constructs
    the same model (same config/seed ⇒ same initial params — the role of
    the reference's conf+params broadcast, ``NetBroadcastTuple``).
    """

    def __init__(self, model, training_master: TrainingMaster,
                 context: Optional[MultiHostContext] = None):
        self.model = model
        self.master = training_master
        self.ctx = context if context is not None else MultiHostContext()
        n = len(jax.devices())
        self.mesh = TrainingMesh(data=n, devices=jax.devices())
        self._step = None
        self._step_guarded = False
        self._zstep = None
        self._zstep_guarded = False
        self._zlayout = None
        self._is_graph = hasattr(model.conf, "network_inputs")

    # -- data plumbing ------------------------------------------------------
    def _to_global(self, a, batch_like: bool):
        spec = (jax.sharding.PartitionSpec("data") if batch_like
                else jax.sharding.PartitionSpec())
        return host_local_to_global(a, self.mesh.mesh, spec)

    def _pack_batch(self, ds: DataSet):
        if self._is_graph:
            from deeplearning4j_tpu.nn.graph import _as_multi

            mds = _as_multi(ds)
            return (
                tuple(self._to_global(f, True) for f in mds.features),
                tuple(self._to_global(l, True) for l in mds.labels),
                tuple(self._to_global(m, True) for m in mds.features_masks),
                tuple(self._to_global(m, True) for m in mds.labels_masks),
            )
        return (
            self._to_global(ds.features, True),
            self._to_global(ds.labels, True),
            self._to_global(ds.features_mask, True),
            self._to_global(ds.labels_mask, True),
        )

    def _fault_policy(self):
        from deeplearning4j_tpu.train import faults

        return faults.active_policy(
            getattr(self.model.conf.global_conf, "fault_policy", None),
            self.model._compute_dtype,
        )

    def _build_step(self, guarded: bool = False):
        raw = self.model.train_step_fn()
        repl = self.mesh.replicated()
        batch = self.mesh.batch_sharded()
        if guarded:  # extra fault-state carry after ``state`` (replicated)
            in_sh = (repl, repl, repl, repl, batch, batch, batch, batch,
                     repl, repl, repl)
            out_sh = (repl, repl, repl, repl, repl)
        else:
            in_sh = (repl, repl, repl, batch, batch, batch, batch,
                     repl, repl, repl)
            out_sh = (repl, repl, repl, repl)
        donate = (0, 1, 2)
        if guarded:
            from deeplearning4j_tpu.train.faults import guard_donation

            donate = guard_donation(0, 1, 2)
        self._step = jax.jit(
            raw, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=donate,
        )
        self._step_guarded = guarded
        return self._step

    # -- training -----------------------------------------------------------
    def fit(self, it: DataSetIterator, epochs: int = 1):
        """``it`` yields this host's LOCAL batches (use
        ShardedDataSetIterator over a deterministic global stream, or a
        host-partitioned source). Global batch = concat over hosts."""
        self.master.execute_training(self, it, epochs=epochs)
        return self.model

    def _fit_sharded(self, it: DataSetIterator, epochs: int = 1, stats=None):
        m = self.model
        policy = self._fault_policy()
        guarded = policy is not None
        if guarded:
            m._ensure_fault_state(policy)
        zopt = None
        if getattr(self.master, "sharded_update", False) or getattr(
                m.conf.global_conf, "sharded_update", False):
            from deeplearning4j_tpu.parallel.zero import (
                make_sharded_train_step,
                shard_model_opt_state,
                unshard_model_opt_state,
            )

            # key the cached step on the POLICY, not just guardedness —
            # see ParallelWrapper.fit
            if self._zstep is None or self._zstep_guarded != guarded \
                    or getattr(self, "_zstep_policy", None) != policy:
                self._zstep, self._zlayout = make_sharded_train_step(
                    m, self.mesh, policy=policy)
                self._zstep_guarded = guarded
                self._zstep_policy = policy
            step = self._zstep
            zopt = shard_model_opt_state(m, self._zlayout,
                                         mesh=self.mesh.mesh)
            # mid-fit serializers gather the live sharded slots through
            # this hook (m.opt_state_ is stale until the finally below)
            zlayout = self._zlayout
            zref = [zopt]
            m._opt_state_sync = (
                lambda: unshard_model_opt_state(m, zlayout, zref[0]))
        else:
            if self._step is None or self._step_guarded != guarded \
                    or getattr(self, "_step_policy", None) != policy:
                self._build_step(guarded=guarded)
                self._step_policy = policy
            step = self._step
        zopt_valid = True
        try:
            for _ in range(epochs):
                for lst in m.listeners:
                    if hasattr(lst, "on_epoch_start"):
                        lst.on_epoch_start(m)
                for ds in it:
                    t0 = time.perf_counter() if stats is not None else 0.0
                    opt_in = zopt if zopt is not None else m.opt_state_
                    batch = self._pack_batch(ds)
                    rng = m._next_rng()
                    # once the step is dispatched it consumes the donated
                    # zopt; if it raises, those buffers are gone and must
                    # not be gathered (batch packing above raising leaves
                    # zopt intact)
                    zopt_valid = zopt is None
                    if guarded:
                        (m.params_, new_o, m.state_, m.fault_state_,
                         m.score_) = step(
                            m.params_, opt_in, m.state_, m.fault_state_,
                            *batch, rng,
                            jnp.asarray(m.iteration, jnp.int32),
                            jnp.asarray(m.epoch, jnp.int32),
                        )
                    else:
                        m.params_, new_o, m.state_, m.score_ = step(
                            m.params_, opt_in, m.state_,
                            *batch, rng,
                            jnp.asarray(m.iteration, jnp.int32),
                            jnp.asarray(m.epoch, jnp.int32),
                        )
                    if zopt is not None:
                        zopt = new_o
                        zref[0] = new_o
                    zopt_valid = True
                    if zopt is None:
                        m.opt_state_ = new_o
                    m.iteration += 1
                    if guarded:
                        from deeplearning4j_tpu.train import faults as _faults

                        _faults.check_fault_state(policy, m.fault_state_, owner=m)
                    if stats is not None:
                        jax.block_until_ready(m.score_)
                        stats.append({
                            "iteration": m.iteration,
                            "step_seconds": time.perf_counter() - t0,
                        })
                    for lst in m.listeners:
                        lst.iteration_done(m, m.iteration, m.epoch)
                it.reset()
                m.epoch += 1
                for lst in m.listeners:
                    if hasattr(lst, "on_epoch_end"):
                        lst.on_epoch_end(m)
        finally:
            if zopt is not None:
                m._opt_state_sync = None
                if zopt_valid:
                    # canonical per-layer opt state restored for the
                    # checkpoint-restart story (save_checkpoint zips it)
                    unshard_model_opt_state(m, self._zlayout, zopt)
                # else: the step failed after consuming its donated zopt
                # buffers — keep the last canonical opt state rather than
                # masking the real error with a deleted-array gather

    # -- evaluation / scoring ----------------------------------------------
    def score(self) -> float:
        return float(self.model.score_)

    def evaluate(self, it: DataSetIterator, top_n: int = 1):
        """Distributed evaluation (reference
        ``spark/impl/multilayer/evaluation/IEvaluateFlatMapFunction.java``
        + ``IEvaluationReduceFunction``): each host evaluates its LOCAL
        shard of the data, then the merge-able Evaluation states
        (confusion counts, top-N tallies) are summed across processes —
        every host returns the identical global Evaluation."""
        from deeplearning4j_tpu.evaluation import Evaluation

        local = self.model.evaluate(it, top_n=top_n)
        if jax.process_count() == 1:
            return local
        from jax.experimental import multihost_utils

        n = local.num_classes or 0
        # fixed-size payload: confusion matrix + topN counters (+ n so
        # hosts that saw no data contribute zeros of the right shape)
        n_global = int(np.max(multihost_utils.process_allgather(
            np.asarray([n], np.int64))))
        conf = np.zeros((n_global, n_global), np.int64)
        if local.confusion is not None:
            m = np.asarray(local.confusion.matrix, np.int64)
            conf[: m.shape[0], : m.shape[1]] = m
        payload = np.concatenate([
            conf.reshape(-1),
            np.asarray([local.top_n_correct, local.top_n_total], np.int64),
        ])
        gathered = multihost_utils.process_allgather(payload)  # (procs, L)
        summed = np.asarray(gathered).sum(axis=0)
        merged = Evaluation(num_classes=n_global,
                            labels=local.label_names, top_n=local.top_n)
        from deeplearning4j_tpu.evaluation.classification import ConfusionMatrix

        cm = ConfusionMatrix(n_global)
        cm.matrix = summed[:-2].reshape(n_global, n_global)
        merged.confusion = cm
        merged.top_n_correct = int(summed[-2])
        merged.top_n_total = int(summed[-1])
        return merged

    # -- checkpoint-restart (the recovery story, SURVEY.md §5) --------------
    def save_checkpoint(self, path: str) -> None:
        """Chief writes the standard ModelSerializer zip; other hosts
        barrier so the file is complete before anyone proceeds."""
        from deeplearning4j_tpu.train.model_serializer import ModelSerializer

        if self.ctx.is_chief:
            ModelSerializer.write_model(self.model, path, save_updater=True)
        self.ctx.barrier("ckpt_save")

    def restore_checkpoint(self, path: str) -> None:
        """Every host restores the same checkpoint (params/updater state/
        iteration counters), re-establishing bit-identical state."""
        from deeplearning4j_tpu.train.model_serializer import ModelSerializer

        restored = (
            ModelSerializer.restore_computation_graph(path)
            if self._is_graph
            else ModelSerializer.restore_multi_layer_network(path)
        )
        m = self.model
        m.params_ = restored.params_
        m.state_ = restored.state_
        m.opt_state_ = restored.opt_state_
        m.iteration = restored.iteration
        m.epoch = restored.epoch
        m.fault_state_ = None  # re-seed good_count from restored iteration
        self._step = None  # donated-buffer jit must not reuse old avals
        self._zstep = None
        self._zlayout = None


# Reference-parity aliases (the reference has one facade per model type;
# here one class handles both, mirroring the type dispatch in fit()).
MultiHostDl4jMultiLayer = MultiHostNetwork
MultiHostComputationGraph = MultiHostNetwork

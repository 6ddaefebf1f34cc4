"""Pipelined training loop (train/pipeline.py): in-graph multi-step
bundling via lax.scan, device prefetch, sync-free listener path.

The backbone assertions are BIT-exactness: a fit at ``steps_per_call=K``
must leave params AND updater slots (Adam m/v incl. the bias-correction
clock) exactly equal to the same fit at K=1 — including a NaN batch
inside a bundle under a FaultPolicy, the ragged epoch tail, and every
data-parallel runtime (ParallelWrapper std + ZeRO-1, SharedTrainingMaster,
DistributedLMTrainer).
"""

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    AsyncDataSetIterator,
    BatchBundle,
    DeviceDataSet,
    ExistingDataSetIterator,
    ListDataSetIterator,
    iter_bundled,
)
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, LSTM, OutputLayer, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.train import faults, pipeline
from deeplearning4j_tpu.train.listeners import (
    CollectScoresIterationListener,
    ScoreIterationListener,
    TrainingListener,
)
from deeplearning4j_tpu.updaters import Adam


def _batches(n, b=8, d=12, c=3, seed=0):
    rng = np.random.default_rng(seed)
    return [
        DataSet(rng.standard_normal((b, d)).astype(np.float32),
                np.eye(c, dtype=np.float32)[rng.integers(0, c, b)])
        for _ in range(n)
    ]


def _mlp(k=1, fault_policy=None, seed=7):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
         .steps_per_call(k))
    if fault_policy is not None:
        b = b.fault_policy(fault_policy)
    conf = (b.list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(12)).build())
    return MultiLayerNetwork(conf).init()


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestBundledParity:
    def test_k4_bit_exact_incl_ragged_tail(self):
        """10 batches at K=4 → two bundles + two ragged singles per
        epoch; params, Adam slots and per-step scores must match K=1
        exactly over 2 epochs."""
        data = _batches(10)
        a, b = _mlp(1), _mlp(4)
        ca, cb = (CollectScoresIterationListener(frequency=1),
                  CollectScoresIterationListener(frequency=1))
        a.set_listeners(ca)
        b.set_listeners(cb)
        a.fit(ExistingDataSetIterator(data), epochs=1)
        b.fit(ExistingDataSetIterator(data), epochs=1)
        assert a.iteration == b.iteration == 10
        _assert_trees_equal(a.params_, b.params_)
        _assert_trees_equal(a.opt_state_, b.opt_state_)
        assert [i for i, _ in ca.scores] == [i for i, _ in cb.scores]
        np.testing.assert_array_equal(
            np.asarray([s for _, s in ca.scores], np.float32),
            np.asarray([s for _, s in cb.scores], np.float32))

    def test_nan_batch_inside_bundle_matches_unbundled_skip(self):
        """A NaN gradient at step 2 — mid-bundle at K=4 — must skip the
        update exactly as the unbundled guarded loop does: params AND
        Adam slots bit-equal, bad/good counters equal."""
        data = _batches(4)
        with faults.fault_injection(nan_grad_steps=[2]):
            a = _mlp(1, fault_policy=True)
            a.fit(ExistingDataSetIterator(data), epochs=1)
        with faults.fault_injection(nan_grad_steps=[2]):
            b = _mlp(4, fault_policy=True)
            b.fit(ExistingDataSetIterator(data), epochs=1)
        assert a.bad_step_count == b.bad_step_count == 1
        assert (int(a.fault_state_["good_count"])
                == int(b.fault_state_["good_count"]) == 3)
        _assert_trees_equal(a.params_, b.params_)
        _assert_trees_equal(a.opt_state_, b.opt_state_)

    def test_divergence_tripwire_trips_at_bundle_end(self):
        """The tripwire is checked once per bundle on the final consec: a
        bad streak filling the tail of a bundle still raises."""
        data = _batches(8)
        policy = faults.FaultPolicy(skip_nonfinite=True,
                                    max_consecutive_bad_steps=2)
        with faults.fault_injection(nan_grad_steps=[2, 3]):
            net = _mlp(4, fault_policy=policy)
            with pytest.raises(faults.TrainingDivergedError):
                net.fit(ExistingDataSetIterator(data), epochs=1)

    def test_computation_graph_bundled_parity(self):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((40, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 40)]

        def build(k):
            from deeplearning4j_tpu.nn.graph import ComputationGraph

            conf = (NeuralNetConfiguration.builder().seed(5)
                    .updater(Adam(1e-3)).steps_per_call(k)
                    .graph_builder()
                    .add_inputs("in")
                    .add_layer("d0", DenseLayer(n_out=8, activation="tanh"),
                               "in")
                    .add_layer("out", OutputLayer(n_out=3,
                                                  activation="softmax",
                                                  loss="mcxent"), "d0")
                    .set_outputs("out")
                    .set_input_types(InputType.feed_forward(4))
                    .build())
            return ComputationGraph(conf).init()

        a, b = build(1), build(2)
        a.fit(DataSet(x, y), epochs=2, batch_size=8)
        b.fit(DataSet(x, y), epochs=2, batch_size=8)
        assert a.iteration == b.iteration == 10
        _assert_trees_equal(a.params_, b.params_)
        _assert_trees_equal(a.opt_state_, b.opt_state_)


class TestBundlingLegality:
    def test_tbptt_rejects_bundling(self):
        conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
                .steps_per_call(4).list()
                .layer(LSTM(n_out=6))
                .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                      loss="mcxent"))
                .backprop_type("tbptt", fwd_length=4, back_length=4)
                .set_input_type(InputType.recurrent(3, 8))
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        f = rng.standard_normal((4, 8, 3)).astype(np.float32)
        l = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 8))]
        with pytest.raises(ValueError, match="tBPTT"):
            net.fit(DataSet(f, l))

    def test_per_step_host_hooks_force_k1(self):
        class BackwardHook(TrainingListener):
            def __init__(self):
                self.calls = 0

            def on_backward_pass(self, model):
                self.calls += 1

        data = _batches(4)
        net = _mlp(4)
        hook = BackwardHook()
        net.set_listeners(hook)
        assert pipeline.bundling_blockers([hook]) == [
            "BackwardHook.on_backward_pass"]
        assert pipeline.resolve_steps_per_call(net) == 1
        net.fit(ExistingDataSetIterator(data), epochs=1)
        assert hook.calls == 4  # every step ran unbundled

    def test_state_coupled_listeners_force_k1(self, tmp_path):
        """Iteration-triggered CheckpointListener (and ProfilerListener)
        snapshot the MODEL per iteration — post-bundle replay would hand
        them end-of-bundle state, so they force K=1; epoch-triggered
        checkpoints bundle fine."""
        from deeplearning4j_tpu.train.listeners import (
            CheckpointListener,
            ProfilerListener,
        )

        per_iter = CheckpointListener(str(tmp_path),
                                      save_every_n_iterations=1)
        per_epoch = CheckpointListener(str(tmp_path),
                                       save_every_n_epochs=1)
        prof = ProfilerListener(str(tmp_path))
        assert pipeline.bundling_blockers([per_iter]) == [
            "CheckpointListener.requires_per_step_state"]
        assert pipeline.bundling_blockers([prof]) == [
            "ProfilerListener.requires_per_step_state"]
        assert pipeline.bundling_blockers([per_epoch]) == []
        net = _mlp(4)
        net.set_listeners(per_iter)
        assert pipeline.resolve_steps_per_call(net) == 1
        net.set_listeners(per_epoch)
        assert pipeline.resolve_steps_per_call(net) == 4

    def test_stats_listener_bundles(self):
        """StatsListener (default config) no longer forces K=1: the
        per-step signals it used to snapshot from live params now arrive
        through the in-graph telemetry stream (obs/telemetry.py), and
        param summaries are taken at bundle granularity. Only the opt-in
        introspection collections still block bundling — they genuinely
        need per-step gradient/activation tensors."""
        from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener

        stats = StatsListener(InMemoryStatsStorage(), session_id="audit")
        assert pipeline.bundling_blockers([stats]) == []
        net = _mlp(4)
        net.set_listeners(stats)
        assert pipeline.resolve_steps_per_call(net) == 4
        grads = StatsListener(InMemoryStatsStorage(), session_id="audit2",
                              collect_gradients=True)
        assert pipeline.bundling_blockers([grads]) == [
            "StatsListener.on_gradient_calculation"]
        net.set_listeners(grads)
        assert pipeline.resolve_steps_per_call(net) == 1
        net.set_listeners()
        assert pipeline.resolve_steps_per_call(net) == 4

    def test_evaluative_listener_iteration_end_forces_k1(self):
        from deeplearning4j_tpu.train.listeners import EvaluativeListener

        per_iter = EvaluativeListener(None, invocation="iteration_end")
        per_epoch = EvaluativeListener(None, invocation="epoch_end")
        assert pipeline.bundling_blockers([per_iter]) == [
            "EvaluativeListener.requires_per_step_state"]
        assert pipeline.bundling_blockers([per_epoch]) == []

    def test_composable_listener_reports_children_not_itself(self):
        """ComposableIterationListener's delegating hook overrides must
        not read as always-blocking: it reports its CHILDREN's needs."""
        from deeplearning4j_tpu.train.listeners import (
            ComposableIterationListener,
        )

        plain = ComposableIterationListener(
            ScoreIterationListener(printer=lambda s: None))
        assert pipeline.bundling_blockers([plain]) == []

        class BackwardHook(TrainingListener):
            def on_backward_pass(self, model):
                pass

        nested = ComposableIterationListener(BackwardHook())
        assert pipeline.bundling_blockers([nested]) == [
            "BackwardHook.on_backward_pass"]

    def test_composable_children_keep_sync_free_path(self, monkeypatch):
        """A composed CollectScores listener keeps the once-per-bundle
        fetch (the composite delegates bundle_done, it doesn't fall to
        the per-step model.score() replay)."""
        from deeplearning4j_tpu.train.listeners import (
            ComposableIterationListener,
        )

        data = _batches(8)
        net = _mlp(4)
        cs = CollectScoresIterationListener(frequency=1)
        net.set_listeners(ComposableIterationListener(cs))

        def banned_score(ds=None):
            raise AssertionError("model.score() sync inside a bundled fit")

        monkeypatch.setattr(net, "score", banned_score)
        before = pipeline._host_fetches
        net.fit(ExistingDataSetIterator(data), epochs=1)
        assert pipeline._host_fetches - before == 2  # one per bundle
        assert [i for i, _ in cs.scores] == list(range(1, 9))

    def test_shape_change_flushes_to_singles(self):
        small = _batches(3, b=8)
        big = _batches(3, b=16, seed=1)
        items = list(iter_bundled(iter(small + big), 2))
        kinds = [type(i).__name__ for i in items]
        # 1 bundle of 8s, ragged 8 flushed as single, 1 bundle of 16s,
        # ragged 16 single
        assert kinds == ["BatchBundle", "DataSet", "BatchBundle", "DataSet"]
        assert items[0].features.shape == (2, 8, 12)
        assert items[2].features.shape == (2, 16, 12)


class TestSyncFreeListeners:
    def test_bundle_scores_fetched_once_no_model_score_sync(self,
                                                            monkeypatch):
        """Inside a bundled fit, Score/CollectScores listeners must never
        call model.score() (a per-step host sync) and must fetch the
        stacked device losses at most once per bundle."""
        data = _batches(8)
        baseline = _mlp(1)
        cb0 = CollectScoresIterationListener(frequency=1)
        baseline.set_listeners(cb0)
        baseline.fit(ExistingDataSetIterator(data), epochs=1)

        net = _mlp(4)
        printed = []
        cs = CollectScoresIterationListener(frequency=1)
        si = ScoreIterationListener(print_iterations=2,
                                    printer=printed.append)
        net.set_listeners(cs, si)

        def banned_score(ds=None):
            raise AssertionError(
                "model.score() host sync inside a bundled fit")

        monkeypatch.setattr(net, "score", banned_score)
        fetches_before = pipeline._host_fetches
        net.fit(ExistingDataSetIterator(data), epochs=1)
        # 8 batches at K=4 = 2 bundles; one shared host fetch per bundle
        assert pipeline._host_fetches - fetches_before == 2
        assert len(printed) == 4  # iterations 2, 4, 6, 8
        np.testing.assert_array_equal(
            np.asarray([s for _, s in cs.scores], np.float32),
            np.asarray([s for _, s in cb0.scores], np.float32))

    def test_no_fetch_when_no_reporting_hit(self):
        """A bundle containing no reporting iteration must not fetch at
        all (ScoreIterationListener at a sparse frequency)."""
        data = _batches(4)
        net = _mlp(4)
        net.set_listeners(ScoreIterationListener(print_iterations=100,
                                                 printer=lambda s: None))
        before = pipeline._host_fetches
        net.fit(ExistingDataSetIterator(data), epochs=1)
        assert pipeline._host_fetches == before

    def test_legacy_listener_gets_per_step_device_score(self):
        """Listeners without bundle_done keep their per-step
        iteration_done contract, with model.score_ rebound to the step's
        device scalar."""
        seen = []

        class Legacy(TrainingListener):
            def iteration_done(self, model, iteration, epoch):
                seen.append((iteration, float(model.score_)))

        data = _batches(4)
        a = _mlp(1)
        la = Legacy()
        a.set_listeners(la)
        a.fit(ExistingDataSetIterator(data), epochs=1)
        ref = list(seen)
        seen.clear()
        b = _mlp(4)
        b.set_listeners(Legacy())
        b.fit(ExistingDataSetIterator(data), epochs=1)
        assert [i for i, _ in seen] == [i for i, _ in ref] == [1, 2, 3, 4]
        np.testing.assert_array_equal(
            np.asarray([s for _, s in seen], np.float32),
            np.asarray([s for _, s in ref], np.float32))


class TestPrefetchAndConf:
    def test_async_device_put_and_bundle_stages(self):
        data = _batches(5)
        it = AsyncDataSetIterator(ExistingDataSetIterator(data),
                                  queue_size=2, device_put=True,
                                  bundle_size=2)
        items = list(it)
        assert [type(i).__name__ for i in items] == [
            "BatchBundle", "BatchBundle", "DeviceDataSet"]
        assert isinstance(items[0].features, jax.Array)
        assert items[0].features.shape == (2, 8, 12)
        assert isinstance(items[2].features, jax.Array)
        # reset restarts the producer with the same stages
        it.reset()
        again = list(it)
        assert [type(i).__name__ for i in again] == [
            "BatchBundle", "BatchBundle", "DeviceDataSet"]

    def test_bundled_shutdown_does_not_drain_inner(self):
        """shutdown() mid-stream must stop the bundling producer promptly
        — not let it run the inner iterator to exhaustion (it would never
        return on an unbounded stream)."""
        inner = ExistingDataSetIterator(_batches(400))
        it = AsyncDataSetIterator(inner, queue_size=1, bundle_size=4)
        assert isinstance(next(iter(it)), BatchBundle)
        it.shutdown()
        assert inner._pos < 60  # staged a few bundles, nowhere near 400

    def test_performance_listener_times_whole_bundles(self):
        """PerformanceListener under bundling measures across bundles —
        the per-step replay would divide by ~0 wall time."""
        from deeplearning4j_tpu.train.listeners import PerformanceListener

        printed = []
        net = _mlp(4)
        net.set_listeners(PerformanceListener(frequency=4,
                                              printer=printed.append))
        net.fit(ExistingDataSetIterator(_batches(12)), epochs=1)
        # first bundle seeds the clock; bundles 2 and 3 report
        assert len(printed) == 2
        for line in printed:
            rate = float(line.split(":")[1].split()[0])
            assert np.isfinite(rate) and rate > 0

    def test_bundle_unstack_roundtrip(self):
        data = _batches(3)
        bundle = BatchBundle.stack(data[:3])
        singles = bundle.unstack()
        assert len(singles) == 3
        for orig, back in zip(data, singles):
            np.testing.assert_array_equal(orig.features,
                                          np.asarray(back.features))
            np.testing.assert_array_equal(orig.labels,
                                          np.asarray(back.labels))

    def test_queue_size_configurable_via_conf(self, monkeypatch):
        captured = {}
        real = AsyncDataSetIterator

        def spy(inner, queue_size=4, **kw):
            captured["queue_size"] = queue_size
            return real(inner, queue_size=queue_size, **kw)

        import deeplearning4j_tpu.nn.multilayer as mln_mod

        monkeypatch.setattr(mln_mod, "AsyncDataSetIterator", spy)
        conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
                .async_queue_size(2).list()
                .layer(DenseLayer(n_out=4, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(12)).build())
        net = MultiLayerNetwork(conf).init()
        net.fit(ExistingDataSetIterator(_batches(2)), epochs=1)
        assert captured["queue_size"] == 2

    def test_queue_depth_scaled_down_by_bundle_size(self, monkeypatch):
        """Each queue slot holds K batches under bundling; the slot count
        scales down so the staged-batch budget stays at the k=1 level."""
        captured = {}
        real = AsyncDataSetIterator

        def spy(inner, queue_size=4, **kw):
            captured["queue_size"] = queue_size
            captured["bundle_size"] = kw.get("bundle_size", 1)
            return real(inner, queue_size=queue_size, **kw)

        import deeplearning4j_tpu.nn.multilayer as mln_mod

        monkeypatch.setattr(mln_mod, "AsyncDataSetIterator", spy)
        conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
                .steps_per_call(4).async_queue_size(8).list()
                .layer(DenseLayer(n_out=4, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(12)).build())
        net = MultiLayerNetwork(conf).init()
        net.fit(ExistingDataSetIterator(_batches(4)), epochs=1)
        assert captured == {"queue_size": 2, "bundle_size": 4}

    def test_conf_serde_roundtrip(self):
        from deeplearning4j_tpu.nn.conf.builders import (
            MultiLayerConfiguration,
        )

        conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
                .steps_per_call(8).async_queue_size(6).list()
                .layer(DenseLayer(n_out=4))
                .layer(OutputLayer(n_out=2, loss="mcxent"))
                .set_input_type(InputType.feed_forward(3)).build())
        back = MultiLayerConfiguration.from_json(conf.to_json())
        assert back.global_conf.steps_per_call == 8
        assert back.global_conf.async_queue_size == 6


class TestDataParallelBundling:
    def test_parallel_wrapper_bundled_parity(self):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        data = _batches(5)
        a, b = _mlp(1), _mlp(2)
        ParallelWrapper(a, workers=4).fit(ExistingDataSetIterator(data))
        ParallelWrapper(b, workers=4).fit(ExistingDataSetIterator(data))
        assert a.iteration == b.iteration == 5
        _assert_trees_equal(a.params_, b.params_)
        _assert_trees_equal(a.opt_state_, b.opt_state_)

    def test_parallel_wrapper_skips_bundling_when_always_padding(self):
        """A batch size never divisible by the data axis means no bundle
        could ever run — the wrapper clamps to k=1 up front instead of
        stacking and unstacking every bundle."""
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        data = _batches(4, b=6)  # 6 % 4 != 0: every batch padded
        a, b = _mlp(1), _mlp(2)
        pa, pb = (ParallelWrapper(a, workers=4),
                  ParallelWrapper(b, workers=4))
        pa.fit(ExistingDataSetIterator(data))
        pb.fit(ExistingDataSetIterator(data))
        assert pb._bstep is None  # bundled step never built
        _assert_trees_equal(a.params_, b.params_)

    def test_parallel_wrapper_zero1_bundled_parity(self):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        data = _batches(4)
        a, b = _mlp(1), _mlp(2)
        ParallelWrapper(a, workers=4, sharded_update=True).fit(
            ExistingDataSetIterator(data))
        ParallelWrapper(b, workers=4, sharded_update=True).fit(
            ExistingDataSetIterator(data))
        _assert_trees_equal(a.params_, b.params_)
        _assert_trees_equal(a.opt_state_, b.opt_state_)

    def test_shared_training_bundled_parity(self):
        from deeplearning4j_tpu.parallel.mesh import TrainingMesh
        from deeplearning4j_tpu.parallel.shared_training import (
            SharedTrainingMaster,
        )

        data = _batches(3)
        a, b = _mlp(1), _mlp(2)
        sa = SharedTrainingMaster(mesh=TrainingMesh(data=8))
        sb = SharedTrainingMaster(mesh=TrainingMesh(data=8))
        sa.fit(a, ExistingDataSetIterator(data), epochs=1)
        sb.fit(b, ExistingDataSetIterator(data), epochs=1)
        assert a.iteration == b.iteration == 3
        _assert_trees_equal(a.params_, b.params_)
        # the residual carry threads the scan identically
        assert sa.residual_magnitude() == sb.residual_magnitude()

    def test_lm_trainer_fit_bundle_parity(self):
        from deeplearning4j_tpu.models.transformer_lm import TransformerLM
        from deeplearning4j_tpu.parallel.mesh import TrainingMesh
        from deeplearning4j_tpu.parallel.transformer import (
            DistributedLMTrainer,
        )

        rng = np.random.default_rng(0)
        ids = rng.integers(0, 64, (2, 8, 8)).astype(np.int32)
        tgt = np.roll(ids, -1, axis=2).astype(np.int32)

        def build():
            m = TransformerLM(vocab_size=64, d_model=16, n_heads=2,
                              n_layers=1, max_length=8).init()
            tr = DistributedLMTrainer(m, TrainingMesh(data=8),
                                      steps_per_call=2)
            tr.place()
            return m, tr

        ma, ta = build()
        mb, tb = build()
        for j in range(2):
            ta.fit_batch(ids[j], tgt[j])
        scores = tb.fit_bundle(ids, tgt)
        assert scores.shape == (2,)
        assert ma.iteration == mb.iteration == 2
        # the single step and the scanned bundle are two programs over
        # an 8-way mesh: the compiler may contract multiply-adds and
        # order the gradient sums differently in each (the XLA:CPU of
        # jax 0.9 does), so the contract is float32 reassociation —
        # 1e-5 of each tensor's scale — not bits
        for x, y in zip(
                jax.tree_util.tree_leaves((ma.params_, ma.opt_state_)),
                jax.tree_util.tree_leaves((mb.params_, mb.opt_state_))):
            x, y = np.asarray(x), np.asarray(y)
            np.testing.assert_allclose(
                x, y, rtol=1e-5, atol=1e-5 * float(np.max(np.abs(y))))


@pytest.mark.slow
def test_bundle_storm_k16():
    """K=16 storm: a long bundled fit with a fault policy and NaN bursts
    stays bit-identical to the unbundled run."""
    data = _batches(64)
    with faults.fault_injection(nan_grad_steps=[5, 17, 18, 40]):
        a = _mlp(1, fault_policy=True)
        a.fit(ExistingDataSetIterator(data), epochs=2)
    with faults.fault_injection(nan_grad_steps=[5, 17, 18, 40]):
        b = _mlp(16, fault_policy=True)
        b.fit(ExistingDataSetIterator(data), epochs=2)
    assert a.bad_step_count == b.bad_step_count
    _assert_trees_equal(a.params_, b.params_)
    _assert_trees_equal(a.opt_state_, b.opt_state_)

"""Step-level fault tolerance (train/faults.py + threading through every
fit path).

Core contract (ISSUE 2 acceptance): with ``skip_nonfinite`` on, a fit
whose batch k produces NaN gradients finishes with params equal to the
same fit with batch k removed — EXACT for the replicated paths (the skip
is a jnp.where on the old buffers and the updater clock runs on the
in-graph good-step count, so trajectories coincide bit for bit), and
parity holds under the ZeRO-1 sharded update. Crash-safety: an
interrupted ``write_model`` never corrupts the previously visible
checkpoint, and ``load_latest_valid`` skips truncated/corrupt newest
checkpoints back to the last good one.

All tests here are single-process tier-1 speed; the multi-process
SIGKILL + truncation drill lives in test_multihost.py (slow tier).
"""

import os
import zipfile

import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet, ExistingDataSetIterator
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.train import faults
from deeplearning4j_tpu.train.faults import (
    FaultPolicy,
    TrainingDivergedError,
    fault_injection,
)
from deeplearning4j_tpu.updaters import Adam

N_IN, N_HID, N_OUT = 5, 7, 3


def _net(policy=None, mixed_precision=False, seed=3):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
    if mixed_precision:
        b = b.compute_dtype("bfloat16")
    if policy is not None:
        b = b.fault_policy(policy)
    conf = (
        b.list()
        .layer(DenseLayer(n_out=N_HID, activation="tanh"))
        .layer(OutputLayer(n_out=N_OUT, activation="softmax"))
        .set_input_type(InputType.feed_forward(N_IN))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _batches(n=4, per=8, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.standard_normal((per, N_IN)).astype(np.float32)
        y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, per)]
        out.append(DataSet(x, y))
    return out


def _assert_same_math(a, b, rel=None):
    """Two DIFFERENT compiled programs computing the same math (guarded
    vs unguarded, poisoned-and-skipped vs batch-removed): a compiler may
    contract a multiply-add into one rounding in one program and two in
    the other (the XLA:CPU of jax 0.9 does), so the contract is equality
    to one float32 ulp of the tensor's largest element, not bits.
    ``rel``: where the programs also SUM gradients over a mesh in
    another order, float32 reassociation — ``rel`` of the tensor's scale."""
    a, b = np.asarray(a), np.asarray(b)
    top = np.float32(np.max(np.abs(b)))
    np.testing.assert_allclose(
        a, b, rtol=rel or 0,
        atol=float(np.spacing(top) if rel is None else rel * top))


class TestNonFiniteGuard:
    def test_nan_step_skipped_same_as_batch_removed(self):
        """Inject NaN grads at step 1 of 4: the run must equal the same
        fit with batch 1 removed — params AND updater state."""
        batches = _batches()
        with fault_injection(nan_grad_steps=[1]):
            a = _net(FaultPolicy())
            a.fit(ExistingDataSetIterator(batches))
        b = _net()
        b.fit(ExistingDataSetIterator(
            [batches[0], batches[2], batches[3]]))
        _assert_same_math(a.params_flat(), b.params_flat())
        _assert_same_math(a.opt_state_flat(), b.opt_state_flat())
        assert a.bad_step_count == 1
        assert int(a.fault_state_["good_count"]) == 3
        assert int(a.fault_state_["consec"]) == 0  # reset by good steps
        # the host iteration counter still counts every batch seen
        assert a.iteration == 4

    def test_guard_enabled_without_faults_is_a_noop(self):
        batches = _batches()
        a = _net(FaultPolicy())
        a.fit(ExistingDataSetIterator(batches), epochs=2)
        b = _net()
        b.fit(ExistingDataSetIterator(batches), epochs=2)
        _assert_same_math(a.params_flat(), b.params_flat())
        assert a.bad_step_count == 0

    def test_max_consecutive_bad_steps_raises(self):
        batches = _batches()
        with fault_injection(nan_grad_steps=[0, 1, 2, 3]):
            a = _net(FaultPolicy(max_consecutive_bad_steps=2))
            with pytest.raises(TrainingDivergedError, match="consecutive"):
                a.fit(ExistingDataSetIterator(batches))
        assert a.bad_step_count == 2  # raised at the limit, not after

    def test_nonconsecutive_bad_steps_do_not_raise(self):
        batches = _batches()
        with fault_injection(nan_grad_steps=[0, 2]):
            a = _net(FaultPolicy(max_consecutive_bad_steps=2))
            a.fit(ExistingDataSetIterator(batches))
        assert a.bad_step_count == 2

    def test_computation_graph_guard(self):
        """The same skip-exactness through the ComputationGraph step."""
        batches = _batches()
        with fault_injection(nan_grad_steps=[1]):
            a = _net(FaultPolicy()).to_computation_graph()
            a.fit(ExistingDataSetIterator(batches))
        b = _net().to_computation_graph()
        b.fit(ExistingDataSetIterator([batches[0], batches[2], batches[3]]))
        for name in a.layer_names:
            for k in a.params_[name]:
                _assert_same_math(a.params_[name][k], b.params_[name][k])
        assert a.bad_step_count == 1

    def test_tbptt_chunk_guard_skips_batch(self):
        """tBPTT path: a poisoned batch (all its chunks) leaves params,
        opt state and carries untouched; clean batches still train."""
        from deeplearning4j_tpu.nn.conf.layers import (
            RnnOutputLayer,
            SimpleRnn,
        )

        def rnn_net(policy=None):
            b = NeuralNetConfiguration.builder().seed(3).updater(Adam(0.01))
            if policy is not None:
                b = b.fault_policy(policy)
            conf = (
                b.list()
                .layer(SimpleRnn(n_out=6))
                .layer(RnnOutputLayer(n_out=2, activation="softmax"))
                .set_input_type(InputType.recurrent(4, 8))
                .backprop_type("tbptt", fwd_length=4, back_length=4)
                .build()
            )
            return MultiLayerNetwork(conf).init()

        rng = np.random.default_rng(1)
        x = rng.standard_normal((6, 8, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[
            rng.integers(0, 2, (6, 8))].astype(np.float32)
        ds = DataSet(x, y)
        with fault_injection(nan_grad_steps=[1]):
            n = rnn_net(FaultPolicy())
            n.fit(ds, epochs=1, batch_size=6)  # iteration 0: clean
            before = n.params_flat().copy()
            n.fit(ds, epochs=1, batch_size=6)  # iteration 1: poisoned
            np.testing.assert_array_equal(before, n.params_flat())
            n.fit(ds, epochs=1, batch_size=6)  # trains again
        assert n.bad_step_count == 2  # both chunks of the bad batch
        assert not np.array_equal(before, n.params_flat())
        assert np.isfinite(n.params_flat()).all()

    def test_policy_json_roundtrip(self):
        pol = FaultPolicy(max_consecutive_bad_steps=7, keep_last=2,
                          init_loss_scale=2.0 ** 10)
        net = _net(pol)
        clone = type(net.conf).from_json(net.conf.to_json())
        assert clone.global_conf.fault_policy == pol


class TestDynamicLossScaling:
    def test_backoff_and_regrow_trace(self):
        """bf16 compute: scale grows x2 after 2 good steps, halves on the
        injected overflow, then recovers — the canonical trace."""
        pol = FaultPolicy(init_loss_scale=2.0 ** 8, scale_growth_interval=2)
        ds = _batches(1)[0]
        with fault_injection(nan_grad_steps=[2]):
            n = _net(pol, mixed_precision=True)
            scales = []
            for _ in range(6):
                n.fit(ds, epochs=1, batch_size=8)
                scales.append(n.loss_scale)
        assert scales == [256.0, 512.0, 256.0, 256.0, 512.0, 512.0]
        assert n.bad_step_count == 1

    def test_scale_floor(self):
        pol = FaultPolicy(init_loss_scale=2.0, min_loss_scale=1.0,
                          scale_growth_interval=100)
        ds = _batches(1)[0]
        with fault_injection(nan_grad_steps=[0, 1, 2]):
            n = _net(pol, mixed_precision=True)
            for _ in range(3):
                n.fit(ds, epochs=1, batch_size=8)
        assert n.loss_scale == 1.0  # clamped, never 0

    def test_scaling_off_for_fp32(self):
        """Default loss_scaling=None only activates under compute_dtype."""
        n = _net(FaultPolicy())
        n.fit(_batches(1)[0], epochs=1, batch_size=8)
        assert n.loss_scale is None
        assert "loss_scale" not in n.fault_state_

    def test_skipped_step_params_unchanged_bf16(self):
        """Overflow-skipped step leaves bf16-compute params bit-identical."""
        pol = FaultPolicy(init_loss_scale=2.0 ** 8)
        ds = _batches(1)[0]
        with fault_injection(nan_grad_steps=[1]):
            n = _net(pol, mixed_precision=True)
            n.fit(ds, epochs=1, batch_size=8)
            before = n.params_flat().copy()
            n.fit(ds, epochs=1, batch_size=8)  # iteration 1 → injected
            after = n.params_flat().copy()
        np.testing.assert_array_equal(before, after)


class TestParallelPathsGuard:
    def test_wrapper_replicated_and_zero1_parity_with_guard(self):
        """ParallelWrapper with the guard: replicated run equals the
        batch-removed reference exactly; the ZeRO-1 sharded run (global
        pre-scatter verdict) matches the replicated one."""
        from deeplearning4j_tpu.parallel import ParallelWrapper

        ds = _batches(1, per=32)[0]
        with fault_injection(nan_grad_steps=[1]):
            repl = _net(FaultPolicy())
            ParallelWrapper.builder(repl).workers(4).build().fit(
                ExistingDataSetIterator([ds]), epochs=3)
            zero = _net(FaultPolicy())
            ParallelWrapper.builder(zero).workers(4).sharded_update(
                True).build().fit(ExistingDataSetIterator([ds]), epochs=3)
        removed = _net()
        ParallelWrapper.builder(removed).workers(4).build().fit(
            ExistingDataSetIterator([ds]), epochs=2)
        _assert_same_math(repl.params_flat(), removed.params_flat())
        assert repl.bad_step_count == 1 and zero.bad_step_count == 1
        np.testing.assert_allclose(zero.params_flat(), repl.params_flat(),
                                   atol=1e-6)
        # gathered-back opt state stays canonical and matches
        np.testing.assert_allclose(zero.opt_state_flat(),
                                   repl.opt_state_flat(), atol=1e-6)

    def test_shared_master_skips_exactly(self):
        """SharedTrainingMaster guard: the poisoned step leaves params and
        the residual untouched; training continues finite."""
        from deeplearning4j_tpu.parallel import SharedTrainingMaster

        ds = _batches(1, per=32)[0]
        with fault_injection(nan_grad_steps=[1]):
            m = _net(FaultPolicy())
            master = SharedTrainingMaster.builder(1e-5).build()
            it = ExistingDataSetIterator([ds])
            master.fit(m, it, epochs=1)
            before = m.params_flat().copy()
            master.fit(m, it, epochs=1)  # iteration 1 → injected → skipped
            np.testing.assert_array_equal(before, m.params_flat())
            master.fit(m, it, epochs=1)
        assert m.bad_step_count == 1
        assert np.isfinite(m.params_flat()).all()
        assert np.isfinite(master.residual_magnitude())

    def test_transformer_trainer_guard_parity(self):
        """DistributedLMTrainer (fp32): guarded run with the poisoned
        batch equals the run without it; bf16 sharded_update variant
        stays finite with the scale backing off once."""
        import jax

        from deeplearning4j_tpu.models.transformer_lm import TransformerLM
        from deeplearning4j_tpu.parallel.mesh import TrainingMesh
        from deeplearning4j_tpu.parallel.transformer import (
            DistributedLMTrainer,
        )

        V, T, B = 17, 8, 8
        rng = np.random.default_rng(0)
        ids = rng.integers(0, V, (B, T)).astype(np.int32)
        tgt = np.roll(ids, -1, axis=1).astype(np.int32)
        tgt[:, -1] = -1

        def model(mp=False):
            kw = dict(vocab_size=V, d_model=16, n_heads=2, n_layers=1,
                      max_length=T)
            if mp:
                kw["compute_dtype"] = "bfloat16"
            return TransformerLM(**kw).init()

        with fault_injection(nan_grad_steps=[2]):
            tr = DistributedLMTrainer(model(), TrainingMesh(data=8),
                                      fault_policy=FaultPolicy()).place()
            for _ in range(4):
                tr.fit_batch(ids, tgt)
        ref = DistributedLMTrainer(model(), TrainingMesh(data=8)).place()
        for _ in range(3):
            ref.fit_batch(ids, tgt)
        for a, b in zip(jax.tree_util.tree_leaves(tr.model.params_),
                        jax.tree_util.tree_leaves(ref.model.params_)):
            _assert_same_math(a, b, rel=1e-5)  # sums over an 8-way mesh
        assert tr.bad_step_count == 1

        with fault_injection(nan_grad_steps=[2]):
            trz = DistributedLMTrainer(
                model(mp=True), TrainingMesh(data=8), sharded_update=True,
                fault_policy=FaultPolicy(init_loss_scale=2.0 ** 10,
                                         scale_growth_interval=100)).place()
            losses = [trz.fit_batch(ids, tgt) for _ in range(4)]
        assert np.isfinite(losses).all()
        assert trz.bad_step_count == 1
        assert trz.loss_scale == 2.0 ** 9  # one backoff
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(trz.model.params_))


class TestCrashSafeCheckpointing:
    def _ckpt(self, net, path):
        from deeplearning4j_tpu.train.model_serializer import ModelSerializer

        ModelSerializer.write_model(net, path, save_updater=True)

    def test_failed_write_leaves_previous_checkpoint(self, tmp_path):
        """A write that dies mid-stream must neither corrupt the visible
        checkpoint nor leave staging debris behind."""
        from deeplearning4j_tpu.train.model_serializer import ModelSerializer

        net = _net()
        net.fit(_batches(1)[0], epochs=1, batch_size=8)
        path = str(tmp_path / "model.zip")
        self._ckpt(net, path)
        good = net.params_flat().copy()

        broken = net.clone()
        broken.opt_state_flat = lambda: (_ for _ in ()).throw(
            RuntimeError("simulated crash mid-serialization"))
        with pytest.raises(RuntimeError, match="simulated crash"):
            self._ckpt(broken, path)
        assert faults.is_valid_checkpoint(path)
        restored = ModelSerializer.restore_multi_layer_network(path)
        np.testing.assert_array_equal(restored.params_flat(), good)
        assert [f for f in os.listdir(tmp_path) if ".tmp-" in f] == []

    def test_truncated_newest_falls_back_to_previous(self, tmp_path):
        net = _net()
        ds = _batches(1)[0]
        net.fit(ds, epochs=1, batch_size=8)
        p1 = faults.save_checkpoint(net, str(tmp_path))
        net.fit(ds, epochs=1, batch_size=8)
        p2 = faults.save_checkpoint(net, str(tmp_path))
        assert p1 != p2
        faults.truncate_file(p2)  # SIGKILL-mid-write stand-in
        ok, reason = faults.validate_checkpoint(p2)
        assert not ok and reason

        with pytest.warns(UserWarning, match="corrupt checkpoint"):
            model, path = faults.load_latest_valid(str(tmp_path))
        assert path == p1
        assert model.iteration == 1  # the older (valid) state

    def test_all_corrupt_raises(self, tmp_path):
        net = _net()
        net.fit(_batches(1)[0], epochs=1, batch_size=8)
        p = faults.save_checkpoint(net, str(tmp_path))
        faults.truncate_file(p)
        with pytest.warns(UserWarning):
            with pytest.raises(FileNotFoundError, match="all corrupt"):
                faults.load_latest_valid(str(tmp_path))

    def test_keep_last_retention_and_tmp_sweep(self, tmp_path):
        net = _net()
        ds = _batches(1)[0]
        paths = []
        for _ in range(5):
            net.fit(ds, epochs=1, batch_size=8)
            paths.append(faults.save_checkpoint(net, str(tmp_path),
                                                keep_last=2))
        # stray staging file from a crashed writer is swept once it is
        # old enough to be debris; a FRESH one (a concurrent writer's
        # in-flight stage) is left alone
        stray = tmp_path / "model.zip.tmp-123-dead"
        stray.write_bytes(b"garbage")
        faults.prune_checkpoints(str(tmp_path), keep_last=2)
        assert stray.exists()  # too young to sweep
        old = __import__("time").time() - 2 * faults._TMP_SWEEP_AGE_S
        os.utime(stray, (old, old))
        faults.prune_checkpoints(str(tmp_path), keep_last=2)
        left = sorted(os.listdir(tmp_path))
        assert left == sorted(os.path.basename(p) for p in paths[-2:])
        # newest valid is the last one written
        assert faults.latest_valid_checkpoint(str(tmp_path)) == paths[-1]

    def test_load_model_guess_names_path_and_entries(self, tmp_path):
        from deeplearning4j_tpu.train.model_serializer import ModelGuesser

        path = str(tmp_path / "notamodel.zip")
        with zipfile.ZipFile(path, "w") as z:
            z.writestr("readme.txt", "hello")
            z.writestr("data.bin", b"\x00\x01")
        with pytest.raises(ValueError) as ei:
            ModelGuesser.load_model_guess(path)
        msg = str(ei.value)
        assert "notamodel.zip" in msg
        assert "readme.txt" in msg and "data.bin" in msg
        assert "configuration.json" in msg  # what was expected

    def test_save_load_resume_through_guarded_fit(self, tmp_path):
        """Checkpoint-resume with the guard on: good_count re-seeds from
        the restored iteration so the Adam clock keeps running."""
        from deeplearning4j_tpu.train.model_serializer import ModelSerializer

        ds = _batches(1)[0]
        a = _net(FaultPolicy())
        a.fit(ds, epochs=2, batch_size=8)
        path = str(tmp_path / "ck.zip")
        self._ckpt(a, path)
        resumed = ModelSerializer.restore_multi_layer_network(path)
        resumed.fit(ds, epochs=2, batch_size=8)

        b = _net(FaultPolicy())
        b.fit(ds, epochs=4, batch_size=8)
        np.testing.assert_allclose(resumed.params_flat(), b.params_flat(),
                                   atol=1e-6)


class TestEarlyStoppingSatellites:
    def _es_parts(self):
        from deeplearning4j_tpu.train.earlystopping import (
            DataSetLossCalculator,
            EarlyStoppingConfiguration,
            EarlyStoppingTrainer,
            MaxEpochsTerminationCondition,
        )

        return (DataSetLossCalculator, EarlyStoppingConfiguration,
                EarlyStoppingTrainer, MaxEpochsTerminationCondition)

    def test_nan_epoch_score_terminates_with_error(self):
        """An empty evaluation iterator yields a NaN score; the trainer
        must stop with an Error termination instead of looping to
        MaxEpochs without ever saving a best model."""
        (DataSetLossCalculator, EarlyStoppingConfiguration,
         EarlyStoppingTrainer, MaxEpochsTerminationCondition) = \
            self._es_parts()

        net = _net()
        cfg = EarlyStoppingConfiguration(
            score_calculator=DataSetLossCalculator(
                ExistingDataSetIterator([])),  # empty → NaN
            epoch_termination_conditions=[
                MaxEpochsTerminationCondition(50)],
        )
        result = EarlyStoppingTrainer(
            cfg, net, ExistingDataSetIterator(_batches(1))).fit()
        assert result.termination_reason == "Error"
        assert "NaN" in result.termination_details
        assert result.total_epochs == 1  # stopped immediately, not at 50

    def test_max_time_clock_starts_at_fit_entry(self, monkeypatch):
        """Setup/compile time before iteration 1 counts against the time
        budget: initialize() arms the clock when fit() starts, so the
        first terminate() check already sees the elapsed setup time."""
        from deeplearning4j_tpu.train import earlystopping as es

        (DataSetLossCalculator, EarlyStoppingConfiguration,
         EarlyStoppingTrainer, MaxEpochsTerminationCondition) = \
            self._es_parts()

        clock = [0.0]

        def fake_monotonic():
            clock[0] += 100.0  # every look at the clock jumps 100s
            return clock[0]

        monkeypatch.setattr(es.time, "monotonic", fake_monotonic)
        net = _net()
        cfg = EarlyStoppingConfiguration(
            score_calculator=DataSetLossCalculator(
                ExistingDataSetIterator(_batches(1))),
            epoch_termination_conditions=[MaxEpochsTerminationCondition(1)],
            iteration_termination_conditions=[
                es.MaxTimeIterationTerminationCondition(10.0)],
        )
        result = EarlyStoppingTrainer(
            cfg, net, ExistingDataSetIterator(_batches(1))).fit()
        # with a lazily-armed clock the first check would read 0s elapsed
        # and the run would end via MaxEpochs instead
        assert result.termination_reason == "IterationTerminationCondition"
        assert "MaxTime" in result.termination_details


class TestCliWiring:
    def test_fault_flags_reach_the_model(self, tmp_path, monkeypatch, capsys):
        from deeplearning4j_tpu import cli

        built = {}

        def fake_dataset(name, batch_size, num_examples):
            return ExistingDataSetIterator(_batches(2)), N_OUT

        def fake_model(name, num_classes, dataset, compute_dtype=None,
                       remat_policy=None):
            built["net"] = _net()
            return built["net"]

        monkeypatch.setattr(cli, "build_dataset", fake_dataset)
        monkeypatch.setattr(cli, "build_model", fake_model)
        ckdir = str(tmp_path / "ck")
        rc = cli.main([
            "--model", "tiny", "--epochs", "2",
            "--skip-nonfinite", "--max-bad-steps", "5",
            "--checkpoint-dir", ckdir, "--keep-last", "2",
        ])
        assert rc == 0
        pol = built["net"].conf.global_conf.fault_policy
        assert pol is not None and pol.skip_nonfinite
        assert pol.max_consecutive_bad_steps == 5
        assert built["net"].bad_step_count == 0
        cks = [f for f in os.listdir(ckdir) if f.endswith(".zip")]
        assert 1 <= len(cks) <= 2  # epoch saves under keep-last-2

        # --resume restores the newest valid checkpoint
        rc = cli.main([
            "--model", "tiny", "--epochs", "1",
            "--checkpoint-dir", ckdir, "--resume",
        ])
        assert rc == 0
        assert "resumed from" in capsys.readouterr().out

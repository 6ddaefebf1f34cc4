"""Sharded input pipeline tests (data/shards.py + data/loader.py +
data/augment.py).

The format round-trips bit-exact and rejects damage typed (CRC flip,
truncation, manifest drift all surface as TornShardError — never a
struct.error or a silently-wrong batch); the multi-worker loader's
stream is deterministic in (seed, epoch, step) and INDEPENDENT of the
worker count; resume from a mid-epoch data_state replays the exact
remaining stream with the rolling fingerprint chain continuing to the
oracle's final value; per-host shard assignment partitions the shard
set disjointly; a torn shard is skipped typed with a ``shard_skip``
forensic while the epoch completes; the data position rides checkpoint
meta through both serializers; and the on-device augmentation stage is
iteration-keyed, bundle-consistent and traces exactly once.
"""

import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet, ExistingDataSetIterator
from deeplearning4j_tpu.data.loader import ShardedLoader
from deeplearning4j_tpu.data.shards import (
    TornShardError,
    assign_host_shards,
    load_manifest,
    pack_iterator,
    read_shard,
    shard_name,
    verify_dir,
    verify_shard,
    write_shard,
)
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.obs import flight
from deeplearning4j_tpu.updaters import Adam

N_IN, N_HID, N_OUT = 4, 6, 3


def _net(seed=3):
    conf = (
        NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
        .list()
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


def _pack(tmp_path, n=12, per=8, seed=0, batches_per_shard=3):
    d = str(tmp_path / "shards")
    pack_iterator(ExistingDataSetIterator(_batches(n, per, seed)), d,
                  batches_per_shard=batches_per_shard)
    return d


def _drain(loader):
    """Consume one epoch; returns (list-of-(features, labels), state)."""
    out = []
    while loader.has_next():
        ds = loader.next()
        out.append((np.asarray(ds.features).copy(),
                    np.asarray(ds.labels).copy()))
    return out, loader.data_state()


class TestShardFormat:
    def test_roundtrip_bit_exact(self, tmp_path):
        batches = _batches(5, per=6, seed=2)
        p = str(tmp_path / shard_name(0, 1))
        write_shard(p, batches)
        back = read_shard(p)
        assert len(back) == 5
        for a, b in zip(batches, back):
            np.testing.assert_array_equal(np.asarray(a.features),
                                          np.asarray(b.features))
            np.testing.assert_array_equal(np.asarray(a.labels),
                                          np.asarray(b.labels))

    def test_ragged_tail_batch(self, tmp_path):
        batches = _batches(2, per=8) + _batches(1, per=3, seed=9)
        p = str(tmp_path / shard_name(0, 1))
        write_shard(p, batches)
        back = read_shard(p)
        assert [np.asarray(b.features).shape[0] for b in back] == [8, 8, 3]

    def test_crc_flip_rejected_typed(self, tmp_path):
        p = str(tmp_path / shard_name(0, 1))
        write_shard(p, _batches(4))
        raw = bytearray(open(p, "rb").read())
        raw[len(raw) // 2] ^= 0xFF  # one payload bit-flip
        open(p, "wb").write(bytes(raw))
        with pytest.raises(TornShardError) as ei:
            read_shard(p)
        assert "CRC" in str(ei.value)

    def test_truncation_rejected_typed(self, tmp_path):
        p = str(tmp_path / shard_name(0, 1))
        write_shard(p, _batches(4))
        raw = open(p, "rb").read()
        open(p, "wb").write(raw[: len(raw) * 2 // 3])
        with pytest.raises(TornShardError):
            read_shard(p)
        assert not verify_shard(p)["ok"]

    def test_verify_never_raises(self, tmp_path):
        p = str(tmp_path / shard_name(0, 1))
        write_shard(p, _batches(3))
        assert verify_shard(p) == {"path": p, "ok": True, "records": 3,
                                   "error": None}
        open(p, "wb").write(b"not a shard at all")
        r = verify_shard(p)
        assert not r["ok"] and r["error"]

    def test_pack_manifest_and_verify_dir(self, tmp_path):
        d = _pack(tmp_path, n=10, batches_per_shard=4)
        m = load_manifest(d)
        assert m["num_shards"] == 3  # 4 + 4 + 2
        assert m["total_batches"] == 10
        assert [s["records"] for s in m["shards"]] == [4, 4, 2]
        assert m["schema"]["features"]["shape"] == [N_IN]
        assert verify_dir(d)["ok"]

    def test_verify_dir_flags_missing_and_count_drift(self, tmp_path):
        d = _pack(tmp_path, n=6, batches_per_shard=3)
        m = load_manifest(d)
        os.remove(os.path.join(d, m["shards"][1]["name"]))
        r = verify_dir(d)
        assert not r["ok"] and r["bad"] == 1
        assert "missing" in r["shards"][1]["error"]

    def test_missing_manifest_typed(self, tmp_path):
        with pytest.raises(TornShardError):
            load_manifest(str(tmp_path))

    def test_no_tmp_litter(self, tmp_path):
        d = _pack(tmp_path)
        litter = [f for f in os.listdir(d) if ".tmp-" in f]
        assert litter == []


class TestHostAssignment:
    def test_partition_disjoint_and_complete(self):
        parts = assign_host_shards(10, 4)
        flat = sorted(i for p in parts for i in p)
        assert flat == list(range(10))
        assert len(parts) == 4
        # round-robin spread: no host more than ceil(10/4)=3
        assert max(len(p) for p in parts) <= 3

    def test_single_host_owns_all(self):
        assert assign_host_shards(5, 1, 0) == [0, 1, 2, 3, 4]

    def test_bad_host_index_typed(self):
        with pytest.raises(ValueError):
            assign_host_shards(4, 2, 2)

    def test_two_host_loaders_disjoint_union_is_all(self, tmp_path):
        d = _pack(tmp_path, n=12, batches_per_shard=2)  # 6 shards
        streams = []
        for h in range(2):
            ld = ShardedLoader(d, num_workers=2, seed=5, host_index=h,
                               host_count=2)
            got, _ = _drain(ld)
            ld.shutdown()
            streams.append(got)
        keys = [{arr[0].tobytes() for arr in s} for s in streams]
        assert not (keys[0] & keys[1])
        all_feats = {np.asarray(b.features).tobytes()
                     for b in _batches(12)}
        assert keys[0] | keys[1] == all_feats


class TestLoaderDeterminism:
    def test_worker_count_invariance(self, tmp_path):
        d = _pack(tmp_path, n=12, batches_per_shard=3)
        ref = None
        for workers in (1, 3):
            ld = ShardedLoader(d, num_workers=workers, seed=7)
            got, st = _drain(ld)
            ld.shutdown()
            sig = [f.tobytes() + l.tobytes() for f, l in got]
            if ref is None:
                ref, ref_fp = sig, st["fingerprint"]
            else:
                assert sig == ref
                assert st["fingerprint"] == ref_fp

    def test_epochs_reshuffle_deterministically(self, tmp_path):
        d = _pack(tmp_path, n=12, batches_per_shard=3)
        ld = ShardedLoader(d, num_workers=1, seed=1)
        assert ld.epoch_plan(0) != ld.epoch_plan(1)  # reshuffled
        assert ld.epoch_plan(0) == ld.epoch_plan(0)  # but pinned
        e0, _ = _drain(ld)
        ld.reset()
        e1, _ = _drain(ld)
        ld.shutdown()
        # same bytes, different order across epochs
        assert ([x[0].tobytes() for x in e0]
                != [x[0].tobytes() for x in e1])
        assert (sorted(x[0].tobytes() for x in e0)
                == sorted(x[0].tobytes() for x in e1))
        # a fresh loader with the same seed replays epoch 0 exactly
        ld2 = ShardedLoader(d, num_workers=2, seed=1)
        again, _ = _drain(ld2)
        ld2.shutdown()
        assert ([x[0].tobytes() for x in again]
                == [x[0].tobytes() for x in e0])

    def test_seed_changes_stream(self, tmp_path):
        d = _pack(tmp_path, n=12, batches_per_shard=3)
        orders = []
        for seed in (0, 1):
            ld = ShardedLoader(d, num_workers=1, seed=seed)
            got, _ = _drain(ld)
            ld.shutdown()
            orders.append([x[0].tobytes() for x in got])
        assert orders[0] != orders[1]

    def test_resume_mid_epoch_bit_identical(self, tmp_path):
        d = _pack(tmp_path, n=12, batches_per_shard=3)
        oracle = ShardedLoader(d, num_workers=2, seed=9)
        full, ostate = _drain(oracle)
        oracle.shutdown()

        # consume 5 batches, snapshot, abandon (the SIGKILL analogue:
        # the state dict is all that survives)
        first = ShardedLoader(d, num_workers=2, seed=9)
        for _ in range(5):
            first.next()
        snap = first.data_state()
        first.shutdown()
        assert snap["batches"] == 5

        resumed = ShardedLoader(d, num_workers=1, seed=9)
        resumed.restore_state(snap)
        tail, rstate = _drain(resumed)
        resumed.shutdown()
        assert len(tail) == len(full) - 5
        for (f, l), (rf, rl) in zip(full[5:], tail):
            assert f.tobytes() == rf.tobytes()
            assert l.tobytes() == rl.tobytes()
        # the rolling fingerprint chain continued to the oracle's value
        assert rstate["fingerprint"] == ostate["fingerprint"]
        assert rstate["batches"] == ostate["batches"]

    def test_restore_rejects_mismatched_world(self, tmp_path):
        d = _pack(tmp_path, n=12, batches_per_shard=3)
        ld = ShardedLoader(d, num_workers=1, seed=4)
        st = ld.data_state()
        ld.shutdown()
        other = ShardedLoader(d, num_workers=1, seed=5)
        with pytest.raises(ValueError):
            other.restore_state(st)  # seed mismatch = different stream
        other.shutdown()

    def test_torn_shard_skipped_typed_with_forensic(self, tmp_path):
        d = _pack(tmp_path, n=12, batches_per_shard=3)  # 4 shards
        ld = ShardedLoader(d, num_workers=2, seed=11)
        victim = ld.epoch_plan(0)[1]
        path = os.path.join(d, ld._names[victim])
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[: len(raw) // 2])
        seq0 = flight.default_flight_recorder().recorded_total
        got, st = _drain(ld)
        ld.shutdown()
        assert len(got) == 9  # 12 minus the torn shard's 3
        skips = [e for e in flight.default_flight_recorder().events()
                 if e["kind"] == "shard_skip"]
        assert skips and skips[-1]["seq"] > seq0
        assert st["batches"] == 9


class TestProvenance:
    def test_fit_records_data_state(self, tmp_path):
        d = _pack(tmp_path, n=8, batches_per_shard=2)
        ld = ShardedLoader(d, num_workers=2, seed=3)
        model = _net()
        model.fit(ld, epochs=1)
        ld.shutdown()
        st = model._data_state
        assert st is not None
        assert st["format"] == "sharded_loader/v1"
        assert st["batches"] == 8 and model.iteration == 8

    @pytest.mark.parametrize("serializer", ["zip", "orbax"])
    def test_data_state_rides_checkpoint_meta(self, tmp_path, serializer):
        from deeplearning4j_tpu.train.listeners import CheckpointListener

        d = _pack(tmp_path, n=6, batches_per_shard=2)
        ld = ShardedLoader(d, num_workers=1, seed=2)
        model = _net()
        ckdir = str(tmp_path / f"ck_{serializer}")
        lst = CheckpointListener(ckdir, save_every_n_epochs=1,
                                 keep_mode="last", serializer=serializer)
        model.add_listeners(lst)
        model.fit(ld, epochs=1)
        ld.shutdown()

        if serializer == "orbax":
            from deeplearning4j_tpu.train.orbax_serializer import (
                OrbaxModelSerializer,
            )

            restored = OrbaxModelSerializer.restore(lst.checkpoints[-1])
        else:
            from deeplearning4j_tpu.train.faults import load_latest_valid

            restored, _path = load_latest_valid(ckdir)
        st = restored._data_state
        assert st is not None and st["batches"] == 6
        assert st["fingerprint"] == model._data_state["fingerprint"]

        # and a fresh loader restored from it continues the stream
        ld2 = ShardedLoader(d, num_workers=2, seed=2)
        ld2.restore_state(st)
        assert ld2.data_state()["fingerprint"] == st["fingerprint"]
        ld2.shutdown()

    def test_fit_resume_stream_matches_oracle(self, tmp_path):
        d = _pack(tmp_path, n=9, batches_per_shard=3)
        oracle_ld = ShardedLoader(d, num_workers=1, seed=6)
        oracle = _net(seed=5)
        oracle.fit(oracle_ld, epochs=2)
        ofp = oracle_ld.data_state()["fingerprint"]
        oracle_ld.shutdown()

        ld_a = ShardedLoader(d, num_workers=2, seed=6)
        m = _net(seed=5)
        m.fit(ld_a, epochs=1)
        state = m._data_state
        ld_a.shutdown()

        ld_b = ShardedLoader(d, num_workers=3, seed=6)
        ld_b.restore_state(state)
        m.fit(ld_b, epochs=1)
        assert ld_b.data_state()["fingerprint"] == ofp
        ld_b.shutdown()
        np.testing.assert_array_equal(
            np.asarray(m.params_flat()), np.asarray(oracle.params_flat()))


class TestAugmentation:
    def test_deterministic_and_iteration_keyed(self):
        from deeplearning4j_tpu.data.augment import parse_augment_spec

        st = parse_augment_spec("normalize:0.5:0.25,crop:2,noise:0.05",
                                seed=7)
        x = np.random.default_rng(0).random((4, 10, 10, 3),
                                            dtype=np.float32)
        a0 = np.asarray(st.apply(x, 0))
        a1 = np.asarray(st.apply(x, 1))
        assert a0.shape == x.shape
        assert not np.array_equal(a0, a1)
        np.testing.assert_array_equal(a0, np.asarray(st.apply(x, 0)))

    def test_bundle_matches_per_step_fold_in(self):
        from deeplearning4j_tpu.data.augment import AugmentStage

        st = AugmentStage(noise=0.1, seed=3)
        x = np.random.default_rng(1).random((4, N_IN), dtype=np.float32)
        bundle = np.stack([x, x])
        ob = np.asarray(st.apply_bundle(bundle, 10))
        np.testing.assert_array_equal(ob[0], np.asarray(st.apply(x, 10)))
        np.testing.assert_array_equal(ob[1], np.asarray(st.apply(x, 11)))

    def test_zero_steady_state_retraces(self):
        from deeplearning4j_tpu.data.augment import AugmentStage
        from deeplearning4j_tpu.obs.trace import retrace_counts

        st = AugmentStage(normalize=(0.0, 1.0), noise=0.01, seed=1)
        x = np.random.default_rng(2).random((8, N_IN), dtype=np.float32)
        before = retrace_counts().get("augment_batch", 0)
        for it in range(6):
            st.apply(x, it)
        # the retrace counter is process-global (other stages in this
        # run traced too): assert THIS stage added exactly one trace
        assert retrace_counts().get("augment_batch", 0) - before == 1

    def test_bad_spec_typed(self):
        from deeplearning4j_tpu.data.augment import parse_augment_spec

        with pytest.raises(ValueError):
            parse_augment_spec("flip:1")
        with pytest.raises(ValueError):
            parse_augment_spec("normalize:a:b")

    def test_fit_with_augment_converges_and_traces_once(self, tmp_path):
        from deeplearning4j_tpu.data.augment import AugmentStage
        from deeplearning4j_tpu.obs.trace import retrace_counts

        d = _pack(tmp_path, n=6, batches_per_shard=2)
        ld = ShardedLoader(d, num_workers=1, seed=1)
        model = _net()
        model.set_augmentation(AugmentStage(normalize=(0.0, 1.0),
                                            noise=0.02, seed=4))
        before = retrace_counts().get("augment_batch", 0)
        model.fit(ld, epochs=2)
        ld.shutdown()
        assert model.iteration == 12
        assert np.isfinite(float(model.score_))
        # 12 augmented steps across 2 epochs, ONE trace of this stage
        assert retrace_counts().get("augment_batch", 0) - before == 1


class TestMixup:
    def _xy(self, b=8, seed=1, classes=4):
        rng = np.random.default_rng(seed)
        x = rng.standard_normal((b, N_IN)).astype(np.float32)
        y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, b)]
        return x, y

    def test_mixup_zero_is_fingerprint_stable(self):
        """mixup=0 must leave the key stream byte-identical to a stage
        built before the knob existed — same seed, same crops/noise."""
        from deeplearning4j_tpu.data.augment import AugmentStage

        x, _ = self._xy()
        a = AugmentStage(noise=0.1, seed=3)
        b = AugmentStage(noise=0.1, mixup=0.0, seed=3)
        np.testing.assert_array_equal(np.asarray(a.apply(x, 5)),
                                      np.asarray(b.apply(x, 5)))

    def test_spec_roundtrip_and_mixes_labels(self):
        from deeplearning4j_tpu.data.augment import parse_augment_spec

        st = parse_augment_spec("normalize:0.0:1.0,mixup:0.4", seed=2)
        assert st.mixup == 0.4
        assert st.mixes_labels
        assert "mixup:0.4" in st.spec()
        assert not parse_augment_spec("noise:0.1").mixes_labels

    def test_negative_alpha_typed(self):
        from deeplearning4j_tpu.data.augment import AugmentStage

        with pytest.raises(ValueError, match="mixup"):
            AugmentStage(mixup=-0.1)

    def test_pair_label_consistent_deterministic_one_trace(self):
        from deeplearning4j_tpu.data.augment import AugmentStage
        from deeplearning4j_tpu.obs.trace import retrace_counts

        st = AugmentStage(mixup=0.4, seed=2)
        x, y = self._xy()
        before = retrace_counts().get("augment_pair", 0)
        x1, y1 = map(np.asarray, st.apply_pair(x, y, 0))
        x2, _y2 = map(np.asarray, st.apply_pair(x, y, 1))
        assert retrace_counts().get("augment_pair", 0) - before == 1
        assert not np.array_equal(x1, x2)  # iteration changes the mix
        # mixed one-hot labels stay a distribution (same lam/perm as x)
        assert np.allclose(y1.sum(1), 1.0, atol=1e-5)
        x1b, y1b = map(np.asarray, st.apply_pair(x, y, 0))
        np.testing.assert_array_equal(x1, x1b)
        np.testing.assert_array_equal(y1, y1b)

    def test_pair_bundle_matches_per_step_fold_in(self):
        from deeplearning4j_tpu.data.augment import AugmentStage

        st = AugmentStage(mixup=0.3, seed=5)
        x, y = self._xy()
        xb, yb = np.stack([x, x]), np.stack([y, y])
        ox, oy = map(np.asarray, st.apply_pair_bundle(xb, yb, 10))
        ex0, ey0 = map(np.asarray, st.apply_pair(x, y, 10))
        ex1, ey1 = map(np.asarray, st.apply_pair(x, y, 11))
        # same lam/perm per inner step; allclose not bit-equal — the
        # vmapped program fuses the mix multiply-adds differently
        np.testing.assert_allclose(ox[0], ex0, atol=1e-6)
        np.testing.assert_allclose(oy[0], ey0, atol=1e-6)
        np.testing.assert_allclose(ox[1], ex1, atol=1e-6)
        np.testing.assert_allclose(oy[1], ey1, atol=1e-6)

    def test_fit_with_mixup_routes_pair_and_traces_once(self):
        from deeplearning4j_tpu.data.augment import AugmentStage
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.obs.trace import retrace_counts

        x, y = self._xy(classes=N_OUT)
        model = _net()
        model.set_augmentation(AugmentStage(mixup=0.3, seed=0))
        before = retrace_counts().get("augment_pair", 0)
        for _ in range(6):
            model.fit(DataSet(x, y))
        assert retrace_counts().get("augment_pair", 0) - before == 1
        assert np.isfinite(float(model.score_))


class TestObservability:
    def test_mixed_family_snapshot(self):
        """A metric family with BOTH the legacy unlabeled child (async
        prefetch) and pool-labeled children (shard loaders) must stay
        snapshot-able — the regression here broke every later
        snapshot() in the process once both data paths had run."""
        from deeplearning4j_tpu.obs.metrics import (
            MetricsRegistry,
            data_pipeline_metrics,
        )

        reg = MetricsRegistry()
        _, _, legacy = data_pipeline_metrics(reg)
        legacy.inc(0.5)
        _, _, pooled = data_pipeline_metrics(reg, pool="shard_loader")
        pooled.inc(1.25)
        fam = reg.snapshot()["data_consumer_wait_seconds_total"]
        assert fam == {"": 0.5, "pool=shard_loader": 1.25}
        assert "pool=\"shard_loader\"" in reg.prometheus_text().replace(
            "'", "\"")

    def test_alert_rules_declared(self):
        from deeplearning4j_tpu.obs.slo import default_rules

        names = {r.name for r in default_rules()}
        assert {"data_loader_stalled", "shard_skips",
                "data_queue_starved"} <= names

    def test_starved_pools_names_the_loader_pool(self, tmp_path):
        from deeplearning4j_tpu.obs.metrics import (
            MetricsRegistry,
            starved_pools,
        )

        reg = MetricsRegistry()
        d = _pack(tmp_path, n=6, batches_per_shard=2)
        ld = ShardedLoader(d, num_workers=1, seed=1, pool="pool_x",
                           registry=reg)
        _drain(ld)
        ld.shutdown()
        # consumer-wait on a cold loader is near-certain but not
        # guaranteed; assert the label plumbing, not the timing (a
        # labelled child's key is the registry's label string)
        pools = starved_pools(reg)
        for name in pools:
            assert name in ("pool=pool_x", "async_prefetch")

    def test_loader_worker_exit_forensics(self, tmp_path):
        d = _pack(tmp_path, n=6, batches_per_shard=2)
        ld = ShardedLoader(d, num_workers=2, seed=1)
        _drain(ld)
        ld.shutdown()
        exits = [e for e in flight.default_flight_recorder().events()
                 if e["kind"] == "loader_worker_exit"]
        assert exits
        assert exits[-1]["reason"] in ("plan_drained", "stopped")


class TestCli:
    def test_data_pack_verify_roundtrip(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import data_main

        out = str(tmp_path / "shards")
        rc = data_main(["pack", "--dataset", "iris", "--batch-size", "8",
                        "--out", out, "--shard-size", "4"])
        assert rc == 0
        assert data_main(["verify", out]) == 0
        capsys.readouterr()

        # corrupt one shard: verify must fail non-zero with a report
        m = load_manifest(out)
        victim = os.path.join(out, m["shards"][0]["name"])
        raw = bytearray(open(victim, "rb").read())
        raw[-5] ^= 0xFF
        open(victim, "wb").write(bytes(raw))
        assert data_main(["verify", out, "--json"]) == 1
        rep = json.loads(capsys.readouterr().out)
        assert not rep["ok"] and rep["bad"] == 1

"""Serving subsystem tests: bucket policy, dynamic batcher, engine
(warmup / zero-recompile steady state / atomic hot reload), the HTTP
front-end, and the ParallelInference regressions it absorbs.

Fast tier: unit coverage + a 2-bucket CPU smoke (one request through
engine and HTTP). Slow tier (@slow): multi-threaded client storms
through ParallelInference and the HTTP server asserting result
integrity, bounded compiles, typed overload rejection, and that hot
reload mid-storm never serves a mixed model.
"""

import gc
import http.client
import json
import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import ParallelInference
from deeplearning4j_tpu.serving import (
    BucketPolicy,
    DynamicBatcher,
    InferenceEngine,
    InferenceServer,
    RequestDeadlineExceeded,
    ServerOverloadedError,
    ServerShutdownError,
)
from deeplearning4j_tpu.serving.buckets import IdentityBucketPolicy
from deeplearning4j_tpu.train.faults import save_checkpoint, truncate_file
from deeplearning4j_tpu.train.model_serializer import ModelSerializer


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """This module traces ~50 small XLA programs across many short-lived
    engines; on the cramped CPU test host the executables otherwise stay
    resident for the rest of the suite (heap pressure the warm-run
    XLA:CPU flake class documented in .claude/skills/verify/SKILL.md is
    sensitive to). Drop them once the module is done — later tests build
    fresh nets and retrace anyway, with the persistent disk cache warm."""
    yield
    gc.collect()
    jax.clear_caches()


def _net(seed: int = 7, n_in: int = 4, n_out: int = 3) -> MultiLayerNetwork:
    conf = (
        NeuralNetConfiguration.builder().seed(seed)
        .list()
        .layer(DenseLayer(n_out=8, activation="relu"))
        .layer(OutputLayer(n_out=n_out, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(n_in))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _rows(n: int, d: int = 4, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, d)).astype(
        np.float32)


# ---------------------------------------------------------------------------
# bucket policy
# ---------------------------------------------------------------------------
class TestBucketPolicy:
    def test_pow2_default(self):
        p = BucketPolicy(max_batch=32)
        assert p.batch_buckets == [1, 2, 4, 8, 16, 32]
        # non-pow2 limit: last bucket is exactly the limit
        assert BucketPolicy(max_batch=12).batch_buckets == [1, 2, 4, 8, 12]

    def test_bucket_for(self):
        p = BucketPolicy(batch_buckets=[2, 4, 16])
        assert p.bucket_for(1) == 2
        assert p.bucket_for(4) == 4
        assert p.bucket_for(5) == 16
        # oversize grows by powers of two past the top and is REMEMBERED
        assert p.bucket_for(17) == 32
        assert p.batch_buckets[-1] == 32
        assert p.bucket_for(30) == 32  # no second growth

    def test_pad_batch_roundtrip(self):
        p = BucketPolicy(batch_buckets=[4, 8])
        x = _rows(3)
        xp, mp, n = p.pad_batch(x)
        assert xp.shape == (4, 4) and n == 3 and mp is None
        np.testing.assert_array_equal(xp[:3], x)
        np.testing.assert_array_equal(xp[3:], 0.0)
        # exact fit: no copy, same object through
        x4 = _rows(4)
        xp, _, n = p.pad_batch(x4)
        assert xp is x4 and n == 4

    def test_seq_buckets_synthesize_mask(self):
        p = BucketPolicy(batch_buckets=[4], seq_buckets=[8, 16])
        x = np.ones((2, 5, 3), np.float32)
        xp, mp, n = p.pad_batch(x)
        assert xp.shape == (4, 8, 3) and n == 2
        assert mp.shape == (2, 5) or mp.shape == (4, 8)
        # real steps masked in, padding masked out
        assert mp.shape == (4, 8)
        np.testing.assert_array_equal(mp[:2, :5], 1.0)
        assert float(mp[:2, 5:].sum()) == 0.0 and float(mp[2:].sum()) == 0.0
        # mask presence is uniform: even exact-fit input gets one
        x2 = np.ones((4, 8, 3), np.float32)
        _, mp2, _ = p.pad_batch(x2)
        assert mp2 is not None and mp2.shape == (4, 8)

    def test_warmup_shapes(self):
        p = BucketPolicy(batch_buckets=[2, 4])
        assert p.warmup_shapes((5,)) == [((2, 5), False), ((4, 5), False)]
        ps = BucketPolicy(batch_buckets=[2], seq_buckets=[8, 16])
        assert ps.warmup_shapes((5, 3)) == [((2, 8, 3), True),
                                            ((2, 16, 3), True)]

    def test_identity_policy(self):
        p = BucketPolicy.identity()
        assert isinstance(p, IdentityBucketPolicy)
        x = _rows(5)
        xp, mp, n = p.pad_batch(x)
        assert xp is x and n == 5 and mp is None
        assert p.bucket_for(7) == 7
        assert p.warmup_shapes((4,)) == []

    def test_bad_buckets_raise(self):
        with pytest.raises(ValueError):
            BucketPolicy(batch_buckets=[0, 2])
        with pytest.raises(ValueError):
            BucketPolicy(seq_buckets=[-1])

    def test_explicit_buckets_union_batch_limit(self):
        """Explicit buckets + max_batch (the batcher's batch_limit): the
        limit joins the list, so a FULL coalesced batch pads to the
        limit instead of growing past it into a never-warmed shape."""
        p = BucketPolicy(batch_buckets=[1, 4, 12], max_batch=32)
        assert p.batch_buckets == [1, 4, 12, 32]
        assert p.bucket_for(32) == 32
        # without max_batch the explicit list is taken as-is
        assert BucketPolicy(batch_buckets=[1, 4, 12]).batch_buckets == \
            [1, 4, 12]

    def test_copy_is_independent(self):
        p = BucketPolicy(batch_buckets=[2, 4], seq_buckets=[8])
        c = p.copy()
        c.batch_buckets.append(64)
        c.seq_buckets.append(16)
        assert p.batch_buckets == [2, 4] and p.seq_buckets == [8]
        assert isinstance(BucketPolicy.identity().copy(),
                          IdentityBucketPolicy)


# ---------------------------------------------------------------------------
# dynamic batcher (pure threading — no jax)
# ---------------------------------------------------------------------------
def _echo_dispatch(batch):
    for r in batch:
        r.finish(r.x * 2.0)


class TestDynamicBatcher:
    def test_dispatch_never_overshoots_batch_limit(self):
        sizes = []
        lock = threading.Lock()

        def dispatch(batch):
            with lock:
                sizes.append(sum(r.rows for r in batch))
            _echo_dispatch(batch)

        b = DynamicBatcher(dispatch, batch_limit=8, max_wait_ms=20,
                           queue_limit=64)
        reqs = [b.submit(_rows(3, seed=i)) for i in range(10)]
        for r in reqs:
            r.result(timeout=10)
        b.shutdown()
        assert sizes and all(s <= 8 for s in sizes)
        # 3-row requests into limit 8 → at most 2 per batch, and the
        # coalescing wait window must actually pair some of them up
        assert any(s == 6 for s in sizes)

    def test_oversized_single_request_dispatches_alone(self):
        sizes = []

        def dispatch(batch):
            sizes.append(sum(r.rows for r in batch))
            _echo_dispatch(batch)

        b = DynamicBatcher(dispatch, batch_limit=4, max_wait_ms=1)
        out = b.submit(_rows(9)).result(timeout=10)
        assert out.shape[0] == 9 and sizes == [9]
        b.shutdown()

    def test_max_wait_dispatches_partial_batch(self):
        b = DynamicBatcher(_echo_dispatch, batch_limit=64, max_wait_ms=10)
        t0 = time.monotonic()
        out = b.submit(_rows(2)).result(timeout=10)
        assert time.monotonic() - t0 < 5.0  # served well before any limit
        np.testing.assert_allclose(out, _rows(2) * 2.0)
        b.shutdown()

    def test_overload_rejects_typed(self):
        release = threading.Event()

        def dispatch(batch):
            release.wait(10)
            _echo_dispatch(batch)

        b = DynamicBatcher(dispatch, batch_limit=1, max_wait_ms=0,
                           queue_limit=2)
        first = b.submit(_rows(1))  # worker takes this, blocks in dispatch
        time.sleep(0.1)
        held = [b.submit(_rows(1)) for _ in range(2)]  # queue now full
        with pytest.raises(ServerOverloadedError):
            b.submit(_rows(1))
        assert b.metrics.rejects == 1
        release.set()
        for r in [first] + held:
            r.result(timeout=10)
        b.shutdown()

    def test_shutdown_drains_then_rejects(self):
        release = threading.Event()

        def dispatch(batch):
            release.wait(10)
            _echo_dispatch(batch)

        b = DynamicBatcher(dispatch, batch_limit=1, max_wait_ms=0,
                           queue_limit=8)
        queued = [b.submit(_rows(1, seed=i)) for i in range(4)]
        release.set()
        b.shutdown(drain=True)
        for r in queued:  # drain SERVED them, not failed them
            assert r.result(timeout=1).shape == (1, 4)
        with pytest.raises(ServerShutdownError):
            b.submit(_rows(1))

    def test_no_caller_blocks_forever_across_shutdown_race(self):
        """Producers hammering submit() while shutdown runs: every
        producer thread must terminate with either a result or a typed
        ServingError — the old put-after-drain hang is impossible."""
        b = DynamicBatcher(_echo_dispatch, batch_limit=4, max_wait_ms=1,
                           queue_limit=8)
        outcomes = []
        lock = threading.Lock()

        def producer(i):
            try:
                out = b.submit(_rows(1, seed=i)).result(timeout=5)
                with lock:
                    outcomes.append(("ok", out.shape))
            except (ServerShutdownError, ServerOverloadedError,
                    RequestDeadlineExceeded) as e:
                with lock:
                    outcomes.append(("err", type(e).__name__))

        threads = [threading.Thread(target=producer, args=(i,))
                   for i in range(16)]
        for i, t in enumerate(threads):
            t.start()
            if i == 7:
                b.shutdown(drain=True)
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        assert len(outcomes) == 16

    def test_result_timeout_raises_typed(self):
        def dispatch(batch):
            time.sleep(0.5)
            _echo_dispatch(batch)

        b = DynamicBatcher(dispatch, batch_limit=1, max_wait_ms=0)
        req = b.submit(_rows(1))
        with pytest.raises(RequestDeadlineExceeded):
            req.result(timeout=0.05)
        # the typed error is also a TimeoutError for generic callers
        assert issubclass(RequestDeadlineExceeded, TimeoutError)
        b.shutdown()

    def test_queued_deadline_dropped_not_dispatched(self):
        release = threading.Event()

        def dispatch(batch):
            release.wait(10)
            _echo_dispatch(batch)

        b = DynamicBatcher(dispatch, batch_limit=1, max_wait_ms=0,
                           queue_limit=8)
        b.submit(_rows(1))  # occupies the worker
        time.sleep(0.05)
        doomed = b.submit(_rows(1), timeout=0.01)  # expires while queued
        time.sleep(0.1)
        release.set()
        with pytest.raises(RequestDeadlineExceeded):
            doomed.result(timeout=5)
        assert b.metrics.deadline_exceeded >= 1
        b.shutdown()

    def test_dispatch_error_propagates_to_all_callers(self):
        def dispatch(batch):
            raise ValueError("boom")

        b = DynamicBatcher(dispatch, batch_limit=8, max_wait_ms=5)
        reqs = [b.submit(_rows(1, seed=i)) for i in range(3)]
        for r in reqs:
            with pytest.raises(ValueError, match="boom"):
                r.result(timeout=5)
        b.shutdown()


# ---------------------------------------------------------------------------
# inference engine
# ---------------------------------------------------------------------------
class TestInferenceEngine:
    def test_two_bucket_smoke(self):
        """Tier-1 smoke: one request through a 2-bucket engine on CPU."""
        net = _net()
        eng = InferenceEngine(net, buckets=BucketPolicy(batch_buckets=[2, 4]))
        rep = eng.warmup()
        assert rep["shapes"] == 2 and eng.warm
        x = _rows(3)
        np.testing.assert_allclose(eng.infer(x), net.output(x), atol=1e-6)

    def test_computation_graph_serves_through_the_jitted_path(self):
        """A one-input one-output ComputationGraph (every zoo CNN — what
        ``cli serve --model resnet50`` stands up) gets the same jitted,
        compile-counted forward as a MultiLayerNetwork: its conf's input
        type drives warmup, and steady state compiles nothing."""
        graph = _net().to_computation_graph()
        eng = InferenceEngine(graph,
                              buckets=BucketPolicy(batch_buckets=[2, 4]))
        assert eng.compile_count_supported
        assert eng.example_shape() == (4,)
        rep = eng.warmup()
        assert rep["shapes"] == 2 and rep["compiles"] == 2
        x = _rows(3)
        np.testing.assert_allclose(eng.infer(x), graph.output_single(x),
                                   atol=1e-6)
        assert eng.compile_count == 2

    def test_warmup_then_steady_state_zero_compiles(self):
        """The acceptance property: after warmup(), mixed request sizes
        cause ZERO new XLA compilations (compile-count hook)."""
        net = _net()
        eng = InferenceEngine(net,
                              buckets=BucketPolicy(batch_buckets=[1, 2, 4, 8]))
        rep = eng.warmup()
        assert rep["compiles"] == 4  # one program per bucket
        assert eng.compile_count == 4
        ref = {n: net.output(_rows(n, seed=n)) for n in range(1, 9)}
        for n in (3, 1, 8, 5, 2, 7, 4, 6, 3, 8, 1):
            out = eng.infer(_rows(n, seed=n))
            # padding never leaks: bucketed result == direct forward
            np.testing.assert_allclose(out, ref[n], atol=1e-6)
        assert eng.compile_count == 4  # steady state compiled NOTHING

    def test_naive_coalescing_compiles_per_size(self):
        """The A/B control: identity buckets compile one program per
        distinct size — the failure mode the policy removes."""
        net = _net()
        eng = InferenceEngine(net, buckets=BucketPolicy.identity())
        for n in (1, 2, 3, 4, 5):
            eng.infer(_rows(n))
        assert eng.compile_count == 5

    def test_oversize_grows_bucket_once(self):
        net = _net()
        eng = InferenceEngine(net, buckets=BucketPolicy(batch_buckets=[2]))
        eng.warmup()
        c0 = eng.compile_count
        eng.infer(_rows(5))  # grows a 8-bucket → one compile
        eng.infer(_rows(7))  # same grown bucket → none
        assert eng.compile_count == c0 + 1

    def test_mesh_bucket_divisibility_enforced(self):
        from deeplearning4j_tpu.parallel.mesh import TrainingMesh

        mesh = TrainingMesh(data=8)
        # nothing divisible → hard error with guidance
        with pytest.raises(ValueError, match="divisible"):
            InferenceEngine(_net(), mesh=mesh,
                            buckets=BucketPolicy(batch_buckets=[2, 4]))
        # partially divisible → non-divisible buckets dropped with a
        # warning (the default pow2 list always contains 1, 2, 4...)
        with pytest.warns(UserWarning, match="dropping"):
            filtered = InferenceEngine(_net(), mesh=mesh,
                                       buckets=BucketPolicy(max_batch=16))
        assert filtered.buckets.batch_buckets == [8, 16]
        eng = InferenceEngine(_net(), mesh=mesh,
                              buckets=BucketPolicy(batch_buckets=[8, 16]))
        eng.warmup()
        x = _rows(3)
        np.testing.assert_allclose(eng.infer(x), eng.model.output(x),
                                   atol=1e-6)

    def test_hot_reload_same_arch_zero_compiles(self, tmp_path):
        net = _net(seed=1)
        eng = InferenceEngine(net, buckets=BucketPolicy(batch_buckets=[4]))
        eng.warmup()
        c0 = eng.compile_count
        v0 = eng.model_version

        # same conf (the retrained-checkpoint case), different weights
        other = _net(seed=1)
        other.set_params_flat(other.params_flat() + 0.25)
        ckpt = str(tmp_path / "m.zip")
        ModelSerializer.write_model(other, ckpt)
        result = eng.reload(ckpt)
        assert result["reloaded"] and result["same_arch"]
        assert eng.model_version == v0 + 1
        assert eng.compile_count == c0  # pure weight swap
        x = _rows(3)
        np.testing.assert_allclose(eng.infer(x), other.output(x), atol=1e-6)

    def test_reload_unchanged_is_noop(self, tmp_path):
        ckpt_dir = str(tmp_path)
        save_checkpoint(_net(seed=5), ckpt_dir)
        eng = InferenceEngine.from_checkpoint(ckpt_dir)
        result = eng.reload()
        assert result["reloaded"] is False and result["reason"] == "unchanged"
        result = eng.reload(force=True)
        assert result["reloaded"] is True

    def test_reload_skips_corrupt_newest(self, tmp_path):
        ckpt_dir = str(tmp_path)
        good = _net(seed=5)
        p1 = save_checkpoint(good, ckpt_dir, stem="ckpt_a")
        eng = InferenceEngine.from_checkpoint(ckpt_dir)
        time.sleep(0.02)
        p2 = save_checkpoint(_net(seed=6), ckpt_dir, stem="ckpt_b")
        truncate_file(p2)  # crash-mid-write debris
        with pytest.warns(UserWarning, match="corrupt"):
            result = eng.reload(force=True)
        assert result["path"] == p1  # fell back to the valid one
        x = _rows(2)
        np.testing.assert_allclose(eng.infer(x), good.output(x), atol=1e-6)

    def test_seq_buckets_rnn_pad_and_unpad(self):
        """Sequence-length bucketing on a recurrent model: the time dim
        pads up to the bucket under a synthesized mask and slices back
        out of per-timestep outputs; zoo models carry the bucket hint."""
        from deeplearning4j_tpu.models.textgen_lstm import TextGenerationLSTM

        assert TextGenerationLSTM.serving_seq_buckets == (8, 16, 32, 64)
        zoo = TextGenerationLSTM(num_classes=6, units=4, max_length=16)
        net = zoo.init()
        pol = zoo.serving_bucket_policy(batch_buckets=[2], max_batch=2)
        assert pol.seq_buckets == [8, 16, 32, 64]
        assert zoo.serving_input_shape() == (1, 6)
        pol.seq_buckets = [8, 16]  # trim for test speed
        pol.batch_buckets = [2]
        eng = InferenceEngine(net, buckets=pol)
        assert eng.warmup()["shapes"] == 2
        c0 = eng.compile_count
        x = np.random.default_rng(0).standard_normal((1, 11, 6)).astype(
            np.float32)
        out = eng.infer(x)
        assert out.shape == (1, 11, 6)  # T sliced back from the 16-bucket
        ref = net.output(x, mask=np.ones((1, 11), np.float32))
        np.testing.assert_allclose(out, ref, atol=1e-5)
        assert eng.compile_count == c0  # mixed-T steady state: no compiles

    def test_from_checkpoint_zip_and_describe(self, tmp_path):
        net = _net(seed=3)
        ckpt = str(tmp_path / "m.zip")
        ModelSerializer.write_model(net, ckpt)
        eng = InferenceEngine.from_checkpoint(ckpt)
        info = eng.describe()
        assert info["model_type"] == "MultiLayerNetwork"
        assert info["version"] == 0 and info["source"] == ckpt
        x = _rows(2)
        np.testing.assert_allclose(eng.infer(x), net.output(x), atol=1e-6)

    def test_engine_copies_policy(self):
        """Two engines sharing one policy object must not see each
        other's mesh filtering or oversize growth."""
        pol = BucketPolicy(batch_buckets=[2])
        a = InferenceEngine(_net(), buckets=pol)
        a.infer(_rows(5))  # grows a's copy to [2, 8]
        assert pol.batch_buckets == [2]
        b = InferenceEngine(_net(), buckets=pol)
        assert b.buckets.batch_buckets == [2]

    def test_selector_load_or_init_branches(self, tmp_path):
        """zoo name / checkpoint zip / checkpoint dir all resolve (the
        serve CLI's model-source surface)."""
        from deeplearning4j_tpu.models.selector import ModelSelector

        net = _net(seed=8)
        d = str(tmp_path)
        p = save_checkpoint(net, d)
        m1, o1 = ModelSelector.load_or_init(p)  # zip
        assert o1 == p
        np.testing.assert_allclose(m1.params_flat(), net.params_flat())
        m2, o2 = ModelSelector.load_or_init(d)  # dir → newest valid
        assert o2 == p
        m3, o3 = ModelSelector.load_or_init("lenet", num_classes=5)  # zoo
        assert o3 == "lenet" and m3.num_params() > 0
        with pytest.raises(ValueError, match="neither"):
            ModelSelector.load_or_init(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# ParallelInference regressions (the satellites it absorbs)
# ---------------------------------------------------------------------------
class _ShapeRecorder:
    """Model proxy recording every dispatched batch's row count."""

    def __init__(self, net):
        self._net = net
        self.dispatched = []
        self._lock = threading.Lock()

    def output(self, x, mask=None):
        with self._lock:
            self.dispatched.append(int(np.asarray(x).shape[0]))
        return self._net.output(x, mask=mask)


class TestParallelInferenceRegressions:
    def test_batch_limit_never_overshoots(self):
        """Old loop: checked total < limit BEFORE pulling the next
        request, dispatching up to limit+rows-1. Now a request that
        would overflow stays queued."""
        rec = _ShapeRecorder(_net())
        pi = (ParallelInference.builder(rec).batch_limit(8)
              .buckets(False).max_wait_ms(30).build())
        results = {}

        def call(i):
            results[i] = pi.output(_rows(3, seed=i))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        pi.shutdown()
        assert rec.dispatched and all(n <= 8 for n in rec.dispatched)
        for i in range(9):
            assert results[i].shape == (3, 3)

    def test_bucketed_dispatch_shapes(self):
        """Default buckets quantize dispatches to powers of two."""
        rec = _ShapeRecorder(_net())
        pi = ParallelInference.builder(rec).batch_limit(8).build()
        out = pi.output(_rows(3))
        assert out.shape == (3, 3)
        assert rec.dispatched == [4]  # 3 rows padded up to the 4-bucket
        # the facade records latency quantiles like the HTTP server does
        assert pi.metrics.snapshot()["latency_p50_ms"] is not None
        pi.shutdown()

    def test_output_timeout(self):
        net = _net()
        slow = _ShapeRecorder(net)
        real_output = slow.output

        def stalling(x, mask=None):
            time.sleep(0.5)
            return real_output(x, mask=mask)

        slow.output = stalling
        pi = ParallelInference.builder(slow).build()
        with pytest.raises(TimeoutError):
            pi.output(_rows(1), timeout=0.05)
        pi.shutdown()

    def test_shutdown_then_output_raises(self):
        pi = ParallelInference.builder(_net()).build()
        assert pi.output(_rows(2)).shape == (2, 3)
        pi.shutdown()
        with pytest.raises(RuntimeError):
            pi.output(_rows(2))

    def test_overload_is_typed(self):
        rec = _ShapeRecorder(_net())
        release = threading.Event()
        entered = threading.Event()
        real_output = rec.output

        def blocking(x, mask=None):
            entered.set()
            release.wait(10)
            return real_output(x, mask=mask)

        rec.output = blocking
        pi = (ParallelInference.builder(rec).batch_limit(1)
              .queue_limit(2).max_wait_ms(0).build())
        held = [threading.Thread(target=lambda i=i: pi.output(_rows(1, seed=i)))
                for i in range(3)]
        # deterministic overload state (a fixed sleep flakes under box
        # load, and starting all three at once races the queue_limit=2
        # bound against the worker's dequeue — under contention a SETUP
        # thread can absorb the 503 meant for the probe): first occupy
        # the worker, THEN fill the queue with the other two
        held[0].start()
        assert entered.wait(10)  # worker is BLOCKED inside the dispatch
        for t in held[1:]:
            t.start()
        deadline = time.monotonic() + 10
        while (pi._batcher.queue_depth() < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert pi._batcher.queue_depth() == 2
        with pytest.raises(ServerOverloadedError):
            pi.output(_rows(1))
        release.set()
        for t in held:
            t.join(timeout=10)
        pi.shutdown()


# ---------------------------------------------------------------------------
# HTTP front-end
# ---------------------------------------------------------------------------
def _http(port, method, path, body=None, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     None if body is None else
                     (body if isinstance(body, bytes) else json.dumps(body)))
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, raw
    finally:
        conn.close()


@pytest.fixture
def served():
    net = _net(seed=21)
    eng = InferenceEngine(net, buckets=BucketPolicy(batch_buckets=[2, 4, 8]))
    eng.warmup()
    server = InferenceServer(eng, port=0, batch_limit=8, max_wait_ms=2,
                             queue_limit=32).start()
    yield net, eng, server
    server.shutdown()


class TestInferenceServer:
    def test_predict_json(self, served):
        net, _, server = served
        x = _rows(3, seed=2)
        status, body = _http(server.port, "POST", "/predict",
                             {"inputs": x.tolist()})
        assert status == 200
        np.testing.assert_allclose(np.asarray(body["outputs"]),
                                   net.output(x), atol=1e-5)
        assert body["model_version"] == 0
        # single-example convenience: 1-D input auto-batches
        status, body = _http(server.port, "POST", "/predict",
                             {"inputs": x[0].tolist()})
        assert status == 200 and len(body["outputs"]) == 1

    def test_predict_npy_roundtrip(self, served):
        import io

        net, _, server = served
        x = _rows(5, seed=3)
        buf = io.BytesIO()
        np.save(buf, x)
        status, raw = _http(server.port, "POST", "/predict_npy",
                            buf.getvalue())
        assert status == 200
        out = np.load(io.BytesIO(raw))
        np.testing.assert_allclose(out, net.output(x), atol=1e-5)

    def test_healthz_and_metrics(self, served):
        _, eng, server = served
        status, health = _http(server.port, "GET", "/healthz")
        assert status == 200 and health["status"] == "ok"
        assert health["model_type"] == "MultiLayerNetwork" and health["warm"]
        _http(server.port, "POST", "/predict",
              {"inputs": _rows(2).tolist()})
        status, m = _http(server.port, "GET", "/metrics")
        assert status == 200
        assert m["requests"] >= 1 and m["dispatches"] >= 1
        assert "queue_depth" in m and m["latency_p50_ms"] is not None
        assert any(int(k) in (2, 4, 8) for k in m["bucket_hits"])

    def test_bad_payload_400_unknown_404(self, served):
        _, _, server = served
        status, body = _http(server.port, "POST", "/predict", {"wrong": 1})
        assert status == 400 and body["error"] == "ValueError"
        # empty npy body is the CLIENT's fault: 400, not 500
        status, body = _http(server.port, "POST", "/predict_npy", b"")
        assert status == 400 and body["error"] == "ValueError"
        status, _ = _http(server.port, "GET", "/nope")
        assert status == 404
        status, _ = _http(server.port, "POST", "/nope")
        assert status == 404

    def test_overload_returns_503(self, served):
        _, eng, server = served
        release = threading.Event()
        real_infer = eng.infer_versioned

        def blocking_infer(x, mask=None):
            release.wait(10)
            return real_infer(x, mask)

        eng.infer_versioned = blocking_infer
        try:
            # tiny queue for the test
            server.batcher._queue.maxsize = 2
            statuses = []
            lock = threading.Lock()

            def post():
                s, _ = _http(server.port, "POST", "/predict",
                             {"inputs": _rows(1).tolist()})
                with lock:
                    statuses.append(s)

            threads = [threading.Thread(target=post) for _ in range(8)]
            for t in threads:
                t.start()
                time.sleep(0.02)
            time.sleep(0.2)
            release.set()
            for t in threads:
                t.join(timeout=15)
            assert 503 in statuses  # backpressure surfaced as HTTP 503
            assert 200 in statuses  # accepted requests still served
        finally:
            eng.infer_versioned = real_infer
            release.set()

    def test_reload_endpoint(self, served, tmp_path):
        net, eng, server = served
        other = _net(seed=21)  # same conf as the served model
        other.set_params_flat(other.params_flat() + 0.25)
        ckpt = str(tmp_path / "new.zip")
        ModelSerializer.write_model(other, ckpt)
        status, body = _http(server.port, "POST", "/reload", {"path": ckpt})
        assert status == 200 and body["reloaded"] and body["same_arch"]
        x = _rows(2, seed=9)
        status, out = _http(server.port, "POST", "/predict",
                            {"inputs": x.tolist()})
        assert out["model_version"] == body["version"]
        np.testing.assert_allclose(np.asarray(out["outputs"]),
                                   other.output(x), atol=1e-5)
        # unchanged → no-op
        status, body2 = _http(server.port, "POST", "/reload", {"path": ckpt})
        assert status == 200 and body2["reloaded"] is False
        # missing source → 409, serving unaffected
        status, _ = _http(server.port, "POST", "/reload",
                          {"path": str(tmp_path / "missing")})
        assert status in (400, 409)

    def test_cli_serve_smoke(self):
        """Satellite smoke: one request through `cli serve` end to end
        (2-bucket engine, ephemeral port, CPU)."""
        from deeplearning4j_tpu.cli import main

        rc = main(["serve", "--model", "lenet", "--batch-limit", "2",
                   "--port", "0", "--smoke"])
        assert rc == 0


# ---------------------------------------------------------------------------
# client storms (slow tier)
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestServingStorm:
    def test_parallel_inference_storm_integrity(self):
        """Multi-threaded client storm through ParallelInference: every
        caller gets exactly its own rows back, bucket padding never
        leaks, and the compiled-program count stays at the bucket
        count."""
        net = _net(seed=4)
        pi = (ParallelInference.builder(net).batch_limit(16)
              .queue_limit(256).max_wait_ms(2).build())
        refs = {n: np.asarray(net.output(_rows(n, d=4, seed=100 + n)))
                for n in range(1, 9)}
        errors = []
        lock = threading.Lock()

        def client(tid):
            rng = np.random.default_rng(tid)
            for _ in range(20):
                n = int(rng.integers(1, 9))
                out = pi.output(_rows(n, d=4, seed=100 + n))
                if not np.allclose(out, refs[n], atol=1e-5):
                    with lock:
                        errors.append((tid, n))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        pi.shutdown()
        assert not errors

    def test_http_storm_with_hot_reload_never_mixes_models(self, tmp_path):
        """Client storm through the HTTP server while checkpoints hot-swap
        underneath: every response must match exactly ONE model version
        (all rows of a response from the same params — atomic swap), and
        steady-state traffic after warmup compiles nothing new."""
        net_a = _net(seed=1)
        net_b = _net(seed=1)  # same conf → pure weight-swap reloads
        net_b.set_params_flat(net_b.params_flat() + 0.25)
        ckpt_b = str(tmp_path / "b.zip")
        ModelSerializer.write_model(net_b, ckpt_b)

        eng = InferenceEngine(net_a,
                              buckets=BucketPolicy(batch_buckets=[2, 4, 8,
                                                                  16]))
        eng.warmup()
        compiles_after_warmup = eng.compile_count
        server = InferenceServer(eng, port=0, batch_limit=16, max_wait_ms=2,
                                 queue_limit=256).start()
        try:
            sizes = range(1, 9)
            ref_a = {n: np.asarray(net_a.output(_rows(n, seed=200 + n)))
                     for n in sizes}
            ref_b = {n: np.asarray(net_b.output(_rows(n, seed=200 + n)))
                     for n in sizes}
            mixed = []
            failures = []
            lock = threading.Lock()
            stop = threading.Event()

            def client(tid):
                rng = np.random.default_rng(tid)
                while not stop.is_set():
                    n = int(rng.integers(1, 9))
                    x = _rows(n, seed=200 + n)
                    status, body = _http(server.port, "POST", "/predict",
                                         {"inputs": x.tolist()})
                    if status != 200:
                        continue  # overload shedding is legal mid-storm
                    out = np.asarray(body["outputs"])
                    is_a = np.allclose(out, ref_a[n], atol=1e-5)
                    is_b = np.allclose(out, ref_b[n], atol=1e-5)
                    # version 0 is net_a; every reload swaps in net_b —
                    # the reported version must attribute the weights
                    # that actually computed the rows
                    ver = body["model_version"]
                    ver_ok = (is_a and ver == 0) or (is_b and ver >= 1)
                    with lock:
                        if not (is_a or is_b) or not ver_ok:
                            mixed.append((tid, n, ver))
                        if status != 200:
                            failures.append(status)

            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(8)]
            for t in threads:
                t.start()
            # hot-swap a few times mid-storm
            for _ in range(3):
                time.sleep(0.4)
                eng.reload(ckpt_b, force=True)
            time.sleep(0.4)
            stop.set()
            for t in threads:
                t.join(timeout=60)
            assert not mixed  # no response ever mixed model versions
            # acceptance: the storm (mixed sizes, reloads) compiled NOTHING
            assert eng.compile_count == compiles_after_warmup
            # and the swap really took: serving B now
            x = _rows(3, seed=203)
            np.testing.assert_allclose(eng.infer(x), ref_b[3], atol=1e-5)
        finally:
            server.shutdown()

    def test_http_overload_storm_typed_rejection(self):
        net = _net(seed=9)
        eng = InferenceEngine(net, buckets=BucketPolicy(batch_buckets=[4]))
        eng.warmup()
        release = threading.Event()
        real_infer = eng.infer_versioned
        eng.infer_versioned = lambda x, mask=None: (release.wait(10),
                                                    real_infer(x, mask))[1]
        server = InferenceServer(eng, port=0, batch_limit=4, max_wait_ms=0,
                                 queue_limit=4).start()
        try:
            statuses = []
            lock = threading.Lock()

            def post():
                s, body = _http(server.port, "POST", "/predict",
                                {"inputs": _rows(1).tolist()})
                with lock:
                    statuses.append((s, body.get("error")
                                     if isinstance(body, dict) else None))

            threads = [threading.Thread(target=post) for _ in range(16)]
            for t in threads:
                t.start()
            time.sleep(0.5)
            release.set()
            for t in threads:
                t.join(timeout=30)
            rejected = [e for s, e in statuses if s == 503]
            assert rejected and all(e == "ServerOverloadedError"
                                    for e in rejected)
        finally:
            eng.infer_versioned = real_infer
            release.set()
            server.shutdown()

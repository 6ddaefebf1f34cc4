"""Observability layer (obs/): metrics registry + Prometheus exposition,
in-graph telemetry (bit-parity, once-per-bundle fetch discipline),
retrace monitor (the zero-steady-state-recompiles CI guard), exporter
HTTP endpoint, serving /healthz + content negotiation, and the listener
satellites (PerformanceListener accounting, ProfilerListener fit-exit
close, data-pipeline wait gauges).
"""

import http.client
import json
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    AsyncDataSetIterator,
    ExistingDataSetIterator,
)
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.obs import telemetry as obs_telemetry
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.obs.exporter import MetricsServer, wants_prometheus
from deeplearning4j_tpu.obs.metrics import (
    MetricsListener,
    MetricsRegistry,
    data_wait_seconds,
)
from deeplearning4j_tpu.obs.telemetry import TelemetryConf
from deeplearning4j_tpu.train import pipeline
from deeplearning4j_tpu.updaters import Adam


def _batches(n, b=8, d=12, c=3, seed=0):
    rng = np.random.default_rng(seed)
    return [
        DataSet(rng.standard_normal((b, d)).astype(np.float32),
                np.eye(c, dtype=np.float32)[rng.integers(0, c, b)])
        for _ in range(n)
    ]


def _mlp(k=1, telemetry=None, fault_policy=None, seed=7):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
         .steps_per_call(k))
    if telemetry is not None:
        b = b.telemetry(telemetry)
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


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("a_total")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("g")
        g.set(4)
        g.inc()
        assert g.value() == 5.0
        h = reg.histogram("h_seconds", ring_size=8)
        for v in range(16):  # ring keeps the last 8: 8..15
            h.observe(float(v))
        assert h.count == 16 and h.sum == sum(range(16))
        assert h.quantile(0.0) == 8.0
        assert h.quantile(1.0) == 15.0

    def test_get_or_create_and_type_conflict(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        assert reg.counter("lbl", labels={"fn": "a"}) is not reg.counter(
            "lbl", labels={"fn": "b"})
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")
        assert reg.get("nope") is None

    def test_callback_gauge(self):
        reg = MetricsRegistry()
        box = [1.0]
        g = reg.gauge("depth", fn=lambda: box[0])
        assert g.value() == 1.0
        box[0] = 7
        assert reg.snapshot()["depth"] == 7.0

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "requests", labels={"code": "200"}).inc(3)
        reg.gauge("depth", "queue depth").set(2)
        h = reg.histogram("lat_seconds", "latency")
        for v in (0.1, 0.2, 0.3):
            h.observe(v)
        text = reg.prometheus_text()
        assert "# TYPE req_total counter" in text
        assert 'req_total{code="200"} 3' in text
        assert "# HELP depth queue depth" in text
        assert "# TYPE lat_seconds summary" in text
        assert 'lat_seconds{quantile="0.5"}' in text
        assert "lat_seconds_count 3" in text

    def test_snapshot_labels(self):
        reg = MetricsRegistry()
        reg.counter("hits_total", labels={"bucket": "8"}).inc(2)
        reg.counter("hits_total", labels={"bucket": "16"}).inc()
        snap = reg.snapshot()
        assert snap["hits_total"] == {"bucket=8": 2.0, "bucket=16": 1.0}


class TestServingMetricsRebase:
    def test_public_surface_unchanged(self):
        from deeplearning4j_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics(ring_size=16)
        m.record_request(4)
        m.record_dispatch(8)
        m.record_dispatch(8)
        m.record_reject()
        m.record_latency(0.010)
        m.record_latency(0.020)
        assert m.requests == 1 and m.examples == 4
        assert m.rejects == 1 and m.dispatches == 2
        assert m.bucket_hits == {8: 2}
        snap = m.snapshot(queue_depth=3)
        for key in ("requests", "examples", "rejects", "deadline_exceeded",
                    "errors", "dispatches", "reloads", "bucket_hits",
                    "uptime_s", "latency_window", "latency_p50_ms",
                    "latency_p90_ms", "latency_p99_ms", "queue_depth"):
            assert key in snap
        assert snap["latency_window"] == 2
        # original index rule: idx = min(int(q*n), n-1) → 0.5 of 2 → [1]
        assert m.latency_quantile(0.5) == 0.020
        text = m.prometheus_text()
        assert "serving_requests_total 1" in text
        assert 'serving_bucket_hits_total{bucket="8"} 2' in text

    def test_instances_are_isolated_by_default(self):
        from deeplearning4j_tpu.serving.metrics import ServingMetrics

        a, b = ServingMetrics(), ServingMetrics()
        a.record_request(1)
        assert a.requests == 1 and b.requests == 0


# ---------------------------------------------------------------------------
# in-graph telemetry
# ---------------------------------------------------------------------------
class TestTelemetryParity:
    def test_k4_bit_identical_params_and_adam_slots(self):
        """The acceptance backbone: telemetry-enabled training must be
        BIT-identical to telemetry-off at K=4 — params AND Adam slots
        (the m/v moments + bias-correction clock)."""
        data = _batches(10)
        a = _mlp(4)
        b = _mlp(4, telemetry=True)
        a.fit(ExistingDataSetIterator(data), epochs=2)
        b.fit(ExistingDataSetIterator(data), epochs=2)
        assert a.iteration == b.iteration == 20
        _assert_trees_equal(a.params_, b.params_)
        _assert_trees_equal(a.opt_state_, b.opt_state_)

    def test_guarded_k4_bit_identical(self):
        """Same under a FaultPolicy (telemetry then also reports loss
        scale/bad count from the fault state)."""
        data = _batches(8)
        a = _mlp(4, fault_policy=True)
        b = _mlp(4, telemetry=True, fault_policy=True)
        a.fit(ExistingDataSetIterator(data), epochs=1)
        b.fit(ExistingDataSetIterator(data), epochs=1)
        _assert_trees_equal(a.params_, b.params_)
        _assert_trees_equal(a.opt_state_, b.opt_state_)

    def test_per_step_values_match_k1(self):
        """Bundled telemetry is exact per-step: grad norms of a K=4 fit
        equal the K=1 fit's, step by step."""
        class Capture:
            def __init__(self):
                self.rows = {}

            def telemetry_done(self, model, it0, epoch, telem):
                host = telem.host()
                for j in range(len(telem)):
                    self.rows[it0 + j + 1] = {k: float(v[j])
                                              for k, v in host.items()}

            def iteration_done(self, model, iteration, epoch):
                pass

        data = _batches(8)
        caps = []
        for k in (1, 4):
            net = _mlp(k, telemetry=True)
            cap = Capture()
            net.set_listeners(cap)
            net.fit(ExistingDataSetIterator(data), epochs=1)
            caps.append(cap.rows)
        assert set(caps[0]) == set(caps[1]) == set(range(1, 9))
        for it in caps[0]:
            for key in ("grad_norm", "param_norm", "update_norm",
                        "update_ratio"):
                assert caps[0][it][key] == caps[1][it][key], (it, key)

    def test_skipped_step_reports_zero_update(self):
        """update norm comes from the ACTUAL post-skip delta: a NaN step
        under the guard must report update_norm == 0."""
        from deeplearning4j_tpu.train import faults

        class Capture:
            rows = {}

            def telemetry_done(self, model, it0, epoch, telem):
                host = telem.host()
                for j in range(len(telem)):
                    self.rows[it0 + j + 1] = {k: float(v[j])
                                              for k, v in host.items()}

            def iteration_done(self, model, iteration, epoch):
                pass

        data = _batches(4)
        with faults.fault_injection(nan_grad_steps=[2]):
            net = _mlp(4, telemetry=True, fault_policy=True)
            cap = Capture()
            net.set_listeners(cap)
            net.fit(ExistingDataSetIterator(data), epochs=1)
        # injection keys on the 0-based iteration ARGUMENT (=2), which is
        # the bundle's third step → host row it0+j+1 == 3
        assert cap.rows[3]["update_norm"] == 0.0
        assert cap.rows[3]["bad_count"] == 1.0
        assert cap.rows[2]["update_norm"] > 0.0
        assert cap.rows[2]["bad_count"] == 0.0
        assert cap.rows[4]["update_norm"] > 0.0
        assert cap.rows[4]["bad_count"] == 1.0

    def test_conf_serde_roundtrip(self):
        conf = _mlp(2, telemetry=TelemetryConf(update_ratio=False)).conf
        from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

        again = MultiLayerConfiguration.from_json(conf.to_json())
        assert again.global_conf.telemetry == TelemetryConf(
            update_ratio=False)
        assert again.to_json() == conf.to_json()


class TestTelemetryFetchDiscipline:
    def test_one_fetch_per_bundle_with_stats_listener(self, monkeypatch):
        """The sync-free regression for the monitoring path: a bundled
        fit with a StatsListener attached fetches the stacked scores at
        most once per bundle AND the stacked telemetry at most once per
        bundle — and never calls model.score()."""
        from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener

        data = _batches(8)
        net = _mlp(4, telemetry=True)
        net.set_listeners(StatsListener(InMemoryStatsStorage(),
                                        reporting_frequency=1,
                                        session_id="fetch"))

        def banned_score(ds=None):
            raise AssertionError("model.score() sync inside a bundled fit")

        monkeypatch.setattr(net, "score", banned_score)
        s0, t0 = pipeline._host_fetches, obs_telemetry._host_fetches
        net.fit(ExistingDataSetIterator(data), epochs=1)
        assert pipeline._host_fetches - s0 == 2  # one per bundle
        assert obs_telemetry._host_fetches - t0 == 2  # one per bundle

    def test_stats_records_carry_per_step_telemetry(self):
        from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener

        storage = InMemoryStatsStorage()
        data = _batches(8)
        net = _mlp(4, telemetry=True)
        net.set_listeners(StatsListener(storage, reporting_frequency=2,
                                        session_id="t"))
        assert pipeline.resolve_steps_per_call(net) == 4
        net.fit(ExistingDataSetIterator(data), epochs=1)
        recs = [r for r in storage.get_records("t") if r["kind"] == "update"]
        assert [r["iteration"] for r in recs] == [1, 2, 4, 6, 8]
        for r in recs:
            assert {"grad_norm", "param_norm", "update_norm",
                    "update_ratio"} <= set(r["telemetry"])
        # param summaries at bundle granularity, marked
        with_params = [r for r in recs if "parameters" in r]
        assert [r["params_at_iteration"] for r in with_params] == [4, 8]

    def test_metrics_listener_publishes(self):
        reg = MetricsRegistry()
        data = _batches(8)
        net = _mlp(4, telemetry=True)
        net.add_listeners(MetricsListener(registry=reg, frequency=4))
        net.fit(ExistingDataSetIterator(data), epochs=1)
        snap = reg.snapshot()
        assert snap["train_steps_total"] == 8.0
        assert snap["train_samples_total"] == 64.0
        assert snap["train_epochs_total"] == 1.0
        assert snap["train_grad_norm"] > 0.0
        assert snap["train_update_ratio"] > 0.0
        assert snap["train_loss"] > 0.0


class TestBundlingLegalityAfterTelemetry:
    def test_pgil_modes(self):
        from deeplearning4j_tpu.train.listeners import (
            ParamAndGradientIterationListener,
        )

        per_param = ParamAndGradientIterationListener(
            output_to_console=False)
        assert pipeline.bundling_blockers([per_param]) == [
            "ParamAndGradientIterationListener.on_gradient_calculation"]
        telem = ParamAndGradientIterationListener(
            output_to_console=False, gradients="telemetry")
        assert pipeline.bundling_blockers([telem]) == []
        none = ParamAndGradientIterationListener(
            output_to_console=False, gradients="none")
        assert pipeline.bundling_blockers([none]) == []
        with pytest.raises(ValueError, match="gradients"):
            ParamAndGradientIterationListener(gradients="bogus")

    def test_pgil_telemetry_mode_writes_per_step_rows(self, tmp_path):
        from deeplearning4j_tpu.train.listeners import (
            ParamAndGradientIterationListener,
        )

        path = str(tmp_path / "pg.tsv")
        data = _batches(8)
        net = _mlp(4, telemetry=True)
        net.set_listeners(ParamAndGradientIterationListener(
            iterations=1, output_to_console=False, file=path,
            gradients="telemetry"))
        assert pipeline.resolve_steps_per_call(net) == 4
        net.fit(ExistingDataSetIterator(data), epochs=1)
        lines = open(path).read().strip().split("\n")
        header = lines[0].split("\t")
        assert header[0] == "iteration" and "grad_norm" in header
        assert len(lines) == 1 + 8  # header + one row per step
        assert [int(r.split("\t")[0]) for r in lines[1:]] == list(range(1, 9))


# ---------------------------------------------------------------------------
# retrace monitor — the CI recompile guard
# ---------------------------------------------------------------------------
class TestRetraceMonitor:
    def test_count_retraces_counts_traces_not_calls(self):
        reg = MetricsRegistry()

        def f(x):
            return x * 2

        jf = jax.jit(obs_trace.count_retraces("f", f, registry=reg))
        jf(np.zeros((2,), np.float32))
        jf(np.ones((2,), np.float32))  # cache hit
        assert obs_trace.retrace_counts(reg) == {"f": 1.0}
        jf(np.zeros((3,), np.float32))  # new shape → retrace
        assert obs_trace.retrace_counts(reg) == {"f": 2.0}

    def test_k16_fit_zero_steady_state_recompiles(self):
        """The guard future PRs must not trip: after a warm epoch, a
        K=16 bundled fit (with telemetry + StatsListener attached, i.e.
        monitoring ON) compiles NOTHING in steady state."""
        from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener

        data = _batches(32)
        net = _mlp(16, telemetry=True)
        net.set_listeners(StatsListener(InMemoryStatsStorage(),
                                        reporting_frequency=8,
                                        session_id="guard"))
        net.fit(ExistingDataSetIterator(data), epochs=1)  # warm: compiles
        with obs_trace.RetraceMonitor() as mon:
            net.fit(ExistingDataSetIterator(data), epochs=2)
        assert mon.total() == 0, (
            f"steady-state recompiles detected: {mon.delta()}")

    def test_serving_storm_zero_recompiles(self):
        """Bucketed serving keeps the PR-3 discipline, now visible in
        the registry: warmup compiles every bucket, a mixed-size storm
        compiles nothing."""
        from deeplearning4j_tpu.serving.buckets import BucketPolicy
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        net = _mlp()
        eng = InferenceEngine(net, buckets=BucketPolicy(batch_buckets=[4, 8]))
        eng.warmup(example_shape=(12,))
        reg = eng.metrics.registry
        with obs_trace.RetraceMonitor(reg) as mon:
            rng = np.random.default_rng(0)
            for n in (1, 3, 4, 5, 8, 2, 7, 8, 1):
                eng.infer(rng.standard_normal((n, 12)).astype(np.float32))
        assert mon.total() == 0, mon.delta()
        assert obs_trace.retrace_counts(reg)["serving_forward"] == \
            eng.compile_count


# ---------------------------------------------------------------------------
# exporter + serving surfaces
# ---------------------------------------------------------------------------
class TestPhases:
    """obs/trace.py's phase primitive: one call site, the profiler's
    annotation and the ring + registry counters."""

    def test_nests_and_records_in_order(self):
        from tests.phase_checks import assert_nested_or_disjoint

        outer, inner = obs_trace.phase("t.outer"), obs_trace.phase("t.inner")
        assert obs_trace.phase("t.outer") is outer  # one object a name
        mark = time.time_ns()
        with outer:
            with inner:
                time.sleep(0.002)
            with inner:
                pass
        got = [e for e in obs_trace.phases(mark) if e[0].startswith("t.")]
        # appended when a phase ends: the two inner ones, then the outer
        assert [e[0] for e in got] == ["t.inner", "t.inner", "t.outer"]
        (_, a0, d0), (_, a1, d1), (_, a2, d2) = got
        assert a2 <= a0 and a0 + d0 <= a1 and a1 + d1 <= a2 + d2
        assert d0 >= 2_000_000 and all(isinstance(x, int) for x in (a0, d0))
        assert_nested_or_disjoint(got)
        # start_ns is on time.time_ns(), the profiler's wall clock
        assert mark <= a2 <= time.time_ns()

    def test_ring_is_bounded(self):
        p = obs_trace.phase("t.flood")
        for _ in range(obs_trace.RING_SIZE + 10):
            p.record(1, 1)
        ring = obs_trace.phases()
        assert len(ring) == obs_trace.RING_SIZE
        assert all(e[0] == "t.flood" for e in ring)

    def test_observe_lands_in_the_same_ring_and_registry(self):
        from deeplearning4j_tpu.obs.metrics import default_registry

        reg = default_registry()
        before = reg.family_values(obs_trace.PHASE_COUNTER).get(
            "phase=t.waited", 0.0)
        start = time.time_ns() - 5_000_000
        obs_trace.observe("t.waited", start, 5_000_000)
        assert obs_trace.phases()[-1] == ("t.waited", start, 5_000_000)
        assert reg.family_values(obs_trace.PHASE_COUNTER)[
            "phase=t.waited"] == before + 1
        assert reg.family_values(obs_trace.PHASE_SECONDS)[
            "phase=t.waited"] >= 0.005
        assert 'host_phase_total{phase="t.waited"}' in reg.prometheus_text()

    def test_each_next_times_items_not_exhaustion(self):
        p = obs_trace.phase("t.iterate")
        mark = time.time_ns()
        assert list(obs_trace.each_next(p, iter([1, 2, 3]))) == [1, 2, 3]
        got = [e for e in obs_trace.phases(mark) if e[0] == "t.iterate"]
        assert len(got) == 3
        assert not p._open.stack  # the exhausted next() was cancelled

    def test_threads_do_not_share_open_phases(self):
        import threading

        p = obs_trace.phase("t.threads")
        mark = time.time_ns()

        def work():
            for _ in range(200):
                with p:
                    with p:
                        pass

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = [e for e in obs_trace.phases(mark) if e[0] == "t.threads"]
        assert len(got) == 4 * 400 and all(d >= 0 for _, _, d in got)

    def test_three_wide_view_is_the_four_wide_one_without_the_cause(self):
        obs_trace.set_cause(None)
        mark = time.time_ns()
        with obs_trace.phase("t.view.outer"):
            obs_trace.set_cause(41)
            with obs_trace.phase("t.view.inner"):
                pass
        obs_trace.observe("t.view.seen", mark, 7)
        obs_trace.observe("t.view.seen", mark, 8, cause=99)
        obs_trace.set_cause(None)
        with obs_trace.phase("t.view.inner"):
            pass
        wide = [e for e in obs_trace.caused_phases(mark)
                if e[0].startswith("t.view.")]
        narrow = [e for e in obs_trace.phases(mark)
                  if e[0].startswith("t.view.")]
        # same entries, same order, plain tuples of three as before
        assert narrow == [e[:3] for e in wide]
        assert all(type(e) is tuple and len(e) == 3 for e in narrow)
        assert all(type(e) is tuple and len(e) == 4 for e in wide)
        # the cause is the thread's when the phase ENDS; observe takes
        # the thread's or the one it is given
        assert [(e[0], e[3]) for e in wide] == [
            ("t.view.inner", 41), ("t.view.outer", 41), ("t.view.seen", 41),
            ("t.view.seen", 99), ("t.view.inner", None)]
        assert len(obs_trace.phases()) == len(obs_trace.caused_phases())

    def test_the_cause_is_the_threads_own(self):
        import threading

        p = obs_trace.phase("t.cause.threads")
        mark = time.time_ns()

        def work(base):
            for i in range(100):
                obs_trace.set_cause(base + i)
                with p:
                    time.sleep(0)  # let the other threads in

        threads = [threading.Thread(target=work, args=(1000 * (k + 1),))
                   for k in range(4)]
        for t in threads:
            t.start()
        obs_trace.set_cause(7)
        with p:
            pass
        for t in threads:
            t.join()
        got = [e for e in obs_trace.caused_phases(mark)
               if e[0] == "t.cause.threads"]
        assert sorted(e[3] for e in got) == sorted(
            [7] + [1000 * (k + 1) + i for k in range(4) for i in range(100)])
        # a thread that never set one has none
        t = threading.Thread(target=lambda: p.record(1, 1))
        t.start()
        t.join()
        assert obs_trace.caused_phases()[-1] == ("t.cause.threads", 1, 1,
                                                 None)
        obs_trace.set_cause(None)

    @pytest.mark.parametrize("runtime", ["multilayer", "graph", "lm"])
    def test_training_phases_carry_the_iteration(self, runtime):
        if runtime == "lm":
            from deeplearning4j_tpu.models.transformer_lm import TransformerLM

            lm = TransformerLM(vocab_size=32, d_model=16, n_heads=2,
                               n_layers=1, max_length=8, seed=1).init()
            ids = np.arange(16, dtype=np.int32).reshape(2, 8) % 32
            lm.fit_batch(ids, ids)
            mark = time.time_ns()
            first = lm.iteration + 1  # counted before the step
            for _ in range(3):
                lm.fit_batch(ids, ids)
        else:
            conf = (NeuralNetConfiguration.builder().seed(7)
                    .updater(Adam(1e-3)).list()
                    .layer(DenseLayer(n_out=8, activation="relu"))
                    .layer(OutputLayer(n_out=3, activation="softmax",
                                       loss="mcxent"))
                    .set_input_type(InputType.feed_forward(4)).build())
            net = MultiLayerNetwork(conf).init()
            if runtime == "graph":
                net = net.to_computation_graph()
            it = ExistingDataSetIterator(_batches(3, b=8, d=4))
            net.fit(it, epochs=1)
            mark = time.time_ns()
            first = net.iteration  # counted after the step
            net.fit(it, epochs=1)
        got = [e for e in obs_trace.caused_phases(mark)
               if e[0] in ("train.put_batch", "train.dispatch",
                           "train.fetch_loss")]
        by_cause = {}
        for name, _, _, cause in got:
            by_cause.setdefault(cause, []).append(name)
        assert sorted(by_cause) == [first, first + 1, first + 2]
        for names in by_cause.values():
            assert names[:2] == ["train.put_batch", "train.dispatch"]

    def test_fit_phases_cover_the_step_and_never_retrace(self):
        """MultiLayerNetwork.fit: iterate / put_batch / dispatch /
        fetch_loss, at most four a step, nested or disjoint, covering
        the wall time of fit; phases on, the step compiles once."""
        from tests.phase_checks import assert_nested_or_disjoint, covered_ns

        from deeplearning4j_tpu.train.listeners import ScoreIterationListener

        # wide enough that a step outweighs the interpreter's own time
        # between two phases (some tens of microseconds), as on the chip
        conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_out=512, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(256)).build())
        net = MultiLayerNetwork(conf).init()
        net.listeners.append(ScoreIterationListener(1))
        batches = _batches(6, b=2048, d=256)
        it = ExistingDataSetIterator(batches)
        with obs_trace.RetraceMonitor() as mon:
            net.fit(it, epochs=1)  # compiles
            mon.rebaseline()
            mark = time.time_ns()
            net.fit(it, epochs=1)
            done = time.time_ns()
        assert mon.total() == 0, mon.delta()
        got = [e for e in obs_trace.phases(mark) if e[0].startswith("train.")]
        names = [e[0] for e in got]
        assert names == ["train.iterate", "train.put_batch", "train.dispatch",
                         "train.fetch_loss"] * 6
        assert_nested_or_disjoint(got)
        # from the first batch's iterate to the last step's end: fit's own
        # prologue and epilogue (epoch hooks, iterator reset, the prefetch
        # thread's start and join) are no part of a step
        lo, hi = got[0][1], max(a + d for _, a, d in got)
        assert mark <= lo and hi <= done
        assert covered_ns(got, lo, hi) >= 0.95 * (hi - lo)


class TestExporter:
    def test_negotiation_rule(self):
        assert wants_prometheus("text/plain;version=0.0.4")
        assert wants_prometheus("application/openmetrics-text")
        assert not wants_prometheus("application/json")
        assert not wants_prometheus("")
        assert wants_prometheus("application/json", "format=prometheus")
        assert not wants_prometheus("text/plain", "format=json")

    def test_http_endpoint(self):
        reg = MetricsRegistry()
        reg.counter("train_steps_total").inc(5)
        srv = MetricsServer(registry=reg, port=0).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            assert r.status == 200
            assert "application/json" in r.getheader("Content-Type")
            assert json.loads(r.read())["train_steps_total"] == 5.0
            conn.request("GET", "/metrics",
                         headers={"Accept": "text/plain"})
            r = conn.getresponse()
            assert r.status == 200
            assert "text/plain" in r.getheader("Content-Type")
            assert b"train_steps_total 5" in r.read()
            conn.request("GET", "/healthz")
            r = conn.getresponse()
            assert r.status == 200 and json.loads(r.read())["status"] == "ok"
            conn.request("GET", "/nope")
            r = conn.getresponse()
            assert r.status == 404
            r.read()
        finally:
            srv.shutdown()


class TestServingSurfaces:
    @pytest.fixture()
    def server(self):
        from deeplearning4j_tpu.serving.buckets import BucketPolicy
        from deeplearning4j_tpu.serving.engine import InferenceEngine
        from deeplearning4j_tpu.serving.server import InferenceServer

        eng = InferenceEngine(_mlp(),
                              buckets=BucketPolicy(batch_buckets=[4]))
        eng.warmup(example_shape=(12,))
        srv = InferenceServer(eng, port=0).start()
        yield srv
        srv.shutdown()

    def test_healthz_canary_keys(self, server):
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        conn.request("GET", "/healthz")
        r = conn.getresponse()
        assert r.status == 200
        body = json.loads(r.read())
        assert body["status"] == "ok"
        assert body["snapshot_version"] == 0
        assert "checkpoint_fingerprint" in body  # None for init engines
        assert body["uptime_s"] >= 0

    def test_metrics_content_negotiation(self, server):
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        conn.request("POST", "/predict",
                     json.dumps({"inputs": [[0.0] * 12]}))
        r = conn.getresponse()
        assert r.status == 200
        r.read()
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        assert "application/json" in r.getheader("Content-Type")
        snap = json.loads(r.read())
        assert "requests" in snap and "queue_depth" in snap
        conn.request("GET", "/metrics",
                     headers={"Accept": "text/plain;version=0.0.4"})
        r = conn.getresponse()
        assert "text/plain" in r.getheader("Content-Type")
        text = r.read().decode()
        assert "serving_requests_total" in text
        assert "serving_queue_depth" in text


# ---------------------------------------------------------------------------
# listener satellites
# ---------------------------------------------------------------------------
class TestPerformanceListenerAccounting:
    def test_variable_batch_sizes_accumulate(self):
        """samples/sec must reflect the ACTUAL per-step sizes: with a
        ragged tail (8,8,8,2 after the window opens) the ratio
        samples_per_sec / batches_per_sec — the dt cancels — is the true
        mean batch size, not the last one extrapolated."""
        from deeplearning4j_tpu.train.listeners import PerformanceListener

        class Model:
            last_batch_size = 0

            def score(self):
                return 0.0

        out = []
        lst = PerformanceListener(frequency=4, printer=out.append)
        m = Model()
        sizes = [8, 8, 8, 8, 2]  # first call opens the window
        for i, bs in enumerate(sizes, start=1):
            m.last_batch_size = bs
            lst.iteration_done(m, i, 0)
        assert len(out) == 1
        mean_bs = (lst.last_samples_per_sec / lst.last_batches_per_sec)
        assert mean_bs == pytest.approx((8 + 8 + 8 + 2) / 4)

    def test_bundle_path_uses_bundle_sizes(self):
        from deeplearning4j_tpu.train.listeners import PerformanceListener

        class Scores:
            def __init__(self, k):
                self.k = k

            def __len__(self):
                return self.k

        class Model:
            last_batch_size = 8

        out = []
        lst = PerformanceListener(frequency=4, printer=out.append)
        m = Model()
        lst.bundle_done(m, 0, 0, Scores(4))   # opens window
        m.last_batch_size = 4
        lst.bundle_done(m, 4, 0, Scores(4))   # 4 steps × batch 4
        assert len(out) == 1
        assert (lst.last_samples_per_sec / lst.last_batches_per_sec
                == pytest.approx(4.0))


class TestProfilerListenerFitExit:
    def test_closes_open_window_at_fit_exit(self, tmp_path):
        """A window spanning past the data (start=1, 999 iterations on a
        4-batch fit) used to leak an open trace; fit() exit closes it."""
        from deeplearning4j_tpu.train.listeners import ProfilerListener

        lst = ProfilerListener(str(tmp_path), start_iteration=1,
                               num_iterations=999)
        net = _mlp()
        net.set_listeners(lst)
        net.fit(ExistingDataSetIterator(_batches(4)), epochs=1)
        assert lst.completed and not lst._active
        # the profiler is actually released: a fresh trace can start
        jax.profiler.start_trace(str(tmp_path / "again"))
        jax.profiler.stop_trace()

    def test_closes_on_mid_epoch_exception(self, tmp_path):
        from deeplearning4j_tpu.data.iterators import DataSetIterator
        from deeplearning4j_tpu.train.listeners import ProfilerListener

        class Poisoned(DataSetIterator):
            def __init__(self, batches):
                self._b = list(batches)
                self._i = 0

            def has_next(self):
                return True

            def next(self):
                if self._i >= 2:
                    raise RuntimeError("boom mid-epoch")
                self._i += 1
                return self._b[self._i - 1]

            def reset(self):
                self._i = 0

            def async_supported(self):
                return False

            def batch(self):
                return 8

        lst = ProfilerListener(str(tmp_path), start_iteration=1,
                               num_iterations=999)
        net = _mlp()
        net.set_listeners(lst)
        with pytest.raises(RuntimeError, match="boom"):
            net.fit(Poisoned(_batches(4)), epochs=1)
        assert lst.completed and not lst._active
        jax.profiler.start_trace(str(tmp_path / "again"))
        jax.profiler.stop_trace()


class TestDataPipelineGauges:
    def test_consumer_wait_counter_grows_on_slow_producer(self):
        class Slow(ExistingDataSetIterator):
            def next(self):
                time.sleep(0.02)
                return super().next()

        _, before = data_wait_seconds()
        it = AsyncDataSetIterator(Slow(_batches(6)), queue_size=2)
        while it.has_next():
            it.next()
        it.shutdown()
        _, after = data_wait_seconds()
        assert after > before  # fit loop waited on the empty queue

    def test_producer_wait_counter_grows_on_slow_consumer(self):
        before, _ = data_wait_seconds()
        it = AsyncDataSetIterator(ExistingDataSetIterator(_batches(8)),
                                  queue_size=1)
        time.sleep(0.3)  # producer fills the depth-1 queue and blocks
        while it.has_next():
            it.next()
        it.shutdown()
        after, _ = data_wait_seconds()
        assert after > before

"""Forensic observability (obs/flight.py, obs/cost.py, serving/rtrace.py):
flight recorder + black-box dumps, per-request serving traces,
hardware-efficiency (MFU) profiling, and the hardening satellites
(server shutdown, registry concurrency, pad-waste metric).

The three ISSUE-7 acceptance drills live here as tier-1 tests:

1. a deliberately diverged fit (fault_injection NaN drill with
   ``max_consecutive_bad_steps`` armed) leaves a READABLE flight dump
   whose last events include the NaN-skips and the divergence trip;
2. a served request with tracing enabled returns a stage timeline whose
   durations sum to (within) the measured end-to-end latency;
3. MFU/FLOPs gauges appear in Prometheus exposition for both a bundled
   fit and a warmed serving engine.
"""

import gc
import http.client
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.obs import cost as obs_cost
from deeplearning4j_tpu.obs.exporter import MetricsServer
from deeplearning4j_tpu.obs.flight import (
    FlightRecorder,
    FlightRecorderListener,
    default_flight_recorder,
    find_dump,
    format_dump,
    install_signal_dump,
)
from deeplearning4j_tpu.obs.metrics import MetricsListener, MetricsRegistry
from deeplearning4j_tpu.serving import (
    BucketPolicy,
    InferenceEngine,
    InferenceServer,
)
from deeplearning4j_tpu.train.faults import (
    FaultPolicy,
    TrainingDivergedError,
    fault_injection,
    save_checkpoint,
)
from deeplearning4j_tpu.updaters import Adam


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Same heap-pressure hygiene as tests/test_serving.py: drop this
    module's executables when done."""
    yield
    gc.collect()
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _reset_default_recorder():
    """The default flight recorder is process-global (the fault guard
    and batcher record into it); restore its dump_dir and drop this
    test's events so later tests (incl. the fault-tolerance suite's own
    divergence drills) never auto-dump into a deleted tmpdir."""
    rec = default_flight_recorder()
    prev_dir = rec.dump_dir
    yield
    rec.dump_dir = prev_dir
    rec.clear()


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


def _serving_net(seed=7, n_in=4, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


# ---------------------------------------------------------------------------
# flight recorder core
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_ring_bounds_and_drop_accounting(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("step", iteration=i)
        assert len(rec) == 4
        assert rec.recorded_total == 10
        evs = rec.events()
        assert [e["iteration"] for e in evs] == [6, 7, 8, 9]
        assert [e["seq"] for e in evs] == [6, 7, 8, 9]
        snap = rec.snapshot()
        assert snap["dropped"] == 6
        assert rec.events(last=2)[0]["iteration"] == 8

    def test_dump_roundtrip_and_overwrite(self, tmp_path):
        rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
        assert rec.dump() is None  # empty ring: no misleading black box
        rec.record("a", x=1)
        p1 = rec.dump(reason="first")
        rec.record("b", y=2.5)
        p2 = rec.dump(reason="second")
        assert p1 == p2  # one file per process, atomically overwritten
        body = json.load(open(p2))
        assert body["reason"] == "second"
        assert [e["kind"] for e in body["events"]] == ["a", "b"]
        assert body["events"][1]["y"] == 2.5
        # the reader helpers resolve and render it
        assert find_dump(str(tmp_path)) == p2
        text = format_dump(body)
        assert "b" in text and "y=2.5" in text

    def test_non_jsonable_values_coerced(self, tmp_path):
        rec = FlightRecorder(dump_dir=str(tmp_path))
        rec.record("step", loss=np.float32(1.5), it=np.int64(3),
                    weird=object())
        body = json.load(open(rec.dump()))
        ev = body["events"][0]
        assert ev["loss"] == 1.5 and ev["it"] == 3
        assert isinstance(ev["weird"], str)

    def test_concurrent_record(self):
        rec = FlightRecorder(capacity=10_000)
        n_threads, per = 8, 500

        def writer(t):
            for i in range(per):
                rec.record("w", thread=t, i=i)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert rec.recorded_total == n_threads * per
        seqs = [e["seq"] for e in rec.events()]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)

    def test_find_dump_missing(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            find_dump(str(tmp_path))


# ---------------------------------------------------------------------------
# ACCEPTANCE DRILL 1: diverged fit leaves a readable black box
# ---------------------------------------------------------------------------
class TestDivergenceDrill:
    def test_nan_drill_dump(self, tmp_path):
        net = _mlp(fault_policy=FaultPolicy(
            skip_nonfinite=True, max_consecutive_bad_steps=2))
        net.add_listeners(FlightRecorderListener(directory=str(tmp_path),
                                                 loss_frequency=1))
        batches = _batches(10)
        with fault_injection(nan_grad_steps=[4, 5, 6]):
            with pytest.raises(TrainingDivergedError):
                net.fit(ExistingDataSetIterator(batches), epochs=1)
        path = find_dump(str(tmp_path))
        body = json.load(open(path))  # readable == parseable JSON
        kinds = [e["kind"] for e in body["events"]]
        # the LAST events tell the postmortem story: the NaN-skip
        # streak, the divergence trip, the dying fit
        tail = kinds[-6:]
        assert "nan_skip" in tail
        assert "divergence_trip" in tail
        assert kinds[-1] == "fit_exception"
        assert body["events"][-1]["error"] == "TrainingDivergedError"
        trip = [e for e in body["events"] if e["kind"] == "divergence_trip"]
        assert trip[-1]["consec"] == 2 and trip[-1]["limit"] == 2
        # healthy steps before the streak carried their losses
        losses = [e["loss"] for e in body["events"]
                  if e["kind"] == "step" and "loss" in e]
        assert losses and all(np.isfinite(losses[:3]))
        # the dump is the superset written at fit exit
        assert body["reason"] == "fit_exception"

    def test_divergence_dumps_even_without_listener(self, tmp_path):
        """check_fault_state dumps BEFORE raising whenever the default
        recorder has a dump_dir — a caller that swallows the error still
        leaves the postmortem."""
        rec = default_flight_recorder()
        rec.dump_dir = str(tmp_path)
        net = _mlp(fault_policy=FaultPolicy(
            skip_nonfinite=True, max_consecutive_bad_steps=1), seed=21)
        with fault_injection(nan_grad_steps=[2, 3]):
            try:
                net.fit(ExistingDataSetIterator(_batches(6, seed=3)),
                        epochs=1)
            except TrainingDivergedError:
                pass  # the swallowing caller
        body = json.load(open(find_dump(str(tmp_path))))
        assert any(e["kind"] == "divergence_trip" for e in body["events"])

    def test_transient_nan_skip_visible_under_bundling(self):
        """The per-dispatch tripwire only sees END-of-bundle consec: a
        NaN step that recovers before the bundle boundary checks in with
        consec==0, and only the bad_count delta against the owner's
        previous check reveals it. The black box must still get it."""
        rec = default_flight_recorder()
        before = rec.recorded_total
        net = _mlp(k=4, fault_policy=FaultPolicy(
            skip_nonfinite=True, max_consecutive_bad_steps=3), seed=33)
        with fault_injection(nan_grad_steps=[1]):
            net.fit(ExistingDataSetIterator(_batches(8, seed=5)), epochs=1)
        skips = [e for e in rec.events()
                 if e["seq"] >= before and e["kind"] == "nan_skip"]
        assert skips, "mid-bundle transient NaN left no nan_skip event"
        assert skips[0]["consec"] == 0 and skips[0]["bad_count"] >= 1
        # and ONE transient must not spam every later clean check
        assert len(skips) == 1


# ---------------------------------------------------------------------------
# flight listener behavior
# ---------------------------------------------------------------------------
class TestFlightRecorderListener:
    def test_clean_fit_records_and_dumps(self, tmp_path):
        rec = FlightRecorder(capacity=512)
        net = _mlp(k=4)
        net.add_listeners(FlightRecorderListener(
            recorder=rec, directory=str(tmp_path), loss_frequency=4))
        net.fit(ExistingDataSetIterator(_batches(8)), epochs=2)
        kinds = [e["kind"] for e in rec.events()]
        assert kinds.count("epoch_start") == 2
        assert kinds.count("epoch_end") == 2
        assert kinds[-1] == "fit_end"
        bundles = [e for e in rec.events() if e["kind"] == "bundle"]
        assert len(bundles) == 4  # 8 batches / K=4 per epoch x 2 epochs
        assert all(b["k"] == 4 for b in bundles)
        # every bundle spans a loss_frequency=4 hit → loss attached
        assert all("loss" in b and np.isfinite(b["loss"]) for b in bundles)
        # clean exit still leaves the black box on disk
        body = json.load(open(find_dump(str(tmp_path))))
        assert body["reason"] == "fit_end"

    def test_off_frequency_bundles_skip_the_fetch(self):
        """loss sampling respects the once-per-bundle discipline: with
        loss_frequency beyond the run length no scores are fetched at
        all (fetch_count is observable on BundleScores)."""
        from deeplearning4j_tpu.train import pipeline as _pipeline

        rec = FlightRecorder()
        net = _mlp(k=4, seed=9)
        net.add_listeners(FlightRecorderListener(recorder=rec,
                                                 loss_frequency=10_000))
        before = _pipeline._host_fetches
        net.fit(ExistingDataSetIterator(_batches(8, seed=2)), epochs=1)
        assert _pipeline._host_fetches == before  # zero score fetches
        bundles = [e for e in rec.events() if e["kind"] == "bundle"]
        assert len(bundles) == 2 and all("loss" not in b for b in bundles)

    def test_checkpoint_events(self, tmp_path):
        from deeplearning4j_tpu.train.faults import load_latest_valid

        rec = default_flight_recorder()
        net = _mlp(seed=11)
        net.fit(ExistingDataSetIterator(_batches(2)), epochs=1)
        path = save_checkpoint(net, str(tmp_path))
        load_latest_valid(str(tmp_path))
        kinds = [e["kind"] for e in rec.events()]
        assert "checkpoint_write" in kinds and "checkpoint_load" in kinds
        w = [e for e in rec.events() if e["kind"] == "checkpoint_write"][-1]
        assert w["path"] == path

    def test_sigterm_dump_chains_previous_handler(self, tmp_path):
        rec = default_flight_recorder()
        rec.dump_dir = str(tmp_path)
        rec.record("before_signal")
        hits = []
        prev = signal.signal(signal.SIGTERM, lambda s, f: hits.append(s))
        try:
            uninstall = install_signal_dump()
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 5
            while not hits and time.monotonic() < deadline:
                time.sleep(0.01)
            assert hits == [signal.SIGTERM]  # chained handler ran
            body = json.load(open(rec.dump_path()))
            assert body["reason"] == f"signal_{int(signal.SIGTERM)}"
            assert any(e["kind"] == "signal" for e in body["events"])
            uninstall()
            assert signal.getsignal(signal.SIGTERM) is not None
        finally:
            signal.signal(signal.SIGTERM, prev)


# ---------------------------------------------------------------------------
# ACCEPTANCE DRILL 2: traced request timeline
# ---------------------------------------------------------------------------
class TestRequestTraceDrill:
    def test_traced_request_timeline_sums(self):
        net = _serving_net()
        engine = InferenceEngine(net,
                                 buckets=BucketPolicy(batch_buckets=[4, 8]))
        engine.warmup()
        server = InferenceServer(engine, port=0, max_wait_ms=1.0).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=30)
            x = np.random.default_rng(0).standard_normal((3, 4)).astype(
                np.float32)
            t0 = time.perf_counter()
            conn.request("POST", "/predict",
                         json.dumps({"inputs": x.tolist(), "trace": True}))
            resp = conn.getresponse()
            body = json.loads(resp.read())
            wall_ms = (time.perf_counter() - t0) * 1e3
            assert resp.status == 200
            tl = body["trace"]
            names = [s["stage"] for s in tl["stages"]]
            assert names == ["queue", "assembly", "forward", "slice",
                             "respond"]
            # the intervals partition enqueue→respond: they sum exactly
            # to the reported total, and the total sits inside the
            # measured end-to-end latency (which adds HTTP + JSON time)
            ssum = sum(s["ms"] for s in tl["stages"])
            assert ssum == pytest.approx(tl["total_ms"], abs=0.01)
            assert tl["total_ms"] <= wall_ms + 0.01
            assert tl["bucket"] == 4
            assert tl["rows"] == 3 and tl["batch_rows_real"] == 3
            assert tl["batch_rows_padded"] == 4
            assert tl["pad_waste"] == pytest.approx(0.25)
            assert tl["model_version"] == 0
            # the same timeline landed in the /trace window
            conn.request("GET", "/trace")
            tb = json.loads(conn.getresponse().read())
            assert tb["recorded_total"] >= 1
            assert tb["traces"][-1]["total_ms"] > 0
            assert tb["pad_waste"]["4"]["real"] >= 3
            conn.close()
        finally:
            server.shutdown()

    def test_per_request_opt_in_when_server_tracing_off(self):
        engine = InferenceEngine(_serving_net(seed=8),
                                 buckets=BucketPolicy(batch_buckets=[4]))
        engine.warmup()
        server = InferenceServer(engine, port=0, max_wait_ms=1.0,
                                 trace_requests=False).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=30)
            x = [[0.0, 0.0, 0.0, 0.0]]
            conn.request("POST", "/predict", json.dumps({"inputs": x}))
            body = json.loads(conn.getresponse().read())
            assert "trace" not in body
            assert len(server.traces) == 0  # nothing sampled when off
            conn.request("POST", "/predict",
                         json.dumps({"inputs": x, "trace": True}))
            body = json.loads(conn.getresponse().read())
            assert body["trace"]["total_ms"] > 0  # opt-in still works
            conn.close()
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# pad-waste metric (satellite)
# ---------------------------------------------------------------------------
class TestPadWasteMetric:
    def test_engine_records_real_vs_padded(self):
        engine = InferenceEngine(_serving_net(seed=5),
                                 buckets=BucketPolicy(batch_buckets=[4, 8]))
        engine.warmup()  # warmup rows are exact-fit: zero waste
        waste0 = engine.metrics.pad_waste()
        assert all(v["padded"] == 0 for v in waste0.values())
        engine.infer(np.zeros((3, 4), np.float32))
        engine.infer(np.zeros((5, 4), np.float32))
        waste = engine.metrics.pad_waste()
        assert waste[4]["padded"] == waste0[4]["padded"] + 1
        assert waste[8]["padded"] == waste0[8]["padded"] + 3
        snap = engine.metrics.snapshot()
        assert snap["pad_waste"]["8"]["waste_ratio"] == pytest.approx(
            waste[8]["padded"] / (waste[8]["padded"] + waste[8]["real"]),
            abs=1e-4)
        text = engine.metrics.prometheus_text()
        assert "serving_padded_samples_total" in text
        assert "serving_real_samples_total" in text


# ---------------------------------------------------------------------------
# ACCEPTANCE DRILL 3: MFU / FLOPs gauges
# ---------------------------------------------------------------------------
class TestHardwareEfficiency:
    def test_bundled_fit_mfu_gauges(self):
        reg = MetricsRegistry()
        net = _mlp(k=4, seed=13)
        net.add_listeners(MetricsListener(registry=reg, frequency=4))
        ds = _batches(1, seed=5)[0]
        out = obs_cost.publish_train_cost(net, ds, steps_per_call=4,
                                          registry=reg)
        assert out["flops"] > 0 and out["flops_per_step"] > 0
        assert out["steps_per_call"] == 4
        net.fit(ExistingDataSetIterator(_batches(16, seed=5)), epochs=1)
        text = reg.prometheus_text()
        assert 'step_flops{k="4",step="train"}' in text
        assert 'step_bytes_accessed{k="4",step="train"}' in text
        assert 'model_flops_utilization{step="train"}' in text
        assert 'step_bytes_per_sec{step="train"}' in text
        # the fit published steps/sec, so scraped MFU is live and > 0
        mfu = reg.get("model_flops_utilization",
                      {"step": "train"}).value()
        assert 0 < mfu < 1

    def test_warmed_engine_mfu_gauges(self):
        engine = InferenceEngine(_serving_net(seed=6),
                                 buckets=BucketPolicy(batch_buckets=[4, 8]))
        engine.warmup()
        out = engine.publish_cost_metrics()
        assert out["bucket"] == 8
        assert out["flops"] > 0 and out["flops_per_example"] > 0
        reg = engine.metrics.registry
        text = reg.prometheus_text()
        assert 'model_flops_utilization{step="serving"}' in text
        assert 'step_flops{bucket="8",step="serving"}' in text
        # MFU is a scrape-to-scrape rate: baseline scrape, serve work,
        # second scrape shows utilization > 0
        gauge = reg.get("model_flops_utilization", {"step": "serving"})
        bps = reg.get("step_bytes_per_sec", {"step": "serving"})
        gauge.value()  # baseline
        for _ in range(3):
            engine.infer(np.zeros((8, 4), np.float32))
        time.sleep(obs_cost._RATE_MIN_WINDOW_S + 0.05)
        # a scrape evaluates BOTH gauges off the one shared rate
        # closure — the second must not read a consumed ~0 delta
        assert bps.value() > 0
        assert gauge.value() > 0

    def test_peak_flops_env_override(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "2.5e12")
        pk = obs_cost.hardware_peak_flops()
        assert pk["per_device"] == 2.5e12
        assert pk["source"] == "env:DL4J_TPU_PEAK_FLOPS"
        monkeypatch.delenv("DL4J_TPU_PEAK_FLOPS")
        pk = obs_cost.hardware_peak_flops()
        assert pk["peak_flops"] > 0 and "source" in pk

    def test_peak_flops_unknown_tpu_kind_is_an_error(self):
        """A TPU the peak table does not list has no utilization: the
        lookup raises instead of assuming the slowest generation."""
        from types import SimpleNamespace

        v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        pk = obs_cost.hardware_peak_flops([v5e] * 4)
        assert pk["per_device"] == 197e12 and pk["n_devices"] == 4
        unknown = SimpleNamespace(platform="tpu", device_kind="TPU v99x")
        with pytest.raises(ValueError, match="v99x"):
            obs_cost.hardware_peak_flops([unknown])

    def test_train_cost_does_not_perturb_training(self):
        """The analysis lowers with ShapeDtypeStructs — params and the
        rng stream must be untouched, so the fit after a cost report is
        bit-identical to one without it."""
        batches = _batches(6, seed=17)

        def run(with_cost):
            net = _mlp(k=1, seed=19)
            if with_cost:
                obs_cost.train_step_analysis(net, batches[0])
            net.fit(ExistingDataSetIterator(batches), epochs=1)
            return jax.tree_util.tree_leaves(net.params_)

        a, b = run(False), run(True)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_profiler_capture_and_busy_guard(self, tmp_path):
        res = obs_cost.profiler_capture(30, log_dir=str(tmp_path))
        assert res["ms"] == 30.0 and os.path.isdir(res["log_dir"])
        errs = []

        def long_capture():
            try:
                obs_cost.profiler_capture(1500)
            except obs_cost.ProfilerBusyError as e:
                errs.append(e)

        t = threading.Thread(target=long_capture)
        t.start()
        time.sleep(0.2)
        with pytest.raises(obs_cost.ProfilerBusyError):
            obs_cost.profiler_capture(30)
        t.join()
        assert not errs  # the long capture itself succeeded


# ---------------------------------------------------------------------------
# debug endpoints
# ---------------------------------------------------------------------------
class TestDebugEndpoints:
    def test_metrics_server_flight_and_profile(self):
        rec = default_flight_recorder()
        rec.record("endpoint_marker", tag="metrics-server")
        server = MetricsServer(registry=MetricsRegistry(), port=0).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=30)
            conn.request("GET", "/debug/flight")
            body = json.loads(conn.getresponse().read())
            assert any(e["kind"] == "endpoint_marker"
                       for e in body["events"])
            conn.request("GET", "/debug/profile?ms=20")
            resp = conn.getresponse()
            prof = json.loads(resp.read())
            assert resp.status == 200 and os.path.isdir(prof["log_dir"])
            conn.close()
        finally:
            server.shutdown()

    def test_inference_server_flight_endpoint(self):
        engine = InferenceEngine(_serving_net(seed=4),
                                 buckets=BucketPolicy(batch_buckets=[4]))
        server = InferenceServer(engine, port=0).start()
        try:
            default_flight_recorder().record("endpoint_marker",
                                             tag="inference-server")
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=30)
            conn.request("GET", "/debug/flight")
            body = json.loads(conn.getresponse().read())
            assert any(e["kind"] == "endpoint_marker"
                       for e in body["events"])
            conn.close()
        finally:
            server.shutdown()

    def test_cli_flight_dump_reader(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import flight_dump_main

        rec = FlightRecorder(dump_dir=str(tmp_path))
        rec.record("nan_skip", consec=2)
        rec.dump(reason="drill")
        assert flight_dump_main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "nan_skip" in out and "reason=drill" in out
        assert flight_dump_main([str(tmp_path), "--json"]) == 0
        assert json.loads(
            capsys.readouterr().out)["events"][0]["kind"] == "nan_skip"
        assert flight_dump_main([str(tmp_path / "nope")]) == 1


# ---------------------------------------------------------------------------
# shutdown hardening (satellite)
# ---------------------------------------------------------------------------
class TestServerShutdownHardening:
    def _no_hang(self, fn, timeout=5.0):
        t = threading.Thread(target=fn, daemon=True)
        t.start()
        t.join(timeout)
        assert not t.is_alive(), "shutdown hung"

    def test_metrics_server_shutdown_never_started(self):
        server = MetricsServer(registry=MetricsRegistry(), port=0)
        self._no_hang(server.shutdown)  # BaseServer.shutdown would hang

    def test_metrics_server_double_shutdown(self):
        server = MetricsServer(registry=MetricsRegistry(), port=0).start()
        server.shutdown()
        self._no_hang(server.shutdown)

    def test_metrics_server_port_released(self):
        server = MetricsServer(registry=MetricsRegistry(), port=0).start()
        port = server.port
        server.shutdown()
        again = MetricsServer(registry=MetricsRegistry(), port=port)
        assert again.port == port
        again.shutdown()

    def test_metrics_server_scrape_during_shutdown(self):
        """Scrapers racing shutdown get a response or a clean socket
        error — never a hung server or a dead handler thread wedging
        close."""
        server = MetricsServer(registry=MetricsRegistry(), port=0).start()
        port = server.port
        stop = threading.Event()
        errors = []

        def scraper():
            while not stop.is_set():
                try:
                    conn = http.client.HTTPConnection("127.0.0.1", port,
                                                      timeout=1)
                    conn.request("GET", "/metrics")
                    conn.getresponse().read()
                    conn.close()
                except OSError:
                    pass  # expected once the socket closes
                except Exception as e:  # pragma: no cover
                    errors.append(e)

        threads = [threading.Thread(target=scraper) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.1)
        self._no_hang(server.shutdown)
        stop.set()
        for t in threads:
            t.join(timeout=3)
        assert not errors

    def test_inference_server_double_shutdown(self):
        engine = InferenceEngine(_serving_net(seed=3),
                                 buckets=BucketPolicy(batch_buckets=[4]))
        server = InferenceServer(engine, port=0).start()
        server.shutdown()
        self._no_hang(server.shutdown)

    def test_inference_server_shutdown_never_started(self):
        engine = InferenceEngine(_serving_net(seed=2),
                                 buckets=BucketPolicy(batch_buckets=[4]))
        server = InferenceServer(engine, port=0)
        self._no_hang(server.shutdown)


# ---------------------------------------------------------------------------
# registry concurrency (satellite)
# ---------------------------------------------------------------------------
class TestRegistryConcurrency:
    def test_writers_vs_scraper(self):
        """N writer threads hammering one counter + one histogram while
        readers scrape: no lost increments, no torn quantiles (every
        scraped quantile lies within the observed value range), no
        exceptions."""
        reg = MetricsRegistry()
        counter = reg.counter("c_total")
        hist = reg.histogram("h_seconds", ring_size=256)
        n_threads, per = 6, 400
        lo, hi = 0.5, 2.5
        stop = threading.Event()
        errors = []

        def writer(t):
            rng = np.random.default_rng(t)
            for _ in range(per):
                counter.inc()
                hist.observe(float(rng.uniform(lo, hi)))

        def reader():
            while not stop.is_set():
                try:
                    text = reg.prometheus_text()
                    assert "c_total" in text
                    snap = reg.snapshot()
                    h = snap["h_seconds"]
                    for q in ("p50", "p90", "p99"):
                        if h[q] is not None:
                            assert lo <= h[q] <= hi, (q, h[q])
                    q99 = hist.quantile(0.99)
                    if q99 is not None:
                        assert lo <= q99 <= hi
                except Exception as e:  # pragma: no cover
                    errors.append(e)
                    return

        writers = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        readers = [threading.Thread(target=reader) for _ in range(2)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join()
        stop.set()
        for t in readers:
            t.join(timeout=5)
        assert not errors
        assert counter.value() == n_threads * per  # no lost increments
        assert hist.count == n_threads * per

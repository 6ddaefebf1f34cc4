"""Fused-kernel layer (ISSUE 12, nn/ops/): KernelRegistry contract,
fused LSTM cell, fused ZeRO-1 update, int8 serving matmul.

Tier-1 runs everything on the CPU mesh: the kernels execute through the
Pallas INTERPRETER (``DL4J_TPU_*=interpret`` — real kernel math, XLA
execution), the fallback paths run natively, and forced probe failures
assert the fallback contract. What the TPU's compiler makes of the same
kernels at real widths is ``tests/test_tpu_compile.py``.

Parity contract asserted here (and documented in ARCHITECTURE.md):
- LSTM cell: forward BIT-exact vs the reference step at fp32 (aligned
  AND lane-padded shapes); grads ≤ 1e-5; bf16 ≤ 2e-2.
- ZeRO-1 fused update: BIT-exact params + Adam slots vs the unfused
  step, including odd-count padding groups.
- int8 matmul: kernel ≡ XLA reference bit-exact at fp32; quantized vs
  f32 serving bounded by the per-channel quantization error (top-1
  agreement on zoo-style heads).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.ops import fused_lstm, fused_update, int8_matmul
from deeplearning4j_tpu.nn.ops.registry import (
    ENV_FLAGS,
    KernelRegistry,
    default_kernel_registry,
    kernel_route,
)


@pytest.fixture
def kernel_env(monkeypatch):
    """Force a kernel mode for one test and leave the process-global
    registry clean afterwards (the registry caches per-process; a test
    must not leak its mode into the rest of the suite)."""
    touched = []

    def set_mode(name, mode):
        monkeypatch.setenv(ENV_FLAGS[name], mode)
        default_kernel_registry().reset(name)
        touched.append(name)

    yield set_mode
    for name in touched:
        default_kernel_registry().reset(name)


def _rand(shape, seed=0, dtype=np.float32):
    return np.asarray(np.random.default_rng(seed).standard_normal(shape),
                      dtype)


# ==========================================================================
# registry
# ==========================================================================
class TestKernelRegistry:
    def test_probe_once_per_process(self):
        reg = KernelRegistry()
        calls = []

        def probe():
            calls.append(1)

        assert reg.probe("fused_lstm", ("k",), probe) is True
        assert reg.probe("fused_lstm", ("k",), probe) is True
        assert len(calls) == 1  # second resolution is a cache hit

    def test_failed_probe_caches_and_reports(self):
        from deeplearning4j_tpu.obs import flight

        reg = KernelRegistry()
        calls = []

        def probe():
            calls.append(1)
            raise RuntimeError("Mosaic reject: Bad lhs type")

        n_before = len(flight.default_flight_recorder())
        assert reg.probe("fused_lstm", ("bad",), probe) is False
        assert reg.probe("fused_lstm", ("bad",), probe) is False
        assert len(calls) == 1  # deterministic reject: exactly one attempt
        events = flight.default_flight_recorder().events()
        new = [e for e in events if e["kind"] == "kernel_fallback"]
        assert any("Bad lhs type" in e.get("reason", "") for e in new)
        assert len(flight.default_flight_recorder()) > n_before

    def test_concurrent_same_key_probes_run_once(self):
        """Probes run OUTSIDE the registry lock; same-key racers wait on
        the in-flight probe instead of compiling twice."""
        import threading
        import time

        reg = KernelRegistry()
        calls = []

        def probe():
            calls.append(1)
            time.sleep(0.15)

        results = []
        threads = [threading.Thread(
            target=lambda: results.append(
                reg.probe("fused_lstm", ("race",), probe)))
            for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == [True] * 4
        assert len(calls) == 1

    def test_fused_conv_kill_switch(self, kernel_env):
        from deeplearning4j_tpu.nn.ops import fused_conv

        kernel_env("fused_conv", "0")
        fused_conv._PROBE_CACHE.clear()
        try:
            assert fused_conv.fused_conv_available(jnp.bfloat16) is False
            snap = default_kernel_registry().snapshot()["fused_conv"]
            assert any("DL4J_TPU_FUSED_CONV=0" in v["reason"]
                       for v in snap.values())
        finally:
            fused_conv._PROBE_CACHE.clear()

    def test_transient_failure_retried(self):
        reg = KernelRegistry()
        calls = []

        def probe():
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("tpu_compile_helper subprocess exit "
                                   "code 1")

        assert reg.probe("fused_conv", ("flaky",), probe) is True
        assert len(calls) == 2

    def test_enabled_gauge_on_default_metrics(self):
        from deeplearning4j_tpu.obs.metrics import default_registry

        reg = KernelRegistry()
        reg.probe("int8_matmul", ("g1",), lambda: None)
        g = default_registry().get("kernel_enabled",
                                   labels={"name": "int8_matmul"})
        assert g is not None and g.value() == 1.0

    def test_env_kill_switch(self, kernel_env):
        kernel_env("fused_lstm", "0")
        assert kernel_route("fused_lstm", ("any",)) is None
        assert default_kernel_registry().enabled(
            "fused_lstm", ("any",)) is False

    def test_auto_mode_disables_off_tpu(self):
        reg = default_kernel_registry()
        reg.reset("fused_lstm")
        assert kernel_route("fused_lstm", ("cpukey",)) is None
        snap = reg.snapshot()["fused_lstm"]
        assert any("non-TPU backend" in v["reason"] for v in snap.values())
        reg.reset("fused_lstm")

    def test_interpret_mode_routes(self, kernel_env):
        kernel_env("fused_lstm", "interpret")
        assert kernel_route("fused_lstm", ("ik",)) is True

    @pytest.mark.parametrize("mode,route", [("0", None), ("1", None),
                                            ("interpret", True)],
                             ids=["off", "auto-on-the-cpu", "interpret"])
    def test_kv_column_write_name_and_switch(self, kernel_env, mode, route):
        """The cache's column write is a registered name with the
        conventional switch: 0 kills it, 1 falls back off the TPU (both
        recorded under the slab's key), ``interpret`` routes to the
        Pallas interpreter and the probe holds the kernel to the
        ``dynamic_update_slice`` form."""
        from deeplearning4j_tpu.nn.ops import kv_column_write as kcw

        assert ENV_FLAGS[kcw.NAME] == "DL4J_TPU_KV_COLUMN_WRITE"
        kernel_env(kcw.NAME, mode)
        key = (2, 3, 2, 16, 128, "float32")
        assert kernel_route(kcw.NAME, key) is route
        impl = kcw.kv_column_write_impl(*key[:5], jnp.float32)
        assert (impl is None) == (route is None)
        verdict = default_kernel_registry().snapshot()[kcw.NAME][repr(key)]
        assert verdict["enabled"] is (route is True)
        if route:
            assert impl.keywords == {"lb": 2, "interpret": True}


# ==========================================================================
# fused LSTM cell
# ==========================================================================
class TestFusedLSTMCell:
    @pytest.mark.parametrize("n_in,n", [(128, 128), (77, 256), (64, 96)])
    @pytest.mark.parametrize("peephole", [False, True])
    def test_forward_parity_fp32(self, n_in, n, peephole):
        B = 8
        x, h, c = (_rand((B, d), i) for i, d in
                   enumerate((n_in, n, n)))
        Wx, Wh, b = _rand((n_in, 4 * n), 3), _rand((n, 4 * n), 4), \
            _rand((4 * n,), 5)
        peeps = ((_rand((n,), 6), _rand((n,), 7), _rand((n,), 8))
                 if peephole else ())
        args = tuple(jnp.asarray(a) for a in (x, h, c, Wx, Wh, b) + peeps)
        # jit both legs: that is how every real caller runs them (eager
        # op-by-op dispatch takes a different gemm path than the
        # compiled program and is ~1e-7 off EITHER compiled leg)
        hf, cf = jax.jit(lambda *a: fused_lstm.fused_lstm_cell(
            *a, interpret=True))(*args)
        hr, cr = jax.jit(fused_lstm.reference_lstm_cell)(*args)
        # lane-aligned: bit-exact. Lane-padded: the zero-padded gemm may
        # sum in another order (it does under the XLA:CPU of jax 0.9),
        # so h'/c' agree within one ulp of the largest pre-activation
        # (every gate nonlinearity has slope <= 1)
        z = (x.astype(np.float64) @ Wx + h.astype(np.float64) @ Wh + b)
        atol = (0.0 if n_in % 128 == 0 and n % 128 == 0
                else float(np.spacing(np.float32(np.max(np.abs(z))))))
        np.testing.assert_allclose(np.asarray(hf), np.asarray(hr),
                                   rtol=0, atol=atol)
        np.testing.assert_allclose(np.asarray(cf), np.asarray(cr),
                                   rtol=0, atol=atol)

    def test_gradients_close(self):
        n_in, n, B = 64, 96, 8
        args = tuple(jnp.asarray(a) for a in (
            _rand((B, n_in), 0), _rand((B, n), 1), _rand((B, n), 2),
            _rand((n_in, 4 * n), 3), _rand((n, 4 * n), 4),
            _rand((4 * n,), 5), _rand((n,), 6), _rand((n,), 7),
            _rand((n,), 8)))

        def loss(cell):
            def f(*a):
                hn, cn = cell(*a)
                return jnp.sum(hn ** 2) + jnp.sum(cn ** 2)
            return f

        gf = jax.grad(loss(lambda *a: fused_lstm.fused_lstm_cell(
            *a, interpret=True)), argnums=tuple(range(9)))(*args)
        gr = jax.grad(loss(fused_lstm.reference_lstm_cell),
                      argnums=tuple(range(9)))(*args)
        for i, (a, b) in enumerate(zip(gf, gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"grad[{i}]")

    def test_bf16_documented_tolerance(self):
        n_in = n = 128
        B = 8
        mk = lambda s, i: jnp.asarray(_rand(s, i)).astype(jnp.bfloat16)
        args = (mk((B, n_in), 0), mk((B, n), 1), mk((B, n), 2),
                mk((n_in, 4 * n), 3), mk((n, 4 * n), 4), mk((4 * n,), 5))
        hf, cf = fused_lstm.fused_lstm_cell(*args, interpret=True)
        hr, cr = fused_lstm.reference_lstm_cell(*args)
        err = np.max(np.abs(np.asarray(hf, np.float32)
                            - np.asarray(hr, np.float32)))
        assert err <= 2e-2  # one MXU pass vs "highest" XLA: documented

    def test_layer_scan_parity_fused_vs_reference(self, kernel_env):
        """Full-sequence apply_with_carry through the fused cell
        (interpret) vs the reference scan: the isolated cell is
        bit-exact, but inside the scan body XLA fuses the surrounding
        ops differently per leg (FMA/epilogue reassociation) — the
        documented full-sequence tolerance is ≤1e-6 absolute at fp32
        (T=1 decode, the latency path, IS bit-exact — see
        TestLSTMDecodeCellPath)."""
        from deeplearning4j_tpu.nn.conf.input_type import InputType
        from deeplearning4j_tpu.nn.conf.layers.recurrent import GravesLSTM

        layer = GravesLSTM(n_out=64, n_in=32, activation="tanh")
        layer.initialize(InputType.recurrent(32))
        params = layer.init_params(jax.random.PRNGKey(0),
                                   InputType.recurrent(32))
        x = jnp.asarray(_rand((4, 12, 32), 1))
        carry = layer.init_carry(4)
        y_ref, c_ref = jax.jit(
            lambda p, x, c: layer.apply_with_carry(p, x, c))(params, x,
                                                             carry)
        kernel_env("fused_lstm", "interpret")
        y_f, c_f = jax.jit(
            lambda p, x, c: layer.apply_with_carry(p, x, c))(params, x,
                                                             carry)
        snap = default_kernel_registry().snapshot()["fused_lstm"]
        assert any(v["enabled"] for v in snap.values())
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_ref),
                                   rtol=0, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(c_f),
                        jax.tree_util.tree_leaves(c_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-6)

    def test_training_fit_parity(self, kernel_env):
        """3 fit steps of the textgen-style stack, fused(interpret) vs
        reference: params within the backward-recompute tolerance (the
        fused backward recomputes gates — same math, XLA op order)."""
        from deeplearning4j_tpu.models.textgen_lstm import (
            TextGenerationLSTM,
        )

        def fit_one():
            m = TextGenerationLSTM(num_classes=11, units=32,
                                   max_length=8).init()
            X = _rand((4, 8, 11), 0)  # (batch, time, vocab) one-hot-ish
            y = np.abs(_rand((4, 8, 11), 1))
            y = y / np.sum(y, axis=-1, keepdims=True)
            for _ in range(3):
                m.fit(X, y.astype(np.float32))
            return m.params_

    # reference leg first (default env: auto → CPU fallback)
        p_ref = fit_one()
        kernel_env("fused_lstm", "interpret")
        p_f = fit_one()
        for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                        jax.tree_util.tree_leaves(p_f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_forced_probe_failure_falls_back_identical(self, kernel_env,
                                                       monkeypatch):
        from deeplearning4j_tpu.nn.conf.input_type import InputType
        from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM
        from deeplearning4j_tpu.obs import flight

        layer = LSTM(n_out=16, n_in=8, activation="tanh")
        layer.initialize(InputType.recurrent(8))
        params = layer.init_params(jax.random.PRNGKey(0),
                                   InputType.recurrent(8))
        x = jnp.asarray(_rand((2, 5, 8), 2))
        carry = layer.init_carry(2)
        y_ref, _ = layer.apply_with_carry(params, x, carry)

        kernel_env("fused_lstm", "interpret")
        monkeypatch.setattr(
            fused_lstm, "_probe_cell",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("forced probe failure")))
        y_f, _ = layer.apply_with_carry(params, x, carry)
        np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_ref))
        snap = default_kernel_registry().snapshot()["fused_lstm"]
        assert any(not v["enabled"] and "forced probe failure"
                   in v["reason"] for v in snap.values())
        assert any(e["kind"] == "kernel_fallback"
                   for e in flight.default_flight_recorder().events())

    def test_exotic_activation_stays_on_reference(self):
        from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM

        layer = LSTM(n_out=16, n_in=8, activation="relu")
        assert fused_lstm.cell_for(layer, jnp.float32) is None


# ==========================================================================
# LSTM decode cell path (PR 9 residue: engine decode reuses the cell)
# ==========================================================================
class TestLSTMDecodeCellPath:
    def _model(self):
        from deeplearning4j_tpu.models.textgen_lstm import (
            TextGenerationLSTM,
        )

        return TextGenerationLSTM(num_classes=23, units=32,
                                  max_length=16).init()

    def _run(self, model, cell_path, n_req=4):
        from deeplearning4j_tpu.serving.generate import GenerationEngine

        eng = GenerationEngine(model, n_slots=3, max_length=48,
                               decode_cell_path=cell_path,
                               default_timeout_s=120.0)
        used_cell = eng.backend.cell_path
        eng.warmup()
        before = dict(eng.trace_counts)
        prompts = [np.random.default_rng(i).integers(0, 23, (6 + i,))
                   .astype(np.int32) for i in range(n_req)]
        outs = [eng.generate(p, max_new=10) for p in prompts]
        retraces = {k: eng.trace_counts.get(k, 0) - before.get(k, 0)
                    for k in eng.trace_counts}
        eng.shutdown()
        return outs, retraces, used_cell

    def test_cell_path_bit_identical_and_zero_retraces(self):
        model = self._model()
        o_legacy, r_legacy, used_l = self._run(model, False)
        o_cell, r_cell, used_c = self._run(model, True)
        assert not used_l and used_c
        for a, b in zip(o_legacy, o_cell):
            np.testing.assert_array_equal(a, b)
        # the satellite's retrace guard: 0 steady-state recompiles with
        # the cell path AND with the fallback
        assert all(v == 0 for v in r_legacy.values()), r_legacy
        assert all(v == 0 for v in r_cell.values()), r_cell

    def test_cell_path_with_fused_kernel_interpret(self, kernel_env):
        model = self._model()
        o_ref, _, _ = self._run(model, True)
        kernel_env("fused_lstm", "interpret")
        o_k, r_k, used = self._run(model, True)
        assert used
        assert all(v == 0 for v in r_k.values()), r_k
        # greedy decode through the interpret kernel stays bit-identical
        # (cell forward is bit-exact at fp32)
        for a, b in zip(o_ref, o_k):
            np.testing.assert_array_equal(a, b)

    def test_describe_reports_cell_path(self):
        from deeplearning4j_tpu.serving.generate import GenerationEngine

        eng = GenerationEngine(self._model(), n_slots=2, max_length=32)
        try:
            assert eng.describe()["decode_cell_path"] is True
        finally:
            eng.shutdown()

    def test_unsupported_stack_falls_back_to_forward_path(self):
        from deeplearning4j_tpu.serving.generate import (
            _cell_decode_supported,
        )
        from deeplearning4j_tpu.nn.conf import (
            InputType,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.conf.layers import (
            GravesBidirectionalLSTM,
            RnnOutputLayer,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = (NeuralNetConfiguration.builder().seed(1).list()
                .layer(GravesBidirectionalLSTM(n_out=8))
                .layer(RnnOutputLayer(n_out=5, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(5)).build())
        net = MultiLayerNetwork(conf).init()
        assert not _cell_decode_supported(net)


# ==========================================================================
# chip_smoke.py: on the chip a fallback is a failure, not a slow pass
# ==========================================================================
def test_chip_smoke_fails_when_a_kernel_it_asked_for_fell_back(monkeypatch):
    """The registry turns any probe failure into "use the reference", so
    on the chip a refused kernel would still pass every phase on dense
    XLA. ``chip_smoke.phase_kernels`` is what makes that loud: steer a
    flash probe to fail and the smoke's verdict for the TPU platform
    raises with the compiler's words (off the chip it only reports)."""
    import importlib.util
    import os

    import deeplearning4j_tpu.nn.conf.layers.attention as A

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: forced")

    default_kernel_registry().reset()
    monkeypatch.setattr(A, "_FLASH_PROBE_CACHE", {})
    assert chip_smoke.phase_kernels("tpu")["refused"] == []
    monkeypatch.setattr(A, "_probe_compiles", refuse)
    assert A._flash_attention_impl(jnp.bfloat16, 512, 64, True) is None
    try:
        report = chip_smoke.phase_kernels("cpu")
        assert len(report["refused"]) == 1
        with pytest.raises(AssertionError, match="Mosaic failed.*forced"):
            chip_smoke.phase_kernels("tpu")
    finally:
        default_kernel_registry().reset()


# ==========================================================================
# fused ZeRO-1 update
# ==========================================================================
class TestFusedZero1:
    def _build(self, seed=7):
        from deeplearning4j_tpu.nn.conf import (
            InputType,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.conf.layers import (
            DenseLayer,
            OutputLayer,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.updaters import Adam

        # 13→30→7: 637 total elements, NOT divisible by the 8 shards →
        # the flat shard carries real zero-padding (odd-count parity)
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Adam(1e-2)).weight_init("xavier").list()
                .layer(DenseLayer(n_out=30, activation="relu"))
                .layer(OutputLayer(n_out=7, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(13)).build())
        return MultiLayerNetwork(conf).init()

    def _run_steps(self, fused, steps=4):
        from deeplearning4j_tpu.parallel import zero
        from deeplearning4j_tpu.parallel.mesh import TrainingMesh

        mesh = TrainingMesh(data=8)
        net = self._build()
        step, layout = zero.make_sharded_train_step(net, mesh,
                                                    fused_update=fused)
        assert layout.n_padding() > 0  # the odd-count case is real
        zopt = zero.shard_model_opt_state(net, layout, mesh=mesh.mesh)
        params, state = net.params_, net.state_
        rng = np.random.default_rng(0)
        X = rng.standard_normal((16, 13)).astype(np.float32)
        y = np.eye(7, dtype=np.float32)[rng.integers(0, 7, 16)]
        for it in range(steps):
            params, zopt, state, score = step(
                params, zopt, state, jnp.asarray(X), jnp.asarray(y),
                None, None, jax.random.PRNGKey(0),
                jnp.asarray(it, jnp.int32), jnp.asarray(0, jnp.int32))
        return params, zopt

    def test_fused_parity_params_and_slots(self, kernel_env):
        """One step: params and both Adam slots within the kernel's
        parity contract (``fused_update.parity_atol``; the two programs
        may round ``b*m + (1-b)*g`` once or twice). Four steps: the
        trajectories stay together to float32 resolution."""
        kernel_env("fused_zero1", "interpret")
        for steps, rtol in ((1, 0.0), (4, 1e-5)):
            p_ref, z_ref = self._run_steps(False, steps=steps)
            p_f, z_f = self._run_steps(None, steps=steps)
            ref = [np.asarray(a) for a in
                   jax.tree_util.tree_leaves((p_ref, z_ref))]
            atol = fused_update.parity_atol(*ref)
            for a, b in zip(ref, jax.tree_util.tree_leaves((p_f, z_f))):
                np.testing.assert_allclose(np.asarray(b), a, rtol=rtol,
                                           atol=atol)
        snap = default_kernel_registry().snapshot().get("fused_zero1", {})
        assert any(v["enabled"] for v in snap.values())

    def test_forced_probe_failure_falls_back_identical(self, kernel_env,
                                                       monkeypatch):
        p_ref, z_ref = self._run_steps(False)
        kernel_env("fused_zero1", "interpret")
        monkeypatch.setattr(
            fused_update, "_probe_group",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("forced zero1 probe failure")))
        p_f, z_f = self._run_steps(None)
        for a, b in zip(jax.tree_util.tree_leaves((p_ref, z_ref)),
                        jax.tree_util.tree_leaves((p_f, z_f))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        snap = default_kernel_registry().snapshot()["fused_zero1"]
        assert any("forced zero1 probe failure" in v["reason"]
                   for v in snap.values())

    def test_non_adam_groups_stay_on_reference(self, kernel_env):
        from deeplearning4j_tpu.parallel.zero import build_layout
        from deeplearning4j_tpu.nn.conf import (
            InputType,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.conf.layers import OutputLayer
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.updaters import RmsProp

        kernel_env("fused_zero1", "interpret")
        conf = (NeuralNetConfiguration.builder().seed(1)
                .updater(RmsProp(1e-2)).list()
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(6)).build())
        net = MultiLayerNetwork(conf).init()
        layout = build_layout(net, 4)
        impls = fused_update.resolve_group_impls(layout, None)
        assert impls == [None] * len(layout.groups)

    def test_fused_adam_apply_padding_lanes_stay_zero(self):
        # 3 × 100 elements: the kernel pads to full (rows, 128) tiles —
        # padded lanes must come back zero (they are sliced off, but the
        # invariant is what makes the bit-parity argument local)
        p = jnp.asarray(_rand((3, 100), 0))
        g = jnp.asarray(_rand((3, 100), 1))
        m = jnp.asarray(_rand((3, 100), 2))
        v = jnp.abs(jnp.asarray(_rand((3, 100), 3)))
        new_p, m2, v2 = jax.jit(lambda *a: fused_update.fused_adam_apply(
            *a, b1=0.9, b2=0.999, eps=1e-8, interpret=True))(
            p, g, m, v, jnp.asarray(0.01, jnp.float32))
        ref_m = jax.jit(lambda m, g: 0.9 * m + (1.0 - 0.9) * g)(m, g)
        np.testing.assert_allclose(
            np.asarray(m2), np.asarray(ref_m), rtol=0,
            atol=fused_update.parity_atol(m, g))
        assert new_p.shape == (3, 100)


# ==========================================================================
# int8 serving matmul
# ==========================================================================
class TestInt8Matmul:
    def test_quantization_error_bound(self):
        w = _rand((64, 32), 0)
        q, s = int8_matmul.quantize_int8(w)
        assert q.dtype == np.int8 and s.shape == (32,)
        err = np.abs(w - q.astype(np.float32) * s)
        assert np.all(err <= s / 2 + 1e-9)  # round-to-nearest bound

    def test_kernel_bit_exact_vs_reference_fp32(self):
        x = jnp.asarray(_rand((8, 100), 1))
        q, s = int8_matmul.quantize_int8(_rand((100, 40), 2) * 0.2)
        got = int8_matmul.int8_matmul(x, jnp.asarray(q), jnp.asarray(s),
                                      interpret=True)
        want = int8_matmul.int8_matmul_reference(x, jnp.asarray(q),
                                                 jnp.asarray(s))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_rank3_head(self):
        x = jnp.asarray(_rand((2, 5, 16), 0))
        q, s = int8_matmul.quantize_int8(_rand((16, 9), 1))
        params = {"W_q8": jnp.asarray(q), "W_scale": jnp.asarray(s)}
        y = int8_matmul.serving_matmul(params, x)
        assert y.shape == (2, 5, 9)

    def _trained_net(self):
        from deeplearning4j_tpu.nn.conf import (
            InputType,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.conf.layers import (
            DenseLayer,
            OutputLayer,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.updaters import Adam

        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(Adam(1e-3)).weight_init("xavier").list()
                .layer(DenseLayer(n_out=64, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(32)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        X = rng.standard_normal((120, 32)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 120)]
        for _ in range(20):
            net.fit(X, y)
        return net, X

    def test_engine_int8_top1_agreement_and_fp32_untouched(self):
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        net, X = self._trained_net()
        e_f32 = InferenceEngine(net)
        e_i8 = InferenceEngine(net, int8_serving=True)
        a = e_f32.infer(X[:64])
        b = e_i8.infer(X[:64])
        agree = np.mean(np.argmax(a, 1) == np.argmax(b, 1))
        assert agree >= 0.99
        # documented tolerance: probabilities move by the per-channel
        # quantization error, not more
        assert np.max(np.abs(a - b)) < 0.05
        # the MODEL keeps fp32 weights (training/checkpoints never see q8)
        assert "W" in net.params_[0] and "W_q8" not in net.params_[0]
        rep = e_i8.int8_report
        assert rep["layers_quantized"] == 2
        assert rep["weight_bytes_int8"] < 0.3 * rep["weight_bytes_fp32"]
        assert e_i8.describe()["int8_serving"] is True

    def test_zoo_model_int8_serving_top1(self):
        """The ISSUE's zoo-model oracle: serve a zoo architecture's
        heads int8-quantized; top-1 must agree with fp32 serving."""
        from deeplearning4j_tpu.models.lenet import LeNet
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        assert LeNet.serving_int8  # hint: heads tolerate quantization
        net = LeNet(num_classes=10).init()
        rng = np.random.default_rng(0)
        X = rng.standard_normal((60, 28, 28, 1)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 60)]
        for _ in range(6):
            net.fit(X, y)
        a = InferenceEngine(net).infer(X[:32])
        e_i8 = InferenceEngine(net, int8_serving=True)
        b = e_i8.infer(X[:32])
        assert e_i8.int8_report["layers_quantized"] >= 1
        assert np.mean(np.argmax(a, 1) == np.argmax(b, 1)) >= 0.99

    def test_engine_kernel_interpret_vs_fallback(self, kernel_env):
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        net, X = self._trained_net()
        b_ref = InferenceEngine(net, int8_serving=True).infer(X[:16])
        kernel_env("int8_matmul", "interpret")
        e_k = InferenceEngine(net, int8_serving=True)
        b_k = e_k.infer(X[:16])
        snap = default_kernel_registry().snapshot().get("int8_matmul", {})
        assert any(v["enabled"] for v in snap.values())
        # same expression; the kernel's lane-padded gemm may sum in
        # another order than the reference's (it does under the XLA:CPU
        # of jax 0.9), which moves the probabilities by a few float32
        # ulps of the logits
        np.testing.assert_allclose(b_k, b_ref, rtol=2e-6, atol=1e-7)

    def test_forced_probe_failure_serves_reference(self, kernel_env,
                                                   monkeypatch):
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        net, X = self._trained_net()
        b_ref = InferenceEngine(net, int8_serving=True).infer(X[:16])
        kernel_env("int8_matmul", "interpret")
        monkeypatch.setattr(
            int8_matmul, "_probe_int8",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("forced int8 probe failure")))
        b_f = InferenceEngine(net, int8_serving=True).infer(X[:16])
        np.testing.assert_array_equal(b_ref, b_f)

    def test_memory_estimator_int8_bytes(self):
        from deeplearning4j_tpu.nn.conf.memory import memory_report_mln

        net, _ = self._trained_net()
        rep = memory_report_mln(net.conf)
        f32 = rep.total_memory_bytes(32, training=False)
        i8 = rep.total_memory_bytes(32, training=False, int8_weights=True)
        assert i8 < f32
        # training bytes never change — int8 is serving-only
        assert rep.total_memory_bytes(32, training=True) == \
            rep.total_memory_bytes(32, training=True)
        w_elems = 32 * 64 + 64 * 10
        assert f32 - i8 == pytest.approx(3 * w_elems - 4 * (64 + 10),
                                         abs=8)

    def test_generic_engine_rejects_int8(self):
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        class Opaque:
            def output(self, x, mask=None):
                return np.asarray(x)

        with pytest.raises(TypeError):
            InferenceEngine(Opaque(), int8_serving=True)

    def test_reload_to_layerless_model_fails_typed(self):
        """The int8 guard must also cover models arriving via hot
        reload, not just __init__ — a layer-less checkpoint must fail
        typed, not AttributeError mid-swap."""
        from deeplearning4j_tpu.serving.engine import InferenceEngine

        net, _ = self._trained_net()
        eng = InferenceEngine(net, int8_serving=True)

        class Opaque:
            def output(self, x, mask=None):
                return np.asarray(x)

        with pytest.raises(TypeError, match="generic output path"):
            eng._quantize_params(Opaque())

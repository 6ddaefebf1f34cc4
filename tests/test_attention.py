"""Attention + ring attention tests (new capability; no reference analog —
SURVEY.md §5 long-context mandate). Ring attention is validated against
dense attention on the 8-device CPU mesh."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers import RnnOutputLayer
from deeplearning4j_tpu.nn.conf.layers.attention import (
    LayerNormalization,
    PositionalEmbeddingLayer,
    SelfAttentionLayer,
    TransformerBlock,
    dense_attention,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import TrainingMesh
from deeplearning4j_tpu.parallel.ring_attention import make_ring_attention
from deeplearning4j_tpu.updaters import Adam


class TestDenseAttention:
    def test_causal_masking(self):
        rng = jax.random.PRNGKey(0)
        q = jax.random.normal(rng, (2, 2, 6, 4))
        out_full = dense_attention(q, q, q, causal=True)
        # causal: output at position t must not change if future positions change
        q2 = q.at[:, :, 4:, :].set(999.0)
        out_pref = dense_attention(q2, q2, q2, causal=True)
        np.testing.assert_allclose(
            np.asarray(out_full[:, :, :4]), np.asarray(out_pref[:, :, :4]),
            rtol=1e-5, atol=1e-6,
        )

    def test_key_padding_mask(self):
        rng = jax.random.PRNGKey(1)
        x = jax.random.normal(rng, (1, 1, 4, 4))
        mask = jnp.asarray([[1.0, 1.0, 0.0, 0.0]])
        out = dense_attention(x, x, x, causal=False, mask=mask)
        # masked keys contribute nothing: recompute with only first 2 positions
        out2 = dense_attention(x[:, :, :, :], x[:, :, :2, :], x[:, :, :2, :],
                               causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                                   rtol=1e-5, atol=1e-6)


class TestBlockedAttention:
    """Long-sequence XLA fallback (VERDICT r3 item 4): the scan-blocked
    formulation must equal the materialized dense computation exactly —
    values AND gradients — for causal and key-masked variants."""

    def _qkv(self, T=1024, hd=8):
        rng = np.random.default_rng(3)
        mk = lambda: jnp.asarray(rng.standard_normal((1, 2, T, hd)),
                                 jnp.float32)
        return mk(), mk(), mk()

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_materialized_dense(self, causal):
        from deeplearning4j_tpu.nn.conf.layers.attention import (
            _blocked_attention,
        )

        q, k, v = self._qkv()
        scale = 1.0 / math.sqrt(q.shape[-1])

        def dense(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            if causal:
                tri = jnp.tril(jnp.ones((q.shape[2],) * 2, bool))
                s = jnp.where(tri, s, -1e30)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

        def blocked(q, k, v):
            return _blocked_attention(q, k, v, causal=causal, mask=None,
                                      scale=scale, block_q=256)

        np.testing.assert_allclose(np.asarray(blocked(q, k, v)),
                                   np.asarray(dense(q, k, v)),
                                   rtol=2e-5, atol=2e-5)
        loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) ** 2)
        gb = jax.grad(loss(blocked), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gb, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name} diverged")

    def test_key_mask_and_routing(self):
        from deeplearning4j_tpu.nn.conf.layers import attention as att

        q, k, v = self._qkv()
        mask = jnp.asarray(
            (np.arange(1024) < 700).astype(np.float32))[None, :]
        got = att._blocked_attention(q, k, v, causal=False, mask=mask,
                                     scale=q.shape[-1] ** -0.5, block_q=512)
        want = att.dense_attention(q[:, :, :, :], k[:, :, :700, :],
                                   v[:, :, :700, :], causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # dense_attention routes T>=1024 through the blocked path (no
        # (T,T) materialization); same numbers either way
        via_router = att.dense_attention(q, k, v, causal=False, mask=mask)
        np.testing.assert_allclose(np.asarray(via_router), np.asarray(got),
                                   rtol=1e-6, atol=1e-6)


class TestSelfAttentionLayer:
    def _net(self, causal=False, T=8, d=12):
        conf = (
            NeuralNetConfiguration.builder().seed(3).updater(Adam(0.01))
            .list()
            .layer(PositionalEmbeddingLayer(max_length=T))
            .layer(SelfAttentionLayer(n_heads=3, causal=causal))
            .layer(RnnOutputLayer(n_out=5, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.recurrent(d))
            .build()
        )
        return MultiLayerNetwork(conf).init()

    def test_shapes_and_training(self):
        net = self._net()
        rng = np.random.default_rng(0)
        x = rng.standard_normal((4, 8, 12)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, (4, 8))]
        net.fit(DataSet(x, y), epochs=3)
        out = net.output(x)
        assert out.shape == (4, 8, 5)
        assert np.isfinite(net.score(DataSet(x, y)))

    def test_mask_zeroes_padded_positions(self):
        net = self._net()
        rng = np.random.default_rng(0)
        x = rng.standard_normal((2, 8, 12)).astype(np.float32)
        fm = np.ones((2, 8), np.float32)
        fm[:, 6:] = 0.0
        # attention layer output at valid positions must ignore padded keys
        out_m = net.output(x, mask=fm)
        x2 = x.copy()
        x2[:, 6:, :] = 123.0  # junk in padded region
        out_m2 = net.output(x2, mask=fm)
        np.testing.assert_allclose(out_m[:, :6], out_m2[:, :6], rtol=1e-4, atol=1e-5)


class TestTransformerBlock:
    def test_learns_copy_task(self):
        """Tiny LM-style task: predict the token at the same position
        (identity over a causal block → learnable)."""
        V, T, d = 7, 6, 16
        conf = (
            NeuralNetConfiguration.builder().seed(5).updater(Adam(0.01))
            .list()
            .layer(PositionalEmbeddingLayer(max_length=T))
            .layer(TransformerBlock(n_heads=4, causal=True))
            .layer(TransformerBlock(n_heads=4, causal=True))
            .layer(RnnOutputLayer(n_out=V, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.recurrent(d))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, V, (64, T))
        # input: one-hot in first V dims of d
        x = np.zeros((64, T, d), np.float32)
        x[np.arange(64)[:, None], np.arange(T)[None, :], ids] = 1.0
        y = np.eye(V, dtype=np.float32)[ids]
        s0 = net.score(DataSet(x, y))
        net.fit(DataSet(x, y), epochs=30, batch_size=32)
        s1 = net.score(DataSet(x, y))
        assert s1 < s0 * 0.5, f"transformer should learn copy task: {s0} -> {s1}"

    def test_serde(self):
        from deeplearning4j_tpu.nn.conf.builders import MultiLayerConfiguration

        conf = (
            NeuralNetConfiguration.builder().seed(1)
            .list()
            .layer(TransformerBlock(n_heads=2, causal=True, mlp_ratio=2))
            .layer(RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.recurrent(8))
            .build()
        )
        conf2 = MultiLayerConfiguration.from_json(conf.to_json())
        blk = conf2.layers[0]
        assert isinstance(blk, TransformerBlock)
        assert blk.n_heads == 2 and blk.causal and blk.mlp_ratio == 2


class TestRingAttention:
    """Ring == dense, on the 8-device CPU mesh (seq axis)."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("seq_devices", [2, 4, 8])
    def test_matches_dense(self, causal, seq_devices):
        mesh = TrainingMesh(data=1, seq=seq_devices,
                            devices=jax.devices()[:seq_devices])
        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        b, h, T, hd = 2, 3, 16, 8
        q = jax.random.normal(kq, (b, h, T, hd))
        k = jax.random.normal(kk, (b, h, T, hd))
        v = jax.random.normal(kv, (b, h, T, hd))
        ring = make_ring_attention(mesh)
        out_ring = ring(q, k, v, causal=causal)
        out_dense = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                                   rtol=2e-4, atol=2e-5)

    def test_matches_dense_with_mask(self):
        mesh = TrainingMesh(data=1, seq=4, devices=jax.devices()[:4])
        rng = jax.random.PRNGKey(7)
        b, h, T, hd = 2, 2, 16, 4
        q = jax.random.normal(rng, (b, h, T, hd))
        mask = (jax.random.uniform(jax.random.PRNGKey(8), (b, T)) > 0.3).astype(
            jnp.float32
        )
        mask = mask.at[:, 0].set(1.0)  # every example keeps >= 1 key
        ring = make_ring_attention(mesh)
        out_ring = ring(q, q, q, causal=False, mask=mask)
        out_dense = dense_attention(q, q, q, causal=False, mask=mask)
        np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_long_sequence_grad_flows(self):
        """Gradients flow through the ring (autodiff over ppermute)."""
        mesh = TrainingMesh(data=1, seq=4, devices=jax.devices()[:4])
        ring = make_ring_attention(mesh)
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 4))

        def loss(q):
            return jnp.sum(ring(q, q, q, causal=True) ** 2)

        g = jax.grad(loss)(q)
        assert np.all(np.isfinite(np.asarray(g)))
        # compare to dense gradient
        def loss_d(q):
            return jnp.sum(dense_attention(q, q, q, causal=True) ** 2)

        gd = jax.grad(loss_d)(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gd), rtol=1e-3,
                                   atol=1e-4)


class TestAdvisorRegressions:
    """Round-1 advisor findings (ADVICE.md) pinned by tests."""

    def test_attention_dropout_applies_to_probabilities(self):
        """Dropout must act on the softmax probability matrix, not the
        weighted sum: with constant values v=c every undropped prob row
        still mixes to a multiple of c, so output stays in span{c} — the
        old (wrong) post-sum dropout produced exact zero entries."""
        rng = jax.random.PRNGKey(3)
        b, h, T, d = 2, 2, 6, 4
        q = jax.random.normal(jax.random.PRNGKey(1), (b, h, T, d))
        k = jax.random.normal(jax.random.PRNGKey(2), (b, h, T, d))
        c = jnp.arange(1.0, d + 1)  # constant value vector per key
        v = jnp.broadcast_to(c, (b, h, T, d))
        out = dense_attention(q, k, v, causal=False,
                              dropout_rate=0.5, dropout_rng=rng)
        # every output row must be a (possibly zero) scalar multiple of c
        ratio = out / c
        spread = jnp.abs(ratio - ratio.mean(-1, keepdims=True)).max()
        assert float(spread) < 1e-5
        # and dropout actually does something (different from no-dropout)
        base = dense_attention(q, k, v, causal=False)
        assert not np.allclose(np.asarray(out), np.asarray(base))

    def test_sinusoidal_positional_embedding_odd_dim(self):
        layer = PositionalEmbeddingLayer(mode="sinusoidal")
        it = InputType.recurrent(5, 3)  # odd feature dim
        layer.initialize(it)
        p = layer.init_params(jax.random.PRNGKey(0), it)
        x = jnp.zeros((2, 3, 5))
        y, _ = layer.apply(p, x)
        assert y.shape == (2, 3, 5)
        assert np.all(np.isfinite(np.asarray(y)))

    def test_generate_windows_context_past_max_length(self):
        from deeplearning4j_tpu.models.transformer_lm import TransformerLM

        lm = TransformerLM(vocab_size=17, d_model=8, n_heads=2, n_layers=1,
                           max_length=8).init()
        prompt = np.arange(6, dtype=np.int32)
        out = lm.generate(prompt, max_new=8)  # grows to 14 > max_length=8
        assert out.shape == (1, 14)
        assert np.all(out < 17)

    @pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
    def test_fused_qkv_bitwise_identical(self, compute_dtype):
        """fused_qkv computes Q,K,V as one (d, 3d) dot: every output
        column block sees only its own weight block, so logits must be
        BITWISE identical to the three-dot layout (param layout/
        checkpoints/TP pspecs unchanged)."""
        from deeplearning4j_tpu.models.transformer_lm import TransformerLM

        ids = np.random.default_rng(0).integers(0, 64, (2, 16)).astype(
            np.int32)
        outs = []
        for fq in (False, True):
            m = TransformerLM(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=2, max_length=32,
                              compute_dtype=compute_dtype,
                              fused_qkv=fq).init()
            outs.append(np.asarray(m.logits(ids)))
        np.testing.assert_array_equal(outs[0], outs[1])


class TestFlashAttentionGate:
    def test_gate_logic(self, monkeypatch):
        """Pallas flash attention only engages on TPU with block-aligned
        unmasked shapes (parity itself is verified on real TPU hardware
        by the round's verify drive: fwd/grad err ~1e-6)."""
        from deeplearning4j_tpu.nn.conf.layers.attention import (
            _flash_attention_route,
        )

        q = jnp.zeros((2, 4, 512, 128))
        # CPU backend in tests → never eligible
        assert _flash_attention_route(q, q, True, None, 0.0) is None
        # kill switch + disqualifiers are independent of backend
        monkeypatch.setenv("DL4J_TPU_FLASH_ATTENTION", "0")
        assert _flash_attention_route(q, q, True, None, 0.0) is None
        monkeypatch.delenv("DL4J_TPU_FLASH_ATTENTION")
        assert _flash_attention_route(q, q, True, jnp.ones((2, 512)),
                                      0.0) is None
        assert _flash_attention_route(q, q, True, None, 0.1) is None
        q_bad = jnp.zeros((2, 4, 100, 128))
        assert _flash_attention_route(q_bad, q_bad, True, None, 0.0) is None
        # cross-attention with mismatched kv length stays dense
        k_short = jnp.zeros((2, 4, 256, 128))
        assert _flash_attention_route(q, k_short, True, None, 0.0) is None

    def test_kernel_not_offered_where_gspmd_would_partition_it(
            self, monkeypatch):
        """A Mosaic kernel cannot be partitioned automatically (the
        TPU lowering raises). With the backend steered to "tpu": under a
        visible mesh with automatic axes the route keeps to XLA; inside
        a shard_map, where every axis is manual, it offers the kernel."""
        import deeplearning4j_tpu.nn.conf.layers.attention as A
        from jax.sharding import Mesh, PartitionSpec as P

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(A, "_flash_attention_impl",
                            lambda *a, **k: "kernel")
        q = jnp.zeros((2, 4, 512, 64))
        assert A._flash_attention_route(q, q, True, None, 0.0) == "kernel"
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))
        seen = []

        def local(x):
            seen.append(A._flash_attention_route(x, x, True, None, 0.0))
            return x

        with jax.set_mesh(mesh):
            assert A._flash_attention_route(q, q, True, None, 0.0) is None
            jax.shard_map(local, mesh=mesh, in_specs=P("data", "model"),
                          out_specs=P("data", "model"))(q)
        assert seen == ["kernel"]

    def test_compile_probe_failure_falls_back_and_caches(self, monkeypatch):
        """A kernel the installed Mosaic refuses (e.g. a bf16 tpu.matmul
        rejected with "Bad lhs type") must disable the flash path for
        that instantiation instead of failing the model step.
        The probe result is cached per (dtype, seq, head_dim, causal)."""
        import deeplearning4j_tpu.nn.conf.layers.attention as A

        monkeypatch.setattr(A, "_FLASH_PROBE_CACHE", {})
        compiles = {"n": 0}

        class _Boom:
            def lower(self, *a, **k):
                return self

            def compile(self):
                compiles["n"] += 1
                raise RuntimeError("Mosaic failed to compile TPU kernel: "
                                   "Bad lhs type")

        monkeypatch.setattr(jax, "jit", lambda *a, **k: _Boom())
        assert A._flash_attention_impl(jnp.bfloat16, 512, 64, True) is None
        assert A._FLASH_PROBE_CACHE == {
            ("bfloat16", 512, 64, True, False): None}
        # both the in-tree and the jax-bundled kernel were attempted
        assert compiles["n"] == 2
        # second call hits the cache: no further compile attempts
        assert A._flash_attention_impl(jnp.bfloat16, 512, 64, True) is None
        assert compiles["n"] == 2
        # a different instantiation re-probes
        assert A._flash_attention_impl(jnp.bfloat16, 1024, 128, True) is None
        assert compiles["n"] == 4

    def test_compile_probe_success_prefers_own_kernel(self, monkeypatch):
        import deeplearning4j_tpu.nn.conf.layers.attention as A

        monkeypatch.setattr(A, "_FLASH_PROBE_CACHE", {})
        monkeypatch.setattr(A, "_probe_compiles",
                            lambda *a, **k: True)
        impl = A._flash_attention_impl(jnp.float32, 128, 128, False)
        assert callable(impl)
        assert A._FLASH_PROBE_CACHE[
            ("float32", 128, 128, False, False)] is impl
        # the chosen impl is the in-tree kernel (probed first)
        from deeplearning4j_tpu.nn.ops.flash_attention import flash_attention
        assert impl.args[0] is flash_attention

    def test_segment_probe_only_tries_in_tree_kernel(self, monkeypatch):
        """has_seg probes cache under their own key, and the jax-bundled
        kernel (different segment API) is never a candidate."""
        import deeplearning4j_tpu.nn.conf.layers.attention as A

        from deeplearning4j_tpu.nn.ops.registry import (
            default_kernel_registry,
        )

        monkeypatch.setattr(A, "_FLASH_PROBE_CACHE", {})
        default_kernel_registry().reset("flash_attention")
        attempted = []

        def probe(fn, *a, **k):
            attempted.append(fn)
            raise RuntimeError("probe reject")  # registry contract:
            # a failing probe RAISES (deterministic → one attempt)

        monkeypatch.setattr(A, "_probe_compiles", probe)
        try:
            assert A._flash_attention_impl(jnp.float32, 256, 64, True,
                                           has_seg=True) is None
            assert ("float32", 256, 64, True, True) in A._FLASH_PROBE_CACHE
            assert len(attempted) == 1  # in-tree only; bundled skipped
        finally:
            default_kernel_registry().reset("flash_attention")

    def test_seq_beyond_own_kernel_cap_tries_bundled(self, monkeypatch):
        """T past the in-tree kernel's MAX_SEQ_LEN must skip it (no
        probe) and try the jax-bundled kernel."""
        import deeplearning4j_tpu.nn.conf.layers.attention as A
        from deeplearning4j_tpu.nn.ops.flash_attention import MAX_SEQ_LEN

        monkeypatch.setattr(A, "_FLASH_PROBE_CACHE", {})
        monkeypatch.setattr(A, "_probe_compiles",
                            lambda *a, **k: True)
        impl = A._flash_attention_impl(jnp.bfloat16, MAX_SEQ_LEN * 2, 128,
                                       True)
        assert callable(impl)
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash,
        )
        assert impl.args[0] is jax_flash

    def test_value_check_rejects_wrong_kernel(self):
        """The probe must EXECUTE the kernel and compare against the
        dense reference — a kernel that compiles but miscomputes (a
        lagging Mosaic can miscompile, not just reject) is refused."""
        import deeplearning4j_tpu.nn.conf.layers.attention as A

        with pytest.raises(RuntimeError, match="value check failed"):
            A._probe_compiles(lambda q, k, v: jnp.zeros_like(q), 128, 64,
                              jnp.float32, False)

    def test_value_check_accepts_correct_kernel(self):
        """A numerically correct implementation passes the value check
        (here: the in-tree Pallas kernel in interpreter mode)."""
        import deeplearning4j_tpu.nn.conf.layers.attention as A
        from deeplearning4j_tpu.nn.ops.flash_attention import flash_attention

        assert A._probe_compiles(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            sm_scale=64 ** -0.5,
                                            interpret=True),
            128, 64, jnp.float32, True)

"""Attention / transformer layers.

The reference predates transformers — it has NO attention layer at all
(SURVEY.md §2.5 parallelism checklist: "SP/CP, ring attention ... ABSENT.
The codebase predates transformers"). These are *new capabilities of the
target stack* (SURVEY.md §5 long-context mandate), designed TPU-first:

- dense attention computes as one fused (b, h, T, T) einsum chain on the
  MXU, causal masking via a static triangular mask (no dynamic shapes);
- the same layer transparently switches to ring attention
  (parallel/ring_attention.py) when the time axis is sharded over the
  mesh's "seq" axis — blockwise online-softmax with K/V rotating around
  the ring via ppermute;
- TP sharding rules for QKV/MLP projections live in
  parallel/tensor_parallel.py (column/row parallel, the Megatron layout).

Layers operate on recurrent-format activations (b, T, d) and compose with
the existing catalog (EmbeddingSequenceLayer, RnnOutputLayer, ...).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import serde
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer, Layer


@serde.register
class LayerNormalization(Layer):
    """Per-feature layer norm (new capability; BatchNormalization is the
    reference's only norm — LN is required by transformer blocks)."""

    def __init__(self, eps: float = 1e-5, **kwargs):
        super().__init__(**kwargs)
        self.eps = float(eps)
        self.n_feat: Optional[int] = None

    def initialize(self, input_type):
        self.n_feat = input_type.size if input_type.kind in ("feedforward", "recurrent") \
            else input_type.channels

    def init_params(self, rng, input_type, dtype=jnp.float32):
        assert self.n_feat
        return {
            "gamma": jnp.ones((self.n_feat,), dtype),
            "beta": jnp.zeros((self.n_feat,), dtype),
        }

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        return y * params["gamma"] + params["beta"], state or {}


def _layer_norm(x, gamma, beta, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


_FLASH_PROBE_CACHE: dict = {}


def _probe_compiles(fn, seq_len: int, head_dim: int, dtype,
                    causal: bool, segment_ids=None) -> bool:
    """Probe a minimal (1,1,T,hd) instance of ``fn(q, k, v)``: compile
    its forward AND value-and-grad programs, EXECUTE both on three
    independently seeded random tensors (q=k=v would hide operand-order /
    transpose miscompiles behind the symmetry of Q·Kᵀ), and compare
    output and all three gradients against a dense fp32 reference. A
    compiler can MIScompile (not just reject) a kernel, and forward-only
    checking would let training run on silently wrong gradients.

    dense_attention is typically called DURING tracing of a model step,
    where an ordinary jit call would be traced into the caller's graph
    (silently "succeeding" and still embedding the pallas op). AOT
    lower+compile sidesteps the trace context, and the value check calls
    the compiled executables with concrete arrays — safe under an
    ambient trace."""
    shape = (1, 1, seq_len, head_dim)
    x3 = [jax.ShapeDtypeStruct(shape, dtype)] * 3

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

    kernel_exe = jax.jit(fn).lower(*x3).compile()
    kernel_vg = jax.jit(
        jax.value_and_grad(loss(fn), argnums=(0, 1, 2))).lower(*x3).compile()

    def dense_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * (head_dim ** -0.5)
        if causal:
            tri = jnp.tril(jnp.ones((seq_len, seq_len), bool))
            s = jnp.where(tri, s, -1e30)
        if segment_ids is not None:
            same = segment_ids[:, None, :, None] == \
                segment_ids[:, None, None, :]
            s = jnp.where(same, s, -1e30)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))

    ref_exe = jax.jit(dense_ref).lower(*x3).compile()
    ref_vg = jax.jit(jax.value_and_grad(
        loss(dense_ref), argnums=(0, 1, 2))).lower(*x3).compile()

    rng = np.random.default_rng(0)
    # numpy (never jnp): under an ambient trace jnp ops stage into the
    # caller's graph and the AOT executables would be handed tracers
    qkv = [np.asarray(rng.standard_normal(shape),
                      np.float32).astype(jnp.dtype(dtype))
           for _ in range(3)]
    tol = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 2e-4

    def check(name, got, want, scale=1.0):
        err = np.max(np.abs(np.asarray(got, dtype=np.float32)
                            - np.asarray(want, dtype=np.float32)))
        if not np.isfinite(err) or err > tol * scale:
            raise RuntimeError(
                f"flash kernel value check failed ({name}): "
                f"max err {err:.3e} > {tol * scale}")

    check("fwd", kernel_exe(*qkv), ref_exe(*qkv))
    _, g_k = kernel_vg(*qkv)
    _, g_r = ref_vg(*qkv)
    for name, a, b in zip(("dq", "dk", "dv"), g_k, g_r):
        # gradients accumulate over T terms; scale tolerance accordingly
        check(name, a, b, scale=8.0)
    return True


def _flash_attention_impl(dtype, seq_len: int, head_dim: int, causal: bool,
                          has_seg: bool = False):
    """Pick a flash implementation for this instantiation, compile-probing
    once per (dtype, seq_len, head_dim, causal): the in-tree Pallas
    kernel (nn/ops/flash_attention.py — written against the matmul forms
    this toolchain's Mosaic accepts) first, the jax-bundled kernel
    second, None (→ dense XLA attention) when neither compiles. What
    Mosaic accepts varies with the installed compiler, and the lowering
    varies with sequence length (block/grid choice), head dim (padding)
    and causality, so the probe is keyed on all four."""
    import logging

    key = (jnp.dtype(dtype).name, int(seq_len), int(head_dim), bool(causal),
           bool(has_seg))
    if key in _FLASH_PROBE_CACHE:
        return _FLASH_PROBE_CACHE[key]

    # probe segment pattern: two packed sequences with an off-block-
    # boundary split so the probe exercises intra-block masking
    probe_seg = None
    if has_seg:
        cut = (seq_len // 2) - (seq_len // 8)
        probe_seg = np.concatenate(  # numpy: see _probe_compiles note
            [np.zeros(cut, np.int32),
             np.ones(seq_len - cut, np.int32)])[None, :]

    def candidates():
        from deeplearning4j_tpu.nn.ops.flash_attention import (
            MAX_SEQ_LEN,
            flash_attention as own_flash,
        )

        if seq_len <= MAX_SEQ_LEN:
            yield "in-tree", own_flash
        if has_seg:
            return  # the bundled kernel's segment API (SegmentIds
            # namedtuple) is not probed here; in-tree or dense
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash,
        )

        yield "jax-bundled", jax_flash

    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    reg = default_kernel_registry()
    impl = None
    sc = head_dim ** -0.5
    for cand_name, kernel in candidates():
        if has_seg:
            probe_fn = (lambda kernel=kernel: _probe_compiles(
                lambda q, k, v: kernel(q, k, v, causal=causal, sm_scale=sc,
                                       segment_ids=probe_seg),
                seq_len, head_dim, dtype, causal, segment_ids=probe_seg))
        else:
            probe_fn = (lambda kernel=kernel: _probe_compiles(
                lambda q, k, v: kernel(q, k, v, causal=causal,
                                       sm_scale=sc),
                seq_len, head_dim, dtype, causal))
        if reg.probe("flash_attention", key + (cand_name,), probe_fn):
            impl = functools.partial(_call_flash, kernel, causal)
            break
    if impl is None:
        logging.getLogger(__name__).warning(
            "Pallas flash attention unavailable for %s — falling back to "
            "dense XLA attention", key)
    _FLASH_PROBE_CACHE[key] = impl
    return impl


def _call_flash(kernel, causal, q, k, v, scale, segment_ids=None):
    if segment_ids is None:
        return kernel(q, k, v, causal=causal, sm_scale=scale)
    return kernel(q, k, v, causal=causal, sm_scale=scale,
                  segment_ids=segment_ids)


def _flash_attention_route(q, k, causal, mask, dropout_rate,
                           segment_ids=None):
    """Route to a Pallas TPU flash-attention kernel when one applies:
    TPU backend, no padding mask / attention dropout, equal q/kv length,
    block-friendly shapes (T multiple of 128; tiny toy shapes stay on
    the einsum path), no mesh axis left for GSPMD to partition over, and
    a kernel that compile-probes OK at this instantiation (see
    ``_flash_attention_impl``). Returns the chosen impl or None. Kill
    switch: DL4J_TPU_FLASH_ATTENTION=0."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    if default_kernel_registry().mode("flash_attention") == "off":
        return None
    if mask is not None or dropout_rate > 0.0:
        return None
    if jax.default_backend() != "tpu":
        return None
    # a Mosaic kernel cannot be partitioned automatically (lowering
    # raises "wrap the call in a shard_map"): under a mesh it is only
    # offered where every axis larger than one is manual. Multi-device
    # callers make their mesh visible (jax.set_mesh) or run attention
    # inside a shard_map (parallel/transformer.py).
    ambient = jax.sharding.get_abstract_mesh()
    if any(size > 1 and name not in ambient.manual_axes
           for name, size in ambient.shape.items()):
        return None
    T = q.shape[2]
    if k.shape[2] != T or T < 128 or T % 128:
        return None
    return _flash_attention_impl(q.dtype, T, q.shape[-1], causal,
                                 has_seg=segment_ids is not None)


BLOCKED_ATTENTION_MIN_T = 1024


def _blocked_attention(q, k, v, *, causal: bool, mask, scale: float,
                       block_q: int, segment_ids=None):
    """Dense attention evaluated one query block at a time under
    ``lax.scan`` with a rematerialized body: peak live scores are
    (b, h, block_q, T) instead of (b, h, T, T), and the backward pass
    recomputes each block's scores rather than storing them (the
    flash-attention memory shape without Pallas — the XLA fallback for
    T >= BLOCKED_ATTENTION_MIN_T when the kernel can't compile on the
    serving toolchain; VERDICT r3 item 4)."""
    b, h, T, hd = q.shape
    nb = T // block_q
    qb = q.reshape(b, h, nb, block_q, hd).transpose(2, 0, 1, 3, 4)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def body(_, blk):
        i, qblk = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", qblk, k) * scale
        if causal:
            qpos = i * block_q + jnp.arange(block_q)
            s = jnp.where(kpos[None, :] <= qpos[:, None], s, -1e30)
        if mask is not None:
            s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
        if segment_ids is not None:
            seg_q = jax.lax.dynamic_slice_in_dim(
                segment_ids, i * block_q, block_q, 1)  # (b, block_q)
            s = jnp.where(
                seg_q[:, None, :, None] == segment_ids[:, None, None, :],
                s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return None, jnp.einsum("bhqk,bhkd->bhqd", p, v)

    _, out = jax.lax.scan(body, None, (jnp.arange(nb), qb))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, T, hd)


def dense_attention(q, k, v, *, causal: bool, mask=None,
                    dropout_rate: float = 0.0, dropout_rng=None,
                    segment_ids=None):
    """Reference dense softmax attention. q,k,v: (b, h, T, hd).

    ``dropout_rate`` drops entries of the softmax probability matrix
    (standard attention dropout), not the weighted sum.

    ``segment_ids``: optional (b, T) int array for PACKED sequences —
    tokens attend only within their own segment (composes with
    ``causal``). Runs on the Pallas flash path when the kernel probes OK
    at this instantiation, else the blocked/einsum fallbacks.

    On TPU with long block-aligned sequences the computation routes to
    the Pallas flash-attention kernel (O(T) memory, no (T, T) scores
    materialization) — same math, the SURVEY §7 "Pallas for the hot ops"
    path. When the kernel is unavailable (toolchain probe) and the
    sequence is long, a scan-blocked formulation bounds the live score
    memory instead.
    """
    T = q.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    if segment_ids is not None:
        segment_ids = jnp.asarray(segment_ids, jnp.int32)
    flash_impl = _flash_attention_route(q, k, causal, mask, dropout_rate,
                                        segment_ids)
    if flash_impl is not None:
        return flash_impl(q, k, v, scale, segment_ids=segment_ids)
    if (T >= BLOCKED_ATTENTION_MIN_T and dropout_rate == 0.0
            and k.shape[2] == T):
        for bq in (512, 256, 128):
            if T % bq == 0:
                return _blocked_attention(q, k, v, causal=causal, mask=mask,
                                          scale=scale, block_q=bq,
                                          segment_ids=segment_ids)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        tri = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(tri, scores, -1e30)
    if mask is not None:  # (b, T) key padding mask
        scores = jnp.where(mask[:, None, None, :] > 0, scores, -1e30)
    if segment_ids is not None:  # packed sequences: same-segment only
        same = segment_ids[:, None, :, None] == \
            segment_ids[:, None, None, :]
        scores = jnp.where(same, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = 1.0 - dropout_rate
        p = jnp.where(jax.random.bernoulli(dropout_rng, keep, p.shape),
                      p / keep, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@serde.register
class SelfAttentionLayer(FeedForwardLayer):
    """Multi-head self-attention over (b, T, d).

    n_out = model width (defaults to n_in); ``n_heads`` must divide it.
    ``causal`` applies an autoregressive mask. When the incoming activation
    is sharded over the mesh "seq" axis (set by the distributed runner),
    the runner substitutes the ring-attention kernel — the math is
    identical (see tests).
    """

    is_recurrent = True  # preserves (b, T) masks

    def __init__(self, n_heads: int = 4, causal: bool = False,
                 attention_dropout: float = 0.0, **kwargs):
        kwargs.setdefault("activation", "identity")
        super().__init__(**kwargs)
        self.n_heads = int(n_heads)
        self.causal = bool(causal)
        self.attention_dropout = float(attention_dropout)

    def initialize(self, input_type):
        super().initialize(input_type)
        if self.n_out is None:
            self.n_out = self.n_in
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out {self.n_out} not divisible by n_heads {self.n_heads}")

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, rng, input_type, dtype=jnp.float32):
        assert self.n_in and self.n_out
        kq, kk, kv, ko = jax.random.split(rng, 4)
        d, m = self.n_in, self.n_out
        return {
            "Wq": self._draw_weight(kq, (d, m), d, m, dtype),
            "Wk": self._draw_weight(kk, (d, m), d, m, dtype),
            "Wv": self._draw_weight(kv, (d, m), d, m, dtype),
            "Wo": self._draw_weight(ko, (m, m), m, m, dtype),
            "bo": jnp.zeros((m,), dtype),
        }

    def _heads(self, x, W):
        b, T, _ = x.shape
        y = x @ W  # (b, T, m)
        return y.reshape(b, T, self.n_heads, -1).transpose(0, 2, 1, 3)

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        b, T, _ = x.shape
        q = self._heads(x, params["Wq"])
        k = self._heads(x, params["Wk"])
        v = self._heads(x, params["Wv"])
        rate = self.attention_dropout if (train and rng is not None) else 0.0
        o = dense_attention(q, k, v, causal=self.causal, mask=mask,
                            dropout_rate=rate, dropout_rng=rng)
        o = o.transpose(0, 2, 1, 3).reshape(b, T, self.n_out)
        y = o @ params["Wo"] + params["bo"]
        if mask is not None:
            y = y * mask[..., None]
        return y, state or {}


@serde.register
class TransformerBlock(FeedForwardLayer):
    """Pre-LN transformer block: x + MHA(LN(x)), then x + MLP(LN(x)).

    One layer config = one block; stack them in a list or use the
    TransformerLM zoo model (which also stacks them along a pipeline axis
    for PP). ``mlp_ratio`` sets the hidden width of the FFN.
    """

    is_recurrent = True

    def __init__(self, n_heads: int = 4, causal: bool = True,
                 mlp_ratio: int = 4, **kwargs):
        kwargs.setdefault("activation", "gelu")
        super().__init__(**kwargs)
        self.n_heads = int(n_heads)
        self.causal = bool(causal)
        self.mlp_ratio = int(mlp_ratio)

    def initialize(self, input_type):
        super().initialize(input_type)
        if self.n_out is None:
            self.n_out = self.n_in
        if self.n_out != self.n_in:
            raise ValueError("TransformerBlock requires nIn == nOut (residual)")
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out {self.n_out} not divisible by n_heads {self.n_heads}")

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, rng, input_type, dtype=jnp.float32):
        assert self.n_in and self.n_out
        d = self.n_out
        h = d * self.mlp_ratio
        kq, kk, kv, ko, k1, k2 = jax.random.split(rng, 6)
        return {
            "ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "Wq": self._draw_weight(kq, (d, d), d, d, dtype),
            "Wk": self._draw_weight(kk, (d, d), d, d, dtype),
            "Wv": self._draw_weight(kv, (d, d), d, d, dtype),
            "Wo": self._draw_weight(ko, (d, d), d, d, dtype),
            "bo": jnp.zeros((d,), dtype),
            "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
            "W1": self._draw_weight(k1, (d, h), d, h, dtype),
            "b1": jnp.zeros((h,), dtype),
            "W2": self._draw_weight(k2, (h, d), h, d, dtype),
            "b2": jnp.zeros((d,), dtype),
        }

    def attention(self, params, x, mask=None, attn_fn=None):
        """MHA sublayer on pre-normed input; ``attn_fn`` overrides the
        attention kernel (ring attention under seq sharding)."""
        b, T, d = x.shape
        hn = self.n_heads

        def heads(W):
            return (x @ W).reshape(b, T, hn, -1).transpose(0, 2, 1, 3)

        q, k, v = heads(params["Wq"]), heads(params["Wk"]), heads(params["Wv"])
        fn = attn_fn if attn_fn is not None else dense_attention
        o = fn(q, k, v, causal=self.causal, mask=mask)
        o = o.transpose(0, 2, 1, 3).reshape(b, T, d)
        return o @ params["Wo"] + params["bo"]

    def mlp(self, params, x):
        h = self.act_fn()(x @ params["W1"] + params["b1"])
        return h @ params["W2"] + params["b2"]

    def block_apply(self, params, x, mask=None, attn_fn=None):
        """Pure block fn, reused by the pipeline-parallel scan."""
        a_in = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        x = x + self.attention(params, a_in, mask=mask, attn_fn=attn_fn)
        m_in = _layer_norm(x, params["ln2_g"], params["ln2_b"])
        return x + self.mlp(params, m_in)

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        y = self.block_apply(params, x, mask=mask)
        if mask is not None:
            y = y * mask[..., None]
        return y, state or {}


@serde.register
class PositionalEmbeddingLayer(Layer):
    """Adds learned (default) or sinusoidal position encodings to (b,T,d).
    ``max_length`` bounds learned tables; sinusoidal is length-agnostic."""

    def __init__(self, max_length: int = 2048, mode: str = "learned", **kwargs):
        super().__init__(**kwargs)
        self.max_length = int(max_length)
        self.mode = mode
        self.n_feat: Optional[int] = None

    def initialize(self, input_type):
        self.n_feat = input_type.size

    def init_params(self, rng, input_type, dtype=jnp.float32):
        if self.mode != "learned":
            return {}
        return {
            "pos": 0.02 * jax.random.normal(rng, (self.max_length, self.n_feat), dtype)
        }

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        T = x.shape[1]
        if self.mode == "learned":
            return x + params["pos"][:T][None], state or {}
        d = x.shape[-1]
        half = (d + 1) // 2  # ceil so odd feature dims work; trimmed below
        pos = jnp.arange(T, dtype=x.dtype)[:, None]
        dim = jnp.arange(half, dtype=x.dtype)[None, :]
        angle = pos / jnp.power(10000.0, 2 * dim / d)
        enc = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)[:, :d]
        return x + enc[None], state or {}

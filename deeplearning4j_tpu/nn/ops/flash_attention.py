"""Flash attention as hand-written Pallas TPU kernels (fwd + bwd).

Why not the jax-bundled kernel: it inherits the caller's
matmul-precision default, and under this package's
``jax_default_matmul_precision="highest"`` a bf16 Mosaic matmul would
ask for a multi-pass algorithm it has no use for (see
``kernel_compat.PRECISION``). This kernel restricts itself to plain 2-D
``dot_general`` per grid cell with ``precision=DEFAULT`` pinned on
every dot. Design (deliberately simpler
than the bundled op; r5 adds segment-id support — packed sequences run
on the flash path; attention *bias* still routes to dense XLA):

- grid ``(b·h, T/B)``; K and V rows for the (batch, head) live whole in
  VMEM (their BlockSpec index map is constant in the q-block dimension,
  so Mosaic DMAs them once per b·h), bounding T at ~4k for bf16 —
  longer sequences belong to ring attention (sequence parallelism)
  across devices anyway.
- online softmax (flash style): running row-max ``m`` and row-sum ``l``
  carried through a ``fori_loop`` over KV blocks in fp32; the causal
  variant loops only to the diagonal block and masks inside it.
- per-row stats are kept lane-broadcast ``(B, 128)`` — the TPU-native
  layout for per-sublane scalars under the (8/16, 128) tile constraint.
- segment ids (packed sequences) enter twice, in the layout each side
  of the score matrix wants: lane-broadcast ``(b·h, T, 128)`` for query
  rows (sublane axis) and natural ``(b·h, 1, T)`` for key columns (lane
  axis); the in-kernel mask is one int compare + where, fused into the
  score tile.
- backward = two kernels (dq over q-blocks; dkv over kv-blocks), each
  recomputing P from the saved log-sum-exp ``L`` (FlashAttention-2
  style; ``D = rowsum(dO·O)`` is a cheap fused XLA reduction outside).

Head dims are zero-padded to a lane multiple (128): padded q/k lanes
add zero to every score and padded v lanes produce zeros that are
sliced off, so the math is unchanged.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128
_TRANS_B = (((1,), (1,)), ((), ()))   # x (m,k) · y (n,k) -> (m,n)
_TRANS_A = (((0,), (0,)), ((), ()))   # x (k,m) · y (k,n) -> (m,n)
_NEG_INF = -1e30
from deeplearning4j_tpu.nn.ops.kernel_compat import PRECISION as _PREC


def _pick_block(T: int) -> int:
    for b in (512, 256, 128):
        if T % b == 0:
            return b
    raise ValueError(f"T={T} must be a multiple of 128")


def _pad_head(x):
    hd = x.shape[-1]
    pad = (-hd) % _LANE
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad)))
    return x, hd


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _seg_where(qseg, kseg, s, B):
    """Mask scores where q and k segments differ. qseg (B,1) int32 (lane
    0 of the lane-broadcast layout); kseg (B,) int32 (natural lane
    layout); broadcast compare → (B,B)."""
    return jnp.where(qseg == kseg.reshape(1, B), s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale: float,
                causal: bool, block: int, T: int, has_seg: bool):
    if has_seg:
        segq_ref, segk_ref, o_ref, lse_ref = rest
    else:
        (o_ref, lse_ref), segq_ref, segk_ref = rest, None, None
    i = pl.program_id(1)
    q = q_ref[0]                                        # (B, hd)
    B = block
    n_kv = jax.lax.select(causal, i + 1, T // B)

    def body(j, carry):
        o, m, l = carry                                 # (B,hd) f32, (B,1) f32
        k = k_ref[0, pl.dslice(j * B, B), :]            # (B, hd)
        v = v_ref[0, pl.dslice(j * B, B), :]
        s = jax.lax.dot_general(q, k, _TRANS_B,
                                preferred_element_type=jnp.float32, precision=_PREC) * scale
        if causal:
            rows = i * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
            cols = j * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if has_seg:
            s = _seg_where(segq_ref[0][:, 0:1],
                           segk_ref[0, 0, pl.dslice(j * B, B)], s, B)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                          # (B, B) f32
        alpha = jnp.exp(m - m_new)                      # (B, 1)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32, precision=_PREC)
        o = o * alpha + pv
        return o, m_new, l

    o0 = jnp.zeros((B, q.shape[-1]), jnp.float32)
    m0 = jnp.full((B, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, n_kv, body, (o0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l_safe).astype(o_ref.dtype)
    lse = m + jnp.log(l_safe)                           # (B, 1)
    lse_ref[0] = jnp.broadcast_to(lse, (B, _LANE))


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, *rest,
               scale: float, causal: bool, block: int, T: int,
               has_seg: bool):
    if has_seg:
        segq_ref, segk_ref, dq_ref = rest
    else:
        (dq_ref,), segq_ref, segk_ref = rest, None, None
    i = pl.program_id(1)
    B = block
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, 0:1]                            # (B, 1)
    dcap = dcap_ref[0][:, 0:1]
    n_kv = jax.lax.select(causal, i + 1, T // B)

    def body(j, dq):
        k = k_ref[0, pl.dslice(j * B, B), :]
        v = v_ref[0, pl.dslice(j * B, B), :]
        s = jax.lax.dot_general(q, k, _TRANS_B,
                                preferred_element_type=jnp.float32, precision=_PREC) * scale
        if causal:
            rows = i * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
            cols = j * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if has_seg:
            s = _seg_where(segq_ref[0][:, 0:1],
                           segk_ref[0, 0, pl.dslice(j * B, B)], s, B)
        p = jnp.exp(s - lse)                            # (B, B)
        dp = jax.lax.dot_general(do, v, _TRANS_B,
                                 preferred_element_type=jnp.float32, precision=_PREC)
        ds = p * (dp - dcap) * scale
        dq = dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC)
        return dq

    dq0 = jnp.zeros((B, q.shape[-1]), jnp.float32)
    dq = jax.lax.fori_loop(0, n_kv, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, *rest,
                scale: float, causal: bool, block: int, T: int,
                has_seg: bool):
    if has_seg:
        segq_ref, segk_ref, dk_ref, dv_ref = rest
    else:
        (dk_ref, dv_ref), segq_ref, segk_ref = rest, None, None
    j = pl.program_id(1)
    B = block
    k = k_ref[0]                                        # (B, hd) this kv block
    v = v_ref[0]
    n_q = T // B
    start = jax.lax.select(causal, j, 0)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * B, B), :]
        do = do_ref[0, pl.dslice(i * B, B), :]
        lse = lse_ref[0, pl.dslice(i * B, B), :][:, 0:1]
        dcap = dcap_ref[0, pl.dslice(i * B, B), :][:, 0:1]
        s = jax.lax.dot_general(q, k, _TRANS_B,
                                preferred_element_type=jnp.float32, precision=_PREC) * scale
        if causal:
            rows = i * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
            cols = j * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if has_seg:
            s = _seg_where(
                segq_ref[0, pl.dslice(i * B, B), :][:, 0:1],
                segk_ref[0, 0], s, B)  # segk blocked on j: (B,)
        p = jnp.exp(s - lse)                            # (B_q, B_k)
        dv = dv + jax.lax.dot_general(p.astype(do.dtype), do, _TRANS_A,
                                      preferred_element_type=jnp.float32, precision=_PREC)
        dp = jax.lax.dot_general(do, v, _TRANS_B,
                                 preferred_element_type=jnp.float32, precision=_PREC)
        ds = p * (dp - dcap) * scale
        dk = dk + jax.lax.dot_general(ds.astype(q.dtype), q, _TRANS_A,
                                      preferred_element_type=jnp.float32, precision=_PREC)
        return dk, dv

    z = jnp.zeros((B, k.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, n_q, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# wrapper with custom VJP
# --------------------------------------------------------------------------
def _seg_layouts(seg):
    """(b, T) int32 → (lane-broadcast q layout (b,T,LANE), natural k
    layout (b,1,T)). Kept at BATCH granularity — the grid's b·h axis
    index-maps back with ``// h`` so the head dimension is never
    materialized (heads share their row's segment ids)."""
    b, T = seg.shape
    seg = seg.astype(jnp.int32)
    return (jnp.broadcast_to(seg[:, :, None], (b, T, _LANE)),
            seg[:, None, :])


def _fwd_impl(q, k, v, seg, causal: bool, scale: float, interpret: bool):
    bh, T, hd = q.shape
    B = _pick_block(T)
    has_seg = seg is not None
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block=B, T=T, has_seg=has_seg)
    row_spec = lambda b, i: (b, i, 0)
    full_spec = lambda b, i: (b, 0, 0)
    in_specs = [
        pl.BlockSpec((1, B, hd), row_spec),
        pl.BlockSpec((1, T, hd), full_spec),
        pl.BlockSpec((1, T, hd), full_spec),
    ]
    args = [q, k, v]
    if has_seg:
        segq, segk = _seg_layouts(seg)
        h = bh // seg.shape[0]  # heads share segments: index-map // h
        in_specs += [pl.BlockSpec((1, B, _LANE),
                                  lambda b, i: (b // h, i, 0)),
                     pl.BlockSpec((1, 1, T),
                                  lambda b, i: (b // h, 0, 0))]
        args += [segq, segk]
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, T // B),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, B, hd), row_spec),
            pl.BlockSpec((1, B, _LANE), row_spec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, T, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, T, _LANE), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return o, lse


def _bwd_impl(q, k, v, seg, o, lse, do, causal: bool, scale: float,
              interpret: bool):
    bh, T, hd = q.shape
    B = _pick_block(T)
    has_seg = seg is not None
    # D_i = rowsum(dO·O): cheap fused XLA reduction, lane-broadcast layout
    dcap = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                keepdims=True), (bh, T, _LANE))
    row_spec = lambda b, i: (b, i, 0)
    full_spec = lambda b, i: (b, 0, 0)
    dq_in_specs = [
        pl.BlockSpec((1, B, hd), row_spec),      # q block
        pl.BlockSpec((1, T, hd), full_spec),     # k full
        pl.BlockSpec((1, T, hd), full_spec),     # v full
        pl.BlockSpec((1, B, hd), row_spec),      # do block
        pl.BlockSpec((1, B, _LANE), row_spec),   # lse block
        pl.BlockSpec((1, B, _LANE), row_spec),   # D block
    ]
    dkv_in_specs = [
        pl.BlockSpec((1, T, hd), full_spec),     # q full
        pl.BlockSpec((1, B, hd), row_spec),      # k block
        pl.BlockSpec((1, B, hd), row_spec),      # v block
        pl.BlockSpec((1, T, hd), full_spec),     # do full
        pl.BlockSpec((1, T, _LANE), full_spec),  # lse full
        pl.BlockSpec((1, T, _LANE), full_spec),  # D full
    ]
    dq_args = [q, k, v, do, lse, dcap]
    dkv_args = [q, k, v, do, lse, dcap]
    if has_seg:
        segq, segk = _seg_layouts(seg)
        h = bh // seg.shape[0]  # heads share segments: index-map // h
        dq_in_specs += [
            pl.BlockSpec((1, B, _LANE), lambda b, i: (b // h, i, 0)),
            pl.BlockSpec((1, 1, T), lambda b, i: (b // h, 0, 0))]
        dkv_in_specs += [
            pl.BlockSpec((1, T, _LANE), lambda b, j: (b // h, 0, 0)),
            pl.BlockSpec((1, 1, B), lambda b, j: (b // h, 0, j))]
        dq_args += [segq, segk]
        dkv_args += [segq, segk]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, block=B,
                          T=T, has_seg=has_seg),
        grid=(bh, T // B),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, B, hd), row_spec),
        out_shape=jax.ShapeDtypeStruct((bh, T, hd), q.dtype),
        interpret=interpret,
    )(*dq_args)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, block=B,
                          T=T, has_seg=has_seg),
        grid=(bh, T // B),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, B, hd), row_spec),
            pl.BlockSpec((1, B, hd), row_spec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, T, hd), k.dtype),
            jax.ShapeDtypeStruct((bh, T, hd), v.dtype),
        ],
        interpret=interpret,
    )(*dkv_args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal: bool, scale: float, interpret: bool):
    o, _ = _fwd_impl(q, k, v, None, causal, scale, interpret)
    return o


def _flash_fwd(q, k, v, causal, scale, interpret):
    o, lse = _fwd_impl(q, k, v, None, causal, scale, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, None, o, lse, do, causal, scale, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_seg(q, k, v, seg, causal: bool, scale: float, interpret: bool):
    o, _ = _fwd_impl(q, k, v, seg, causal, scale, interpret)
    return o


def _flash_seg_fwd(q, k, v, seg, causal, scale, interpret):
    o, lse = _fwd_impl(q, k, v, seg, causal, scale, interpret)
    return o, (q, k, v, seg, o, lse)


def _flash_seg_bwd(causal, scale, interpret, res, do):
    import numpy as _np

    q, k, v, seg, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, seg, o, lse, do, causal, scale,
                           interpret)
    # integer input → float0 cotangent (jax's symbolic zero for ints)
    dseg = _np.zeros(seg.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)

# VMEM budget: K+V rows resident per (b·h) — bf16 at hd=128 costs
# 2·T·128·2B; cap T so kernel working set stays well under ~16 MB
MAX_SEQ_LEN = 4096


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: float | None = None,
                    segment_ids=None,
                    interpret: bool = False):
    """O(T)-memory attention. q, k, v: (b, h, T, head_dim) with equal
    q/kv lengths, T a multiple of 128 and ≤ MAX_SEQ_LEN. Differentiable
    (custom VJP, FlashAttention-2-style backward).

    ``segment_ids``: optional (b, T) int array for packed sequences —
    a token attends only to keys with the SAME segment id (composes
    with ``causal``). ``interpret=True`` runs the Pallas interpreter
    (CPU testing)."""
    b, h, T, hd = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"q/k/v shapes must match exactly (got q={q.shape}, "
            f"k={k.shape}, v={v.shape}); cross-attention / differing kv "
            "lengths are not supported by this kernel — use dense_attention")
    if T % _LANE or T > MAX_SEQ_LEN:
        raise ValueError(
            f"T={T} must be a multiple of {_LANE} and <= {MAX_SEQ_LEN} "
            "(longer sequences: use ring attention / dense)")
    scale = float(sm_scale) if sm_scale is not None else hd ** -0.5
    qp, _ = _pad_head(q)
    kp, _ = _pad_head(k)
    vp, _ = _pad_head(v)
    hp = qp.shape[-1]
    q3 = qp.reshape(b * h, T, hp)
    k3 = kp.reshape(b * h, T, hp)
    v3 = vp.reshape(b * h, T, hp)
    if segment_ids is not None:
        if segment_ids.shape != (b, T):
            raise ValueError(
                f"segment_ids must be (b, T)=({b}, {T}), got "
                f"{segment_ids.shape}")
        seg = jnp.asarray(segment_ids, jnp.int32)  # (b, T); heads share
        out = _flash_seg(q3, k3, v3, seg, causal, scale, interpret)
    else:
        out = _flash(q3, k3, v3, causal, scale, interpret)
    return out.reshape(b, h, T, hp)[..., :hd]

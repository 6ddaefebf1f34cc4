"""Fused Pallas conv+BN+ReLU training kernels (VERDICT r3 item 1; the
TPU-native analogue of the reference's hand-tuned conv fast-path module,
``deeplearning4j-cuda/.../CudnnConvolutionHelper.java:1``).

Why these exist — the round-3 roofline (BASELINE.md): the ResNet-50 train
step is HBM-bandwidth-bound at ~31% MFU; ~18% of the traffic is
structural, forced by XLA op boundaries between conv / BN-stats /
normalize+ReLU. The fix is to change WHERE the normalize happens: these
kernels emit the RAW conv output plus its per-channel (sum, sum-of-
squares) statistics in the conv epilogue (one pass), and apply the
PREVIOUS layer's BN normalize+ReLU on the fly while READING their input
tile in VMEM (zero extra passes). Activations cross HBM exactly once in
each direction, and the normalized tensors are never stored at all — the
backward kernels re-derive them in VMEM from the raw input (remat inside
the kernel, where recompute is free because the operands are already
resident).

Op granularity:       y, stats = conv(act(x * scale + shift), W)
with ``scale``/``shift`` the folded per-channel affine of the upstream
BatchNormalization (gamma/beta/mean/var combine OUTSIDE the kernel, in
plain jnp on (C,)-vectors) and ``stats[0] = colsum(y)``,
``stats[1] = colsum(y^2)`` feeding the downstream BN. Because stats are
ordinary differentiable outputs, the cross-layer gradient chain
(next layer's normalize → this conv's statistics) is handled by jax
autodiff composing the custom VJPs — no hand-plumbed whole-block
backward.

Coverage: stride-1 pointwise (1x1) and stride-1 SAME 3x3 — the dominant
FLOP carriers of the bottleneck block. Stems, stride-2 convs, pooling and
the FC head stay on the XLA path (see ``nn/conf/layers/fused_block.py``).

Like the flash-attention kernel, callers compile-probe these ops (what
Mosaic accepts varies with the installed compiler and the shapes) and
fall back to the XLA composition on failure.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128          # MXU/VPU lane width
SUBLANE_F32 = 8

from deeplearning4j_tpu.nn.ops.kernel_compat import (  # noqa: E402
    PRECISION as _PREC,
)

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _pad_axis(a, axis: int, to: int):
    pad = to - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _fold(x, scale, shift, relu_in: bool):
    """In-VMEM input fold: normalize+activation of the upstream layer,
    computed in f32 on the VPU, re-cast to bf16 for the MXU."""
    u = x.astype(jnp.float32) * scale + shift
    if relu_in:
        u = jnp.maximum(u, 0.0)
    return u


# ---------------------------------------------------------------------------
# pointwise (1x1, stride 1) fused conv
# ---------------------------------------------------------------------------


def _pw_fwd_kernel(x_ref, s_ref, t_ref, w_ref, y_ref, st_ref, acc_ref,
                   *, relu_in: bool, m_valid: int, bm: int):
    j, i = pl.program_id(0), pl.program_id(1)
    xn = _fold(x_ref[...], s_ref[0, :], t_ref[0, :], relu_in)
    acc_ref[...] = jnp.dot(xn.astype(jnp.bfloat16), w_ref[...],
                           preferred_element_type=jnp.float32, precision=_PREC)
    y = acc_ref[...]
    y_ref[...] = y.astype(jnp.bfloat16)
    # rows past m_valid are padding — keep them out of the statistics
    rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0) + i * bm
    ym = jnp.where(rows < m_valid, y, 0.0)

    @pl.when(i == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    st_ref[0:1, :] += jnp.sum(ym, axis=0, keepdims=True)
    st_ref[1:2, :] += jnp.sum(ym * ym, axis=0, keepdims=True)


def _pw_bwd_dx_kernel(x_ref, s_ref, t_ref, w_ref, z_ref, dz_ref, ds_ref,
                      dx_ref, gs_ref, gt_ref,
                      *, relu_in: bool, m_valid: int, bm: int):
    """dx (+ dscale/dshift) for the pointwise op. Grid (1, I): full Cin
    and Cout resident. dz_eff = dz + dsum + 2*z*dsumsq recomputed on the
    fly; xn re-derived from x (never stored)."""
    i = pl.program_id(1)
    dzeff = (dz_ref[...].astype(jnp.float32) + ds_ref[0:1, :]
             + 2.0 * z_ref[...].astype(jnp.float32) * ds_ref[1:2, :])
    rows = jax.lax.broadcasted_iota(jnp.int32, dzeff.shape, 0) + i * bm
    dzeff = jnp.where(rows < m_valid, dzeff, 0.0)
    # dxn = dzeff @ W^T
    dxn = jax.lax.dot_general(
        dzeff.astype(jnp.bfloat16), w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_PREC,
    )
    x = x_ref[...].astype(jnp.float32)
    u = x * s_ref[0, :] + t_ref[0, :]
    du = jnp.where(u > 0, dxn, 0.0) if relu_in else dxn
    dx_ref[...] = (du * s_ref[0, :]).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _():
        gs_ref[...] = jnp.zeros_like(gs_ref)
        gt_ref[...] = jnp.zeros_like(gt_ref)

    gs_ref[0:1, :] += jnp.sum(du * x, axis=0, keepdims=True)
    gt_ref[0:1, :] += jnp.sum(du, axis=0, keepdims=True)


def _pw_bwd_dw_kernel(x_ref, s_ref, t_ref, z_ref, dz_ref, ds_ref, dw_ref,
                      *, relu_in: bool, m_valid: int, bm: int):
    """dW = xn^T @ dz_eff, accumulated over the M grid. Grid (I,)."""
    i = pl.program_id(0)
    dzeff = (dz_ref[...].astype(jnp.float32) + ds_ref[0:1, :]
             + 2.0 * z_ref[...].astype(jnp.float32) * ds_ref[1:2, :])
    rows = jax.lax.broadcasted_iota(jnp.int32, dzeff.shape, 0) + i * bm
    dzeff = jnp.where(rows < m_valid, dzeff, 0.0)
    xn = _fold(x_ref[...], s_ref[0, :], t_ref[0, :], relu_in)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jax.lax.dot_general(
        xn.astype(jnp.bfloat16), dzeff.astype(jnp.bfloat16),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_PREC,
    )


def _pw_shapes(x, w):
    m, cin = x.shape
    cout = w.shape[1]
    mp = _round_up(m, LANE)
    cinp = _round_up(cin, LANE)
    coutp = _round_up(cout, LANE)
    return m, cin, cout, mp, cinp, coutp


# Scoped-VMEM budget for choosing the M-block. The hardware limit is
# ~16MB; at bm=512, Cin=512, Cout=2048 the dw kernel's footprint is
# 20.9MB (measured OOM, BENCH r4) — the resident (Cin, Cout) panel plus
# double-buffered M-blocks plus f32 intermediates. The estimate below is
# deliberately coarse (panel + 12 bytes per M-row element covers the
# bf16 blocks twice for pipelining and one f32 intermediate each side);
# 12MB leaves headroom for Mosaic's own scratch.
_VMEM_BUDGET = 12 * 1024 * 1024


def _pw_block_m(mp: int, cinp: int, coutp: int) -> int:
    for bm in (512, 256, 128):
        if bm <= max(mp, 128) and (
                4 * cinp * coutp + 12 * bm * (cinp + coutp)) <= _VMEM_BUDGET:
            return bm
    return 128


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def pw_conv(x, scale, shift, w, relu_in: bool = False,
            interpret: bool = False):
    """Fused pointwise conv: (y, stats) = 1x1conv(act(x*scale+shift), W).

    x: (M, Cin) bf16 raw upstream output; scale/shift: (Cin,) f32;
    w: (Cin, Cout) bf16. Returns y (M, Cout) bf16 and stats (2, Cout)
    f32 = [colsum(y); colsum(y^2)] for the downstream BatchNormalization.
    """
    y, st = _pw_forward(x, scale, shift, w, relu_in, interpret)
    return y, st


def _pw_forward(x, scale, shift, w, relu_in, interpret):
    m, cin, cout, mp, cinp, coutp = _pw_shapes(x, w)
    bm = _pw_block_m(mp, cinp, coutp)
    mp = _round_up(mp, bm)
    xp = _pad_axis(_pad_axis(x, 0, mp), 1, cinp)
    wp = _pad_axis(_pad_axis(w, 0, cinp), 1, coutp)
    sp = _pad_axis(scale.reshape(1, -1), 1, cinp)
    tp = _pad_axis(shift.reshape(1, -1), 1, cinp)
    grid = (1, mp // bm)
    y, st = pl.pallas_call(
        functools.partial(_pw_fwd_kernel, relu_in=relu_in, m_valid=m, bm=bm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, cinp), lambda j, i: (i, 0)),
            pl.BlockSpec((1, cinp), lambda j, i: (0, 0)),
            pl.BlockSpec((1, cinp), lambda j, i: (0, 0)),
            pl.BlockSpec((cinp, coutp), lambda j, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, coutp), lambda j, i: (i, 0)),
            pl.BlockSpec((SUBLANE_F32, coutp), lambda j, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, coutp), jnp.bfloat16),
            jax.ShapeDtypeStruct((SUBLANE_F32, coutp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, coutp), jnp.float32)],
        interpret=interpret,
    )(xp, sp, tp, wp)
    return y[:m, :cout], st[:2, :cout]


def _pw_fwd_rule(x, scale, shift, w, relu_in, interpret):
    y, st = _pw_forward(x, scale, shift, w, relu_in, interpret)
    return (y, st), (x, scale, shift, w, y)


def _pw_bwd_rule(relu_in, interpret, res, cts):
    x, scale, shift, w, z = res
    dz, dst = cts
    m, cin, cout, mp, cinp, coutp = _pw_shapes(x, w)
    bm = _pw_block_m(mp, cinp, coutp)
    mp = _round_up(mp, bm)
    xp = _pad_axis(_pad_axis(x, 0, mp), 1, cinp)
    zp = _pad_axis(_pad_axis(z, 0, mp), 1, coutp)
    dzp = _pad_axis(_pad_axis(dz, 0, mp), 1, coutp)
    dstp = _pad_axis(_pad_axis(dst, 0, SUBLANE_F32), 1, coutp)
    wp = _pad_axis(_pad_axis(w, 0, cinp), 1, coutp)
    sp = _pad_axis(scale.reshape(1, -1), 1, cinp)
    tp = _pad_axis(shift.reshape(1, -1), 1, cinp)

    dx, gs, gt = pl.pallas_call(
        functools.partial(_pw_bwd_dx_kernel, relu_in=relu_in, m_valid=m,
                          bm=bm),
        grid=(1, mp // bm),
        in_specs=[
            pl.BlockSpec((bm, cinp), lambda j, i: (i, 0)),
            pl.BlockSpec((1, cinp), lambda j, i: (0, 0)),
            pl.BlockSpec((1, cinp), lambda j, i: (0, 0)),
            pl.BlockSpec((cinp, coutp), lambda j, i: (0, 0)),
            pl.BlockSpec((bm, coutp), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, coutp), lambda j, i: (i, 0)),
            pl.BlockSpec((SUBLANE_F32, coutp), lambda j, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, cinp), lambda j, i: (i, 0)),
            pl.BlockSpec((SUBLANE_F32, cinp), lambda j, i: (0, 0)),
            pl.BlockSpec((SUBLANE_F32, cinp), lambda j, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, cinp), x.dtype),
            jax.ShapeDtypeStruct((SUBLANE_F32, cinp), jnp.float32),
            jax.ShapeDtypeStruct((SUBLANE_F32, cinp), jnp.float32),
        ],
        interpret=interpret,
    )(xp, sp, tp, wp, zp, dzp, dstp)

    dw = pl.pallas_call(
        functools.partial(_pw_bwd_dw_kernel, relu_in=relu_in, m_valid=m,
                          bm=bm),
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, cinp), lambda i: (i, 0)),
            pl.BlockSpec((1, cinp), lambda i: (0, 0)),
            pl.BlockSpec((1, cinp), lambda i: (0, 0)),
            pl.BlockSpec((bm, coutp), lambda i: (i, 0)),
            pl.BlockSpec((bm, coutp), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANE_F32, coutp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((cinp, coutp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((cinp, coutp), jnp.float32),
        interpret=interpret,
    )(xp, sp, tp, zp, dzp, dstp)

    return (dx[:m, :cin],
            gs[0, :cin],
            gt[0, :cin],
            dw[:cin, :cout].astype(w.dtype))


pw_conv.defvjp(_pw_fwd_rule, _pw_bwd_rule)


# ---------------------------------------------------------------------------
# 3x3 SAME stride-1 fused conv
# ---------------------------------------------------------------------------


def _c3_fwd_kernel(x_ref, s_ref, t_ref, w_ref, y_ref, st_ref, xp_ref,
                   acc_ref, *, relu_in: bool, h: int, wd: int, cinp: int):
    n = pl.program_id(0)
    xn = _fold(x_ref[0], s_ref[0, :], t_ref[0, :], relu_in).astype(jnp.bfloat16)
    xp_ref[...] = jnp.zeros_like(xp_ref)
    xp_ref[1:h + 1, 1:wd + 1, :] = xn
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for dy in range(3):
        for dx in range(3):
            op = xp_ref[dy:dy + h, dx:dx + wd, :].reshape(h * wd, cinp)
            acc_ref[...] += jnp.dot(op, w_ref[dy, dx],
                                    preferred_element_type=jnp.float32, precision=_PREC)
    y = acc_ref[...]
    y_ref[0] = y.reshape(h, wd, -1).astype(jnp.bfloat16)

    @pl.when(n == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    st_ref[0:1, :] += jnp.sum(y, axis=0, keepdims=True)
    st_ref[1:2, :] += jnp.sum(y * y, axis=0, keepdims=True)


def _c3_bwd_dx_kernel(x_ref, s_ref, t_ref, w_ref, z_ref, dz_ref, ds_ref,
                      dx_ref, gs_ref, gt_ref, dxp_ref,
                      *, relu_in: bool, h: int, wd: int, coutp: int):
    n = pl.program_id(0)
    dzeff = (dz_ref[0].astype(jnp.float32)
             + ds_ref[0:1, :].reshape(1, 1, -1)
             + 2.0 * z_ref[0].astype(jnp.float32)
             * ds_ref[1:2, :].reshape(1, 1, -1))
    dzf = dzeff.reshape(h * wd, coutp).astype(jnp.bfloat16)
    dxp_ref[...] = jnp.zeros_like(dxp_ref)
    for dy in range(3):
        for dx in range(3):
            g = jax.lax.dot_general(
                dzf, w_ref[dy, dx],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            ).reshape(h, wd, -1)
            dxp_ref[dy:dy + h, dx:dx + wd, :] += g
    x = x_ref[0].astype(jnp.float32)
    u = x * s_ref[0, :] + t_ref[0, :]
    dxn = dxp_ref[1:h + 1, 1:wd + 1, :]
    du = jnp.where(u > 0, dxn, 0.0) if relu_in else dxn
    dx_ref[0] = (du * s_ref[0, :]).astype(dx_ref.dtype)

    @pl.when(n == 0)
    def _():
        gs_ref[...] = jnp.zeros_like(gs_ref)
        gt_ref[...] = jnp.zeros_like(gt_ref)

    gs_ref[0:1, :] += jnp.sum(du * x, axis=(0, 1)).reshape(1, -1)
    gt_ref[0:1, :] += jnp.sum(du, axis=(0, 1)).reshape(1, -1)


def _c3_bwd_dw_kernel(x_ref, s_ref, t_ref, z_ref, dz_ref, ds_ref, dw_ref,
                      xp_ref, *, relu_in: bool, h: int, wd: int, cinp: int,
                      coutp: int):
    n = pl.program_id(0)
    xn = _fold(x_ref[0], s_ref[0, :], t_ref[0, :], relu_in).astype(jnp.bfloat16)
    xp_ref[...] = jnp.zeros_like(xp_ref)
    xp_ref[1:h + 1, 1:wd + 1, :] = xn
    dzeff = (dz_ref[0].astype(jnp.float32)
             + ds_ref[0:1, :].reshape(1, 1, -1)
             + 2.0 * z_ref[0].astype(jnp.float32)
             * ds_ref[1:2, :].reshape(1, 1, -1))
    dzf = dzeff.reshape(h * wd, coutp).astype(jnp.bfloat16)

    @pl.when(n == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for dy in range(3):
        for dx in range(3):
            op = xp_ref[dy:dy + h, dx:dx + wd, :].reshape(h * wd, cinp)
            dw_ref[dy, dx] += jax.lax.dot_general(
                op, dzf,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )


def _c3_shapes(x, w):
    n, h, wd, cin = x.shape
    cout = w.shape[-1]
    cinp = _round_up(cin, LANE)
    coutp = _round_up(cout, LANE)
    return n, h, wd, cin, cout, cinp, coutp


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def conv3x3(x, scale, shift, w, relu_in: bool = False,
            interpret: bool = False):
    """Fused 3x3 SAME stride-1 conv: (y, stats) with the same contract as
    :func:`pw_conv`. x: (N, H, W, Cin) bf16; w: (3, 3, Cin, Cout) bf16."""
    return _c3_forward(x, scale, shift, w, relu_in, interpret)


def _c3_forward(x, scale, shift, w, relu_in, interpret):
    n, h, wd, cin, cout, cinp, coutp = _c3_shapes(x, w)
    xp = _pad_axis(x, 3, cinp)
    wp = _pad_axis(_pad_axis(w, 2, cinp), 3, coutp)
    sp = _pad_axis(scale.reshape(1, -1), 1, cinp)
    tp = _pad_axis(shift.reshape(1, -1), 1, cinp)
    y, st = pl.pallas_call(
        functools.partial(_c3_fwd_kernel, relu_in=relu_in, h=h, wd=wd,
                          cinp=cinp),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, wd, cinp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, cinp), lambda i: (0, 0)),
            pl.BlockSpec((1, cinp), lambda i: (0, 0)),
            pl.BlockSpec((3, 3, cinp, coutp), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, wd, coutp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((SUBLANE_F32, coutp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wd, coutp), jnp.bfloat16),
            jax.ShapeDtypeStruct((SUBLANE_F32, coutp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((h + 2, wd + 2, cinp), jnp.bfloat16),
            pltpu.VMEM((h * wd, coutp), jnp.float32),
        ],
        interpret=interpret,
    )(xp, sp, tp, wp)
    return y[..., :cout], st[:2, :cout]


def _c3_fwd_rule(x, scale, shift, w, relu_in, interpret):
    y, st = _c3_forward(x, scale, shift, w, relu_in, interpret)
    return (y, st), (x, scale, shift, w, y)


def _c3_bwd_rule(relu_in, interpret, res, cts):
    x, scale, shift, w, z = res
    dz, dst = cts
    n, h, wd, cin, cout, cinp, coutp = _c3_shapes(x, w)
    xp = _pad_axis(x, 3, cinp)
    zp = _pad_axis(z, 3, coutp)
    dzp = _pad_axis(dz, 3, coutp)
    dstp = _pad_axis(_pad_axis(dst, 0, SUBLANE_F32), 1, coutp)
    wp = _pad_axis(_pad_axis(w, 2, cinp), 3, coutp)
    sp = _pad_axis(scale.reshape(1, -1), 1, cinp)
    tp = _pad_axis(shift.reshape(1, -1), 1, cinp)

    dx, gs, gt = pl.pallas_call(
        functools.partial(_c3_bwd_dx_kernel, relu_in=relu_in, h=h, wd=wd,
                          coutp=coutp),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, wd, cinp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, cinp), lambda i: (0, 0)),
            pl.BlockSpec((1, cinp), lambda i: (0, 0)),
            pl.BlockSpec((3, 3, cinp, coutp), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((1, h, wd, coutp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, wd, coutp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((SUBLANE_F32, coutp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, wd, cinp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((SUBLANE_F32, cinp), lambda i: (0, 0)),
            pl.BlockSpec((SUBLANE_F32, cinp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wd, cinp), x.dtype),
            jax.ShapeDtypeStruct((SUBLANE_F32, cinp), jnp.float32),
            jax.ShapeDtypeStruct((SUBLANE_F32, cinp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((h + 2, wd + 2, cinp), jnp.float32),
        ],
        interpret=interpret,
    )(xp, sp, tp, wp, zp, dzp, dstp)

    dw = pl.pallas_call(
        functools.partial(_c3_bwd_dw_kernel, relu_in=relu_in, h=h, wd=wd,
                          cinp=cinp, coutp=coutp),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, wd, cinp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, cinp), lambda i: (0, 0)),
            pl.BlockSpec((1, cinp), lambda i: (0, 0)),
            pl.BlockSpec((1, h, wd, coutp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, wd, coutp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((SUBLANE_F32, coutp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((3, 3, cinp, coutp), lambda i: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, 3, cinp, coutp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((h + 2, wd + 2, cinp), jnp.bfloat16),
        ],
        interpret=interpret,
    )(xp, sp, tp, zp, dzp, dstp)

    return (dx[..., :cin],
            gs[0, :cin],
            gt[0, :cin],
            dw[:, :, :cin, :cout].astype(w.dtype))


conv3x3.defvjp(_c3_fwd_rule, _c3_bwd_rule)


# ---------------------------------------------------------------------------
# compile-probe gate (the flash-attention pattern: AOT compile + execute a
# tiny instance, value-check fwd AND grads against the XLA reference; a
# compiler can reject a kernel OR miscompile it)
# ---------------------------------------------------------------------------

_PROBE_CACHE: dict = {}


def fused_conv_available(dtype=jnp.bfloat16) -> bool:
    """True when the Pallas fused-conv ops compile AND compute correct
    values/gradients on this backend. Verdicts live in the kernel
    REGISTRY (probe-once-per-process, ``DL4J_TPU_FUSED_CONV=0`` kill
    switch honored, fallbacks observable); ``_PROBE_CACHE`` mirrors them
    for introspection only — the registry is authoritative, so
    ``KernelRegistry.reset("fused_conv")`` genuinely re-probes. The
    interpret mode is not supported here (the fused-block layer calls
    the compiled kernels; tests drive ``interpret=`` explicitly)."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    key = jnp.dtype(dtype).name
    reg = default_kernel_registry()
    cached = reg.enabled("fused_conv", (key,))
    if cached is not None:
        _PROBE_CACHE[key] = cached
        return cached
    if reg.mode("fused_conv") == "off":
        reg.disable("fused_conv", (key,),
                    "disabled via DL4J_TPU_FUSED_CONV=0")
        _PROBE_CACHE[key] = False
        return False

    def probe():
        rng = np.random.default_rng(0)

        def mk(shape, scale=1.0, shift=0.0, dt=dtype):
            # numpy (never jnp): under an ambient trace jnp.asarray
            # stages into the caller's graph and the AOT executables
            # below would be handed tracers instead of concrete
            # buffers — the exact latent bug the flash probe had
            return np.asarray(rng.standard_normal(shape) * scale + shift,
                              np.float32).astype(jnp.dtype(dt))

        x2 = mk((64, 128))
        s = mk(128, 0.2, 1.0, jnp.float32)
        t = mk(128, 0.1, 0.0, jnp.float32)
        w2 = mk((128, 128), 0.05)
        x4 = mk((1, 8, 8, 128))
        w4 = mk((3, 3, 128, 128), 0.05)

        def loss(fn):
            def f(x, s, t, w):
                y, st = fn(x, s, t, w)
                return jnp.sum(y.astype(jnp.float32) ** 2) * 1e-3 + jnp.sum(
                    st * 1e-4)
            return f

        for kern, ref, args in (
            (functools.partial(pw_conv, relu_in=True),
             functools.partial(pw_conv_reference, relu_in=True),
             (x2, s, t, w2)),
            (functools.partial(conv3x3, relu_in=True),
             functools.partial(conv3x3_reference, relu_in=True),
             (x4, s, t, w4)),
        ):
            shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
            vg_k = jax.jit(jax.value_and_grad(
                loss(kern), argnums=(0, 1, 2, 3))).lower(*shapes).compile()
            vg_r = jax.jit(jax.value_and_grad(
                loss(ref), argnums=(0, 1, 2, 3))).lower(*shapes).compile()
            vk, gk = vg_k(*args)
            vr, gr = vg_r(*args)
            tol = 5e-2
            if not np.isfinite(float(vk)) or abs(float(vk) - float(vr)) > \
                    tol * (abs(float(vr)) + 1.0):
                raise RuntimeError(f"fused-conv probe value mismatch: "
                                   f"{float(vk)} vs {float(vr)}")
            for a, b in zip(jax.tree_util.tree_leaves(gk),
                            jax.tree_util.tree_leaves(gr)):
                a = np.asarray(a, np.float32)
                b = np.asarray(b, np.float32)
                err = np.max(np.abs(a - b) / (np.abs(b) + 1.0))
                if not np.isfinite(err) or err > tol:
                    raise RuntimeError(
                        f"fused-conv probe grad mismatch: rel {err:.3e}")

    ok = reg.probe("fused_conv", (key,), probe)
    _PROBE_CACHE[key] = ok
    return ok


# ---------------------------------------------------------------------------
# pure-XLA reference implementations (parity oracle + fallback path)
# ---------------------------------------------------------------------------


def pw_conv_reference(x, scale, shift, w, relu_in: bool = False):
    xn = _fold(x, scale, shift, relu_in).astype(x.dtype)
    # plain XLA — inherits the package "highest" default (fp32 parity);
    # the _PREC pin is for in-Mosaic-kernel dots only
    y = jnp.dot(xn, w, preferred_element_type=jnp.float32)
    st = jnp.stack([y.sum(0), (y * y).sum(0)])
    return y.astype(x.dtype), st


def conv3x3_reference(x, scale, shift, w, relu_in: bool = False):
    # f32 operands on bf16-rounded values == bf16 matmul with f32
    # accumulation (products exact in f32), and keeps the autodiff
    # cotangent dtypes consistent
    xn = _fold(x, scale, shift, relu_in).astype(x.dtype).astype(jnp.float32)
    y = jax.lax.conv_general_dilated(
        xn, w.astype(jnp.float32), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    st = jnp.stack([y.sum((0, 1, 2)), (y * y).sum((0, 1, 2))])
    return y.astype(x.dtype), st

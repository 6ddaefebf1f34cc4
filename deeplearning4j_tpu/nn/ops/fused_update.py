"""Fused ZeRO-1 weight update as a Pallas TPU kernel (TPP-style,
arXiv 2104.05755 applied to the arXiv 2004.13336 sharded update).

The ZeRO-1 step (parallel/zero.py) consumes the synchronized gradient
sharded over the data axis, updates each replica's 1/N flat shard, and
gathers the fresh shards back. GSPMD inserts the reduce-scatter (from
the ``P("data", None)`` constraint on the gradient) and the all-gather
(from the replicated constraint on the result); between them XLA lowers
the Adam math as ~8 separate elementwise HLOs whose intermediates
(m', v', the biased-corrected update, the subtraction) each round-trip
HBM over the full shard. This kernel computes the whole update —

    m' = β₁·m + (1-β₁)·g
    v' = β₂·v + (1-β₂)·g²
    p' = p - α·m'/(√v' + ε)        α = lr·√(1-β₂ᵗ)/(1-β₁ᵗ)

— in ONE pass over the flat shard: p/g/m/v stream HBM→VMEM once, three
results stream back, nothing else is materialized. α is computed
OUTSIDE the kernel with exactly the scalar expression ``Adam.apply``
uses, so the fused step computes the same expressions as the unfused
reference. Parity contract (the probe and tests/test_fused_kernels.py,
compiled leg against compiled leg): params AND both Adam slots agree
within :func:`parity_atol` — two float32 ulps of the largest operand.
They are not bit-equal in general: a compiler may contract
``β·m + (1-β)·g`` into a fused multiply-add (one rounding) in one
program and not in the other, as the XLA:CPU of jax 0.9 does. The
zero-padding lanes of odd-count groups stay exactly zero through the
update either way.

The collectives stay where GSPMD puts them: the kernel's operands carry
the ``(N, chunk)`` flat-shard layout and its sharding constraints, so
reduce-scatter → fused-update → all-gather compiles into one program
with the update portion single-pass. The availability probe compiles
the kernel UNDER the actual training mesh's shardings (a partitioner
that cannot place a Pallas call inside the sharded region fails the
probe, not the training step) and falls back to the reference
composition — same contract as every kernel in ``nn.ops.registry``
(``DL4J_TPU_FUSED_ZERO1`` = 0 | 1 | interpret).

Coverage: exact-type :class:`~deeplearning4j_tpu.updaters.Adam` groups
in fp32 (the canonical ZeRO-1 configuration). Other updaters/dtypes
take the reference path per group — the layout already splits groups by
(updater config, dtype), so mixing costs nothing.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_LANE = 128
_BLOCK_ROWS = 256  # rows of 128 lanes per grid cell: 8 × 128 KiB in VMEM


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _adam_kernel(alpha_ref, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, b1: float, b2: float,
                 eps: float):
    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    alpha = alpha_ref[0, 0]
    update = alpha * m / (jnp.sqrt(v) + eps)
    po_ref[...] = p_ref[...] - update
    mo_ref[...] = m
    vo_ref[...] = v


def fused_adam_apply(p, g, m, v, alpha, *, b1: float, b2: float, eps: float,
                     interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass Adam over arbitrarily-shaped fp32 operands (the flat
    (N, chunk) shard in the ZeRO-1 step). ``alpha`` is the precomputed
    bias-corrected step size (traced scalar). Returns (p', m', v')."""
    shape = p.shape
    total = int(np.prod(shape)) if shape else 1
    rows = _round_up(-(-total // _LANE), _BLOCK_ROWS)
    pad = rows * _LANE - total

    def to2d(a):
        flat = a.reshape(-1)
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        return flat.reshape(rows, _LANE)

    alpha2 = jnp.asarray(alpha, p.dtype).reshape(1, 1)
    grid = (rows // _BLOCK_ROWS,)
    blk = pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda r: (r, 0))
    out = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda r: (0, 0)),
                  blk, blk, blk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), p.dtype)] * 3,
        interpret=interpret,
    )(alpha2, to2d(p), to2d(g), to2d(m), to2d(v))

    def back(a):
        return a.reshape(-1)[:total].reshape(shape)

    return back(out[0]), back(out[1]), back(out[2])


# --------------------------------------------------------------------------
# group-level impl + probe (wired from parallel/zero.py)
# --------------------------------------------------------------------------
def parity_atol(*operands) -> float:
    """Largest absolute difference the parity contract allows between
    the fused update and its reference on these operands: two float32
    ulps of the largest operand magnitude (see the module docstring)."""
    top = max(float(np.max(np.abs(np.asarray(o)))) for o in operands)
    return 2.0 * float(np.finfo(np.float32).eps) * max(top, 1.0)


def _adam_alpha(upd, t, iteration, epoch):
    """EXACTLY ``Adam.apply``'s scalar pipeline — parity depends on
    reusing the same expressions in the same order."""
    tf = t.astype(jnp.float32) if hasattr(t, "astype") else float(t)
    return upd.lr(iteration, epoch) * jnp.sqrt(1 - upd.beta2 ** tf) \
        / (1 - upd.beta1 ** tf)


def _make_impl(interpret: bool) -> Callable:
    def impl(upd, p2d, g2d, state, t, iteration, epoch):
        alpha = _adam_alpha(upd, t, iteration, epoch)
        new_p, m, v = fused_adam_apply(
            p2d, g2d, state["m"], state["v"], alpha,
            b1=upd.beta1, b2=upd.beta2, eps=upd.epsilon,
            interpret=interpret)
        return new_p, {"m": m, "v": v}
    return impl


def _probe_group(upd, n_shards: int, mesh, interpret: bool) -> None:
    """Compile (AOT) and execute the fused update UNDER the training
    mesh's flat-shard shardings; assert parity (:func:`parity_atol`) vs
    the unfused reference program. A GSPMD partitioner that cannot
    place the Pallas call inside the sharded region fails HERE, not in
    the train step."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    chunk = 2 * _LANE
    shape = (max(int(n_shards), 1), chunk)
    rng = np.random.default_rng(0)

    def mk():
        # numpy: probes can run under an ambient trace (see fused_lstm)
        return np.asarray(rng.standard_normal(shape), np.float32)

    p, g, m = mk(), mk(), mk()
    v = np.abs(mk())  # v is a running mean of squares — non-negative
    t = np.asarray(3.0, np.float32)
    it = np.asarray(2, np.int32)
    ep = np.asarray(0, np.int32)
    impl = _make_impl(interpret)

    def fused_fn(p, g, m, v, t, it, ep):
        new_p, st = impl(upd, p, g, {"m": m, "v": v}, t, it, ep)
        return new_p, st["m"], st["v"]

    def ref_fn(p, g, m, v, t, it, ep):
        delta, st = upd.apply(g, {"m": m, "v": v}, t, it, ep)
        return p - delta, st["m"], st["v"]

    if mesh is not None:
        shard = NamedSharding(mesh, P("data", None))
        repl = NamedSharding(mesh, P())
        in_sh = (shard,) * 4 + (repl,) * 3
        out_sh = (repl,) * 3
        args = tuple(jax.device_put(a, s)
                     for a, s in zip((p, g, m, v, t, it, ep), in_sh))
        k = jax.jit(fused_fn, in_shardings=in_sh,
                    out_shardings=out_sh)
        r = jax.jit(ref_fn, in_shardings=in_sh, out_shardings=out_sh)
    else:
        args = (p, g, m, v, t, it, ep)
        k = jax.jit(fused_fn)
        r = jax.jit(ref_fn)
    shapes = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
              for a in (p, g, m, v, t, it, ep)]
    got = k.lower(*shapes).compile()(*args)
    want = r.lower(*shapes).compile()(*args)
    atol = parity_atol(p, g, m, v)
    for name, a, b in zip(("p", "m", "v"), got, want):
        err = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        if not err <= atol:  # also catches NaN
            raise RuntimeError(
                f"fused ZeRO-1 update parity check failed ({name}): "
                f"max abs err {err:.3e} > {atol:.3e}")


def resolve_group_impls(layout, mesh=None,
                        enabled: Optional[bool] = None) -> List[Optional[Callable]]:
    """One fused-update impl (or None → reference ``updater.apply``)
    per layout group, resolved ONCE at step-build time through the
    kernel registry. ``enabled=False`` short-circuits (explicit opt-out
    knob); None/True go through the env/backend route."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry
    from deeplearning4j_tpu.updaters import Adam

    impls: List[Optional[Callable]] = []
    if enabled is False:
        return [None] * len(layout.groups)
    reg = default_kernel_registry()
    for grp in layout.groups:
        if type(grp.updater) is not Adam or \
                jnp.dtype(grp.dtype) != jnp.float32:
            impls.append(None)
            continue
        key = ("adam", jnp.dtype(grp.dtype).name, int(layout.n_shards))
        interpret = reg.resolve(
            "fused_zero1", key,
            lambda interp, grp=grp: functools.partial(
                _probe_group, grp.updater, layout.n_shards, mesh, interp))
        impls.append(None if interpret is None else _make_impl(interpret))
    return impls

"""Fused LSTM cell as a Pallas TPU kernel (TPP-style, arXiv 2104.05755).

One time step of the (Graves)LSTM — the hot inner loop of the textgen
training scan and of the GenerationEngine's per-slot decode — is four
gate matmuls plus a chain of elementwise ops:

    z = x_t @ Wx + h @ Wh + b          # (B, 4n): gates [i, f, o, g]
    i = σ(z_i [+ pI·c]); f = σ(z_f [+ pF·c]); g = tanh(z_g)
    c' = f·c + i·g
    o = σ(z_o [+ pO·c'])
    h' = o·tanh(c')

XLA lowers this as separate gemm + elementwise ops whose intermediates
(z, the four gates, c') round-trip HBM every step of every scan
iteration. This kernel computes the whole cell in one ``pallas_call``:
both gemms hit the MXU with f32 accumulation, the gate chain runs on the
VPU over the z tile still resident in VMEM, and only (h', c') leave the
kernel — the scan-friendly carry layout, ``(B, n)`` each, exactly what
``lax.scan`` carries between steps.

Layout: gate blocks are padded **independently** to the 128-lane tile
(``Wx (nIn, 4, n) → (nIn_p, 4·n_p)``), so in-kernel gate slicing at
``n_p`` boundaries reads the same values the reference reads at ``n``
boundaries; padded lanes carry zero weights/bias and provably stay zero
through the gate chain (σ(0)·tanh(0) = 0), so the sliced-off columns
never contaminate real ones.

Differentiation: ``custom_vjp``. The forward is the fused kernel; the
backward recomputes the gates from the saved ``(x, h, c)`` residuals and
applies the standard LSTM cell gradient as an XLA composition (the
flash-attention recompute discipline — recompute in the backward instead
of materializing gate activations in the forward). Parity contract
(tests/test_fused_kernels.py, compiled leg against compiled leg, fp32
under the interpreter): forward bit-exact vs the reference step at
lane-aligned shapes; at lane-padded shapes the zero-padded gemm may sum
in another order, so (h', c') agree within one float32 ulp of the
largest gate pre-activation (bit-exact under the XLA:CPU of jax 0.4,
not of 0.9); gradients allclose at ≤1e-5; bf16 carries
the documented ~1e-2 tolerance of one MXU pass vs the "highest"
-precision XLA path.

Availability runs through ``nn.ops.registry`` (probe-once-per-process,
``kernel_fallback`` flight event + ``kernel_enabled{name=fused_lstm}``
gauge): kill/mode switch ``DL4J_TPU_FUSED_LSTM`` = 0 | 1 (auto) |
interpret. Only tanh/sigmoid cells route to the kernel — exotic
activations stay on the reference step.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deeplearning4j_tpu.nn.ops.kernel_compat import PRECISION as _PREC

_LANE = 128
_SUBLANE = 8


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


# --------------------------------------------------------------------------
# reference cell (the exact math of LSTM._step / GravesLSTM._step)
# --------------------------------------------------------------------------
def reference_lstm_cell(x, h, c, Wx, Wh, b, pI=None, pF=None, pO=None):
    """The pure-XLA cell — fallback path and parity oracle. Must stay
    bit-identical to ``recurrent.LSTM._step`` (tanh/sigmoid instance):
    same expressions, same order."""
    z = x @ Wx + h @ Wh + b
    n = h.shape[-1]
    if pI is not None:
        i = jax.nn.sigmoid(z[:, :n] + pI * c)
        f = jax.nn.sigmoid(z[:, n:2 * n] + pF * c)
        g = jnp.tanh(z[:, 3 * n:])
        c_new = f * c + i * g
        o = jax.nn.sigmoid(z[:, 2 * n:3 * n] + pO * c_new)
    else:
        i = jax.nn.sigmoid(z[:, :n])
        f = jax.nn.sigmoid(z[:, n:2 * n])
        o = jax.nn.sigmoid(z[:, 2 * n:3 * n])
        g = jnp.tanh(z[:, 3 * n:])
        c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    return h_new, c_new


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------
def _cell_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref, *rest,
                 n_p: int, peephole: bool):
    if peephole:
        pi_ref, pf_ref, po_ref, h_out, c_out = rest
    else:
        (h_out, c_out) = rest
    x = x_ref[...]
    h = h_ref[...]
    c = c_ref[...].astype(jnp.float32)
    # both gate gemms accumulate f32 on the MXU; bias add on the VPU
    z = jax.lax.dot_general(x, wx_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=_PREC)
    z = z + jax.lax.dot_general(h, wh_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_PREC)
    z = z + b_ref[...].astype(jnp.float32)
    zi = z[:, :n_p]
    zf = z[:, n_p:2 * n_p]
    zo = z[:, 2 * n_p:3 * n_p]
    zg = z[:, 3 * n_p:]
    if peephole:
        i = jax.nn.sigmoid(zi + pi_ref[...] * c)
        f = jax.nn.sigmoid(zf + pf_ref[...] * c)
        g = jnp.tanh(zg)
        c_new = f * c + i * g
        o = jax.nn.sigmoid(zo + po_ref[...] * c_new)
    else:
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
        o = jax.nn.sigmoid(zo)
        g = jnp.tanh(zg)
        c_new = f * c + i * g
    h_out[...] = (o * jnp.tanh(c_new)).astype(h_out.dtype)
    c_out[...] = c_new.astype(c_out.dtype)


def _pack_gates(w, n: int, n_p: int):
    """(d, 4n) gate-packed matrix → (d, 4·n_p) with each gate block
    zero-padded independently to the lane tile."""
    d = w.shape[0]
    w4 = w.reshape(d, 4, n)
    if n_p != n:
        w4 = jnp.pad(w4, ((0, 0), (0, 0), (0, n_p - n)))
    return w4.reshape(d, 4 * n_p)


def _cell_impl(x, h, c, Wx, Wh, b, peeps, interpret: bool):
    B, n_in = x.shape
    n = h.shape[-1]
    n_p = _round_up(n, _LANE)
    in_p = _round_up(n_in, _LANE)
    B_p = _round_up(B, _SUBLANE)

    def pad2(a, rows, cols):
        return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))

    xp = pad2(x, B_p, in_p)
    hp = pad2(h, B_p, n_p)
    cp = pad2(c, B_p, n_p)
    wxp = pad2(_pack_gates(Wx, n, n_p), in_p, 4 * n_p)
    whp = pad2(_pack_gates(Wh, n, n_p), n_p, 4 * n_p)
    bp = _pack_gates(b.reshape(1, -1), n, n_p)
    args = [xp, hp, cp, wxp, whp, bp]
    if peeps is not None:
        for pvec in peeps:
            args.append(jnp.pad(pvec.reshape(1, -1), ((0, 0), (0, n_p - n))))
    kern = functools.partial(_cell_kernel, n_p=n_p,
                             peephole=peeps is not None)
    h_new, c_new = pl.pallas_call(
        kern,
        out_shape=[jax.ShapeDtypeStruct((B_p, n_p), h.dtype),
                   jax.ShapeDtypeStruct((B_p, n_p), c.dtype)],
        interpret=interpret,
    )(*args)
    return h_new[:B, :n], c_new[:B, :n]


# --------------------------------------------------------------------------
# backward (XLA composition; recomputes gates from residuals)
# --------------------------------------------------------------------------
def _cell_bwd_math(x, h, c, Wx, Wh, b, peeps, dh, dc):
    pI, pF, pO = peeps if peeps is not None else (None, None, None)
    z = x @ Wx + h @ Wh + b
    n = h.shape[-1]
    zi, zf, zo, zg = (z[:, :n], z[:, n:2 * n], z[:, 2 * n:3 * n],
                      z[:, 3 * n:])
    if pI is not None:
        i = jax.nn.sigmoid(zi + pI * c)
        f = jax.nn.sigmoid(zf + pF * c)
    else:
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
    g = jnp.tanh(zg)
    c_new = f * c + i * g
    o = jax.nn.sigmoid(zo + pO * c_new if pO is not None else zo)
    tanh_c = jnp.tanh(c_new)

    do = dh * tanh_c
    dzo = do * o * (1.0 - o)
    dc_t = dc + dh * o * (1.0 - tanh_c * tanh_c)
    if pO is not None:
        dc_t = dc_t + dzo * pO
    di = dc_t * g
    df = dc_t * c
    dg = dc_t * i
    dzi = di * i * (1.0 - i)
    dzf = df * f * (1.0 - f)
    dzg = dg * (1.0 - g * g)
    dc_prev = dc_t * f
    if pI is not None:
        dc_prev = dc_prev + dzi * pI + dzf * pF
    dz = jnp.concatenate([dzi, dzf, dzo, dzg], axis=1)
    dx = dz @ Wx.T
    dh_prev = dz @ Wh.T
    dWx = x.T @ dz
    dWh = h.T @ dz
    db = jnp.sum(dz, axis=0)
    out = (dx, dh_prev, dc_prev, dWx.astype(Wx.dtype),
           dWh.astype(Wh.dtype), db.astype(b.dtype))
    if pI is not None:
        dpI = jnp.sum(dzi * c, axis=0).astype(pI.dtype)
        dpF = jnp.sum(dzf * c, axis=0).astype(pF.dtype)
        dpO = jnp.sum(dzo * c_new, axis=0).astype(pO.dtype)
        return out + (dpI, dpF, dpO)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _cell_plain(x, h, c, Wx, Wh, b, interpret):
    return _cell_impl(x, h, c, Wx, Wh, b, None, interpret)


def _cell_plain_fwd(x, h, c, Wx, Wh, b, interpret):
    out = _cell_impl(x, h, c, Wx, Wh, b, None, interpret)
    return out, (x, h, c, Wx, Wh, b)


def _cell_plain_bwd(interpret, res, cts):
    x, h, c, Wx, Wh, b = res
    dh, dc = cts
    return _cell_bwd_math(x, h, c, Wx, Wh, b, None, dh, dc)


_cell_plain.defvjp(_cell_plain_fwd, _cell_plain_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _cell_peep(x, h, c, Wx, Wh, b, pI, pF, pO, interpret):
    return _cell_impl(x, h, c, Wx, Wh, b, (pI, pF, pO), interpret)


def _cell_peep_fwd(x, h, c, Wx, Wh, b, pI, pF, pO, interpret):
    out = _cell_impl(x, h, c, Wx, Wh, b, (pI, pF, pO), interpret)
    return out, (x, h, c, Wx, Wh, b, pI, pF, pO)


def _cell_peep_bwd(interpret, res, cts):
    x, h, c, Wx, Wh, b, pI, pF, pO = res
    dh, dc = cts
    return _cell_bwd_math(x, h, c, Wx, Wh, b, (pI, pF, pO), dh, dc)


_cell_peep.defvjp(_cell_peep_fwd, _cell_peep_bwd)


def fused_lstm_cell(x, h, c, Wx, Wh, b, pI=None, pF=None, pO=None, *,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One fused LSTM step → (h_new, c_new). Peephole (GravesLSTM) when
    pI/pF/pO are given. Differentiable (custom VJP; backward is the XLA
    gate-recompute composition)."""
    if pI is not None:
        return _cell_peep(x, h, c, Wx, Wh, b, pI, pF, pO, interpret)
    return _cell_plain(x, h, c, Wx, Wh, b, interpret)


# --------------------------------------------------------------------------
# probe + routing (registry-cached per instantiation)
# --------------------------------------------------------------------------
def _probe_cell(n_in: int, n: int, dtype, peephole: bool,
                interpret: bool, B: int = 8) -> None:
    """Compile (AOT — safe under an ambient trace) and EXECUTE the fused
    cell forward + grad at a (B, n_in/n) instance; compare against the
    reference cell. Raises on any mismatch — a compiler can MIScompile
    a kernel, not just reject it. ``B`` is the CALLER's padded
    batch, not a toy size: a VMEM overflow at the real batch must fail
    the probe, not the training step's compile."""
    rng = np.random.default_rng(0)

    def mk(shape):
        # numpy (never jnp): under an ambient trace jnp ops stage into
        # the caller's graph and the AOT executables below would be
        # handed tracers instead of concrete buffers
        return np.asarray(rng.standard_normal(shape),
                          np.float32).astype(jnp.dtype(dtype))

    x, h, c = mk((B, n_in)), mk((B, n)), mk((B, n))
    Wx, Wh = mk((n_in, 4 * n)), mk((n, 4 * n))
    b = mk((4 * n,))
    peeps = (mk((n,)), mk((n,)), mk((n,))) if peephole else ()
    args = (x, h, c, Wx, Wh, b) + peeps
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]

    def loss(cell):
        def f(*a):
            h_new, c_new = cell(*a)
            return (jnp.sum(h_new.astype(jnp.float32) ** 2)
                    + jnp.sum(c_new.astype(jnp.float32) ** 2))
        return f

    def fused(*a):
        return fused_lstm_cell(*a, interpret=interpret)

    argnums = tuple(range(len(args)))
    k_fwd = jax.jit(fused).lower(*shapes).compile()
    k_vg = jax.jit(jax.value_and_grad(
        loss(fused), argnums=argnums)).lower(*shapes).compile()
    r_fwd = jax.jit(reference_lstm_cell).lower(*shapes).compile()
    r_vg = jax.jit(jax.value_and_grad(
        loss(reference_lstm_cell), argnums=argnums)).lower(*shapes).compile()

    tol = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-5

    def check(name, a, b_, scale=1.0):
        err = np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b_, np.float32)))
        if not np.isfinite(err) or err > tol * scale:
            raise RuntimeError(
                f"fused LSTM cell value check failed ({name}): "
                f"max err {err:.3e} > {tol * scale}")

    for name, a, b_ in zip(("h", "c"), k_fwd(*args), r_fwd(*args)):
        check(name, a, b_)
    _, gk = k_vg(*args)
    _, gr = r_vg(*args)
    for idx, (a, b_) in enumerate(zip(gk, gr)):
        check(f"grad[{idx}]", a, b_, scale=8.0)


def cell_for(layer, dtype, batch: Optional[int] = None
             ) -> Optional["functools.partial"]:
    """The fused cell bound for ``layer`` (an LSTM/GravesLSTM instance)
    or None → reference step. Routes through the kernel registry:
    probe-once per (class, n_in, n_out, dtype, padded-batch), mode
    switch ``DL4J_TPU_FUSED_LSTM``, auto mode requires the TPU backend.
    Only tanh/sigmoid cells qualify — anything else is reference-path
    by construction."""
    if getattr(layer, "activation", None) != "tanh" or \
            getattr(layer, "gate_activation", None) != "sigmoid":
        return None
    n_in, n = layer.n_in, layer.n_out
    if not n_in or not n:
        return None
    # mro walk instead of isinstance: importing recurrent.py here would
    # be a cycle (recurrent routes its _step through this module)
    peephole = any(b.__name__ == "GravesLSTM" for b in type(layer).__mro__)
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    dtype = jnp.dtype(dtype)
    # key on the PADDED batch (sublane granularity): the probe must fail
    # where the real batch's VMEM working set would, not at a toy size
    B_p = _round_up(max(int(batch or 1), 1), _SUBLANE)
    key = (type(layer).__name__, int(n_in), int(n), dtype.name, B_p)
    interpret = default_kernel_registry().resolve(
        "fused_lstm", key,
        lambda interp: functools.partial(
            _probe_cell, int(n_in), int(n), dtype, peephole, interp,
            B=B_p))
    if interpret is None:
        return None
    return functools.partial(fused_lstm_cell, interpret=interpret)

"""The absorbed latent decode core as one Pallas TPU kernel: a decode
step's attention over a latent cache that reads only the LIVE columns of
each slot's slab, and each of them once.

``models/decoder_lm._latent_attention`` scores a step's queries, taken
into the latent space, against the cached entries and sums the entries
under the softmax weights. As two einsums over the slab that reads every
column of every slot twice, whatever the slots hold, and writes the
float32 scores between them. Here, per slot, the slab ``(width, Tc)``
(T-minor, as it lies: no transpose, no slice copied) is walked in column
tiles only up to the slot's length, and a tile serves both products
under one running softmax:

- a flat grid over the LIVE tiles alone, slot after slot: which slot and
  which of its tiles a grid step takes is a table made from the slots'
  lengths outside the kernel and handed over, with the lengths and the
  layer's index, by scalar prefetch, where the index maps read it; the
  grid's bound is the number of live tiles (a dynamic bound), so a dead
  tile costs neither a DMA nor a grid step, and a slot of length 0 is
  not visited at all;
- the step's own entry (``new``) opens the running softmax at a slot's
  first tile: m = its score, z = 1, acc = the entry. A slot of length 0
  gets its own latent outside the kernel, as the einsum path gives it;
- per tile: scores ``(heads, width) x (width, tile)`` in float32 times
  ``scale``; the weights go into the second product in the slab's dtype,
  ``acc += e (heads, tile) x tile^T`` contracting the minor dimension of
  both (no transposed copy); in a slot's last, partly live tile the
  columns at and past the length are masked out of the scores AND zeroed
  in the tile, so nothing past the length reaches the result;
- output ``acc[:, :kv_rank] / z`` in the slab's dtype: what the einsum
  path calls ``lat``. Same arithmetic, same precisions.

Availability via ``nn.ops.registry`` (``DL4J_TPU_LATENT_DECODE_CORE`` =
0 | 1 | interpret), keyed by ``(heads, width, Tc, tile, dtype)``.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.ops.kernel_compat import PRECISION as _PREC

NAME = "latent_decode_core"
#: columns a tile, chosen on the chip among 512, 1,024 and 2,048 (PERF.md,
#: PR 33): a 576 x 2048 bfloat16 tile is 2.4 MB, so two in flight and the
#: float32 scores and weights of 128 heads (1 MB each) fit the default
#: VMEM budget
TILE = 2048
_NEG = -1e30
_TRANS_B = (((1,), (1,)), ((), ()))   # x (m, k) . y (n, k) -> (m, n)


def _kernel(len_ref, slot_ref, tile_ref, layer_ref, q_ref, new_ref, slab_ref,
            o_ref, m_ref, z_ref, acc_ref, *, scale: float, tile: int,
            kv_rank: int, precision):
    del layer_ref  # the slab's index map's own
    i = pl.program_id(0)
    s, t = slot_ref[i], tile_ref[i]
    length = len_ref[s]
    f32 = jnp.float32

    @pl.when(t == 0)
    def _open():
        own = new_ref[...].astype(f32)                       # (1, width)
        m_ref[...] = jnp.sum(q_ref[...].astype(f32) * own, axis=-1,
                             keepdims=True) * scale
        z_ref[...] = jnp.ones_like(z_ref)
        acc_ref[...] = jnp.broadcast_to(own, acc_ref.shape)

    def _fold(kv, live):
        sc = jax.lax.dot_general(
            q_ref[...], kv, (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=precision) * scale
        if live is not None:
            sc = jnp.where(live, sc, _NEG)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, sc.max(-1, keepdims=True))
        keep = jnp.exp(m_old - m_new)
        e = jnp.exp(sc - m_new)
        z_ref[...] = z_ref[...] * keep + e.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * keep + jax.lax.dot_general(
            e.astype(kv.dtype), kv, _TRANS_B,
            preferred_element_type=f32, precision=precision)
        m_ref[...] = m_new

    @pl.when((t + 1) * tile <= length)
    def _whole_tile():
        _fold(slab_ref[...], None)

    @pl.when((t * tile < length) & (length < (t + 1) * tile))
    def _last_tile():
        col = t * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        live = col < length
        kv = slab_ref[...]
        _fold(jnp.where(live, kv, jnp.zeros_like(kv)), live)

    @pl.when((t + 1) * tile >= length)
    def _close():  # the slot's last live tile: its block goes out
        o_ref[...] = (acc_ref[:, :kv_rank] / z_ref[...]).astype(o_ref.dtype)


def _precision(dtype):
    """One MXU pass is exact for bfloat16 operands (and Mosaic refuses
    more: ``kernel_compat``); float32 operands keep the package's
    "highest"."""
    return _PREC if jnp.dtype(dtype) == jnp.bfloat16 else jax.lax.Precision.HIGHEST


def latent_decode_core(q_lat, new, slab, layer, lengths, *, scale: float,
                       kv_rank: int, tile: int = TILE,
                       interpret: bool = False):
    """q_lat (slots, heads, width): the step's queries in the latent
    space; new (slots, width): the step's own cache entries; slab
    (layers, slots, width, Tc): a segment's latent cache, of which layer
    ``layer`` () is read, slot s in its first ``lengths[s]`` (slots,)
    columns. Returns (slots, heads, kv_rank): the softmax-weighted sum of
    the live entries' latents and the own one, in the slab's dtype."""
    n_slots, heads, width = q_lat.shape
    t_c = slab.shape[-1]
    if t_c % tile:
        raise ValueError(f"slot length {t_c} is not a multiple of the "
                         f"tile {tile}")
    lengths = jnp.minimum(lengths.astype(jnp.int32), t_c)
    # the walk: grid step i takes tile ``tile_of[i]`` of slot
    # ``slot_of[i]``, the live tiles of slot 0, then those of slot 1, ...;
    # entries past the number of live tiles are never reached
    tiles = (lengths + tile - 1) // tile
    ends = jnp.cumsum(tiles)
    step = jnp.arange(n_slots * (t_c // tile), dtype=jnp.int32)
    slot_of = jnp.minimum(
        jnp.searchsorted(ends, step, side="right", method="compare_all"),
        n_slots - 1).astype(jnp.int32)
    tile_of = step - (ends - tiles)[slot_of]
    # with nothing live anywhere one step still runs (slot_of[0] is then
    # the last slot, whose row, like every empty one, is replaced below)
    n_steps = jnp.maximum(ends[-1], 1)

    def row(i, len_ref, slot_ref, tile_ref, layer_ref):
        return (slot_ref[i], 0, 0)

    def slab_block(i, len_ref, slot_ref, tile_ref, layer_ref):
        return (layer_ref[0], slot_ref[i], 0, tile_ref[i])

    new = new.astype(slab.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, tile=tile,
                          kv_rank=kv_rank, precision=_precision(slab.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_steps,),
            in_specs=[
                pl.BlockSpec((None, heads, width), row),
                pl.BlockSpec((None, 1, width), row),
                pl.BlockSpec((None, None, width, tile), slab_block),
            ],
            out_specs=pl.BlockSpec((None, heads, kv_rank), row),
            scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, kv_rank), slab.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=NAME,
    )(lengths, slot_of, tile_of, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q_lat.astype(slab.dtype), new[:, None, :], slab)
    # a slot that holds nothing was not visited: its softmax has the own
    # entry alone
    own = jnp.broadcast_to(new[:, None, :kv_rank], out.shape)
    return jnp.where((lengths > 0)[:, None, None], out, own)


def latent_decode_reference(q_lat, new, slab, layer, lengths, *, scale: float,
                            kv_rank: int):
    """The same result by whole-slab einsums: the probe's oracle (the
    model's einsum path, for one query a slot and a cache described by
    its lengths)."""
    f32, dt = jnp.float32, slab.dtype
    kv = slab[layer]
    s_own = jnp.einsum("shc,sc->sh", q_lat, new,
                       preferred_element_type=f32)[..., None] * scale
    s_c = jnp.einsum("shc,sct->sht", q_lat, kv,
                     preferred_element_type=f32) * scale
    live = jnp.arange(kv.shape[-1])[None, :] < lengths[:, None]
    s_c = jnp.where(live[:, None], s_c, _NEG)
    m = jnp.maximum(s_own, s_c.max(-1, keepdims=True))
    e_own, e_c = jnp.exp(s_own - m), jnp.exp(s_c - m)
    lat = (e_own.astype(dt).astype(f32) * new[:, None].astype(f32)
           + jnp.einsum("sht,sct->shc", e_c.astype(dt), kv,
                        preferred_element_type=f32))
    return (lat[..., :kv_rank] / (e_own + e_c.sum(-1, keepdims=True))).astype(dt)


def _probe(heads: int, width: int, t_c: int, tile: int, dtype, kv_rank: int,
           interpret: bool) -> None:
    """Compile the kernel at the caller's widths (three slots: one that
    ends inside its second tile, one empty, one short) and hold it to the
    einsums."""
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    # numpy arguments: a probe may run under an ambient trace
    q = np.asarray(rng.standard_normal((3, heads, width)), np.float32).astype(dt)
    new = np.asarray(rng.standard_normal((3, width)), np.float32).astype(dt)
    slab = np.asarray(rng.standard_normal((1, 3, width, t_c)),
                      np.float32).astype(dt)
    lengths = np.asarray([min(tile + 3, t_c), 0, min(5, t_c)], np.int32)
    layer = np.zeros((), np.int32)
    scale = 1.0 / float(np.sqrt(width))
    args = (q, new, slab, layer, lengths)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    got = jax.jit(functools.partial(
        latent_decode_core, scale=scale, kv_rank=kv_rank, tile=tile,
        interpret=interpret)).lower(*shapes).compile()(*args)
    want = jax.jit(functools.partial(
        latent_decode_reference, scale=scale,
        kv_rank=kv_rank)).lower(*shapes).compile()(*args)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    if not np.isfinite(err) or err > tol:
        raise RuntimeError(f"latent decode kernel vs einsums: rel {err:.3e} "
                           f"> {tol}")


def latent_decode_impl(heads: int, width: int, t_c: int, dtype,
                       kv_rank: int):
    """:func:`latent_decode_core` with its tile, rank and ``interpret``
    flag bound, where the registry admits this instantiation; None for the
    einsum path (kill switch, no TPU, a slot length the tile does not
    divide, a refused probe: each recorded as a fallback)."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    tile = min(TILE, int(t_c))
    dtype = jnp.dtype(dtype)
    key = (int(heads), int(width), int(t_c), tile, dtype.name)
    reg = default_kernel_registry()
    if t_c % tile:
        reg.disable(NAME, key, f"slot length {t_c} is not a multiple of "
                               f"the tile {tile}")
        return None
    interpret = reg.resolve(NAME, key, lambda interp: functools.partial(
        _probe, int(heads), int(width), int(t_c), tile, dtype, int(kv_rank),
        interp))
    if interpret is None:
        return None
    return functools.partial(latent_decode_core, kv_rank=int(kv_rank),
                             tile=tile, interpret=interpret)

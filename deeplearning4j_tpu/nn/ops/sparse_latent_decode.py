"""A latent layer's decode-step attention over a SELECTION of cached
positions as one Pallas TPU kernel: the live slots' rows are read once,
where they lie, and nothing passes through HBM between the two products.

``models/decoder_lm._sparse_latent_attention`` gathers the ``topk`` chosen
rows of every slot from the position-major slab ``(entries, slots, T, row)``
(XLA's row-by-row gather: 84 MB written and read back a layer at the glm
cell's shapes, at a tenth of HBM speed, for all slots whoever streams), then
scores them and sums them under the softmax as two einsums with the float32
scores between. Here, per LIVE slot, the slab is walked in tiles of rows up
to the slot's length and the selection comes as a MEMBERSHIP bias ``(slots,
1, T)`` float32, 0 at a selected position and ``-1e30`` elsewhere
(:func:`selection_bias`, made once by the layer that owns the selection):

- a STATIC flat grid over slots x tiles whose first steps take the live
  tiles, slot after slot (:func:`live_walk`, made once a step from the
  slots' lengths; the walk, the lengths, whether a slot's own entry is in
  its selection and the layer's index go in by scalar prefetch); the steps
  past the last live tile repeat its block, so a dead tile costs no DMA and
  no product, and an idle slot (length 0) is not visited at all;
- where ``own_in`` is true the step's own entry opens the running softmax
  at a slot's first tile (m = its score, z = 1, acc = the entry), else the
  softmax opens empty;
- per tile: scores ``(heads, row) x tile^T`` in float32 times ``scale`` plus
  the bias; the weights go into the second product in the slab's dtype,
  ``acc += e (heads, tile) x tile``; in a slot's last, partly live tile the
  rows at and past the length are zeroed in the tile (the bias keeps them
  out of the scores), so nothing past a length reaches the result;
- output ``acc[:, :kv_rank] / z`` in the slab's dtype: what the einsum path
  calls ``lat``. Same arithmetic and precisions as the gathered branch; only
  the order of the float32 sum differs. An idle slot's row of the output is
  its own latent.

Its bytes are the live contexts', not the selections': right where a
selection is a good part of a live context (the glm cell: 14-50 %), and
:func:`plan` declines where slots are much longer than a selection
(``T > MAX_SPAN x topk``), and under a mesh.

Availability via ``nn.ops.registry`` (``DL4J_TPU_SPARSE_LATENT_DECODE`` =
0 | 1 | interpret), keyed by ``(heads, row, T, topk, tile, dtype)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.ops.decode_attention import live_tiles
from deeplearning4j_tpu.nn.ops.latent_decode import _NEG, _TRANS_B, _precision

NAME = "sparse_latent_decode"
#: rows a tile, chosen on the chip among 512, 1,024 and 2,048 (PERF.md,
#: PR 49)
TILE = 2048
#: slot lengths, in selections, up to which streaming the live rows under a
#: mask is the form that was measured to win (PERF.md, PR 49: the glm cell
#: is at 7); past it the kernel declines
MAX_SPAN = 8


def selection_bias(scores, vals, idx, n_sel, lengths):
    """The selection ``idx[:, :n_sel]`` of ``decoder_lm._select_indices`` as
    the kernel's bias (slots, 1, T) float32: 0 at a selected position,
    ``-1e30`` elsewhere. ``scores`` (slots, T) as the sort saw them (-0 as
    +0), ``vals`` and ``idx`` (slots, k) the sort's values and columns,
    ``n_sel`` (slots,) how many of them count. The sort is stable and
    descending, so its first ``n_sel`` are the positions above the k-th
    value and, of those equal to it, the ones up to the column of the last
    that counts (where the own position took the k-th's place, one fewer):
    elementwise over the scores, no scatter and no running count. No
    position at or past a row's length is in."""
    kth = vals[:, -1:]
    last = jnp.maximum(n_sel - 1, 0)[:, None]
    tied = (jnp.take_along_axis(vals, last, axis=1) == kth) & (n_sel[:, None] > 0)
    edge = jnp.where(tied, jnp.take_along_axis(idx, last, axis=1), -1)
    col = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :]
    chosen = ((scores > kth) | ((scores == kth) & (col <= edge))) & (
        col < lengths[:, None])
    return jnp.where(chosen, 0.0, _NEG).astype(jnp.float32)[:, None, :]


def live_walk(lengths, t_c: int, tile: int):
    """The kernel's walk over the live tiles of a slab whose slots hold
    ``t_c`` rows, made ONCE a decode step (every layer of the step walks
    alike): lengths (slots,) int32, 0 for an idle slot -> (slot_of, tile_of
    (slots x tiles,), n_live (1,)) int32: ``decode_attention.live_tiles``'
    walk (the live tiles of slot 0, then those of slot 1, ...) for a STATIC
    grid: the steps from ``n_live`` on repeat the last live one (the block
    before is kept: no DMA). With nothing live anywhere ``n_live`` is 1 and
    the one step takes an idle slot, whose row is replaced outside."""
    _lengths, slot_of, tile_of, n_live = live_tiles(lengths, t_c, tile)
    step = jnp.minimum(jnp.arange(slot_of.shape[0], dtype=jnp.int32),
                       n_live[0] - 1)
    return slot_of[step], tile_of[step], n_live


def _kernel(len_ref, own_ref, slot_ref, tile_ref, live_ref, layer_ref,
            q_ref, new_ref, bias_ref, slab_ref, o_ref, m_ref, z_ref, acc_ref,
            *, scale: float, tile: int, kv_rank: int, precision):
    del layer_ref  # the slab's index map's own
    i = pl.program_id(0)
    s, t = slot_ref[i], tile_ref[i]
    length = len_ref[s]
    live = i < live_ref[0]
    f32 = jnp.float32

    @pl.when(live & (t == 0))
    def _open():
        own = new_ref[...].astype(f32)                       # (1, row)
        own_in = own_ref[s] > 0
        score = jnp.sum(q_ref[...].astype(f32) * own, axis=-1,
                        keepdims=True) * scale
        m_ref[...] = jnp.where(own_in, score, _NEG)
        z_ref[...] = jnp.where(own_in, jnp.ones_like(z_ref),
                               jnp.zeros_like(z_ref))
        acc_ref[...] = jnp.where(own_in, jnp.broadcast_to(own, acc_ref.shape),
                                 jnp.zeros_like(acc_ref))

    def _fold(rows):
        sc = jax.lax.dot_general(
            q_ref[...], rows, _TRANS_B,
            preferred_element_type=f32, precision=precision) * scale
        sc = sc + bias_ref[...]
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, sc.max(-1, keepdims=True))
        keep = jnp.exp(m_old - m_new)
        # a tile with nothing selected and nothing before it: no weight
        e = jnp.where(sc > 0.5 * _NEG, jnp.exp(sc - m_new), 0.0)
        z_ref[...] = z_ref[...] * keep + e.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * keep + jax.lax.dot_general(
            e.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=precision)
        m_ref[...] = m_new

    @pl.when(live & ((t + 1) * tile <= length))
    def _whole_tile():
        _fold(slab_ref[...])

    @pl.when(live & (t * tile < length) & (length < (t + 1) * tile))
    def _last_tile():
        row = t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        rows = slab_ref[...]
        _fold(jnp.where(row < length, rows, jnp.zeros_like(rows)))

    @pl.when(live & ((t + 1) * tile >= length))
    def _close():  # the slot's last live tile: its block goes out
        o_ref[...] = (acc_ref[:, :kv_rank] / z_ref[...]).astype(o_ref.dtype)


def sparse_latent_decode(q_lat, new, slab, layer, lengths, bias, own_in, walk,
                         *, scale: float, kv_rank: int, tile: int = TILE,
                         interpret: bool = False):
    """q_lat (slots, heads, row): the step's queries in the latent space;
    new (slots, row): the step's own cache entries; slab (entries, slots, T,
    row): a segment's latent cache, position-major, of which entry ``layer``
    () is read, slot s in its first ``lengths[s]`` (slots,) rows (0: an idle
    slot); bias (slots, 1, T) float32 (:func:`selection_bias`); own_in
    (slots,) bool: whether the own entry is in the slot's selection; walk:
    :func:`live_walk` of the same lengths and tile. Returns (slots, heads,
    kv_rank): the softmax-weighted sum of the selected entries' latents and,
    where it is in, the own one, in the slab's dtype. A live slot has a
    selected position or its own entry in."""
    n_slots, heads, width = q_lat.shape
    t_c = slab.shape[2]
    if t_c % tile:
        raise ValueError(f"slot length {t_c} is not a multiple of the "
                         f"tile {tile}")
    lengths = jnp.minimum(lengths.astype(jnp.int32), t_c)
    slot_of, tile_of, n_live = walk

    def row(i, len_ref, own_ref, slot_ref, tile_ref, live_ref, layer_ref):
        return (slot_ref[i], 0, 0)

    def bias_block(i, len_ref, own_ref, slot_ref, tile_ref, live_ref,
                   layer_ref):
        return (slot_ref[i], 0, tile_ref[i])

    def slab_block(i, len_ref, own_ref, slot_ref, tile_ref, live_ref,
                   layer_ref):
        return (layer_ref[0], slot_ref[i], tile_ref[i], 0)

    new = new.astype(slab.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, tile=tile, kv_rank=kv_rank,
                          precision=_precision(slab.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n_slots * (t_c // tile),),
            in_specs=[
                pl.BlockSpec((None, heads, width), row),
                pl.BlockSpec((None, 1, width), row),
                pl.BlockSpec((None, 1, tile), bias_block),
                pl.BlockSpec((None, None, tile, width), slab_block),
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
    )(lengths, own_in.astype(jnp.int32), slot_of, tile_of, n_live,
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      q_lat.astype(slab.dtype), new[:, None, :], bias, slab)
    # an idle slot was not visited: its softmax has the own entry alone
    own = jnp.broadcast_to(new[:, None, :kv_rank], out.shape)
    return jnp.where((lengths > 0)[:, None, None], out, own)


def sparse_latent_decode_reference(q_lat, new, slab, layer, lengths, bias,
                                   own_in, *, scale: float, kv_rank: int):
    """The same result by whole-slab einsums under the bias: the probe's
    oracle (a live slot: the softmax over its selected rows and, where it
    is in, the own entry; an idle one: its own latent)."""
    f32, dt = jnp.float32, slab.dtype
    t_c = slab.shape[2]
    lengths = jnp.minimum(lengths.astype(jnp.int32), t_c)
    held = jnp.arange(t_c)[None, :, None] < lengths[:, None, None]
    rows = jnp.where(held, slab[layer], jnp.zeros((), dt))
    s_own = jnp.einsum("shc,sc->sh", q_lat, new,
                       preferred_element_type=f32)[..., None] * scale
    s_own = jnp.where(own_in[:, None, None], s_own, _NEG)
    s_c = jnp.einsum("shc,stc->sht", q_lat, rows,
                     preferred_element_type=f32) * scale + bias
    m = jnp.maximum(s_own, s_c.max(-1, keepdims=True))
    e_own = jnp.where(own_in[:, None, None], jnp.exp(s_own - m), 0.0)
    e_c = jnp.where(s_c > 0.5 * _NEG, jnp.exp(s_c - m), 0.0)
    lat = (e_own * new[:, None].astype(f32)
           + jnp.einsum("sht,stc->shc", e_c.astype(dt), rows,
                        preferred_element_type=f32))
    lat = (lat[..., :kv_rank] / (e_own + e_c.sum(-1, keepdims=True))).astype(dt)
    own = jnp.broadcast_to(new[:, None, :kv_rank].astype(dt), lat.shape)
    return jnp.where((lengths > 0)[:, None, None], lat, own)


def _probe(heads: int, width: int, t_c: int, tile: int, dtype, kv_rank: int,
           interpret: bool) -> None:
    """Compile the kernel at the caller's widths and slot length (four slots:
    one that ends inside its second tile with its own entry out, one idle
    with NaN in its rows, one short, one whole) and hold it to the einsums.
    Draws one slot's rows and rolls them a slot: a slab of a cell's slots
    drawn on the host costs seconds of set-up."""
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    # numpy arguments: a probe may run under an ambient trace
    q = rng.standard_normal((4, heads, width), np.float32).astype(dt)
    new = rng.standard_normal((4, width), np.float32).astype(dt)
    one = rng.standard_normal((t_c, width), np.float32).astype(dt)
    slab = np.stack([np.roll(one, s, axis=0) for s in range(4)])[None]
    slab[0, 1] = np.nan
    lengths = np.asarray([min(tile + 3, t_c), 0, min(5, t_c), t_c], np.int32)
    own_in = np.asarray([False, True, True, True])
    chosen = rng.random((4, t_c)) < 0.3
    chosen[:, 0] = True
    chosen &= np.arange(t_c)[None, :] < lengths[:, None]
    bias = np.where(chosen, 0.0, _NEG).astype(np.float32)[:, None, :]
    layer = np.zeros((), np.int32)
    scale = 1.0 / float(np.sqrt(width))
    args = (q, new, slab, layer, lengths, bias, own_in)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]

    def kernel(q, new, slab, layer, lengths, bias, own_in):
        return sparse_latent_decode(
            q, new, slab, layer, lengths, bias, own_in,
            live_walk(lengths, t_c, tile), scale=scale, kv_rank=kv_rank,
            tile=tile, interpret=interpret)

    got = jax.jit(kernel).lower(*shapes).compile()(*args)
    want = jax.jit(functools.partial(
        sparse_latent_decode_reference, scale=scale,
        kv_rank=kv_rank)).lower(*shapes).compile()(*args)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    if not np.isfinite(err) or err > tol:
        raise RuntimeError(f"sparse latent decode kernel vs einsums: rel "
                           f"{err:.3e} > {tol}")


def plan(t_c: int, topk: int):
    """The tile where the kernel has a form for these shapes and a gain;
    None, unrecorded, where it has not: a slot length the tile does not
    divide, slots of more than ``MAX_SPAN`` selections (the stream's bytes
    grow with the context where the selection's do not: ruled on at the
    glm cell's key, PERF.md, PR 49), an ambient mesh (a Mosaic call is not
    partitioned)."""
    tile = min(TILE, int(t_c))
    ambient = jax.sharding.get_abstract_mesh()
    if (t_c % tile or t_c > MAX_SPAN * topk
            or any(size > 1 for size in ambient.shape.values())):
        return None
    return tile


def sparse_latent_decode_impl(heads: int, width: int, t_c: int, topk: int,
                              dtype, kv_rank: int):
    """(:func:`sparse_latent_decode` with its tile, rank and ``interpret``
    flag bound, the tile) where :func:`plan` has a form for the shapes and
    the registry admits this instantiation; None for the gathered branch
    (the registry's part recorded as a fallback: kill switch, no TPU, a
    refused probe)."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    tile = plan(t_c, topk)
    if tile is None:
        return None
    dtype = jnp.dtype(dtype)
    key = (int(heads), int(width), int(t_c), int(topk), tile, dtype.name)
    interpret = default_kernel_registry().resolve(
        NAME, key, lambda interp: functools.partial(
            _probe, int(heads), int(width), int(t_c), tile, dtype,
            int(kv_rank), interp))
    if interpret is None:
        return None
    return functools.partial(sparse_latent_decode, kv_rank=int(kv_rank),
                             tile=tile, interpret=interpret), tile

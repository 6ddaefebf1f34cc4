"""The cached attention core of a one-token decode step as one Pallas TPU
kernel: a layer's queries against the K and V slabs where they lie, reading
only the LIVE column tiles of each slot, and each of them once.

``models/transformer_lm._attend_cached`` and the cached branch of
``models/decoder_lm.block`` score a step's queries against a layer's whole
K slab and sum its whole V slab under the softmax weights: two einsums that
read every column of every slot, whatever the slots hold, and write the
float32 scores between them. Here, per slot, K ``(hkv, hd, T)`` and V
``(hkv, vd, T)`` (T minor, as they lie: no transpose, no slice copied) are
walked in column tiles only up to the slot's length, under one running
softmax, as ``latent_decode`` walks a latent slab:

- the slabs come WHOLE, ``(layers, slots, hkv, hd | vd, T)``, with the
  layer's index by scalar prefetch where the index maps read it (a custom
  call's operand is made whole: a scan's slice of a slab would be copied a
  layer);
- a flat grid over the LIVE tiles alone, slot after slot: which slot and
  which of its tiles a grid step takes is a table made from the slots'
  lengths (:func:`live_tiles`) ONCE a step, outside the layer loop, and
  handed to every layer's call; the grid's bound is the number of live
  tiles (a dynamic bound), so a dead tile costs neither a DMA nor a grid
  step and a slot of length 0 (an idle one) is not visited at all;
- all heads of a slot go through ONE product a tile: the queries arrive as
  a block-diagonal matrix ``(grp x hkv, hkv x hd)`` (row ``g x hkv + k``
  holds query head ``k x grp + g`` in the columns of key head ``k``, zeros
  elsewhere: exact), so ``scores = Q (hkv x hd, tile)`` and ``acc += e
  (hkv x vd, tile)^T`` are two matrix products whose cost is loading the
  tile, whatever the group; of ``acc (grp x hkv, hkv x vd)`` only the
  diagonal blocks mean anything, and the close takes them;
- the step's own key and value open the running softmax at a slot's first
  tile: m = its score, z = 1, acc = its value, so a slot at position 0 is
  finite (it is not visited, and gets its own value outside the kernel, as
  ``_joint_softmax`` gives it);
- scores in float32 times ``scale``; the weights go into the second
  product in the slab's dtype, float32 accumulation, one division at the
  close; in a slot's last, partly live tile the columns at and past the
  length are masked out of the scores AND zeroed in the value tile.

Availability via ``nn.ops.registry`` (``DL4J_TPU_DECODE_ATTENTION`` = 0 | 1
| interpret), keyed by ``(hkv, grp, hd, vd, T, tile, dtype)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.ops.kernel_compat import mesh_in_sight
from deeplearning4j_tpu.nn.ops.latent_decode import _TRANS_B, _precision

NAME = "decode_attention"
#: columns a tile, chosen on the chip among 128, 256, 512 and 1,024 on the
#: chat shape with half its slots live (PERF.md, PR 44: 1.65 / 1.82 / 2.30 /
#: 3.43 ms; the dead part of a slot's last tile costs more than the steps
#: do); a slot length it does not divide has no tile
TILE = 128
#: bytes of one layer's K + V slab under which the einsums stay: the
#: smallest slab timed on the chip, the ouro cell's 36.7 MB a call, still
#: wins (4.53 against 10.21 ms over 192 calls, 4 of 5 slots live: PERF.md,
#: PR 44); under it nothing was timed
MIN_SLAB_BYTES = 32 << 20
_NEG = -1e30


def live_tiles(lengths, t: int, tile: int):
    """lengths (slots,) -> the kernel's walk: (the lengths clamped to ``t``,
    slot_of, tile_of (slots x t // tile,) int32: grid step i takes tile
    ``tile_of[i]`` of slot ``slot_of[i]``, the live tiles of slot 0, then
    those of slot 1, ...; how many steps there are (1,), at least one).
    Entries past that are never reached. One table serves every layer of
    a step."""
    n_slots = lengths.shape[0]
    lengths = jnp.minimum(lengths.astype(jnp.int32), t)
    tiles = (lengths + tile - 1) // tile
    ends = jnp.cumsum(tiles)
    step = jnp.arange(n_slots * (t // tile), dtype=jnp.int32)
    slot_of = jnp.minimum(
        jnp.searchsorted(ends, step, side="right", method="compare_all"),
        n_slots - 1).astype(jnp.int32)
    tile_of = step - (ends - tiles)[slot_of]
    # with nothing live anywhere one step still runs (slot_of[0] is then
    # the last slot, whose row, like every empty one, is replaced outside)
    return lengths, slot_of, tile_of, jnp.maximum(ends[-1:], 1)


def _kernel(len_ref, slot_ref, tile_ref, steps_ref, layer_ref, q_ref, kn_ref,
            vn_ref, k_ref, v_ref, o_ref, m_ref, z_ref, acc_ref, *,
            scale: float, tile: int, hkv: int, grp: int, precision):
    del steps_ref, layer_ref  # the grid's and the index maps' own
    i = pl.program_id(0)
    s, t = slot_ref[i], tile_ref[i]
    length = len_ref[s]
    vd = v_ref.shape[1]
    f32 = jnp.float32

    @pl.when(t == 0)
    def _open():
        m_ref[...] = jnp.sum(q_ref[...].astype(f32) * kn_ref[...].astype(f32),
                             axis=-1, keepdims=True) * scale
        z_ref[...] = jnp.ones_like(z_ref)
        acc_ref[...] = jnp.broadcast_to(vn_ref[...].astype(f32), acc_ref.shape)

    def _fold(live):
        k = k_ref[...].reshape(-1, tile)              # (hkv x hd, tile)
        v = v_ref[...].reshape(-1, tile)              # (hkv x vd, tile)
        sc = jax.lax.dot_general(
            q_ref[...], k, (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=precision) * scale
        if live is not None:
            sc = jnp.where(live, sc, _NEG)
            v = jnp.where(live, v, jnp.zeros_like(v))
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, sc.max(-1, keepdims=True))
        keep = jnp.exp(m_old - m_new)
        e = jnp.exp(sc - m_new)
        z_ref[...] = z_ref[...] * keep + e.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * keep + jax.lax.dot_general(
            e.astype(v.dtype), v, _TRANS_B,
            preferred_element_type=f32, precision=precision)
        m_ref[...] = m_new

    @pl.when((t + 1) * tile <= length)
    def _whole_tile():
        _fold(None)

    @pl.when((t * tile < length) & (length < (t + 1) * tile))
    def _last_tile():
        col = t * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        _fold(col < length)

    @pl.when((t + 1) * tile >= length)
    def _close():  # the slot's last live tile: its diagonal blocks go out
        row = jax.lax.broadcasted_iota(jnp.int32, (hkv, hkv * vd), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (hkv, hkv * vd), 1)
        own = (col >= row * vd) & (col < (row + 1) * vd)
        for g in range(grp):
            rows = pl.ds(g * hkv, hkv)
            o_ref[g:g + 1, :] = jnp.sum(
                jnp.where(own, acc_ref[rows, :] / z_ref[rows, :], 0.0),
                axis=0, keepdims=True)


def decode_attention(q, k_new, v_new, k_slab, v_slab, layer, table, *,
                     scale: float, tile: int, interpret: bool = False):
    """q (slots, hkv, grp, hd): the step's queries, query head ``k x grp +
    g`` reading key/value head k; k_new (slots, hkv, hd), v_new (slots,
    hkv, vd): the step's own key and value; k_slab (layers, slots, hkv, hd,
    T) and v_slab (layers, slots, hkv, vd, T): a stack's caches, of which
    layer ``layer`` () is read, slot s in its first ``lengths[s]`` columns;
    ``table`` = :func:`live_tiles` (lengths, T, tile). Returns (slots, hkv,
    grp, vd) float32: the softmax-weighted sum of the live columns' values
    and the own one."""
    n_slots, hkv, grp, hd = q.shape
    vd, t = v_slab.shape[3], k_slab.shape[4]
    if t % tile:
        raise ValueError(f"slot length {t} is not a multiple of the tile "
                         f"{tile}")
    dt = k_slab.dtype
    heads = grp * hkv
    rows = -(-heads // 8) * 8          # whole sublane tiles; the rest zeros
    # block-diagonal queries: row g x hkv + k, the columns of key head k
    same = jnp.eye(hkv, dtype=bool)[None, None, :, :, None]
    q_bd = jnp.where(same, q.astype(dt).transpose(0, 2, 1, 3)[:, :, :, None],
                     jnp.zeros((), dt)).reshape(n_slots, heads, hkv * hd)
    q_bd = jnp.pad(q_bd, ((0, 0), (0, rows - heads), (0, 0)))
    k_new = k_new.astype(dt).reshape(n_slots, 1, hkv * hd)
    v_new = v_new.astype(dt)
    lengths, slot_of, tile_of, n_steps = table

    def row(i, len_ref, slot_ref, tile_ref, steps_ref, layer_ref):
        return (slot_ref[i], 0, 0)

    def block(i, len_ref, slot_ref, tile_ref, steps_ref, layer_ref):
        return (layer_ref[0], slot_ref[i], 0, 0, tile_ref[i])

    block_bytes = hkv * (hd + vd) * tile * dt.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, tile=tile, hkv=hkv, grp=grp,
                          precision=_precision(dt)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_steps[0],),
            in_specs=[
                pl.BlockSpec((None, rows, hkv * hd), row),
                pl.BlockSpec((None, 1, hkv * hd), row),
                pl.BlockSpec((None, 1, hkv * vd), row),
                pl.BlockSpec((None, None, hkv, hd, tile), block),
                pl.BlockSpec((None, None, hkv, vd, tile), block),
            ],
            out_specs=pl.BlockSpec((None, grp, hkv * vd), row),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, hkv * vd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_slots, grp, hkv * vd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a K and a V tile, each double-buffered, and the float32
            # scores, weights and accumulator beside them
            vmem_limit_bytes=2 * block_bytes + (16 << 20)),
        interpret=interpret,
        name=NAME,
    )(lengths, slot_of, tile_of, n_steps,
      jnp.reshape(layer, (1,)).astype(jnp.int32), q_bd, k_new,
      v_new.reshape(n_slots, 1, hkv * vd), k_slab, v_slab)
    out = out.reshape(n_slots, grp, hkv, vd).transpose(0, 2, 1, 3)
    # a slot that holds nothing was not visited: its softmax has the own
    # entry alone
    own = jnp.broadcast_to(v_new.astype(jnp.float32)[:, :, None, :], out.shape)
    return jnp.where((lengths > 0)[:, None, None, None], out, own)


def decode_attention_reference(q, k_new, v_new, k_slab, v_slab, layer,
                               lengths, *, scale: float):
    """The same result by whole-slab einsums: the probe's oracle (the
    models' einsum paths, for one query a slot and a cache described by its
    lengths)."""
    f32, dt = jnp.float32, k_slab.dtype
    kc, vc = k_slab[layer], v_slab[layer]
    q, k_new, v_new = q.astype(dt), k_new.astype(dt), v_new.astype(dt)
    s_own = jnp.einsum("skgd,skd->skg", q, k_new,
                       preferred_element_type=f32)[..., None] * scale
    s_c = jnp.einsum("skgd,skdt->skgt", q, kc,
                     preferred_element_type=f32) * scale
    live = jnp.arange(kc.shape[-1])[None, :] < lengths[:, None]
    s_c = jnp.where(live[:, None, None], s_c, _NEG)
    m = jnp.maximum(s_own, s_c.max(-1, keepdims=True))
    e_own, e_c = jnp.exp(s_own - m), jnp.exp(s_c - m)
    o = (e_own.astype(dt).astype(f32) * v_new[:, :, None].astype(f32)
         + jnp.einsum("skgt,skdt->skgd", e_c.astype(dt), vc,
                      preferred_element_type=f32))
    return o / (e_own + e_c.sum(-1, keepdims=True))


def _probe(hkv: int, grp: int, hd: int, vd: int, t: int, tile: int, dtype,
           interpret: bool) -> None:
    """Compile the kernel at the caller's widths and tile (two layers of
    four slots of at most two tiles: one that ends inside its second tile,
    one empty, one short, one full) and hold it to the einsums."""
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    t = min(t, 2 * tile)   # the kernel's body does not know the slot length

    def draw(*shape):  # numpy arguments: a probe may run under a trace
        return rng.standard_normal(shape, np.float32).astype(dt)

    q, k_new, v_new = draw(4, hkv, grp, hd), draw(4, hkv, hd), draw(4, hkv, vd)
    k_slab, v_slab = draw(2, 4, hkv, hd, t), draw(2, 4, hkv, vd, t)
    lengths = np.asarray([min(tile + 3, t), 0, min(5, t), t], np.int32)
    layer = np.ones((), np.int32)
    scale = 1.0 / float(np.sqrt(hd))
    args = (q, k_new, v_new, k_slab, v_slab, layer, lengths)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]

    def kernel(q, k_new, v_new, k_slab, v_slab, layer, lengths):
        return decode_attention(q, k_new, v_new, k_slab, v_slab, layer,
                                live_tiles(lengths, t, tile), scale=scale,
                                tile=tile, interpret=interpret)

    got = jax.jit(kernel).lower(*shapes).compile()(*args)
    want = jax.jit(functools.partial(
        decode_attention_reference,
        scale=scale)).lower(*shapes).compile()(*args)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    if not np.isfinite(err) or err > tol:
        raise RuntimeError(f"decode attention kernel vs einsums: rel "
                           f"{err:.3e} > {tol}")


def tile_for(t: int) -> int:
    """Columns a tile for slots of ``t`` columns: ``TILE`` (one tile of
    lanes) where it divides ``t``, else 0."""
    return 0 if t % TILE else TILE


def decode_attention_impl(slots: int, hkv: int, grp: int, hd: int, vd: int,
                          t: int, dtype):
    """(:func:`decode_attention` with its tile and ``interpret`` flag
    bound, the tile) where the registry admits this instantiation; None
    for the einsum path: kill switch, no TPU, a refused probe (each
    recorded as a fallback), and, unrecorded because the kernel has no
    form for them or no gain, a slot length the tile does not divide, a
    slab under a mesh (``kernel_compat.mesh_in_sight``) and a layer's K + V
    slab under ``MIN_SLAB_BYTES``."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    dtype = jnp.dtype(dtype)
    tile = tile_for(int(t))
    if (not tile or mesh_in_sight()
            or slots * hkv * (hd + vd) * t * dtype.itemsize < MIN_SLAB_BYTES):
        return None
    key = (int(hkv), int(grp), int(hd), int(vd), int(t), tile, dtype.name)
    interpret = default_kernel_registry().resolve(
        NAME, key, lambda interp: functools.partial(
            _probe, int(hkv), int(grp), int(hd), int(vd), int(t), tile, dtype,
            interp))
    if interpret is None:
        return None
    return functools.partial(decode_attention, tile=tile,
                             interpret=interpret), tile

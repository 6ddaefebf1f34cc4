"""The after-loop column write of a time-minor cache slab as one Pallas TPU
kernel: a decode step reads and rewrites only the 128-column blocks that
hold the LIVE slots' positions.

``models/transformer_lm._put_columns`` writes one new key (or value)
column a slot into the donated slab ``(entries, slots, heads, head size,
T)``, time minor, as one ``dynamic_update_slice`` a slot. A one-column
update touches one lane of every tile along the head size, so XLA reads
and rewrites whole tiles, at a quarter of HBM speed, for every slot, live
or idle. Here the same tiles move once, as large blocks:

- the slab comes WHOLE and is aliased to the output, so a block no grid
  step visits (another column block, an idle slot) is bit for bit what it
  was;
- a flat grid over (slot, block of entries) pairs whose first steps are
  the live slots': which slot a grid step takes is the table of the live
  slots (``ssm_decode.live_table``), handed over by scalar prefetch with
  the clamped write positions. The steps past the live slots' stay on the
  last live block and do nothing, so an idle slot costs no DMA, only its
  empty steps (~0.2 us each on the chip). The grid's bound is static: with
  a dynamic bound (live slots x blocks a slot, as ``ssm_decode`` has it)
  the SECOND executable that held this kernel at a probe's small shapes
  halted the core on the chip (PERF.md, PR 43);
- a grid step takes the block ``(entries a block, 1, heads, head size,
  128)`` at column block ``wp[s] // 128``, sets lane ``wp[s] % 128`` of
  every (entry, head) tile to the new column by a select over a lane iota,
  and writes the block back. The new columns arrive turned outside the
  kernel, the head size on the sublanes as the slab has it and an
  (entry, head) pair a lane, so a tile's column is one lane spread over the
  tile's lanes.

Availability via ``nn.ops.registry`` (``DL4J_TPU_KV_COLUMN_WRITE`` = 0 | 1
| interpret), keyed by ``(entries, slots, heads, head size, T, dtype)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.ops.kernel_compat import mesh_in_sight

NAME = "kv_column_write"
#: columns of time a block spans: one tile of lanes
LANES = 128
#: bytes of the slab a grid step takes (entries a block x heads x head
#: size x 128 columns): read and written, each double-buffered; chosen on
#: the chip (PERF.md, PR 43)
BLOCK_BYTES = 2 << 20


def entries_a_block(entries: int, heads: int, head_size: int,
                    itemsize: int) -> int:
    """Entries (layers, or (pass, layer) pairs) a grid step takes: the most
    that keep the block within ``BLOCK_BYTES`` and its (entry, head) pairs
    within one tile of lanes, evened out over the blocks of a slot."""
    fit = min(BLOCK_BYTES // (heads * head_size * LANES * itemsize),
              LANES // heads)
    fit = max(1, min(int(entries), fit))
    return -(-entries // -(-entries // fit))


def _kernel(slot_ref, live_ref, wp_ref, steps_ref, c_ref, k_ref, o_ref, *,
            blocks: int):
    lb, heads, head_size, lanes = k_ref.shape
    i = pl.program_id(0)
    steps = steps_ref[0]
    lane = wp_ref[slot_ref[jnp.minimum(i, steps - 1) // blocks]] % lanes

    @pl.when((i < steps) & (live_ref[0] > 0))
    def _step():
        here = jax.lax.broadcasted_iota(
            jnp.int32, (head_size, lanes), 1) == lane
        cols = c_ref[...]                        # (head size, lb x heads)
        for l in range(lb):
            for h in range(heads):
                j = l * heads + h
                col = jnp.broadcast_to(cols[:, j:j + 1], (head_size, lanes))
                o_ref[l, h] = jnp.where(here, col, k_ref[l, h])

    @pl.when((i < steps) & (live_ref[0] == 0))
    def _nothing_live():  # the one slot's grid steps of an idle batch
        o_ref[...] = k_ref[...]


def kv_column_write(slab, new, wp, table, *, lb: int,
                    interpret: bool = False):
    """slab (entries, slots, heads, head size, T), T a multiple of 128; new
    (entries, slots, heads, head size): slot s's column -> slab[:, s, :, :,
    wp[s]] in the slots ``table`` = ``live_table(active)`` names, ``wp``
    (slots,) within 0..T-1; ``lb`` entries a block. Returns the slab,
    aliased: every other bit as it was (the idle slots' too)."""
    entries, n_slots, heads, head_size, t = slab.shape
    if t % LANES:
        raise ValueError(f"{t} columns are no whole blocks of {LANES}")
    blocks = -(-entries // lb)
    slot_of, n_live, _live = table
    # the head size to the sublanes, a block's (entry, head) pairs to the
    # lanes: (slots, blocks, head size, lb x heads)
    cols = jnp.pad(new.astype(slab.dtype),
                   ((0, blocks * lb - entries), (0, 0), (0, 0), (0, 0)))
    cols = cols.reshape(blocks, lb, n_slots, heads, head_size).transpose(
        2, 0, 4, 1, 3).reshape(n_slots, blocks, head_size, lb * heads)
    width = lb * heads
    # the grid steps that do something: the live slots' blocks; with
    # nothing live one slot's steps still run, and copy
    steps = (jnp.maximum(n_live, 1) * blocks).astype(jnp.int32)

    def columns(i, slot_ref, live_ref, wp_ref, steps_ref):
        i = jnp.minimum(i, steps_ref[0] - 1)
        return (slot_ref[i // blocks], i % blocks, 0, 0)

    def block(i, slot_ref, live_ref, wp_ref, steps_ref):
        i = jnp.minimum(i, steps_ref[0] - 1)
        s = slot_ref[i // blocks]
        return (i % blocks, s, 0, 0, wp_ref[s] // LANES)

    at = (lb, None, heads, head_size, LANES)
    block_bytes = lb * heads * head_size * LANES * slab.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_slots * blocks,),
            in_specs=[
                pl.BlockSpec((None, None, head_size, width), columns),
                pl.BlockSpec(at, block),
            ],
            out_specs=pl.BlockSpec(at, block)),
        out_shape=jax.ShapeDtypeStruct(slab.shape, slab.dtype),
        input_output_aliases={5: 0},   # the slab, after four tables + cols
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the block read and written, each double-buffered
            vmem_limit_bytes=4 * block_bytes + (8 << 20)),
        interpret=interpret,
        name=NAME,
    )(slot_of, n_live, wp.astype(jnp.int32), steps, cols, slab)


def kv_column_reference(slab, new, wp, table):
    """The same by one ``dynamic_update_slice`` a slot, an idle slot's
    column put back as it was: the probe's oracle."""
    live = table[2]
    for s in range(new.shape[1]):
        at = (0, s, 0, 0, wp[s])
        old = jax.lax.dynamic_slice(slab, at, (*new.shape[:1], 1,
                                               *new.shape[2:], 1))
        col = jnp.where(live[s], new[:, s:s + 1, :, :, None], old)
        slab = jax.lax.dynamic_update_slice(slab, col, at)
    return slab


def _probe(heads: int, head_size: int, lb: int, ragged: bool, dtype,
           interpret: bool) -> None:
    """Compile the kernel at the caller's block (two blocks of entries a
    slot, the second cut short where the caller's is; three slots: live,
    idle, live; two column blocks) and hold it to the ``jnp`` form, bit for
    bit over the whole slab."""
    rng = np.random.default_rng(0)
    entries = 2 * lb - 1 if ragged and lb > 1 else 2 * lb
    # numpy arguments: a probe may run under an ambient trace
    slab = rng.standard_normal((entries, 3, heads, head_size, 2 * LANES),
                               np.float32).astype(dtype)
    new = rng.standard_normal((entries, 3, heads, head_size),
                              np.float32).astype(dtype)
    wp = np.asarray([2 * LANES - 1, 5, LANES], np.int32)
    table = (np.asarray([0, 2, 2], np.int32), np.asarray([2], np.int32),
             np.asarray([True, False, True]))
    args = (slab, new, wp, table)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
    got = jax.jit(functools.partial(
        kv_column_write, lb=lb,
        interpret=interpret)).lower(*shapes).compile()(*args)
    want = jax.jit(kv_column_reference).lower(*shapes).compile()(*args)
    # as bits: numpy compares a two-byte float an element at a time
    bits = np.dtype(f"uint{8 * dtype.itemsize}")
    got, want = (np.asarray(a).view(bits) for a in (got, want))
    if not np.array_equal(got, want):
        raise RuntimeError(
            f"kv column kernel vs dynamic_update_slice: "
            f"{int(np.sum(got != want))} of {got.size} values differ")


def kv_column_write_impl(entries: int, slots: int, heads: int,
                         head_size: int, t: int, dtype):
    """:func:`kv_column_write` with its block and ``interpret`` flag bound,
    where the registry admits this instantiation; None for the
    ``dynamic_update_slice`` loop: kill switch, no TPU, a refused probe
    (each recorded as a fallback), and, unrecorded because the kernel has
    no form for them, a slot length that is no whole blocks of 128 columns
    and a slab under a mesh (a Mosaic call cannot be partitioned
    automatically; multi-device callers make their mesh visible,
    ``jax.set_mesh``)."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    if t % LANES or mesh_in_sight():
        return None
    dtype = jnp.dtype(dtype)
    key = (int(entries), int(slots), int(heads), int(head_size), int(t),
           dtype.name)
    lb = entries_a_block(int(entries), int(heads), int(head_size),
                         dtype.itemsize)
    interpret = default_kernel_registry().resolve(
        NAME, key, lambda interp: functools.partial(
            _probe, int(heads), int(head_size), lb, bool(entries % lb),
            dtype, interp))
    if interpret is None:
        return None
    return functools.partial(kv_column_write, lb=lb, interpret=interpret)

"""The cached one-step recurrence of a state-space (Mamba-2) layer as one
Pallas TPU kernel: a decode step that touches the LIVE slots' state only,
and each block of it once.

``models/decoder_lm._ssm_step`` reads a layer's state over all slots once
for the readout ``h C`` and reads it again and writes it for ``decay h +
(dt x) (outer) B``, live and idle slots alike (the idle ones' update is
thrown away by a ``where``). Here a slot's state lies ``(state size, heads
x head size)`` float32, the state size major, and is walked in tiles of
columns; a tile serves the readout AND the update from one read and is
written back in place:

- the segment's states ``(layers, slots, state size, heads x head size)``
  come WHOLE, with the layer's index by scalar prefetch (a custom call's
  operand is made whole: a scan's slice of it would be copied a layer),
  and are aliased to the output, so a block no grid step visits (an idle
  slot, another layer) is bit for bit what it was;
- a flat grid over (live slot, column tile) pairs: which slot a grid step
  takes is a table of the live slots (:func:`live_table`) made from
  ``active`` outside the kernel and handed over by scalar prefetch; the
  grid's bound is the number of live slots x the tiles a slot (a dynamic
  bound), so an idle slot costs neither a DMA nor a grid step;
- per tile, a few hundred columns at a time: ``hc = sum_n h C`` from the
  OLD state, a sum over sublanes, and ``h_new = decay h + B (outer) (dt
  x)``, float32 on the vector unit, the products in ``_ssm_step``'s
  order: the new state has its numbers, the readout its numbers up to the
  order of the sum over the state size. The per-(head, channel) scalars
  ``dt x`` and ``decay`` are lane vectors as they arrive; B and C are
  turned to columns once a tile;
- what the kernel returns beside the states is ``hc`` (slots, heads x head
  size); the caller finishes ``y = decay hc + dt (B . C) x`` with
  ``_ssm_step``'s own arithmetic. Rows of ``hc`` the kernel did not visit
  are made 0 by a ``where`` outside it (their block is never written).

Availability via ``nn.ops.registry`` (``DL4J_TPU_SSM_DECODE_STEP`` = 0 | 1
| interpret), keyed by ``(heads, head size, state size, slots, tile,
dtype)``, the tile in columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "ssm_decode_step"
#: bytes of the state block a grid step takes (state size x columns a tile
#: x 4): read and written, each double-buffered, four of them fit the
#: default VMEM budget; chosen on the chip (PERF.md, PR 36)
TILE_BYTES = 2 << 20
#: columns the kernel's inner loop takes at a time (a 128 x 256 float32
#: piece is 32 vector registers)
_CHUNK = 256


def live_table(active):
    """active (slots,) bool -> (the live slots' indices in order, then
    filler (slots,) int32, how many are live (1,) int32, ``active``): the
    kernel's walk. One table serves every state-space layer of a step."""
    ends = jnp.cumsum(active.astype(jnp.int32))
    n = active.shape[0]
    # the i-th live slot is the first whose running count passes i; with
    # nothing live one grid step still runs, on the last slot, and copies
    slot_of = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n, dtype=jnp.int32), side="right",
                         method="compare_all"), n - 1).astype(jnp.int32)
    return slot_of, ends[-1:], active


def _kernel(slot_ref, live_ref, layer_ref, dtx_ref, dec_ref, b_ref, c_ref,
            h_ref, hc_ref, o_ref, *, chunk: int):
    del slot_ref, layer_ref  # the index maps' own
    n, tile = h_ref.shape

    @pl.when(live_ref[0] > 0)
    def _step():
        b_col = b_ref[...].reshape(n, 1)
        c_col = c_ref[...].reshape(n, 1)

        def columns(j, carry):
            at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
            h = h_ref[:, at]                                  # (N, chunk)
            hc_ref[:, at] = jnp.sum(h * c_col, axis=0, keepdims=True)
            o_ref[:, at] = h * dec_ref[:, at] + dtx_ref[:, at] * b_col
            return carry

        jax.lax.fori_loop(0, tile // chunk, columns, 0)

    @pl.when(live_ref[0] == 0)
    def _nothing_live():  # the one grid step of an idle batch: as it was
        o_ref[...] = h_ref[...]


def ssm_decode_step(states, layer, table, dtx, decay, bvec, cvec, *,
                    tile: int, interpret: bool = False):
    """states (layers, slots, N, H x P) float32: a segment's recurrent
    states, of which layer ``layer`` () is read and written in the slots
    ``table`` = :func:`live_table` (active) names; dtx = dt x and decay =
    exp(dt a) spread over a head's channels, both (slots, H x P); bvec and
    cvec (slots, G, N); ``tile`` columns a block (a divisor of the H x P /
    G columns of a group). Returns (hc (slots, H x P) = sum_n h C of the
    OLD state, zero in idle slots; the states, aliased: layer ``layer``'s
    live slots hold ``decay h + B (outer) dtx``, every other block its old
    bits)."""
    _layers, n_slots, n, cols = states.shape
    groups = bvec.shape[1]
    per_group = cols // groups
    if per_group % tile:
        raise ValueError(f"a tile of {tile} columns does not divide a "
                         f"group's {per_group}")
    tiles = cols // tile
    slot_of, n_live, live = table
    # with nothing live one step still runs and copies its block
    n_steps = jnp.maximum(n_live[0], 1) * tiles

    def row(i, slot_ref, live_ref, layer_ref):
        return (slot_ref[i // tiles], 0, i % tiles)

    def group(i, slot_ref, live_ref, layer_ref):
        return (slot_ref[i // tiles] * groups
                + (i % tiles) * tile // per_group, 0, 0)

    def block(i, slot_ref, live_ref, layer_ref):
        return (layer_ref[0], slot_ref[i // tiles], 0, i % tiles)

    f32 = jnp.float32
    chunk = _CHUNK if tile % _CHUNK == 0 else tile
    hc, new = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_steps,),
            in_specs=[
                pl.BlockSpec((None, 1, tile), row),
                pl.BlockSpec((None, 1, tile), row),
                pl.BlockSpec((None, 1, n), group),
                pl.BlockSpec((None, 1, n), group),
                pl.BlockSpec((None, None, n, tile), block),
            ],
            out_specs=[pl.BlockSpec((None, 1, tile), row),
                       pl.BlockSpec((None, None, n, tile), block)]),
        out_shape=[jax.ShapeDtypeStruct((n_slots, 1, cols), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={7: 1},   # the states, after three tables
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=NAME,
    )(slot_of, n_live, jnp.reshape(layer, (1,)).astype(jnp.int32),
      dtx.astype(f32)[:, None], decay.astype(f32)[:, None],
      bvec.astype(f32).reshape(n_slots * groups, 1, n),
      cvec.astype(f32).reshape(n_slots * groups, 1, n), states)
    return jnp.where(live[:, None], hc[:, 0], 0.0), new


def ssm_decode_reference(states, layer, table, dtx, decay, bvec, cvec):
    """The same by whole-state ``jnp``: the probe's oracle
    (``_ssm_step``'s two passes and its ``where``, on the kernel's
    operands)."""
    cols, live = states.shape[3], table[2]
    rep = cols // bvec.shape[1]
    b_c = jnp.repeat(bvec, rep, axis=1).transpose(0, 2, 1)   # (slots, N, cols)
    c_c = jnp.repeat(cvec, rep, axis=1).transpose(0, 2, 1)
    old = states[layer]
    hc = jnp.sum(old * c_c, axis=1)
    new = old * decay[:, None] + dtx[:, None] * b_c
    new = jnp.where(live[:, None, None], new, old)
    return jnp.where(live[:, None], hc, 0.0), states.at[layer].set(new)


def _probe(heads: int, p: int, n: int, groups: int, tile: int,
           interpret: bool) -> None:
    """Compile the kernel at the caller's widths (two layers of three
    slots: live, idle, live) and hold it to the ``jnp`` form: the live
    slots' numbers, the idle slot's and the other layer's bits."""
    rng = np.random.default_rng(0)
    cols = heads * p
    # numpy arguments: a probe may run under an ambient trace
    states = rng.standard_normal((2, 3, n, cols)).astype(np.float32)
    dtx = rng.standard_normal((3, cols)).astype(np.float32)
    decay = np.repeat(rng.uniform(0.5, 1.0, (3, heads)), p,
                      axis=1).astype(np.float32)
    bvec = rng.standard_normal((3, groups, n)).astype(np.float32)
    cvec = rng.standard_normal((3, groups, n)).astype(np.float32)
    table = (np.asarray([0, 2, 2], np.int32), np.asarray([2], np.int32),
             np.asarray([True, False, True]))
    layer = np.ones((), np.int32)
    args = (states, layer, table, dtx, decay, bvec, cvec)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
    hc, new = jax.jit(functools.partial(
        ssm_decode_step, tile=tile,
        interpret=interpret)).lower(*shapes).compile()(*args)
    hc_w, new_w = jax.jit(ssm_decode_reference).lower(
        *shapes).compile()(*args)
    hc, new, hc_w, new_w = (np.asarray(v) for v in (hc, new, hc_w, new_w))
    for what, got, want in (("readout", hc, hc_w), ("state", new, new_w)):
        err = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6)
        if not np.isfinite(err) or err > 1e-5:
            raise RuntimeError(f"ssm decode kernel vs jnp, {what}: rel "
                               f"{err:.3e} > 1e-5")
    if not (np.array_equal(new[0], states[0])
            and np.array_equal(new[1, 1], states[1, 1])):
        raise RuntimeError("ssm decode kernel wrote a block it was not "
                           "to visit")


def _tile(heads: int, p: int, n: int, groups: int) -> int:
    """Columns a block: the most that keep the block within
    ``TILE_BYTES``, divide a group's columns and lie on the lane tiling (a
    multiple of 128, or all the columns); 0 where none does."""
    cols = heads * p
    per_group = cols // groups
    fits = [t for t in range(1, per_group + 1)
            if per_group % t == 0 and (t % 128 == 0 or t == cols)
            and t * n * 4 <= TILE_BYTES]
    return max(fits, default=0)


def ssm_decode_impl(heads: int, p: int, n: int, groups: int, slots: int,
                    dtype):
    """:func:`ssm_decode_step` with its tile and ``interpret`` flag bound,
    where the registry admits this instantiation; None for the ``jnp``
    path (kill switch, no TPU, a state that is not float32, columns no
    tile divides, a refused probe: each recorded as a fallback)."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    dtype = jnp.dtype(dtype)
    tile = _tile(int(heads), int(p), int(n), int(groups))
    key = (int(heads), int(p), int(n), int(slots), tile, dtype.name)
    reg = default_kernel_registry()
    if dtype != jnp.float32 or not tile:
        reg.disable(NAME, key, "a float32 state in tiles of columns that "
                               "divide a group is what the kernel takes")
        return None
    interpret = reg.resolve(NAME, key, lambda interp: functools.partial(
        _probe, int(heads), int(p), int(n), int(groups), tile, interp))
    if interpret is None:
        return None
    return functools.partial(ssm_decode_step, tile=tile, interpret=interpret)

"""The grouped SwiGLU products of an expert layer at FEW ROWS A GROUP as one
Pallas TPU kernel: every hit expert's three matrices read once, in wide
contiguous tiles, against a short window of its rows.

``nn/conf/layers/moe._grouped_swiglu`` runs ``(silu(r Eg_e) * (r Eu_e)) Ed_e``
over rows sorted by expert as three ``jax.lax.ragged_dot``, whose Mosaic
kernel picks its own tiles, (128, 512, 512): 128 rows a visit and half a
megabyte a DMA, whatever the groups hold. A decode step gives a hit expert
one to six rows. Here:

- the expert stacks come WHOLE, ``(groups, d, f)`` and ``(groups, f, d)``
  (several layers' experts are ``layers x count`` groups: a custom call's
  operand is made whole, a scan's slice of it would be copied a layer),
  with the walk by scalar prefetch (:func:`group_table`): which group the
  j-th grid row takes, where its rows start and how many they are;
- a STATIC grid over (count, tiles of f): the j-th row of the grid takes
  the j-th group that has rows; the rows of the grid past the last hit
  group repeat the block before them, so a group without rows costs no DMA
  (and no product: ``pl.when``). No dynamic bound (a second executable with
  one halted the core under ``kv_column_write``: PERF.md, PR 43);
- per grid step one tile of ``Eg`` and ``Eu`` ``(d, tile)`` and one of ``Ed``
  ``(tile, d)``: gate, up, ``silu * u`` and that tile's part of the down
  product, summed over the tiles in float32 into the output, which stays in
  VMEM through the call, as the rows do (M is small: :data:`MAX_ROWS`); the
  ``(M, f)`` intermediate never leaves the chip's fast memory;
- a group's rows are taken ``window`` at a time from an ALIGNED start (the
  packing of the rows' dtype: 16 rows of bfloat16, 8 of float32), the rows
  of the window that are not the group's masked out of ``silu * u`` before
  the down product, and the window's result added to the output's rows in
  place; a group of more than a window's rows loops, so any sizes are right
  and few are fast. The MXU loads a tile of weights once a window, however
  few rows the window holds: the window is chosen so that a decode step's
  groups take ONE;
- bfloat16 operands, float32 accumulation, one exact MXU pass
  (``kernel_compat.PRECISION``): ``ragged_dot``'s numbers up to the order of
  the sum over f. Rows past ``sum(sizes)`` come back 0.

Availability via ``nn.ops.registry`` (``DL4J_TPU_GROUPED_EXPERTS`` = 0 | 1 |
interpret), keyed by ``(d, f, count, M, window, tile, dtype)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.ops.latent_decode import _precision

NAME = "grouped_experts"
#: rows (pairs of a step) up to which the kernel is asked for: the rows and
#: the float32 output stay in VMEM through the call (6 bytes x d a row), and
#: past a window's rows a group the MXU loads each weight tile once a
#: window, which ``ragged_dot``'s 128-row tiles do better (PERF.md, PR 48)
MAX_ROWS = 1024
#: rows of a group a product takes at a time (PERF.md, PR 48: chosen on the
#: chip among 16, 32 and 64 at the four expert cells' shapes)
WINDOW = 32
#: bytes of one grid step's three weight tiles (each double-buffered beside
#: the rows and the output: VMEM is 128 MiB on a v5e)
TILE_BYTES = 20 << 20
#: what a tile of f must be a multiple of where it is not all of f (lanes)
_LANE = 128


def group_table(sizes, first):
    """sizes (count,) int32: the rows of each group of ONE layer, in order;
    ``first`` () the stack index of the layer's first group -> the kernel's
    walk (gid, start, size), (count,) int32 each: grid row j takes group
    ``gid[j]`` of the stack, whose ``size[j]`` rows begin at ``start[j]``:
    the groups that have rows, in order, then the last of them again with
    size 0 (the block before is repeated: no DMA)."""
    count = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    seen = jnp.cumsum((sizes > 0).astype(jnp.int32))
    j = jnp.arange(count, dtype=jnp.int32)
    # the j-th hit group is the first whose running count of hits passes j
    which = jnp.minimum(jnp.sum(seen[None, :] <= j[:, None], axis=1),
                        count - 1).astype(jnp.int32)
    n_hit = seen[-1]
    which = jnp.where(j < n_hit, which,
                      jnp.take(which, jnp.maximum(n_hit - 1, 0)))
    start = jnp.cumsum(sizes) - sizes
    return (which + jnp.asarray(first, jnp.int32),
            jnp.take(start, which),
            jnp.where(j < n_hit, jnp.take(sizes, which), 0))


def _kernel(gid_ref, start_ref, size_ref, rows_ref, eg_ref, eu_ref, ed_ref,
            o_ref, *, window: int, align: int, precision):
    del gid_ref  # the index maps' own
    j, t = pl.program_id(0), pl.program_id(1)
    m = rows_ref.shape[0]
    f32 = jnp.float32

    @pl.when((j == 0) & (t == 0))
    def _open():
        o_ref[...] = jnp.zeros_like(o_ref)

    n = size_ref[j]

    @pl.when(n > 0)
    def _group():
        lo = start_ref[j]
        hi = lo + n
        base = (lo // align) * align

        def one_window(k, carry):
            own = base + k * window          # the first row this window owns
            at = pl.multiple_of(jnp.minimum(own, m - window), align)
            r = rows_ref[pl.ds(at, window), :]
            g = jnp.dot(r, eg_ref[...], preferred_element_type=f32,
                        precision=precision)
            u = jnp.dot(r, eu_ref[...], preferred_element_type=f32,
                        precision=precision)
            row = at + jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            mine = (row >= jnp.maximum(lo, own)) & (row < hi)
            h = jnp.where(mine, jax.nn.silu(g) * u, 0.0).astype(r.dtype)
            o_ref[pl.ds(at, window), :] += jnp.dot(
                h, ed_ref[...], preferred_element_type=f32,
                precision=precision)
            return carry

        jax.lax.fori_loop(0, (hi - base + window - 1) // window, one_window, 0)


def grouped_experts(rows, eg, eu, ed, sizes, first, *, window: int, tile: int,
                    interpret: bool = False):
    """rows (M, d), sorted by group; eg, eu (groups, d, f) and ed (groups, f,
    d): the stacks; sizes (count,) the rows of the groups ``first .. first +
    count`` of the stack in order (every other group has none; ``first`` may
    be traced). ``window`` rows a product, ``tile`` columns of f a grid step
    (a divisor of f). Returns (M, d) float32: row i through its group's
    ``(silu(r Eg) * (r Eu)) Ed``; the rows past ``sum(sizes)`` 0. (A caller
    that stacks the result as a scan's ``ys`` has XLA fuse the call into the
    stacking write under its own 16 MB of scoped VMEM, which this call's
    blocks exceed: carry or use the result in the loop.)"""
    m, d = rows.shape
    f = eg.shape[2]
    count = sizes.shape[0]
    if f % tile:
        raise ValueError(f"a tile of {tile} columns does not divide f = {f}")
    dt = rows.dtype
    align = 32 // dt.itemsize               # rows a packed sublane tile
    if window % align:
        raise ValueError(f"a window of {window} rows is not whole tiles of "
                         f"{align} rows of {dt.name}")
    held = max(window, -(-m // align) * align)
    if held != m:
        rows = jnp.pad(rows, ((0, held - m), (0, 0)))
    n_t = f // tile
    table = group_table(sizes, first)

    def whole(j, t, gid, start, size):
        return (0, 0)

    def tile_of(j, t, size):
        # a grid row without rows repeats the block before it
        return jnp.where(size[j] > 0, t, n_t - 1)

    def columns(j, t, gid, start, size):
        return (gid[j], 0, tile_of(j, t, size))

    def band(j, t, gid, start, size):
        return (gid[j], tile_of(j, t, size), 0)

    tile_bytes = 3 * d * tile * dt.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, align=align,
                          precision=_precision(dt)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(count, n_t),
            in_specs=[pl.BlockSpec((held, d), whole),
                      pl.BlockSpec((None, d, tile), columns),
                      pl.BlockSpec((None, d, tile), columns),
                      pl.BlockSpec((None, tile, d), band)],
            out_specs=pl.BlockSpec((held, d), whole)),
        out_shape=jax.ShapeDtypeStruct((held, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the weight tiles, the rows and the output, each
            # double-buffered, and a window's float32 products beside them
            vmem_limit_bytes=min(
                2 * tile_bytes + 2 * held * d * (dt.itemsize + 4) + (16 << 20),
                120 << 20)),
        interpret=interpret,
        name=NAME,
    )(*table, rows, eg, eu, ed)
    return out[:m] if held != m else out


def grouped_experts_reference(rows, eg, eu, ed, sizes, first: int):
    """The same by ``jnp``, a group at a time through its own matrices in
    float32 accumulation: the probe's oracle. ``sizes`` and ``first`` are
    concrete here (numbers, not tracers)."""
    f32, prec = jnp.float32, jax.lax.Precision.HIGHEST
    out, at = [], 0
    for g, n in enumerate(int(n) for n in sizes):
        r = rows[at:at + n]
        gate = jnp.dot(r, eg[first + g], preferred_element_type=f32,
                       precision=prec)
        up = jnp.dot(r, eu[first + g], preferred_element_type=f32,
                     precision=prec)
        h = (jax.nn.silu(gate) * up).astype(rows.dtype)
        out.append(jnp.dot(h, ed[first + g], preferred_element_type=f32,
                           precision=prec))
        at += n
    out.append(jnp.zeros((rows.shape[0] - at, rows.shape[1]), f32))
    return jnp.concatenate(out)


@functools.lru_cache(maxsize=None)
def _probe(d: int, f: int, count: int, window: int, tile: int, dtype,
           interpret: bool) -> None:
    """Compile the kernel at the caller's widths, window and tile (a stack
    of ``count`` + 1 groups, at most four, of which the first is another
    layer's: an empty group, one of a window + 1 rows, one of a row; three
    rows left over past the groups) and hold it to the ``jnp`` form.
    Remembered where it passed: it does not depend on M, which the
    registry's key carries, so an engine's several row counts (the decode
    step's, the small prefill buckets') cost one compile. The stacks are one
    drawn matrix rolled a column a group: at a cell's widths drawing every
    group costs seconds of set-up."""
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    sizes = np.asarray([0, window + 1, 1][:count], np.int32)
    m = int(sizes.sum()) + 3

    def stack(k, n):  # numpy arguments: a probe may run under a trace
        one = (rng.standard_normal((k, n), np.float32)
               / np.sqrt(k)).astype(dt)
        return np.stack([np.roll(one, g, axis=1) for g in range(count + 1)])

    rows = rng.standard_normal((m, d), np.float32).astype(dt)
    args = (rows, stack(d, f), stack(d, f), stack(f, d))
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    got = jax.jit(lambda r, eg, eu, ed: grouped_experts(
        r, eg, eu, ed, sizes, np.asarray(1, np.int32), window=window,
        tile=tile, interpret=interpret)).lower(*shapes).compile()(*args)
    want = jax.jit(functools.partial(
        grouped_experts_reference, sizes=tuple(sizes),
        first=1)).lower(*shapes).compile()(*args)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    if not np.isfinite(err) or err > tol:
        raise RuntimeError(f"grouped experts kernel vs jnp: rel {err:.3e} > "
                           f"{tol}")
    if np.any(got[int(sizes.sum()):]):
        raise RuntimeError("grouped experts kernel left numbers in rows "
                           "past the groups")


def tile_for(d: int, f: int, itemsize: int) -> int:
    """Columns of f a grid step: the widest divisor of f on the lane tiling
    (a multiple of 128, or all of f) whose three weight tiles stay within
    ``TILE_BYTES``; 0 where none does."""
    fits = [t for t in range(1, f + 1)
            if f % t == 0 and (t % _LANE == 0 or t == f)
            and 3 * d * t * itemsize <= TILE_BYTES]
    return max(fits, default=0)


def plan(m: int, d: int, f: int, dtype):
    """(window, tile) where the kernel has a form for these shapes and a
    gain; None, unrecorded, where it has not: more than ``MAX_ROWS`` rows (a
    prefill's long groups are where 128-row tiles are right), widths no
    tile divides, an ambient mesh (a Mosaic call is not partitioned; under
    ``shard_map`` the shares of ``parallel/moe.py`` keep one path)."""
    dtype = jnp.dtype(dtype)
    tile = tile_for(int(d), int(f), dtype.itemsize)
    ambient = jax.sharding.get_abstract_mesh()
    if (m > MAX_ROWS or not tile or dtype.itemsize not in (2, 4)
            or any(size > 1 for size in ambient.shape.values())):
        return None
    return max(WINDOW, 32 // dtype.itemsize), tile


def grouped_experts_impl(m: int, d: int, f: int, count: int, dtype):
    """:func:`grouped_experts` with its window, tile and ``interpret`` flag
    bound, where :func:`plan` has a form for the shapes and the registry
    admits this instantiation; None for the ``ragged_dot`` path (the
    registry's part recorded as a fallback: kill switch, no TPU, a refused
    probe)."""
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    planned = plan(m, d, f, dtype)
    if planned is None:
        return None
    window, tile = planned
    dtype = jnp.dtype(dtype)
    key = (int(d), int(f), int(count), int(m), window, tile, dtype.name)
    interpret = default_kernel_registry().resolve(
        NAME, key, lambda interp: functools.partial(
            _probe, int(d), int(f), min(int(count), 3), window, tile, dtype,
            interp))
    if interpret is None:
        return None
    return functools.partial(grouped_experts, window=window, tile=tile,
                             interpret=interpret)

#!/usr/bin/env python
"""Time a latent layer's decode-step attention over a selection alone on the
chip, at the glm-5.2-ep16 cell's key: five layers' slabs (5, 32, 14336, 640)
in bfloat16, position-major, 64 heads, ``kv_rank`` 512, a selection of 2,048
positions a slot (``decoder_lm._select_indices`` over drawn scores, made
once outside the timed program: the select is another scope), one scan over
the layers with the layer's index traced, as the decode program has it. The
ways to take the core:

    gather      the present branch of ``_sparse_latent_attention``: XLA's
                gather of slots x 2,048 rows, two einsums, the float32
                scores between; it runs for every slot whoever streams
    gather.live the same branch over the live slots alone, compacted: what a
                program-level loop over the live slots could give at best
    stream.tT   the kernel (``nn/ops/sparse_latent_decode.py``): the live
                rows streamed in tiles of T under the selection's bias
    one_row_copy  whether Mosaic takes a copy of ONE row of the slab as it
                lies (what a fetch by scalar-prefetched indices needs): the
                compile's verdict, no timing

under loads drawn from the cell's mix (lengths 4,097-14,336, mean ~9 k):

    cell17  17 slots live (0.68 requests/s: Little's 17 of 32)
    cell32  every slot live, the same mix
    full    every slot at its whole length
    empty   every slot idle: the grid's own cost

    chiprun -- python scripts/sparse_latent_decode_microbench.py \
        --out chiprun_out/sparse_latent_decode_microbench.json

One JSON object: per variant and load the milliseconds a layer, the share of
819 GB/s by the LIVE bytes (rows x 1,280 B) and by the SELECTED bytes
(``benchmark/lib/work_sparse.py``: selected positions x 1,152 B), and the
largest gap to ``gather`` on the live rows. The last stage runs the
registry's probe at the cell's key three times in ONE process (a Mosaic
kernel whose second executable halts the core shows there, not in a first
call). Needs the chip (``--cpu`` is a rehearsal at a tiny size under the
Pallas interpreter: no timing means anything there).
"""

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HBM_BYTES_PER_S = 819e9  # one TPU v5e, Google Cloud documentation
_NEG = -1e30


def loads(n_slots, t_c, k, seed=0):
    """name -> lengths (slots,) int32, 0 for an idle slot."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def busy(n):
        lengths = np.zeros((n_slots,), np.int32)
        at = rng.choice(n_slots, size=min(n, n_slots), replace=False)
        # the cell's mix: contexts past the selection up to the slot, mean
        # about five eighths of the slot
        lengths[at] = rng.triangular(2 * k + 1, t_c * 0.55, t_c, at.size)
        return np.clip(lengths, 0, t_c).astype(np.int32)

    return {"cell17": busy(n_slots // 2 + 1), "cell32": busy(n_slots),
            "full": np.full((n_slots,), t_c, np.int32),
            "empty": np.zeros((n_slots,), np.int32)}


def gathered_core(q_lat, new, slab, layer, sel, *, scale, kv_rank):
    """The present branch under ``attn_sparse_core``, line for line."""
    import jax.numpy as jnp

    f32, dt = jnp.float32, slab.dtype
    idx, n_sel, own_in = sel[:3]
    b = q_lat.shape[0]
    rows = slab.at[layer, jnp.arange(b)[:, None], idx].get(
        mode="promise_in_bounds")
    s_c = jnp.einsum("bhc,bkc->bhk", q_lat, rows,
                     preferred_element_type=f32) * scale
    counts = jnp.arange(idx.shape[1])[None, :] < n_sel[:, None]
    s_c = jnp.where(counts[:, None], s_c, _NEG)
    s_own = jnp.einsum("bhc,bc->bh", q_lat, new,
                       preferred_element_type=f32) * scale
    s_own = jnp.where(own_in[:, None], s_own, _NEG)
    m = jnp.maximum(s_c.max(-1), s_own)
    e_c, e_own = jnp.exp(s_c - m[..., None]), jnp.exp(s_own - m)
    lat = (jnp.einsum("bhk,bkc->bhc", e_c.astype(dt), rows,
                      preferred_element_type=f32)
           + e_own[..., None] * new[:, None].astype(f32))
    z = (e_c.sum(-1) + e_own)[..., None]
    return (lat[..., :kv_rank] / z).astype(dt)


def layers_of(core):
    """core(q, new, slabs, layer) -> (slots, heads, rank), scanned over the
    layers of ``slabs``; the outputs are summed so that none is dropped."""
    import jax
    import jax.numpy as jnp

    def run(q, new, slabs):
        def body(acc, xs):
            q_l, new_l, layer = xs
            return acc + core(q_l, new_l, slabs, layer).astype(jnp.float32), None

        one = jax.eval_shape(core, q[0], new[0], slabs,
                             jnp.zeros((), jnp.int32))
        acc, _ = jax.lax.scan(
            body, jnp.zeros(one.shape, jnp.float32),
            (q, new, jnp.arange(slabs.shape[0], dtype=jnp.int32)))
        return acc

    return run


def one_row_copy(n_slots, t_c, width, dtype, interpret, sharding=None):
    """Whether a copy of ONE row of the slab, addressed from a
    scalar-prefetched index, compiles (``sharding``: for a described chip,
    no chip attached): "ok" or the compiler's first line."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(idx_ref, slab_ref, o_ref, row, sem):
        s = pl.program_id(0)
        copy = pltpu.make_async_copy(
            slab_ref.at[s, pl.ds(idx_ref[s], 1), :], row, sem)
        copy.start()
        copy.wait()
        o_ref[...] = row[...]

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_slots,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, 1, width), lambda s, idx: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, width), dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((n_slots, 1, width), dtype),
        interpret=interpret, name="one_row_copy")
    try:
        jax.jit(call).lower(
            jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((n_slots, t_c, width), dtype,
                                 sharding=sharding)).compile()
        return "ok"
    except Exception as e:  # noqa: BLE001 — the compiler's verdict is the result
        return f"{type(e).__name__}: {str(e).splitlines()[0]}"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiles", default="512,1024,2048")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--loads", default="cell17,cell32,full,empty")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DL4J_TPU_SPARSE_LATENT_DECODE"] = "interpret"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models import decoder_lm
    from deeplearning4j_tpu.nn.ops import sparse_latent_decode as sld
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    if args.cpu:
        n_layers, n_slots, heads, width, t_c, k, rank = 2, 4, 4, 128, 64, 8, 64
        tiles = [8, 16]
    else:
        if jax.default_backend() != "tpu":
            raise SystemExit("no TPU here: times from another backend say "
                             "nothing (--cpu rehearses the control flow)")
        n_layers, n_slots, heads, width, t_c, k, rank = (
            5, 32, 64, 640, 14336, 2048, 512)
        tiles = [int(t) for t in args.tiles.split(",")]
    dt, scale = jnp.bfloat16, 0.0722
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (n_layers, n_slots, heads, width), dt)
    new = jax.random.normal(keys[1], (n_layers, n_slots, width), dt)
    slabs = jax.lax.map(
        lambda key: jax.random.normal(key, (n_slots, t_c, width), dt),
        jax.random.split(keys[2], n_layers))
    scores = jax.random.normal(keys[3], (n_slots, t_c), jnp.float32)
    own = jax.random.normal(keys[4], (n_slots,), jnp.float32)

    out = {"device": jax.devices()[0].device_kind, "loads": {},
           "one_row_copy": one_row_copy(n_slots, t_c, width, dt, args.cpu)}
    print("one_row_copy", out["one_row_copy"], flush=True)
    cases = {name: v for name, v in loads(n_slots, t_c, k).items()
             if name in args.loads.split(",")}

    def variants(lengths, sel):
        yield "gather", lambda q_l, new_l, slab, layer: gathered_core(
            q_l, new_l, slab, layer, sel, scale=scale, kv_rank=rank), None
        at = np.flatnonzero(np.asarray(lengths))
        if 0 < at.size < n_slots:
            sel_live = tuple(a[at] for a in sel[:3])
            yield "gather.live", lambda q_l, new_l, slab, layer: gathered_core(
                q_l, new_l, slab, layer, sel_live, scale=scale,
                kv_rank=rank), at
        for tile in tiles:
            kernel = functools.partial(
                sld.sparse_latent_decode, lengths=lengths, bias=sel[3],
                own_in=sel[2], walk=sld.live_walk(lengths, t_c, tile),
                scale=scale, kv_rank=rank, tile=tile, interpret=args.cpu)
            yield f"stream.t{tile}", kernel, None

    for load, lengths in cases.items():
        lens = jnp.asarray(lengths)
        held = jnp.arange(t_c)[None, :] < lens[:, None]
        sel = jax.jit(functools.partial(
            decoder_lm._select_indices, k=k, as_bias=True))(
                jnp.where(held, scores, -jnp.inf), own, lens)
        live = lengths > 0
        n_sel = np.asarray(sel[1])[live]
        out["loads"][load] = {
            "busy_slots": int(live.sum()), "live_positions": int(lengths.sum()),
            "selected_positions": int(n_sel.sum())}
        want = None
        for name, core, at in variants(lens, sel):
            # the compacted control holds a slab of its own slots
            args_ = (q, new, slabs) if at is None else (
                q[:, at], new[:, at], slabs[:, at])
            run = jax.jit(layers_of(core))
            t0 = time.perf_counter()
            got = np.asarray(run(*args_))
            first_s = time.perf_counter() - t0
            if at is not None:
                full_rows = np.zeros_like(want)
                full_rows[at] = got
                got = full_rows
            if want is None:
                want = got
            gap = float(np.abs(got - want)[live].max()) if live.any() else 0.0
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                res = run(*args_)
            res.block_until_ready()
            ms = 1e3 * (time.perf_counter() - t0) / args.repeats / n_layers
            share = lambda n: 100 * n / HBM_BYTES_PER_S / (ms / 1e3)  # noqa: E731
            out.setdefault(name, {})[load] = {
                "ms_a_layer": ms, "first_call_s": first_s,
                "live_hbm_share_pct": share(int(lengths.sum()) * width * 2),
                "selected_hbm_share_pct": share(int(n_sel.sum()) * (rank + 64) * 2),
                "max_gap_live_rows": gap}
            print(name, load, json.dumps(out[name][load]), flush=True)
            del args_

    # the probe at the cell's key, three times in one process
    reg = default_kernel_registry()
    out["probes"] = []
    for _ in range(3):
        reg.reset(sld.NAME)
        t0 = time.perf_counter()
        admitted = sld.sparse_latent_decode_impl(heads, width, t_c, k, dt, rank)
        out["probes"].append({
            "admitted": admitted is not None,
            "seconds": time.perf_counter() - t0,
            "verdict": reg.snapshot().get(sld.NAME)})
        print("probe", json.dumps(out["probes"][-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0 if all(p["admitted"] for p in out["probes"]) else 1


if __name__ == "__main__":
    sys.exit(main())

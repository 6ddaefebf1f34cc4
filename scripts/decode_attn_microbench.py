#!/usr/bin/env python
"""Time the cached attention core of a one-token decode step alone on the
chip, at the slabs of the cells it costs most in, bfloat16, all layers'
calls in one program as the decode program has them:

    chat     gpt2-large.chat: 36 layers, 24 slots of 1,024, 20 heads of 64
    granite  granite-4.0-h-small-ep2: one layer, 64 slots of 4,096, 8
             key/value heads of 128 under 32 query heads
    mimo     mimo-v2.5-ep16: a full layer, 64 slots of 1,536, 4 key/value
             heads of 192 (values 128) under 64 query heads
    ouro     ouro-2.6b: 192 (pass, layer) entries, 5 slots of 896, 16 heads
             of 128

Two ways to attend:

    einsums  the two whole-slab einsums under one softmax, a layer's slices
             as a scan's inputs (``transformer_lm._attend_cached``,
             ``decoder_lm.block``): every column of every slot
    tN       the kernel (``nn/ops/decode_attention.py``), N columns a tile,
             the walk made once outside the layer loop: the live tiles only

at the live shares the rule names (ISSUE 44): the chat shape with 2 of 24
slots live at 200-500 positions (under 2.2 ms), with 12 of 24 at 64-768
(under 3.5 ms) and with all 24 at 1,024 (reported, not a gate); the other
shapes at their cells' live shares (admitted where the kernel beats the
einsums by 20 % or more).

    chiprun -- python scripts/decode_attn_microbench.py \
        --out chiprun_out/decode_attn_microbench.json

One JSON object: per shape, load and variant the milliseconds of all
layers' calls, GB/s and the share of HBM speed on the LIVE tiles' bytes
(what the kernel cannot avoid; the einsums': the whole slabs), the largest
error against the einsums relative to the result's scale, and whether the
compiled program holds a slab-sized ``copy``. Last, the registry's probe
runs at the chat and the granite keys, twice, in this one process: with a
dynamic grid bound the second executable that held ``kv_column_write`` at a
probe's small shapes halted the core (PERF.md section 6, PR 43). Needs the
chip (``--cpu`` is a rehearsal at a tiny size under the Pallas interpreter:
no timing means anything there).
"""

import argparse
import functools
import json
import math
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HBM_BYTES_PER_S = 819e9  # one TPU v5e, Google Cloud documentation

# (layers, slots, hkv, grp, hd, vd, T), tiles to try, loads: (live slots,
# shortest, longest), the lengths drawn log-uniform between the two
SHAPES = {
    "chat": ((36, 24, 20, 1, 64, 64, 1024), (128, 256, 512, 1024),
             {"2of24": (2, 200, 500), "12of24": (12, 64, 768),
              "24full": (24, 1024, 1024)}),
    "granite": ((1, 64, 8, 4, 128, 128, 4096), (128, 256, 512, 1024),
                {"34of64": (34, 400, 3000), "64full": (64, 4096, 4096)}),
    "mimo": ((1, 64, 4, 16, 192, 128, 1536), (128, 256, 512),
             {"10of64": (10, 150, 900)}),
    "ouro": ((192, 5, 16, 1, 128, 128, 896), (128,),
             {"4of5": (4, 130, 600)}),
}
TINY = {
    "chat": ((3, 4, 4, 1, 16, 16, 64), (8, 16),
             {"2of4": (2, 5, 40), "4full": (4, 64, 64)}),
    "granite": ((1, 4, 2, 4, 16, 8, 64), (16,), {"3of4": (3, 1, 64)}),
}
RULE_MS = {"chat": {"2of24": 2.2, "12of24": 3.5}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeats", type=int, default=30)
    ap.add_argument("--shapes", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.ops import decode_attention as da

    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("no TPU here: times from another backend say "
                         "nothing (--cpu rehearses the control flow)")
    bf16, f32 = jnp.bfloat16, jnp.float32

    def einsums(scale, q, k_new, v_new, k_slab, v_slab, lengths):
        live = (jnp.arange(k_slab.shape[-1])[None, :]
                < lengths[:, None])[:, None, None]

        def layer(carry, kv):
            kc, vc = kv
            s_own = jnp.einsum("skgd,skd->skg", q, k_new,
                               preferred_element_type=f32)[..., None] * scale
            s_c = jnp.where(live, jnp.einsum(
                "skgd,skdt->skgt", q, kc, preferred_element_type=f32) * scale,
                -1e30)
            m = jnp.maximum(s_own, s_c.max(-1, keepdims=True))
            e_own, e_c = jnp.exp(s_own - m), jnp.exp(s_c - m)
            o = (e_own * v_new[:, :, None].astype(f32) + jnp.einsum(
                "skgt,skdt->skgd", e_c.astype(vc.dtype), vc,
                preferred_element_type=f32))
            return carry, o / (e_own + e_c.sum(-1, keepdims=True))

        return jax.lax.scan(layer, 0, (k_slab, v_slab))[1]

    def kernel(tile, scale, q, k_new, v_new, k_slab, v_slab, lengths):
        table = da.live_tiles(lengths, k_slab.shape[-1], tile)  # once

        def layer(carry, i):
            return carry, da.decode_attention(
                q, k_new, v_new, k_slab, v_slab, i, table, scale=scale,
                tile=tile, interpret=args.cpu)

        return jax.lax.scan(
            layer, 0, jnp.arange(k_slab.shape[0], dtype=jnp.int32))[1]

    def slab_copies(compiled, floor):
        """``copy`` operations at least as large as one layer of a slab."""
        return [name for name, dims in re.findall(
            r"%(\S+) = \w+\[([\d,]+)\]\S* copy\(", compiled.as_text())
            if math.prod(map(int, dims.split(","))) >= floor]

    out = {"device": jax.devices()[0].device_kind,
           "hbm_bytes_per_s": HBM_BYTES_PER_S}
    wanted = [s for s in args.shapes.split(",") if s]
    for name, (shape, tiles, loads) in (TINY if args.cpu else SHAPES).items():
        if wanted and name not in wanted:
            continue
        layers, slots, hkv, grp, hd, vd, t = shape
        scale = 1.0 / math.sqrt(hd)
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 5)
        q = jax.random.normal(keys[0], (slots, hkv, grp, hd), f32).astype(bf16)
        k_new = jax.random.normal(keys[1], (slots, hkv, hd), f32).astype(bf16)
        v_new = jax.random.normal(keys[2], (slots, hkv, vd), f32).astype(bf16)
        k_slab = jax.jit(lambda k: jax.random.normal(
            k, (layers, slots, hkv, hd, t), f32).astype(bf16))(keys[3])
        v_slab = jax.jit(lambda k: jax.random.normal(
            k, (layers, slots, hkv, vd, t), f32).astype(bf16))(keys[4])
        column_bytes = layers * hkv * (hd + vd) * 2     # K + V, a position
        out[name] = {"shape": list(shape), "rule_ms": RULE_MS.get(name, {}),
                     "layer_slab_bytes": slots * hkv * (hd + vd) * t * 2}
        variants = {"einsums": functools.partial(einsums, scale)}
        for tile in tiles:
            variants[f"t{tile}"] = functools.partial(kernel, tile, scale)
        for load, (n_live, lo, hi) in loads.items():
            rng = np.random.default_rng(7)
            lengths = np.zeros((slots,), np.int32)
            # log-uniform, as a chat's prompts and answers are spread:
            # 64-768 has a mean of ~285
            lengths[rng.choice(slots, size=n_live, replace=False)] = np.exp(
                rng.uniform(np.log(lo), np.log(hi), n_live)).astype(np.int32)
            lens = jnp.asarray(lengths)
            want = None
            for variant, fn in variants.items():
                tile = int(variant[1:]) if variant != "einsums" else 0
                try:
                    compiled = jax.jit(fn).lower(
                        q, k_new, v_new, k_slab, v_slab, lens).compile()
                    copies = slab_copies(compiled, slots * hkv * vd * t)
                    got = np.asarray(compiled(q, k_new, v_new, k_slab, v_slab,
                                              lens))
                    if want is None:
                        want = got
                    # an idle slot has no result to compare
                    err = float(np.max(np.abs(got - want)[:, lengths > 0])
                                / np.max(np.abs(want)))
                    t0 = time.perf_counter()
                    for _ in range(args.repeats):
                        res = compiled(q, k_new, v_new, k_slab, v_slab, lens)
                    jax.block_until_ready(res)
                    ms = 1e3 * (time.perf_counter() - t0) / args.repeats
                    read = column_bytes * (
                        int(np.sum(-(-lengths // tile) * tile)) if tile
                        else slots * t)
                    got = {"ms_all_layers": ms, "bytes_read": read,
                           "gb_per_s": read / (ms / 1e3) / 1e9,
                           "hbm_share_pct":
                               100 * read / (ms / 1e3) / HBM_BYTES_PER_S,
                           "rel_err_vs_einsums": err,
                           "slab_sized_copies": copies}
                except Exception as e:  # noqa: BLE001 — a refused variant is a reading
                    got = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
                out[name].setdefault(load, {"live_slots": n_live,
                                            "mean_length": float(
                                                lengths[lengths > 0].mean())}
                                     )[variant] = got
                print(name, load, variant, json.dumps(got), flush=True)
        del k_slab, v_slab

    # the registry's probe at two keys, twice, in ONE process, each an
    # executable of its own
    probes = ([(4, 1, 16, 16, 64, 8), (2, 4, 16, 8, 64, 16)] if args.cpu else
              [(20, 1, 64, 64, 1024, da.tile_for(1024)),
               (8, 4, 128, 128, 4096, da.tile_for(4096))])
    def write():
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)

    write()  # the readings are kept whatever the probes do to the core
    for key in 2 * probes:
        da._probe(*key, jnp.dtype(bf16), args.cpu)
    out["probes_in_one_process"] = 2 * len(probes)
    print("probes_in_one_process", out["probes_in_one_process"], flush=True)
    write()
    return 0


if __name__ == "__main__":
    sys.exit(main())

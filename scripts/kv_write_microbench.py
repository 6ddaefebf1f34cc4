#!/usr/bin/env python
"""Time the cache's after-loop column write alone on the chip, at the slabs
of the two cells it costs most in: gpt2-large.chat's (36 layers, 24 slots of
1,024, 20 heads of 64) and ouro-2.6b.reason-looped's (192 (pass, layer)
entries, 5 slots of 896, 16 heads of 128), bfloat16, a K and a V slab
donated to one program as the decode program has them. Two ways to write
one new column a slot:

    loop     one ``dynamic_update_slice`` a slot and slab, every slot
             (``transformer_lm._put_columns`` without the kernel)
    lbN      the kernel (``nn/ops/kv_column_write.py``), N entries a block,
             the live slots only

with every slot live and with half of them.

    chiprun -- python scripts/kv_write_microbench.py \
        --out chiprun_out/kv_write_microbench.json

One JSON object: per shape, variant and load the milliseconds of both slabs,
GB/s and the share of HBM speed on the TILE bytes (the live slots' 128-column
blocks, read and written: what a column write cannot avoid), whether the
compiled program holds a slab-sized ``copy``, and whether the slabs' sums a
(entry, slot) equal the reference's (one update a live slot). A last case
holds the kernel to the reference bit for bit where the block does not
divide the entries, and the registry's probe runs at three blocks, twice,
in this one process. The rule the kernel is held to (ISSUE 43): all 24 slots
of the chat shape under 2.5 ms, all 5 of the ouro shape under 4.5 ms, no
slab-sized copy. Needs the chip (``--cpu`` is a rehearsal at a tiny size
under the Pallas interpreter: no timing means anything there).
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

SHAPES = {  # (entries, slots, heads, head size, T), entries a block to try
    "chat": ((36, 24, 20, 64, 1024), (3, 6, 12)),
    "ouro": ((192, 5, 16, 128, 896), (2, 4, 8)),
}
TINY = {"chat": ((6, 4, 4, 16, 256), (2, 3)), "ouro": ((8, 3, 2, 32, 128), (4,))}
RULE_MS = {"chat": 2.5, "ouro": 4.5}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeats", type=int, default=30)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.ops import kv_column_write as kcw
    from deeplearning4j_tpu.nn.ops.ssm_decode import live_table

    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("no TPU here: times from another backend say "
                         "nothing (--cpu rehearses the control flow)")
    bf16 = jnp.bfloat16

    def loop(slab, new, wp, table):
        for s in range(new.shape[1]):
            slab = jax.lax.dynamic_update_slice(
                slab, new[:, s:s + 1, :, :, None], (0, s, 0, 0, wp[s]))
        return slab

    def both_slabs(put):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def run(k, v, new_k, new_v, wp, active):
            table = live_table(active)
            return put(k, new_k, wp, table), put(v, new_v, wp, table)
        return run

    @jax.jit
    def digest(slab):
        """(entries, slots) sums of a slab and of its magnitudes."""
        s = slab.astype(jnp.float32)
        return jnp.stack([s.sum(axis=(2, 3, 4)), jnp.abs(s).sum(axis=(2, 3, 4))])

    def slab_copies(compiled, shape):
        """``copy`` operations at least as large as one entry of the slab."""
        floor = math.prod(shape[1:])
        return [name for name, dims in re.findall(
            r"%(\S+) = \w+\[([\d,]+)\]\S* copy\(", compiled.as_text())
            if math.prod(map(int, dims.split(","))) >= floor]

    out = {"device": jax.devices()[0].device_kind,
           "hbm_bytes_per_s": HBM_BYTES_PER_S}
    for name, (shape, lbs) in (TINY if args.cpu else SHAPES).items():
        entries, slots, heads, hd, t = shape
        key = jax.random.PRNGKey(len(name))
        new_k, new_v = (jax.random.normal(k, shape[:4], jnp.float32).astype(bf16)
                        for k in jax.random.split(key, 2))
        wp = jnp.asarray(np.random.default_rng(1).integers(0, t, slots), jnp.int32)
        fresh = jax.jit(lambda i: jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32).astype(bf16))
        loads = {"all": np.ones((slots,), bool), "half": np.zeros((slots,), bool)}
        loads["half"][np.random.default_rng(2).choice(
            slots, size=(slots + 1) // 2, replace=False)] = True
        variants = {"loop": loop}
        for lb in lbs:
            variants[f"lb{lb}"] = functools.partial(
                kcw.kv_column_write, lb=lb, interpret=args.cpu)
        reference = both_slabs(kcw.kv_column_reference)
        tile_bytes = 2 * 2 * entries * heads * hd * 128 * 2  # K + V, read + written
        out[name] = {"shape": list(shape), "rule_ms_all_slots": RULE_MS[name],
                     "tile_bytes_a_slot": tile_bytes,
                     "entries_a_block_chosen": kcw.entries_a_block(
                         entries, heads, hd, 2)}
        for load, active in loads.items():
            act = jnp.asarray(active)
            want = [np.asarray(digest(s))
                    for s in reference(fresh(0), fresh(1), new_k, new_v, wp, act)]
            for variant, put in variants.items():
                run = both_slabs(put)
                try:
                    k, v = fresh(0), fresh(1)
                    compiled = run.lower(k, v, new_k, new_v, wp, act).compile()
                    copies = slab_copies(compiled, shape)
                    k, v = compiled(k, v, new_k, new_v, wp, act)
                    got = [np.asarray(digest(s)) for s in (k, v)]
                    # the loop writes the idle slots too: its live slots'
                    rows = active if variant == "loop" else slice(None)
                    equal = all(np.array_equal(g[:, :, rows], w[:, :, rows])
                                for g, w in zip(got, want))
                    t0 = time.perf_counter()
                    for _ in range(args.repeats):
                        k, v = compiled(k, v, new_k, new_v, wp, act)
                    jax.block_until_ready((k, v))
                    ms = 1e3 * (time.perf_counter() - t0) / args.repeats
                    del k, v
                    # the loop moves every slot's tiles, the kernel the live ones'
                    moved = tile_bytes * (slots if variant == "loop"
                                          else int(active.sum()))
                    got = {"ms_both_slabs": ms, "slots_written": moved // tile_bytes,
                           "tile_gb_per_s": moved / (ms / 1e3) / 1e9,
                           "hbm_share_pct": 100 * moved / (ms / 1e3) / HBM_BYTES_PER_S,
                           "slab_sized_copies": copies,
                           "sums_equal_reference": bool(equal)}
                except Exception as e:  # noqa: BLE001 — a refused variant is a reading
                    got = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
                out[name].setdefault(variant, {})[load] = got
                print(name, variant, load, json.dumps(got), flush=True)

    # a block that does not divide the entries, bit for bit on the chip
    shape = (7, 3, 2, 32, 256) if args.cpu else (37, 3, 20, 64, 256)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    slab = jax.random.normal(keys[0], shape, jnp.float32).astype(bf16)
    new = jax.random.normal(keys[1], shape[:4], jnp.float32).astype(bf16)
    wp = jnp.asarray([255, 0, 128], jnp.int32)
    table = live_table(jnp.asarray([True, False, True]))
    lb = 3 if args.cpu else 6
    got = jax.jit(functools.partial(kcw.kv_column_write, lb=lb,
                                    interpret=args.cpu))(slab, new, wp, table)
    want = jax.jit(kcw.kv_column_reference)(slab, new, wp, table)
    out["cut_short_block"] = {"shape": list(shape), "entries_a_block": lb,
                              "bits_equal_reference": bool(jnp.array_equal(got, want))}
    print("cut_short_block", json.dumps(out["cut_short_block"]), flush=True)

    # the registry's probe at several blocks in ONE process, each an
    # executable of its own: with a dynamic grid bound the second halted
    # the core (PERF.md section 6, PR 43)
    probes = ([(2, 16, 2, False), (4, 32, 3, True)] if args.cpu else
              [(20, 64, 6, False), (16, 128, 4, False), (1, 576, 14, True)])
    for heads, hd, lb, ragged in 2 * probes:
        kcw._probe(heads, hd, lb, ragged, jnp.dtype(bf16), args.cpu)
    out["probes_in_one_process"] = 2 * len(probes)
    print("probes_in_one_process", out["probes_in_one_process"], flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

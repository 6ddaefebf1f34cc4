#!/usr/bin/env python
"""Time the latent decode core alone on the chip, at the deepseek-v2-ep8
cell's shapes: six layers' slabs (6, 48, 576, 10240) in bfloat16, 128
heads, one scan over the layers as the decode program has it. The
length-aware kernel (``nn/ops/latent_decode.py``) at several tiles against
the whole-slab einsums, under loads that say what each part costs:

    empty   every slot idle: the grid's own cost, nothing read
    cell12  12 slots busy with ~5.5 k positions (the cell after the kernel)
    cell25  25 slots busy with ~5.5 k positions (the cell before it)
    full    every slot at its whole length

    chiprun -- python scripts/latent_decode_microbench.py \
        --out chiprun_out/latent_microbench.json

One JSON object: per variant and load the milliseconds of six layers, the
share of the HBM roofline of the LIVE bytes, and the largest gap to the
einsums on the rows that hold something. Needs the chip (``--cpu`` is a
rehearsal at a tiny size under the Pallas interpreter: no timing means
anything there).
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


def loads(n_slots, t_c, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)

    def busy(n):
        lengths = np.zeros((n_slots,), np.int32)
        at = rng.choice(n_slots, size=min(n, n_slots), replace=False)
        lengths[at] = np.clip(rng.lognormal(np.log(t_c * 0.5), 0.4, at.size),
                              t_c // 10, t_c - 1).astype(np.int32)
        return lengths

    return {"empty": np.zeros((n_slots,), np.int32),
            "cell12": busy(n_slots // 4), "cell25": busy(n_slots // 2 + 1),
            "full": np.full((n_slots,), t_c, np.int32)}


def six_layers(core):
    """core(q, new, slabs, layer, lengths) -> (slots, heads, rank), scanned
    over the layers of ``slabs``; the layers' outputs are summed so that
    none is dropped."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(q, new, slabs, lengths):
        def body(acc, xs):
            q_l, new_l, layer = xs
            return acc + core(q_l, new_l, slabs, layer,
                              lengths).astype(jnp.float32), None

        n = slabs.shape[0]
        one = jax.eval_shape(core, q[0], new[0], slabs,
                             jnp.zeros((), jnp.int32), lengths)
        acc, _ = jax.lax.scan(
            body, jnp.zeros(one.shape, jnp.float32),
            (q, new, jnp.arange(n, dtype=jnp.int32)))
        return acc

    return run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiles", default="512,1024,2048")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--loads", default="empty,cell12,cell25,full")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.ops import latent_decode as ld

    if args.cpu:
        n_layers, n_slots, heads, width, t_c, rank = 2, 4, 4, 32, 64, 16
        tiles = [8, 16]
    else:
        if jax.default_backend() != "tpu":
            raise SystemExit("no TPU here: times from another backend say "
                             "nothing (--cpu rehearses the control flow)")
        n_layers, n_slots, heads, width, t_c, rank = 6, 48, 128, 576, 10240, 512
        tiles = [int(t) for t in args.tiles.split(",")]
    dt, scale = jnp.bfloat16, 0.1147
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (n_layers, n_slots, heads, width), dt)
    new = jax.random.normal(keys[1], (n_layers, n_slots, width), dt)
    slabs = jax.lax.map(
        lambda k: jax.random.normal(k, (n_slots, width, t_c), dt),
        jax.random.split(keys[2], n_layers))

    variants = {"einsums": functools.partial(
        ld.latent_decode_reference, scale=scale, kv_rank=rank)}
    for tile in tiles:
        variants[f"kernel.t{tile}"] = functools.partial(
            ld.latent_decode_core, scale=scale, kv_rank=rank, tile=tile,
            interpret=args.cpu)

    out = {"device": jax.devices()[0].device_kind, "loads": {}}
    cases = {k: v for k, v in loads(n_slots, t_c).items()
             if k in args.loads.split(",")}
    want = {}
    for name, core in variants.items():
        run = six_layers(core)
        for load, lengths in cases.items():
            lens = jnp.asarray(lengths)
            t0 = time.perf_counter()
            got = np.asarray(run(q, new, slabs, lens))
            first_s = time.perf_counter() - t0
            if name == "einsums":
                want[load] = got
            rows = lengths > 0
            gap = float(np.abs(got - want[load])[rows].max()) if rows.any() else 0.0
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                res = run(q, new, slabs, lens)
            res.block_until_ready()
            ms = 1e3 * (time.perf_counter() - t0) / args.repeats
            live = int(lengths.sum()) * n_layers * width * 2
            out["loads"][load] = {"busy_slots": int(rows.sum()),
                                  "live_positions": int(lengths.sum())}
            out.setdefault(name, {})[load] = {
                "ms_six_layers": ms, "first_call_s": first_s,
                "live_hbm_share_pct": 100 * live / HBM_BYTES_PER_S / (ms / 1e3),
                "max_gap_live_rows": gap}
            print(name, load, json.dumps(out[name][load]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

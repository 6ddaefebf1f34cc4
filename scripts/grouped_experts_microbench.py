#!/usr/bin/env python
"""Time the expert layers' grouped SwiGLU products alone on the chip, at the
four expert cells' decode shapes: a stack of several layers' held experts
(``layers x count`` groups), one scan over the layers with the layer's index
traced, as the decode program has it, M = slots x top-k rows of which the
first few are pairs sorted by expert. Two ways to take the products:

    ragged128   ``moe._ragged_swiglu``: three ``jax.lax.ragged_dot`` (XLA's
                Mosaic grouped kernel, its own tiles (128, 512, 512)), 128
                rows a chunk, the sizes spread over the whole stack: the
                parent's path
    wW.tT       the kernel (``nn/ops/grouped_experts.py``) at a window of W
                rows and tiles of T columns of f

under loads drawn as the cells' steps have them (``moe_pairs_local`` and
``moe_experts_hit`` of the ledger's PR 47 lines: granite 36 of 36 held
experts hit at 4.4 rows, mimo / deepseek / glm a part of the held experts
hit at 1.1-1.3 rows), a heavier one, and every row a pair (what a prefill at
a small bucket gives).

    chiprun -- python scripts/grouped_experts_microbench.py \
        --out chiprun_out/grouped_experts_microbench.json

One JSON object: per cell, load and variant the milliseconds a layer, the
share of 819 GB/s by the bytes ``moe_hbm_share.serve`` counts (the hit
experts' three matrices), and the largest gap to ``ragged128`` over the
pairs' rows. The last stage runs the registry's probe at the four cells'
keys three times in ONE process (a Mosaic kernel whose second executable
halts the core shows there, not in a first call). Needs the chip (``--cpu``
is a rehearsal at a tiny size under the Pallas interpreter: no timing means
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

#: cell -> d, f, held experts a layer, layers of the stack timed, M = slots
#: x top-k, pairs of the loads (the first is the cell's own: pairs a step =
#: ``moe_pairs_per_expert.serve`` x the experts hit), tiles of f tried
CELLS = {
    "granite": dict(d=4096, f=768, count=36, layers=5, m=640,
                    pairs=(160, 320), tiles=(768, 384, 256)),
    "mimo": dict(d=4096, f=2048, count=16, layers=4, m=512,
                 pairs=(8, 32), tiles=(512, 256)),
    "deepseek": dict(d=5120, f=1536, count=20, layers=3, m=288,
                     pairs=(5, 18), tiles=(512, 384, 256)),
    "glm": dict(d=6144, f=2048, count=16, layers=3, m=256,
                pairs=(10, 24), tiles=(512, 256)),
}
TINY = {
    "tiny": dict(d=32, f=48, count=4, layers=2, m=40, pairs=(6, 20),
                 tiles=(48, 16)),
}


def draw_sizes(rng, layers, count, pairs):
    """(layers, count) int32: ``pairs`` pairs a layer, each on one of the
    held experts uniformly (a router over random weights)."""
    import numpy as np

    return np.stack([np.bincount(rng.integers(0, count, size=pairs),
                                 minlength=count)
                     for _ in range(layers)]).astype(np.int32)


def scanned(step, layers):
    """step(rows, layer, sizes) -> (M, d) float32, scanned over the layers
    with the sum as the carry: one program, the stack closed over."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(stack, rows, sizes):
        def body(acc, x):
            layer, r, s = x
            return acc + step(stack, r, layer, s), None

        acc, _ = jax.lax.scan(
            body, jnp.zeros(rows.shape[1:], jnp.float32),
            (jnp.arange(layers, dtype=jnp.int32), rows, sizes))
        return acc

    return run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="granite,mimo,deepseek,glm")
    ap.add_argument("--windows", default="16,32,64")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--probes", type=int, default=3)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DL4J_TPU_GROUPED_EXPERTS"] = "interpret"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.conf.layers.moe import _ragged_swiglu
    from deeplearning4j_tpu.nn.ops import grouped_experts as ge
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    if args.cpu:
        cells, windows, dt = TINY, [16], jnp.bfloat16
    else:
        if jax.default_backend() != "tpu":
            raise SystemExit("no TPU here: times from another backend say "
                             "nothing (--cpu rehearses the control flow)")
        cells = {k: CELLS[k] for k in args.cells.split(",") if k}
        windows = [int(w) for w in args.windows.split(",")]
        dt = jnp.bfloat16
    out = {"device": jax.devices()[0].device_kind, "cells": {}}

    for name, c in cells.items():
        d, f, count, layers, m = c["d"], c["f"], c["count"], c["layers"], c["m"]
        groups = layers * count
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        stack = {
            "Eg": (jax.random.normal(keys[0], (groups, d, f), jnp.float32)
                   / np.sqrt(d)).astype(dt),
            "Eu": (jax.random.normal(keys[1], (groups, d, f), jnp.float32)
                   / np.sqrt(d)).astype(dt),
            "Ed": (jax.random.normal(keys[2], (groups, f, d), jnp.float32)
                   / np.sqrt(f)).astype(dt)}
        rows = jax.random.normal(keys[3], (layers, m, d), jnp.float32).astype(dt)
        expert_bytes = 3 * d * f * jnp.dtype(dt).itemsize

        def ragged(stack, r, layer, s):
            whole = jax.lax.dynamic_update_slice(
                jnp.zeros((groups,), jnp.int32), s, (layer * count,))
            return _ragged_swiglu(r, stack, whole, 128)

        def kernel(stack, r, layer, s, *, window, tile):
            return ge.grouped_experts(
                r, stack["Eg"], stack["Eu"], stack["Ed"], s, layer * count,
                window=window, tile=tile, interpret=args.cpu)

        variants = {"ragged128": ragged}
        for w in windows:
            for t in c["tiles"]:
                variants[f"w{w}.t{t}"] = functools.partial(
                    kernel, window=w, tile=t)
        got_cell = out["cells"].setdefault(name, {
            "shape": {k: c[k] for k in ("d", "f", "count", "layers", "m")}})
        for pairs in list(c["pairs"]) + [m]:
            sizes = draw_sizes(np.random.default_rng(pairs), layers, count,
                               pairs)
            hit = int((sizes > 0).sum())
            s_dev = jnp.asarray(sizes)
            want = None
            for vname, step in variants.items():
                run = scanned(lambda st, r, layer, s, step=step: jnp.where(
                    (jnp.arange(m) < jnp.sum(s))[:, None],
                    step(st, r, layer, s), 0.0), layers)
                try:
                    t0 = time.perf_counter()
                    first = np.asarray(run(stack, rows, s_dev))
                    first_s = time.perf_counter() - t0
                    if want is None:
                        want = first
                    gap = float(np.abs(first - want).max())
                    t0 = time.perf_counter()
                    for _ in range(args.repeats):
                        res = run(stack, rows, s_dev)
                    res.block_until_ready()
                    ms = 1e3 * (time.perf_counter() - t0) / args.repeats / layers
                    got = {"ms_a_layer": ms, "first_call_s": first_s,
                           "experts_hit_a_layer": hit / layers,
                           "hbm_share_pct": 100 * (hit / layers) * expert_bytes
                           / HBM_BYTES_PER_S / (ms / 1e3),
                           "max_gap_to_ragged": gap,
                           "scale": float(np.abs(want).max())}
                except Exception as e:  # noqa: BLE001 — a refused variant is a reading
                    got = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
                got_cell.setdefault(str(pairs), {})[vname] = got
                print(name, pairs, vname, json.dumps(got), flush=True)
        del stack, rows

    # the registry's probe at the cells' keys, several times in one process
    probes = []
    for round_ in range(args.probes):
        default_kernel_registry().reset(ge.NAME)
        ge._probe.cache_clear()   # a passed probe is remembered: run it again
        for name, c in cells.items():
            impl = ge.grouped_experts_impl(c["m"], c["d"], c["f"], c["count"],
                                           dt)
            probes.append({"round": round_, "cell": name,
                           "engaged": impl is not None})
            print("probe", json.dumps(probes[-1]), flush=True)
    out["probes"] = probes
    out["registry"] = default_kernel_registry().snapshot().get(ge.NAME, {})
    print("registry", json.dumps(out["registry"]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""What the limits of a ``hybrid_decoder_lm`` cell are held against, read at
the cell's own size in one process (``tools/latent_gap_readings.py`` for this
family: its ``serve``, ``rows_of`` and ``gap_stats`` are used as they are):

    python3 benchmark/tools/hybrid_gap_readings.py \\
        --workload granite-4.0-h-small-ep2.longform-sessions --seed 1 [--requests 4]
    python3 benchmark/tools/hybrid_gap_readings.py \\
        --rehearse tiny-granite:tiny-longform --seed 1     (CPU, tiny)

The family's server serves ``--requests`` requests of the mix (the longest of
its schedule among them, all sent at once) and is freed. Then, one JSON line
each:

- ``sound``: the served tokens through the harness's own comparison
  (``family.reference_serve``);
- ``wrong_token``: what ONE wrong token reads wherever it falls: at every
  served position the reference's best logit less that of a random other id,
  and less the runner-up's (the least a wrong token can read), as quantiles;
- ``expert_flips``: the program's own layers (``models/decoder_lm.py``,
  bfloat16, the chunked scan) and the reference's (float32, the sequential
  scan) run over prompt + served tokens, and at every served position the
  held experts each chose are compared, layer by layer: the share of
  positions where some layer's sets differ, and the served tokens' gaps among
  those positions and among the others.

The limits go into ``benchmark/limits/<config>.<traffic>.json`` by hand.
"""

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from tools.latent_gap_readings import QUANTILES, gap_stats, padded, rows_of, serve  # noqa: E402


def program_pass(cfg, params, rows, offset, held):
    """The program's layers over each row, one layer a call -> per row (held
    experts chosen (layers, served positions, held) bool, logits at the
    served positions). A layer is ``decoder_lm.block``; what its router
    chose is read where the block hands the mixer's result to its second
    half (``_ffn``), by ``cfg.route()`` on the router's own input, as
    ``moe_dropless_ffn`` calls it."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import decoder_lm as dl

    def one_layer(kind, ffn, bp, stacks, layer, x, q_pos, real):
        chosen, second_half = [], dl._ffn

        def watched(cfg_, ffn_, bp_, x_, mask_, layer_):
            r = dl._rms_norm(x_, bp_["norm2"], cfg.norm_eps).reshape(-1, cfg.d_model)
            z = jnp.matmul(r, bp_["Wr"], precision=jax.lax.Precision.HIGHEST)
            picked, _w = cfg.route()(z, None, cfg.top_k)
            chosen.append(((picked[:, :, None] - offset) == jnp.arange(held)).any(1))
            return second_half(cfg_, ffn_, bp_, x_, mask_, layer_)

        dl._ffn = watched
        try:
            x, _made, _counts = dl.block(cfg, kind, ffn, {**bp, **stacks}, x, q_pos, None, real,
                                         layer if stacks else None)
        finally:
            dl._ffn = second_half
        return x, chosen[0]

    runs = [jax.jit(functools.partial(one_layer, kind, ffn)) for kind, ffn, _n in cfg.segments()]
    head = jax.jit(lambda top, x: dl._head(cfg, top, x))
    top = {k: params[k] for k in ("norm_f", "embed")}
    out = []
    for seq, at, n, n_real in rows:
        q_pos = jnp.arange(seq.size, dtype=jnp.int32)[None]
        real = q_pos < n_real
        x = dl._embed(cfg, params, jnp.asarray(seq)[None])
        masks = []
        for run, (_kind, _ffn, layers), seg in zip(runs, cfg.segments(), params["segments"]):
            stacks = {k: seg[k] for k in dl.EXPERT_STACKS if k in seg}
            for j in range(layers):
                bp = {k: v[j] for k, v in seg.items() if k not in stacks}
                x, chosen = run(bp, stacks, jnp.asarray(j, jnp.int32), x, q_pos, real)
                masks.append(np.asarray(chosen)[at[:n]])
        out.append((np.stack(masks), np.asarray(head(top, x[0, at]))[:n]))
    return out


def reference_pass(config, seed, rows, ref, offset, held):
    """The reference's layers over each row (float32, a layer's weights made,
    applied to every row and dropped) -> per row (held experts chosen, logits
    at the served positions)."""
    import jax

    cfg = ref._Frozen(config)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]

    @functools.partial(jax.jit, static_argnums=(0,))
    def one_layer(ssm, w, x):
        mixer = ref.mamba if ssm else ref.attention
        h = x + r * mixer(cfg, w, ref.rms_norm(x, w["norm1"], eps), "float32")
        m = ref.rms_norm(h, w["norm2"], eps)
        chosen = ref.route(cfg, w, m)[:, offset:offset + held] > 0
        return h + r * ref.experts(cfg, w, m, "float32"), chosen

    top = ref.make_top(cfg, seed)
    xs = [ref.embed(cfg, top, seq) for seq, _at, _n, _real in rows]
    masks = [[] for _ in rows]
    for i in range(ref.n_layers(cfg)):
        w = ref.make_layer(cfg, seed, i)
        for k, (_seq, at, n, _real) in enumerate(rows):
            xs[k], chosen = one_layer(ref.is_ssm(cfg, i), w, xs[k])
            masks[k].append(np.asarray(chosen)[at[:n]])
        del w
    return [(np.stack(m), np.asarray(ref._head_jit(cfg, top, x[at], "float32"))[:n])
            for m, x, (_seq, at, n, _real) in zip(masks, xs, rows)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--requests", type=int, default=None, help="the mix's check_requests unless given")
    args = ap.parse_args()

    import run as bench_run

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        names = dict(zip(("config", "traffic"), args.rehearse.split(":")))
        config, traffic = bench_run.load_cell_files(names)
    else:
        _cell, config, traffic = bench_run.load_cell(
            bench_run.load_json(bench_run.ROOT, "BENCHMARK.json"), args.workload)
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("hybrid_gap_readings: needs the TPU")
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    family = bench_run.load_module("families", config["family"])
    kind = bench_run.load_module("kinds", traffic["kind"])
    from reference import granite_hybrid as ref

    samples, cfg, params = serve(family, kind, config, traffic, args.seed,
                                 args.requests or traffic["check_requests"])
    rows = rows_of(samples, padded(config, traffic, ref), traffic["answer_len"]["max"])
    offset, held = ref.experts_held(config)
    program = program_pass(cfg, params, rows, offset, held)
    del params

    sound = family.reference_serve(config, traffic, args.seed, samples)
    print(json.dumps({"reading": "sound", "through": "reference_serve", **sound}), flush=True)

    rng = np.random.default_rng(args.seed)
    vocab = family.vocab_size(config)
    reference = reference_pass(config, args.seed, rows, ref, offset, held)
    logits = np.concatenate([lg for _m, lg in reference])
    served = np.concatenate([np.asarray(s["tokens"]) for s in samples])
    best = logits.max(-1)
    served_gap = best - logits[np.arange(served.size), served]
    others = (served[:, None] + rng.integers(1, vocab, (served.size, 16))) % vocab
    random_gap = (best[:, None] - np.take_along_axis(logits, others, axis=1)).ravel()
    runner_up = best - np.partition(logits, -2, axis=-1)[:, -2]
    print(json.dumps({
        "reading": "wrong_token", "positions": int(served.size),
        "sound_widest_by_this_pass": float(served_gap.max()),
        "random_other_id": {**{f"p{q}": float(np.percentile(random_gap, q)) for q in QUANTILES},
                            "mean": float(random_gap.mean())},
        "runner_up": {**{f"p{q}": float(np.percentile(runner_up, q)) for q in QUANTILES},
                      "mean": float(runner_up.mean())}}), flush=True)

    differs = np.concatenate([(pm != rm).any(-1) for (pm, _), (rm, _) in zip(program, reference)],
                             axis=1)                      # (layers, served positions)
    flipped = differs.any(0)
    print(json.dumps({
        "reading": "expert_flips", "positions": int(served.size),
        "share_of_positions_with_a_flip": float(flipped.mean()),
        "share_by_layer": [float(d.mean()) for d in differs],
        "served_tokens": {"flipped": gap_stats(served_gap[flipped]),
                          "not_flipped": gap_stats(served_gap[~flipped])},
        "widest_ten_served_gaps": [{"gap": float(served_gap[i]), "flipped": bool(flipped[i]),
                                    "layers_flipped": int(differs[:, i].sum())}
                                   for i in np.argsort(-served_gap)[:10]]}), flush=True)


if __name__ == "__main__":
    main()

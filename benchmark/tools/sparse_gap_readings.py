"""What the limits of a ``sparse_latent_decoder_lm`` cell are held against,
read at the cell's own size in one process: where the program's SELECTION
differs from the reference's, and what a served token's gap reads there.

    python3 benchmark/tools/sparse_gap_readings.py \\
        --workload glm-5.2-ep16.longctx-agent --seed 1 [--requests 4]
    python3 benchmark/tools/sparse_gap_readings.py \\
        --rehearse tiny-glm:tiny-longctx --seed 1           (CPU, tiny)

A selection is a discrete choice, as an expert's is: the program scores in
float32 what it projected in bfloat16, the reference in float32 throughout,
so at the margin of the top ``index_topk`` the two may keep other positions,
and every layer that attends by that selection then reads other entries.
The family's server serves ``--requests`` requests of the mix (the longest
of its schedule among them, all sent at once; ``tools/latent_gap_readings.py``'s
``serve``) and is freed. Then, one JSON line each:

- ``sound``: the served tokens through the harness's own comparison
  (``family.reference_serve``);
- ``selection_flips``: the program's own layers (``models/decoder_lm.py``,
  the configuration's dtype, the prefill form: the selection as a mask) and
  the reference's (float32) run over prompt + served tokens, and at every
  served position the two selections of every layer that owns an indexer
  are compared: the share of positions where the sets differ, by how many
  entries (of ``index_topk``), and the served tokens' gaps among those
  positions and among the others; the held experts chosen are compared
  alike (``expert_flips``), since both flips move a logit;
- ``wrong_token``: what ONE wrong token reads wherever it falls, from the
  same pass: at every served position the reference's best logit less that
  of a random other id, and less the runner-up's (the least a wrong token
  can read), and the share of random wrong tokens each limit of
  ``LIMITS_TRIED`` refuses.

The limits go into ``benchmark/limits/<config>.<traffic>.json`` by hand.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

LIMITS_TRIED = (1.5, 2.0, 2.5, 3.0, 4.0, 5.0)


def program_pass(cfg, params, rows, offset, held):
    """The program's layers over each row, one layer a call -> per row
    (selections at the served positions (owning layers, served, T) bool,
    held experts chosen (expert layers, served, held) bool)."""
    import functools

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import decoder_lm as dl

    def one_layer(kind, ffn, bp, stacks, layer, x, q_pos, real, sel):
        h, _made, sel = dl._sparse_latent_attention(cfg, kind, bp, x, q_pos, None, sel,
                                                    jnp.sum(real))
        chosen = jnp.zeros((x.shape[1], held), bool)
        if ffn == "experts":
            r = dl._rms_norm(h, bp["norm2"], cfg.norm_eps).reshape(-1, cfg.d_model)
            z = jnp.matmul(r, bp["Wr"], precision=jax.lax.Precision.HIGHEST)
            picked, _w = cfg.route()(z, bp["br"], cfg.top_k)
            chosen = ((picked[:, :, None] - offset) == jnp.arange(held)).any(1)
        x, _ = dl._ffn(cfg, ffn, {**bp, **stacks}, h, real, layer if stacks else None)
        return x, sel, chosen

    runs = [jax.jit(functools.partial(one_layer, kind, ffn)) for kind, ffn, _n in cfg.segments()]
    out = []
    for seq, at, n, n_real in rows:
        q_pos = jnp.arange(seq.size, dtype=jnp.int32)[None]
        real = q_pos < n_real
        x = dl._embed(cfg, params, jnp.asarray(seq)[None])
        sel, selections, experts = None, [], []
        for run, (kind, ffn, layers), seg in zip(runs, cfg.segments(), params["segments"]):
            stacks = {k: seg[k] for k in dl.EXPERT_STACKS if k in seg}
            for j in range(layers):
                bp = {k: v[j] for k, v in seg.items() if k not in stacks}
                x, sel, chosen = run(bp, stacks, jnp.asarray(j, jnp.int32), x, q_pos, real, sel)
                if cfg.attn_kinds[kind]["index"]["own"] and sel is not None:
                    selections.append(np.asarray(sel[0, at[:n]]))
                if ffn == "experts":
                    experts.append(np.asarray(chosen)[at[:n]])
        out.append((np.stack(selections), np.stack(experts)))
    return out


def reference_pass(config, seed, rows, ref, offset, held):
    """The reference's layers over each row (float32, a layer's weights
    made, applied to every row and dropped) -> per row (selections at the
    served positions, held experts chosen, logits at the served positions)."""
    import jax

    cfg = ref._Frozen(config)
    eps = cfg["rms_norm_eps"]

    def one_layer(index, w, x, selected):
        a, selected = ref.attention(cfg, ref.owns_indexer(cfg, index), w,
                                    ref.rms_norm(x, w["norm1"], eps), "float32", selected)
        h = x + a
        m = ref.rms_norm(h, w["norm2"], eps)
        if ref.is_dense(cfg, index):
            return h + ref.swiglu(m, w["mlp.gate"], w["mlp.up"], w["mlp.down"], "float32"), \
                selected, None
        chosen = ref.route(cfg, w, m)[:, offset:offset + held] > 0
        return h + ref.experts(cfg, w, m, "float32"), selected, chosen

    top = ref.make_top(cfg, seed)
    xs = [top["embed"][np.asarray(seq)] for seq, _at, _n, _real in rows]
    chosen_sets = [None] * len(rows)
    selections, experts = [[] for _ in rows], [[] for _ in rows]
    for i in range(ref.n_layers(cfg)):
        w = ref.make_layer(cfg, seed, i)
        run = jax.jit(lambda w, x, s, i=i: one_layer(i, w, x, s))
        for r, (_seq, at, n, _real) in enumerate(rows):
            xs[r], chosen_sets[r], chosen = run(w, xs[r], chosen_sets[r])
            if ref.owns_indexer(cfg, i) and chosen_sets[r] is not None:
                selections[r].append(np.asarray(chosen_sets[r][at[:n]]))
            if chosen is not None:
                experts[r].append(np.asarray(chosen)[at[:n]])
        del w
    return [(np.stack(s), np.stack(e), np.asarray(ref._head_jit(cfg, top, x[at], "float32"))[:n])
            for s, e, x, (_seq, at, n, _real) in zip(selections, experts, xs, rows)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--requests", type=int, default=None, help="the mix's check_requests unless given")
    args = ap.parse_args()

    import run as bench_run
    from tools import latent_gap_readings as base

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        names = dict(zip(("config", "traffic"), args.rehearse.split(":")))
        config, traffic = bench_run.load_cell_files(names)
    else:
        _cell, config, traffic = bench_run.load_cell(
            bench_run.load_json(bench_run.ROOT, "BENCHMARK.json"), args.workload)
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("sparse_gap_readings: needs the TPU")
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    family = bench_run.load_module("families", config["family"])
    kind = bench_run.load_module("kinds", traffic["kind"])
    from reference import glm_dsa as ref

    samples, cfg, params = base.serve(family, kind, config, traffic, args.seed,
                                      args.requests or traffic["check_requests"])
    rows = base.rows_of(samples, base.padded(config, traffic, ref), traffic["answer_len"]["max"])
    offset, held = ref.experts_held(config)
    program = program_pass(cfg, params, rows, offset, held)
    del params

    sound = family.reference_serve(config, traffic, args.seed, samples)
    print(json.dumps({"reading": "sound", "through": "reference_serve", **sound}), flush=True)

    reference = reference_pass(config, args.seed, rows, ref, offset, held)
    logits = np.concatenate([lg for _s, _e, lg in reference])
    served = np.concatenate([np.asarray(s["tokens"]) for s in samples])
    served_gap = logits.max(-1) - logits[np.arange(served.size), served]
    # entries of the program's set that the reference's lacks, a layer and served position
    apart = np.concatenate([(ps & ~rs).sum(-1) for (ps, _), (rs, _e, _l) in zip(program, reference)],
                           axis=1)
    experts = np.concatenate([(pe != re).any(-1) for (_, pe), (_s, re, _l) in zip(program, reference)],
                             axis=1).any(0)
    flipped = (apart > 0).any(0)
    topk = ref.index_dims(config)[2]
    print(json.dumps({
        "reading": "selection_flips", "positions": int(served.size), "index_topk": topk,
        "share_of_positions_with_another_selection": float(flipped.mean()),
        "share_by_owning_layer": [float((a > 0).mean()) for a in apart],
        "entries_apart_where_apart": {
            "mean": float(apart[apart > 0].mean()) if flipped.any() else 0.0,
            "most": int(apart.max())},
        "share_of_positions_with_an_expert_flip": float(experts.mean()),
        "served_tokens": {
            "another_selection": base.gap_stats(served_gap[flipped]),
            "same_selection": base.gap_stats(served_gap[~flipped]),
            "same_selection_and_experts": base.gap_stats(served_gap[~flipped & ~experts])},
        "widest_ten_served_gaps": [
            {"gap": float(served_gap[i]), "entries_apart": int(apart[:, i].sum()),
             "expert_flip": bool(experts[i])} for i in np.argsort(-served_gap)[:10]]}), flush=True)

    rng = np.random.default_rng(args.seed)
    vocab = family.vocab_size(config)
    best = logits.max(-1)
    others = (served[:, None] + rng.integers(1, vocab, (served.size, 16))) % vocab
    random_gap = (best[:, None] - np.take_along_axis(logits, others, axis=1)).ravel()
    runner_up = best - np.partition(logits, -2, axis=-1)[:, -2]
    print(json.dumps({
        "reading": "wrong_token", "positions": int(served.size),
        "random_other_id": {**{f"p{q}": float(np.percentile(random_gap, q)) for q in base.QUANTILES},
                            "mean": float(random_gap.mean())},
        "runner_up": {**{f"p{q}": float(np.percentile(runner_up, q)) for q in base.QUANTILES},
                      "mean": float(runner_up.mean())},
        "share_of_random_wrong_tokens_refused_at_limit": {
            str(lim): float((random_gap > lim).mean()) for lim in LIMITS_TRIED}}), flush=True)


if __name__ == "__main__":
    main()

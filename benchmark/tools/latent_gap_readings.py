"""What the widest-gap limit of a ``latent_decoder_lm`` cell is held
against, read at the cell's own size in one process:

    python3 benchmark/tools/latent_gap_readings.py \\
        --workload deepseek-v2-ep8.longdoc-reason --seed 1 [--requests 4]
    python3 benchmark/tools/latent_gap_readings.py \\
        --rehearse tiny-deepseek:tiny-longdoc --seed 1     (CPU, tiny)

The family's server serves ``--requests`` requests of the mix (the longest
of its schedule among them, all sent at once) and is freed. Then, one JSON
line each:

- ``sound``: the served tokens through the harness's own comparison
  (``family.reference_serve``);
- ``planted``: the same with the LAST served token of every request
  replaced by a seeded wrong id (nothing follows it, so the reference sees
  the context the program saw), through the function ``reference_serve``
  reduces (``served_token_gaps``), and the gap each planted token read;
- ``wrong_token``: what ONE wrong token reads wherever it falls: at every
  served position the reference's best logit less that of a random other
  id, and less the runner-up's (the least a wrong token can read), as
  quantiles, with the share that a limit would refuse;
- ``expert_flips``: the cause of a wide sound gap. The program's own layers
  (``models/decoder_lm.py``, bfloat16, expanded form) and the reference's
  (float32) run over prompt + served tokens, and at every served position
  the held experts each chose are compared, layer by layer: the share of
  positions where some layer's sets differ, and the served tokens' gaps
  among those positions and among the others.

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

QUANTILES = (0, 1, 5, 25, 50, 75)
LIMITS_TRIED = (3.0, 3.5, 4.0, 4.5, 5.0, 6.0)


def serve(family, kind, config, traffic, seed, n):
    """``n`` requests of the mix's schedule, the longest among them, sent
    at once to a fresh server -> (samples as ``reference_serve`` takes
    them, the program's configuration and parameters). The server's cache
    is freed; the parameters stay for the program's own pass."""
    from lib import arrivals

    plan = arrivals.plan(traffic, seed, 40.0, family.vocab_size(config))
    order = sorted(range(len(plan)), key=lambda i: -(len(plan[i]["prompt"]) + plan[i]["max_new"]))
    rest = np.random.default_rng(seed).permutation(order[1:])[: n - 1]
    requests = [dict(plan[i], due=0.0) for i in [order[0], *rest]]
    server = family.Server(config, traffic, seed)
    try:
        cfg, params = server.model.cfg, server.model.params_
        # the client returns as soon as every request has ended
        results = kind.drive(server, requests, 1.0,
                             60.0 + 0.1 * max(r["max_new"] for r in requests))["results"]
    finally:
        server.close()
    failed = [r["error"] or "unfinished" for r in results if r["error"] or not r["done"]]
    if failed:
        raise SystemExit(f"latent_gap_readings: requests failed: {failed}")
    return ([{"prompt": q["prompt"], "tokens": r["tokens"]} for q, r in zip(requests, results)],
            cfg, params)


def padded(config, traffic, ref):
    longest = traffic["prompt_len"]["max"] + traffic["answer_len"]["max"]
    return -(-longest // ref.QUERY_BLOCK) * ref.QUERY_BLOCK


def rows_of(samples, pad_to, answers_pad):
    """(ids padded to ``pad_to``, positions whose logits chose a served
    token padded to ``answers_pad``, served tokens, real positions) a
    sample, as ``served_token_gaps`` lays them: one shape for every row."""
    out = []
    for s in samples:
        full = list(s["prompt"]) + list(s["tokens"])
        seq = np.zeros((pad_to,), np.int32)
        seq[: len(full) - 1] = full[:-1]
        at = np.zeros((answers_pad,), np.int32)
        at[: len(s["tokens"])] = len(s["prompt"]) - 1 + np.arange(len(s["tokens"]))
        out.append((seq, at, len(s["tokens"]), len(full) - 1))
    return out


def program_pass(cfg, params, rows, offset, held):
    """The program's layers over each row, one layer a call -> per row
    (held experts chosen (layers with experts, served positions, held)
    bool, logits at the served positions). The routing is
    ``cfg.route()`` on the router's own input, as ``moe_dropless_ffn``
    calls it."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import decoder_lm as dl

    def one_layer(kind, ffn, bp, stacks, layer, x, q_pos, real):
        x, _ = dl._latent_attention(cfg, kind, bp, x, q_pos, None, jnp.sum(real))
        chosen = jnp.zeros((x.shape[1], held), bool)
        if ffn == "experts":
            r = dl._rms_norm(x, bp["norm2"], cfg.norm_eps).reshape(-1, cfg.d_model)
            z = jnp.matmul(r, bp["Wr"], precision=jax.lax.Precision.HIGHEST)
            picked, _w = cfg.route()(z, None, cfg.top_k)
            chosen = ((picked[:, :, None] - offset) == jnp.arange(held)).any(1)
        x, _ = dl._ffn(cfg, ffn, {**bp, **stacks}, x, real, layer if stacks else None)
        return x, chosen

    runs = [jax.jit(functools.partial(one_layer, kind, ffn)) for kind, ffn, _n in cfg.segments()]
    head = jax.jit(lambda top, x: dl._head(cfg, top, x))
    top = {k: params[k] for k in ("norm_f", "head")}
    out = []
    for seq, at, n, n_real in rows:
        q_pos = jnp.arange(seq.size, dtype=jnp.int32)[None]
        real = q_pos < n_real
        x = dl._embed(cfg, params, jnp.asarray(seq)[None])
        masks = []
        for run, (_kind, ffn, layers), seg in zip(runs, cfg.segments(), params["segments"]):
            stacks = {k: seg[k] for k in dl.EXPERT_STACKS if k in seg}
            for j in range(layers):
                bp = {k: v[j] for k, v in seg.items() if k not in stacks}
                x, chosen = run(bp, stacks, jnp.asarray(j, jnp.int32), x, q_pos, real)
                if ffn == "experts":
                    masks.append(np.asarray(chosen)[at[:n]])
        out.append((np.stack(masks), np.asarray(head(top, x[0, at]))[:n]))
    return out


def reference_pass(config, seed, rows, ref, offset, held):
    """The reference's layers over each row (float32, a layer's weights
    made, applied to every row and dropped) -> per row (held experts
    chosen, logits at the served positions)."""
    import jax

    cfg = ref._Frozen(config)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def expert_layer(w, x):
        h = x + ref.attention(cfg, w, ref.rms_norm(x, w["norm1"], eps), "float32")
        m = ref.rms_norm(h, w["norm2"], eps)
        chosen = ref.route(cfg, w, m)[:, offset:offset + held] > 0
        return h + ref.experts(cfg, w, m, "float32"), chosen

    top = ref.make_top(cfg, seed)
    xs = [top["embed"][np.asarray(seq)] for seq, _at, _n, _real in rows]
    masks = [[] for _ in rows]
    for i in range(ref.n_layers(cfg)):
        w = ref.make_layer(cfg, seed, i)
        for r, (_seq, at, n, _real) in enumerate(rows):
            if ref.is_dense(cfg, i):
                xs[r] = ref._layer_jit(cfg, i, w, xs[r], "float32")
            else:
                xs[r], chosen = expert_layer(w, xs[r])
                masks[r].append(np.asarray(chosen)[at[:n]])
        del w
    return [(np.stack(m), np.asarray(ref._head_jit(cfg, top, x[at], "float32"))[:n])
            for m, x, (_seq, at, n, _real) in zip(masks, xs, rows)]


def gap_stats(gaps):
    if not gaps.size:
        return {"positions": 0}
    return {"positions": int(gaps.size), "widest": float(gaps.max()), "mean": float(gaps.mean()),
            "below_best_share": float((gaps > 0).mean())}


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
        raise SystemExit("latent_gap_readings: needs the TPU")
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    family = bench_run.load_module("families", config["family"])
    kind = bench_run.load_module("kinds", traffic["kind"])
    from reference import deepseek_v2 as ref

    samples, cfg, params = serve(family, kind, config, traffic, args.seed,
                                 args.requests or traffic["check_requests"])
    answers_pad = traffic["answer_len"]["max"]
    rows = rows_of(samples, padded(config, traffic, ref), answers_pad)
    offset, held = ref.experts_held(config)
    program = program_pass(cfg, params, rows, offset, held)
    del params

    sound = family.reference_serve(config, traffic, args.seed, samples)
    print(json.dumps({"reading": "sound", "through": "reference_serve", **sound}), flush=True)

    rng = np.random.default_rng(args.seed)
    vocab = family.vocab_size(config)
    wrong = [dict(s, tokens=s["tokens"][:-1] + [int((s["tokens"][-1] + rng.integers(1, vocab)) % vocab)])
             for s in samples]
    # what ``reference_serve`` reduces, token by token
    gaps = ref.served_token_gaps(config, args.seed, wrong, pad_to=rows[0][0].size,
                                 answers_pad=answers_pad)["served"]
    last = np.cumsum([len(s["tokens"]) for s in samples]) - 1
    print(json.dumps({"reading": "planted", "through": "served_token_gaps",
                      "served_logit_gap": float(gaps.max()),
                      "served_logit_gap_mean": float(gaps.mean()),
                      "planted_tokens_read": [float(g) for g in gaps[last]]}), flush=True)

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
                      "mean": float(runner_up.mean())},
        "share_of_random_wrong_tokens_refused_at_limit": {
            str(lim): float((random_gap > lim).mean()) for lim in LIMITS_TRIED}}), flush=True)

    differs = np.concatenate([(pm != rm).any(-1) for (pm, _), (rm, _) in zip(program, reference)],
                             axis=1)                      # (expert layers, served positions)
    flipped = differs.any(0)
    forward = np.concatenate([lg for _m, lg in program]).argmax(-1)
    forward_gap = best - logits[np.arange(served.size), forward]
    print(json.dumps({
        "reading": "expert_flips", "positions": int(served.size),
        "share_of_positions_with_a_flip": float(flipped.mean()),
        "share_by_expert_layer": [float(d.mean()) for d in differs],
        "served_tokens": {"flipped": gap_stats(served_gap[flipped]),
                          "not_flipped": gap_stats(served_gap[~flipped])},
        "program_forward_argmax": {"flipped": gap_stats(forward_gap[flipped]),
                                   "not_flipped": gap_stats(forward_gap[~flipped])},
        "widest_ten_served_gaps": [{"gap": float(served_gap[i]), "flipped": bool(flipped[i]),
                                    "layers_flipped": int(differs[:, i].sum())}
                                   for i in np.argsort(-served_gap)[:10]]}), flush=True)


if __name__ == "__main__":
    main()

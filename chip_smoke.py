#!/usr/bin/env python
"""Proof that the two main paths — a trainer that takes steps and a
server that answers requests — run on one TPU v5e through the entry
points a user calls, at the full width of models the repo supports.

    python chip_smoke.py              one chip: device, lm_train, lm_serve,
                                      hybrid_serve, sparse_serve,
                                      looped_serve, parallel_serve,
                                      resnet_train, resnet_serve, kernels
    python chip_smoke.py --chips 4    the cross-chip paths only: the
                                      DistributedLMTrainer on a 2x2 mesh and
                                      tensor-parallel serving on 1x4, each
                                      against its single-device reference
    python chip_smoke.py --rehearse [--chips 4]
                                      the same phases and control flow at a
                                      tiny size on the CPU (no chip proof:
                                      its last line says so)

One process owns the chip: everything runs here, nothing is spawned.
Every phase prints one JSON line; a phase that fails raises and the
exit code is non-zero. The last line of a successful chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import gc
import http.client
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
# jax and the package are imported inside the functions: --rehearse has to
# pin the platform before the first import of jax.

# -- sizes -------------------------------------------------------------------
FULL = {
    # the LM of bench.py's train cell and ROADMAP S1: GPT-2-small widths
    "lm": dict(vocab_size=32000, d_model=768, n_heads=12, n_layers=12,
               max_length=512, compute_dtype="bfloat16"),
    "lm_batch": 16, "lm_steps": 5,
    "slots": 4, "requests": 6, "prompt_len": (64, 128), "max_new": 32,
    "resnet": dict(num_classes=1000, compute_dtype="bfloat16"),
    "image": 224, "resnet_batch": 128, "resnet_steps": 3,
    "serve_model": "resnet50",
    # --chips 4: float32 so that a tolerance means something (the
    # package pins matmul precision "highest")
    "lm4": dict(vocab_size=32000, d_model=768, n_heads=12, n_layers=4,
                max_length=512),
    "lm4_batch": 8, "lm4_steps": 3,
    "mlp4": (768, 3072, 768, 1000), "mlp4_rows": 32,
    # the deepseek-v2-ep8 cell's latent decode core: 128 heads over
    # entries of 512 + 64 values, slots of 10,240
    "latent_core": dict(heads=128, width=576, t_c=10240, dtype="bfloat16",
                        kv_rank=512),
    # the glm-5.2-ep16 cell's attention over a selection: 64 heads over rows
    # of 640 (512 + 64 values, the tail zero), slots of 14,336, 2,048 kept
    "sparse_core": dict(heads=64, width=640, t_c=14336, topk=2048,
                        dtype="bfloat16", kv_rank=512),
    # the granite-4.0-h-small-ep2 cell's state-space decode step: 64 slots
    # of 128 heads x 64 x 128 float32 a layer, one group of B and C
    "ssm_step": dict(heads=128, p=64, n=128, groups=1, slots=64,
                     dtype="float32"),
    # the falcon-h1-34b-l6.docqa-steady cell's three kernel keys: the
    # state-space step at 48 slots of 32 heads x 128 x 256 float32 in two
    # groups, decode attention at 48 slots of 4,096 with FIVE query heads a
    # key head (4 of 128), and the column write at its six-entry slab
    "parallel_keys": {
        "ssm_decode_step": dict(heads=32, p=128, n=256, groups=2, slots=48,
                                dtype="float32"),
        "decode_attention": dict(slots=48, hkv=4, grp=5, hd=128, vd=128,
                                 t=4096, dtype="bfloat16"),
        "kv_column_write": dict(entries=6, slots=48, heads=4, head_size=128,
                                t=4096, dtype="bfloat16")},
    # attention and a Mamba-2 mixer side by side in every block at a
    # middling size (20 query heads on 4 key heads of 64: five a key head;
    # 8 state-space heads of 64 over a state of 64 in two groups, an inner
    # width of 512 that is not expand x hidden; the published multipliers),
    # float32 so that equal tokens mean something
    "parallel": dict(
        vocab_size=1024, d_model=640, n_heads=20, head_dim=64, v_head_dim=64,
        rotary_dim=64,
        attn_kinds={"parallel": {
            "parallel": True, "n_kv_heads": 4, "rope_theta": 1e11,
            "key_multiplier": 0.011048543456039804, "out_multiplier": 0.0375,
            "ssm": dict(n_heads=8, head_dim=64, d_state=64, n_groups=2,
                        d_conv=4, expand=2, chunk=8, d_inner=512,
                        in_multiplier=0.25, out_multiplier=0.0884,
                        multipliers=[0.354, 0.25, 0.177, 0.5, 0.354])}},
        layers=[("parallel", "dense")] * 3, dense_width=1280, max_length=128,
        param_dtype="float32", embedding_multiplier=5.657,
        mlp_multipliers=[0.177, 0.0112], logits_scaling=128.0),
    # the cache's column write at the gpt2-large.chat cell's slab (36
    # layers, 24 slots of 1,024, 20 heads of 64) and at the
    # ouro-2.6b.reason-looped cell's (192 (pass, layer) entries, 5 slots of
    # 896, 16 heads of 128)
    "kv_columns": [
        dict(entries=36, slots=24, heads=20, head_size=64, t=1024,
             dtype="bfloat16"),
        dict(entries=192, slots=5, heads=16, head_size=128, t=896,
             dtype="bfloat16")],
    # decode attention over the live tiles at the gpt2-large.chat cell's
    # slabs (24 slots of 1,024, 20 heads of 64) and at the
    # granite-4.0-h-small-ep2 cell's (64 slots of 4,096, 8 key/value heads
    # of 128 under 32 query heads): two probes in one process
    "decode_attn": [
        dict(slots=24, hkv=20, grp=1, hd=64, vd=64, t=1024,
             dtype="bfloat16"),
        dict(slots=64, hkv=8, grp=4, hd=128, vd=128, t=4096,
             dtype="bfloat16")],
    # the expert layers' grouped products of a decode step at the four
    # expert cells' keys (granite, mimo, deepseek, glm): slots x top-k rows,
    # hidden x expert width, the experts held a layer: four probes in one
    # process
    "grouped_experts": [
        dict(m=640, d=4096, f=768, count=36, dtype="bfloat16"),
        dict(m=512, d=4096, f=2048, count=16, dtype="bfloat16"),
        dict(m=288, d=5120, f=1536, count=20, dtype="bfloat16"),
        dict(m=256, d=6144, f=2048, count=16, dtype="bfloat16")],
    # the tiny state-space hybrid of tests/test_granite_lm.py (two Mamba-2
    # layers, a NoPE attention layer, another Mamba-2 layer; experts and a
    # shared expert in each), float32 so that equal tokens mean something
    "hybrid": dict(
        vocab_size=256, d_model=64, n_heads=4, head_dim=16, v_head_dim=16,
        rotary_dim=0,
        attn_kinds={"ssm": {"ssm": dict(n_heads=8, head_dim=16, d_state=16,
                                        n_groups=1, d_conv=4, expand=2,
                                        chunk=8)},
                    "attention": {"n_kv_heads": 2, "rope_theta": 1e4}},
        layers=[("ssm", "experts")] * 2 + [("attention", "experts"),
                                           ("ssm", "experts")],
        dense_width=0, expert_width=32, n_experts=8, top_k=3,
        experts_held=(4, 4), shared_width=48, max_length=128,
        routing={"n_group": 1, "topk_group": 1, "renormalise": True},
        param_dtype="float32", embedding_multiplier=12,
        residual_multiplier=0.22, attention_multiplier=0.0625,
        logits_scaling=16, tied_head=True),
    # latent attention over an indexer's selection at a middling size (a
    # latent entry of 96 + 32 values = one tile of lanes, 4 indexer heads of
    # 64 that keep 16 positions): a dense layer that owns the indexer, two
    # expert layers that share its selection, an expert layer that owns one;
    # float32 so that equal tokens mean something
    "sparse": dict(
        vocab_size=512, d_model=256, n_heads=4, head_dim=64, v_head_dim=32,
        rotary_dim=32,
        attn_kinds={
            kind: {"rope_theta": 1e4, "latent": {"q_rank": 64, "kv_rank": 96},
                   "index": {"heads": 4, "head_dim": 64, "topk": 16,
                             "own": own}}
            for kind, own in (("indexed", True), ("shared", False))},
        layers=[("indexed", "dense"), ("shared", "experts"),
                ("shared", "experts"), ("indexed", "experts")],
        dense_width=512, expert_width=128, n_experts=8, top_k=2,
        experts_held=(4, 4), shared_width=128, max_length=128,
        routing={"scoring": "sigmoid", "scale": 2.5}, param_dtype="float32"),
    # a looped decoder at Ouro-2.6B's published widths and three of its 48
    # layers: the stack run four times a token over one set of weights, a
    # cache entry a (pass, layer), sandwich norms, the exit gate; float32
    # so that equal tokens mean something
    "looped": dict(
        vocab_size=49152, d_model=2048, n_heads=16, head_dim=128,
        v_head_dim=128, rotary_dim=128,
        attn_kinds={"full": {"n_kv_heads": 16, "rope_theta": 1e6}},
        layers=[("full", "dense")] * 3, dense_width=5632, norm_eps=1e-6,
        max_length=128, param_dtype="float32", passes=4,
        sandwich_norm=True, exit_gate=True),
}
TINY = {
    "lm": dict(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
               max_length=128, compute_dtype="bfloat16"),
    "lm_batch": 4, "lm_steps": 5,
    "slots": 4, "requests": 6, "prompt_len": (8, 16), "max_new": 8,
    "resnet": dict(num_classes=10, compute_dtype="bfloat16"),
    "image": 32, "resnet_batch": 4, "resnet_steps": 3,
    "serve_model": "lenet",
    "lm4": dict(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
                max_length=128),
    "lm4_batch": 4, "lm4_steps": 3,
    "mlp4": (32, 64, 32, 8), "mlp4_rows": 8,
    "latent_core": dict(heads=4, width=32, t_c=64, dtype="float32",
                        kv_rank=16),
    "sparse_core": dict(heads=4, width=128, t_c=64, topk=8, dtype="float32",
                        kv_rank=96),
    "ssm_step": dict(heads=8, p=16, n=16, groups=1, slots=3,
                     dtype="float32"),
    "kv_columns": [
        dict(entries=2, slots=4, heads=4, head_size=16, t=128,
             dtype="bfloat16"),
        dict(entries=12, slots=3, heads=2, head_size=32, t=128,
             dtype="float32")],
    # (slabs this small are under the kernel's floor: nothing resolves)
    "decode_attn": [
        dict(slots=4, hkv=4, grp=1, hd=16, vd=16, t=128, dtype="bfloat16"),
        dict(slots=3, hkv=2, grp=4, hd=32, vd=16, t=128, dtype="float32")],
    "grouped_experts": [
        dict(m=24, d=32, f=48, count=4, dtype="float32"),
        dict(m=40, d=64, f=32, count=2, dtype="bfloat16")],
}
TINY["hybrid"] = FULL["hybrid"]
TINY["sparse"] = FULL["sparse"]
TINY["parallel"] = dict(
    FULL["parallel"], vocab_size=256, d_model=64, n_heads=10, head_dim=16,
    v_head_dim=16, rotary_dim=16, dense_width=128,
    attn_kinds={"parallel": dict(
        FULL["parallel"]["attn_kinds"]["parallel"], n_kv_heads=2,
        ssm=dict(FULL["parallel"]["attn_kinds"]["parallel"]["ssm"],
                 head_dim=8, d_state=16, d_inner=64))})
# (a rehearsal asks again at the keys TINY has: it checks the control flow)
TINY["parallel_keys"] = {"ssm_decode_step": TINY["ssm_step"],
                         "decode_attention": TINY["decode_attn"][1],
                         "kv_column_write": TINY["kv_columns"][1]}
TINY["looped"] = dict(
    FULL["looped"], vocab_size=256, d_model=64, n_heads=4, head_dim=16,
    v_head_dim=16, rotary_dim=16, dense_width=160, passes=3,
    attn_kinds={"full": {"n_kv_heads": 4, "rope_theta": 1e6}})

#: relative tolerance of one logit row against another: bf16 keeps 8
#: bits of mantissa and a 12-block stack rounds the residual stream
#: after every matmul; float32 under "highest" differs only by
#: reassociation of partial sums
LOGIT_RTOL = {"bfloat16": 2e-2, "float32": 1e-5}


# -- bookkeeping ---------------------------------------------------------------
class CompileMeter:
    """Seconds spent in backend compiles and persistent-cache hits and
    misses, read from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self):
        return self.seconds, self.hits, self.misses


def memory_stats():
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use")}


def run_phase(name, fn, meter, *args):
    """Run one phase, print its line, and drop what it left on the
    device. Whatever the phase raises ends the run."""
    import jax

    s0, h0, m0 = meter.read()
    t0 = time.perf_counter()
    out = fn(*args)
    seconds = time.perf_counter() - t0
    s1, h1, m1 = meter.read()
    keep = out.pop("_keep", None)
    gc.collect()
    jax.clear_caches()
    print(json.dumps({
        "phase": name, "seconds": round(seconds, 3),
        "compile_seconds": round(s1 - s0, 3),
        "cache_hits": h1 - h0, "cache_misses": m1 - m0,
        **memory_stats(), "checked": out}), flush=True)
    return keep


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def lm_batch(vocab, batch, seq, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tgt[:, -1] = -1
    return ids, tgt


# -- greedy-token comparison -------------------------------------------------
def next_token_logits(model, prefix, rows):
    """The logit row that predicts the token after ``prefix``, computed
    by a cached decode over ``rows`` identical rows (row 0 returned):
    prefill all but the last token, then one ``decode_step``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import (
        decode_step,
        init_decode_cache,
        prefill_cache,
    )

    cfg = model.cfg
    n = len(prefix) - 1
    bucket = next(t for t in model.prefill_buckets() if t >= n)
    ids = np.zeros((rows, bucket), np.int32)
    ids[:, :n] = np.asarray(prefix[:-1], np.int32)
    last = jnp.full((rows,), int(prefix[-1]), jnp.int32)

    @jax.jit
    def row(params, ids, last):
        cache = init_decode_cache(cfg, rows)
        _, cache = prefill_cache(cfg, params, cache, ids,
                                 length=jnp.asarray(n, jnp.int32))
        logits, _ = decode_step(cfg, params, cache, last)
        return logits[0]

    return np.asarray(row(model.params_, jnp.asarray(ids), last), np.float32)


def compare_greedy(got, want, prompt_len, row_got, row_want, rtol):
    """Greedy tokens of two programs over the same weights. Equal is
    the contract on the CPU. On the chip two programs may tile or
    partition their matmuls differently; where the tokens part, compare
    the LOGITS at that position — ``row_got(prefix)`` and
    ``row_want(prefix)`` recompute the row each side decoded from — and
    fail unless the rows agree within ``rtol`` and the two candidates
    were that close to a tie."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"lengths differ: {got.shape} {want.shape}")
    diff = np.nonzero(got != want)[0]
    if diff.size == 0:
        return {"tokens_equal": True}
    pos = int(diff[0])
    check(pos >= prompt_len, f"prompts differ at {pos}")
    a, b = row_got(want[:pos]), row_want(want[:pos])
    scale = float(np.max(np.abs(b)))
    max_diff = float(np.max(np.abs(a - b)))
    top2 = np.sort(b)[-2:]
    cand_gap = float(abs(b[got[pos]] - b[want[pos]]))
    report = {"tokens_equal": False, "first_difference_at": pos,
              "tokens": [int(got[pos]), int(want[pos])],
              "logit_rows_max_diff": max_diff, "logit_scale": scale,
              "top2_gap": float(top2[1] - top2[0]),
              "candidates_gap": cand_gap, "rtol": rtol}
    print(json.dumps({"greedy_tokens_part": report}), flush=True)
    check(max_diff <= rtol * scale,
          f"logit rows differ by {max_diff} > {rtol} * {scale}: {report}")
    check(cand_gap <= 2 * rtol * scale,
          f"tokens part where the logits do not tie: {report}")
    return report


# -- one-chip phases -----------------------------------------------------------
def phase_device(want_count, rehearse):
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(rehearse or dev["platform"] == "tpu",
          f"no TPU: JAX reports {dev}")
    check(len(devs) == want_count,
          f"need {want_count} device(s), JAX reports {dev}")
    import jaxlib

    return {"device": dev, "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "_keep": dev}


def phase_lm_train(size):
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(**size["lm"]).init()
    ids, tgt = lm_batch(model.cfg.vocab_size, size["lm_batch"],
                        model.cfg.max_length)
    losses = [model.fit_batch(ids, tgt) for _ in range(size["lm_steps"])]
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on the repeated batch: {losses}")
    compiles = model._jit_cache["step"]._cache_size()
    check(compiles == 1, f"train step compiled {compiles} times")
    model.opt_state_ = None  # lm_serve needs the weights only
    return {"params": model.num_params(), "losses": losses,
            "step_compiles": compiles, "_keep": model}


def _http(port, method, path, body=None, timeout=600):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     None if body is None else json.dumps(body))
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def step_ids_in_ring(gen, since_ns, ahead=None):
    """What the engine's ring entries since ``since_ns`` say of its
    decode steps (``obs/trace.py``: an entry's cause is its step's id):
    each step one put, one dispatch, one fetch and one emit under one id,
    and ``gen.turn`` between two steps. ``ahead``: the engine's
    ``decode_steps_ahead`` where its loop keeps a step in flight: as many
    steps, and at least one, were dispatched before the step before them
    was fetched."""
    from deeplearning4j_tpu.obs import trace as obs_trace

    mine = gen._dispatch_gen >> 32
    steps, began = {}, {}
    for name, start, _, cause in obs_trace.caused_phases(since_ns):
        if cause is not None and cause >> 32 == mine:
            steps.setdefault(cause, []).append(name)
            began[cause, name] = start
    check(steps, "the ring holds no entry with one of the engine's step ids")
    step = ["gen.decode.put", "gen.decode.dispatch", "gen.decode.fetch",
            "gen.emit"]
    decoded = {i: names for i, names in steps.items() if "gen.emit" in names}
    for i, names in decoded.items():
        check([n for n in names if n in step] == step,
              f"step {i}: not one id over one step's four phases: {names}")
    turns = sum(names.count("gen.turn") for names in steps.values())
    check(0 < turns <= len(decoded),
          f"{turns} gen.turn entries beside {len(decoded)} decode steps")
    out = {"decode_steps": len(decoded), "turns": turns}
    if ahead is not None:
        out["dispatched_ahead"] = sum(
            1 for i in decoded if i + 1 in decoded
            and began[i + 1, "gen.decode.dispatch"]
            < began[i, "gen.decode.fetch"])
        check(0 < ahead == out["dispatched_ahead"],
              f"decode_steps_ahead {ahead}, and {out['dispatched_ahead']} "
              f"of {len(decoded)} steps in the ring were dispatched before "
              "the fetch of the step before them")
    return out


def phase_lm_serve(size, model):
    import numpy as np

    from deeplearning4j_tpu.serving import (
        BucketPolicy,
        InferenceEngine,
        InferenceServer,
    )
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    rng = np.random.default_rng(1)
    lo, hi = size["prompt_len"]
    prompts = [rng.integers(0, model.cfg.vocab_size,
                            int(rng.integers(lo, hi + 1))).tolist()
               for _ in range(size["requests"])]
    max_new = size["max_new"]

    gen = GenerationEngine(model, n_slots=size["slots"])
    eng = InferenceEngine(model, buckets=BucketPolicy(batch_buckets=[1]))
    srv = InferenceServer(eng, port=0, generation=gen).start()
    try:
        warm = gen.warmup()
        traced = dict(gen.trace_counts)
        mark = time.time_ns()
        answers = [None] * len(prompts)

        def ask(i):
            answers[i] = _http(srv.port, "POST", "/generate",
                               {"prompt": prompts[i], "max_new": max_new,
                                "temperature": 0.0, "stream": False})

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        check(not any(t.is_alive() for t in threads), "a request hung")
        bodies = []
        for i, (status, raw) in enumerate(answers):
            check(status == 200, f"request {i}: HTTP {status} {raw[:300]}")
            body = json.loads(raw)
            check(len(body["tokens"]) == max_new
                  and body["sequence"] == prompts[i] + body["tokens"],
                  f"request {i}: wrong token count or sequence")
            bodies.append(body)
        retraces = {k: v - traced.get(k, 0)
                    for k, v in gen.trace_counts.items()
                    if v != traced.get(k, 0)}
        check(not retraces, f"retraced after warm-up: {retraces}")
        status, raw = _http(srv.port, "GET", "/healthz")
        check(status == 200, f"/healthz: HTTP {status} {raw[:300]}")
        # K = 1 and no prefix cache: the loop keeps a step in flight
        ring = step_ids_in_ring(
            gen, mark, ahead=gen.metrics.snapshot()["decode_steps_ahead"])
    finally:
        srv.generation = None
        srv.shutdown()
        gen.shutdown()

    solo = model.generate_cached(np.asarray(prompts[0], np.int32),
                                 max_new=max_new)[0]
    parity = compare_greedy(
        bodies[0]["sequence"], solo, len(prompts[0]),
        lambda prefix: next_token_logits(model, prefix, size["slots"]),
        lambda prefix: next_token_logits(model, prefix, 1),
        LOGIT_RTOL["bfloat16"])
    return {"requests": len(prompts), "slots": size["slots"],
            "prompt_lens": [len(p) for p in prompts], "max_new": max_new,
            "warmup": {k: warm.get(k) for k in ("buckets", "compiles")},
            "retraces_after_warmup": 0, "healthz": 200, "ring": ring,
            "vs_generate_cached": parity}


def serve_decoder(model, prompts, max_new, buckets):
    """``prompts`` through a three-slot ``GenerationEngine`` over a
    ``DecoderLM`` (more requests than slots: slots are claimed again and
    rows sit idle beside live ones), each request's tokens against the
    model's own cached generation on one slot; no retrace after warm-up.
    Returns (what was served, the engine's counters, the ring's step ids,
    its memory report, the warm-up's)."""
    import numpy as np

    from deeplearning4j_tpu.serving.generate import GenerationEngine

    gen = GenerationEngine(model, n_slots=3, max_length=96,
                           prefill_buckets=buckets)
    try:
        warm = gen.warmup()
        traced = dict(gen.trace_counts)
        mark = time.time_ns()
        requests = [gen.submit(p, max_new=max_new) for p in prompts]
        served = [np.asarray(r.result(timeout=900)) for r in requests]
        check(gen.trace_counts == traced,
              f"retraced after warm-up: {traced} -> {gen.trace_counts}")
        snapshot = gen.metrics.snapshot()
        ring = step_ids_in_ring(gen, mark,
                                ahead=snapshot["decode_steps_ahead"])
        report = gen.describe()["memory"]
    finally:
        gen.shutdown()
    for i, (prompt, got) in enumerate(zip(prompts, served)):
        alone = model.generate_cached(prompt, max_new=max_new)
        check(np.array_equal(got[-max_new:], alone[-max_new:]),
              f"request {i}: engine {got[-max_new:].tolist()} != alone "
              f"{alone[-max_new:].tolist()}")
    return served, snapshot, ring, report, {
        k: warm.get(k) for k in ("buckets", "compiles")}


def phase_hybrid_serve(size):
    """A decoder whose layers are state-space mixers around an attention
    layer (``models/decoder_lm.py``), served by ``GenerationEngine``: more
    requests than slots, so slots are claimed again over another request's
    recurrent state and rows sit idle beside live ones; every request's
    tokens against the model's own cached generation on one slot."""
    import numpy as np

    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    model = DecoderLM.from_dict(size["hybrid"]).init()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, model.cfg.vocab_size, n)
               for n in (5, 9, 20, 31, 2)]
    max_new = 24
    _served, snapshot, ring, report, warm = serve_decoder(
        model, prompts, max_new, [8, 16, 32])
    check(snapshot["state_slots"] > 0, "no live state slot was counted")
    return {"requests": len(prompts), "slots": 3, "max_new": max_new,
            "segments": model.cfg.segments(), "warmup": warm,
            "state_bytes": report["state_bytes"],
            "slab_bytes": report["slab_bytes"],
            "state_slots": snapshot["state_slots"],
            "late_slot_steps": snapshot["late_slot_steps"], "ring": ring,
            "tokens_equal_generate_cached": True}


def phase_parallel_serve(size, platform):
    """A decoder whose every block has an attention AND a state-space mixer
    on one normed input (``models/decoder_lm.py``: ``_Parallel``), served
    by ``GenerationEngine``: more requests than slots, so slots are claimed
    again over another request's columns and state; every request's tokens
    against the model's own cached generation on one slot, one step id a
    decode step and a turn between steps (``serve_decoder``). Then the
    three kernels of a decode step at the falcon-h1-34b-l6.docqa-steady
    cell's keys, all probes in this one process: on the TPU a fallback at
    any of them fails the phase."""
    import numpy as np

    from deeplearning4j_tpu.models.decoder_lm import DecoderLM
    from deeplearning4j_tpu.nn.ops.decode_attention import decode_attention_impl
    from deeplearning4j_tpu.nn.ops.kv_column_write import kv_column_write_impl
    from deeplearning4j_tpu.nn.ops.ssm_decode import ssm_decode_impl

    model = DecoderLM.from_dict(size["parallel"]).init()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, model.cfg.vocab_size, n)
               for n in (5, 9, 20, 31, 2)]
    max_new = 24
    _served, snapshot, ring, report, warm = serve_decoder(
        model, prompts, max_new, [8, 16, 32])
    check(snapshot["state_slots"] > 0 and snapshot["attn_positions_read"] > 0,
          f"the segment counts positions read AND state slots: {snapshot}")
    (entry,) = report["cache_plan"]
    check(entry["bytes_state"] == report["state_bytes"]
          and entry["bytes_columns"] == report["slab_bytes"],
          f"the entry's bytes by half: {report}")
    keys = size["parallel_keys"]
    engaged = {
        "ssm_decode_step": ssm_decode_impl(**keys["ssm_decode_step"]),
        "decode_attention": decode_attention_impl(**keys["decode_attention"]),
        "kv_column_write": kv_column_write_impl(**keys["kv_column_write"])}
    fell_back = sorted(k for k, impl in engaged.items() if impl is None)
    check(platform != "tpu" or not fell_back,
          f"kernels fell back at the cell's keys: {fell_back}")
    return {"requests": len(prompts), "slots": 3, "max_new": max_new,
            "segments": model.cfg.segments(), "warmup": warm,
            "state_bytes": report["state_bytes"],
            "slab_bytes": report["slab_bytes"],
            "state_slots": snapshot["state_slots"],
            "attn_positions_read": snapshot["attn_positions_read"],
            "kernels_at_the_cells_keys": {k: impl is not None
                                          for k, impl in engaged.items()},
            "late_slot_steps": snapshot["late_slot_steps"], "ring": ring,
            "tokens_equal_generate_cached": True}


def phase_sparse_serve(size):
    """A decoder whose latent attention reads the positions an indexer
    selects (``models/decoder_lm.py``), served by ``GenerationEngine``:
    prompts past the indexer's top-k, so prefills select and every decode
    step scores the key slab, keeps the exact top-k and attends over the
    chosen rows (on the chip through ``nn/ops/sparse_latent_decode.py``),
    two layers by a selection another segment's layer made; every
    request's tokens against the model's own cached generation on one slot
    and against the greedy tokens of ONE forward over what was served (the
    selection as a mask, no cache)."""
    import numpy as np

    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    model = DecoderLM.from_dict(size["sparse"]).init()
    topk = model.cfg.attn_kinds["indexed"]["index"]["topk"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, model.cfg.vocab_size, n)
               for n in (5, 9, 20, 31, 40)]
    max_new = 24
    served, snapshot, ring, report, warm = serve_decoder(
        model, prompts, max_new, [8, 16, 32, 64])
    plan = report["cache_plan"]
    agree = total = 0
    for prompt, got in zip(prompts, served):
        greedy = model.logits(got[None, :-1])[0, len(prompt) - 1:].argmax(-1)
        agree += int((greedy == got[-max_new:]).sum())
        total += max_new
    # a near-tie of two logits or of two indexer scores may fall the other
    # way in the other form; more than a few is a fault
    check(agree >= 0.9 * total,
          f"forward's greedy tokens agree with {agree} of {total} served")
    scored, read = (snapshot["index_positions_scored"],
                    snapshot["sparse_positions_read"])
    check(0 < read < scored, f"selection not active: {read} of {scored}")
    check(snapshot["decode_steps_ahead"] > 0, "no step was launched ahead")
    return {"requests": len(prompts), "slots": 3, "max_new": max_new,
            "segments": model.cfg.segments(), "index_topk": topk,
            "warmup": warm,
            "cache_plan": [{k: p[k] for k in ("kind", "layers", "values",
                                              "row", "bytes")} for p in plan],
            "index_positions_scored": scored, "sparse_positions_read": read,
            "decode_steps_ahead": snapshot["decode_steps_ahead"],
            "late_slot_steps": snapshot["late_slot_steps"], "ring": ring,
            "tokens_equal_generate_cached": True,
            "tokens_equal_forward_greedy": [agree, total]}


def phase_resnet_train(size):
    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.models.resnet50 import ResNet50

    hw, batch = size["image"], size["resnet_batch"]
    net = ResNet50(height=hw, width=hw, **size["resnet"]).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hw, hw, 3)).astype(np.float32)
    n_cls = size["resnet"]["num_classes"]
    y = np.eye(n_cls, dtype=np.float32)[rng.integers(0, n_cls, batch)]
    data = DataSet(x, y)
    losses = []
    for _ in range(size["resnet_steps"]):
        net.fit(data, epochs=1, batch_size=batch)
        losses.append(float(net.score_))
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    return {"batch": batch, "image": hw, "losses": losses}


def phase_resnet_serve(size):
    from deeplearning4j_tpu import cli

    # in-process: this process holds the chip, a child could not
    rc = cli.serve_main(["--model", size["serve_model"], "--port", "0",
                         "--smoke"])
    check(rc == 0, f"cli serve --smoke returned {rc}")
    return {"model": size["serve_model"], "rc": rc}


def phase_kernels(platform, size=None):
    """Which Pallas kernels the phases asked for and what each resolved
    to. On the TPU backend a kernel that fell back to its reference is
    a failure: the run would otherwise pass on dense XLA. No phase above
    serves a dense latent layer, ``hybrid_serve`` takes its state-space step
    and ``sparse_serve`` its attention over a selection at a tiny size and
    ``lm_serve`` / ``looped_serve`` write small or short slabs and no phase's
    expert layer has a cell's widths, so with ``size`` the four decode
    kernels, the cache's column write and the expert layers' grouped
    products are asked for here, at the widths a cell runs them at (each
    probe holds its kernel to the ``jnp`` form)."""
    from deeplearning4j_tpu.nn.conf.layers import attention
    from deeplearning4j_tpu.nn.ops.decode_attention import decode_attention_impl
    from deeplearning4j_tpu.nn.ops.grouped_experts import grouped_experts_impl
    from deeplearning4j_tpu.nn.ops.kv_column_write import kv_column_write_impl
    from deeplearning4j_tpu.nn.ops.latent_decode import latent_decode_impl
    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry
    from deeplearning4j_tpu.nn.ops.sparse_latent_decode import (
        sparse_latent_decode_impl,
    )
    from deeplearning4j_tpu.nn.ops.ssm_decode import ssm_decode_impl

    if size is not None:
        latent_decode_impl(**size["latent_core"])
        sparse_latent_decode_impl(**size["sparse_core"])
        keys = size["parallel_keys"]
        for step in (size["ssm_step"], keys["ssm_decode_step"]):
            ssm_decode_impl(**step)
        for slab in size["kv_columns"] + [keys["kv_column_write"]]:
            kv_column_write_impl(**slab)
        for slabs in size["decode_attn"] + [keys["decode_attention"]]:
            decode_attention_impl(**slabs)
        for products in size["grouped_experts"]:
            grouped_experts_impl(**products)
    snap = default_kernel_registry().snapshot()
    flash = {repr(k): (None if impl is None
                       else getattr(impl.args[0], "__module__", "?"))
             for k, impl in attention._FLASH_PROBE_CACHE.items()}
    refused = []
    for key, winner in flash.items():
        if winner is None:  # every candidate lost: say why each did
            why = [f"{k}: {v['reason']}"
                   for k, v in snap.get("flash_attention", {}).items()
                   if k.startswith(key[:-1] + ",")]
            refused.append(f"flash_attention {key} fell back to dense "
                           f"attention ({'; '.join(why)})")
    for kernel, entries in snap.items():
        for key, verdict in entries.items():
            if verdict["enabled"] or kernel == "flash_attention":
                continue  # a flash candidate may lose while another wins
            refused.append(f"{kernel} {key}: {verdict['reason']}")
    out = {"registry": snap, "flash_attention_winner": flash,
           "refused": refused}
    if platform == "tpu" and refused:
        print(json.dumps({"phase": "kernels", "checked": out}), flush=True)
        raise AssertionError(
            "kernels fell back to their reference on the TPU backend: "
            + "; ".join(refused))
    return out


# -- --chips 4 -----------------------------------------------------------------
def device_bytes(tree):
    """{device id: bytes of ``tree`` resident there}, from the arrays'
    own shards."""
    import jax

    held = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    return held


def check_shares(what, held, expect_each, devices):
    ids = sorted(d.id for d in devices)
    check(sorted(held) == ids and all(held[i] == expect_each for i in ids),
          f"{what}: bytes per device {held}, ledger says "
          f"{expect_each} on each of {ids}")


def phase_dist_train(size):
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.parallel.mesh import TrainingMesh
    from deeplearning4j_tpu.parallel.transformer import (
        DistributedLMTrainer,
        param_pspecs,
    )

    devices = jax.devices()
    cfg = size["lm4"]
    ids, tgt = lm_batch(cfg["vocab_size"], size["lm4_batch"],
                        cfg["max_length"])
    steps = size["lm4_steps"]

    ref = TransformerLM(**cfg).init()
    ref_losses = [ref.fit_batch(ids, tgt) for _ in range(steps)]
    del ref
    gc.collect()

    model = TransformerLM(**cfg).init()
    mesh = TrainingMesh(data=2, model=2, devices=devices)
    trainer = DistributedLMTrainer(model, mesh).place()

    # the ledger: every leaf's shard under the spec param_pspecs gives it
    flat_s, treedef = jax.tree_util.tree_flatten(
        param_pspecs(model.cfg),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    expect = sum(
        int(np.prod(jax.sharding.NamedSharding(mesh.mesh, spec)
                    .shard_shape(leaf.shape))) * leaf.dtype.itemsize
        for leaf, spec in zip(treedef.flatten_up_to(model.params_), flat_s))
    held = device_bytes(model.params_)
    check_shares("trainer params", held, expect, devices)

    losses = [trainer.fit_batch(ids, tgt) for _ in range(steps)]
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    check_shares("trainer params after the steps",
                 device_bytes(model.params_), expect, devices)
    return {"mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "losses": losses, "single_device_losses": ref_losses,
            "rtol": 1e-4, "param_bytes_total": sum(
                leaf.nbytes for leaf in
                jax.tree_util.tree_leaves(model.params_)),
            "param_bytes_per_device": held}


def phase_sharded_serve(size):
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs import flight
    from deeplearning4j_tpu.parallel.serving_mesh import ServingMesh
    from deeplearning4j_tpu.serving import InferenceEngine
    from deeplearning4j_tpu.serving.generate import GenerationEngine
    from deeplearning4j_tpu.serving.sharded import (
        ShardedInferenceEngine,
        sharded_generation_engine,
    )

    devices = jax.devices()
    mesh = ServingMesh.from_spec("1x4")
    rng = np.random.default_rng(2)

    # -- /predict: a feed-forward stack at the LM's FFN widths, sharded
    #    against solo, logits (identity output) not probabilities
    d_in, d_hid, d_mid, d_out = size["mlp4"]

    def mlp():
        conf = (NeuralNetConfiguration.builder().seed(11).list()
                .layer(DenseLayer(n_out=d_hid, activation="relu"))
                .layer(DenseLayer(n_out=d_mid, activation="relu"))
                .layer(OutputLayer(n_out=d_out, activation="identity",
                                   loss="mse"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        return MultiLayerNetwork(conf).init()

    x = rng.standard_normal((size["mlp4_rows"], d_in)).astype(np.float32)
    y_solo = InferenceEngine(mlp()).infer(x)
    sharded = ShardedInferenceEngine(mlp(), mesh=mesh)
    y_sh = sharded.infer(x)
    rtol = LOGIT_RTOL["float32"]
    scale = float(np.max(np.abs(y_solo)))
    np.testing.assert_allclose(y_sh, y_solo, rtol=rtol, atol=rtol * scale)
    check(not sharded.fallback_active, "sharded engine demoted to solo")
    rep = sharded.shard_report
    held_mlp = device_bytes(sharded._snap.params)
    check_shares("sharded /predict params", held_mlp,
                 rep["per_device_bytes"], devices)

    # -- /generate: the LM, params and KV slab on the mesh, against the
    #    solo engine
    cfg = size["lm4"]
    lo, hi = size["prompt_len"]
    prompt = rng.integers(0, cfg["vocab_size"], (lo + hi) // 2)
    max_new, slots = size["max_new"], size["slots"]

    lm_solo = TransformerLM(**cfg).init()
    solo = GenerationEngine(lm_solo, n_slots=slots)
    try:
        toks_solo = solo.submit(prompt, max_new=max_new,
                                temperature=0.0).result(timeout=900)
    finally:
        solo.shutdown()
    del solo
    gc.collect()

    lm = TransformerLM(**cfg).init()
    gsh = sharded_generation_engine(lm, mesh, n_slots=slots)
    try:
        gsh.warmup()
        traced = dict(gsh.trace_counts)
        toks_sh = gsh.submit(prompt, max_new=max_new,
                             temperature=0.0).result(timeout=900)
        check(gsh.trace_counts == traced,
              f"retraced after warm-up: {traced} -> {gsh.trace_counts}")
        held_lm = device_bytes(lm.params_)
        check_shares("sharded LM params", held_lm,
                     gsh.shard_report["per_device_bytes"], devices)
        slab = (gsh.backend._kc, gsh.backend._vc)
        held_kv = device_bytes(slab)
        check_shares("KV slab", held_kv,
                     sum(a.nbytes for a in slab) // len(devices), devices)
    finally:
        gsh.shutdown()
    parity = compare_greedy(
        toks_sh, toks_solo, len(prompt),
        lambda prefix: next_token_logits(lm, prefix, slots),
        lambda prefix: next_token_logits(lm_solo, prefix, slots), rtol)

    fell_back = [e for e in flight.default_flight_recorder().events()
                 if e.get("kind") == "sharded_fallback"]
    check(not fell_back, f"sharded_fallback recorded: {fell_back}")
    return {"mesh": "1x4", "predict_max_abs_diff": float(
                np.max(np.abs(y_sh - y_solo))), "predict_scale": scale,
            "rtol": rtol, "predict_param_bytes_per_device": held_mlp,
            "predict_ledger": {k: rep[k] for k in (
                "total_bytes", "per_device_bytes", "replicated_bytes")},
            "lm_param_bytes_per_device": held_lm,
            "lm_ledger": {k: gsh.shard_report[k] for k in (
                "total_bytes", "per_device_bytes", "replicated_bytes")},
            "kv_bytes_per_device": held_kv,
            "generate_vs_solo": parity, "sharded_fallback_events": 0}


# -- entry -------------------------------------------------------------------
def phase_looped_serve(size):
    """A decoder whose whole stack runs several times a token over one set
    of weights (``models/decoder_lm.py``: ``passes``), served by
    ``GenerationEngine``: every request's tokens against the model's own
    cached generation on one slot, the cache plan's entries a position
    (passes x layers) and the passes the launched steps ran."""
    import numpy as np

    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    model = DecoderLM.from_dict(size["looped"]).init()
    cfg = model.cfg
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 9, 20, 31, 40)]
    max_new = 24
    _served, snapshot, ring, report, warm = serve_decoder(
        model, prompts, max_new, [8, 16, 32, 64])
    plan = report["cache_plan"]
    entries = cfg.passes * cfg.n_layers
    check(snapshot["cache_entries_per_position"] == entries
          and [p["passes"] for p in plan] == [cfg.passes],
          f"cache entries a position: {snapshot} {plan}")
    # a step launched for a slot the host had stopped streams nothing
    check(snapshot["stack_passes"] >= cfg.passes * snapshot["decode_steps"] > 0
          and snapshot["stack_passes"] % cfg.passes == 0,
          f"stack passes: {snapshot}")
    check(snapshot["decode_steps_ahead"] > 0, "no step was launched ahead")
    return {"requests": len(prompts), "slots": 3, "max_new": max_new,
            "passes": cfg.passes, "layers": cfg.n_layers, "warmup": warm,
            "cache_plan": [{k: p[k] for k in ("kind", "layers", "passes",
                                              "values", "bytes")}
                           for p in plan],
            "stack_passes": snapshot["stack_passes"],
            "decode_steps": snapshot["decode_steps"],
            "cache_entries_per_position": entries,
            "decode_steps_ahead": snapshot["decode_steps_ahead"],
            "late_slot_steps": snapshot["late_slot_steps"], "ring": ring,
            "tokens_equal_generate_cached": True}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the cross-chip phases only")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: control flow, not proof")
    args = ap.parse_args(argv)

    if args.rehearse:
        # must precede the first import of jax
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    size = TINY if args.rehearse else FULL

    from deeplearning4j_tpu.runtime import enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    t0 = time.perf_counter()
    dev = run_phase("device", phase_device, meter, args.chips, args.rehearse)
    if args.chips == 4:
        run_phase("dist_train", phase_dist_train, meter, size)
        run_phase("sharded_serve", phase_sharded_serve, meter, size)
    else:
        model = run_phase("lm_train", phase_lm_train, meter, size)
        run_phase("lm_serve", phase_lm_serve, meter, size, model)
        del model
        run_phase("hybrid_serve", phase_hybrid_serve, meter, size)
        run_phase("sparse_serve", phase_sparse_serve, meter, size)
        run_phase("looped_serve", phase_looped_serve, meter, size)
        run_phase("parallel_serve", phase_parallel_serve, meter, size,
                  dev["platform"])
        run_phase("resnet_train", phase_resnet_train, meter, size)
        run_phase("resnet_serve", phase_resnet_serve, meter, size)
    run_phase("kernels", phase_kernels, meter, dev["platform"], size)
    seconds, hits, misses = meter.read()
    print(json.dumps({
        "total_seconds": round(time.perf_counter() - t0, 3),
        "compile_seconds": round(seconds, 3), "cache_hits": hits,
        "cache_misses": misses, "compile_cache": cache_dir}), flush=True)
    if args.rehearse:
        print(json.dumps({"rehearsal_only": True, "device": dev}))
    else:
        print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

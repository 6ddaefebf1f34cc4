#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}

Flagship metric (BASELINE.md north star): ResNet-50 train throughput,
images/sec/chip, mixed-precision (bf16 compute, fp32 master weights).
Methodology mirrors the reference's benchmark machinery
(``BenchmarkDataSetIterator`` replayed synthetic batch +
``PerformanceListener`` samples/sec; SURVEY.md §6): one synthetic batch
replayed, compile excluded by warmup, steady-state timed. The full train
step (fwd + bwd + SGD update) is one jitted XLA program with donated
buffers.

Second north-star metric (BASELINE.json): data-parallel all-reduce
bandwidth (GB/s) — time a psum of a param-sized fp32 buffer across the
device mesh; reported in "extra" (degenerate on one chip, still
recorded with n_devices).

The default entry measures on the chip or fails: when the first device
JAX reports is not a TPU it exits non-zero and prints no number, and an
exception exits non-zero. ``BENCH_FORCE_CPU=1`` is the one explicit way
to rehearse the script on the CPU; every result names the platform,
device kind and device count it ran on.

vs_baseline is measured against the round-1 recording (1292.8 img/s/chip,
fp32, BASELINE.md) — the regression gate for subsequent rounds.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

ROUND1_IMG_PER_SEC = 1292.8  # BASELINE.md 2026-07-29, fp32, batch 128

def _force_cpu() -> bool:
    """``BENCH_FORCE_CPU=1``: rehearse on the CPU, said out loud."""
    return os.environ.get("BENCH_FORCE_CPU") == "1"


def _rehearse_on_cpu(n_devices: int = 0) -> None:
    """Under ``BENCH_FORCE_CPU=1`` pin JAX to the CPU before it starts
    (``n_devices`` > 0 also asks for that many virtual devices)."""
    if not _force_cpu():
        return
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{n_devices}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def _init_devices():
    """The devices the headline runs on: a TPU, or the CPU when
    ``BENCH_FORCE_CPU=1`` asked for a rehearsal. Anything else fails
    before a number can be printed."""
    import jax

    _rehearse_on_cpu()
    devices = jax.devices()
    if not _force_cpu() and devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py: no TPU (JAX reports {devices[0].platform}:"
            f"{devices[0].device_kind} x{len(devices)}); nothing measured. "
            "BENCH_FORCE_CPU=1 rehearses on the CPU.")
    return devices


def _bench_resnet(batch: int, compute_dtype, fused_pallas: bool = False):
    import os

    import jax.numpy as jnp

    from deeplearning4j_tpu.models.resnet50 import ResNet50

    model = ResNet50(
        num_classes=1000,
        compute_dtype=compute_dtype,
        stem_space_to_depth=os.environ.get("BENCH_S2D", "0") == "1",
        fused_pallas=fused_pallas,
    ).init()

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)).astype(np.float32))
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)])

    step = model._get_jit("train", model._make_train_step)

    def run_one():
        (model.params_, model.opt_state_, model.state_, model.score_) = step(
            model.params_, model.opt_state_, model.state_,
            (x,), (y,), (None,), (None,),
            model._next_rng(), jnp.asarray(model.iteration, jnp.int32),
            jnp.asarray(model.epoch, jnp.int32),
        )
        model.iteration += 1

    # warmup (compile + settle); sync via the score scalar (a host
    # round-trip ends only when the queued steps have run)
    for _ in range(3):
        run_one()
    float(model.score_)

    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        run_one()
    float(model.score_)
    dt = time.perf_counter() - t0
    return batch * iters / dt


def _bench_transformer(batch: int = 16, seq: int = 512, n_layers: int = 12):
    """TransformerLM train throughput (tokens/sec) — the flagship
    distributed model's single-chip number, reported in extra alongside
    the ResNet-50 headline. GPT-2-small-ish shape (d=768, L=12, h=12).
    Also called at (b=4, T=2048) for the long-context variant, where the
    flash kernel's O(T) memory matters vs dense attention's (T, T)
    scores. Returns (tokens_per_sec, analytic_flops_per_step,
    tokens_per_step, cost_analysis_flops or None); the MFU headline uses
    the ANALYTIC count — see the comment at the formula below for why
    cost_analysis is only a cross-check here (VERDICT r3 item 4)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import TransformerLM

    d, V = 768, 32000
    model = TransformerLM(vocab_size=V, d_model=d, n_heads=12,
                          n_layers=n_layers, max_length=seq,
                          compute_dtype="bfloat16").init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (batch, seq)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tgt[:, -1] = -1

    # drive the jitted step directly (fit_batch host-syncs every call,
    # which would serialize dispatch)
    step = model._jit_cache.setdefault("step", model._make_step())
    ids_d = jnp.asarray(ids, jnp.int32)
    tgt_d = jnp.asarray(tgt, jnp.int32)

    # Analytic matmul FLOPs per train step, MAC=2, bwd = 2x fwd. XLA's
    # cost_analysis() is WRONG here: the blocks run under lax.scan and the
    # loop body is counted ONCE, not n_layers times (r4 finding: it
    # reported 1.60e12 for this config vs 5.85e12 analytic — exactly one
    # body + the out-of-scan head/loss). Dense causal attention executes
    # the full T^2 matmuls, so count them fully; layernorm/softmax/gelu
    # vector ops are omitted on both this and the ResNet number.
    # 24*d^2 per token per layer = QKV+O (8d^2) + 4d-wide MLP (16d^2).
    fwd = (n_layers * (24 * batch * seq * d * d
                       + 4 * batch * seq * seq * d)
           + 2 * batch * seq * d * V)
    flops = float(3 * fwd)
    flops_ca = None
    try:
        lowered = step.lower(
            model.params_, model.opt_state_, ids_d, tgt_d,
            jnp.asarray(0, jnp.int32))
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops_ca = float(ca.get("flops", 0.0)) or None
    except Exception:
        pass  # cost analysis is best-effort; throughput still reported

    def run_one():
        model.iteration += 1
        model.params_, model.opt_state_, model.score_ = step(
            model.params_, model.opt_state_, ids_d, tgt_d,
            jnp.asarray(model.iteration, jnp.int32),
        )

    run_one()  # compile
    float(model.score_)
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        run_one()
    float(model.score_)
    dt = time.perf_counter() - t0
    return batch * seq * iters / dt, flops, batch * seq, flops_ca


def _bench_lm_decode(batch: int = 8, prompt: int = 128, new: int = 128):
    """KV-cache autoregressive decode throughput (generated tokens/sec)
    — the serving-side counterpart of the train metric (the reference's
    serving story is ParallelInference; here single-chip generation via
    per-layer KV caches, ``TransformerLM.generate_cached``). Greedy
    decoding; the host sampling loop and per-step dispatch are part of
    what's measured, as they are in real serving."""
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(vocab_size=32000, d_model=768, n_heads=12,
                          n_layers=12, max_length=prompt + new + 8,
                          compute_dtype="bfloat16").init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 32000, (batch, prompt)).astype(np.int32)
    model.generate_cached(ids, max_new=4)  # compile prefill + decode step
    t0 = time.perf_counter()
    out = model.generate_cached(ids, max_new=new)
    dt = time.perf_counter() - t0
    assert out.shape[1] == prompt + new
    return batch * new / dt


def _bench_dp_sharded_update(devices, batch: int = 16, seq: int = 512,
                             n_layers: int = 12):
    """Data-parallel TransformerLM weight-update A/B: replicated update vs
    the ZeRO-1 sharded update (parallel/zero.py) over all devices. Same
    math either way — the interesting numbers are tokens/sec and the
    measured per-replica optimizer-state bytes (sharded mode stores 1/N
    of the Adam m/v on each replica). Returns
    {replicated: {...}, zero1: {...}}."""
    from deeplearning4j_tpu.parallel.zero import measure_dp_update

    out = {}
    for key, sharded in (("replicated", False), ("zero1", True)):
        tps, opt_bytes, global_batch = measure_dp_update(
            batch, seq, sharded=sharded, n_layers=n_layers)
        out[key] = {
            "tokens_per_sec": round(tps, 1),
            "opt_state_bytes_per_replica": opt_bytes,
            "global_batch": global_batch,
        }
    return out


def _bench_allreduce(devices, mb: float = 256.0):
    """Time an all-reduce (psum) of an fp32 buffer sharded over all
    devices; returns (algo_bandwidth_GB_per_s, n_devices). Algorithmic
    bandwidth = 2*(n-1)/n * bytes / time (ring allreduce convention)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel.mesh import shard_map

    n = len(devices)
    n_elem = int(mb * 1e6 / 4)
    n_elem -= n_elem % max(n, 1)
    mesh = Mesh(np.array(devices), ("d",))
    x = jnp.zeros((n_elem,), jnp.float32) + 1.0
    x = jax.device_put(x, NamedSharding(mesh, P("d")))

    f = jax.jit(
        shard_map(
            lambda v: jax.lax.psum(v, "d"),
            mesh=mesh, in_specs=P("d"), out_specs=P("d"),
        )
    )
    y = f(x)
    y.block_until_ready()
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        y = f(x)
    y.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    bytes_ = n_elem * 4
    algbw = (2 * (n - 1) / max(n, 1)) * bytes_ / dt / 1e9 if n > 1 else bytes_ / dt / 1e9
    return round(algbw, 2), n


def _bench_serving(n_clients: int = 8, n_requests: int = 30,
                   max_size: int = 16, batch_limit: int = 32):
    """Serving A/B: bucketed batching (warmup pre-compiles every bucket)
    vs naive coalescing (one XLA program per distinct dispatched size).
    A multi-threaded client storm with mixed request sizes drives each
    mode through the same DynamicBatcher; per-request latency p50/p99,
    req/s and the engine compile count are the readout. Writes the full
    A/B to BENCH_serving.json next to this script and returns it."""
    import threading

    import jax

    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        BucketPolicy,
        DynamicBatcher,
        InferenceEngine,
    )
    from deeplearning4j_tpu.serving.batcher import make_dispatcher
    from deeplearning4j_tpu.updaters import Adam

    d_in, d_hidden, d_out = 128, 256, 10

    def fresh_engine(policy):
        conf = (NeuralNetConfiguration.builder().seed(11).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_out=d_hidden, activation="relu"))
                .layer(OutputLayer(n_out=d_out, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        net = MultiLayerNetwork(conf).init()
        return InferenceEngine(net, buckets=policy)

    rng = np.random.default_rng(0)
    # one fixed input per size: naive mode's compile set is then exactly
    # the distinct sizes, not distinct values
    inputs = {n: rng.standard_normal((n, d_in)).astype(np.float32)
              for n in range(1, max_size + 1)}

    def storm(engine, warm: bool) -> dict:
        if warm:
            warm_report = engine.warmup()
        else:
            warm_report = None
        batcher = DynamicBatcher(
            make_dispatcher(engine.infer, metrics=engine.metrics),
            batch_limit=batch_limit, max_wait_ms=2.0, queue_limit=4096,
            metrics=engine.metrics)
        compiles_before_storm = engine.compile_count
        lats = []
        lock = threading.Lock()

        def client(tid):
            crng = np.random.default_rng(100 + tid)
            mine = []
            for _ in range(n_requests):
                n = int(crng.integers(1, max_size + 1))
                t0 = time.perf_counter()
                batcher.submit(inputs[n]).result(timeout=120)
                mine.append(time.perf_counter() - t0)
            with lock:
                lats.extend(mine)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        batcher.shutdown()
        lats.sort()

        def q(p):
            return lats[min(int(p * len(lats)), len(lats) - 1)]

        return {
            "requests": len(lats),
            "req_per_sec": round(len(lats) / wall, 1),
            "latency_p50_ms": round(q(0.50) * 1e3, 3),
            "latency_p99_ms": round(q(0.99) * 1e3, 3),
            "storm_compiles": engine.compile_count - compiles_before_storm,
            "total_compiles": engine.compile_count,
            "warmup": warm_report,
        }

    bucketed = storm(fresh_engine(BucketPolicy(max_batch=batch_limit)),
                     warm=True)
    naive = storm(fresh_engine(BucketPolicy.identity()), warm=False)

    result = {
        "metric": "serving_p99_latency_ms_bucketed",
        "value": bucketed["latency_p99_ms"],
        "unit": "ms",
        "vs_baseline": (
            round(naive["latency_p99_ms"] / bucketed["latency_p99_ms"], 2)
            if bucketed["latency_p99_ms"] else None),
        "extra": {
            "bucketed": bucketed,
            "naive_coalescing": naive,
            "config": (f"MLP {d_in}->{d_hidden}->{d_out}, "
                       f"{n_clients} clients x {n_requests} reqs, "
                       f"sizes 1..{max_size}, batch_limit {batch_limit}, "
                       "max_wait 2ms"),
            "platform": jax.devices()[0].platform,
            "note": ("vs_baseline = naive p99 / bucketed p99; "
                     "storm_compiles is the acceptance signal "
                     "(bucketed+warm must be 0)"),
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_serving.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_generate(n_clients: int = 8, reqs_per_client: int = 3,
                    n_slots: int = 8):
    """Continuous-batching generation A/B (serving/generate.py): a
    mixed-length client storm through the slotted GenerationEngine vs
    the full-prefix ``generate()`` baseline (re-runs the whole growing
    prefix per token) and the solo KV-cache ``generate_cached`` middle
    tier. Greedy decoding; per-request outputs must be BIT-IDENTICAL
    across all three (parity is part of the gate), steady-state decode
    must trace zero new XLA programs, and the engine must clear >= 3x
    the full-prefix tokens/sec. Compile costs are excluded from every
    mode the same way: one warm pass first, the timed pass measures
    steady state. Writes BENCH_generate.json next to this script."""
    import threading

    import jax

    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    # The storm lives in the regime the engine exists for: generations a
    # hundred-plus tokens deep, where the full-prefix baseline re-runs an
    # ever-growing O(T) forward per token while the slab decode stays
    # O(1) per token per slot. Short-prompt/short-decode workloads are
    # dispatch-bound on a small host and hide that asymmetry.
    model = TransformerLM(vocab_size=512, d_model=128, n_heads=4,
                          n_layers=4, max_length=256, seed=11).init()
    rng = np.random.default_rng(0)
    clients = []
    for c in range(n_clients):
        mine = []
        for _ in range(reqs_per_client):
            tp = int(rng.integers(48, 97))
            mn = int(rng.integers(112, 145))
            mine.append((rng.integers(0, 512, (tp,)).astype(np.int32), mn))
        clients.append(mine)
    all_reqs = [r for mine in clients for r in mine]
    total_new = sum(mn for _, mn in all_reqs)

    full_out = {}

    def run_full():
        for i, (prompt, mn) in enumerate(all_reqs):
            full_out[i] = model.generate(prompt, max_new=mn)[0]

    run_full()  # warm: one compile per distinct prefix length
    t0 = time.perf_counter()
    lats_full = []
    for prompt, mn in all_reqs:
        t1 = time.perf_counter()
        model.generate(prompt, max_new=mn)
        lats_full.append(time.perf_counter() - t1)
    full_dt = time.perf_counter() - t0
    full_tps = total_new / full_dt

    # tri-modal parity leg 1: solo KV-cache decode ≡ full-prefix
    # reference (leg 2, engine ≡ solo, is checked per client below)
    solo_out = {}
    parity_fail = 0
    for i, (prompt, mn) in enumerate(all_reqs):
        solo_out[i] = model.generate_cached(prompt, max_new=mn)[0]
        if not np.array_equal(solo_out[i], full_out[i]):
            parity_fail += 1
    t0 = time.perf_counter()
    for prompt, mn in all_reqs:
        model.generate_cached(prompt, max_new=mn)
    cached_tps = total_new / (time.perf_counter() - t0)

    engine = GenerationEngine(model, n_slots=n_slots,
                              queue_limit=len(all_reqs) + 4,
                              default_timeout_s=600.0)
    warm = engine.warmup()
    traces_before = dict(engine.trace_counts)
    lats_eng = []
    lock = threading.Lock()

    def client(cid):
        base = cid * reqs_per_client
        mine = []
        bad = 0
        for j, (prompt, mn) in enumerate(clients[cid]):
            t1 = time.perf_counter()
            out = engine.submit(prompt, max_new=mn,
                                timeout=600).result(timeout=600)
            mine.append(time.perf_counter() - t1)
            if not np.array_equal(out, solo_out[base + j]):
                bad += 1
        with lock:
            lats_eng.extend(mine)
            nonlocal parity_fail
            parity_fail += bad

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng_dt = time.perf_counter() - t0
    eng_tps = total_new / eng_dt
    storm_retraces = {
        k: engine.trace_counts.get(k, 0) - traces_before.get(k, 0)
        for k in engine.trace_counts}
    engine.shutdown()

    # -- shared-prefix storm (ISSUE 16): the production shape where
    # thousands of requests share one system prompt. Identical prompts,
    # greedy: after one priming request (the excluded warm pass, same as
    # every other mode) the n-gram draft predicts the continuation and
    # every admit copies cached prefix KV instead of re-running prefill.
    # A/B: the plain engine (PR 9 configuration) vs speculation + prefix
    # cache on the SAME storm; outputs must stay bit-identical to solo
    # generate_cached and steady state must trace zero new programs.
    sp_prompt = rng.integers(0, 512, (96,)).astype(np.int32)
    sp_mn = 128
    sp_ref = model.generate_cached(sp_prompt, max_new=sp_mn)[0]
    sp_total = n_clients * reqs_per_client * sp_mn

    def shared_storm(**eng_kwargs):
        eng = GenerationEngine(model, n_slots=n_slots,
                               queue_limit=n_clients * reqs_per_client + 4,
                               default_timeout_s=600.0, **eng_kwargs)
        eng.warmup()
        # priming request: learns the n-gram continuation + captures the
        # prefix KV entry, so the timed pass measures steady state
        eng.submit(sp_prompt, max_new=sp_mn,
                   timeout=600).result(timeout=600)
        before = dict(eng.trace_counts)
        lats, fails = [], [0]
        lk = threading.Lock()

        def cl():
            mine, bad = [], 0
            for _ in range(reqs_per_client):
                t1 = time.perf_counter()
                out = eng.submit(sp_prompt, max_new=sp_mn,
                                 timeout=600).result(timeout=600)
                mine.append(time.perf_counter() - t1)
                if not np.array_equal(out, sp_ref):
                    bad += 1
            with lk:
                lats.extend(mine)
                fails[0] += bad

        t0 = time.perf_counter()
        ths = [threading.Thread(target=cl) for _ in range(n_clients)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        dt = time.perf_counter() - t0
        retr = {k: eng.trace_counts.get(k, 0) - before.get(k, 0)
                for k in eng.trace_counts}
        snap = eng.metrics.snapshot()
        eng.shutdown()
        return sp_total / dt, lats, fails[0], retr, snap

    plain_tps, _, plain_fail, plain_retr, _ = shared_storm()
    spec_tps, spec_lats, spec_fail, spec_retr, spec_snap = shared_storm(
        spec_decode_k=8, prefix_cache_mb=16.0)
    parity_fail += plain_fail + spec_fail
    shared_prefix = {
        "spec_engine_tokens_per_sec": round(spec_tps, 1),
        "plain_engine_tokens_per_sec": round(plain_tps, 1),
        "speedup_vs_plain_engine": (round(spec_tps / plain_tps, 2)
                                    if plain_tps else None),
        "draft_acceptance_rate": spec_snap.get("draft_acceptance"),
        "prefill_flops_avoided": spec_snap.get("prefill_flops_avoided"),
        "prefix_hits": spec_snap.get("prefix_hits"),
        "prefix_lookups": spec_snap.get("prefix_lookups"),
        "latency_p50_ms": None,  # filled below once q() exists
        "requests": n_clients * reqs_per_client,
        "tokens": sp_total,
        "spec_decode_k": 8,
        "prefix_cache_mb": 16.0,
        "storm_retraces": {"plain": plain_retr, "spec": spec_retr},
        "parity_failures": plain_fail + spec_fail,
        "config": (f"shared prompt len 96, max_new {sp_mn}, "
                   f"{n_clients} clients x {reqs_per_client} reqs, "
                   "greedy, one priming request excluded"),
        "note": ("gate: speedup_vs_plain_engine >= 2.0, parity vs solo "
                 "generate_cached bit-identical, 0 storm retraces"),
    }

    def q(lats, p):
        lats = sorted(lats)
        return round(lats[min(int(p * len(lats)), len(lats) - 1)] * 1e3, 2)

    shared_prefix["latency_p50_ms"] = q(spec_lats, 0.5)
    result = {
        "metric": "generation_tokens_per_sec_continuous_batching",
        "value": round(eng_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(eng_tps / full_tps, 2) if full_tps else None,
        "extra": {
            "full_prefix_tokens_per_sec": round(full_tps, 1),
            "solo_kv_cache_tokens_per_sec": round(cached_tps, 1),
            "engine_vs_solo_cached": (round(eng_tps / cached_tps, 2)
                                      if cached_tps else None),
            "latency_p50_ms": {"engine": q(lats_eng, 0.5),
                               "full_prefix": q(lats_full, 0.5)},
            "latency_p99_ms": {"engine": q(lats_eng, 0.99),
                               "full_prefix": q(lats_full, 0.99)},
            "requests": len(all_reqs),
            "tokens": total_new,
            "n_slots": n_slots,
            "parity_failures": parity_fail,
            "storm_retraces": storm_retraces,
            "shared_prefix_storm": shared_prefix,
            "warmup": warm,
            "config": ("TransformerLM d128 L4 h4 V512 maxlen256, "
                       f"{n_clients} clients x {reqs_per_client} reqs, "
                       "prompts 48..96, max_new 112..144, greedy"),
            "platform": jax.devices()[0].platform,
            "note": ("gate: vs_baseline (engine / full-prefix) >= 3.0, "
                     "storm_retraces all 0, parity_failures 0 — "
                     "per-request greedy output bit-identical across "
                     "engine / solo generate_cached / full-prefix "
                     "generate"),
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_generate.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_pipeline(ks=(1, 4, 16), n_batches=192, batch=32, d_in=64,
                    d_hidden=64, d_out=10, epochs=3):
    """Dispatch-amortization A/B for the pipelined training loop
    (train/pipeline.py): train the SAME small MLP through the real fit
    path at steps_per_call K ∈ ``ks`` and measure steady-state optimizer
    steps/sec. On a dispatch-bound loop (small model, CPU or a fast
    accelerator) bundling K steps into one lax.scan dispatch should
    multiply throughput. CPU-measurable by design — this doubles as the
    no-TPU fallback headline. Writes BENCH_pipeline.json and returns the
    result dict."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.updaters import Adam

    rng = np.random.default_rng(0)
    batches = [
        DataSet(rng.standard_normal((batch, d_in)).astype(np.float32),
                np.eye(d_out, dtype=np.float32)[
                    rng.integers(0, d_out, batch)])
        for _ in range(n_batches)
    ]

    def run(k):
        conf = (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(1e-3)).steps_per_call(k).list()
                .layer(DenseLayer(n_out=d_hidden, activation="relu"))
                .layer(OutputLayer(n_out=d_out, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        net = MultiLayerNetwork(conf).init()
        it = ExistingDataSetIterator(batches)
        net.fit(it, epochs=1)  # warmup epoch: compile both step shapes
        float(net.score_)
        t0 = time.perf_counter()
        net.fit(it, epochs=epochs)
        float(net.score_)  # drain the async dispatch queue
        dt = time.perf_counter() - t0
        return epochs * n_batches / dt

    per_k = {f"k{k}": round(run(k), 1) for k in ks}
    base = per_k.get("k1") or next(iter(per_k.values()))
    top_k = max(ks)
    top = per_k[f"k{top_k}"]
    result = {
        "metric": f"pipeline_steps_per_sec_k{top_k}",
        "value": top,
        "unit": "optimizer steps/sec",
        "vs_baseline": round(top / base, 3) if base else None,
        "extra": {
            "steps_per_sec": per_k,
            "config": (f"MLP {d_in}->{d_hidden}->{d_out}, batch {batch}, "
                       f"{n_batches} batches x {epochs} epochs, "
                       f"K in {list(ks)}"),
            "platform": jax.devices()[0].platform,
            "note": ("vs_baseline = steps/sec at the largest K over "
                     "steps_per_call=1; the acceptance gate is >= 1.5x "
                     "(dispatch amortization via in-graph lax.scan)"),
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_pipeline.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_obs(k=16, n_batches=192, batch=32, d_in=64, d_hidden=64,
               d_out=10, epochs=3):
    """Telemetry-overhead A/B (obs/telemetry.py): the SAME K-bundled MLP
    fit (the _bench_pipeline shape) trained (a) bare and (b) with the
    full monitoring surface on — in-graph per-step telemetry computed
    inside the lax.scan bundle plus a MetricsListener publishing
    steps/samples/loss/norms into the registry. The acceptance gate is
    telemetry-on >= 95% of telemetry-off steps/sec at K=16: monitoring
    must not claw back the pipelining win it was redesigned to protect.
    CPU-measurable by design; writes BENCH_obs.json."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs.metrics import MetricsListener, MetricsRegistry
    from deeplearning4j_tpu.obs.trace import RetraceMonitor
    from deeplearning4j_tpu.updaters import Adam

    rng = np.random.default_rng(0)
    batches = [
        DataSet(rng.standard_normal((batch, d_in)).astype(np.float32),
                np.eye(d_out, dtype=np.float32)[
                    rng.integers(0, d_out, batch)])
        for _ in range(n_batches)
    ]

    def build(telemetry: bool):
        b = (NeuralNetConfiguration.builder().seed(11)
             .updater(Adam(1e-3)).steps_per_call(k))
        if telemetry:
            b = b.telemetry(True)
        conf = (b.list()
                .layer(DenseLayer(n_out=d_hidden, activation="relu"))
                .layer(OutputLayer(n_out=d_out, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        net = MultiLayerNetwork(conf).init()
        if telemetry:
            net.add_listeners(MetricsListener(registry=MetricsRegistry(),
                                              frequency=10))
        it = ExistingDataSetIterator(batches)
        net.fit(it, epochs=1)  # warmup: compile both step shapes
        float(net.score_)
        return net, it

    def timed(net, it):
        t0 = time.perf_counter()
        net.fit(it, epochs=epochs)
        float(net.score_)  # drain the async dispatch queue
        return epochs * n_batches / (time.perf_counter() - t0)

    # interleaved best-of-N: CPU frequency/allocator drift across a long
    # process otherwise biases whichever arm runs later (observed: the
    # later arm measures FASTER than a bare earlier baseline)
    net_off, it_off = build(False)
    net_on, it_on = build(True)
    off_sps = on_sps = 0.0
    on_retraces = 0
    with RetraceMonitor() as mon:
        for _ in range(3):
            off_sps = max(off_sps, timed(net_off, it_off))
            mon.rebaseline()
            on_sps = max(on_sps, timed(net_on, it_on))
            on_retraces += mon.total()
    overhead_pct = round((1.0 - on_sps / off_sps) * 100.0, 2)
    result = {
        "metric": "obs_telemetry_overhead_pct",
        "value": overhead_pct,
        "unit": "% steps/sec lost with telemetry+metrics on",
        "vs_baseline": round(on_sps / off_sps, 4),
        "extra": {
            "steps_per_sec": {"telemetry_off": round(off_sps, 1),
                              "telemetry_on": round(on_sps, 1)},
            "steady_state_retraces_telemetry_on": on_retraces,
            "config": (f"MLP {d_in}->{d_hidden}->{d_out}, batch {batch}, "
                       f"{n_batches} batches x {epochs} epochs, K={k}, "
                       "MetricsListener(frequency=10)"),
            "platform": jax.devices()[0].platform,
            "note": ("gate: overhead <= 5% at K=16 — in-graph telemetry "
                     "rides the lax.scan bundle and is host-fetched at "
                     "most once per dispatch, so monitoring keeps the "
                     "pipelining win"),
        },
    }
    # forensic-layer overheads ride the same artifact: request tracing
    # under a serving storm (gate <= 5% p99) and the flight-recorder
    # ring on the K=16 bundled fit (gate <= 2% steps/sec)
    result["extra"]["tracing_ab"] = _bench_request_tracing()
    result["extra"]["flight_recorder"] = _bench_flight_overhead(
        batches, k=k, epochs=epochs)
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_obs.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_request_tracing(n_clients: int = 4, n_requests: int = 60,
                           max_size: int = 16, batch_limit: int = 32,
                           rounds: int = 10):
    """Per-request tracing A/B: the SAME warmed bucketed engine stormed
    through two batchers — request tracing on vs off — with the
    latencies POOLED across interleaved rounds and the quantiles taken
    over each pooled set. On this 2-core box a storm's p99 is
    scheduler-dominated and swings 10x round to round; interleaving
    spreads that noise over both arms equally, and pooling ~1.4k
    samples/arm makes the quantile stable where best-of-round was not.
    The trace itself is ~6 monotonic reads plus a ring append per
    request, so the p99 cost must stay <= 5% (the ISSUE 7 CI gate); the
    padded/real row counters always run (they are the pad-waste metric,
    not part of the tracing knob)."""
    import threading

    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        BucketPolicy,
        DynamicBatcher,
        InferenceEngine,
        TraceBuffer,
    )
    from deeplearning4j_tpu.serving.batcher import make_dispatcher
    from deeplearning4j_tpu.updaters import Adam

    d_in, d_hidden, d_out = 128, 256, 10
    conf = (NeuralNetConfiguration.builder().seed(11).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=d_hidden, activation="relu"))
            .layer(OutputLayer(n_out=d_out, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(d_in)).build())
    engine = InferenceEngine(MultiLayerNetwork(conf).init(),
                             buckets=BucketPolicy(max_batch=batch_limit))
    engine.warmup()
    rng = np.random.default_rng(0)
    inputs = {n: rng.standard_normal((n, d_in)).astype(np.float32)
              for n in range(1, max_size + 1)}

    def storm(tracing: bool) -> list:
        traces = TraceBuffer(256) if tracing else None
        batcher = DynamicBatcher(
            make_dispatcher(engine.infer_versioned, metrics=engine.metrics,
                            traces=traces),
            batch_limit=batch_limit, max_wait_ms=2.0, queue_limit=4096,
            metrics=engine.metrics, trace_requests=tracing)
        lats = []
        lock = threading.Lock()

        def client(tid):
            crng = np.random.default_rng(100 + tid)
            mine = []
            for _ in range(n_requests):
                n = int(crng.integers(1, max_size + 1))
                t0 = time.perf_counter()
                batcher.submit(inputs[n]).result(timeout=120)
                mine.append(time.perf_counter() - t0)
            with lock:
                lats.extend(mine)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        batcher.shutdown()
        return lats

    import gc

    pooled = {False: [], True: []}
    for _ in range(rounds):
        for arm in (False, True):
            # GC pauses on this 2-core box land on random requests and
            # dominate an un-collected p99; collecting at round
            # boundaries keeps the pause out of both arms' storms
            gc.collect()
            pooled[arm].extend(storm(arm))

    def quantiles(lats: list) -> dict:
        lats = sorted(lats)
        n = len(lats)

        def q(p):
            return round(lats[min(int(p * n), n - 1)] * 1e3, 3)

        return {"samples": n, "p50_ms": q(0.50), "p90_ms": q(0.90),
                "p99_ms": q(0.99)}

    off = quantiles(pooled[False])
    on = quantiles(pooled[True])
    overhead_pct = round((on["p99_ms"] / off["p99_ms"] - 1.0) * 100.0, 2)
    return {
        "tracing_off": off,
        "tracing_on": on,
        "p99_overhead_pct": overhead_pct,
        "gate": "p99 overhead <= 5%",
        "gate_pass": bool(overhead_pct <= 5.0),
    }


def _bench_flight_overhead(batches, k: int = 16, epochs: int = 3):
    """Flight-recorder ring overhead on the K-bundled fit: the same MLP
    trained bare vs with a FlightRecorderListener (private ring, no dump
    directory — the claim under test is the RING, not dump IO).
    Interleaved best-of-3; gate <= 2% steps/sec at K=16."""
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs.flight import (
        FlightRecorder,
        FlightRecorderListener,
    )
    from deeplearning4j_tpu.updaters import Adam

    n_batches = len(batches)
    d_in = batches[0].features.shape[1]

    def build(flight: bool):
        conf = (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(1e-3)).steps_per_call(k).list()
                .layer(DenseLayer(n_out=64, activation="relu"))
                .layer(OutputLayer(n_out=batches[0].labels.shape[1],
                                   activation="softmax", loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        net = MultiLayerNetwork(conf).init()
        if flight:
            net.add_listeners(FlightRecorderListener(
                recorder=FlightRecorder(capacity=2048)))
        it = ExistingDataSetIterator(batches)
        net.fit(it, epochs=1)  # warmup
        float(net.score_)
        return net, it

    def timed(net, it):
        t0 = time.perf_counter()
        net.fit(it, epochs=epochs)
        float(net.score_)
        return epochs * n_batches / (time.perf_counter() - t0)

    net_off, it_off = build(False)
    net_on, it_on = build(True)
    off_sps = on_sps = 0.0
    for _ in range(5):  # interleaved best-of-5: the ring's real cost is
        # well under this box's ±3% run-to-run drift, so the per-arm max
        # needs the extra rounds to converge
        off_sps = max(off_sps, timed(net_off, it_off))
        on_sps = max(on_sps, timed(net_on, it_on))
    overhead_pct = round((1.0 - on_sps / off_sps) * 100.0, 2)
    return {
        "steps_per_sec": {"flight_off": round(off_sps, 1),
                          "flight_on": round(on_sps, 1)},
        "overhead_pct": overhead_pct,
        "k": k,
        "gate": "steps/sec overhead <= 2% at K=16",
        "gate_pass": bool(overhead_pct <= 2.0),
    }


def _bench_tune(n_trials=8, steps=96, k=8, n_batches=24, batch=32,
                d_in=32, d_hidden=32, d_out=5):
    """Trials/sec A/B for the hyperparameter tuner (tune/runner.py):
    the SAME n-trial lr/l2 study executed (a) sequentially — each trial
    trained alone through the stock single-step fit path (the
    TensorFlow-era tuner shape: one process per trial, one dispatch per
    step) and (b) as ONE vmapped population with ``steps_per_call=k``
    bundling (n trials x k steps per dispatch). Numerics are
    bit-identical by construction (the tuner's parity tests pin that
    down), so the ratio is pure dispatch/vectorization win — meaningful
    on any backend, and this doubles as the no-TPU fallback artifact.
    Writes BENCH_tune.json and returns the result dict."""
    import functools

    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.train.earlystopping import (
        DataSetLossCalculator,
        ScoreCalculatorObjective,
    )
    from deeplearning4j_tpu.tune import (
        AshaScheduler,
        ContinuousParameterSpace,
        SearchSpace,
        Study,
        mlp_factory,
    )

    # Every Study builds fresh jit closures, so without a persistent
    # compile cache the "timed" run would re-pay XLA compilation and the
    # ratio would measure relative compile cost, not dispatch. The
    # shared cache (threshold 0: these programs compile fast) lets the
    # warmup run compile and the timed run only re-trace.
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    rng = np.random.default_rng(7)
    mk = lambda n: [  # noqa: E731
        DataSet(rng.standard_normal((batch, d_in)).astype(np.float32),
                np.eye(d_out, dtype=np.float32)[
                    rng.integers(0, d_out, batch)])
        for _ in range(n)]
    train, val = mk(n_batches), mk(4)
    space = SearchSpace(
        functools.partial(mlp_factory, d_in, d_out, widths=(d_hidden,)),
        {"lr": ContinuousParameterSpace(1e-3, 1e-1, scale="log"),
         "l2": ContinuousParameterSpace(1e-5, 1e-2, scale="log")})

    def objective():
        return ScoreCalculatorObjective(
            DataSetLossCalculator(ExistingDataSetIterator(val)))

    def run(engine, spc, workers=None):
        # single-rung ladder: both engines train every trial to `steps`
        # (scheduler decisions would otherwise let one engine do less
        # work and fake the ratio)
        study = Study(space, train, objective(),
                      scheduler=AshaScheduler(steps, steps, eta=2),
                      num_trials=n_trials, seed=3, engine=engine,
                      steps_per_call=spc, workers=workers)
        study.run()  # warmup: compile both paths
        study2 = Study(space, train, objective(),
                       scheduler=AshaScheduler(steps, steps, eta=2),
                       num_trials=n_trials, seed=3, engine=engine,
                       steps_per_call=spc, workers=workers)
        t0 = time.perf_counter()
        study2.run()
        dt = time.perf_counter() - t0
        return n_trials / dt

    seq = run("pool", 1, workers=1)      # sequential: one trial at a time
    pop = run("population", k)
    result = {
        "metric": "tune_trials_per_sec_population",
        "value": round(pop, 2),
        "unit": f"trials/sec ({steps} steps each)",
        "vs_baseline": round(pop / seq, 3) if seq else None,
        "extra": {
            "sequential_trials_per_sec": round(seq, 2),
            "population_trials_per_sec": round(pop, 2),
            "config": (f"{n_trials} trials, MLP {d_in}->{d_hidden}->"
                       f"{d_out}, batch {batch}, {steps} steps/trial, "
                       f"steps_per_call {k}"),
            "platform": jax.devices()[0].platform,
            "note": ("vs_baseline = vmapped-population trials/sec over "
                     "sequential solo training; acceptance gate >= 2x "
                     "(N-trial vmap + K-step scan per dispatch)"),
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_tune.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_reshard(d_in=384, d_hidden=512, n_hidden=3, d_out=7,
                   batch=16, n_from=8, n_to=2, rounds=5):
    """Elastic N→M resharding A/B (parallel/reshard.py): move a trained
    model's state — params + ZeRO-1 sharded Adam slots — from an
    ``n_from``-device mesh onto an ``n_to``-device mesh two ways:

    (a) **reshard-in-place** (the PR-8 engine): the flat-shard opt state
        is re-split (N, chunk_N)→(M, chunk_M) with device ops + a
        device_put onto the target sharding, params re-place
        device-to-device — ``host_bytes == 0`` by construction;
    (b) **gather-to-host-and-reload** (the legacy path): gather the
        canonical per-layer state to host numpy, then re-shard it onto
        the target mesh — every byte staged through host buffers.

    Both paths produce bit-identical target state (asserted). The
    transfer-size ledger is the acceptance instrument: the reshard path
    must stage ≤ 0.5× the gather path's host bytes (it stages none).
    Wall times are best-of-``rounds`` interleaved (sequential A/B
    mismeasures on this box). Writes BENCH_reshard.json."""
    import gc

    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import reshard as _reshard
    from deeplearning4j_tpu.parallel.mesh import TrainingMesh
    from deeplearning4j_tpu.parallel.zero import (
        build_layout,
        shard_model_opt_state,
    )
    from deeplearning4j_tpu.updaters import Adam

    devices = jax.devices()
    if len(devices) < n_from:
        raise RuntimeError(f"need {n_from} devices, have {len(devices)}")
    b = NeuralNetConfiguration.builder().seed(11).updater(Adam(1e-3)).list()
    for _ in range(n_hidden):
        b = b.layer(DenseLayer(n_out=d_hidden, activation="relu"))
    conf = (b.layer(OutputLayer(n_out=d_out, activation="softmax",
                                loss="mcxent"))
            .set_input_type(InputType.feed_forward(d_in)).build())
    model = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(3)
    ds = DataSet(rng.standard_normal((batch, d_in)).astype(np.float32),
                 np.eye(d_out, dtype=np.float32)[
                     rng.integers(0, d_out, batch)])
    for _ in range(2):  # materialize non-trivial Adam slots
        model.fit(ds)

    mesh_n = TrainingMesh(data=n_from, devices=devices[:n_from])
    mesh_m = TrainingMesh(data=n_to, devices=devices[:n_to])
    layout_n = build_layout(model, n_from)
    layout_m = build_layout(model, n_to)
    z_n = shard_model_opt_state(model, layout_n, mesh=mesh_n.mesh)
    jax.block_until_ready(z_n)

    def run_reshard():
        stats = _reshard.TransferStats()
        z_m, stats = _reshard.reshard_zero1(z_n, layout_n, layout_m,
                                            mesh_m, stats=stats)
        plan = _reshard.plan_replicated(model.params_, mesh_m,
                                        n_from=n_from)
        p_m, stats = plan.execute(model.params_, stats)
        jax.block_until_ready((z_m, p_m))
        return z_m, p_m, stats

    def run_gather():
        stats = _reshard.TransferStats()
        canonical = layout_n.unshard_opt_state(z_n, model.opt_state_)
        # every canonical leaf is a host-materialized copy: account it
        host_p, stats = _reshard.gather_to_host(model.params_, stats)
        for layer in canonical:
            for slots in layer.values():
                for s in slots.values():
                    stats.add(_reshard.ROUTE_HOST,
                              np.asarray(s).nbytes)
        z_m = layout_m.shard_opt_state(canonical, mesh=mesh_m.mesh)
        p_m = jax.device_put(host_p, mesh_m.replicated())
        jax.block_until_ready((z_m, p_m))
        return z_m, p_m, stats

    # parity: both paths land the same bytes on the target mesh
    zr, pr, _ = run_reshard()
    zg, pg, _ = run_gather()
    for a, bslots in zip(zr, zg):
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(bslots[k]))
    for pa, pb in zip(jax.tree_util.tree_leaves(pr),
                      jax.tree_util.tree_leaves(pg)):
        assert np.array_equal(np.asarray(pa), np.asarray(pb))

    wall_r, wall_g = [], []
    stats_r = stats_g = None
    for _ in range(rounds):  # interleaved best-of-N
        gc.collect()
        t0 = time.perf_counter()
        *_, stats_r = run_reshard()
        wall_r.append(time.perf_counter() - t0)
        gc.collect()
        t0 = time.perf_counter()
        *_, stats_g = run_gather()
        wall_g.append(time.perf_counter() - t0)
    wr, wg = min(wall_r), min(wall_g)
    host_ratio = (stats_r.host_bytes / stats_g.host_bytes
                  if stats_g.host_bytes else None)
    result = {
        "metric": "reshard_vs_gather_host_bytes_ratio",
        "value": round(host_ratio, 6) if host_ratio is not None else None,
        "unit": f"host-staged bytes, reshard/gather ({n_from}->{n_to} "
                "devices)",
        "vs_baseline": round(wr / wg, 3) if wg else None,
        "extra": {
            "reshard_host_bytes": int(stats_r.host_bytes),
            "gather_host_bytes": int(stats_g.host_bytes),
            "reshard_device_bytes": int(stats_r.device_bytes),
            "reshard_wall_ms": round(wr * 1e3, 3),
            "gather_wall_ms": round(wg * 1e3, 3),
            "wall_ratio": round(wr / wg, 3) if wg else None,
            "rounds": rounds,
            "bit_identical_target_state": True,
            "config": (f"MLP {d_in}->{n_hidden}x{d_hidden}->{d_out}, "
                       f"ZeRO-1 Adam slots, {n_from}->{n_to} reshard"),
            "platform": jax.devices()[0].platform,
            "note": ("gate: reshard stages <= 0.5x the gather path's "
                     "host bytes (it stages 0 — the no-gather-to-host "
                     "contract of the N->M path); wall_ratio reported "
                     "for reference, CPU virtual devices share one "
                     "heap so wall gains are understated there"),
        },
    }
    gate_ok = stats_r.host_bytes <= 0.5 * stats_g.host_bytes
    result["extra"]["gate_host_bytes_le_half"] = bool(gate_ok)
    if not gate_ok:
        result["extra"]["gate_failure"] = (
            f"reshard staged {stats_r.host_bytes} host bytes vs gather "
            f"{stats_g.host_bytes}")
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_reshard.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_sharded(batch=8, reps=30, gen_new=16, d_in=64, d_hidden=256,
                   d_out=8):
    """Mesh-sharded serving gates (parallel/serving_mesh.py +
    serving/sharded.py): a tensor-parallel engine on a 2x4 (batch,
    model) mesh must be *correct and cheap per device* before any
    throughput claim:

    - **parity**: sharded inference matches the solo engine within
      float-reassociation tolerance (rtol 1e-5 — GSPMD re-orders the
      TP partial sums), and sharded *greedy generation* matches the
      solo token stream EXACTLY (argmax is reassociation-robust here);
    - **memory**: per-device weight bytes <= total/n_model +
      replicated + slack — the whole point of TP serving is that no
      device holds the full model;
    - **storm**: ``reps`` repeated fixed-shape dispatches retrace 0
      times (sharded placement must not cost steady-state compiles),
      and the second generation request retraces 0;
    - **ledger**: reshard-on-load stages 0 host bytes (checkpoint →
      mesh is device→device, both for inference and the KV-slab
      engine).

    Wall-clock A/B (sharded vs solo dispatch) is reported but its
    speedup gate is ``tpu_pending`` — CPU virtual devices share one
    heap, so TP wins only materialize on real accelerators. Writes
    BENCH_sharded.json."""
    import tempfile

    import jax

    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.serving_mesh import ServingMesh
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    from deeplearning4j_tpu.serving.generate import GenerationEngine
    from deeplearning4j_tpu.serving.sharded import (
        ShardedInferenceEngine,
        sharded_generation_engine,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    devices = jax.devices()
    if len(devices) < 8:
        raise RuntimeError(f"need 8 devices, have {len(devices)}")
    mesh = ServingMesh(batch=2, model=4, devices=devices[:8])

    def _net(seed=11):
        conf = (NeuralNetConfiguration.builder().seed(seed).list()
                .layer(DenseLayer(n_out=d_hidden, activation="relu"))
                .layer(DenseLayer(n_out=d_hidden, activation="relu"))
                .layer(OutputLayer(n_out=d_out, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(5)
    x = rng.standard_normal((batch, d_in)).astype(np.float32)

    # -- inference leg: reshard-on-load from a checkpoint ------------------
    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "ck")
        save_checkpoint(_net(), ck)
        solo = InferenceEngine.from_checkpoint(ck)
        sharded = ShardedInferenceEngine.from_checkpoint(ck, mesh=mesh)
    y_solo = solo.infer(x)
    y_sh = sharded.infer(x)
    max_abs = float(np.max(np.abs(y_solo - y_sh)))
    parity_ok = bool(np.allclose(y_solo, y_sh, rtol=1e-5, atol=1e-6))

    rep = sharded.shard_report
    slack = rep["replicated_bytes"] + 4096
    mem_ok = rep["per_device_bytes"] <= (rep["total_bytes"] / mesh.n_model
                                         + slack)
    ratio = rep["per_device_bytes"] / rep["total_bytes"]
    host_bytes = int(sharded.reshard_stats.host_bytes)

    # -- dispatch storm: fixed shape, zero retraces, wall A/B --------------
    c0 = sharded.compile_count
    t0 = time.perf_counter()
    for _ in range(reps):
        sharded.infer(x)
    wall_sh = (time.perf_counter() - t0) / reps
    storm_retraces = sharded.compile_count - c0
    t0 = time.perf_counter()
    for _ in range(reps):
        solo.infer(x)
    wall_solo = (time.perf_counter() - t0) / reps

    # -- generation leg: greedy token parity + steady-state retrace 0 ------
    def _lm(seed=3):
        return TransformerLM(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, max_length=64, seed=seed).init()

    prompt = np.asarray([5, 9, 11, 2])
    gsolo = GenerationEngine(_lm(), n_slots=4, max_length=64)
    try:
        toks_solo = list(gsolo.submit(prompt, max_new=gen_new,
                                      temperature=0.0).result(timeout=120))
    finally:
        gsolo.shutdown()
    gsh = sharded_generation_engine(_lm(), mesh, n_slots=4, max_length=64)
    try:
        toks_sh = list(gsh.submit(prompt, max_new=gen_new,
                                  temperature=0.0).result(timeout=240))
        tc0 = dict(gsh.trace_counts)
        list(gsh.submit(np.asarray([7, 1, 3]), max_new=gen_new,
                        temperature=0.0).result(timeout=240))
        tc1 = dict(gsh.trace_counts)
    finally:
        gsh.shutdown()
    gen_parity = toks_solo == toks_sh
    gen_retraces = sum(tc1.get(k, 0) - tc0.get(k, 0) for k in tc1
                       if k.startswith("generation_"))
    gen_host_bytes = int(gsh.shard_stats.host_bytes)

    gates = {
        "inference_parity_rtol1e5": parity_ok,
        "generation_greedy_tokens_exact": bool(gen_parity),
        "per_device_weight_bytes_le_1_over_n": bool(mem_ok),
        "storm_retraces_zero": storm_retraces == 0,
        "generation_steady_retraces_zero": gen_retraces == 0,
        "reshard_host_bytes_zero": host_bytes == 0 and gen_host_bytes == 0,
    }
    gates_ok = all(gates.values())
    on_tpu = jax.devices()[0].platform == "tpu"
    result = {
        "metric": "sharded_per_device_weight_ratio",
        "value": round(ratio, 6),
        "unit": (f"per-device / total weight bytes on a 2x4 mesh "
                 f"(bound 1/{mesh.n_model} + replicated)"),
        "vs_baseline": round(wall_sh / wall_solo, 3) if wall_solo else None,
        "extra": {
            "gates": gates,
            "gates_ok": gates_ok,
            "max_abs_diff": max_abs,
            "per_device_bytes": int(rep["per_device_bytes"]),
            "total_bytes": int(rep["total_bytes"]),
            "replicated_bytes": int(rep["replicated_bytes"]),
            "estimator_agreement": rep["estimator_agreement"],
            "reshard_host_bytes": host_bytes,
            "gen_reshard_host_bytes": gen_host_bytes,
            "storm_retraces": int(storm_retraces),
            "gen_steady_retraces": int(gen_retraces),
            "sharded_infer_ms": round(wall_sh * 1e3, 3),
            "solo_infer_ms": round(wall_solo * 1e3, 3),
            "tokens": len(toks_sh),
            "policy": rep["policy"],
            "mesh": {"batch": 2, "model": 4},
            "platform": jax.devices()[0].platform,
            "tpu_pending": not on_tpu,
            "note": ("correctness/memory/retrace gates bind on any "
                     "backend; the dispatch speedup gate is tpu_pending "
                     "— 8 virtual CPU devices share one heap, so the "
                     "wall ratio here measures partitioning overhead, "
                     "not the TP win"),
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_sharded.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_kernels(n_requests: int = 12, gen_slots: int = 6,
                   zero_steps: int = 60, int8_rounds: int = 5):
    """Fused-kernel A/Bs (ISSUE 12, nn/ops/): each of the three TPP-style
    kernels vs its reference path, parity asserted alongside throughput.

    1. **fused LSTM decode** — GenerationEngine tokens/sec on a greedy
       request storm, direct-cell decode path (fused Pallas cell on TPU)
       vs the PR-9 generic ``_forward`` path. Per-request outputs must be
       bit-identical; zero steady-state retraces in both modes.
    2. **fused ZeRO-1 update** — sharded-step optimizer steps/sec, fused
       single-pass Adam kernel vs the reference composition, on the
       largest local mesh; a forced-interpret parity leg asserts
       bit-exact params + Adam slots through the REAL kernel math even
       where the compiled kernel cannot run.
    3. **int8 serving matmul** — InferenceEngine rows/sec at the largest
       batch bucket, int8 weight-quantized heads vs fp32, plus the
       backend-independent instrument (weight bytes ≤ 0.5×) and serving
       top-1 agreement.

    Gates (ISSUE 12): LSTM decode ≥1.3× and int8 ≥1.5× apply where the
    kernels actually ENGAGE (TPU); on the CPU fallback each leg gates on
    no-regression (≥0.9× — both legs then run the same reference math,
    the margin is measurement noise on this 2-core box) with the real
    win recorded ``tpu_pending`` — the ZeRO-1 gate is ≤1.0× (no
    regression) on CPU by construction. Writes BENCH_kernels.json."""
    import gc
    import jax

    from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry

    reg = default_kernel_registry()
    platform = jax.devices()[0].platform
    results = {}

    # ---- 1. fused LSTM decode --------------------------------------------
    from deeplearning4j_tpu.models.textgen_lstm import TextGenerationLSTM
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    model = TextGenerationLSTM(num_classes=77, units=256,
                               max_length=40).init()
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 77, (int(rng.integers(16, 33)),)
                          ).astype(np.int32), int(rng.integers(48, 65)))
            for _ in range(n_requests)]
    total_new = sum(mn for _, mn in reqs)

    def run_engine(cell_path):
        eng = GenerationEngine(model, n_slots=gen_slots, max_length=128,
                               queue_limit=n_requests + 4,
                               default_timeout_s=600.0,
                               decode_cell_path=cell_path)
        eng.warmup()
        before = dict(eng.trace_counts)
        t0 = time.perf_counter()
        pending = [eng.submit(p, max_new=mn, timeout=600)
                   for p, mn in reqs]
        outs = [r.result(timeout=600) for r in pending]
        dt = time.perf_counter() - t0
        retraces = sum(eng.trace_counts.get(k, 0) - before.get(k, 0)
                       for k in eng.trace_counts)
        eng.shutdown()
        return outs, total_new / dt, retraces

    # interleaved best-of-3: sequential A/B mismeasures on this box
    ref_tps = fused_tps = 0.0
    ref_out = fused_out = None
    retr = 0
    for _ in range(3):
        gc.collect()
        ref_out, tps, r1 = run_engine(False)
        ref_tps = max(ref_tps, tps)
        gc.collect()
        fused_out, tps, r2 = run_engine(True)
        fused_tps = max(fused_tps, tps)
        retr += r1 + r2
    lstm_parity = sum(
        0 if np.array_equal(a, b) else 1
        for a, b in zip(ref_out, fused_out))
    lstm_live = any(v["enabled"]
                    for v in reg.snapshot().get("fused_lstm", {}).values())
    lstm_ratio = fused_tps / ref_tps if ref_tps else None
    results["fused_lstm_decode"] = {
        "engine_tokens_per_sec_fused": round(fused_tps, 1),
        "engine_tokens_per_sec_reference": round(ref_tps, 1),
        "ratio": round(lstm_ratio, 3),
        "kernel_engaged": lstm_live,
        "parity_failures": lstm_parity,
        "storm_retraces": retr,
        "gate": ("fused/reference >= 1.3 (kernel engaged)" if lstm_live
                 else "no regression >= 0.9 on CPU fallback; 1.3x gate "
                      "tpu_pending"),
        "gate_pass": bool(lstm_parity == 0 and retr == 0 and
                          (lstm_ratio >= 1.3 if lstm_live
                           else lstm_ratio >= 0.9)),
        "tpu_pending": not lstm_live,
    }

    # ---- 2. fused ZeRO-1 update ------------------------------------------
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import zero
    from deeplearning4j_tpu.parallel.mesh import TrainingMesh
    from deeplearning4j_tpu.updaters import Adam

    n_dev = len(jax.devices())
    mesh = TrainingMesh(data=n_dev)

    def build_net(seed=7):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Adam(1e-3)).weight_init("xavier").list()
                .layer(DenseLayer(n_out=512, activation="relu"))
                .layer(DenseLayer(n_out=512, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(256)).build())
        return MultiLayerNetwork(conf).init()

    Xz = rng.standard_normal((8 * n_dev, 256)).astype(np.float32)
    yz = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 8 * n_dev)]

    def zero_leg(fused):
        net = build_net()
        step, layout = zero.make_sharded_train_step(net, mesh,
                                                    fused_update=fused)
        zopt = zero.shard_model_opt_state(net, layout, mesh=mesh.mesh)
        params, state = net.params_, net.state_
        import jax.numpy as jnp

        def one(i, params, zopt, state):
            return step(params, zopt, state, jnp.asarray(Xz),
                        jnp.asarray(yz), None, None,
                        jax.random.PRNGKey(0), jnp.asarray(i, jnp.int32),
                        jnp.asarray(0, jnp.int32))

        params, zopt, state, score = one(0, params, zopt, state)
        jax.block_until_ready(score)
        t0 = time.perf_counter()
        for i in range(zero_steps):
            params, zopt, state, score = one(i + 1, params, zopt, state)
        jax.block_until_ready(score)
        dt = time.perf_counter() - t0
        return zero_steps / dt, params, zopt

    ref_sps = fused_sps = 0.0
    for _ in range(3):
        gc.collect()
        ref_sps = max(ref_sps, zero_leg(False)[0])
        gc.collect()
        fused_sps = max(fused_sps, zero_leg(None)[0])
    zero_live = any(v["enabled"]
                    for v in reg.snapshot().get("fused_zero1", {}).values())
    # parity leg: force the kernel math through the interpreter where the
    # compiled kernel cannot engage (the oracle half of the A/B)
    interp_parity = None
    if not zero_live:
        prev = os.environ.get("DL4J_TPU_FUSED_ZERO1")
        os.environ["DL4J_TPU_FUSED_ZERO1"] = "interpret"
        reg.reset("fused_zero1")
        try:
            _, p_f, z_f = zero_leg(None)
            _, p_r, z_r = zero_leg(False)
            interp_parity = all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree_util.tree_leaves((p_f, z_f)),
                                jax.tree_util.tree_leaves((p_r, z_r))))
        finally:
            if prev is None:
                os.environ.pop("DL4J_TPU_FUSED_ZERO1", None)
            else:
                os.environ["DL4J_TPU_FUSED_ZERO1"] = prev
            reg.reset("fused_zero1")
    zero_ratio = fused_sps / ref_sps if ref_sps else None
    results["fused_zero1_update"] = {
        "steps_per_sec_fused": round(fused_sps, 1),
        "steps_per_sec_reference": round(ref_sps, 1),
        "ratio": round(zero_ratio, 3),
        "kernel_engaged": zero_live,
        "n_devices": n_dev,
        "interpret_parity_bit_exact": interp_parity,
        "gate": "no regression (ISSUE: <= 1.0x on CPU; real win "
                "tpu_pending) + bit-exact parity",
        "gate_pass": bool(zero_ratio >= 0.9 and
                          (interp_parity is not False)),
        "tpu_pending": not zero_live,
    }

    # ---- 3. int8 serving matmul ------------------------------------------
    from deeplearning4j_tpu.serving.buckets import BucketPolicy
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    conf8 = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
             .weight_init("xavier").list()
             .layer(DenseLayer(n_out=512, activation="relu"))
             .layer(DenseLayer(n_out=512, activation="relu"))
             .layer(OutputLayer(n_out=64, activation="softmax",
                                loss="mcxent"))
             .set_input_type(InputType.feed_forward(512)).build())
    net8 = MultiLayerNetwork(conf8).init()
    Xi = rng.standard_normal((400, 512)).astype(np.float32)
    yi = np.eye(64, dtype=np.float32)[rng.integers(0, 64, 400)]
    for _ in range(10):
        net8.fit(Xi, yi)
    bucket = 64
    pol = BucketPolicy(batch_buckets=[bucket], max_batch=bucket)
    e_f32 = InferenceEngine(net8, buckets=pol)
    e_i8 = InferenceEngine(net8, buckets=pol.copy(), int8_serving=True)
    Xb = Xi[:bucket]
    for e in (e_f32, e_i8):
        e.warmup()

    def int8_leg(eng, n=40):
        t0 = time.perf_counter()
        for _ in range(n):
            eng.infer(Xb)
        return bucket * n / (time.perf_counter() - t0)

    f32_rps = i8_rps = 0.0
    for _ in range(int8_rounds):
        gc.collect()
        f32_rps = max(f32_rps, int8_leg(e_f32))
        gc.collect()
        i8_rps = max(i8_rps, int8_leg(e_i8))
    a = e_f32.infer(Xi[:128])
    b = e_i8.infer(Xi[:128])
    top1 = float(np.mean(np.argmax(a, 1) == np.argmax(b, 1)))
    rep = e_i8.int8_report
    bytes_ratio = (rep["weight_bytes_int8"] / rep["weight_bytes_fp32"]
                   if rep and rep["weight_bytes_fp32"] else None)
    int8_live = any(v["enabled"]
                    for v in reg.snapshot().get("int8_matmul", {}).values())
    int8_ratio = i8_rps / f32_rps if f32_rps else None
    results["int8_serving_matmul"] = {
        "rows_per_sec_int8": round(i8_rps, 1),
        "rows_per_sec_f32": round(f32_rps, 1),
        "ratio": round(int8_ratio, 3),
        "bucket": bucket,
        "kernel_engaged": int8_live,
        "weight_bytes_ratio": round(bytes_ratio, 3),
        "top1_agreement": top1,
        "quantized_layers": rep["layers_quantized"] if rep else 0,
        "gate": ("int8/f32 >= 1.5 at the largest bucket (kernel "
                 "engaged)" if int8_live else
                 "CPU fallback: weight bytes <= 0.5x (the bandwidth "
                 "instrument the TPU win is made of) + top-1 >= 0.99 + "
                 "ratio >= 0.8 (the XLA fallback re-materializes the "
                 "f32 weights per dispatch — measured 0.80-0.87x on "
                 "this box; the kernel exists to turn that into the "
                 "bandwidth win); 1.5x gate tpu_pending"),
        "gate_pass": bool(top1 >= 0.99 and
                          (int8_ratio >= 1.5 if int8_live else
                           (bytes_ratio is not None and bytes_ratio <= 0.5
                            and int8_ratio >= 0.8))),
        "tpu_pending": not int8_live,
    }

    gates_ok = all(v["gate_pass"] for v in results.values())
    result = {
        "metric": "fused_kernels_ab",
        "value": round(results["fused_lstm_decode"]
                       ["engine_tokens_per_sec_fused"], 1),
        "unit": "tokens/sec (fused LSTM decode headline)",
        "vs_baseline": results["fused_lstm_decode"]["ratio"],
        "extra": {
            **results,
            "kernel_registry": reg.snapshot(),
            "platform": platform,
            "ok": gates_ok,
            "note": ("three fused-kernel A/Bs vs their reference paths; "
                     "gates per ISSUE 12 — on CPU fallback the kernels "
                     "cannot engage, so the speedup gates record "
                     "tpu_pending and gate on parity + no-regression "
                     "(the ZeRO-1 CPU gate is <= 1.0x by design)"),
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_kernels.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_chaos():
    """The full resilience drill matrix (chaos/drills.py) — single-fault
    AND paired-fault storms — as a scored artifact. Gates (ISSUE 13):
    every drill green (an injected fault surfaces as a typed error or a
    completed recovery — never a hang, a bare exception, or a corrupt
    artifact), >= 12 drills with >= 3 paired compositions, zero
    silent-corruption findings. Writes BENCH_chaos.json and returns the
    headline record."""
    import time as _time

    import jax

    from deeplearning4j_tpu.chaos import drills

    t0 = _time.monotonic()
    scorecard = drills.run_matrix(fast_only=False, verbose=True)
    wall = _time.monotonic() - t0
    recoveries = {d["drill"]: d["recovery_s"]
                  for d in scorecard["drills"] if "recovery_s" in d}
    gates = {
        "all_drills_green": scorecard["ok"],
        "matrix_floor_12": scorecard["n_drills"]
        - scorecard["n_skipped"] >= 12,
        "paired_floor_3": scorecard["n_paired"] >= 3,
        "zero_silent_corruption":
            not scorecard["silent_corruption_findings"],
        # ISSUE 14: the lock witness rides every drill; an
        # acquisition-order cycle anywhere in the matrix is an ABBA
        # deadlock pattern waiting for the right schedule
        "zero_lock_cycles": scorecard.get("lock_cycles", 0) == 0,
        # ISSUE 15: detection verified, not just recovery — >= 8 drills
        # declare expected_alerts and every one of those alerts FIRED
        # in the drill's detection evaluator
        "alerts_verified_floor_8":
            scorecard.get("alerts_verified", 0) >= 8,
    }
    result = {
        "metric": "chaos_drills_green",
        "value": scorecard["n_green"],
        "unit": "drills",
        "gates": gates,
        "gates_ok": all(gates.values()),
        "extra": {
            "n_drills": scorecard["n_drills"],
            "n_red": scorecard["n_red"],
            "n_skipped": scorecard["n_skipped"],
            "n_paired": scorecard["n_paired"],
            "wall_s": round(wall, 1),
            "recovery_latency_s": recoveries,
            "verdicts": {d["drill"]: d["verdict"]
                         for d in scorecard["drills"]},
            "silent_corruption_findings":
                scorecard["silent_corruption_findings"],
            "n_devices": len(jax.devices()),
            "platform": jax.devices()[0].platform,
        },
        "scorecard": scorecard,
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_chaos.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def _bench_alerts(k=16, n_batches=192, batch=32, d_in=64, d_hidden=64,
                  d_out=10, epochs=24, rounds=5, tick_interval_s=0.25):
    """SLO alert-engine gates (ISSUE 15), two legs in one artifact:

    1. **Evaluator overhead** — the SAME K=16-bundled MLP fit
       (_bench_obs's shape) with the flight listener on, (a) bare and
       (b) with a full default-pack AlertEvaluator watching the flight
       ring and ticking at scrape cadence on a sidecar thread. Gate:
       ≤ 1% steps/sec lost — watching must be free next to training.
    2. **Detection latency** — inject real faults (a NaN-gradient storm
       through the chaos grad_nan seam; disk-full on the checkpoint
       fsync) and count evaluator ticks from fault to alert FIRING.
       Gate: ≤ 2 ticks for every fault — the contract the chaos matrix
       asserts drill-by-drill via expected_alerts.

    CPU-measurable by design; writes BENCH_alerts.json."""
    import threading as _threading

    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs import slo
    from deeplearning4j_tpu.obs.alerts import AlertEvaluator
    from deeplearning4j_tpu.obs.flight import FlightRecorderListener
    from deeplearning4j_tpu.updaters import Adam

    rng = np.random.default_rng(0)
    batches = [
        DataSet(rng.standard_normal((batch, d_in)).astype(np.float32),
                np.eye(d_out, dtype=np.float32)[
                    rng.integers(0, d_out, batch)])
        for _ in range(n_batches)
    ]

    from deeplearning4j_tpu.obs.flight import FlightRecorder

    def build():
        conf = (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(1e-3)).steps_per_call(k).list()
                .layer(DenseLayer(n_out=d_hidden, activation="relu"))
                .layer(OutputLayer(n_out=d_out, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        net = MultiLayerNetwork(conf).init()
        # each arm records flight events into its OWN ring (the ring's
        # cost is gated separately in BENCH_obs); only the watched
        # arm's ring gets the evaluator's observer, so the A/B delta
        # isolates exactly the alert engine: per-event observer +
        # scrape-cadence evaluator ticks
        rec = FlightRecorder()
        net.add_listeners(FlightRecorderListener(recorder=rec,
                                                 directory=None,
                                                 dump_every_s=None))
        it = ExistingDataSetIterator(batches)
        net.fit(it, epochs=1)  # warmup: compile both step shapes
        float(net.score_)
        return net, it, rec

    def timed(net, it):
        t0 = time.perf_counter()
        net.fit(it, epochs=epochs)
        float(net.score_)  # drain the async dispatch queue
        return epochs * n_batches / (time.perf_counter() - t0)

    net_off, it_off, _rec_off = build()
    net_on, it_on, rec_on = build()
    evaluator = slo.build_default_evaluator(recorder=rec_on,
                                            min_tick_interval=0.0)
    stop = _threading.Event()

    def ticker():
        while not stop.wait(tick_interval_s):
            evaluator.tick()

    events0 = rec_on.recorded_total
    on_wall = 0.0
    try:
        # interleaved, order-alternated rounds: CPU frequency/allocator
        # drift across a long process biases whichever arm runs later
        # (the _bench_obs lesson). The sidecar ticker runs ONLY while
        # the watched arm is timed — a ticker spanning both arms would
        # bill the engine's tick cost to the baseline too and gate
        # nothing.
        ratios = []
        off_sps = on_sps = 0.0
        for r in range(rounds):
            def timed_on():
                stop.clear()
                t = _threading.Thread(target=ticker, daemon=True,
                                      name="alert-ticker")
                t.start()
                try:
                    return timed(net_on, it_on)
                finally:
                    stop.set()
                    t.join(timeout=5)

            if r % 2 == 0:
                off = timed(net_off, it_off)
                on = timed_on()
            else:
                on = timed_on()
                off = timed(net_off, it_off)
            ratios.append(on / off)
            off_sps = max(off_sps, off)
            on_sps = max(on_sps, on)
            on_wall += epochs * n_batches / on
    finally:
        stop.set()
    ticks_run = evaluator.ticks
    ab_ratio = sorted(ratios)[len(ratios) // 2]
    ab_overhead_pct = round((1.0 - ab_ratio) * 100.0, 2)
    events_per_sec = (rec_on.recorded_total - events0) / max(on_wall,
                                                             1e-9)

    # THE GATED NUMBER is a direct decomposition: (marginal per-event
    # observer cost + per-tick evaluation cost) x the rates actually
    # measured at K=16. The wall-clock A/B above stays as a sanity
    # cross-check, but its per-round ratios swing +-3-4% on this box —
    # a 1% gate read off it would be judging timing noise, in either
    # direction (the first draft of this bench was caught in review
    # gating an A/B whose two arms were identical). Microbenching the
    # two engine costs at N=20k/2k iterations is stable to well under
    # a microsecond; counting the sidecar ticks against the step
    # thread is conservative (they run on their own core).
    N_EV = 20000
    rec_bare = FlightRecorder()
    t0 = time.perf_counter()
    for _ in range(N_EV):
        rec_bare.record("bundle", it0=0, k=k, epoch=0)
    t_rec_bare = (time.perf_counter() - t0) / N_EV
    t0 = time.perf_counter()
    for _ in range(N_EV):
        rec_on.record("bundle", it0=0, k=k, epoch=0)
    t_rec_watched = (time.perf_counter() - t0) / N_EV
    t_event = max(t_rec_watched - t_rec_bare, 0.0)
    N_TICK = 2000
    t0 = time.perf_counter()
    for _ in range(N_TICK):
        evaluator.tick()
    t_tick = (time.perf_counter() - t0) / N_TICK
    evaluator.unwatch()
    overhead_pct = round(
        (events_per_sec * t_event + t_tick / tick_interval_s) * 100.0, 3)

    # -- detection-latency leg ---------------------------------------------
    from deeplearning4j_tpu.chaos.plan import ChaosPlan
    from deeplearning4j_tpu.train.faults import FaultPolicy, save_checkpoint

    def detect(fault_name, alert_name, plan, workload):
        ev = AlertEvaluator(slo.default_rules(),
                            min_tick_interval=0.0, record_events=False)
        ev.watch_flight(None)
        try:
            ev.tick()  # baseline sample before the fault
            with plan.armed():
                try:
                    workload()
                except Exception:  # noqa: BLE001 — the injected fault
                    # surfacing typed IS the workload here; detection is
                    # what this leg measures
                    pass
            ticks = 0
            for _ in range(4):
                ticks += 1
                ev.tick()
                if alert_name in ev.fired_names():
                    break
            fired = alert_name in ev.fired_names()
            return {"fault": fault_name, "alert": alert_name,
                    "fired": fired,
                    "ticks_to_fire": ticks if fired else None}
        finally:
            ev.unwatch()

    def nan_fit():
        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(Adam(1e-2))
                .fault_policy(FaultPolicy(skip_nonfinite=True,
                                          max_consecutive_bad_steps=100))
                .list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=d_out, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        MultiLayerNetwork(conf).init().fit(
            ExistingDataSetIterator(batches[:4]), epochs=1)

    import shutil
    import tempfile as _tempfile

    ck_dir = _tempfile.mkdtemp(prefix="bench_alerts_ck_")
    net_ck, _it_ck, _rec_ck = build()

    detections = [
        detect("nan_gradient_storm", "nan_step_storm",
               ChaosPlan([{"seam": "grad_nan", "at_iterations": [1]}],
                         name="bench_nan"), nan_fit),
        detect("checkpoint_fsync_enospc", "storage_errors",
               ChaosPlan([{"seam": "fs.fsync", "mode": "enospc",
                           "match": {"surface": "checkpoint"}}],
                         name="bench_enospc"),
               lambda: save_checkpoint(net_ck, ck_dir)),
    ]
    shutil.rmtree(ck_dir, ignore_errors=True)
    worst_ticks = max((d["ticks_to_fire"] or 99) for d in detections)
    gates = {
        "evaluator_overhead_le_1pct": overhead_pct <= 1.0,
        "detection_within_2_ticks":
            all(d["fired"] for d in detections) and worst_ticks <= 2,
    }
    result = {
        "metric": "alerts_evaluator_overhead_pct",
        "value": overhead_pct,
        "unit": "% steps/sec lost with the alert engine watching "
                "(direct decomposition: per-event observer cost + "
                "per-tick cost, x measured rates at K=16)",
        "vs_baseline": round(ab_ratio, 4),
        "gates": gates,
        "gates_ok": all(gates.values()),
        "extra": {
            "steps_per_sec": {"watched": round(on_sps, 1),
                              "bare": round(off_sps, 1)},
            "ab_overhead_pct_cross_check": ab_overhead_pct,
            "ab_per_round_ratios": [round(r, 4) for r in ratios],
            "observer_cost_us_per_event": round(t_event * 1e6, 3),
            "tick_cost_us": round(t_tick * 1e6, 2),
            "flight_events_per_sec_at_k16": round(events_per_sec, 1),
            "evaluator_ticks_during_ab": ticks_run,
            "n_rules": len(slo.default_rules()),
            "detection": detections,
            "worst_detection_ticks": worst_ticks,
            "config": (f"MLP {d_in}->{d_hidden}->{d_out}, batch {batch}, "
                       f"{n_batches} batches x {epochs} epochs, K={k}, "
                       f"sidecar tick every {tick_interval_s}s during "
                       "the watched arm only; private flight ring per "
                       "arm, evaluator observes only the watched one"),
            "platform": jax.devices()[0].platform,
            "note": ("gate 1: the watching engine costs <= 1% steps/sec "
                     "at K=16 — gated on the direct cost decomposition; "
                     "the wall-clock A/B rides along as a cross-check "
                     "but its per-round noise on this 2-core box is "
                     "+-3-4%, unusable for a 1% verdict. gate 2: fault "
                     "-> alert FIRING within 2 evaluator ticks (the "
                     "chaos expected_alerts contract)"),
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_alerts.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return result


def _bench_registry(n_tenants: int = 6, reqs_per_tenant: int = 24,
                    canary_window_s: float = 1.5):
    """Continuous-deployment bench (ISSUE 11): a multiplexed storm
    across two registry models through the HTTP router — gate 1: ZERO
    steady-state recompiles (trace-counter-asserted across ALL live
    engines) — then a deliberately regressed publish mid-traffic —
    gate 2: the publish→regression_trip→rollback wall time is at most
    2× the canary window. Writes BENCH_registry.json and returns it."""
    import http.client
    import tempfile
    import threading

    import jax

    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        InferenceServer,
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    d_in, d_out = 64, 10

    def fresh_net(seed, hidden):
        conf = (NeuralNetConfiguration.builder().seed(seed).list()
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(OutputLayer(n_out=d_out, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        return MultiLayerNetwork(conf).init()

    tmp = tempfile.mkdtemp(prefix="bench_registry_")
    reg = ModelRegistry(os.path.join(tmp, "registry"))
    models = {"alpha": fresh_net(1, 32), "beta": fresh_net(2, 64)}
    for name, net in models.items():
        path = save_checkpoint(net, os.path.join(tmp, f"ck_{name}"))
        reg.publish(name, path, score=1.0)

    probe_x = np.zeros((8, d_in), np.float32)
    bad_versions = set()

    def score_probe(engine):
        # the held-out validation re-run against the live engine: the
        # scrambled snapshot "scores" terribly, everything else is fine
        src = str(engine.describe()["source"])
        return 9.0 if any(f"v{v:04d}" in src for v in bad_versions) else 1.0

    router = ModelRouter(reg, batch_limit=16, max_wait_ms=2.0,
                         queue_limit=4096, tenant_quota=None,
                         canary_fraction=0.25,
                         canary_window_s=canary_window_s,
                         score_probe=score_probe,
                         score_trip_tolerance=0.1, refresh_s=0.05)
    for name in models:
        router.managed(name)  # build + warm both engines up front
    server = InferenceServer(router=router, port=0).start()
    port = server.port

    def retraces():
        fam = router.metrics.registry.family_values("jit_retraces_total")
        return sum(fam.values())

    names = sorted(models)
    lats, lock = [], threading.Lock()

    def client(tid, stop_at=None):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        crng = np.random.default_rng(100 + tid)
        mine = []
        for i in range(reqs_per_tenant):
            if stop_at is not None and time.perf_counter() > stop_at:
                break
            name = names[(tid + i) % len(names)]
            n = int(crng.integers(1, 9))
            x = crng.standard_normal((n, d_in)).astype(np.float32)
            t0 = time.perf_counter()
            conn.request("POST", f"/models/{name}/predict",
                         json.dumps({"inputs": x.tolist()}),
                         headers={"X-Tenant": f"tenant-{tid}"})
            resp = conn.getresponse()
            body = resp.read()
            if resp.status == 200:
                mine.append(time.perf_counter() - t0)
        conn.close()
        with lock:
            lats.extend(mine)

    # phase 1: multiplexed steady-state storm, compile-count gated
    compiles_before = retraces()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_tenants)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    storm_s = time.perf_counter() - t0
    storm_retraces = retraces() - compiles_before
    lats.sort()
    p50 = lats[len(lats) // 2] * 1e3 if lats else None
    p99 = lats[min(int(0.99 * len(lats)), len(lats) - 1)] * 1e3 \
        if lats else None

    # phase 2: regressed publish mid-traffic → measure rollback latency
    # (same arch, different weights; the score probe is what flags it)
    bad = fresh_net(99, 32)
    bad_path = save_checkpoint(bad, os.path.join(tmp, "ck_alpha"))
    stop_at = time.perf_counter() + 4 * canary_window_s + 10
    bg = [threading.Thread(target=client, args=(10 + t, stop_at))
          for t in range(2)]
    for t in bg:
        t.start()
    t_pub = time.perf_counter()
    rec = reg.publish("alpha", bad_path, score=0.99)  # passes validation
    bad_versions.add(rec["version"])
    rollback_s = None
    deadline = time.perf_counter() + 4 * canary_window_s + 10
    while time.perf_counter() < deadline:
        status = reg.get("alpha")["versions"][str(rec["version"])]["status"]
        if status == "rolled_back":
            rollback_s = time.perf_counter() - t_pub
            break
        time.sleep(0.02)
    for t in bg:
        t.join()
    active_after = reg.get("alpha")["active_version"]
    server.shutdown()

    gate_retraces = storm_retraces == 0
    gate_rollback = (rollback_s is not None
                     and rollback_s <= 2.0 * canary_window_s)
    out = {
        "metric": "registry_bad_publish_rollback_seconds",
        "value": None if rollback_s is None else round(rollback_s, 3),
        "unit": "seconds",
        "vs_baseline": None,
        "extra": {
            "platform": jax.default_backend(),
            "models": len(models),
            "storm": {
                "tenants": n_tenants,
                "requests": len(lats),
                "seconds": round(storm_s, 2),
                "req_per_sec": round(len(lats) / storm_s, 1),
                "p50_ms": None if p50 is None else round(p50, 2),
                "p99_ms": None if p99 is None else round(p99, 2),
                "retraces": int(storm_retraces),
            },
            "canary_window_s": canary_window_s,
            "rollback": {
                "latency_s": None if rollback_s is None
                else round(rollback_s, 3),
                "active_version_after": active_after,
                "gate": "rollback_latency <= 2x canary_window",
            },
            "gates": {"zero_storm_retraces": gate_retraces,
                      "rollback_within_2x_window": gate_rollback},
            "ok": bool(gate_retraces and gate_rollback),
        },
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_registry.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def _bench_cluster(dispatch_s: float = 0.06, batch_limit: int = 3,
                   n_conns: int = 9, duration_s: float = 6.0,
                   canary_window_s: float = 2.0):
    """Multi-replica tier bench (ISSUE 17): capacity scaling and
    cross-replica rollback latency. The accelerator step is modeled by
    a fixed per-dispatch delay (chaos seam, active-role dispatches) so
    throughput is dispatch-serialized per replica — the regime where a
    tier scales by adding replicas, not cores. Gate 1: N=3 replicas
    behind a session-sticky front sustain >= 2.2x the single-replica
    storm. Gate 2: a regressed publish's cluster-wide rollback (every
    replica's canary torn down, registry status rolled_back) lands
    within the canary window + 2x the tightened refresh interval.
    Writes BENCH_cluster.json and returns it."""
    import http.client
    import tempfile
    import threading

    import jax

    from deeplearning4j_tpu.chaos import ChaosPlan
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        ClusterCoordinator,
        InferenceServer,
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.train.faults import save_checkpoint

    d_in = 16

    def fresh_net(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        return MultiLayerNetwork(conf).init()

    tmp = tempfile.mkdtemp(prefix="bench_cluster_")
    ck1 = save_checkpoint(fresh_net(1), os.path.join(tmp, "ck1"))
    ck2 = save_checkpoint(fresh_net(2), os.path.join(tmp, "ck2"))
    payload = json.dumps(
        {"inputs": np.zeros((1, d_in), np.float32).tolist()})

    def storm(ports, seconds):
        """Closed-loop storm: each connection is pinned to its home
        replica (the session-sticky front), counts 200s."""
        counts = [0] * len(ports)
        stop = time.perf_counter() + seconds
        barrier = threading.Barrier(len(ports))

        def client(i):
            conn = http.client.HTTPConnection("127.0.0.1", ports[i],
                                              timeout=120)
            barrier.wait()
            while time.perf_counter() < stop:
                conn.request("POST", "/models/m/predict", payload,
                             headers={"X-Tenant": f"t{i}"})
                resp = conn.getresponse()
                resp.read()
                if resp.status == 200:
                    counts[i] += 1
            conn.close()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(ports))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts) / (time.perf_counter() - t0)

    def make_tier(regdir, cluster_ids):
        """One router+server per replica id (or one uncoordinated
        replica when cluster_ids is empty), all sharing regdir."""
        tier = []
        for rid in (cluster_ids or [None]):
            reg = ModelRegistry(regdir)
            coord = None
            if rid is not None:
                coord = ClusterCoordinator(regdir, rid, heartbeat_s=0.2)
            router = ModelRouter(reg, batch_limit=batch_limit,
                                 max_wait_ms=20.0, queue_limit=4096,
                                 canary_fraction=0.5,
                                 canary_window_s=canary_window_s,
                                 refresh_s=0.1, cluster=coord)
            router.managed("m")
            if coord is not None:
                coord.start(inflight_fn=router.tenant_inflight)
            tier.append({"coord": coord, "router": router,
                         "server": InferenceServer(router=router,
                                                   port=0).start()})
        return tier

    # the "accelerator step": every active-role dispatch takes
    # dispatch_s, serialized per replica batcher — canary dispatches
    # are left to the rollback plan below
    delay_plan = ChaosPlan([{"seam": "registry.version_dispatch",
                             "mode": "delay", "delay_s": dispatch_s,
                             "match": {"role": "active"}, "times": None}],
                           name="bench_cluster_dispatch")

    with delay_plan.armed():
        # phase 1: single replica, all connections on it
        reg_a = ModelRegistry(os.path.join(tmp, "single"))
        reg_a.publish("m", ck1, score=0.5)
        single = make_tier(os.path.join(tmp, "single"), [])
        rps_1 = storm([single[0]["server"].port] * n_conns, duration_s)
        single[0]["server"].shutdown()

        # phase 2: the 3-replica tier on a shared journal
        regdir = os.path.join(tmp, "tier")
        pub = ModelRegistry(regdir)
        pub.publish("m", ck1, score=0.5)
        tier = make_tier(regdir, ["r1", "r2", "r3"])
        ports = [t["server"].port for t in tier]
        rps_3 = storm([ports[i % 3] for i in range(n_conns)], duration_s)
        ratio = rps_3 / rps_1 if rps_1 else None

        # phase 3: regressed publish -> cluster-wide rollback latency.
        # The canary's dispatches fail typed; the lease holder trips
        # and every replica tears its window down from the WAL.
        rollback_plan = ChaosPlan(
            [{"seam": "registry.version_dispatch", "mode": "error",
              "match": {"role": "canary"}, "times": None}],
            name="bench_cluster_rollback")
        refresh_s = max(t["coord"].canary_refresh_s for t in tier)
        with rollback_plan.armed():
            t_pub = time.perf_counter()
            rec = pub.publish("m", ck2, score=0.45)
            rollback_s = None
            conn = [http.client.HTTPConnection("127.0.0.1", p, timeout=120)
                    for p in ports]
            deadline = time.perf_counter() + 4 * canary_window_s + 20
            i = 0
            while time.perf_counter() < deadline:
                c = conn[i % 3]
                i += 1
                try:
                    c.request("POST", "/models/m/predict", payload,
                              headers={"X-Tenant": "probe"})
                    c.getresponse().read()
                except Exception:  # noqa: BLE001 — canary-slice 500s
                    conn[(i - 1) % 3] = http.client.HTTPConnection(
                        "127.0.0.1", ports[(i - 1) % 3], timeout=120)
                pub.refresh(force=True)
                status = pub.get("m")["versions"].get(
                    str(rec["version"]), {}).get("status")
                torn_down = all(
                    t["router"].describe()["live"]["m"]["canary_version"]
                    is None for t in tier)
                if status == "rolled_back" and torn_down:
                    rollback_s = time.perf_counter() - t_pub
                    break
                time.sleep(0.02)
        active_after = pub.get("m")["active_version"]
        for t in tier:
            t["server"].shutdown()
            if t["coord"] is not None:
                t["coord"].shutdown()

    rollback_bound = canary_window_s + 2.0 * refresh_s
    gate_scaling = ratio is not None and ratio >= 2.2
    gate_rollback = rollback_s is not None and rollback_s <= rollback_bound
    out = {
        "metric": "cluster_n3_throughput_ratio",
        "value": None if ratio is None else round(ratio, 2),
        "unit": "x_single_replica",
        "vs_baseline": None,
        "extra": {
            "platform": jax.default_backend(),
            "dispatch_s": dispatch_s,
            "batch_limit": batch_limit,
            "connections": n_conns,
            "single_replica_rps": round(rps_1, 1),
            "three_replica_rps": round(rps_3, 1),
            "canary_window_s": canary_window_s,
            "cluster_refresh_s": refresh_s,
            "rollback": {
                "latency_s": None if rollback_s is None
                else round(rollback_s, 3),
                "bound_s": round(rollback_bound, 3),
                "active_version_after": active_after,
                "gate": "cluster-wide rollback <= canary_window + "
                        "2x refresh interval",
            },
            "gates": {"n3_throughput_ge_2.2x": bool(gate_scaling),
                      "rollback_within_bound": bool(gate_rollback)},
            "ok": bool(gate_scaling and gate_rollback),
        },
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_cluster.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def _bench_loadgen(compression: float = 20.0, skip_s: float = 8.0):
    """Load generation + adaptive capacity bench (ISSUE 18). One
    compiled diurnal+flash stream replayed twice against identical
    serving stacks: a static leg (fixed 25ms coalescing deadline) and a
    controllers leg (ControllerHub + DeadlineTuner on a tight latency
    SLO). Gates: (1) steady-state p99 with controllers ON beats the
    static baseline; (2) identical seeds compile identical streams
    (fingerprint-asserted, plus serde roundtrip and a differing-seed
    check); (3) the bucket auto-tuner's set switch is pre-compiled —
    every compile during the post-switch steady replay is attributable
    to an explicit retune warmup, never a steady-state dispatch retrace
    (trace-counter-asserted); (4) a verdict-carrying controller_retune
    flight event was observed. Writes BENCH_loadgen.json."""
    import jax

    from deeplearning4j_tpu.loadgen import (
        ControllerHub,
        DeadlineTuner,
        LoadPlan,
        LoadRunner,
        batcher_target,
        diurnal_flash_plan,
    )
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs import flight as _flight
    from deeplearning4j_tpu.obs.alerts import AlertEvaluator
    from deeplearning4j_tpu.obs.slo import default_rules
    from deeplearning4j_tpu.serving import BucketPolicy, InferenceEngine
    from deeplearning4j_tpu.serving.batcher import (
        DynamicBatcher,
        make_dispatcher,
    )
    from deeplearning4j_tpu.serving.metrics import ServingMetrics

    d_in = 16

    def fresh_stack(max_wait_ms: float, buckets):
        conf = (NeuralNetConfiguration.builder().seed(3).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        met = ServingMetrics()
        engine = InferenceEngine(
            MultiLayerNetwork(conf).init(),
            buckets=BucketPolicy(batch_buckets=list(buckets),
                                 max_batch=32), metrics=met)
        engine.warmup()
        batcher = DynamicBatcher(
            make_dispatcher(engine.infer, metrics=met),
            batch_limit=32, max_wait_ms=max_wait_ms,
            queue_limit=1024, metrics=met)
        return engine, batcher, met

    rec = _flight.default_flight_recorder()

    # -- gate 2: compile determinism + serde roundtrip ----------------------
    plan = diurnal_flash_plan()
    s1 = plan.compile()
    fp = s1.fingerprint()
    gate_fp_same = plan.compile().fingerprint() == fp
    gate_fp_diff = plan.compile(seed=plan.seed + 1).fingerprint() != fp
    gate_serde = (LoadPlan.from_json(plan.to_json())
                  .compile().fingerprint() == fp)

    # -- leg A: static baseline ---------------------------------------------
    engine_a, batcher_a, _ = fresh_stack(25.0, [32])
    try:
        rep_off = LoadRunner(s1, batcher_target(batcher_a, (d_in,)),
                             compression=compression).run()
    finally:
        batcher_a.shutdown(drain=False)

    # -- leg B: the observe→act loop on the SAME stream ---------------------
    engine_b, batcher_b, met_b = fresh_stack(25.0, [32])
    evaluator = AlertEvaluator(default_rules(latency_slo_ms=8.0),
                               registry=met_b.registry,
                               min_tick_interval=0.0)
    tuner = DeadlineTuner(batcher_b, engine=engine_b, shrink=0.3,
                          cooldown_s=0.5, min_rows=10 ** 9)
    hub = ControllerHub(evaluator, [tuner])
    seq_b = rec.recorded_total
    try:
        rep_on = LoadRunner(s1, batcher_target(batcher_b, (d_in,)),
                            compression=compression,
                            on_tick=hub.tick).run()
    finally:
        batcher_b.shutdown(drain=False)
    retunes = [e for e in rec.events()
               if e["seq"] >= seq_b and e["kind"] == "controller_retune"]
    p99_off = rep_off.p_steady(0.99, skip_s) * 1e3
    p99_on = rep_on.p_steady(0.99, skip_s) * 1e3
    gate_p99 = (rep_on.ok() > 0 and rep_off.ok() > 0
                and p99_on < p99_off)
    gate_retune = any(e.get("verdict") for e in retunes)

    # -- gate 3: bucket learning lands with zero steady-state retraces ------
    # light steady traffic on a deliberately coarse [32] bucket set:
    # the tuner learns the observed dispatch mix, pre-compiles the
    # proposal, and switches; the second replay (auto-tuner still
    # armed) must attribute every compile to an explicit retune warmup
    steady = LoadPlan(
        [{"process": "poisson", "rps": 30.0}],
        [{"name": "steady", "kind": "predict",
          "rows": {"dist": "lognormal", "median": 3, "sigma": 0.8,
                   "max": 8}}],
        name="steady-learn", seed=5, duration_s=8.0, tick_s=0.5)
    sc = steady.compile()
    engine_c, batcher_c, met_c = fresh_stack(2.0, [32])
    ev_c = AlertEvaluator(default_rules(latency_slo_ms=10000.0),
                          registry=met_c.registry, min_tick_interval=0.0)
    tuner_c = DeadlineTuner(batcher_c, engine=engine_c, min_rows=48,
                            cooldown_s=0.5)
    hub_c = ControllerHub(ev_c, [tuner_c])
    try:
        LoadRunner(sc, batcher_target(batcher_c, (d_in,)),
                   compression=3.0, on_tick=hub_c.tick).run()
        buckets_learned = list(engine_c.buckets.batch_buckets)
        seq_c = rec.recorded_total
        c0 = engine_c._compile_count
        LoadRunner(sc, batcher_target(batcher_c, (d_in,)),
                   compression=3.0, on_tick=hub_c.tick).run()
        c1 = engine_c._compile_count
    finally:
        batcher_c.shutdown(drain=False)
    warm_compiles = sum(
        e.get("compiles", 0) for e in rec.events()
        if e["seq"] >= seq_c and e["kind"] == "controller_retune"
        and e.get("action") == "bucket_retune")
    gate_learned = buckets_learned != [32]
    gate_zero_retrace = (c1 - c0) == warm_compiles

    ok = bool(gate_p99 and gate_fp_same and gate_fp_diff and gate_serde
              and gate_retune and gate_learned and gate_zero_retrace)
    out = {
        "metric": "loadgen_adaptive_p99_speedup",
        "value": (round(p99_off / p99_on, 2) if p99_on > 0 else None),
        "unit": "x_static_baseline",
        "vs_baseline": None,
        "extra": {
            "platform": jax.default_backend(),
            "plan": s1.plan.name,
            "seed": s1.plan.seed,
            "n_requests": len(s1),
            "fingerprint": fp[:16],
            "compression": compression,
            "steady_skip_s": skip_s,
            "static": {"p99_ms": round(p99_off, 3),
                       "ok": rep_off.ok(),
                       "outcomes": dict(rep_off.outcomes)},
            "controllers": {"p99_ms": round(p99_on, 3),
                            "ok": rep_on.ok(),
                            "outcomes": dict(rep_on.outcomes),
                            "retunes": len(retunes)},
            "bucket_learning": {
                "initial": [32],
                "learned": buckets_learned,
                "second_replay_compiles": c1 - c0,
                "attributed_warm_compiles": warm_compiles,
            },
            "gates": {
                "p99_on_lt_off": bool(gate_p99),
                "fingerprint_same_seed": bool(gate_fp_same),
                "fingerprint_diff_seed": bool(gate_fp_diff),
                "serde_roundtrip": bool(gate_serde),
                "controller_retune_with_verdict": bool(gate_retune),
                "bucket_set_learned": bool(gate_learned),
                "zero_steady_state_retraces": bool(gate_zero_retrace),
            },
            "ok": ok,
        },
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_loadgen.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def _bench_data(k=16, n_batches=96, batch=32, d_in=256, d_hidden=64,
                d_out=10, epochs=4, workers=4):
    """Sharded input pipeline bench (ISSUE 19). The K=16 pipelined fit
    from BENCH_pipeline, at 4x its per-batch byte volume (d_in 256 vs
    64: 32 KiB of features per batch), fed three ways:

    - **reference**: in-memory ExistingDataSetIterator — the
      compute-bound ceiling (no input cost at all);
    - **legacy**: a single-producer text-decode iterator (one async
      prefetch thread parsing CSV per batch) — the pre-ISSUE-19 shape
      of "real" input. Gate: demonstrably INPUT-bound (steps/sec well
      under the ceiling AND the ``data_queue_starved`` alert fires,
      naming the starved pool);
    - **loader**: the same batches packed into record shards and read
      back through the multi-worker ShardedLoader. Gate: steps/sec
      within 10% of the DOCUMENTED 1418 steps/sec K=16 CPU baseline
      (BENCH_pipeline.json, measured at 1x volume with free in-memory
      input) — shard decode at 4x the bytes stays off the critical
      path. The in-process in-memory ceiling is also reported; on this
      single-core container any input work serializes with compute, so
      the ceiling ratio is informational, not a gate. A separate leg
      fits under a compressed diurnal+flash loadgen replay and gates
      ``data_queue_starved`` / ``data_loader_stalled`` /
      ``shard_skips`` all staying SILENT.

    Plus the determinism gate: a mid-epoch data_state snapshot restored
    into a fresh loader replays the remaining stream so its rolling
    fingerprint lands bit-identical on the uninterrupted oracle's.
    Writes BENCH_data.json."""
    import shutil
    import tempfile
    import threading

    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import (
        DataSetIterator,
        ExistingDataSetIterator,
    )
    from deeplearning4j_tpu.data.loader import ShardedLoader
    from deeplearning4j_tpu.data.shards import pack_iterator
    from deeplearning4j_tpu.loadgen import (
        LoadRunner,
        batcher_target,
        diurnal_flash_plan,
    )
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs.alerts import AlertEvaluator
    from deeplearning4j_tpu.obs.metrics import default_registry
    from deeplearning4j_tpu.obs.slo import default_rules
    from deeplearning4j_tpu.serving import BucketPolicy, InferenceEngine
    from deeplearning4j_tpu.serving.batcher import (
        DynamicBatcher,
        make_dispatcher,
    )
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    from deeplearning4j_tpu.updaters import Adam

    rng = np.random.default_rng(0)
    batches = [
        DataSet(rng.standard_normal((batch, d_in)).astype(np.float32),
                np.eye(d_out, dtype=np.float32)[
                    rng.integers(0, d_out, batch)])
        for _ in range(n_batches)
    ]
    bytes_per_batch = batch * d_in * 4

    class _CsvIterator(DataSetIterator):
        """The legacy input shape: one producer thread decoding text
        per batch (async_supported stays True, so fit wraps it in the
        single-producer AsyncDataSetIterator — exactly the pre-shard
        pipeline)."""

        def __init__(self):
            self.pre_processor = None
            self._rows = [
                ("\n".join(",".join(f"{v:.8e}" for v in row)
                           for row in np.asarray(b.features)),
                 np.asarray(b.labels))
                for b in batches
            ]
            self._i = 0

        def has_next(self):
            return self._i < len(self._rows)

        def next(self):
            text, labels = self._rows[self._i]
            self._i += 1
            feats = np.array(
                [[float(t) for t in line.split(",")]
                 for line in text.split("\n")], dtype=np.float32)
            return DataSet(feats, labels)

        def reset(self):
            self._i = 0

    def fresh_net():
        conf = (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(1e-3)).steps_per_call(k).list()
                .layer(DenseLayer(n_out=d_hidden, activation="relu"))
                .layer(OutputLayer(n_out=d_out, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(d_in)).build())
        return MultiLayerNetwork(conf).init()

    class _Ticker:
        """Fresh default-rules evaluator over the process registry,
        ticking on a 50ms cadence between start() and stop() — armed
        only around the TIMED window so warmup compiles don't dilute
        the rate-rule denominators."""

        def __init__(self):
            self.ev = AlertEvaluator(default_rules(),
                                     registry=default_registry(),
                                     min_tick_interval=0.0)
            self._stop = threading.Event()
            self._t = None

        def start(self):
            self.ev.tick()  # baseline sample at the window's edge

            def loop():
                while not self._stop.is_set():
                    self.ev.tick()
                    self._stop.wait(0.05)

            self._t = threading.Thread(target=loop, daemon=True)
            self._t.start()

        def stop(self):
            self._stop.set()
            self._t.join()
            self.ev.tick()
            return self.ev.fired_names()

    def timed_fit(it, trials=2, ticker=None):
        """Best steady-state steps/sec over ``trials`` timed fits (one
        warmup fit first compiles both step shapes); the CPU runners
        are noisy enough that single-shot legs can't gate a 10%
        margin. ``ticker`` (if given) is armed around the timed fits
        only."""
        net = fresh_net()
        net.fit(it, epochs=1)  # warmup epoch: compile both step shapes
        float(net.score_)
        if ticker is not None:
            ticker.start()
        best = 0.0
        for _ in range(trials):
            t0 = time.perf_counter()
            net.fit(it, epochs=epochs)
            float(net.score_)  # drain the async dispatch queue
            best = max(best, epochs * n_batches / (time.perf_counter() - t0))
        return best

    # -- leg A: compute-bound ceiling (no input cost) -----------------------
    ref_sps = timed_fit(ExistingDataSetIterator(batches))

    # -- leg B: legacy single-producer decode at the same byte volume -------
    tick_b = _Ticker()
    legacy_sps = timed_fit(_CsvIterator(), ticker=tick_b)
    legacy_fired = tick_b.stop()
    gate_legacy_bound = (legacy_sps <= 0.8 * ref_sps
                         and "data_queue_starved" in legacy_fired)

    shard_dir = tempfile.mkdtemp(prefix="bench_data_shards_")
    try:
        pack_iterator(ExistingDataSetIterator(batches), shard_dir,
                      batches_per_shard=8)

        # -- leg C: multi-worker loader throughput (same conditions as
        # the reference leg — the 10% gate compares equal CPU load) ----
        ld = ShardedLoader(shard_dir, num_workers=workers, seed=7,
                           max_pending=8)
        tick_c = _Ticker()
        try:
            loader_sps = timed_fit(ld, ticker=tick_c)
        finally:
            loader_fired = tick_c.stop()
            ld.shutdown()
        documented_baseline = 1418.2  # BENCH_pipeline.json k16, 1x volume
        gate_loader_fast = loader_sps >= 0.9 * documented_baseline

        # -- leg D: loader fit under a concurrent diurnal+flash loadgen
        # replay — the data alerts must stay silent ---------------------
        conf = (NeuralNetConfiguration.builder().seed(3).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(16)).build())
        met = ServingMetrics()
        engine = InferenceEngine(
            MultiLayerNetwork(conf).init(),
            buckets=BucketPolicy(batch_buckets=[32], max_batch=32),
            metrics=met)
        engine.warmup()
        batcher = DynamicBatcher(make_dispatcher(engine.infer, metrics=met),
                                 batch_limit=32, max_wait_ms=5.0,
                                 queue_limit=1024, metrics=met)
        stream = diurnal_flash_plan(duration_s=60.0).compile()
        lg_thread = threading.Thread(
            target=lambda: LoadRunner(stream, batcher_target(batcher, (16,)),
                                      compression=8.0).run(),
            daemon=True)
        ld2 = ShardedLoader(shard_dir, num_workers=workers, seed=7,
                            max_pending=8)
        tick_d = _Ticker()
        net_d = fresh_net()
        net_d.fit(ld2, epochs=1)  # warmup
        float(net_d.score_)
        lg_thread.start()
        tick_d.start()
        try:
            while lg_thread.is_alive():
                net_d.fit(ld2, epochs=1)
                float(net_d.score_)
            lg_thread.join()
        finally:
            concurrent_fired = tick_d.stop()
            ld2.shutdown()
            batcher.shutdown(drain=False)
        noisy = {"data_queue_starved", "data_loader_stalled",
                 "shard_skips"} & (set(loader_fired)
                                   | set(concurrent_fired))
        gate_loader_quiet = not noisy

        # -- determinism gate: mid-stream snapshot → restored replay -------
        def drain_fp(ld):
            while ld.has_next():
                ld.next()
            return ld.data_state()["fingerprint"]

        oracle = ShardedLoader(shard_dir, num_workers=2, seed=7)
        oracle_fp = drain_fp(oracle)
        oracle.shutdown()
        first = ShardedLoader(shard_dir, num_workers=2, seed=7)
        for _ in range(n_batches // 3):
            first.next()
        snap = first.data_state()
        first.shutdown()
        resumed = ShardedLoader(shard_dir, num_workers=workers, seed=7)
        resumed.restore_state(snap)
        gate_resume = drain_fp(resumed) == oracle_fp
        resumed.shutdown()
    finally:
        shutil.rmtree(shard_dir, ignore_errors=True)

    ok = bool(gate_legacy_bound and gate_loader_fast
              and gate_loader_quiet and gate_resume)
    out = {
        "metric": f"sharded_loader_steps_per_sec_k{k}",
        "value": round(loader_sps, 1),
        "unit": "optimizer steps/sec",
        "vs_baseline": round(loader_sps / documented_baseline, 3),
        "extra": {
            "documented_k16_baseline": documented_baseline,
            "vs_in_memory_reference": round(loader_sps / ref_sps, 3),
            "steps_per_sec": {
                "in_memory_reference": round(ref_sps, 1),
                "legacy_single_producer": round(legacy_sps, 1),
                "sharded_loader": round(loader_sps, 1),
            },
            "config": (f"MLP {d_in}->{d_hidden}->{d_out}, batch {batch}, "
                       f"{bytes_per_batch} feature bytes/batch (4x the "
                       f"BENCH_pipeline volume), {n_batches} batches x "
                       f"{epochs} epochs, K={k}, {workers} loader "
                       "workers; silence leg fits under a diurnal-flash "
                       "loadgen replay at 8x compression"),
            "platform": jax.devices()[0].platform,
            "alerts": {
                "legacy_leg_fired": list(legacy_fired),
                "loader_leg_fired": list(loader_fired),
                "concurrent_leg_fired": list(concurrent_fired),
            },
            "gates": {
                "legacy_input_bound_and_detected": bool(gate_legacy_bound),
                "loader_within_10pct_of_documented_baseline":
                    bool(gate_loader_fast),
                "loader_data_alerts_silent": bool(gate_loader_quiet),
                "resume_replay_bit_identical": bool(gate_resume),
            },
            "ok": ok,
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_data.json")
    with open(out_path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return out


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    compute_dtype = "bfloat16"
    if len(sys.argv) > 2 and sys.argv[2] == "fp32":
        compute_dtype = None

    devices = _init_devices()
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    img_per_sec = _bench_resnet(batch, compute_dtype)

    extra = {
        "batch": batch,
        "compute_dtype": compute_dtype or "float32",
        "n_devices": len(devices),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
    }
    # MFU vs chip peak. FLOPs/image from XLA's own cost analysis of the
    # full train step (fwd+bwd+updater, MAC=2 flops): 22.55 GFLOP/img at
    # batch 128 (measured 2026-07-29, batch-invariant per image).
    # Peak default 197 TFLOP/s (v5e bf16); override via BENCH_PEAK_TFLOPS.
    import os
    peak_tflops = float(os.environ.get("BENCH_PEAK_TFLOPS", "197"))
    flops_per_img = 22.55e9
    extra["mfu_pct"] = round(
        100.0 * img_per_sec * flops_per_img / (peak_tflops * 1e12), 2
    )
    extra["mfu_assumed_peak_tflops"] = peak_tflops
    # fused-Pallas ResNet path (VERDICT r4 item 1): measured alongside the
    # XLA-composition headline when the kernels pass the compile probe AND
    # the run is bf16 (the kernels only serve bf16 activations)
    if os.environ.get("BENCH_SKIP_FUSED", "0") != "1":
        try:
            from deeplearning4j_tpu.nn.ops.fused_conv import (
                fused_conv_available,
            )
            import jax.numpy as jnp  # noqa: F811

            if compute_dtype != "bfloat16":
                extra["resnet50_fused_kernels"] = "skipped (fp32 run)"
            elif fused_conv_available(jnp.bfloat16):
                extra["resnet50_fused_images_per_sec"] = round(
                    _bench_resnet(batch, compute_dtype, fused_pallas=True),
                    2)
                extra["resnet50_fused_kernels"] = "pallas"
            else:
                extra["resnet50_fused_kernels"] = (
                    "probe-rejected (XLA fallback identical to headline)")
        except Exception as e:
            extra["resnet50_fused_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_SKIP_LM", "0") != "1":
        try:
            lm_tps, lm_flops, lm_tokens_per_step, lm_flops_ca = (
                _bench_transformer())
            extra["transformer_lm_tokens_per_sec"] = round(lm_tps, 1)
            extra["transformer_lm_config"] = ("d768 L12 h12 T512 b16 bf16 "
                                              "(fp32 masters)")
            if lm_flops:
                # FLOP-based MFU, same MAC=2 convention as the ResNet
                # headline, from the ANALYTIC matmul count (cost_analysis
                # undercounts lax.scan bodies — see _bench_transformer)
                extra["transformer_lm_mfu_pct"] = round(
                    100.0 * lm_flops * lm_tps / lm_tokens_per_step
                    / (peak_tflops * 1e12), 2)
                extra["transformer_lm_flops_per_step"] = lm_flops
                if lm_flops_ca:
                    extra["transformer_lm_flops_cost_analysis"] = lm_flops_ca
            # record which attention impl the probe selected (in-tree
            # pallas / jax-bundled pallas / dense fallback)
            from deeplearning4j_tpu.nn.conf.layers.attention import (
                _FLASH_PROBE_CACHE,
            )

            impls = []
            for key, impl in _FLASH_PROBE_CACHE.items():
                if impl is None:
                    impls.append(f"{key}: dense-fallback")
                else:
                    mod = getattr(impl.args[0], "__module__", "?")
                    impls.append(
                        f"{key}: "
                        + ("in-tree" if "deeplearning4j_tpu" in mod
                           else "jax-bundled"))
            extra["attention_impl"] = impls or ["no flash-eligible shapes"]
        except Exception as e:
            extra["transformer_lm_error"] = f"{type(e).__name__}: {e}"
        # decode at full d768 shape is minutes-slow on a CPU validation
        # run — hardware (or explicit opt-in) only
        if (os.environ.get("BENCH_SKIP_DECODE", "0") != "1"
                and (extra.get("platform") != "cpu"
                     or os.environ.get("BENCH_FORCE_DECODE") == "1")):
            try:
                extra["transformer_lm_decode_tokens_per_sec"] = round(
                    _bench_lm_decode(), 1)
                extra["transformer_lm_decode_config"] = (
                    "d768 L12 h12 b8 prompt128 new128 bf16 KV-cache greedy")
            except Exception as e:
                extra["transformer_lm_decode_error"] = (
                    f"{type(e).__name__}: {str(e)[:200]}")
        if os.environ.get("BENCH_SKIP_LONG_CONTEXT", "0") != "1":
            try:
                extra["transformer_lm_long_ctx_tokens_per_sec"] = round(
                    _bench_transformer(batch=4, seq=2048)[0], 1)
                extra["transformer_lm_long_ctx_config"] = (
                    "d768 L12 h12 T2048 b4 bf16")
            except Exception as e:
                # dense fallback at T=2048 can exhaust HBM — record why
                extra["transformer_lm_long_ctx_error"] = (
                    f"{type(e).__name__}: {str(e)[:300]}")
    # DP weight-update A/B (ZeRO-1 sharded vs replicated): needs >=2
    # devices to be non-degenerate; skippable like the other extras
    if (os.environ.get("BENCH_SKIP_DP_SHARDED", "0") != "1"
            and len(devices) > 1):
        try:
            ab = _bench_dp_sharded_update(devices)
            extra["dp_sharded_update"] = ab
            extra["dp_sharded_update_config"] = (
                f"d768 L12 h12 T512 b{ab['zero1']['global_batch']} "
                f"bf16 dp{len(devices)}")
        except Exception as e:
            extra["dp_sharded_update_error"] = (
                f"{type(e).__name__}: {str(e)[:300]}")
    try:
        gbps, n = _bench_allreduce(devices)
        extra["allreduce_algbw_gbps"] = gbps
        if n == 1:
            # a 1-device psum measures HBM copy bandwidth, not ICI — flag
            # so the number is never misread as an interconnect result
            extra["allreduce_degenerate_single_device"] = True
    except Exception as e:
        extra["allreduce_error"] = f"{type(e).__name__}: {e}"

    result = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec / ROUND1_IMG_PER_SEC, 3),
        "extra": extra,
    }
    print(json.dumps(result))


#: subcommand → (bench function, virtual CPU devices it wants when
#: rehearsed, gate that decides the exit code). Each is meaningful on
#: any backend (parity / retrace / ledger gates) and writes its own
#: BENCH_<name>.json; off the TPU the metric name says so.
_SUBCOMMANDS = {
    "serving": (_bench_serving, 0, None),
    "generate": (_bench_generate, 0, None),
    "registry": (_bench_registry, 0, None),
    "cluster": (_bench_cluster, 0, lambda o: o["extra"]["ok"]),
    "loadgen": (_bench_loadgen, 0, lambda o: o["extra"]["ok"]),
    "kernels": (_bench_kernels, 8, None),
    "chaos": (_bench_chaos, 8, lambda o: o["gates_ok"]),
    "pipeline": (_bench_pipeline, 0, None),
    "data": (_bench_data, 0, lambda o: o["extra"]["ok"]),
    "alerts": (_bench_alerts, 0, lambda o: o["gates_ok"]),
    "obs": (_bench_obs, 0, None),
    "reshard": (_bench_reshard, 8, None),
    "sharded": (_bench_sharded, 8, lambda o: o["extra"]["gates_ok"]),
    "tune": (_bench_tune, 0, None),
}


def _run_subcommand(name: str) -> int:
    fn, cpu_devices, gate = _SUBCOMMANDS[name]
    _rehearse_on_cpu(cpu_devices)
    import jax

    out = fn()
    if jax.devices()[0].platform != "tpu":
        out["metric"] = "cpu_fallback_" + out["metric"]
    print(json.dumps({k: v for k, v in out.items() if k != "scorecard"}))
    return 0 if gate is None or gate(out) else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] in _SUBCOMMANDS:
        sys.exit(_run_subcommand(sys.argv[1]))
    main()

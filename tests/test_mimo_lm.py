"""DecoderLM (models/decoder_lm.py) against the plain reference of
MiMo-V2.5 (benchmark/reference/mimo_v2.py) at a tiny size: seeded random
weights, logits and not tokens. The published keys are translated by the
benchmark's family module, as the cell does; float32 parameters here, so
the tolerances are those of float32 summation order (1e-5 on logits of
size ~0.5), far under what a wrong mask, base, head map or weight gives.
"""

import copy
import gc
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from reference import mimo_v2 as ref  # noqa: E402

from deeplearning4j_tpu.models import decoder_lm  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.moe import moe_dropless_ffn  # noqa: E402

TOL = 1e-5
SEED = 5


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_families_decoder_lm", os.path.join(BENCH, "families", "decoder_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


fam = _family()


def tiny(**changes):
    """The rehearsal preset in float32, with ``changes`` to published keys."""
    with open(os.path.join(BENCH, "configs", "tiny-mimo.json")) as f:
        cfg = json.load(f)
    cfg["deployment"]["param_dtype"] = "float32"
    cfg.update(changes)
    return cfg


def build(cfg, seed=SEED):
    model = fam._model(cfg)
    model.params_ = fam.program_params(cfg, seed, model.cfg)
    return model


def ids_of(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg["vocab_size"], (n,))


@pytest.fixture(scope="module")
def base():
    cfg = tiny()
    return cfg, build(cfg)


# -- the whole model ----------------------------------------------------------
def test_forward_matches_reference(base):
    cfg, model = base
    ids = ids_of(cfg, 40)
    want = np.asarray(ref.logits(cfg, SEED, ids))
    got = model.logits(ids[None])[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("prompt_len", [3, 8, 29],
                         ids=["shorter-than-window", "the-window", "several-windows"])
def test_prefill_then_decode_matches_reference(base, prompt_len):
    """Bucketed prefill (bucket 32 > the ring of 8 for the long prompt),
    then 30 tokens through the cache: every ring wraps several times. The
    logits each token was chosen from against the reference's full
    forward over prompt + tokens."""
    cfg, model = base
    out, logits = model.generate_cached(ids_of(cfg, prompt_len), max_new=30,
                                        return_logits=True)
    want = np.asarray(ref.logits(cfg, SEED, out[:-1]))[prompt_len - 1:]
    np.testing.assert_allclose(logits, want, atol=TOL)


@pytest.mark.parametrize("changes", [
    {"add_swa_attention_sink_bias": False},
    {"add_full_attention_sink_bias": True},
    {"swa_rope_theta": 500.0, "rope_theta": 10000.0},
    {"partial_rotary_factor": 1.0},
    {"partial_rotary_factor": 0.17},
    {"num_key_value_heads": 4, "swa_num_key_value_heads": 8},
    {"num_key_value_heads": 8, "swa_num_key_value_heads": 2},
    {"attention_value_scale": 1.0},
    {"sliding_window": 3},
], ids=["no-sink", "sink-in-full-layers", "other-rotary-bases", "all-dims-rotated",
        "few-dims-rotated", "kv-4-and-8", "kv-8-and-2", "no-value-scale", "window-3"])
def test_each_published_key_is_read(changes):
    """One key changed, forward and cached decode against the reference
    with the same change; and the change does move the logits, so a key
    the program ignored would fail."""
    cfg = tiny(**changes)
    model = build(cfg)
    ids = ids_of(cfg, 21)
    want = np.asarray(ref.logits(cfg, SEED, ids))
    np.testing.assert_allclose(model.logits(ids[None])[0], want, atol=TOL)
    out, logits = model.generate_cached(ids[:10], max_new=12, return_logits=True)
    full = np.asarray(ref.logits(cfg, SEED, out[:-1]))[9:]
    np.testing.assert_allclose(logits, full, atol=TOL)
    unchanged = np.asarray(ref.logits(tiny(), SEED, ids))
    assert np.abs(unchanged - want).max() > 20 * TOL


def test_partial_rotation_leaves_the_rest_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 24))
    pos = jnp.arange(6)[None] + 3
    got = decoder_lm._rotate(x, pos, 8, 1e4)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    assert np.abs(np.asarray(got[..., :8] - x[..., :8])).max() > 0.1
    # the reference counts positions from 0: rotate 9 and keep the last 6
    long = jnp.concatenate([jnp.zeros((3, 2, 24)), x[0]])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref.rotate(long, 1e4, 8))[3:],
                               atol=1e-6)


def test_cache_is_sized_by_layer_kind(base):
    cfg, model = base
    plan = model.cfg.cache_plan(n_slots=3, max_length=64)
    assert [(p["kind"], p["layers"], p["ring"]) for p in plan] == [
        ("full", 1, False), ("window", 2, True), ("full", 1, False), ("window", 1, True)]
    full, ring = plan[0], plan[1]
    # (layers, slots, kv heads, head size, columns): 2 kv heads and the
    # slot's length in a full layer, 4 and the window in a window layer
    assert full["k"] == (1, 3, 2, 24, 64) and full["v"] == (1, 3, 2, 16, 64)
    assert ring["k"] == (2, 3, 4, 24, 8) and ring["v"] == (2, 3, 4, 16, 8)
    caches = decoder_lm.init_cache(model.cfg, 3, 64)
    assert [tuple(k.shape) for k, _ in caches] == [p["k"] for p in plan]


def test_ring_positions():
    cfg = decoder_lm.DecoderConfig(
        vocab_size=8, d_model=8, n_heads=2, head_dim=4, v_head_dim=4, rotary_dim=2,
        attn_kinds={"full": {"n_kv_heads": 1, "rope_theta": 1e4, "window": None},
                    "window": {"n_kv_heads": 1, "rope_theta": 1e4, "window": 4, "sink": True}},
        layers=[("full", "dense"), ("window", "dense")], dense_width=8, max_length=16)
    got = decoder_lm.cache_positions(cfg, jnp.asarray([0, 3, 4, 10]),
                                     decoder_lm.init_cache(cfg, 4, 16))
    np.testing.assert_array_equal(np.asarray(got["window"]), [
        [-4, -3, -2, -1], [0, 1, 2, -1], [0, 1, 2, 3], [8, 9, 6, 7]])
    np.testing.assert_array_equal(np.asarray(got["full"][1][:5]), [0, 1, 2, -1, -1])


# -- the expert layer ---------------------------------------------------------
def expert_layer(cfg, layer=1, seed=SEED):
    """(reference weights of one expert layer, the program's leaves)."""
    w = ref.make_layer(cfg, seed, layer)
    bp = {"Wr": w["router.w"], "br": w["router.bias"], "Eg": w["experts.gate"],
          "Eu": w["experts.up"], "Ed": w["experts.down"]}
    return w, bp


def all_experts(cfg):
    out = copy.deepcopy(cfg)
    out["n_routed_experts"] = cfg["published"]["n_routed_experts"]
    out["deployment"]["experts_offset"] = 0
    return out


def share(cfg, offset, count):
    out = copy.deepcopy(cfg)
    out["n_routed_experts"], out["deployment"]["experts_offset"] = count, offset
    return out


def tokens(cfg, n=24):
    return jax.random.normal(jax.random.PRNGKey(3), (n, cfg["hidden_size"]), jnp.float32)


def test_dropless_layer_matches_the_dense_sum_with_a_bias_that_moves_the_choice():
    cfg = all_experts(tiny())
    w, bp = expert_layer(cfg)
    x = tokens(cfg)
    k = cfg["num_experts_per_tok"]
    # a large bias on four experts: it changes who is chosen ...
    bias = w["router.bias"].at[jnp.asarray([1, 6, 11, 13])].add(5.0)
    plain = ref.route(cfg, w, x)
    w["router.bias"] = bp["br"] = bias
    biased = ref.route(cfg, w, x)
    assert (np.asarray((plain > 0) != (biased > 0)).sum(-1) > 0).all()
    assert (np.asarray(biased[:, [1, 6, 11, 13]]) > 0).all()
    # ... and not the weights: the chosen experts' own scores, renormalised
    s = np.asarray(jax.nn.sigmoid(x @ w["router.w"]))
    chosen = np.asarray(biased > 0)
    np.testing.assert_allclose(np.asarray(biased),
                               s * chosen / (s * chosen).sum(-1, keepdims=True), atol=1e-6)
    want = np.asarray(ref.experts(cfg, w, x, "float32"))
    y, pairs, hit = moe_dropless_ffn(x, x, bp, k, (0, 16))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-8)
    assert int(pairs) == x.shape[0] * k  # no token dropped
    assert int(hit) == int(chosen.any(0).sum())


def test_a_token_whose_experts_are_all_absent_gets_exactly_zero():
    cfg = all_experts(tiny())
    w, bp = expert_layer(cfg)
    x = tokens(cfg)
    # the router sends everything to experts 0-3; this holder has 8-11
    bp["br"] = w["router.bias"].at[:4].add(50.0)
    held = {k: (v[8:12] if k[0] == "E" else v) for k, v in bp.items()}
    y, pairs, hit = moe_dropless_ffn(x, x, held, 4, (8, 4))
    assert int(pairs) == 0 and int(hit) == 0
    np.testing.assert_array_equal(np.asarray(y), np.zeros_like(np.asarray(y)))


def test_idle_rows_stay_out_of_the_experts():
    cfg = all_experts(tiny())
    _w, bp = expert_layer(cfg)
    x = tokens(cfg)
    mask = jnp.arange(x.shape[0]) % 3 == 0
    y, pairs, _hit = moe_dropless_ffn(x, x, bp, 4, (0, 16), mask)
    whole, _, _ = moe_dropless_ffn(x, x, bp, 4, (0, 16))
    assert int(pairs) == int(mask.sum()) * 4
    np.testing.assert_array_equal(np.asarray(y)[~np.asarray(mask)], 0.0)
    np.testing.assert_allclose(np.asarray(y)[np.asarray(mask)],
                               np.asarray(whole)[np.asarray(mask)], rtol=1e-4, atol=1e-8)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: a layer of 16 experts held 4 at a time. What the
    four holders compute, each from the generator's weights for ITS
    experts, adds up to the uncut reference's output for the layer: the
    router, its choice and its weights are over all 16 in every share."""
    cfg = tiny()
    uncut = all_experts(cfg)
    x = tokens(cfg)
    w_all, _ = expert_layer(uncut)
    want = np.asarray(ref.experts(uncut, w_all, x, "float32"))
    total = np.zeros_like(want)
    total_ref = np.zeros_like(want)
    pairs = 0
    for offset in (0, 4, 8, 12):
        held = share(cfg, offset, 4)
        w, bp = expert_layer(held)
        np.testing.assert_array_equal(np.asarray(w["experts.gate"]),
                                      np.asarray(w_all["experts.gate"][offset:offset + 4]))
        y, n, _hit = moe_dropless_ffn(x, x, bp, cfg["num_experts_per_tok"], (offset, 4))
        total += np.asarray(y)
        total_ref += np.asarray(ref.experts(held, w, x, "float32"))
        pairs += int(n)
    assert np.abs(want).max() > 5e-4
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-8)
    np.testing.assert_allclose(total_ref, want, rtol=1e-4, atol=1e-8)
    assert pairs == x.shape[0] * cfg["num_experts_per_tok"]


def test_manual_expert_parallelism_calls_the_same_layer():
    """parallel/moe.py: inside a shard_map over an "expert" axis of 4,
    every shard computes the share of the 4 experts it holds."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deeplearning4j_tpu.parallel.moe import expert_parallel_dropless_ffn

    cfg = all_experts(tiny())
    w, bp = expert_layer(cfg)
    x = tokens(cfg)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    specs = {k: (P("expert") if k[0] == "E" else P()) for k in bp}
    run = jax.jit(jax.shard_map(
        lambda x, bp: expert_parallel_dropless_ffn(x, x, bp, 4, "expert"),
        mesh=mesh, in_specs=(P(), specs), out_specs=P(), check_vma=False))
    y, pairs, hit = run(x, bp)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.experts(cfg, w, x, "float32")),
                               rtol=1e-4, atol=1e-8)
    assert int(pairs) == x.shape[0] * 4 and 1 <= int(hit) <= 16


# -- the engine ---------------------------------------------------------------
@pytest.fixture(scope="module")
def engine(base):
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    _cfg, model = base
    gen = GenerationEngine(model, n_slots=3, max_length=96, prefill_buckets=[8, 16, 32])
    gen.warmup()
    yield gen
    gen.shutdown(drain=False)


def test_engine_serves_what_the_model_generates_alone(base, engine):
    cfg, model = base
    traced = dict(engine.trace_counts)
    prompts = [ids_of(cfg, n, seed=n) for n in (5, 9, 20, 31, 12)]
    requests = [engine.submit(p, max_new=24) for p in prompts]
    for prompt, req in zip(prompts, requests):
        served = np.asarray(req.result(timeout=120))
        alone = model.generate_cached(prompt, max_new=24)
        np.testing.assert_array_equal(served[-24:], alone[-24:])
    assert engine.trace_counts == traced  # no program traced after warm-up


@pytest.mark.parametrize("policy", [dict(temperature=0.8, top_k=4), dict(temperature=1.1, top_p=0.7)],
                         ids=["top_k", "top_p"])
def test_engine_samples_what_the_model_samples_by_seed(base, engine, policy):
    """Temperature and top_p reach the step as their bits in the slots'
    one int32 array: a sampled request beside a greedy one gives what the
    model samples alone from the same seed."""
    cfg, model = base
    prompt, other = ids_of(cfg, 11, seed=17), ids_of(cfg, 6, seed=18)
    greedy = engine.submit(other, max_new=9)
    served = np.asarray(engine.submit(prompt, max_new=9, seed=13, **policy).result(timeout=120))
    alone = model.generate_cached(prompt, max_new=9, rng=jax.random.PRNGKey(13), **policy)
    np.testing.assert_array_equal(served[-9:], alone[-9:])
    np.testing.assert_array_equal(np.asarray(greedy.result(timeout=120))[-9:],
                                  model.generate_cached(other, max_new=9)[-9:])


def test_engine_counts_pairs_and_experts_by_hand(base, engine):
    """One request alone in the engine: every decode step routes one
    token. The counters against a count made from the reference's router
    along the served sequence (layer by layer, the reference's own hidden
    states)."""
    cfg, model = base
    before = engine.metrics.snapshot()
    prompt = ids_of(cfg, 6, seed=77)
    served = np.asarray(engine.submit(prompt, max_new=10).result(timeout=120))[-10:]
    after = engine.metrics.snapshot()
    steps = after["decode_steps"] - before["decode_steps"]
    assert steps == 9
    seq = np.concatenate([prompt, served])[:-1]
    offset, held = ref.experts_held(cfg)
    top = ref.make_top(cfg, SEED)
    x = top["embed"][jnp.asarray(seq)]
    pairs = hit = 0
    for i in range(ref.n_layers(cfg)):
        w = ref.make_layer(cfg, SEED, i)
        if not ref.is_dense(cfg, i):
            h = x + ref.attention(cfg, i, w, ref.rms_norm(x, w["norm1"], 1e-5), "float32")
            weights = np.asarray(ref.route(cfg, w, ref.rms_norm(h, w["norm2"], 1e-5)))
            local = weights[len(prompt):, offset:offset + held] > 0  # the decode steps' tokens
            pairs += int(local.sum())
            hit += int(local.sum())  # one token a step: a pair is an expert hit
        x = ref.layer(cfg, i, w, x)
    assert after["moe_pairs_local"] - before["moe_pairs_local"] == pairs > 0
    assert after["moe_experts_hit"] - before["moe_experts_hit"] == hit


def test_memory_report_and_describe_follow_the_plan(base, engine):
    from deeplearning4j_tpu.serving.generate import generation_memory_report

    _cfg, model = base
    report = generation_memory_report(model, n_slots=3, max_length=96)
    plan = model.cfg.cache_plan(3, 96)
    assert report["cache_bytes"] == sum(p["bytes"] for p in plan)
    assert [(p["kind"], p["columns"], p["ring"]) for p in report["cache_plan"]] == [
        ("full", 96, False), ("window", 8, True), ("full", 96, False), ("window", 8, True)]
    assert report["param_bytes"] == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(model.params_))
    described = engine.describe()
    assert described["backend"] == "decoder" and described["spec_decode_k"] == 1
    assert described["memory"]["cache_plan"] == report["cache_plan"]
    assert engine.backend.cache_bytes == report["cache_bytes"]


def test_prefix_cache_and_speculation_are_refused_or_pinned(base):
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    _cfg, model = base
    with pytest.raises(ValueError, match="no prefix cache"):
        GenerationEngine(model, n_slots=2, max_length=64, prefix_cache_mb=1)
    gen = GenerationEngine(model, n_slots=2, max_length=64, spec_decode_k=4)
    try:
        assert gen.spec_decode_k == 1
    finally:
        gen.shutdown(drain=False)


def test_counters_reach_the_metrics_endpoint(engine):
    text = engine.metrics.registry.prometheus_text()
    assert "generation_moe_pairs_local_total" in text
    assert "generation_moe_experts_hit_total" in text
    assert {"moe_pairs_local", "moe_experts_hit"} <= set(engine.metrics.snapshot())


@pytest.mark.parametrize("family_module", ["decoder_lm", "transformer_lm"])
def test_closing_a_server_frees_the_device(family_module):
    """The benchmark's reference runs after the window in the same
    process: what the server held (weights, cache, the /predict
    snapshot's own reference to the weights) has to be gone when
    ``close()`` returns, not when the last thread has let go."""
    spec = importlib.util.spec_from_file_location(
        "bench_families_" + family_module, os.path.join(BENCH, "families", family_module + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    preset = {"decoder_lm": ("tiny-mimo", "tiny-reason"), "transformer_lm": ("tiny-lm", "tiny-chat")}
    with open(os.path.join(BENCH, "configs", preset[family_module][0] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", preset[family_module][1] + ".json")) as f:
        traffic = json.load(f)

    def live():
        gc.collect()
        return {id(a): a.nbytes for a in jax.live_arrays()}

    before = live()
    server = module.Server(config, traffic, 3)
    held = sum(n for i, n in live().items() if i not in before)
    assert held > 100_000
    server.close()
    left = sum(n for i, n in live().items() if i not in before)
    assert left == 0, f"{left} of {held} bytes still live after close()"


def test_rows_in_chunks_give_what_one_call_gives():
    """The grouped products run ``chunk`` rows at a time, for as many
    chunks as the held pairs fill: 96 pairs in chunks of 8 (groups cut by
    chunk edges), and in chunks of 40 (96 is no multiple), against one
    call over all rows."""
    cfg = all_experts(tiny())
    _w, bp = expert_layer(cfg)
    x = tokens(cfg)
    whole, pairs, hit = moe_dropless_ffn(x, x, bp, 4, (0, 16))
    for chunk in (8, 40):
        y, n, h = moe_dropless_ffn(x, x, bp, 4, (0, 16), chunk=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(whole), rtol=1e-4, atol=1e-8)
        assert (int(n), int(h)) == (int(pairs), int(hit))
    # few held pairs: the loop stops after the chunks they fill
    held = {k: (v[4:8] if k[0] == "E" else v) for k, v in bp.items()}
    a, _, _ = moe_dropless_ffn(x, x, held, 4, (4, 4), chunk=8)
    b, _, _ = moe_dropless_ffn(x, x, held, 4, (4, 4))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-8)


def test_steady_steps_put_nothing(base, engine, monkeypatch):
    """A step hands back, on the device, the inputs of the next one, and a
    prefill writes its slot's row of them; the backend puts them only
    after the host changed a slot itself (a finish). One request on a
    warmed engine: one put, its prefill's (the request's row and the
    prompt padded to its bucket), and none for the ten steps after it;
    the tokens are the model's own."""
    import deeplearning4j_tpu.serving.generate as generate

    cfg, model = base
    puts = []
    real = generate.jnp.asarray

    def counted(x, *args, **kwargs):
        if isinstance(x, (np.ndarray, list)):
            puts.append(np.shape(x))
        return real(x, *args, **kwargs)

    monkeypatch.setattr(generate.jnp, "asarray", counted)
    prompt = ids_of(cfg, 7, seed=91)
    served = np.asarray(engine.submit(prompt, max_new=11).result(timeout=120))
    monkeypatch.undo()
    np.testing.assert_array_equal(served, model.generate_cached(prompt, max_new=11))
    assert puts == [(8 + 8,)], puts

"""DecoderLM's latent attention, group-limited routing and shared expert
(models/decoder_lm.py, nn/conf/layers/moe.py) against the plain reference
of DeepSeek-V2 (benchmark/reference/deepseek_v2.py) at a tiny size with
every mechanism present: 4 routing groups of 4 experts of which 2 groups
stay a token and one group is held, two shared experts, YaRN with an
original context of 16 (every sequence here crosses it), prefill
attention in blocks of 8 (``small_blocks``). Seeded random weights, logits and not tokens.
The published keys are translated by the benchmark's family module, as
the cell does; float32 parameters here, so the tolerances are those of
float32 summation order (1e-5 on logits of size ~0.5; the absorbed and the
expanded attention contract in different orders), far under what a wrong
frequency, scale, group, weight or layout gives.
"""

import copy
import importlib.util
import json
import math
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

from reference import deepseek_v2 as ref  # noqa: E402

from deeplearning4j_tpu.models import decoder_lm  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.moe import (  # noqa: E402
    group_limited_softmax_route,
    moe_dropless_ffn,
    shared_swiglu,
)
from deeplearning4j_tpu.nn.ops import latent_decode  # noqa: E402
from deeplearning4j_tpu.nn.ops.registry import (  # noqa: E402
    ENV_FLAGS,
    default_kernel_registry,
)

TOL = 1e-5
SEED = 7


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_families_latent_decoder_lm",
        os.path.join(BENCH, "families", "latent_decoder_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


fam = _family()


def tiny(**changes):
    """The rehearsal preset in float32, with ``changes`` to published keys
    (a dict value updates a nested group, None removes it)."""
    with open(os.path.join(BENCH, "configs", "tiny-deepseek.json")) as f:
        cfg = json.load(f)
    cfg["deployment"]["param_dtype"] = "float32"
    for key, value in changes.items():
        if isinstance(value, dict):
            cfg[key] = {**cfg[key], **value}
        else:
            cfg[key] = value
    return cfg


def build(cfg, seed=SEED):
    model = fam._model(cfg)
    model.params_ = fam.program_params(cfg, seed, model.cfg)
    return model


def ids_of(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg["vocab_size"], (n,))


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """The program's two block sizes cut to the tiny size for this file, so
    that its prompts cross prefill blocks and expert chunks as the cell's
    do (every model here is built, and so traced, under them)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder_lm, "PREFILL_BLOCK", 8)
        patch.setattr(decoder_lm, "EXPERT_TOKEN_CHUNK", 16)
        yield


@pytest.fixture(scope="module")
def base():
    cfg = tiny()
    return cfg, build(cfg)


#: the two ways a decode step reads the latent cache: the whole-slab
#: einsums (what the CPU gets) and the length-aware kernel
#: (``nn/ops/latent_decode.py``) under the Pallas interpreter
CORES = ["einsums", "kernel"]


def kernel_interpreted(patch):
    """The registry's mode ``interpret`` and a tile of 8 columns, for what
    is traced while ``patch`` lasts."""
    patch.setenv(ENV_FLAGS[latent_decode.NAME], "interpret")
    patch.setattr(latent_decode, "TILE", 8)
    default_kernel_registry().reset(latent_decode.NAME)


# -- the whole model ----------------------------------------------------------
def test_forward_matches_reference(base):
    cfg, model = base
    ids = ids_of(cfg, 40)  # five prefill blocks, past the original context of 16
    want = np.asarray(ref.logits(cfg, SEED, ids))
    got = model.logits(ids[None])[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("prompt_len", [3, 8, 29],
                         ids=["below-a-block", "one-block", "several-blocks"])
def test_prefill_then_decode_matches_reference(base, prompt_len):
    """Bucketed prefill (the expanded form by blocks of 8; the prompt of 29
    in a bucket of 32 whose last block is padding in part), then 30 tokens
    through the latent cache (the absorbed form), from inside the original
    rotary context of 16 to far past it. The logits each token was chosen
    from against the reference's full forward over prompt + tokens."""
    cfg, model = base
    out, logits = model.generate_cached(ids_of(cfg, prompt_len), max_new=30,
                                        return_logits=True)
    want = np.asarray(ref.logits(cfg, SEED, out[:-1]))[prompt_len - 1:]
    np.testing.assert_allclose(logits, want, atol=TOL)


YARN = {"beta_fast": 0.25, "beta_slow": 0.1}
ATTENTION_KEYS = [
    {"rope_theta": 10000}, {"rope_scaling": {"factor": 3}}, {"rope_scaling": YARN},
    {"rope_scaling": {"mscale": 5.0}}, {"rope_scaling": {"mscale_all_dim": 3.0}},
    {"rope_scaling": {"original_max_position_embeddings": 64}}, {"rope_scaling": None},
    {"q_lora_rank": 16}, {"kv_lora_rank": 24}, {"qk_nope_head_dim": 8},
    {"qk_rope_head_dim": 8}, {"v_head_dim": 16}, {"rms_norm_eps": 0.01}]
FFN_KEYS = [
    {"n_group": 2, "topk_group": 1}, {"topk_group": 3}, {"topk_method": "greedy"},
    {"norm_topk_prob": True}, {"routed_scaling_factor": 2.0}, {"n_shared_experts": 1},
    {"n_shared_experts": None}, {"first_k_dense_replace": 2}, {"moe_layer_freq": 2},
    {"num_experts_per_tok": 2}]


def _case_id(changes):
    return "-".join(f"{k}={v}" for k, v in changes.items()).replace(" ", "")[:60]


@pytest.mark.parametrize("changes", ATTENTION_KEYS + FFN_KEYS, ids=_case_id)
def test_each_published_key_is_read(changes):
    """One key changed, the forward against the reference with the same
    change and, for a key of the attention (whose decode is another form
    than its prefill), cached decode too; and the change does move the
    logits, so a key the program ignored would fail."""
    cfg = tiny(**changes)
    model = build(cfg)
    ids = ids_of(cfg, 21)
    want = np.asarray(ref.logits(cfg, SEED, ids))
    np.testing.assert_allclose(model.logits(ids[None])[0], want, atol=TOL)
    if changes in ATTENTION_KEYS:
        out, logits = model.generate_cached(ids[:10], max_new=8, return_logits=True)
        full = np.asarray(ref.logits(cfg, SEED, out[:-1]))[9:]
        np.testing.assert_allclose(logits, full, atol=TOL)
    unchanged = np.asarray(ref.logits(tiny(), SEED, ids))
    assert np.abs(unchanged - want).max() > 20 * TOL


# -- rotary scaling -----------------------------------------------------------
def test_yarn_frequencies_and_scale_by_hand():
    """The tiny preset: 16 rotated dimensions (8 pairs), base 100, factor 8,
    original context 16. Pair i turns 100^(-i/8) a position, so it makes
    16 x 100^(-i/8) / 2 pi turns in the original context: 2.55, 1.43,
    0.81, ... . ``beta_fast`` 4 turns are reached by no pair (its
    correction dimension is negative: floor -> 0), ``beta_slow`` 1 turn
    lies between pairs 1 and 2 (1.62: ceil -> 2). So pair 0 keeps its
    frequency, pair 1 takes the mean of its own and its own / 8, pairs
    2-7 are slowed 8 x."""
    sc = tiny()["rope_scaling"]
    inv, amp = decoder_lm.yarn_frequencies(16, 100.0, sc)
    plain = 100.0 ** (-np.arange(8) / 8.0)
    want = plain * np.asarray([1.0, (1 + 1 / 8) / 2] + [1 / 8] * 6)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert 1.737 * math.log(16 / (2 * math.pi * 4)) < 0 < 1.737 * math.log(16 / (2 * math.pi)) < 2
    assert amp == 1.0  # mscale / mscale_all_dim, both 0.707
    np.testing.assert_allclose(np.asarray(ref.yarn(tiny())[0]), want, rtol=1e-6)
    # m = 0.1 x 0.707 x ln 8 + 1 = 1.14702; the scores' scale is m^2 / sqrt(16 + 16)
    m = 0.1 * 0.707 * math.log(8) + 1
    assert abs(m - 1.14702) < 1e-5
    cfg = build(tiny()).cfg
    assert abs(decoder_lm.softmax_scale(cfg, "latent") - m * m / math.sqrt(32)) < 1e-7
    # the published numbers: 32 pairs, base 10000, factor 40 over 4096
    pub = json.load(open(os.path.join(BENCH, "configs", "deepseek-v2-ep8.json")))
    inv, amp = decoder_lm.yarn_frequencies(64, 10000.0, pub["rope_scaling"])
    # 32 turns: 64 ln(4096 / 64 pi) / (2 ln 10000) = 10.47 -> 10; 1 turn: 22.5 -> 23
    assert inv[10] == np.float32(10000.0 ** (-10 / 32)) and amp == 1.0
    np.testing.assert_allclose(inv[23:], 10000.0 ** (-np.arange(23, 32) / 32) / 40, rtol=1e-6)
    assert 10000.0 ** (-16 / 32) / 40 < inv[16] < 10000.0 ** (-16 / 32)
    assert abs(0.1 * 0.707 * math.log(40) + 1 - 1.26080) < 1e-5


# -- absorbed against expanded ------------------------------------------------
@pytest.mark.parametrize("core", CORES)
def test_absorbed_step_equals_expanded_attention(base, core, monkeypatch):
    """One latent layer on the same float32 weights: the expanded form over
    21 positions against the absorbed form for the last position over a
    cache that holds the entries of the first 20 (with idle columns after
    them). Equal to rounding: the two contract in different orders. The
    kernel reads the same cache as layer 1 of a segment's three slabs, by
    the rows' lengths."""
    _cfg, model = base
    cfg = model.cfg
    bp = {k: v[0] for k, v in model.params_["segments"][1].items() if v.ndim and k[0] != "E"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 21, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(21)[None], (2, 21))
    whole, entries = decoder_lm._latent_attention(cfg, "latent", bp, x, pos)
    assert entries.shape == (2, 21, 32)  # kv_lora_rank 16 + 16 rotated
    slab = jnp.zeros((2, 32, 40)).at[:, :, :20].set(entries[:, :20].transpose(0, 2, 1))
    c_pos = decoder_lm.cache_positions(
        cfg, jnp.asarray([20, 20]), decoder_lm.init_cache(cfg, 2, 40))["latent"]
    cache = ("columns", slab, c_pos)
    if core == "kernel":
        kernel_interpreted(monkeypatch)
        cache = ("kernel", jnp.full((3, 2, 32, 40), jnp.nan).at[1].set(slab),
                 jnp.asarray(1, jnp.int32), jnp.asarray([20, 20], jnp.int32))
    step, entry = decoder_lm._latent_attention(
        cfg, "latent", bp, x[:, 20:], pos[:, 20:], cache)
    np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(whole[:, 20]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(entry[:, 0]), np.asarray(entries[:, 20]), atol=1e-6)
    assert np.abs(np.asarray(whole[:, 20] - x[:, 20])).max() > 1e-3  # attention did add something


def test_blocked_attention_equals_plain_softmax():
    """``_causal_blocked``: 21 positions in blocks of 8 (a last block padded
    in part) and in one block of 32 against a plain causal softmax; with
    ``n_real`` 11 the third block is not computed (zeros) and the first two
    are unchanged."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 21, 3, d), jnp.float32)
               for i, d in ((0, 10), (1, 10), (2, 6)))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
    s = jnp.where(jnp.tril(jnp.ones((21, 21), bool)), s, -jnp.inf)
    whole = np.asarray(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v))
    for block in (8, 32):
        got = decoder_lm._causal_blocked(q, k, v, 0.3, block)
        np.testing.assert_allclose(np.asarray(got), whole, atol=2e-6)
    part = np.asarray(decoder_lm._causal_blocked(q, k, v, 0.3, 8, n_real=jnp.asarray(11)))
    np.testing.assert_allclose(part[:, :16], whole[:, :16], atol=2e-6)
    np.testing.assert_array_equal(part[:, 16:], 0.0)


# -- the cache ----------------------------------------------------------------
def test_cache_plan_is_one_latent_slab(base):
    _cfg, model = base
    plan = model.cfg.cache_plan(n_slots=3, max_length=64)
    assert [(p["kind"], p["layers"], p["ring"], p["values"]) for p in plan] == [
        ("latent", 1, False, 32), ("latent", 2, False, 32)]
    assert plan[1]["slabs"] == [(2, 3, 32, 64)] and "k" not in plan[1]
    assert plan[1]["bytes"] == 2 * 3 * 32 * 64 * 4  # float32 here
    caches = decoder_lm.init_cache(model.cfg, 3, 64)
    assert [tuple(a.shape for a in seg) for seg in caches] == [((1, 3, 32, 64),), ((2, 3, 32, 64),)]


def test_published_cut_caches_576_values_a_position():
    """The cell's configuration, by hand: 512 + 64 values a position and
    layer, 6 layers in bfloat16 = 6,912 B a position (K and V by head would
    be 128 x (192 + 128) x 2 x 6 = 491,520), 3.40 GB for 48 slots of
    10,240."""
    with open(os.path.join(BENCH, "configs", "deepseek-v2-ep8.json")) as f:
        pub = json.load(f)
    cfg = decoder_lm.DecoderConfig(**fam.program_config(pub))
    plan = cfg.cache_plan(48, 10240)
    assert [(p["layers"], p["values"], p["slabs"]) for p in plan] == [
        (1, 576, [(1, 48, 576, 10240)]), (5, 576, [(5, 48, 576, 10240)])]
    total = sum(p["bytes"] for p in plan)
    assert total == 48 * 10240 * 6912 == 3_397_386_240
    shapes = decoder_lm.segment_shapes(cfg, "latent", "experts")
    assert shapes["Wqb"][0] == (1536, 128, 192) and shapes["Wkva"][0] == (5120, 576)
    assert shapes["Wuk"][0] == shapes["Wuv"][0] == (512, 128, 128)
    assert shapes["Sg"][0] == (5120, 3072) and shapes["Wr"][0] == (5120, 160) and "br" not in shapes
    assert shapes["Eg"][0] == (20, 5120, 1536)
    assert cfg.routing == {"n_group": 8, "topk_group": 3, "renormalise": False,
                           "scale": 16.0}


def test_prefill_bucket_longer_than_the_slot_is_refused(base):
    _cfg, model = base
    cfg = model.cfg
    caches = decoder_lm.init_cache(cfg, 1, 16)
    with pytest.raises(ValueError, match="prefill bucket longer than the slot"):
        jax.eval_shape(lambda p, c: decoder_lm.prefill_slot(
            cfg, p, c, jnp.zeros((1, 32), jnp.int32), jnp.asarray(20), jnp.asarray(0)),
            model.params_, caches)


# -- the expert layer ---------------------------------------------------------
def expert_layer(cfg, layer=1, seed=SEED):
    """(reference weights of one expert layer, the program's leaves)."""
    w = ref.make_layer(cfg, seed, layer)
    bp = {"Wr": w["router.w"], "Eg": w["experts.gate"], "Eu": w["experts.up"],
          "Ed": w["experts.down"], "Sg": w["shared.gate"], "Su": w["shared.up"],
          "Sd": w["shared.down"]}
    return w, bp


def share(cfg, offset, count):
    out = copy.deepcopy(cfg)
    out["n_routed_experts"], out["deployment"]["experts_offset"] = count, offset
    return out


def tokens(cfg, n=24):
    return jax.random.normal(jax.random.PRNGKey(3), (n, cfg["hidden_size"]), jnp.float32)


def route_of(cfg):
    return build(cfg).cfg.route()


def test_group_limited_routing_by_hand():
    """16 experts in 4 groups of 4, 2 groups kept, 3 experts a token. Token
    0's six best experts lie in all four groups; groups 2 and 0 hold the
    two largest scores, so its three experts are the best three INSIDE
    groups 0 and 2, though experts 5 and 13 score higher than the third of
    them. Weights: the softmax probabilities as they are, times 16."""
    z = np.full((2, 16), -4.0, np.float32)
    z[0, [8, 1, 5, 13, 9, 2]] = [3.0, 2.8, 2.6, 2.4, 1.0, 0.5]   # groups 2, 0, 1, 3, 2, 0
    z[1, [4, 5, 6, 12]] = [2.0, 1.9, 1.8, 1.0]                   # groups 1, 1, 1, 3
    chosen, w = group_limited_softmax_route(jnp.asarray(z), None, 3, n_group=4, topk_group=2,
                                            scale=16.0)
    assert np.asarray(chosen).tolist() == [[8, 1, 9], [4, 5, 6]]
    p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w)[0], 16 * p[0, [8, 1, 9]], rtol=1e-6)
    assert np.asarray(w)[0].sum() < 16 * 0.75  # not renormalised: experts 5 and 13 keep their mass
    _, renorm = group_limited_softmax_route(jnp.asarray(z), None, 3, n_group=4, topk_group=2,
                                            renormalise=True)
    np.testing.assert_allclose(np.asarray(renorm).sum(-1), 1.0, rtol=1e-6)
    # the reference's rule gives the same experts and weights
    cfg = share(tiny(), 0, 16)
    x = tokens(cfg)
    w_ref, _ = expert_layer(cfg)
    weights = np.asarray(ref.route(cfg, w_ref, x))
    chosen, w = route_of(cfg)(x @ w_ref["router.w"], None, 3)
    for t in range(x.shape[0]):
        assert sorted(np.nonzero(weights[t])[0]) == sorted(np.asarray(chosen[t]).tolist())
        np.testing.assert_allclose(weights[t, np.asarray(chosen[t])], np.asarray(w[t]), rtol=1e-5)
        assert len({int(e) // 4 for e in chosen[t]}) <= 2


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test: a layer of 16 experts held a routing group of 4 at a
    time. What the four holders compute of the routed experts, each from
    the generator's weights for ITS experts, plus the shared expert counted
    ONCE, adds up to the uncut reference's output for the layer: the
    router, its groups, its choice and its weights are over all 16 in every
    share, and every holder computes the same shared expert."""
    cfg = tiny()
    uncut = share(cfg, 0, 16)
    x = tokens(cfg)
    w_all, bp_all = expert_layer(uncut)
    want = np.asarray(ref.experts(uncut, w_all, x, "float32"))
    route = route_of(cfg)
    total = np.zeros_like(want)
    total_ref = np.zeros_like(want)
    pairs = 0
    for offset in (0, 4, 8, 12):
        held = share(cfg, offset, 4)
        w, bp = expert_layer(held)
        np.testing.assert_array_equal(np.asarray(w["experts.gate"]),
                                      np.asarray(w_all["experts.gate"][offset:offset + 4]))
        y, n, _hit = moe_dropless_ffn(x, x, bp, 3, (offset, 4), route=route)
        with_shared, _, _ = moe_dropless_ffn(x, x, bp, 3, (offset, 4), route=route, shared=True)
        np.testing.assert_allclose(np.asarray(with_shared - y), np.asarray(shared_swiglu(x, bp)),
                                   rtol=1e-4, atol=1e-7)
        np.testing.assert_allclose(np.asarray(with_shared),
                                   np.asarray(ref.experts(held, w, x, "float32")),
                                   rtol=1e-4, atol=1e-7)
        total += np.asarray(y)
        total_ref += np.asarray(ref.routed(held, w, x, "float32"))
        pairs += int(n)
    shared = np.asarray(shared_swiglu(x, bp_all))
    assert np.abs(want).max() > 5e-4 and np.abs(shared).max() > 1e-4
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(total_ref + shared, want, rtol=1e-4, atol=1e-7)
    assert pairs == x.shape[0] * 3


def test_manual_expert_parallelism_counts_the_shared_expert_once():
    """parallel/moe.py: inside a shard_map over an "expert" axis of 4, every
    shard computes its routing group's share and the same shared expert;
    the sum over the axis holds the shared expert once."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deeplearning4j_tpu.parallel.moe import expert_parallel_dropless_ffn

    cfg = share(tiny(), 0, 16)
    w, bp = expert_layer(cfg)
    x = tokens(cfg)
    route = route_of(cfg)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    specs = {k: (P("expert") if k[0] == "E" else P()) for k in bp}
    run = jax.jit(jax.shard_map(
        lambda x, bp: expert_parallel_dropless_ffn(x, x, bp, 3, "expert", route=route,
                                                   shared=True),
        mesh=mesh, in_specs=(P(), specs), out_specs=P(), check_vma=False))
    y, pairs, hit = run(x, bp)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.experts(cfg, w, x, "float32")),
                               rtol=1e-4, atol=1e-7)
    assert int(pairs) == x.shape[0] * 3 and 1 <= int(hit) <= 16


def test_expert_layer_in_token_chunks_gives_what_one_call_gives(base, monkeypatch):
    """``EXPERT_TOKEN_CHUNK``: 21 tokens of which 17 real, 16 and then 8 at
    a time (a last chunk padded), against one call."""
    _cfg, model = base
    cfg = model.cfg
    bp = {k: v[0] for k, v in model.params_["segments"][1].items()}
    r = jax.random.normal(jax.random.PRNGKey(5), (21, cfg.d_model), jnp.float32)
    mask = jnp.arange(21) < 17
    monkeypatch.setattr(decoder_lm, "EXPERT_TOKEN_CHUNK", 21)
    whole, pairs, _hit = decoder_lm._experts(cfg, bp, r, jnp.float32, mask, None)
    for chunk in (16, 8):
        monkeypatch.setattr(decoder_lm, "EXPERT_TOKEN_CHUNK", chunk)
        y, n, _ = decoder_lm._experts(cfg, bp, r, jnp.float32, mask, None)
        np.testing.assert_allclose(np.asarray(y), np.asarray(whole), rtol=1e-4, atol=1e-7)
        assert int(n) == int(pairs) > 0


# -- the engine ---------------------------------------------------------------
def _engine(model):
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    gen = GenerationEngine(model, n_slots=3, max_length=96, prefill_buckets=[8, 16, 32])
    gen.warmup()
    return gen


@pytest.fixture(scope="module")
def engine(base):
    gen = _engine(base[1])
    yield gen
    gen.shutdown(drain=False)


@pytest.fixture(scope="module")
def kernel_engine(base):
    """An engine whose decode program was traced with the kernel (12 tiles
    of 8 columns a slot)."""
    with pytest.MonkeyPatch.context() as patch:
        kernel_interpreted(patch)
        gen = _engine(base[1])
        verdicts = default_kernel_registry().snapshot()[latent_decode.NAME]
    default_kernel_registry().reset(latent_decode.NAME)
    assert [v["enabled"] for v in verdicts.values()] == [True], verdicts
    yield gen
    gen.shutdown(drain=False)


@pytest.mark.parametrize("core", CORES)
def test_engine_serves_what_the_model_generates_alone(base, core, request):
    """``alone`` is the model's own cached generation, whose decode program
    reads the cache by the einsums under either engine."""
    cfg, model = base
    engine = request.getfixturevalue("engine" if core == "einsums" else "kernel_engine")
    traced = dict(engine.trace_counts)
    prompts = [ids_of(cfg, n, seed=n) for n in (5, 9, 20, 31, 12)]
    requests = [engine.submit(p, max_new=24) for p in prompts]
    for prompt, req in zip(prompts, requests):
        served = np.asarray(req.result(timeout=120))
        alone = model.generate_cached(prompt, max_new=24)
        np.testing.assert_array_equal(served[-24:], alone[-24:])
    assert engine.trace_counts == traced  # no program traced after warm-up


def test_engine_counts_latent_positions_by_hand(base, engine):
    """One request alone in the engine: a prompt of 6 and 10 tokens. The
    first comes from the prefill; decode step j = 1..9 has 6 + j - 1
    positions of the slot behind it: 6 + 7 + ... + 14 = 90."""
    cfg, _model = base
    before = engine.metrics.snapshot()
    engine.submit(ids_of(cfg, 6, seed=77), max_new=10).result(timeout=120)
    after = engine.metrics.snapshot()
    assert after["decode_steps"] - before["decode_steps"] == 9
    assert after["latent_positions_read"] - before["latent_positions_read"] == sum(range(6, 15)) == 90
    assert after["moe_pairs_local"] > before["moe_pairs_local"]  # the new router counts as the old


def test_memory_report_and_describe_follow_the_plan(base, engine):
    from deeplearning4j_tpu.serving.generate import generation_memory_report

    _cfg, model = base
    report = generation_memory_report(model, n_slots=3, max_length=96)
    # 16 + 16 values a position and layer, three layers, float32 here
    assert report["cache_bytes"] == 3 * 96 * 3 * 32 * 4
    assert [(p["kind"], p["layers"], p["columns"], p["ring"], p["values"])
            for p in report["cache_plan"]] == [("latent", 1, 96, False, 32),
                                               ("latent", 2, 96, False, 32)]
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


def test_latent_counter_reaches_the_metrics_endpoint(engine):
    assert "generation_latent_positions_read_total" in engine.metrics.registry.prometheus_text()
    assert "latent_positions_read" in engine.metrics.snapshot()

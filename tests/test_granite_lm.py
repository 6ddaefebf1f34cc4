"""DecoderLM's state-space layers among attention layers
(models/decoder_lm.py) against the plain reference of Granite-4.0-H
(benchmark/reference/granite_hybrid.py) at a tiny size with every mechanism
present: three Mamba-2 layers around one NoPE grouped-query attention layer
(segments of 2, 1 and 1), a chunk of 8 (every prompt here crosses chunks),
4 of 8 top-3 experts held and a shared expert in every layer, the four
multipliers, a tied head. Seeded random weights, logits and not tokens. The
published keys are translated by the benchmark's family module, as the cell
does. The reference scans the recurrence one position at a time; the
program prefills by chunks and decodes one step over a cached state. The
logits are ~0.003 in size (the embedding is drawn at 0.02 / 12 and read back
by the tied head, over 16): float32 parameters give the tolerance of float32
summation order (5e-8; 5e-9 is what the two sides differ by here), bfloat16
ones that of its rounding (1.5e-4; 3.4e-5 read), far under what a wrong decay,
tail, scale or layout gives: the least any one published key moves the logits
is 1.7e-5, a key of the mixer 2e-3 to 5e-3.
"""

import copy
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

from reference import granite_hybrid as ref  # noqa: E402

from deeplearning4j_tpu.models import decoder_lm  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.moe import (  # noqa: E402
    group_limited_softmax_route,
    moe_dropless_ffn,
    shared_swiglu,
)

TOL = {"float32": 5e-8, "bfloat16": 1.5e-4}
SEED = 11
CHUNK = 8


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_families_hybrid_decoder_lm",
        os.path.join(BENCH, "families", "hybrid_decoder_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


fam = _family()


def tiny(dtype="float32", **changes):
    """The rehearsal preset, with ``changes`` to published keys."""
    with open(os.path.join(BENCH, "configs", "tiny-granite.json")) as f:
        cfg = json.load(f)
    cfg["deployment"]["param_dtype"] = dtype
    cfg.update(changes)
    return cfg


def build(cfg, seed=SEED):
    model = fam._model(cfg)
    model.params_ = fam.program_params(cfg, seed, model.cfg)
    return model


def ids_of(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg["vocab_size"], (n,))


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both(request):
    cfg = tiny(request.param)
    return cfg, build(cfg), TOL[request.param]


@pytest.fixture(scope="module")
def base():
    cfg = tiny()
    return cfg, build(cfg)


# -- the whole model ----------------------------------------------------------
def test_forward_matches_reference(both):
    cfg, model, tol = both
    ids = ids_of(cfg, 43)  # five chunks and a part of a sixth
    want = np.asarray(ref.logits(cfg, SEED, ids))
    got = model.logits(ids[None])[0]
    assert np.abs(want).max() > 0.002
    np.testing.assert_allclose(got, want, atol=tol)


@pytest.mark.parametrize("prompt_len", [2, 8, 29],
                         ids=["below-the-tail", "one-chunk", "several-chunks"])
def test_prefill_then_decode_matches_reference(both, prompt_len):
    """Bucketed prefill (the chunked form; the prompt of 2 is shorter than
    the convolution's tail of 3, the one of 29 sits in a bucket of 32 whose
    last chunk is padding in part), then 30 tokens through the cache (one
    step of the recurrence each, the attention layer over its slab). The
    logits each token was chosen from against the reference's full forward
    over prompt + tokens, whose recurrence runs a position at a time."""
    cfg, model, tol = both
    out, logits = model.generate_cached(ids_of(cfg, prompt_len), max_new=30,
                                        return_logits=True)
    want = np.asarray(ref.logits(cfg, SEED, out[:-1]))[prompt_len - 1:]
    np.testing.assert_allclose(logits, want, atol=tol)


KEYS = [{"embedding_multiplier": 5}, {"residual_multiplier": 0.5},
        {"attention_multiplier": 40.0}, {"logits_scaling": 4}, {"rms_norm_eps": 0.01},
        {"mamba_chunk_size": 5}, {"mamba_d_conv": 3}, {"mamba_n_groups": 2},
        {"mamba_d_state": 8}, {"mamba_n_heads": 4, "mamba_d_head": 32},
        {"num_key_value_heads": 4}, {"num_experts_per_tok": 2},
        {"shared_intermediate_size": 16}, {"intermediate_size": 16},
        {"layer_types": ["attention", "mamba", "mamba", "attention"]},
        {"layer_types": ["mamba"] * 4}]


def _case_id(changes):
    return "-".join(f"{k}={v}" for k, v in changes.items()).replace(" ", "")[:50]


@pytest.mark.parametrize("changes", KEYS, ids=_case_id)
def test_each_published_key_is_read(changes):
    """One key changed: the forward and cached decoding against the
    reference with the same change; and the change does move the logits
    (but the chunk size, which must not), so a key the program ignored
    would fail."""
    cfg = tiny(**changes)
    model = build(cfg)
    ids = ids_of(cfg, 21)
    want = np.asarray(ref.logits(cfg, SEED, ids))
    np.testing.assert_allclose(model.logits(ids[None])[0], want, atol=TOL["float32"])
    out, logits = model.generate_cached(ids[:10], max_new=8, return_logits=True)
    full = np.asarray(ref.logits(cfg, SEED, out[:-1]))[9:]
    np.testing.assert_allclose(logits, full, atol=TOL["float32"])
    moved = np.abs(np.asarray(ref.logits(tiny(), SEED, ids)) - want).max()
    if "mamba_chunk_size" in changes:
        assert moved == 0
    else:
        assert moved > 5 * TOL["float32"]


@pytest.mark.parametrize("key,value", [("hidden_act", "gelu"), ("attention_bias", True),
                                       ("mamba_proj_bias", True), ("mamba_conv_bias", False),
                                       ("position_embedding_type", "rope"),
                                       ("tie_word_embeddings", False)])
def test_what_is_not_built_is_refused(key, value):
    with pytest.raises(ValueError, match="is not built"):
        fam.program_config(tiny(**{key: value}))


# -- the recurrence -----------------------------------------------------------
def _scan_inputs(t, b=2, g=2, r=2, p=4, n=6, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, t, g, r, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, g, r), jnp.float32) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (g, r), jnp.float32, 0.0, 2.5))
    bm = jax.random.normal(ks[3], (b, t, g, n), jnp.float32)
    cm = jax.random.normal(ks[4], (b, t, g, n), jnp.float32)
    return x, dt, a, bm, cm


def _sequential(x, dt, a, bm, cm):
    """The recurrence a position at a time, in NumPy float64."""
    x, dt, a, bm, cm = (np.asarray(v, np.float64) for v in (x, dt, a, bm, cm))
    b, t, g, r, p = x.shape
    h = np.zeros((b, g, r, p, bm.shape[-1]))
    ys = []
    for i in range(t):
        h = (h * np.exp(dt[:, i] * a)[..., None, None]
             + (dt[:, i, ..., None] * x[:, i])[..., None] * bm[:, i, :, None, None, :])
        ys.append((h * cm[:, i, :, None, None, :]).sum(-1))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("length", [1, 2, 3, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK],
                         ids=lambda n: f"T={n}")
def test_chunked_scan_equals_the_sequential_one(length):
    """The chunked dual form against the recurrence written out, at
    lengths around the chunk; and the one-step cached form, stepped
    through the same inputs, gives the same again."""
    x, dt, a, bm, cm = _scan_inputs(length)
    want_y, want_h = _sequential(x, dt, a, bm, cm)
    y, h = decoder_lm._ssm_chunked(x, dt, a, bm, cm, CHUNK)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-4, atol=1e-5)
    state = jnp.zeros_like(h)
    for i in range(length):
        y_i, state = decoder_lm._ssm_step(state, x[:, i], dt[:, i], a, bm[:, i], cm[:, i])
        np.testing.assert_allclose(np.asarray(y_i), want_y[:, i], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state), want_h, rtol=1e-4, atol=1e-5)


def test_chunks_past_the_real_length_are_not_visited():
    """``n_real``: padding has dt = 0, and whole chunks of it are skipped:
    the state is the one after the real positions either way."""
    x, dt, a, bm, cm = _scan_inputs(4 * CHUNK)
    real = CHUNK + 3
    dt = dt.at[:, real:].set(0.0)
    _, want_h = _sequential(x[:, :real], dt[:, :real], a, bm[:, :real], cm[:, :real])
    y_all, h_all = decoder_lm._ssm_chunked(x, dt, a, bm, cm, CHUNK)
    y, h = jax.jit(lambda n: decoder_lm._ssm_chunked(x, dt, a, bm, cm, CHUNK, n))(real)
    np.testing.assert_allclose(np.asarray(h_all), want_h, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_all))
    np.testing.assert_array_equal(np.asarray(y[:, :2 * CHUNK]), np.asarray(y_all[:, :2 * CHUNK]))
    assert not np.asarray(y[:, 2 * CHUNK:]).any()


def _prefill(model, prompt, bucket, slots=2, slot=1):
    cfg = model.cfg
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    return jax.jit(lambda p, c, i, n, s: decoder_lm.prefill_slot(cfg, p, c, i, n, s))(
        model.params_, decoder_lm.init_cache(cfg, slots, 64), jnp.asarray(padded),
        jnp.asarray(len(prompt), jnp.int32), jnp.asarray(slot, jnp.int32))


@pytest.mark.parametrize("length", [1, 2, 3, 11], ids=lambda n: f"prompt={n}")
def test_a_padded_bucket_leaves_what_the_unpadded_prompt_leaves(base, length):
    """The same prompt in a bucket of its own length and in buckets of 16
    and 32 (padding of less than a chunk, and of whole chunks): the same
    state, convolution tail (zeros where the prompt is shorter than 3),
    attention columns and logits; the other slot untouched."""
    cfg, model = base
    prompt = ids_of(cfg, length, seed=length)
    want_logits, want = _prefill(model, prompt, length)
    for bucket in (16, 32):
        logits, caches = _prefill(model, prompt, bucket)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits), atol=2e-6)
        for (kind, _f, _n), got, exp in zip(model.cfg.segments(), caches, want):
            for g, e in zip(got, exp):
                g, e = np.asarray(g), np.asarray(e)
                assert not g[:, 0].any()                      # slot 0 was not asked for
                if kind == "attention":                      # columns past the prompt are padding's
                    g, e = g[..., :length], e[..., :length]
                np.testing.assert_allclose(g[:, 1], e[:, 1], rtol=1e-4, atol=2e-6)
    state, tail = want[0]
    assert np.abs(np.asarray(state[:, 1])).max() > 1e-4
    tail = np.asarray(tail[:, 1])                             # (layers, channels, 3)
    assert (tail[..., :max(3 - length, 0)] == 0).all()
    assert np.abs(tail[..., max(3 - length, 0):]).max() > 1e-4


def test_an_idle_slot_keeps_its_state_bit_for_bit_and_its_nan_reaches_no_live_row(base):
    """Three slots, the middle one idle with NaN planted in its state, tail
    and attention columns: after a decode step its arrays are bit for bit
    as they were, and the live rows' logits and state are those of a step
    in which the idle slot held zeros."""
    cfg, model = base
    dcfg = model.cfg
    step = jax.jit(lambda p, c, t, pos, act: decoder_lm.decode_step(dcfg, p, c, t, pos, act))
    caches = decoder_lm.init_cache(dcfg, 3, 64)
    key = jax.random.PRNGKey(4)
    caches = [tuple(0.1 * jax.random.normal(jax.random.fold_in(key, 7 * i + j), c.shape,
                                             jnp.float32).astype(c.dtype)
                    for j, c in enumerate(seg)) for i, seg in enumerate(caches)]
    planted = [tuple(c.at[:, 1].set(jnp.nan) for c in seg) for seg in caches]
    toks, pos = jnp.asarray([5, 9, 17], jnp.int32), jnp.asarray([6, 3, 11], jnp.int32)
    active = jnp.asarray([True, False, True])
    clean_logits, clean, _ = step(model.params_, caches, toks, pos, active)
    logits, after, _ = step(model.params_, planted, toks, pos, active)
    live = np.asarray([0, 2])
    np.testing.assert_array_equal(np.asarray(logits)[live], np.asarray(clean_logits)[live])
    assert np.isfinite(np.asarray(logits)[live]).all()
    for (kind, _f, _n), seg_after, seg_planted, seg_clean, seg_before in zip(
            dcfg.segments(), after, planted, clean, caches):
        for a, p, c, b in zip(seg_after, seg_planted, seg_clean, seg_before):
            a, p, c, b = (np.asarray(v) for v in (a, p, c, b))
            np.testing.assert_array_equal(a[:, live], c[:, live])
            if kind == "ssm":
                assert np.isnan(a[:, 1]).all()
                np.testing.assert_array_equal(c[:, 1].view(np.uint8), b[:, 1].view(np.uint8))
                assert (c[:, live] != b[:, live]).any()       # the live rows did move


def test_the_state_goes_through_the_layer_loop_as_a_carry(base):
    """The decode program's jaxpr: a state-space segment's scan carries the
    segment's states and tails (and stacks none of them as an output), so
    XLA may update them in place on the donated buffer."""
    _cfg, model = base
    dcfg = model.cfg
    caches = decoder_lm.init_cache(dcfg, 3, 64)
    jaxpr = jax.make_jaxpr(lambda p, c: decoder_lm.decode_step(
        dcfg, p, c, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32)))(model.params_, caches)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 3
    state_shapes = {c.shape for seg in caches for c in seg if c.ndim == 4 and c.dtype == jnp.float32
                    and c.shape[2:] == (16, 128)}
    assert state_shapes == {(2, 3, 16, 128), (1, 3, 16, 128)}
    carried = 0
    for e in scans:
        n_carry = e.params["num_carry"]
        outs = [v.aval.shape for v in e.outvars]
        for shape in state_shapes:
            if shape in outs[:n_carry]:
                carried += 1
            assert shape not in outs[n_carry:]
            assert (shape[0],) + shape not in outs[n_carry:]
    assert carried == 2


# -- the cache plan -----------------------------------------------------------
def test_cache_plan_has_a_state_and_a_tail_and_no_columns(base):
    _cfg, model = base
    plan = model.cfg.cache_plan(3, 64)
    assert [(p["kind"], p["layers"], p["columns"]) for p in plan] == [
        ("ssm", 2, 0), ("attention", 1, 64), ("ssm", 1, 0)]
    assert plan[0]["state"] == (2, 3, 16, 8 * 16) and plan[0]["conv"] == (2, 3, 128 + 32, 3)
    assert plan[0]["bytes"] == 2 * 3 * (8 * 16 * 16 * 4 + 160 * 3 * 4)     # float32 parameters here
    assert plan[0] == model.cfg.cache_plan(3, 4096)[0]                     # whatever the length
    caches = decoder_lm.init_cache(model.cfg, 3, 64)
    assert [tuple((c.shape, c.dtype.name) for c in seg) for seg in caches] == [
        (((2, 3, 16, 128), "float32"), ((2, 3, 160, 3), "float32")),
        (((1, 3, 2, 16, 64), "float32"), ((1, 3, 2, 16, 64), "float32")),
        (((1, 3, 16, 128), "float32"), ((1, 3, 160, 3), "float32"))]
    bf16 = build(tiny("bfloat16")).cfg
    assert [c.dtype.name for c in decoder_lm.init_cache(bf16, 1, 8)[0]] == ["float32", "bfloat16"]


def test_published_cut_by_arithmetic():
    """The cell's configuration, by shapes alone: 4,757 M parameters, a
    slot's 37.75 MB of state and 0.46 MB of tail whatever its length, 4,096
    B a position in the one attention layer; 64 slots of 4,096."""
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-small-ep2.json")) as f:
        config = json.load(f)
    cfg = fam._model(config).cfg
    assert cfg.segments() == [("ssm", "experts", 5), ("attention", "experts", 1),
                              ("ssm", "experts", 4)]
    shapes = jax.eval_shape(lambda: decoder_lm.init_params(cfg))
    assert "head" not in shapes
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    stored = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(shapes))
    assert 4.755e9 < count < 4.760e9 and 9.50e9 < stored < 9.53e9
    plan = cfg.cache_plan(64, 4096)
    state = sum(int(np.prod(p["state"])) * 4 for p in plan if "state" in p)
    tails = sum(int(np.prod(p["conv"])) * 2 for p in plan if "conv" in p)
    slab = sum(p["bytes"] for p in plan if "state" not in p)
    assert state == 64 * 9 * 128 * 64 * 128 * 4 and state // 64 == 37_748_736
    assert tails == 64 * 9 * 8448 * 3 * 2 and tails // 64 == 456_192
    assert slab == 64 * 4096 * 2 * 8 * 128 * 2 and slab // (64 * 4096) == 4096
    assert 3.50e9 < state + tails + slab < 3.54e9
    assert 12.9e9 < stored + state + tails + slab < 13.1e9
    assert plan[0]["state"] == (5, 64, 128, 128 * 64) and plan[0]["conv"] == (5, 64, 8448, 3)
    # the input projection's three parts start at multiples of 128 lanes
    seg = decoder_lm.segment_shapes(cfg, "ssm", "experts")
    assert seg["Win"][0] == (4096, 8192 + 8448 + 128) and 8192 % 128 == 0 and (8192 + 8448) % 128 == 0


# -- positions, scales, the head ----------------------------------------------
def test_rotary_dim_zero_runs_and_rotates_nothing(base):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 4, 16), jnp.float32)
    pos = jnp.arange(10, dtype=jnp.int32).reshape(2, 5)
    assert decoder_lm._rotate(x, pos, 0, 10000.0) is x
    # and so the attention layer does not know where a sequence starts
    _cfg, model = base
    dcfg = model.cfg
    bp = {k: v[0] for k, v in model.params_["segments"][1].items()}
    h = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (1, 7, dcfg.d_model), jnp.float32)
    at = jnp.arange(7, dtype=jnp.int32)[None]
    here, _, _ = decoder_lm.block(dcfg, "attention", "experts", bp, h, at)
    there, _, _ = decoder_lm.block(dcfg, "attention", "experts", bp, h, at + 40)
    np.testing.assert_array_equal(np.asarray(here), np.asarray(there))


def test_a_long_full_layer_attends_by_blocks_and_gives_the_same(monkeypatch):
    """``BLOCKED_SCORE_BYTES``: where the scores of a whole bucket would be
    larger, a full layer without a cache attends by blocks of
    ``PREFILL_BLOCK`` under one running softmax (grouped keys and values
    repeated a query head). Forced at the tiny size (blocks of 8 over 43
    positions) it gives the logits of the one-tensor path, and a prefill in
    a padded bucket the same columns."""
    cfg = tiny()
    ids = ids_of(cfg, 43)
    whole = build(cfg)
    want = whole.logits(ids[None])[0]
    _, want_caches = _prefill(whole, ids[:21], 32)
    monkeypatch.setattr(decoder_lm, "BLOCKED_SCORE_BYTES", 0)
    monkeypatch.setattr(decoder_lm, "PREFILL_BLOCK", 8)
    blocked = build(cfg)
    np.testing.assert_allclose(blocked.logits(ids[None])[0], want, atol=TOL["float32"])
    np.testing.assert_allclose(want, np.asarray(ref.logits(cfg, SEED, ids)), atol=TOL["float32"])
    _, caches = _prefill(blocked, ids[:21], 32)
    for got, exp in zip(jax.tree_util.tree_leaves(caches), jax.tree_util.tree_leaves(want_caches)):
        np.testing.assert_allclose(np.asarray(got)[..., :3], np.asarray(exp)[..., :3],
                                   rtol=1e-4, atol=1e-7)


def test_the_attention_scale_is_the_configurations(base):
    _cfg, model = base
    assert decoder_lm.softmax_scale(model.cfg, "attention") == 0.0625 != 16 ** -0.5
    plain = decoder_lm.DecoderConfig(
        vocab_size=8, d_model=32, n_heads=2, head_dim=16, v_head_dim=16, rotary_dim=0,
        attn_kinds={"full": {"rope_theta": 1e4}}, layers=[("full", "dense")], dense_width=8)
    assert decoder_lm.softmax_scale(plain, "full") == 0.25
    assert (plain.embedding_multiplier, plain.residual_multiplier, plain.logits_scaling,
            plain.tied_head) == (1.0, 1.0, 1.0, False)


def test_the_tied_head_holds_one_leaf(base):
    _cfg, model = base
    assert set(model.params_) == {"embed", "segments", "norm_f"}
    x = jax.random.normal(jax.random.PRNGKey(2), (3, model.cfg.d_model), jnp.float32)
    got = decoder_lm._head(model.cfg, model.params_, x)
    want = decoder_lm._rms_norm(x, model.params_["norm_f"], 1e-5) @ model.params_["embed"].T / 16
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-7)
    # as an untied model's, had its head been the transpose
    jaxpr = str(jax.make_jaxpr(lambda p, x: decoder_lm._head(model.cfg, p, x))(model.params_, x))
    assert "transpose" not in jaxpr


# -- the expert layer ---------------------------------------------------------
def expert_layer(cfg, layer=1, seed=SEED):
    """(reference weights of one layer, the program's expert leaves)."""
    w = ref.make_layer(cfg, seed, layer)
    bp = {"Wr": w["router.w"], "Eg": w["experts.gate"], "Eu": w["experts.up"],
          "Ed": w["experts.down"], "Sg": w["shared.gate"], "Su": w["shared.up"],
          "Sd": w["shared.down"]}
    return w, bp


def share(cfg, offset, count):
    out = copy.deepcopy(cfg)
    out["num_local_experts"], out["deployment"]["experts_offset"] = count, offset
    return out


def tokens(cfg, n=24):
    return jax.random.normal(jax.random.PRNGKey(3), (n, cfg["hidden_size"]), jnp.float32)


def test_one_group_renormalised_is_softmax_over_the_top_k_logits():
    """The rule exists: group-limited softmax routing with one group,
    renormalised, chooses the k largest router outputs and weighs them by a
    softmax over THOSE outputs, which is the published gate."""
    z = 2.0 * jax.random.normal(jax.random.PRNGKey(8), (50, 72), jnp.float32)
    chosen, w = group_limited_softmax_route(z, None, 10, n_group=1, topk_group=1,
                                            renormalise=True, scale=1.0)
    top, want = jax.lax.top_k(z, 10)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_allclose(np.asarray(w), np.asarray(jax.nn.softmax(top, axis=-1)),
                               rtol=1e-5, atol=1e-8)
    # and the reference's gate gives the same experts and weights
    cfg = share(tiny(), 0, 8)
    x = tokens(cfg)
    w_ref, _ = expert_layer(cfg)
    weights = np.asarray(ref.route(cfg, w_ref, x))
    chosen, w = build(cfg).cfg.route()(x @ w_ref["router.w"], None, 3)
    for t in range(x.shape[0]):
        assert sorted(np.nonzero(weights[t])[0]) == sorted(np.asarray(chosen[t]).tolist())
        np.testing.assert_allclose(weights[t, np.asarray(chosen[t])], np.asarray(w[t]), rtol=1e-5)


def test_the_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test: a layer of 8 experts held 4 at a time (the cell:
    0-35 and 36-71 of 72). What the two holders compute of the routed
    experts, each from the generator's weights for ITS experts, plus the
    shared expert counted ONCE, adds up to the uncut reference's output for
    the layer: the router, its choice and its weights are over all 8 in
    both shares, and both holders compute the same shared expert."""
    cfg = tiny()
    uncut = share(cfg, 0, 8)
    x = tokens(cfg)
    w_all, bp_all = expert_layer(uncut)
    want = np.asarray(ref.experts(uncut, w_all, x, "float32"))
    route = build(cfg).cfg.route()
    total, total_ref, pairs = np.zeros_like(want), np.zeros_like(want), 0
    for offset in (0, 4):
        held = share(cfg, offset, 4)
        w, bp = expert_layer(held)
        np.testing.assert_array_equal(np.asarray(w["experts.gate"]),
                                      np.asarray(w_all["experts.gate"][offset:offset + 4]))
        y, n, _hit = moe_dropless_ffn(x, x, bp, 3, (offset, 4), route=route)
        with_shared, _, _ = moe_dropless_ffn(x, x, bp, 3, (offset, 4), route=route, shared=True)
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


# -- the engine ---------------------------------------------------------------
def _engine(model, **kw):
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    gen = GenerationEngine(model, n_slots=3, max_length=96, prefill_buckets=[8, 16, 32], **kw)
    gen.warmup()
    return gen


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def served(request):
    cfg = tiny(request.param)
    model = build(cfg)
    gen = _engine(model)
    yield cfg, model, gen
    gen.shutdown(drain=False)


def test_engine_serves_what_the_model_generates_alone(served):
    """Five requests over three slots, so slots are claimed again with
    another request's state in them and rows sit idle beside live ones;
    ``alone`` is the model's own cached generation on a one-slot cache."""
    cfg, model, engine = served
    traced = dict(engine.trace_counts)
    prompts = [ids_of(cfg, n, seed=n) for n in (5, 9, 20, 31, 2)]
    requests = [engine.submit(p, max_new=24) for p in prompts]
    for prompt, req in zip(prompts, requests):
        got = np.asarray(req.result(timeout=120))
        alone = model.generate_cached(prompt, max_new=24)
        np.testing.assert_array_equal(got[-24:], alone[-24:])
    assert engine.trace_counts == traced  # no program traced after warm-up


def test_engine_counts_live_state_slots_by_hand(served):
    cfg, _model, engine = served
    before = engine.metrics.snapshot()
    engine.submit(ids_of(cfg, 6), max_new=10).result(timeout=120)
    after = engine.metrics.snapshot()
    # token 0 comes from the prefill; nine decode steps advance one live slot each
    assert after["decode_steps"] - before["decode_steps"] == 9
    assert after["state_slots"] - before["state_slots"] == 9
    assert "generation_state_slots_total" in engine.metrics.registry.prometheus_text()


def test_memory_report_lists_state_and_slab_apart(served):
    from deeplearning4j_tpu.serving.generate import generation_memory_report

    _cfg, model, engine = served
    item = 4 if model.cfg.param_dtype == "float32" else 2
    report = generation_memory_report(model, n_slots=3, max_length=96)
    state = 3 * 3 * (8 * 16 * 16 * 4 + 160 * 3 * item)        # three ssm layers, three slots
    slab = 3 * 96 * 2 * 2 * 16 * item                         # K and V, two heads of 16
    assert (report["state_bytes"], report["slab_bytes"]) == (state, slab)
    assert report["cache_bytes"] == state + slab == engine.backend.cache_bytes
    assert [(p["kind"], p["layers"], p["columns"]) for p in report["cache_plan"]] == [
        ("ssm", 2, 0), ("attention", 1, 96), ("ssm", 1, 0)]
    assert report["cache_plan"][0]["state"] == (2, 3, 16, 128)
    described = engine.describe()
    assert described["backend"] == "decoder" and described["spec_decode_k"] == 1
    assert described["memory"]["cache_plan"] == report["cache_plan"]
    # the state does not grow with the slot's length; the slab does
    longer = generation_memory_report(model, n_slots=3, max_length=128)
    assert longer["state_bytes"] == state and longer["slab_bytes"] > slab


@pytest.mark.parametrize("asked", [{"prefix_cache_mb": 1}, {"spec_decode_k": 4}],
                         ids=["prefix-cache", "speculation"])
def test_prefix_cache_and_speculation_are_refused_with_the_typed_error(base, asked):
    from deeplearning4j_tpu.serving.batcher import ServingError
    from deeplearning4j_tpu.serving.generate import GenerationEngine, RecurrentStateError

    _cfg, model = base
    with pytest.raises(RecurrentStateError, match="state") as caught:
        GenerationEngine(model, n_slots=2, max_length=64, **asked)
    assert isinstance(caught.value, ServingError) and isinstance(caught.value, ValueError)


def test_a_request_longer_than_the_slot_is_refused(served):
    from deeplearning4j_tpu.models.transformer_lm import ContextWindowExceeded

    cfg, _model, engine = served
    with pytest.raises(ContextWindowExceeded):
        engine.submit(ids_of(cfg, 40), max_new=60)

"""DecoderLM's latent attention over an indexer's selection, the shared
selection, the key slab beside the latent one and the scaled sigmoid router
(models/decoder_lm.py, nn/conf/layers/moe.py) against the plain reference of
GLM-5.2 (benchmark/reference/glm_dsa.py) at a tiny size with every mechanism
present: four layers of a published pattern of eight (a dense layer that
owns an indexer, two expert layers that share its selection ACROSS a segment
boundary, an expert layer that owns one), 4 indexer heads of 32 that keep 8
positions, 2 of 8 sigmoid-routed experts held with a shared expert, prefill
attention and indexer in blocks of 8 (``small_blocks``). Seeded random
weights, logits and not tokens. The published keys are translated by the
benchmark's family module, as the cell does; float32 parameters here, so the
tolerances are those of float32 summation order (1e-5 on logits of size
~0.5; the absorbed and the expanded attention contract in different orders),
far under what a wrong selection, frequency, scale, weight or layout gives.
A selection is a discrete choice: where two indexer scores lie within
rounding of each other at the margin of the top 8, program and reference may
keep different positions and the logits then differ by far more than
rounding. At float32 that is a part in millions a score; the seeds here read
equal sets, which ``test_forward_selects_what_the_reference_selects``
asserts position by position.
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

from reference import glm_dsa as ref  # noqa: E402

from deeplearning4j_tpu.models import decoder_lm  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.moe import (  # noqa: E402
    moe_dropless_ffn,
    shared_swiglu,
    sigmoid_topk_route,
)
from deeplearning4j_tpu.nn.ops import sparse_latent_decode  # noqa: E402
from deeplearning4j_tpu.nn.ops.registry import (  # noqa: E402
    ENV_FLAGS,
    default_kernel_registry,
)

TOL = 1e-5
SEED = 7


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_families_sparse_latent_decoder_lm",
        os.path.join(BENCH, "families", "sparse_latent_decoder_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


fam = _family()


def tiny(**changes):
    """The rehearsal preset in float32, with ``changes`` to published keys
    (a dict value updates a nested group)."""
    with open(os.path.join(BENCH, "configs", "tiny-glm.json")) as f:
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


@pytest.fixture(params=["gathered", "kernel"])
def both_ways(request, base, monkeypatch):
    """(cfg, model) whose decode steps attend over gathered rows (the
    registry's verdict on the CPU) and, a model of its own on the same
    weights, through ``nn/ops/sparse_latent_decode.py`` under the Pallas
    interpreter in tiles of 8 rows (slots of 128 are sixteen selections
    long: the span is widened for the test)."""
    if request.param == "gathered":
        yield base
        return
    kernel = sparse_latent_decode
    monkeypatch.setenv(ENV_FLAGS[kernel.NAME], "interpret")
    monkeypatch.setattr(kernel, "TILE", 8)
    monkeypatch.setattr(kernel, "MAX_SPAN", 16)
    default_kernel_registry().reset(kernel.NAME)
    cfg = tiny()
    yield cfg, build(cfg)
    verdicts = default_kernel_registry().snapshot()[kernel.NAME]
    assert verdicts and all(v["enabled"] for v in verdicts.values())
    default_kernel_registry().reset(kernel.NAME)


def program_selections(model, ids):
    """The selection each layer of ``model`` attends by in a forward over
    ids (T,): bool (T, T) a layer (None: all before), a layer at a time
    through ``block`` as ``_run_stack`` hands them on."""
    cfg = model.cfg
    ids = jnp.asarray(ids, jnp.int32)[None]
    q_pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    x = decoder_lm._embed(cfg, model.params_, ids)
    out, sel = [], None
    for (kind, ffn, n), seg in zip(cfg.segments(), model.params_["segments"]):
        for j in range(n):
            bp = {k: v[j] for k, v in seg.items()}
            x, (_entries, sel, _held), _counts = decoder_lm.block(
                cfg, kind, ffn, bp, x, q_pos, sel=sel)
            out.append(None if sel is None else np.asarray(sel[0]))
    return out


# -- the whole model ----------------------------------------------------------
def test_forward_matches_reference(base):
    cfg, model = base
    ids = ids_of(cfg, 40)  # five blocks of queries, every one past the top 8
    want = np.asarray(ref.logits(cfg, SEED, ids))
    got = model.logits(ids[None])[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL)


def test_forward_selects_what_the_reference_selects(base):
    """Layer by layer: an owner's selection is the reference's, row t has
    min(8, t + 1) positions none of which lies after t, and a sharer
    attends by the owner's before it: layers 1 and 2 by layer 0's (across
    the segment boundary), layer 3 by its own."""
    cfg, model = base
    ids = ids_of(cfg, 40)
    theirs = []
    ref.logits(cfg, SEED, ids, selections=theirs)
    ours = program_selections(model, ids)
    assert len(ours) == len(theirs) == 4
    for mine, want in zip(ours, theirs):
        np.testing.assert_array_equal(mine, want)
    np.testing.assert_array_equal(ours[0].sum(-1), np.minimum(8, np.arange(40) + 1))
    assert not np.triu(ours[0], 1).any()
    np.testing.assert_array_equal(ours[1], ours[0])
    np.testing.assert_array_equal(ours[2], ours[0])
    assert (ours[3] != ours[0]).any()


@pytest.mark.parametrize("prompt_len", [3, 8, 13, 29],
                         ids=["below-the-top-k", "the-top-k", "past-it",
                              "several-blocks"])
def test_prefill_then_decode_matches_reference(both_ways, prompt_len):
    """Bucketed prefill (the expanded form under the selection's mask, by
    blocks of 8; the prompt of 29 in a bucket of 32 whose last block is
    padding in part; the prompts of 3 and 8 select nothing yet), then 30
    tokens through BOTH caches (indexer scores over the key slab, the exact
    top 8 of cached and own, the absorbed form over the gathered rows). The
    logits each token was chosen from against the reference's full forward
    over prompt + tokens."""
    cfg, model = both_ways
    out, logits = model.generate_cached(ids_of(cfg, prompt_len), max_new=30,
                                        return_logits=True)
    want = np.asarray(ref.logits(cfg, SEED, out[:-1]))[prompt_len - 1:]
    np.testing.assert_allclose(logits, want, atol=TOL)


def test_selection_moves_the_logits(base):
    """The same weights with ``index_topk`` past the context are another
    model: the tolerance above would not pass a selection that was not
    applied."""
    cfg, model = base
    ids = ids_of(cfg, 40)
    dense = build(tiny(index_topk=64))
    assert np.abs(dense.logits(ids[None])[0] - model.logits(ids[None])[0]).max() > 100 * TOL


def test_topk_past_the_context_is_the_dense_latent_layer(base):
    """``index_topk`` >= context: every layer, owner or sharer, forward,
    prefill and cached decode, gives what the dense latent kind gives on the
    same weights (the indexer's leaves then decide nothing)."""
    cfg = tiny(index_topk=64)
    model = build(cfg)
    program = fam.program_config(cfg)
    program["attn_kinds"] = {name: {k: v for k, v in kind.items() if k != "index"}
                             for name, kind in program["attn_kinds"].items()}
    dense = decoder_lm.DecoderLM.from_dict(program)
    indexer = ("Iq", "Ik", "norm_ik", "bias_ik", "Iw")
    dense.params_ = {**model.params_, "segments": [
        {k: v for k, v in seg.items() if k not in indexer}
        for seg in model.params_["segments"]]}
    ids = ids_of(cfg, 40)
    np.testing.assert_allclose(model.logits(ids[None])[0], dense.logits(ids[None])[0],
                               atol=TOL)
    out, logits = model.generate_cached(ids[:13], max_new=20, return_logits=True)
    _out, want = dense.generate_cached(ids[:13], max_new=20, return_logits=True)
    np.testing.assert_allclose(logits, want, atol=TOL)
    np.testing.assert_allclose(
        logits, np.asarray(ref.logits(cfg, SEED, out[:-1]))[12:], atol=TOL)


ATTENTION_KEYS = [
    {"index_topk": 5}, {"index_topk": 16}, {"index_n_heads": 2}, {"index_head_dim": 16},
    {"rope_parameters": {"rope_theta": 10000}}, {"q_lora_rank": 16}, {"kv_lora_rank": 24},
    {"qk_nope_head_dim": 8}, {"qk_rope_head_dim": 8}, {"v_head_dim": 16},
    {"rms_norm_eps": 0.01},
    {"indexer_types": ["full", "full", "full", "shared", "full", "shared", "shared", "shared"]},
    {"deployment": {"layers": [1, 2, 3, 4]}}]
FFN_KEYS = [
    {"routed_scaling_factor": 1.0}, {"num_experts_per_tok": 2}, {"n_shared_experts": None},
    {"moe_intermediate_size": 16},
    {"mlp_layer_types": ["dense", "dense", "dense", "sparse", "sparse", "sparse", "sparse",
                         "sparse"]}]


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


def test_a_sharing_layer_before_any_owner_is_refused():
    with pytest.raises(ValueError, match="no layer before it makes one"):
        build(tiny(deployment={"layers": [2, 3, 4, 5]}))


# -- the selection ------------------------------------------------------------
def _scores(rows, n, seed):
    """Float32 rows with what a selection has to get right: exact ties
    (among them at the k-th place), zeros of both signs, negative values,
    a tail that cannot be chosen."""
    rng = np.random.default_rng(seed)
    s = rng.normal(size=(rows, n)).astype(np.float32)
    s[:, rng.integers(0, n, 12)] = 0.0
    s[0, :9] = 0.75
    s[1, ::3] = -0.0
    s[2] = np.round(s[2], 1)
    s[:, n - 7:] = -np.inf
    s[3, 5:] = -np.inf
    return s


@pytest.mark.parametrize("k", [1, 4, 8, 29, 30, 64], ids=lambda k: f"k={k}")
def test_select_mask_is_the_exact_top_k_with_ties_to_the_lower_position(k):
    """``_select_mask`` (bisection on the bits, no sort) against a stable
    sort by hand, against ``lax.top_k``'s set and against the reference's
    ``top_positions``; where fewer than k can be
    chosen, all of them and no other."""
    s = _scores(6, 37, seed=k)
    got = np.asarray(decoder_lm._select_mask(jnp.asarray(s), k))
    want = np.zeros_like(got)
    for r in range(s.shape[0]):
        order = np.argsort(-np.where(s[r] == 0, 0.0, s[r]), kind="stable")[:k]
        want[r, order] = True
    want &= s > -np.inf
    np.testing.assert_array_equal(got, want)
    _vals, idx = jax.lax.top_k(jnp.where(jnp.asarray(s) == 0, 0.0, jnp.asarray(s)), min(k, 37))
    by_top_k = np.zeros_like(got)
    np.put_along_axis(by_top_k, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(got, by_top_k & (s > -np.inf))
    np.testing.assert_array_equal(
        got, np.asarray(ref.top_positions(jnp.where(jnp.asarray(s) == 0, 0.0, jnp.asarray(s)), k)))
    assert got[3].sum() == min(k, 5)


@pytest.mark.parametrize("t,k", [(37, 8), (128, 64), (300, 40), (256, 5)],
                         ids=lambda v: str(v))
def test_decode_selection_is_the_prefill_selection_of_the_same_row(t, k):
    """``_select_indices`` (a sort of the cached scores, the own one set
    against the k-th best) keeps the set ``_select_mask`` keeps over the
    row with the own score at column ``lengths``, which is how a prefill
    selects for that position: rows with fewer than k, exactly k and many
    more positions behind them, scores with ties (two decimals, zeros of
    both signs) and an own score that wins, loses and ties."""
    rng = np.random.default_rng(t + k)
    lengths = np.asarray([0, 1, k - 1, k, t - 1, t - 1, t - 1], np.int32)
    s = np.round(rng.normal(size=(7, t)), 2).astype(np.float32)
    s[:, ::7] = 0.0
    s[:, 3::11] = -0.0
    s[np.arange(t)[None, :] >= lengths[:, None]] = -np.inf
    kth = np.sort(s[4:], axis=-1)[:, -k]
    own = np.asarray([0.5, -0.0, 0.1, -3.0, kth[0] + 1.0, kth[1] - 1.0, kth[2]], np.float32)
    idx, n_sel, own_in = decoder_lm._select_indices(
        jnp.asarray(s), jnp.asarray(own), jnp.asarray(lengths), k)
    row = np.concatenate([s, np.full((7, 1), -np.inf, np.float32)], axis=1)
    row[np.arange(7), lengths] = own
    want = np.array(decoder_lm._select_mask(jnp.asarray(row), k))
    assert np.asarray(own_in).tolist() == want[np.arange(7), lengths].tolist()
    assert np.asarray(own_in).tolist()[:3] == [True] * 3       # room for all
    assert np.asarray(own_in).tolist()[4:] == [True, False, False]  # wins, loses, ties
    for r in range(7):
        got = np.zeros(t + 1, bool)
        got[np.asarray(idx)[r, :int(n_sel[r])]] = True
        want[r, lengths[r]] = False
        np.testing.assert_array_equal(got, want[r])


def test_decode_selection_counts_the_own_position_among_the_cached():
    """``_select_indices``: three rows over 6 cached columns, k = 4. Row 0
    has 2 positions behind it (room for all: own in, 2 cached count); row 1
    has 6 (a full row) and an own score that beats the 4th best cached one
    (own in, it takes that one's place: 3 count); row 2's own score TIES
    the 4th best and loses to the lower position (own out, 4 count). The
    columns come back best first, the selected ones before the rest."""
    s = np.asarray([[0.3, 0.9, -np.inf, -np.inf, -np.inf, -np.inf],
                    [0.1, 0.8, 0.5, 0.7, 0.2, 0.6],
                    [0.1, 0.8, 0.5, 0.7, 0.2, 0.6]], np.float32)
    idx, n_sel, own_in = decoder_lm._select_indices(
        jnp.asarray(s), jnp.asarray([0.0, 0.55, 0.5], jnp.float32),
        jnp.asarray([2, 6, 6], jnp.int32), 4)
    assert np.asarray(own_in).tolist() == [True, True, False]
    assert np.asarray(n_sel).tolist() == [2, 3, 4]
    assert np.asarray(idx)[0, :2].tolist() == [1, 0]
    assert np.asarray(idx)[1, :3].tolist() == [1, 3, 5]
    assert np.asarray(idx)[2].tolist() == [1, 3, 5, 2]


def test_blocked_attention_under_a_selection_equals_a_masked_softmax():
    """``_causal_blocked`` with ``allowed``: 21 positions in blocks of 8, a
    selection that leaves whole key blocks empty for some queries (so the
    running sums pass through blocks without a real score), against a plain
    softmax over the allowed positions."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 21, 3, d), jnp.float32)
               for i, d in ((0, 10), (1, 10), (2, 6)))
    rng = np.random.default_rng(0)
    allowed = np.tril(rng.random((2, 21, 21)) < 0.25)
    allowed[:, np.arange(21), np.arange(21)] = True
    allowed[0, 20, :16] = False  # nothing of the first two key blocks
    allowed[0, 20, 20] = False
    allowed[0, 20, 17] = True
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
    s = jnp.where(jnp.asarray(allowed)[:, None], s, -jnp.inf)
    whole = np.asarray(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v))
    got = decoder_lm._causal_blocked(q, k, v, 0.3, 8, allowed=jnp.asarray(allowed))
    np.testing.assert_allclose(np.asarray(got), whole, atol=2e-6)


def test_absorbed_step_over_gathered_rows_equals_expanded_attention(both_ways):
    """One owning layer on the same float32 weights: the expanded form under
    the mask over 21 positions against the absorbed form for the last
    position over slabs that hold the entries of the first 20 (layer 1 of
    two, idle rows after them, NaN in the other layer), the view as the
    entry's ``open`` makes it; a sharer handed the owner's selection gives
    on the owner's weights what the owner gives."""
    _cfg, model = both_ways
    cfg = model.cfg

    def view(kind, slabs, at, lengths):
        _sliced, held, look = cfg.mixer(kind).open(
            slabs, lengths[:, None], None, None, False)
        return look(None, held, at)

    bp = {k: v[0] for k, v in model.params_["segments"][0].items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 21, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(21)[None], (2, 21))
    whole, (entries, keys), mask = decoder_lm._sparse_latent_attention(
        cfg, "indexed", bp, x, pos)
    assert entries.shape == (2, 21, 128) and keys.shape == (2, 21, 32)
    assert not np.asarray(entries[..., 32:]).any()  # 16 + 16 values, a zero tail
    assert mask.shape == (2, 21, 21) and np.asarray(mask[:, 20]).sum(-1).tolist() == [8, 8]
    slabs = tuple(jnp.full((2, 2, 40, e.shape[-1]), jnp.nan).at[1].set(0.0)
                  .at[1, :, :20].set(e[:, :20]) for e in (entries, keys))
    at, lengths = jnp.asarray(1, jnp.int32), jnp.asarray([20, 20], jnp.int32)
    step, (entry, key), sel = decoder_lm._sparse_latent_attention(
        cfg, "indexed", bp, x[:, 20:], pos[:, 20:],
        view("indexed", slabs, at, lengths))
    np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(whole[:, 20]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(entry[:, 0]), np.asarray(entries[:, 20]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(key[:, 0]), np.asarray(keys[:, 20]), atol=1e-6)
    idx, n_sel, own_in = (np.asarray(a) for a in sel[:3])
    for r in range(2):
        chosen = set(idx[r, :n_sel[r]].tolist()) | ({20} if own_in[r] else set())
        assert chosen == set(np.flatnonzero(np.asarray(mask[r, 20])).tolist())
    shared, (entry_s,), _sel = decoder_lm._sparse_latent_attention(
        cfg, "shared", bp, x[:, 20:], pos[:, 20:],
        view("shared", (slabs[0],), at, lengths), sel)
    np.testing.assert_array_equal(np.asarray(shared), np.asarray(step))
    np.testing.assert_array_equal(np.asarray(entry_s), np.asarray(entry))
    assert np.abs(np.asarray(whole[:, 20] - x[:, 20])).max() > 1e-3  # attention did add something


# -- the cache ----------------------------------------------------------------
def test_cache_plan_has_a_key_slab_where_a_layer_owns_the_indexer(base):
    """Three segments: an owner (latent rows + indexer keys), two sharers
    (latent rows alone), an owner. A sharing layer has no indexer leaf."""
    _cfg, model = base
    cfg = model.cfg
    assert cfg.segments() == [("indexed", "dense", 1), ("shared", "experts", 2),
                              ("indexed", "experts", 1)]
    plan = cfg.cache_plan(n_slots=3, max_length=64)
    assert [(p["kind"], p["layers"], p["ring"], p["values"], p["row"], p.get("index"))
            for p in plan] == [("indexed", 1, False, 64, 128, 32),
                               ("shared", 2, False, 32, 128, None),
                               ("indexed", 1, False, 64, 128, 32)]
    assert plan[0]["slabs"] == [(1, 3, 64, 128), (1, 3, 64, 32)]
    assert plan[1]["slabs"] == [(2, 3, 64, 128)]
    assert plan[0]["bytes"] == 3 * 64 * (128 + 32) * 4  # float32 here
    caches = decoder_lm.init_cache(cfg, 3, 64)
    assert [tuple(a.shape for a in seg) for seg in caches] == [
        ((1, 3, 64, 128), (1, 3, 64, 32)), ((2, 3, 64, 128),),
        ((1, 3, 64, 128), (1, 3, 64, 32))]
    indexer = {"Iq", "Ik", "norm_ik", "bias_ik", "Iw"}
    leaves = [set(seg) for seg in model.params_["segments"]]
    assert indexer <= leaves[0] and indexer <= leaves[2] and not indexer & leaves[1]
    assert model.params_["segments"][0]["Iq"].shape == (1, 24, 4, 32)


def test_published_cut_by_hand():
    """The cell's configuration, by hand (ISSUE 40's arithmetic): 3,881.5 M
    parameters; a cached position keeps 5 x 576 values of latent + 2 x 128
    of indexer keys = 6,272 B of mathematics, stored in rows of 640 (five
    whole lane tiles) = 6,912 B; 32 slots x 14,336 = 3.17 GB."""
    with open(os.path.join(BENCH, "configs", "glm-5.2-ep16.json")) as f:
        pub = json.load(f)
    cfg = decoder_lm.DecoderConfig(**fam.program_config(pub))
    assert cfg.segments() == [("indexed", "dense", 1), ("shared", "experts", 3),
                              ("indexed", "experts", 1)]
    plan = cfg.cache_plan(32, 14336)
    assert [(p["layers"], p["values"], p["slabs"]) for p in plan] == [
        (1, 704, [(1, 32, 14336, 640), (1, 32, 14336, 128)]),
        (3, 576, [(3, 32, 14336, 640)]),
        (1, 704, [(1, 32, 14336, 640), (1, 32, 14336, 128)])]
    assert sum(p["layers"] * p["values"] for p in plan) * 2 == 6272
    assert sum(p["bytes"] for p in plan) == 32 * 14336 * 6912 == 3_170_893_824
    shapes = decoder_lm.segment_shapes(cfg, "indexed", "experts")
    assert shapes["Wqb"][0] == (2048, 64, 256) and shapes["Wkva"][0] == (6144, 576)
    assert shapes["Wuk"][0] == (512, 64, 192) and shapes["Wuv"][0] == (512, 64, 256)
    assert shapes["Iq"][0] == (2048, 32, 128) and shapes["Ik"][0] == (6144, 128)
    assert shapes["Iw"][0] == (6144, 32)
    assert shapes["Eg"][0] == (16, 6144, 2048) and shapes["Sg"][0] == (6144, 2048)
    assert shapes["Wr"][0] == (6144, 256) and shapes["br"][0] == (256,)
    assert "Iq" not in decoder_lm.segment_shapes(cfg, "shared", "experts")
    assert cfg.routing == {"scoring": "sigmoid", "scale": 2.5}
    params = jax.eval_shape(lambda: decoder_lm.init_params(cfg))
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert 3_881_000_000 < count < 3_882_000_000
    # every number of the catalog row under the same key but the four cuts
    assert pub["first_k_dense_replace"] == 3 and len(pub["indexer_types"]) == 78
    assert [pub["indexer_types"][i] for i in pub["deployment"]["layers"]] == [
        "full", "shared", "shared", "shared", "full"]
    assert [pub["mlp_layer_types"][i] for i in pub["deployment"]["layers"]] == [
        "dense", "sparse", "sparse", "sparse", "sparse"]


def test_idle_rows_keep_their_cache_and_stay_out_of_the_active_rows(both_ways):
    """A decode step over three rows of which the middle one is idle: what
    the idle row could read of its slabs (its first ``pos`` rows) is bit for
    bit as it was, and the active rows' logits are bit for bit what they are
    when the idle row holds another token at another position."""
    cfg, model = both_ways
    dcfg = model.cfg
    caches = decoder_lm.init_cache(dcfg, 3, 32)
    prefill = jax.jit(lambda c, i, n, s: decoder_lm.prefill_slot(dcfg, model.params_, c, i, n, s))
    for slot, n in enumerate((12, 9, 15)):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :n] = ids_of(cfg, n, seed=slot)
        _logits, caches = prefill(caches, jnp.asarray(padded), jnp.asarray(n), jnp.asarray(slot))
    step = jax.jit(lambda c, t, p, a: decoder_lm.decode_step(dcfg, model.params_, c, t, p, a))
    active = jnp.asarray([True, False, True])
    toks, pos = jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([12, 9, 15], jnp.int32)
    logits, after, _ = step(caches, toks, pos, active)
    for seg_before, seg_after in zip(caches, after):
        for before, now in zip(seg_before, seg_after):
            np.testing.assert_array_equal(np.asarray(now[:, 1, :9]), np.asarray(before[:, 1, :9]))
            assert (np.asarray(now[:, 0, 12]) != np.asarray(before[:, 0, 12])).any()
    other, _, _ = step(caches, jnp.asarray([5, 99, 7], jnp.int32),
                       jnp.asarray([12, 4, 15], jnp.int32), active)
    np.testing.assert_array_equal(np.asarray(logits)[[0, 2]], np.asarray(other)[[0, 2]])


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
    bp = {"Wr": w["router.w"], "br": w["router.bias"], "Eg": w["experts.gate"],
          "Eu": w["experts.up"], "Ed": w["experts.down"], "Sg": w["shared.gate"],
          "Su": w["shared.up"], "Sd": w["shared.down"]}
    return w, bp


def share(cfg, offset, count):
    out = copy.deepcopy(cfg)
    out["n_routed_experts"], out["deployment"]["experts_offset"] = count, offset
    return out


def tokens(cfg, n=24):
    return jax.random.normal(jax.random.PRNGKey(3), (n, cfg["hidden_size"]), jnp.float32)


def test_scaled_sigmoid_routing_by_hand():
    """Three of eight experts a token by sigmoid score + bias; the bias
    moves expert 6 into the choice and stays out of the weights; the
    weights are the chosen scores renormalised to one and THEN times 2.5;
    scale 1 is the rule as it was."""
    z = np.asarray([[2.0, -1.0, 0.5, 1.5, -3.0, 0.0, 0.4, -2.0]], np.float32)
    bias = np.zeros((8,), np.float32)
    bias[6] = 0.2
    chosen, w = sigmoid_topk_route(jnp.asarray(z), jnp.asarray(bias), 3, scale=2.5)
    assert np.asarray(chosen).tolist() == [[0, 3, 6]]
    s = 1 / (1 + np.exp(-z[0, [0, 3, 6]]))
    np.testing.assert_allclose(np.asarray(w)[0], 2.5 * s / s.sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(), 2.5, rtol=1e-6)
    _, plain = sigmoid_topk_route(jnp.asarray(z), jnp.asarray(bias), 3)
    np.testing.assert_allclose(np.asarray(plain)[0], s / s.sum(), rtol=1e-6)
    # the reference's rule gives the same experts and weights
    cfg = share(tiny(), 0, 8)
    x = tokens(cfg)
    w_ref, bp = expert_layer(cfg)
    weights = np.asarray(ref.route(cfg, w_ref, x))
    chosen, w = build(cfg).cfg.route()(x @ bp["Wr"], bp["br"], 3)
    for t in range(x.shape[0]):
        assert sorted(np.nonzero(weights[t])[0]) == sorted(np.asarray(chosen[t]).tolist())
        np.testing.assert_allclose(weights[t, np.asarray(chosen[t])], np.asarray(w[t]), rtol=1e-5)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test: a layer of 8 experts held 2 at a time (the sixteen
    shares of the deployment, four here). What the four holders compute of
    the routed experts under the scaled sigmoid rule, each from the
    generator's weights for ITS experts, plus the shared expert counted
    ONCE, adds up to the uncut reference's output for the layer: the
    router, its choice and its weights are over all 8 in every share, and
    every holder computes the same shared expert."""
    cfg = tiny()
    uncut = share(cfg, 0, 8)
    x = tokens(cfg)
    w_all, bp_all = expert_layer(uncut)
    want = np.asarray(ref.experts(uncut, w_all, x, "float32"))
    route = build(cfg).cfg.route()
    total = np.zeros_like(want)
    total_ref = np.zeros_like(want)
    pairs = 0
    for offset in (0, 2, 4, 6):
        held = share(cfg, offset, 2)
        w, bp = expert_layer(held)
        np.testing.assert_array_equal(np.asarray(w["experts.gate"]),
                                      np.asarray(w_all["experts.gate"][offset:offset + 2]))
        y, n, _hit = moe_dropless_ffn(x, x, bp, 3, (offset, 2), route=route)
        with_shared, _, _ = moe_dropless_ffn(x, x, bp, 3, (offset, 2), route=route, shared=True)
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


# -- the engine ---------------------------------------------------------------
@pytest.fixture(scope="module")
def engine(base):
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    gen = GenerationEngine(base[1], n_slots=3, max_length=96, prefill_buckets=[8, 16, 32])
    gen.warmup()
    yield gen
    gen.shutdown(drain=False)


def test_engine_serves_what_the_model_generates_alone(base, engine):
    cfg, model = base
    traced = dict(engine.trace_counts)
    prompts = [ids_of(cfg, n, seed=n) for n in (5, 9, 20, 31, 12)]
    before = engine.metrics.snapshot()
    requests = [engine.submit(p, max_new=24) for p in prompts]
    for prompt, req in zip(prompts, requests):
        served = np.asarray(req.result(timeout=120))
        alone = model.generate_cached(prompt, max_new=24)
        np.testing.assert_array_equal(served[-24:], alone[-24:])
    assert engine.trace_counts == traced  # no program traced after warm-up
    assert engine.metrics.snapshot()["decode_steps_ahead"] > before["decode_steps_ahead"]


def test_engine_counts_scored_and_selected_positions_by_hand(base, engine):
    """One request alone in the engine: a prompt of 6 and 10 tokens. The
    first comes from the prefill; decode step j = 1..9 has 6 + j - 1
    positions of the slot behind it, all of which the indexer scores: 6 + 7
    + ... + 14 = 90; the attention reads at most 8 of them: 6 + 7 + 8 x 7 =
    69."""
    cfg, _model = base
    before = engine.metrics.snapshot()
    engine.submit(ids_of(cfg, 6, seed=77), max_new=10).result(timeout=120)
    after = engine.metrics.snapshot()
    assert after["decode_steps"] - before["decode_steps"] == 9
    assert after["index_positions_scored"] - before["index_positions_scored"] == sum(range(6, 15)) == 90
    assert after["sparse_positions_read"] - before["sparse_positions_read"] == 6 + 7 + 8 * 7 == 69
    assert after["latent_positions_read"] - before["latent_positions_read"] == 90
    assert after["moe_pairs_local"] > before["moe_pairs_local"]


def test_memory_report_and_describe_follow_the_plan(base, engine):
    from deeplearning4j_tpu.serving.generate import generation_memory_report

    _cfg, model = base
    report = generation_memory_report(model, n_slots=3, max_length=96)
    # four layers of latent rows of 128 and two of indexer keys of 32, float32 here
    assert report["cache_bytes"] == 3 * 96 * (4 * 128 + 2 * 32) * 4
    assert [(p["kind"], p["layers"], p["columns"], p["values"], p["row"], p.get("index"))
            for p in report["cache_plan"]] == [("indexed", 1, 96, 64, 128, 32),
                                               ("shared", 2, 96, 32, 128, None),
                                               ("indexed", 1, 96, 64, 128, 32)]
    assert report["param_bytes"] == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(model.params_))
    described = engine.describe()
    assert described["backend"] == "decoder" and described["spec_decode_k"] == 1
    assert described["memory"]["cache_plan"] == report["cache_plan"]
    assert engine.backend.cache_bytes == report["cache_bytes"]
    assert engine.backend.index_topk == 8


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


def test_selection_counters_reach_the_metrics_endpoint(engine):
    text = engine.metrics.registry.prometheus_text()
    assert "generation_index_positions_scored_total" in text
    assert "generation_sparse_positions_read_total" in text
    snapshot = engine.metrics.snapshot()
    assert "index_positions_scored" in snapshot and "sparse_positions_read" in snapshot


def test_a_model_without_an_indexer_counts_no_selection():
    """The two counters stay at zero where no layer selects (a dense latent
    model): ``record_selection`` is not reached."""
    from deeplearning4j_tpu.serving.generate import GenerationEngine
    from tests.decoder_kinds import decoder_lm as kind_model

    model = kind_model("latent")
    gen = GenerationEngine(model, n_slots=2, max_length=64, prefill_buckets=[8, 16])
    try:
        assert gen.backend.index_topk == 0
        prompt = np.random.default_rng(3).integers(0, model.cfg.vocab_size, (6,))
        gen.submit(prompt, max_new=5).result(timeout=120)
        snap = gen.metrics.snapshot()
        assert snap["latent_positions_read"] > 0
        assert snap["index_positions_scored"] == snap["sparse_positions_read"] == 0
    finally:
        gen.shutdown(drain=False)

"""The kernel for a latent layer's decode-step attention over a selection
(``nn/ops/sparse_latent_decode.py``) under the Pallas interpreter against
the gathered-rows branch of the SAME function,
``decoder_lm._sparse_latent_attention``: one layer's decode step over
position-major slabs, handed over once as ("rows", ...) and once as the view
``_IndexedLatent.open`` makes with the kernel admitted. Tiles of 8 rows on
slots of 40 and a selection of 12, so the lengths cross every edge a tile
and the selection have; float32 (equal to summation order) and bfloat16
(equal to its rounding)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import decoder_lm
from deeplearning4j_tpu.nn.ops import sparse_latent_decode as sld
from deeplearning4j_tpu.nn.ops.registry import ENV_FLAGS, default_kernel_registry

TILE, T_C, LAYERS, LAYER, K = 8, 40, 3, 1, 12
TOL = {"float32": 2e-6, "bfloat16": 3e-2}
LENGTHS = {"inactive": 0, "one": 1, "tile-1": TILE - 1, "tile": TILE,
           "tile+1": TILE + 1, "k-1": K - 1, "k": K, "k+1": K + 1,
           "whole-slot": T_C}
MIXED = [0, 1, TILE - 1, TILE, TILE + 1, K, K + 1, T_C, 0, 3 * TILE + 2]


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """The registry's mode ``interpret`` and a tile of the tiny size; the
    verdicts of this file's keys do not outlive a test."""
    monkeypatch.setenv(ENV_FLAGS[sld.NAME], "interpret")
    monkeypatch.setattr(sld, "TILE", TILE)
    default_kernel_registry().reset(sld.NAME)
    yield
    default_kernel_registry().reset(sld.NAME)


def config(dtype, layers=(("indexed", "dense"),), seed=3, **more):
    """4 heads of 16 + 16 over a 16 + 16-wide latent entry in rows of 128,
    4 indexer heads of 32 that keep ``K`` positions; a kind that owns the
    indexer and one that shares a selection."""
    kind = {"rope_theta": 100.0, "latent": {"q_rank": 24, "kv_rank": 16}}
    index = {"heads": 4, "head_dim": 32, "topk": K}
    return decoder_lm.DecoderConfig(
        vocab_size=64, d_model=32, n_heads=4, head_dim=32, v_head_dim=12,
        rotary_dim=16,
        attn_kinds={"indexed": {**kind, "index": {**index, "own": True}},
                    "shared": {**kind, "index": {**index, "own": False}}},
        layers=list(layers), dense_width=64, max_length=T_C,
        param_dtype=dtype, seed=seed, **more)


def layer_of(dtype):
    cfg = config(dtype)
    seg = decoder_lm.init_params(cfg)["segments"][0]
    return cfg, {k: v[0] for k, v in seg.items()}


def slabs_of(cfg, lengths, dead, key=5):
    """(the step's input, the slabs with zeros past every length, the same
    with ``dead`` there and in every row of an idle slot's): latent rows
    and indexer keys, other layers' entries around the one read."""
    dt = cfg.dtype
    b = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(key), 3)
    x = jax.random.normal(keys[0], (b, 1, cfg.d_model), jnp.float32).astype(dt)
    live = (jnp.arange(T_C)[None, :, None]
            < jnp.asarray(lengths, jnp.int32)[:, None, None])

    def slab(k, width, fill):
        held = jax.random.normal(k, (LAYERS, b, T_C, width), jnp.float32).astype(dt)
        return held.at[LAYER].set(jnp.where(live, held[LAYER], jnp.asarray(fill, dt)))

    return x, *(tuple(slab(k, w, fill) for k, w in zip(keys[1:], (128, 32)))
                for fill in (0.0, dead))


def views(cfg, kind, slabs, lengths, active):
    """("rows", ...) as the declined entry hands it over, and the kernel's
    view as ``_IndexedLatent.open`` makes it for rows of which ``active``
    stream."""
    pos = jnp.asarray(lengths, jnp.int32)
    at = jnp.asarray(LAYER, jnp.int32)
    _sliced, held, look = cfg.mixer(kind).open(
        slabs, pos[:, None], None, jnp.asarray(active)[:, None], False)
    view = look(None, held, at)
    assert view[0] == "kernel"
    return ("rows", slabs, at, pos), view


def own_step_both_ways(dtype, lengths, dead=0.0):
    """An OWNER's decode step (score, select, attend) both ways; the rows
    of length 0 are idle to the kernel."""
    cfg, bp = layer_of(dtype)
    x, clean, dirty = slabs_of(cfg, lengths, dead)
    pos = jnp.asarray(lengths, jnp.int32)[:, None]
    active = [n > 0 for n in lengths]
    rows, _ = views(cfg, "indexed", clean, lengths, active)
    _, kernel = views(cfg, "indexed", dirty, lengths, active)
    want, made_w, sel_w = decoder_lm._sparse_latent_attention(
        cfg, "indexed", bp, x, pos, rows)
    got, made_g, sel_g = decoder_lm._sparse_latent_attention(
        cfg, "indexed", bp, x, pos, kernel)
    for g, w in zip(made_g, made_w):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    return (np.asarray(want[:, 0], np.float32), np.asarray(got[:, 0], np.float32),
            np.asarray(x[:, 0], np.float32), sel_w, sel_g)


def chosen_of(sel):
    """The selection's cached positions a row, as sets."""
    idx, n_sel = np.asarray(sel[0]), np.asarray(sel[1])
    return [set(idx[r, :n_sel[r]].tolist()) for r in range(len(n_sel))]


def biased_of(bias):
    return [set(np.flatnonzero(np.asarray(row[0]) == 0).tolist()) for row in bias]


# -- the layer both ways --------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", list(LENGTHS.values()), ids=list(LENGTHS))
def test_kernel_equals_gathered_rows_at_each_edge(dtype, length):
    want, got, x, sel_w, sel_g = own_step_both_ways(dtype, [length, length])
    assert chosen_of(sel_g) == chosen_of(sel_w) == biased_of(sel_g[3])
    np.testing.assert_array_equal(np.asarray(sel_g[2]), np.asarray(sel_w[2]))
    if length:
        np.testing.assert_allclose(got, want, atol=TOL[dtype])
    assert np.abs(got - x).max() > 1e-3  # attention did add something
    snap = default_kernel_registry().snapshot()[sld.NAME]
    assert [v["enabled"] for v in snap.values()] == [True]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_equals_gathered_rows_on_a_batch_of_mixed_lengths(dtype):
    """Idle rows among live ones: the live rows agree, and both rules of
    the own position are in the batch."""
    want, got, _x, sel_w, sel_g = own_step_both_ways(dtype, MIXED)
    live = np.asarray(MIXED) > 0
    np.testing.assert_allclose(got[live], want[live], atol=TOL[dtype])
    assert chosen_of(sel_g) == biased_of(sel_g[3])
    assert [w for w, n in zip(chosen_of(sel_w), MIXED) if n] == [
        g for g, n in zip(chosen_of(sel_g), MIXED) if n]


@pytest.mark.parametrize("dead", [float("nan"), 3e38], ids=["nan", "huge"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_nothing_past_a_length_or_in_an_idle_slot_reaches_the_result(dtype, dead):
    """Rows at and past a length, and every row of an idle slot, holding NaN
    or huge values change nothing: the same bits as with zeros there."""
    _want, clean, _x, _sw, _sg = own_step_both_ways(dtype, MIXED)
    want, got, _x, _sw, _sg = own_step_both_ways(dtype, MIXED, dead)
    np.testing.assert_array_equal(got, clean)
    live = np.asarray(MIXED) > 0
    np.testing.assert_allclose(got[live], want[live], atol=TOL[dtype])


@pytest.mark.parametrize("own_in", [True, False], ids=["own-in", "own-out"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_sharer_attends_by_the_selection_it_is_handed(dtype, own_in):
    """A sharing layer and a selection made by hand: ``n_sel`` under K (the
    columns past it are whatever a sort left there), the own position in or
    out, positions in both tiles' interiors and on their edges."""
    cfg, bp = layer_of(dtype)
    bp = {k: v for k, v in bp.items() if k not in
          ("Iq", "Ik", "norm_ik", "bias_ik", "Iw")}
    lengths = [T_C, 3 * TILE + 2, TILE + 1]
    x, clean, _dirty = slabs_of(cfg, lengths, 0.0)
    rows, kernel = views(cfg, "shared", clean[:1], lengths, [True] * 3)
    picks = [[0, 7, 8, 15, 16, 23, 39], [25, 1, 8], [8]]
    idx = np.zeros((3, K), np.int32)
    for r, p in enumerate(picks):
        idx[r, :len(p)] = p
    n_sel = np.asarray([len(p) for p in picks], np.int32)
    bias = np.full((3, 1, T_C), sld._NEG, np.float32)
    for r, p in enumerate(picks):
        bias[r, 0, p] = 0.0
    sel = (jnp.asarray(idx), jnp.asarray(n_sel), jnp.full((3,), own_in))
    pos = jnp.asarray(lengths, jnp.int32)[:, None]
    want, (entry_w,), _ = decoder_lm._sparse_latent_attention(
        cfg, "shared", bp, x, pos, rows, sel)
    got, (entry_g,), handed = decoder_lm._sparse_latent_attention(
        cfg, "shared", bp, x, pos, kernel, (*sel, jnp.asarray(bias)))
    assert len(handed) == 4  # the selection goes on as it came
    np.testing.assert_array_equal(np.asarray(entry_g, np.float32),
                                  np.asarray(entry_w, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=TOL[dtype])
    # the own position moves the result where it is in
    other, _, _ = decoder_lm._sparse_latent_attention(
        cfg, "shared", bp, x, pos, kernel,
        (sel[0], sel[1], ~sel[2], jnp.asarray(bias)))
    assert np.abs(np.asarray(other, np.float32) - np.asarray(got, np.float32)).max() > 1e-3


def test_a_sharer_on_the_owners_weights_gives_what_the_owner_gives():
    cfg, bp = layer_of("float32")
    lengths = [T_C, K + 1, 3]
    x, clean, _dirty = slabs_of(cfg, lengths, 0.0)
    pos = jnp.asarray(lengths, jnp.int32)[:, None]
    _, own_view = views(cfg, "indexed", clean, lengths, [True] * 3)
    _, share_view = views(cfg, "shared", clean[:1], lengths, [True] * 3)
    owner, (entry, _key), sel = decoder_lm._sparse_latent_attention(
        cfg, "indexed", bp, x, pos, own_view)
    sharer, (entry_s,), _sel = decoder_lm._sparse_latent_attention(
        cfg, "shared", bp, x, pos, share_view, sel)
    np.testing.assert_array_equal(np.asarray(sharer), np.asarray(owner))
    np.testing.assert_array_equal(np.asarray(entry_s), np.asarray(entry))


def test_an_idle_slot_returns_its_own_latent():
    """Length 0: the softmax has the step's own entry alone, so the core's
    output is that entry's latent, bit for bit, whatever the slab holds."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((2, 128)), jnp.float32)
    slab = jnp.full((1, 2, T_C, 128), jnp.nan, jnp.float32)
    lengths = jnp.zeros((2,), jnp.int32)
    out = sld.sparse_latent_decode(
        q, new, slab, jnp.zeros((), jnp.int32), lengths,
        jnp.full((2, 1, T_C), sld._NEG, jnp.float32), jnp.ones((2,), bool),
        sld.live_walk(lengths, T_C, TILE), scale=0.3, kv_rank=16, tile=TILE,
        interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.broadcast_to(np.asarray(new)[:, None, :16], (2, 4, 16)))


# -- the selection as a bias ----------------------------------------------------
def select(scores, own, lengths, k):
    scores = jnp.asarray(scores, jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    held = jnp.arange(scores.shape[1])[None, :] < lengths[:, None]
    return decoder_lm._select_indices(
        jnp.where(held, scores, -jnp.inf), jnp.asarray(own, jnp.float32),
        lengths, k, as_bias=True)


def test_the_bias_is_the_sorts_first_n_sel_on_rows_with_ties():
    """k = 4 over 8 cached columns. Row 0: three ties at the k-th value, the
    two from the left fill the count. Row 1: the same, and the own position
    beats the k-th outright and takes its place (one tie fewer). Row 2: the
    own position equals the k-th value and stays out (the lower position
    wins a tie). Row 3: every score equal. Row 4: -0.0 and +0.0 are one
    value, in the scores and in the own score."""
    scores = [[5, 1, 3, 1, 9, 1, 0, 0],
              [5, 1, 3, 1, 9, 1, 0, 0],
              [5, 1, 3, 1, 9, 1, 0, 0],
              [2, 2, 2, 2, 2, 2, 2, 2],
              [-0.0, 0.0, -1, 0.0, -0.0, -2, 7, -3]]
    sel = select(scores, [0.5, 2.0, 1.0, 2.0, -0.0], [8] * 5, 4)
    assert chosen_of(sel) == biased_of(sel[3]) == [
        {4, 0, 2, 1}, {4, 0, 2}, {4, 0, 2, 1}, {0, 1, 2, 3}, {6, 0, 1, 3}]
    assert np.asarray(sel[2]).tolist() == [False, True, False, False, False]


def test_the_bias_keeps_out_what_lies_past_a_length():
    """Lengths under k: every cached position is in and nothing else, the
    own position with them; length 0: nothing; length k: the own position
    has to beat the k-th."""
    rng = np.random.default_rng(1)
    scores = rng.standard_normal((5, 8)).astype(np.float32)
    lengths = [0, 1, 3, 4, 8]
    sel = select(scores, [-9.0] * 5, lengths, 4)
    assert chosen_of(sel) == biased_of(sel[3])
    assert biased_of(sel[3])[:4] == [set(), {0}, {0, 1, 2}, {0, 1, 2, 3}]
    assert len(biased_of(sel[3])[4]) == 4
    assert np.asarray(sel[2]).tolist() == [True, True, True, False, False]


@pytest.mark.parametrize("seed", range(4))
def test_the_bias_is_the_sorts_first_n_sel_on_drawn_rows(seed):
    """Scores drawn from few values (ties everywhere), lengths on both sides
    of k, own scores among the values."""
    rng = np.random.default_rng(seed)
    scores = rng.integers(-2, 3, (16, T_C)).astype(np.float32)
    scores[scores == 0] = rng.choice([0.0, -0.0], (scores == 0).sum())
    sel = select(scores, rng.integers(-2, 3, 16), rng.integers(0, T_C + 1, 16), K)
    assert chosen_of(sel) == biased_of(sel[3])


# -- the whole program ----------------------------------------------------------
def test_decode_step_through_the_kernel_equals_the_gathered_program(monkeypatch):
    """The whole decode program both ways on one cache: an owner, a segment
    of two sharers (one scan, the layer's index traced, the owner's
    selection handed across the boundary), an owner. Logits equal to
    summation order, the written caches equal; a row that is not active
    reads nothing (NaN all over its slabs changes no logit)."""
    cfg = config("float32", layers=[
        ("indexed", "dense"), ("shared", "experts"), ("shared", "experts"),
        ("indexed", "experts")], seed=11, expert_width=16, n_experts=4,
        top_k=2, experts_held=(0, 4),
        routing={"scoring": "sigmoid", "scale": 2.5}, shared_width=16)
    params = decoder_lm.init_params(cfg)
    key = jax.random.PRNGKey(2)
    caches = [tuple(jax.random.normal(key, s.shape, jnp.float32) for s in seg)
              for seg in decoder_lm.init_cache(cfg, 4, T_C)]
    ids = jnp.asarray([3, 9, 27, 50], jnp.int32)
    pos = jnp.asarray([5, 17, 33, 21], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    got = decoder_lm.decode_step(cfg, params, caches, ids, pos, active)
    assert set(default_kernel_registry().snapshot()[sld.NAME]) == {
        repr((4, 128, T_C, K, TILE, "float32"))}
    stale = [tuple(c.at[:, 3].set(jnp.nan) for c in seg) for seg in caches]
    unread = decoder_lm.decode_step(cfg, params, stale, ids, pos, active)
    np.testing.assert_array_equal(np.asarray(unread[0])[:3], np.asarray(got[0])[:3])
    monkeypatch.setenv(ENV_FLAGS[sld.NAME], "0")
    default_kernel_registry().reset(sld.NAME)
    want = decoder_lm.decode_step(cfg, params, caches, ids, pos, active)
    np.testing.assert_allclose(np.asarray(got[0])[:3], np.asarray(want[0])[:3],
                               atol=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g)[:, :3], np.asarray(w)[:, :3],
                                   atol=1e-5)
    assert (int(got[2][0]), int(got[2][1])) == (int(want[2][0]), int(want[2][1]))


# -- what it declines -----------------------------------------------------------
@pytest.mark.parametrize("mode", ["0", "1"], ids=["off", "auto-on-the-cpu"])
def test_modes_that_keep_the_gathered_rows(monkeypatch, mode):
    """The kill switch, and auto mode off the TPU: ``open`` hands the cache
    over as ("rows", ...), the selection has three parts, one fallback
    recorded."""
    monkeypatch.setenv(ENV_FLAGS[sld.NAME], mode)
    cfg, _bp = layer_of("float32")
    mixer = cfg.mixer("indexed")
    slabs = tuple(decoder_lm.init_cache(cfg, 2, T_C)[0])
    assert mixer.kernel(slabs) is None
    pos = jnp.asarray([[5], [9]], jnp.int32)
    _sliced, held, look = mixer.open(slabs, pos, None, None, False)
    assert look(None, held, 0)[0] == "rows"
    assert len(mixer.first(None, 2, 1, slabs)) == 3
    (verdict,) = default_kernel_registry().snapshot()[sld.NAME].values()
    assert not verdict["enabled"]


def test_shapes_and_meshes_it_has_no_form_for_are_declined_unrecorded():
    """Slots of more than ``MAX_SPAN`` selections, a slot length the tile
    does not divide, a mesh in sight: the gathered rows, and not a word to
    the registry. A prefill bucket (Tq > 1 has no cache to open) and the
    forward never ask."""
    assert sld.sparse_latent_decode_impl(4, 128, 16 * TILE, 2 * TILE - 1,
                                         jnp.float32, 16) is None
    assert sld.sparse_latent_decode_impl(4, 128, 2 * TILE + 1, K,
                                         jnp.float32, 16) is None
    mesh = jax.make_mesh((2,), ("x",))
    with jax.set_mesh(mesh):
        assert sld.sparse_latent_decode_impl(4, 128, T_C, K, jnp.float32,
                                             16) is None
    assert sld.NAME not in default_kernel_registry().snapshot()
    assert sld.sparse_latent_decode_impl(4, 128, T_C, K, jnp.float32,
                                         16) is not None


def test_a_selection_starts_with_a_bias_only_where_the_kernel_serves():
    cfg, _bp = layer_of("float32")
    mixer = cfg.mixer("indexed")
    slabs = tuple(decoder_lm.init_cache(cfg, 2, T_C)[0])
    first = mixer.first(None, 2, 1, slabs)
    assert [a.shape for a in first] == [(2, K), (2,), (2,), (2, 1, T_C)]
    assert mixer.first(None, 2, 16, None) is not None  # a prefill's mask
    assert cfg.mixer("shared").first("handed", 2, 1, slabs[:1]) == "handed"


def test_chip_smoke_asks_for_the_kernel_and_fails_where_it_fell_back(monkeypatch):
    """``chip_smoke.py``'s ``kernels`` phase resolves the kernel at the glm
    cell's key: enabled, it passes and says so; a fallback on the TPU
    platform raises with the kernel's name."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert chip_smoke.FULL["sparse_core"] == dict(
        heads=64, width=640, t_c=14336, topk=2048, dtype="bfloat16",
        kv_rank=512)

    for other in ("latent_decode_core", "ssm_decode_step", "kv_column_write",
                  "grouped_experts"):
        monkeypatch.setenv(ENV_FLAGS[other], "interpret")
    default_kernel_registry().reset()
    report = chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    (verdict,) = report["registry"][sld.NAME].values()
    assert verdict["enabled"] and report["refused"] == []
    monkeypatch.setenv(ENV_FLAGS[sld.NAME], "1")
    default_kernel_registry().reset()
    with pytest.raises(AssertionError, match=sld.NAME):
        chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    default_kernel_registry().reset()

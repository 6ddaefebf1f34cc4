"""The length-aware latent decode kernel (``nn/ops/latent_decode.py``)
under the Pallas interpreter against the einsum path of the SAME function,
``decoder_lm._latent_attention``: one latent layer's decode step over a
slab, handed over once as (slab, position map) and once as (the segment's
slabs, layer, lengths). Tiles of 8 columns on slots of 40, so the lengths
cross every edge a tile has; float32 (equal to summation order) and
bfloat16 (equal to its rounding)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import decoder_lm
from deeplearning4j_tpu.nn.ops import latent_decode
from deeplearning4j_tpu.nn.ops.registry import ENV_FLAGS, default_kernel_registry

TILE, T_C, LAYERS, LAYER = 8, 40, 3, 1
TOL = {"float32": 2e-6, "bfloat16": 3e-2}
LENGTHS = {"inactive": 0, "one": 1, "tile-1": TILE - 1, "tile": TILE,
           "tile+1": TILE + 1, "whole-slot": T_C}
MIXED = [0, 1, TILE - 1, TILE, TILE + 1, T_C, 0, 3 * TILE + 2]


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """The registry's mode ``interpret`` and a tile of the tiny size; the
    verdicts of this file's keys do not outlive a test."""
    monkeypatch.setenv(ENV_FLAGS[latent_decode.NAME], "interpret")
    monkeypatch.setattr(latent_decode, "TILE", TILE)
    default_kernel_registry().reset(latent_decode.NAME)
    yield
    default_kernel_registry().reset(latent_decode.NAME)


def layer_of(dtype, seed=3):
    """A latent layer's configuration and one layer's leaves, 4 heads of
    16 + 16 over a 16 + 16-wide latent entry, seeded."""
    cfg = decoder_lm.DecoderConfig(
        vocab_size=64, d_model=32, n_heads=4, head_dim=32, v_head_dim=12,
        rotary_dim=16,
        attn_kinds={"latent": {"rope_theta": 100.0,
                               "latent": {"q_rank": 24, "kv_rank": 16}}},
        layers=[("latent", "dense")], dense_width=64, max_length=T_C,
        param_dtype=dtype, seed=seed)
    seg = decoder_lm.init_params(cfg)["segments"][0]
    return cfg, {k: v[0] for k, v in seg.items()}


def step_both_ways(dtype, lengths, dead=0.0):
    """(einsum path, kernel path): the layer's output for one new position
    a row behind ``lengths`` cached ones; the kernel's slab holds ``dead``
    in every column at or past a row's length (the einsums' holds zeros:
    0 x NaN is NaN to them) and other layers' slabs around the one read."""
    cfg, bp = layer_of(dtype)
    dt = cfg.dtype
    b = len(lengths)
    pos = jnp.asarray(lengths, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(keys[0], (b, 1, cfg.d_model), jnp.float32).astype(dt)
    held = jax.random.normal(keys[1], (b, 32, T_C), jnp.float32).astype(dt)
    live = jnp.arange(T_C)[None, None, :] < pos[:, None, None]
    c_pos = decoder_lm.cache_positions(
        cfg, pos, decoder_lm.init_cache(cfg, b, T_C))["latent"]
    want, entry_w = decoder_lm._latent_attention(
        cfg, "latent", bp, x, pos[:, None],
        ("columns", jnp.where(live, held, 0), c_pos))
    slabs = jax.random.normal(keys[2], (LAYERS, b, 32, T_C), jnp.float32).astype(dt)
    slabs = slabs.at[LAYER].set(jnp.where(live, held, jnp.asarray(dead, dt)))
    got, entry_g = decoder_lm._latent_attention(
        cfg, "latent", bp, x, pos[:, None],
        ("kernel", slabs, jnp.asarray(LAYER, jnp.int32), pos))
    np.testing.assert_array_equal(np.asarray(entry_g, np.float32),
                                  np.asarray(entry_w, np.float32))
    return (np.asarray(want[:, 0], np.float32), np.asarray(got[:, 0], np.float32),
            np.asarray(x[:, 0], np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", list(LENGTHS.values()), ids=list(LENGTHS))
def test_kernel_equals_einsums_at_each_tile_edge(dtype, length):
    want, got, x = step_both_ways(dtype, [length, length])
    np.testing.assert_allclose(got, want, atol=TOL[dtype])
    assert np.abs(got - x).max() > 1e-3  # attention did add something
    snap = default_kernel_registry().snapshot()[latent_decode.NAME]
    assert [v["enabled"] for v in snap.values()] == [True]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_equals_einsums_on_a_batch_of_mixed_lengths(dtype):
    want, got, _x = step_both_ways(dtype, MIXED)
    np.testing.assert_allclose(got, want, atol=TOL[dtype])


@pytest.mark.parametrize("dead", [float("nan"), 3e38], ids=["nan", "huge"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_nothing_past_a_length_reaches_the_result(dtype, dead):
    """Dead columns that hold NaN or huge values change nothing: the same
    bits as with zeros there."""
    _want, clean, _x = step_both_ways(dtype, MIXED)
    want, got, _x = step_both_ways(dtype, MIXED, dead)
    np.testing.assert_array_equal(got, clean)
    np.testing.assert_allclose(got, want, atol=TOL[dtype])


def test_an_inactive_row_returns_its_own_latent():
    """Length 0: the softmax has the step's own entry alone, so the core's
    output is that entry's latent, bit for bit, whatever the slab holds."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 4, 32)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((2, 32)), jnp.float32)
    slab = jnp.full((1, 2, 32, T_C), jnp.nan, jnp.float32)
    out = latent_decode.latent_decode_core(
        q, new, slab, jnp.zeros((), jnp.int32), jnp.zeros((2,), jnp.int32),
        scale=0.3, kv_rank=16, tile=TILE, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.broadcast_to(np.asarray(new)[:, None, :16], (2, 4, 16)))


def test_a_slot_the_tile_does_not_divide_takes_the_einsums():
    assert latent_decode.latent_decode_impl(4, 32, T_C + 1, jnp.float32, 16) is None
    (verdict,) = default_kernel_registry().snapshot()[latent_decode.NAME].values()
    assert not verdict["enabled"] and "multiple of the tile" in verdict["reason"]


@pytest.mark.parametrize("mode,enabled", [("0", False), ("1", False)],
                         ids=["off", "auto-on-the-cpu"])
def test_modes_that_keep_the_einsum_path(monkeypatch, mode, enabled):
    """The kill switch, and auto mode off the TPU: ``_run_stack`` hands the
    cache over as (slab, position map), one fallback recorded."""
    monkeypatch.setenv(ENV_FLAGS[latent_decode.NAME], mode)
    cfg, _bp = layer_of("float32")
    slab = jnp.zeros((1, 2, 32, T_C), jnp.float32)
    assert (cfg.mixer("latent").kernel(slab) is not None) is enabled
    (verdict,) = default_kernel_registry().snapshot()[latent_decode.NAME].values()
    assert verdict["enabled"] is enabled


def test_decode_step_through_the_kernel_equals_the_einsum_program(monkeypatch):
    """The whole decode program both ways on one cache: logits equal to
    summation order, the written caches equal; a row that is not active
    reads nothing at its stale position (NaN all over its slab changes no
    logit, its own included)."""
    cfg = decoder_lm.DecoderConfig(
        vocab_size=64, d_model=32, n_heads=4, head_dim=32, v_head_dim=12,
        rotary_dim=16,
        attn_kinds={"latent": {"rope_theta": 100.0,
                               "latent": {"q_rank": 24, "kv_rank": 16}}},
        layers=[("latent", "dense"), ("latent", "experts"), ("latent", "experts")],
        dense_width=64, expert_width=16, n_experts=4, top_k=2,
        experts_held=(0, 4), max_length=T_C, param_dtype="float32", seed=11,
        routing={"n_group": 2, "topk_group": 1, "renormalise": False, "scale": 2.0},
        shared_width=16)
    params = decoder_lm.init_params(cfg)
    key = jax.random.PRNGKey(2)
    caches = [tuple(jax.random.normal(key, s.shape, jnp.float32) for s in seg)
              for seg in decoder_lm.init_cache(cfg, 4, T_C)]
    ids = jnp.asarray([3, 9, 27, 50], jnp.int32)
    pos = jnp.asarray([5, 17, 0, 33], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    got = decoder_lm.decode_step(cfg, params, caches, ids, pos, active)
    stale = [tuple(c.at[:, 3].set(jnp.nan) for c in seg) for seg in caches]
    unread = decoder_lm.decode_step(cfg, params, stale, ids, pos, active)
    np.testing.assert_array_equal(np.asarray(unread[0]), np.asarray(got[0]))
    monkeypatch.setenv(ENV_FLAGS[latent_decode.NAME], "0")
    default_kernel_registry().reset(latent_decode.NAME)
    want = decoder_lm.decode_step(cfg, params, caches, ids, pos, active)
    np.testing.assert_allclose(np.asarray(got[0])[:3], np.asarray(want[0])[:3],
                               atol=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g)[:, :3], np.asarray(w)[:, :3],
                                   atol=1e-5)
    assert (int(got[2][0]), int(got[2][1])) == (int(want[2][0]), int(want[2][1]))


def test_chip_smoke_asks_for_the_kernel_and_fails_where_it_fell_back(monkeypatch):
    """``chip_smoke.py``'s ``kernels`` phase resolves the kernel itself (no
    phase of it serves a latent layer): enabled, it passes and says so; a
    fallback on the TPU platform raises with the kernel's name."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert chip_smoke.FULL["latent_core"] == dict(
        heads=128, width=576, t_c=10240, dtype="bfloat16", kv_rank=512)

    # the phase asks for the state-space decode kernel and the cache's
    # column write too
    monkeypatch.setenv(ENV_FLAGS["ssm_decode_step"], "interpret")
    monkeypatch.setenv(ENV_FLAGS["kv_column_write"], "interpret")
    monkeypatch.setenv(ENV_FLAGS["grouped_experts"], "interpret")
    monkeypatch.setenv(ENV_FLAGS["sparse_latent_decode"], "interpret")
    default_kernel_registry().reset()
    report = chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    (verdict,) = report["registry"][latent_decode.NAME].values()
    assert verdict["enabled"] and report["refused"] == []
    monkeypatch.setenv(ENV_FLAGS[latent_decode.NAME], "1")
    default_kernel_registry().reset()
    with pytest.raises(AssertionError, match=latent_decode.NAME):
        chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    default_kernel_registry().reset()

"""The cache's column-write kernel (``nn/ops/kv_column_write.py``) under the
Pallas interpreter against the loop it replaces, through the SAME function,
``transformer_lm._put_columns``: once with the kernel's switch on
``interpret`` and once on ``0``. The live slots' columns land bit for bit,
and every other bit of the slab (the idle slots' too) is what it was."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import decoder_lm, transformer_lm
from deeplearning4j_tpu.nn.ops import kv_column_write as kcw
from deeplearning4j_tpu.nn.ops.registry import ENV_FLAGS, default_kernel_registry

BF16 = jnp.bfloat16
L, S, HEADS, HD = 5, 6, 4, 16
LOADS = {"all-live": [1] * S, "mixed": [1, 0, 1, 1, 0, 1],
         "only-the-last": [0] * (S - 1) + [1], "only-the-first": [1] + [0] * (S - 1)}


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """The registry's mode ``interpret``; the verdicts of this file's keys
    do not outlive a test."""
    monkeypatch.setenv(ENV_FLAGS[kcw.NAME], "interpret")
    default_kernel_registry().reset(kcw.NAME)
    yield
    default_kernel_registry().reset(kcw.NAME)


def operands(t, shape=(L, S, HEADS, HD), dtype=BF16, seed=0):
    """(slab (*shape, t), new columns (L, S, heads, 1, hd)), seeded."""
    entries, slots, heads, hd = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    slab = jax.random.normal(keys[0], (*shape, t), jnp.float32).astype(dtype)
    new = jax.random.normal(keys[1], (entries, slots, heads, 1, hd),
                            jnp.float32).astype(dtype)
    return slab, new


def put(monkeypatch, mode, slab, new, wp, active=None):
    """``_put_columns`` under the switch ``mode``: (the slab, the program's
    jaxpr)."""
    monkeypatch.setenv(ENV_FLAGS[kcw.NAME], mode)
    default_kernel_registry().reset(kcw.NAME)
    wp = jnp.asarray(wp, jnp.int32).reshape(new.shape[1], -1)
    act = None if active is None else jnp.asarray(active, bool)

    def program(slab):  # a function of its own a call: a trace is kept by function
        return transformer_lm._put_columns(slab, new, wp, act)
    return np.asarray(jax.jit(program)(slab)), str(jax.make_jaxpr(program)(slab))


def expected(slab, new, wp, active):
    """The slab with the live slots' columns set, by numpy."""
    want = np.array(slab)
    for s in np.flatnonzero(np.asarray(active, bool)):
        want[:, s, :, :, wp[s]] = np.asarray(new)[:, s, :, 0, :]
    return want


def as_bits(a):
    return np.asarray(a).view(np.uint16 if a.dtype == BF16 else np.uint32)


@pytest.mark.parametrize("load", list(LOADS.values()), ids=list(LOADS))
@pytest.mark.parametrize("t", [128, 256, 384])
def test_live_columns_land_and_every_other_bit_stays(monkeypatch, t, load):
    slab, new = operands(t, seed=t)
    wp = np.random.default_rng(t).integers(0, t, S)
    got, text = put(monkeypatch, "interpret", slab, new, wp, load)
    assert text.count("pallas_call") == 1
    np.testing.assert_array_equal(as_bits(got), as_bits(expected(slab, new, wp, load)))
    # the loop writes the idle slots too: the live ones' columns are its
    loop, text = put(monkeypatch, "0", slab, new, wp, load)
    assert "pallas_call" not in text
    live = np.asarray(load, bool)
    np.testing.assert_array_equal(as_bits(got[:, live]), as_bits(loop[:, live]))
    np.testing.assert_array_equal(as_bits(got[:, ~live]), as_bits(np.asarray(slab)[:, ~live]))


@pytest.mark.parametrize("wp", [[383] * S, [0, 127, 128, 255, 256, 383],
                                [127] * S, [128] * S],
                         ids=["the-last-column", "blocks-first-and-last-lanes",
                              "a-last-lane", "a-first-lane"])
def test_the_column_block_and_the_lane_are_the_positions(monkeypatch, wp):
    slab, new = operands(384, seed=7)
    got, _text = put(monkeypatch, "interpret", slab, new, wp, LOADS["mixed"])
    np.testing.assert_array_equal(
        as_bits(got), as_bits(expected(slab, new, wp, LOADS["mixed"])))


def test_nothing_live_copies_one_slots_blocks(monkeypatch):
    slab, new = operands(256, seed=2)
    got, text = put(monkeypatch, "interpret", slab, new, [3] * S, [0] * S)
    assert text.count("pallas_call") == 1
    np.testing.assert_array_equal(as_bits(got), as_bits(slab))


def test_without_a_mask_every_slot_is_walked(monkeypatch):
    slab, new = operands(256, seed=4)
    wp = [0, 200, 255, 128, 127, 31]
    got, _text = put(monkeypatch, "interpret", slab, new, wp)
    loop, _text = put(monkeypatch, "0", slab, new, wp)
    np.testing.assert_array_equal(as_bits(got), as_bits(loop))


def test_a_latent_slab_goes_as_one_head_of_its_entry(monkeypatch):
    """``decoder_lm.decode_step``'s form for a dense latent layer: (L, S,
    576, T) as a slab of one head whose head size is the entry."""
    slab, new = operands(256, shape=(3, 4, 1, 576), seed=5)
    wp, load = [255, 0, 130, 17], [1, 1, 0, 1]
    got, text = put(monkeypatch, "interpret", slab, new, wp, load)
    assert text.count("pallas_call") == 1
    np.testing.assert_array_equal(as_bits(got), as_bits(expected(slab, new, wp, load)))


@pytest.mark.parametrize("entries,fit,lb", [(7, 3, 3), (5, 2, 2), (7, 4, 4), (9, 5, 5)],
                         ids=["7=3+3+1", "5=2+2+1", "7=4+3", "9=5+4"])
def test_entries_the_block_does_not_divide(monkeypatch, entries, fit, lb):
    """The last block of a slot is cut short: its entries past the end are
    neither read into the slab nor written."""
    monkeypatch.setattr(kcw, "BLOCK_BYTES", fit * HEADS * HD * 128 * 2)
    assert kcw.entries_a_block(entries, HEADS, HD, 2) == lb and entries % lb
    slab, new = operands(256, shape=(entries, S, HEADS, HD), seed=entries)
    wp = [5, 255, 128, 127, 0, 77]
    got, _text = put(monkeypatch, "interpret", slab, new, wp, LOADS["mixed"])
    np.testing.assert_array_equal(
        as_bits(got), as_bits(expected(slab, new, wp, LOADS["mixed"])))


@pytest.mark.parametrize("shape,lb", [
    ((36, 20, 64), 6), ((192, 16, 128), 4), ((48, 16, 128), 4), ((27, 1, 576), 14),
    ((12, 4, 128), 12), ((40, 4, 128), 14), ((5, 4, 16), 5), ((3, 160, 64), 1)],
    ids=["chat", "ouro", "ouro-cut", "latent", "few-kv-heads", "evened-out", "tiny",
         "many-heads"])
def test_entries_a_block_from_the_shapes(shape, lb):
    """~2 MB of the slab a grid step, a block's (entry, head) pairs within
    one tile of lanes, evened out over a slot's blocks."""
    entries, heads, hd = shape
    assert kcw.entries_a_block(entries, heads, hd, 2) == lb
    assert lb * heads * hd * 128 * 2 <= max(kcw.BLOCK_BYTES, heads * hd * 256)


def test_float32_slabs_go_through_it_too(monkeypatch):
    slab, new = operands(128, dtype=jnp.float32, seed=8)
    wp = [0, 1, 64, 126, 127, 127]
    got, text = put(monkeypatch, "interpret", slab, new, wp, LOADS["mixed"])
    assert text.count("pallas_call") == 1
    np.testing.assert_array_equal(
        as_bits(got), as_bits(expected(slab, new, wp, LOADS["mixed"])))


def test_several_columns_a_row_take_the_loop(monkeypatch):
    """``decode_steps``' K-wide write: no kernel, nothing recorded."""
    slab, _new = operands(256, seed=9)
    new = jax.random.normal(jax.random.PRNGKey(1), (L, S, HEADS, 3, HD),
                            jnp.float32).astype(BF16)
    wp = np.minimum(np.arange(S)[:, None] * 50 + np.arange(3)[None], 255)
    got, text = put(monkeypatch, "interpret", slab, new, wp)
    assert "pallas_call" not in text
    want = np.array(slab)
    for s in range(S):
        for j in range(3):
            want[:, s, :, :, wp[s, j]] = np.asarray(new)[:, s, :, j, :]
    np.testing.assert_array_equal(as_bits(got), as_bits(want))
    assert kcw.NAME not in default_kernel_registry().snapshot()


@pytest.mark.parametrize("t", [96, 200], ids=["under-a-block", "no-whole-blocks"])
def test_a_length_of_no_whole_blocks_takes_the_loop(monkeypatch, t):
    slab, new = operands(t, seed=t)
    wp = [t - 1, 0, 5, 64, 95, 31]
    got, text = put(monkeypatch, "interpret", slab, new, wp, LOADS["mixed"])
    assert "pallas_call" not in text
    np.testing.assert_array_equal(as_bits(got), as_bits(expected(slab, new, wp, [1] * S)))
    assert kcw.NAME not in default_kernel_registry().snapshot()


def test_under_a_visible_mesh_the_loop_stays(monkeypatch):
    """A Mosaic call cannot be partitioned automatically: with an axis
    larger than one in sight nothing is asked of the registry."""
    from jax.sharding import Mesh

    slab, new = operands(128, seed=1)
    with jax.set_mesh(Mesh(np.asarray(jax.devices()[:2]), ("model",))):
        assert kcw.kv_column_write_impl(*slab.shape, slab.dtype) is None
        _got, text = put(monkeypatch, "interpret", slab, new, [3] * S)
    assert "pallas_call" not in text
    assert kcw.NAME not in default_kernel_registry().snapshot()
    assert kcw.kv_column_write_impl(*slab.shape, slab.dtype) is not None


@pytest.mark.parametrize("mode", ["0", "1"], ids=["off", "auto-on-the-cpu"])
def test_modes_that_keep_the_loop(monkeypatch, mode):
    """The kill switch, and auto mode off the TPU: one fallback recorded
    under the slab's key."""
    slab, new = operands(128, seed=3)
    _got, text = put(monkeypatch, mode, slab, new, [3] * S)
    assert "pallas_call" not in text
    ((key, verdict),) = default_kernel_registry().snapshot()[kcw.NAME].items()
    assert key == repr((L, S, HEADS, HD, 128, "bfloat16"))
    assert verdict["enabled"] is False
    assert ("DL4J_TPU_KV_COLUMN_WRITE=0" if mode == "0" else "non-TPU") in verdict["reason"]


def test_a_refused_probe_is_one_fallback_event_and_the_loop(monkeypatch):
    from deeplearning4j_tpu.obs import flight
    from deeplearning4j_tpu.obs.metrics import default_registry

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: forced")

    monkeypatch.setattr(kcw, "_probe", refuse)
    slab, new = operands(128, seed=3)

    def fallbacks():
        return [e for e in flight.default_flight_recorder().events()
                if e["kind"] == "kernel_fallback" and e.get("kernel") == kcw.NAME
                and "forced" in e.get("reason", "")]

    before = len(fallbacks())
    for _ in range(2):  # the second call is a dict hit
        assert kcw.kv_column_write_impl(*slab.shape, slab.dtype) is None
    assert len(fallbacks()) - before == 1
    gauge = default_registry().get("kernel_enabled", labels={"name": kcw.NAME})
    assert gauge is not None and gauge.value() == 0.0


def test_the_scalar_position_path_has_no_kernel():
    """``decode_step`` on a cache whose ``pos`` is one scalar writes one
    column for all rows by one update: the kernel is not asked."""
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=64, max_length=128, d_model=32, n_heads=2, n_layers=2,
        compute_dtype="bfloat16")
    params = transformer_lm.init_params(cfg, jax.random.PRNGKey(0))
    cache = transformer_lm.init_decode_cache(cfg, 3)
    ids = jnp.asarray([1, 2, 3], jnp.int32)
    text = str(jax.make_jaxpr(
        lambda c: transformer_lm.decode_step(cfg, params, c, ids))(cache))
    assert "pallas_call" not in text
    cache = dict(cache, pos=jnp.asarray([4, 0, 127], jnp.int32))
    text = str(jax.make_jaxpr(
        lambda c: transformer_lm.decode_step(cfg, params, c, ids))(cache))
    assert text.count("pallas_call") == 2  # K and V


@pytest.mark.parametrize("active", [[True, False, True, True], None],
                         ids=["a-row-idle", "no-mask"])
def test_transformer_decode_step_through_the_kernel_equals_the_loops(monkeypatch, active):
    """The chat path's program both ways on one cache: the logits bit for
    bit (the cache is only read before the write), the live rows' cache
    bit for bit, an idle row's cache as it was."""
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=64, max_length=256, d_model=64, n_heads=4, n_layers=3,
        compute_dtype="bfloat16")
    params = transformer_lm.init_params(cfg, jax.random.PRNGKey(0))
    shape = (cfg.n_layers, 4, cfg.n_heads, 16, 256)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    cache = {"k": jax.random.normal(keys[0], shape, jnp.float32).astype(BF16),
             "v": jax.random.normal(keys[1], shape, jnp.float32).astype(BF16),
             "pos": jnp.asarray([5, 128, 255, 300], jnp.int32)}
    ids = jnp.asarray([3, 9, 27, 50], jnp.int32)
    act = None if active is None else jnp.asarray(active)

    def both():
        def program(cache):
            return transformer_lm.decode_step(cfg, params, cache, ids, act)
        return jax.jit(program)(cache), str(jax.make_jaxpr(program)(cache))

    got, text = both()
    assert text.count("pallas_call") == 2
    monkeypatch.setenv(ENV_FLAGS[kcw.NAME], "0")
    default_kernel_registry().reset(kcw.NAME)
    want, text = both()
    assert "pallas_call" not in text
    rows = np.asarray([True] * 4 if active is None else active)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for name in ("k", "v"):
        np.testing.assert_array_equal(as_bits(got[1][name])[:, rows],
                                      as_bits(want[1][name])[:, rows])
        np.testing.assert_array_equal(as_bits(got[1][name])[:, ~rows],
                                      as_bits(cache[name])[:, ~rows])
    np.testing.assert_array_equal(np.asarray(got[1]["pos"]), np.asarray(want[1]["pos"]))


def _chip_smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    return chip_smoke


def _tiny_decoders():
    """Full attention alone, and a latent layer among full ones: float32,
    slots of 128, so that ``decode_step`` writes whole blocks."""
    full = dict(vocab_size=64, d_model=32, n_heads=4, head_dim=8, v_head_dim=8,
                rotary_dim=8, attn_kinds={"full": {"n_kv_heads": 2, "rope_theta": 1e4}},
                layers=[("full", "dense")] * 3, dense_width=64, max_length=128,
                param_dtype="float32")
    looped = dict(full, passes=2, sandwich_norm=True, exit_gate=True)
    latent = dict(full, rotary_dim=4, attn_kinds={
        "full": {"n_kv_heads": 2, "rope_theta": 1e4},
        "latent": {"rope_theta": 1e4, "latent": {"q_rank": 16, "kv_rank": 12}}},
        layers=[("latent", "dense"), ("full", "dense"), ("latent", "dense")])
    return {"full": full, "looped": looped, "latent": latent}


@pytest.mark.parametrize("kind", list(_tiny_decoders()))
def test_decoder_decode_step_through_the_kernel_equals_the_loops(monkeypatch, kind):
    """``decoder_lm.decode_step`` both ways on one cache with a row idle:
    the live rows' logits and slabs bit for bit, one call a slab."""
    cfg = decoder_lm.DecoderConfig(**_tiny_decoders()[kind])
    params = decoder_lm.init_params(cfg)
    key = jax.random.PRNGKey(2)
    caches = [tuple(0.1 * jax.random.normal(jax.random.fold_in(key, 5 * i + j), c.shape,
                                             jnp.float32).astype(c.dtype)
                    for j, c in enumerate(seg))
              for i, seg in enumerate(decoder_lm.init_cache(cfg, 4, 128))]
    slabs = sum(len(seg) for seg in caches)
    ids = jnp.asarray([3, 9, 27, 50], jnp.int32)
    pos = jnp.asarray([5, 127, 0, 64], jnp.int32)
    act = jnp.asarray([True, True, False, True])

    def both():
        def program(caches):
            return decoder_lm.decode_step(cfg, params, caches, ids, pos, act)
        return jax.jit(program)(caches), str(jax.make_jaxpr(program)(caches))

    got, text = both()
    assert text.count("pallas_call") == slabs
    monkeypatch.setenv(ENV_FLAGS[kcw.NAME], "0")
    default_kernel_registry().reset(kcw.NAME)
    want, text = both()
    assert "pallas_call" not in text
    rows = np.asarray(act)
    np.testing.assert_array_equal(np.asarray(got[0])[rows], np.asarray(want[0])[rows])
    for seg_g, seg_w, seg_0 in zip(got[1], want[1], caches):
        for g, w, c in zip(seg_g, seg_w, seg_0):
            np.testing.assert_array_equal(np.asarray(g)[:, rows], np.asarray(w)[:, rows])
            np.testing.assert_array_equal(np.asarray(g)[:, ~rows], np.asarray(c)[:, ~rows])


@pytest.mark.parametrize("prefix_cache_mb", [0, 1], ids=["a-step-in-flight", "lock-step"])
def test_the_engine_serves_generate_cacheds_tokens_through_the_kernel(monkeypatch,
                                                                      prefix_cache_mb):
    """More requests than slots through both of ``TransformerLM``'s decode
    programs (slots idle beside live ones, claimed again): each request's
    tokens are those of the model's own cached generation on one slot."""
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                       max_length=128, seed=9, compute_dtype="bfloat16").init()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, n).astype(np.int32) for n in (5, 9, 20, 31, 40)]
    eng = GenerationEngine(lm, n_slots=3, max_length=128,
                           prefix_cache_mb=prefix_cache_mb)
    try:
        served = [np.asarray(r.result(timeout=600))
                  for r in [eng.submit(p, max_new=12) for p in prompts]]
    finally:
        eng.shutdown()
    verdicts = default_kernel_registry().snapshot()[kcw.NAME]
    assert verdicts[repr((2, 3, 4, 8, 128, "bfloat16"))]["enabled"]
    default_kernel_registry().reset(kcw.NAME)
    monkeypatch.setenv(ENV_FLAGS[kcw.NAME], "0")
    for prompt, got in zip(prompts, served):
        alone = np.asarray(lm.generate_cached(prompt, max_new=12)).ravel()
        np.testing.assert_array_equal(got[-12:], alone[-12:])


def test_a_sharded_engine_keeps_the_loop():
    """``sharded_generation_engine`` traces its programs with the mesh in
    sight, so the slab sharded over slots and heads is written by the
    partitioned loop (a Mosaic call would need a ``shard_map``): nothing is
    asked of the registry, and the tokens are the solo engine's."""
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.parallel.serving_mesh import ServingMesh
    from deeplearning4j_tpu.serving.generate import GenerationEngine
    from deeplearning4j_tpu.serving.sharded import sharded_generation_engine

    def lm():
        return TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                             max_length=128, seed=9, compute_dtype="bfloat16").init()

    prompt = np.asarray([5, 9, 11, 2], np.int32)
    eng = sharded_generation_engine(
        lm(), ServingMesh(batch=2, model=4, devices=jax.devices()[:8]), n_slots=4)
    try:
        sharded = np.asarray(eng.submit(prompt, max_new=6).result(timeout=600))
    finally:
        eng.shutdown()
    assert kcw.NAME not in default_kernel_registry().snapshot()
    eng = GenerationEngine(lm(), n_slots=4)
    try:
        solo = np.asarray(eng.submit(prompt, max_new=6).result(timeout=600))
    finally:
        eng.shutdown()
    assert default_kernel_registry().snapshot()[kcw.NAME][
        repr((2, 4, 4, 8, 128, "bfloat16"))]["enabled"]
    np.testing.assert_array_equal(sharded, solo)


def test_chip_smoke_asks_for_the_kernel_and_fails_where_it_fell_back(monkeypatch):
    """``chip_smoke.py``'s ``kernels`` phase resolves the kernel itself at
    the two cells' keys: enabled, it passes and says so; a fallback on the
    TPU platform raises with the kernel's name."""
    from deeplearning4j_tpu.nn.ops import latent_decode, ssm_decode

    for name in (latent_decode.NAME, ssm_decode.NAME, "grouped_experts",
                 "sparse_latent_decode"):
        monkeypatch.setenv(ENV_FLAGS[name], "interpret")
    chip_smoke = _chip_smoke()
    assert chip_smoke.FULL["kv_columns"] == [
        dict(entries=36, slots=24, heads=20, head_size=64, t=1024, dtype="bfloat16"),
        dict(entries=192, slots=5, heads=16, head_size=128, t=896, dtype="bfloat16")]
    default_kernel_registry().reset()
    report = chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    verdicts = report["registry"][kcw.NAME]
    assert len(verdicts) == len(chip_smoke.TINY["kv_columns"]) == 2
    assert all(v["enabled"] for v in verdicts.values()) and report["refused"] == []
    monkeypatch.setenv(ENV_FLAGS[kcw.NAME], "1")
    default_kernel_registry().reset()
    with pytest.raises(AssertionError, match=kcw.NAME):
        chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    default_kernel_registry().reset()

"""Decode attention over the live tiles (``nn/ops/decode_attention.py``)
under the Pallas interpreter against the einsum paths it stands in for,
through the SAME functions: ``transformer_lm.decode_step`` and
``decoder_lm.decode_step`` once with the kernel's switch on ``interpret``
and once on ``0``; then which calls take it, the once-a-step walk, and the
counter it brings. The tile is cut to 8 columns and the slab's floor to 0
so that tiny slabs walk several tiles."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import decoder_lm, transformer_lm
from deeplearning4j_tpu.nn.ops import decode_attention as da
from deeplearning4j_tpu.nn.ops.registry import ENV_FLAGS, default_kernel_registry

BF16, F32 = jnp.bfloat16, jnp.float32
TILE, T = 8, 32
#: an idle slot first and between live ones, one column, a tile's edge -1,
#: +0, +1, a slot in its third tile, a full slot
LENGTHS = [0, 1, 7, 0, 8, 9, 17, 32]
SHAPES = {"gpt2-like": (4, 1, 16, 16), "grouped": (2, 4, 16, 16),
          "values-narrower": (2, 2, 16, 8), "three-heads": (3, 2, 8, 8)}
TOL = {F32: 2e-5, BF16: 2e-2}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """The registry's mode ``interpret``, tiles of 8 columns, no floor under
    the slab; the verdicts of this file's keys do not outlive a test."""
    monkeypatch.setenv(ENV_FLAGS[da.NAME], "interpret")
    monkeypatch.setattr(da, "TILE", TILE)
    monkeypatch.setattr(da, "MIN_SLAB_BYTES", 0)
    default_kernel_registry().reset(da.NAME)
    yield
    default_kernel_registry().reset(da.NAME)


def _mesh():
    """Two devices in sight, as ``serving/sharded.py`` traces its programs."""
    return jax.make_mesh((2,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def switch(monkeypatch, mode):
    monkeypatch.setenv(ENV_FLAGS[da.NAME], mode)
    default_kernel_registry().reset(da.NAME)


def operands(shape, dtype, slots=len(LENGTHS), layers=3, t=T, seed=0):
    """(q, k_new, v_new, k_slab, v_slab), seeded."""
    hkv, grp, hd, vd = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    sizes = [(slots, hkv, grp, hd), (slots, hkv, hd), (slots, hkv, vd),
             (layers, slots, hkv, hd, t), (layers, slots, hkv, vd, t)]
    return [jax.random.normal(k, s, F32).astype(dtype)
            for k, s in zip(keys, sizes)]


def through_kernel(args, layer, lengths, scale=0.25, tile=TILE):
    lengths = jnp.asarray(lengths, jnp.int32)
    t = args[3].shape[-1]

    def program(*args):
        return da.decode_attention(
            *args, jnp.asarray(layer, jnp.int32),
            da.live_tiles(lengths, t, tile), scale=scale, tile=tile,
            interpret=True)
    return np.asarray(jax.jit(program)(*args))


def through_einsums(args, layer, lengths, scale=0.25):
    return np.asarray(jax.jit(lambda *a: da.decode_attention_reference(
        *a, jnp.asarray(layer), jnp.asarray(lengths, jnp.int32),
        scale=scale))(*args))


def rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# -- the kernel alone ----------------------------------------------------------
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES.values()), ids=list(SHAPES))
def test_the_kernel_equals_the_einsums(shape, dtype):
    args = operands(shape, dtype, seed=sum(shape))
    got = through_kernel(args, 1, LENGTHS)
    want = through_einsums(args, 1, LENGTHS)
    assert got.shape == (len(LENGTHS), shape[0], shape[1], shape[3])
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert rel(got, want) < TOL[dtype]


@pytest.mark.parametrize("tile", [8, 16, 32])
def test_every_tile_gives_the_same_numbers(tile):
    args = operands(SHAPES["grouped"], F32, seed=tile)
    assert rel(through_kernel(args, 2, LENGTHS, tile=tile),
               through_einsums(args, 2, LENGTHS)) < TOL[F32]


def test_a_slot_at_zero_gets_its_own_value():
    """An idle slot, or one at position 0, is not visited: its softmax
    holds the step's own entry alone, finite, as ``_joint_softmax`` has
    it."""
    args = operands(SHAPES["grouped"], F32, seed=3)
    got = through_kernel(args, 0, [0] * len(LENGTHS))
    own = np.broadcast_to(np.asarray(args[2])[:, :, None, :], got.shape)
    np.testing.assert_array_equal(got, own)


def test_only_the_layer_and_the_live_columns_are_read():
    """Every other layer's entries and every column at or past a slot's
    length hold NaN: none reaches the result."""
    args = operands(SHAPES["gpt2-like"], F32, seed=5)
    want = through_kernel(args, 1, LENGTHS)
    dead = np.arange(T)[None, :] >= np.asarray(LENGTHS)[:, None]
    for i in (3, 4):
        slab = np.array(args[i])
        slab[[0, 2]] = np.nan
        slab[1] = np.where(dead[:, None, None, :], np.nan, slab[1])
        args[i] = jnp.asarray(slab)
    np.testing.assert_array_equal(through_kernel(args, 1, LENGTHS), want)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_a_slot_among_others_is_the_slot_alone(dtype):
    """The walk is a slot at a time and shares nothing between slots: a
    row's bits do not depend on what the other slots hold."""
    args = operands(SHAPES["grouped"], dtype, seed=9)
    among = through_kernel(args, 1, LENGTHS)
    for s in (2, 5, 7):
        alone = [n if i == s else 0 for i, n in enumerate(LENGTHS)]
        np.testing.assert_array_equal(through_kernel(args, 1, alone)[s],
                                      among[s])


def test_the_walk_lists_the_live_tiles_slot_after_slot():
    lengths, slot_of, tile_of, steps = (np.asarray(a) for a in da.live_tiles(
        jnp.asarray([0, 9, 0, 40, 8], jnp.int32), 32, 8))
    assert lengths.tolist() == [0, 9, 0, 32, 8] and steps.tolist() == [7]
    assert slot_of[:7].tolist() == [1, 1, 3, 3, 3, 3, 4]
    assert tile_of[:7].tolist() == [0, 1, 0, 1, 2, 3, 0]
    # nothing live: one step still runs, and its row is replaced outside
    assert np.asarray(da.live_tiles(jnp.zeros(3, jnp.int32), 32, 8)[3]).tolist() == [1]


def test_a_slot_length_no_tile_divides_is_refused():
    args = operands(SHAPES["gpt2-like"], F32, t=20)
    with pytest.raises(ValueError, match="not a multiple"):
        through_kernel(args, 0, [3] * len(LENGTHS))
    assert da.tile_for(20) == 0 and da.tile_for(24) == 8


def test_the_cells_slot_lengths_are_whole_tiles(monkeypatch):
    monkeypatch.undo()  # the tile as the chip chose it
    assert [da.tile_for(t) for t in (1024, 1536, 896, 4096, 1000)] == \
        [128, 128, 128, 128, 0]


# -- the registry ---------------------------------------------------------------
@pytest.mark.parametrize("mode,enabled", [("0", False), ("1", False),
                                          ("interpret", True)],
                         ids=["off", "auto-on-the-cpu", "interpret"])
def test_the_name_and_the_switch(monkeypatch, mode, enabled):
    assert ENV_FLAGS[da.NAME] == "DL4J_TPU_DECODE_ATTENTION"
    switch(monkeypatch, mode)
    impl = da.decode_attention_impl(4, 2, 4, 16, 8, 32, F32)
    assert (impl is not None) == enabled
    verdict = default_kernel_registry().snapshot()[da.NAME][
        repr((2, 4, 16, 8, 32, 8, "float32"))]
    assert verdict["enabled"] is enabled
    if enabled:
        assert impl[1] == 8 and impl[0].keywords == {"tile": 8, "interpret": True}


def test_a_small_slab_a_ragged_length_and_a_mesh_ask_nothing(monkeypatch):
    """What the kernel has no form for, or no gain in, keeps the einsums
    without a word to the registry: a layer's K + V under the floor, a slot
    length no tile divides, a mesh in sight."""
    monkeypatch.setattr(da, "MIN_SLAB_BYTES", 32 << 20)
    assert da.decode_attention_impl(4, 12, 1, 64, 64, 512, BF16) is None  # 6 MB
    monkeypatch.setattr(da, "MIN_SLAB_BYTES", 0)
    assert da.decode_attention_impl(4, 2, 4, 16, 8, 20, F32) is None
    with jax.set_mesh(_mesh()):
        assert da.decode_attention_impl(4, 2, 4, 16, 8, 32, F32) is None
    assert da.NAME not in default_kernel_registry().snapshot()
    assert da.decode_attention_impl(4, 2, 4, 16, 8, 32, F32) is not None


def test_the_cells_slabs_against_the_floor(monkeypatch):
    """The chat, granite, mimo and ouro cells' slabs are worth a call (the
    smallest, 36.7 MB, still wins on the chip: PERF.md, PR 44);
    ``chip_smoke.py``'s GPT-2-small slots (6 MB a layer) are not."""
    monkeypatch.undo()  # the floor and the tile as the module has them
    seen = []
    monkeypatch.setattr(default_kernel_registry(), "resolve",
                        lambda name, key, probe: seen.append(key) or True)
    assert da.decode_attention_impl(24, 20, 1, 64, 64, 1024, BF16) is not None
    assert da.decode_attention_impl(64, 8, 4, 128, 128, 4096, BF16) is not None
    assert da.decode_attention_impl(64, 4, 16, 192, 128, 1536, BF16) is not None
    assert da.decode_attention_impl(5, 16, 1, 128, 128, 896, BF16) is not None
    assert da.decode_attention_impl(4, 12, 1, 64, 64, 512, BF16) is None
    assert seen == [(20, 1, 64, 64, 1024, 128, "bfloat16"),
                    (8, 4, 128, 128, 4096, 128, "bfloat16"),
                    (4, 16, 192, 128, 1536, 128, "bfloat16"),
                    (16, 1, 128, 128, 896, 128, "bfloat16")]


def test_a_failed_probe_is_a_recorded_fallback(monkeypatch):
    def refuses(*_a, **_k):
        raise RuntimeError("Mosaic says no")
    monkeypatch.setattr(da, "_probe", refuses)
    assert da.decode_attention_impl(4, 2, 4, 16, 8, 32, F32) is None
    verdict = default_kernel_registry().snapshot()[da.NAME][
        repr((2, 4, 16, 8, 32, 8, "float32"))]
    assert not verdict["enabled"] and "Mosaic says no" in verdict["reason"]


# -- transformer_lm: the site ---------------------------------------------------
def _lm(compute_dtype=None, t=T):
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=64, max_length=t, d_model=32, n_heads=4, n_layers=3,
        compute_dtype=compute_dtype)
    params = transformer_lm.init_params(cfg, jax.random.PRNGKey(0))
    cache = transformer_lm.init_decode_cache(cfg, len(LENGTHS))
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    for name, key in zip("kv", keys):
        cache[name] = jax.random.normal(key, cache[name].shape,
                                        F32).astype(cache[name].dtype)
    return cfg, params, cache


def _traced(program, *args):
    return jax.jit(program)(*args), str(jax.make_jaxpr(program)(*args))


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"],
                         ids=["float32", "bfloat16"])
def test_the_engines_step_through_the_kernel_equals_the_einsums(monkeypatch,
                                                                compute_dtype):
    """``decode_step`` with per-row positions and ``active`` both ways on
    one cache: the live rows' logits and the new columns within rounding;
    the layer loop holds ONE kernel call (its body is traced once)."""
    cfg, params, cache = _lm(compute_dtype)
    cache["pos"] = jnp.asarray([5, 1, 7, 20, 8, 9, 17, 31], jnp.int32)
    ids = jnp.arange(8, dtype=jnp.int32)
    active = jnp.asarray([0, 1, 1, 0, 1, 1, 1, 1], bool)

    def both():  # a function of its own a call: a trace is kept by function
        return _traced(lambda c: transformer_lm.decode_step(
            cfg, params, c, ids, active), cache)

    got, text = both()
    assert text.count("name=decode_attention") == 1
    switch(monkeypatch, "0")
    want, text = both()
    assert "decode_attention" not in text
    rows = np.asarray(active)
    tol = TOL[F32 if compute_dtype is None else BF16]
    assert np.isfinite(np.asarray(got[0])).all()
    assert rel(np.asarray(got[0])[rows], np.asarray(want[0])[rows]) < tol
    for name in "kv":
        g, w = (np.asarray(c[name], np.float32)[:, rows] for c in (got[1], want[1]))
        assert rel(g, w) < tol


def test_without_active_every_row_reads_its_position(monkeypatch):
    cfg, params, cache = _lm()
    cache["pos"] = jnp.asarray(LENGTHS, jnp.int32)
    ids = jnp.arange(8, dtype=jnp.int32)

    def both():
        return _traced(lambda c: transformer_lm.decode_step(
            cfg, params, c, ids)[0], cache)

    got, text = both()
    assert text.count("name=decode_attention") == 1
    switch(monkeypatch, "0")
    want, _text = both()
    assert rel(np.asarray(got), np.asarray(want)) < TOL[F32]


def _text_of(program, *args):
    return str(jax.make_jaxpr(program)(*args))


def test_more_columns_a_scalar_position_and_a_mesh_keep_the_einsums():
    cfg, params, cache = _lm()
    ids = jnp.arange(8, dtype=jnp.int32)
    rows = dict(cache, pos=jnp.asarray(LENGTHS, jnp.int32))
    # K > 1: the speculative verify
    assert "decode_attention" not in _text_of(
        lambda c: transformer_lm.decode_steps(cfg, params, c,
                                              jnp.stack([ids, ids], 1)), rows)
    # one position for all rows: ``generate_cached``
    assert "decode_attention" not in _text_of(
        lambda c: transformer_lm.decode_step(cfg, params, c, ids),
        dict(cache, pos=jnp.asarray(5, jnp.int32)))
    # a mesh in sight: ``serving/sharded.py`` traces under ``jax.set_mesh``
    with jax.set_mesh(_mesh()):
        assert "decode_attention" not in _text_of(
            lambda c: transformer_lm.decode_step(cfg, params, c, ids), rows)
    assert da.NAME not in default_kernel_registry().snapshot()
    assert "name=decode_attention" in _text_of(
        lambda c: transformer_lm.decode_step(cfg, params, c, ids), rows)


def test_a_ragged_slot_length_and_a_failed_probe_keep_the_einsums(monkeypatch):
    ids = jnp.arange(8, dtype=jnp.int32)
    cfg, params, cache = _lm(t=20)
    cache["pos"] = jnp.asarray([0, 1, 7, 0, 8, 9, 17, 19], jnp.int32)
    assert "decode_attention" not in _text_of(
        lambda c: transformer_lm.decode_step(cfg, params, c, ids), cache)

    def refuses(*_a, **_k):
        raise RuntimeError("Mosaic says no")
    monkeypatch.setattr(da, "_probe", refuses)
    cfg, params, cache = _lm()
    cache["pos"] = jnp.asarray(LENGTHS, jnp.int32)
    assert "decode_attention" not in _text_of(
        lambda c: transformer_lm.decode_step(cfg, params, c, ids), cache)
    (verdict,) = default_kernel_registry().snapshot()[da.NAME].values()
    assert not verdict["enabled"]


def test_the_walk_is_made_once_a_step(monkeypatch):
    """``live_tiles`` runs once a trace of the step, outside the layer loop,
    and every layer's call takes that table."""
    calls = []
    real = da.live_tiles

    def counted(lengths, t, tile):
        calls.append((t, tile))
        return real(lengths, t, tile)
    monkeypatch.setattr(transformer_lm, "live_tiles", counted)
    cfg, params, cache = _lm()
    cache["pos"] = jnp.asarray(LENGTHS, jnp.int32)
    text = _text_of(lambda c: transformer_lm.decode_step(
        cfg, params, c, jnp.arange(8, dtype=jnp.int32)), cache)
    assert calls == [(T, TILE)]
    # the table's cumulated tiles are the step's, not the loop body's
    body = text[text.index("scan["):]
    assert "cumsum" in text[:text.index("scan[")] and "cumsum" not in body[
        :body.index("name=decode_attention")]


def test_engine_parity_holds_under_the_kernel(monkeypatch):
    """More requests than slots through the engine (slots idle beside live
    ones, claimed again), float32 so that equal tokens mean something: each
    request's tokens are the model's own cached generation's, whose scalar
    position keeps the einsums."""
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                       max_length=64, seed=9).init()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, n).astype(np.int32) for n in (5, 9, 20, 31, 40)]
    eng = GenerationEngine(lm, n_slots=3, max_length=64)
    try:
        served = [np.asarray(r.result(timeout=600))
                  for r in [eng.submit(p, max_new=12) for p in prompts]]
        snap = eng.metrics.snapshot()
    finally:
        eng.shutdown()
    verdicts = default_kernel_registry().snapshot()[da.NAME]
    assert verdicts[repr((4, 1, 8, 8, 64, 8, "float32"))]["enabled"]
    for prompt, got in zip(prompts, served):
        alone = np.asarray(lm.generate_cached(prompt, max_new=12)).ravel()
        np.testing.assert_array_equal(got[-12:], alone[-12:])
    # 11 decode steps a request, at the prompt's length and the ten after
    assert snap["attn_positions_read"] == sum(
        sum(range(p.size, p.size + 11)) for p in prompts)


# -- decoder_lm: the site --------------------------------------------------------
def _decoders():
    """Full attention under grouped queries, with a scaled value and
    narrower values, with its own softmax scale; a windowed kind with a
    sink beside a full one; the stack run twice a token."""
    full = dict(vocab_size=64, d_model=32, n_heads=4, head_dim=8, v_head_dim=8,
                rotary_dim=8, attn_kinds={"full": {"n_kv_heads": 2, "rope_theta": 1e4}},
                layers=[("full", "dense")] * 3, dense_width=64, max_length=T,
                param_dtype="float32")
    return {
        "grouped": full,
        "scaled-narrow-values": dict(full, v_head_dim=4, value_scale=0.707),
        "its-own-softmax-scale": dict(full, attention_multiplier=0.0625),
        "window-and-sink-beside-full": dict(full, attn_kinds={
            "full": {"n_kv_heads": 2, "rope_theta": 1e4},
            "window": {"n_kv_heads": 4, "rope_theta": 1e4, "window": 8,
                       "sink": True}},
            layers=[("full", "dense"), ("window", "dense"), ("full", "dense")]),
        "looped": dict(full, passes=2, sandwich_norm=True, exit_gate=True),
    }


#: segments of full attention that take the kernel: one call a segment's loop
#: (a stack that runs more than once has the one loop body too)
KERNEL_CALLS = {"grouped": 1, "scaled-narrow-values": 1,
                "its-own-softmax-scale": 1,
                "window-and-sink-beside-full": 2, "looped": 1}


@pytest.mark.parametrize("kind", list(_decoders()))
def test_decoder_step_through_the_kernel_equals_the_einsums(monkeypatch, kind):
    """``decoder_lm.decode_step`` both ways on one cache with rows idle:
    the live rows' logits within rounding, a stack that runs more than
    once too; a windowed kind and a sink keep the einsums."""
    cfg = decoder_lm.DecoderConfig(**_decoders()[kind])
    params = decoder_lm.init_params(cfg)
    key = jax.random.PRNGKey(2)
    caches = [tuple(0.3 * jax.random.normal(jax.random.fold_in(key, 5 * i + j),
                                             c.shape, F32).astype(c.dtype)
                    for j, c in enumerate(seg))
              for i, seg in enumerate(decoder_lm.init_cache(cfg, 8, T))]
    ids = jnp.arange(8, dtype=jnp.int32)
    pos = jnp.asarray([5, 1, 7, 0, 8, 9, 17, 31], jnp.int32)
    act = jnp.asarray([0, 1, 1, 1, 1, 0, 1, 1], bool)

    def both():
        return _traced(lambda c: decoder_lm.decode_step(
            cfg, params, c, ids, pos, act)[0], caches)

    got, text = both()
    assert text.count("name=decode_attention") == KERNEL_CALLS[kind]
    switch(monkeypatch, "0")
    want, off = both()
    assert "decode_attention" not in off
    rows = np.asarray(act)
    assert np.isfinite(np.asarray(got)).all()
    if KERNEL_CALLS[kind]:
        assert rel(np.asarray(got)[rows], np.asarray(want)[rows]) < TOL[F32]
    else:  # not admitted: the same program, byte for byte
        assert text == off
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_decoder_under_the_floor_keeps_its_program(monkeypatch):
    """A site the rule does not admit lowers to the text it had: the floor
    on a layer's K + V bytes decides before anything is asked."""
    cfg = decoder_lm.DecoderConfig(**_decoders()["grouped"])
    params = decoder_lm.init_params(cfg)
    caches = decoder_lm.init_cache(cfg, 8, T)
    ids, pos = jnp.arange(8, dtype=jnp.int32), jnp.asarray(LENGTHS, jnp.int32)

    def lowered():
        return jax.jit(lambda c: decoder_lm.decode_step(
            cfg, params, c, ids, pos)).lower(caches).as_text()

    monkeypatch.setattr(da, "MIN_SLAB_BYTES", 32 << 20)
    under_the_floor = lowered()
    assert da.NAME not in default_kernel_registry().snapshot()
    switch(monkeypatch, "0")
    assert lowered() == under_the_floor


def test_a_decoder_makes_its_walk_once_a_segment(monkeypatch):
    calls = []
    real = da.live_tiles

    def counted(lengths, t, tile):
        calls.append((t, tile))
        return real(lengths, t, tile)
    monkeypatch.setattr(decoder_lm, "live_tiles", counted)
    cfg = decoder_lm.DecoderConfig(**_decoders()["grouped"])
    params = decoder_lm.init_params(cfg)
    caches = decoder_lm.init_cache(cfg, 8, T)
    jax.make_jaxpr(lambda c: decoder_lm.decode_step(
        cfg, params, c, jnp.arange(8, dtype=jnp.int32),
        jnp.asarray(LENGTHS, jnp.int32)))(caches)
    assert calls == [(T, TILE)]   # three layers, one segment, one table


def test_the_decoder_engine_serves_generate_cacheds_tokens(monkeypatch):
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    lm = DecoderLM.from_dict(dict(_decoders()["scaled-narrow-values"],
                                  max_length=64)).init()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n).astype(np.int32) for n in (6, 9, 17, 30)]
    eng = GenerationEngine(lm, n_slots=2, max_length=64)
    try:
        before = eng.metrics.snapshot()
        served = [np.asarray(r.result(timeout=600))
                  for r in [eng.submit(p, max_new=10) for p in prompts]]
        after = eng.metrics.snapshot()
        text = eng.metrics.registry.prometheus_text()
    finally:
        eng.shutdown()
    assert default_kernel_registry().snapshot()[da.NAME][
        repr((2, 2, 8, 4, 64, 8, "float32"))]["enabled"]
    switch(monkeypatch, "0")
    for prompt, got in zip(prompts, served):
        alone = np.asarray(lm.generate_cached(prompt, max_new=10)).ravel()
        np.testing.assert_array_equal(got[-10:], alone[-10:])
    # the first token comes from the prefill; decode step j = 1..9 has the
    # prompt + j - 1 positions behind it
    assert after["attn_positions_read"] - before["attn_positions_read"] == sum(
        sum(range(p.size, p.size + 9)) for p in prompts)
    assert after["latent_positions_read"] == 0
    assert "generation_attn_positions_read_total" in text


# -- chip_smoke.py ----------------------------------------------------------------
def _chip_smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    return chip_smoke


def test_chip_smoke_asks_for_the_kernel_and_fails_where_it_fell_back(monkeypatch):
    """``chip_smoke.py``'s ``kernels`` phase resolves the kernel itself at
    the chat and the granite cells' keys, two probes in one process:
    enabled, it passes and says so; a fallback on the TPU platform raises
    with the kernel's name."""
    from deeplearning4j_tpu.nn.ops import kv_column_write, latent_decode, ssm_decode

    for name in (latent_decode.NAME, ssm_decode.NAME, kv_column_write.NAME,
                 "grouped_experts", "sparse_latent_decode"):
        monkeypatch.setenv(ENV_FLAGS[name], "interpret")
    chip_smoke = _chip_smoke()
    assert chip_smoke.FULL["decode_attn"] == [
        dict(slots=24, hkv=20, grp=1, hd=64, vd=64, t=1024, dtype="bfloat16"),
        dict(slots=64, hkv=8, grp=4, hd=128, vd=128, t=4096, dtype="bfloat16")]
    for slab in chip_smoke.FULL["decode_attn"]:  # both over the floor
        assert (slab["slots"] * slab["hkv"] * (slab["hd"] + slab["vd"])
                * slab["t"] * 2) >= 32 << 20
    monkeypatch.setattr(da, "TILE", 128)
    default_kernel_registry().reset()
    report = chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    verdicts = report["registry"][da.NAME]
    assert len(verdicts) == len(chip_smoke.TINY["decode_attn"]) == 2
    assert all(v["enabled"] for v in verdicts.values()) and report["refused"] == []
    switch(monkeypatch, "1")
    default_kernel_registry().reset()
    with pytest.raises(AssertionError, match=da.NAME):
        chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    default_kernel_registry().reset()


"""The live-slot state-space decode kernel (``nn/ops/ssm_decode.py``) under
the Pallas interpreter against the ``jnp`` path of the SAME function,
``decoder_lm._ssm_mixer``: one Mamba-2 layer's decode step over a segment's
states, handed over once as (states, tails, layer) and once with the live
slots' table behind them. 16 heads of 16 x 16 in two groups and blocks of
128 columns, so a slot's state takes two grid steps and each lies in another
group; float32 throughout (the state's dtype): the new state and the
readout equal to summation order."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import decoder_lm
from deeplearning4j_tpu.nn.ops import ssm_decode
from deeplearning4j_tpu.nn.ops.registry import ENV_FLAGS, default_kernel_registry

HEADS, P, N, GROUPS, TILE = 16, 16, 16, 2, 128
SLOTS, LAYERS, LAYER = 6, 3, 1
TOL = dict(rtol=2e-6, atol=2e-6)
LOADS = {"all-live": [1] * SLOTS, "none-live": [0] * SLOTS,
         "mixed": [1, 0, 1, 1, 0, 1], "only-the-last": [0] * (SLOTS - 1) + [1],
         "only-the-first": [1] + [0] * (SLOTS - 1)}


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """The registry's mode ``interpret`` and blocks of ``TILE`` columns; the
    verdicts of this file's keys do not outlive a test."""
    monkeypatch.setenv(ENV_FLAGS[ssm_decode.NAME], "interpret")
    monkeypatch.setattr(ssm_decode, "TILE_BYTES", N * TILE * 4)
    default_kernel_registry().reset(ssm_decode.NAME)
    yield
    default_kernel_registry().reset(ssm_decode.NAME)


def layer_of(seed=3):
    """A state-space layer's configuration and one layer's leaves, seeded;
    ``A_log`` and ``dt_bias`` spread so that the decays differ by head."""
    cfg = decoder_lm.DecoderConfig(
        vocab_size=64, d_model=HEADS * P // 2, n_heads=4, head_dim=16, v_head_dim=16,
        rotary_dim=0,
        attn_kinds={"ssm": {"ssm": dict(n_heads=HEADS, head_dim=P, d_state=N,
                                        n_groups=GROUPS, d_conv=4, expand=2,
                                        chunk=8)}},
        layers=[("ssm", "dense")], dense_width=64, max_length=32,
        param_dtype="float32", seed=seed)
    seg = decoder_lm.init_params(cfg)["segments"][0]
    bp = {k: v[0] for k, v in seg.items()}
    rng = np.random.default_rng(seed)
    bp["A_log"] = jnp.asarray(rng.uniform(-1.0, 1.5, bp["A_log"].shape), jnp.float32)
    bp["dt_bias"] = jnp.asarray(rng.uniform(-2.0, 1.0, bp["dt_bias"].shape), jnp.float32)
    return cfg, bp


def caches_of(cfg, idle_holds=None, active=None, seed=5):
    """A segment's states and tails, seeded; ``idle_holds`` in every entry
    of the idle slots' state at every layer."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    conv = cfg.mixer("ssm").conv
    states = jax.random.normal(keys[0], (LAYERS, SLOTS, N, HEADS * P), jnp.float32)
    tails = jax.random.normal(keys[1], (LAYERS, SLOTS, conv, 3), jnp.float32)
    if idle_holds is not None:
        states = jnp.where(jnp.asarray(active, bool)[None, :, None, None],
                           states, idle_holds)
    x = jax.random.normal(keys[2], (SLOTS, 1, cfg.d_model), jnp.float32)
    return states, tails, x


def step_both_ways(load, idle_holds=None):
    """((x, states, tails) of the ``jnp`` path, the same of the kernel
    path, the states before) for one decode step at layer ``LAYER``."""
    cfg, bp = layer_of()
    active = jnp.asarray(load, bool)
    states, tails, x = caches_of(cfg, idle_holds, load)
    layer = jnp.asarray(LAYER, jnp.int32)
    mask = active[:, None]
    entry = cfg.mixer("ssm")
    want_x, want = decoder_lm._ssm_mixer(entry, bp, x, (states, tails, layer, None), mask)
    assert entry.kernel(states) is not None
    got_x, got = decoder_lm._ssm_mixer(
        entry, bp, x, (states, tails, layer, ssm_decode.live_table(active)), mask)
    return ((np.asarray(want_x), *map(np.asarray, want)),
            (np.asarray(got_x), *map(np.asarray, got)), np.asarray(states))


@pytest.mark.parametrize("load", list(LOADS.values()), ids=list(LOADS))
def test_kernel_equals_the_jnp_step(load):
    (want_x, want_h, want_t), (got_x, got_h, got_t), before = step_both_ways(load)
    live = np.asarray(load, bool)
    np.testing.assert_allclose(got_x[live], want_x[live], **TOL)
    np.testing.assert_allclose(got_h, want_h, **TOL)
    np.testing.assert_array_equal(got_t, want_t)
    if live.any():
        assert np.abs(got_h[LAYER, live] - before[LAYER, live]).max() > 1e-2
    (verdict,) = default_kernel_registry().snapshot()[ssm_decode.NAME].values()
    assert verdict == {"enabled": True, "reason": "probe ok"}
    ((key, _),) = default_kernel_registry().snapshot()[ssm_decode.NAME].items()
    assert key == repr((HEADS, P, N, SLOTS, TILE, "float32"))


@pytest.mark.parametrize("load", list(LOADS.values()), ids=list(LOADS))
def test_what_no_grid_step_visits_keeps_its_bits(load):
    """The idle slots of the layer that is written, and every slot of the
    other layers, are bit for bit what they were."""
    _want, (_x, got_h, _t), before = step_both_ways(load)
    idle = ~np.asarray(load, bool)
    np.testing.assert_array_equal(got_h[LAYER, idle].view(np.uint8),
                                  before[LAYER, idle].view(np.uint8))
    for other in set(range(LAYERS)) - {LAYER}:
        np.testing.assert_array_equal(got_h[other].view(np.uint8),
                                      before[other].view(np.uint8))


@pytest.mark.parametrize("held", [float("nan"), 3e38], ids=["nan", "huge"])
@pytest.mark.parametrize("load", [LOADS["mixed"], LOADS["only-the-last"]],
                         ids=["mixed", "only-the-last"])
def test_nothing_of_an_idle_slot_reaches_a_live_row(load, held):
    """NaN or huge values all over the idle slots' states: the live rows'
    output and new state have the bits they have with zeros there, and the
    idle slots keep what was planted."""
    _w, (clean_x, clean_h, _t), _b = step_both_ways(load, idle_holds=0.0)
    _w, (got_x, got_h, _t), before = step_both_ways(load, idle_holds=held)
    live = np.asarray(load, bool)
    np.testing.assert_array_equal(got_x[live], clean_x[live])
    np.testing.assert_array_equal(got_h[:, live], clean_h[:, live])
    assert np.isfinite(got_x[live]).all()
    np.testing.assert_array_equal(got_h[:, ~live].view(np.uint8),
                                  before[:, ~live].view(np.uint8))


@pytest.mark.parametrize("layer", range(LAYERS))
def test_the_layer_named_is_the_one_read_and_written(layer):
    """``ssm_decode_step`` on the whole states: the readout is layer
    ``layer``'s, its live slots are the ones that change."""
    rng = np.random.default_rng(layer)
    states = jnp.asarray(rng.standard_normal((LAYERS, SLOTS, N, HEADS * P)), jnp.float32)
    dtx = jnp.asarray(rng.standard_normal((SLOTS, HEADS * P)), jnp.float32)
    decay = jnp.asarray(np.repeat(rng.uniform(0.5, 1, (SLOTS, HEADS)), P, -1), jnp.float32)
    bvec = jnp.asarray(rng.standard_normal((SLOTS, GROUPS, N)), jnp.float32)
    cvec = jnp.asarray(rng.standard_normal((SLOTS, GROUPS, N)), jnp.float32)
    table = ssm_decode.live_table(jnp.asarray(LOADS["mixed"], bool))
    args = (states, jnp.asarray(layer, jnp.int32), table, dtx, decay, bvec, cvec)
    hc, new = ssm_decode.ssm_decode_step(*args, tile=TILE, interpret=True)
    hc_w, new_w = ssm_decode.ssm_decode_reference(*args)
    np.testing.assert_allclose(np.asarray(hc), np.asarray(hc_w), **TOL)
    np.testing.assert_allclose(np.asarray(new), np.asarray(new_w), **TOL)
    changed = np.abs(np.asarray(new) - np.asarray(states)).max(axis=(2, 3)) > 0
    want = np.zeros((LAYERS, SLOTS), bool)
    want[layer] = LOADS["mixed"]
    np.testing.assert_array_equal(changed, want)


def test_the_table_lists_the_live_slots_in_order():
    slot_of, n_live, active = ssm_decode.live_table(jnp.asarray(LOADS["mixed"], bool))
    assert int(n_live[0]) == 4 and np.asarray(slot_of)[:4].tolist() == [0, 2, 3, 5]
    assert np.asarray(active).tolist() == [bool(v) for v in LOADS["mixed"]]
    slot_of, n_live, _a = ssm_decode.live_table(jnp.zeros((SLOTS,), bool))
    assert int(n_live[0]) == 0 and (np.asarray(slot_of) == SLOTS - 1).all()


@pytest.mark.parametrize("heads,p,n,groups,tile", [
    (128, 64, 128, 1, 4096), (8, 16, 16, 1, 128), (16, 16, 16, 2, 128), (12, 8, 16, 1, 96),
    (12, 8, 16, 3, 0)], ids=["cell", "tiny", "two-groups", "all-columns", "none-fits"])
def test_the_tile_divides_a_group_and_lies_on_the_lanes(monkeypatch, heads, p, n,
                                                           groups, tile):
    monkeypatch.setattr(ssm_decode, "TILE_BYTES", 2 << 20)
    assert ssm_decode._tile(heads, p, n, groups) == tile


def test_columns_no_tile_divides_and_a_rounded_state_take_the_jnp_path():
    assert ssm_decode.ssm_decode_impl(12, 8, 16, 3, SLOTS, jnp.float32) is None
    assert ssm_decode.ssm_decode_impl(HEADS, P, N, GROUPS, SLOTS, jnp.bfloat16) is None
    verdicts = default_kernel_registry().snapshot()[ssm_decode.NAME]
    assert len(verdicts) == 2
    assert all(not v["enabled"] and "float32 state" in v["reason"]
               for v in verdicts.values())


@pytest.mark.parametrize("mode", ["0", "1"], ids=["off", "auto-on-the-cpu"])
def test_modes_that_keep_the_jnp_path(monkeypatch, mode):
    """The kill switch, and auto mode off the TPU: ``_run_stack`` hands the
    cache over without a table, one fallback recorded."""
    monkeypatch.setenv(ENV_FLAGS[ssm_decode.NAME], mode)
    cfg, _bp = layer_of()
    states = jnp.zeros((1, SLOTS, N, HEADS * P), jnp.float32)
    assert cfg.mixer("ssm").kernel(states) is None
    (verdict,) = default_kernel_registry().snapshot()[ssm_decode.NAME].values()
    assert verdict["enabled"] is False
    assert ("DL4J_TPU_SSM_DECODE_STEP=0" if mode == "0" else "non-TPU") in verdict["reason"]


def _chip_smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    return chip_smoke


@pytest.mark.parametrize("active", [[True, True, False, True], None],
                         ids=["a-row-idle", "no-mask"])
def test_decode_step_through_the_kernel_equals_the_jnp_program(monkeypatch, active):
    """The whole decode program of the tiny hybrid (two Mamba-2 layers, an
    attention layer, another Mamba-2 layer; ``chip_smoke.py``'s and
    ``tiny-granite``'s shapes) both ways on one cache: logits and written
    caches equal to summation order, the expert counters equal; the jaxpr
    of the kernel's program holds one ``pallas_call`` a state-space layer
    loop and the other's none."""
    monkeypatch.setattr(ssm_decode, "TILE_BYTES", 2 << 20)
    cfg = decoder_lm.DecoderConfig(**_chip_smoke().FULL["hybrid"])
    params = decoder_lm.init_params(cfg)
    key = jax.random.PRNGKey(2)
    caches = [tuple(0.1 * jax.random.normal(jax.random.fold_in(key, 5 * i + j), c.shape,
                                             jnp.float32).astype(c.dtype)
                    for j, c in enumerate(seg))
              for i, seg in enumerate(decoder_lm.init_cache(cfg, 4, 32))]
    ids = jnp.asarray([3, 9, 27, 50], jnp.int32)
    pos = jnp.asarray([5, 17, 0, 21], jnp.int32)
    act = None if active is None else jnp.asarray(active)

    def both():  # a function of its own a call: a trace is kept by function
        def program(caches):
            return decoder_lm.decode_step(cfg, params, caches, ids, pos, act)
        return program(caches), str(jax.make_jaxpr(program)(caches))

    got, text = both()
    assert text.count("pallas_call") == 2
    monkeypatch.setenv(ENV_FLAGS[ssm_decode.NAME], "0")
    default_kernel_registry().reset(ssm_decode.NAME)
    want, text = both()
    assert "pallas_call" not in text
    rows = np.asarray([True] * 4 if active is None else active)
    np.testing.assert_allclose(np.asarray(got[0])[rows], np.asarray(want[0])[rows],
                               rtol=1e-5, atol=1e-8)
    for (kind, _f, _n), seg_g, seg_w in zip(cfg.segments(), got[1], want[1]):
        for g, w in zip(seg_g, seg_w):
            np.testing.assert_allclose(np.asarray(g)[:, rows], np.asarray(w)[:, rows],
                                       rtol=1e-5, atol=1e-7)
            if kind == "ssm":  # an idle row's state and tail: as they were, both ways
                np.testing.assert_array_equal(np.asarray(g)[:, ~rows],
                                              np.asarray(w)[:, ~rows])
    assert (int(got[2][0]), int(got[2][1])) == (int(want[2][0]), int(want[2][1]))


def test_chip_smoke_asks_for_the_kernel_and_fails_where_it_fell_back(monkeypatch):
    """``chip_smoke.py``'s ``kernels`` phase resolves the kernel itself at
    the cell's key (``hybrid_serve`` takes it at a tiny one): enabled, it
    passes and says so; a fallback on the TPU platform raises with the
    kernel's name."""
    from deeplearning4j_tpu.nn.ops import latent_decode

    monkeypatch.setattr(ssm_decode, "TILE_BYTES", 2 << 20)
    monkeypatch.setenv(ENV_FLAGS[latent_decode.NAME], "interpret")
    monkeypatch.setenv(ENV_FLAGS["kv_column_write"], "interpret")
    monkeypatch.setenv(ENV_FLAGS["grouped_experts"], "interpret")
    monkeypatch.setenv(ENV_FLAGS["sparse_latent_decode"], "interpret")
    chip_smoke = _chip_smoke()
    assert chip_smoke.FULL["ssm_step"] == dict(
        heads=128, p=64, n=128, groups=1, slots=64, dtype="float32")
    default_kernel_registry().reset()
    report = chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    ((key, verdict),) = report["registry"][ssm_decode.NAME].items()
    assert key == repr((8, 16, 16, 3, 128, "float32"))
    assert verdict["enabled"] and report["refused"] == []
    monkeypatch.setenv(ENV_FLAGS[ssm_decode.NAME], "1")
    default_kernel_registry().reset()
    with pytest.raises(AssertionError, match=ssm_decode.NAME):
        chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    default_kernel_registry().reset()

"""The grouped SwiGLU kernel (``nn/ops/grouped_experts.py``) under the Pallas
interpreter: against a float32 ``jax.numpy`` reference and against today's
``ragged_dot`` path (``moe._ragged_swiglu``) over the shapes a step can
hand it (empty groups anywhere, a row a group, a group of a window + 1 rows,
every row in one group, none at all, rows past the groups), a stack of
several layers with the layer's index traced, widths shaped like the cells'
768 / 1,536 / 2,048 (24, 40 and 56 columns in tiles of 8, and whole), in
bfloat16 and float32; then through ``moe_dropless_ffn`` (the four routing
tests of ``tests/test_mimo_lm.py`` with the kernel forced, same tolerances)
and through one decode step of the tiny granite and mimo models against the
same step without the kernel."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import decoder_kinds  # noqa: E402
import test_mimo_lm as mimo  # noqa: E402

from deeplearning4j_tpu.models import decoder_lm as dl  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import moe  # noqa: E402
from deeplearning4j_tpu.nn.ops import grouped_experts as ge  # noqa: E402
from deeplearning4j_tpu.nn.ops.registry import ENV_FLAGS, default_kernel_registry  # noqa: E402

D, COUNT, LAYERS = 32, 5, 3
WINDOW = {"float32": 8, "bfloat16": 16}
#: relative to the largest number of the result: float32 differs by the
#: order of sums, bfloat16 by an ulp of the rounded ``silu * u`` besides
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """The registry's mode ``interpret``, windows of 8 rows, tiles of f in
    multiples of 8 and at most 16 wide at the tiny models' d = 64; the
    verdicts of this file's keys do not outlive a test."""
    monkeypatch.setenv(ENV_FLAGS[ge.NAME], "interpret")
    monkeypatch.setattr(ge, "WINDOW", 8)
    monkeypatch.setattr(ge, "_LANE", 8)
    monkeypatch.setattr(ge, "TILE_BYTES", 3 * 64 * 16 * 4)
    default_kernel_registry().reset(ge.NAME)
    yield
    default_kernel_registry().reset(ge.NAME)


def sizes_of(case, window, m):
    """(sizes (COUNT,), M) of a named case."""
    return {
        "empty-groups-first": ([0, 0, 3, 2, 4], 9),
        "empty-groups-last": ([3, 2, 4, 0, 0], 9),
        "empty-groups-in-the-middle": ([3, 0, 0, 2, 4], 9),
        "one-row-a-group": ([1, 1, 1, 1, 1], 5),
        "a-window-and-a-row": ([0, window + 1, 2, 0, 1], window + 4),
        "all-rows-in-one-group": ([0, 0, m, 0, 0], m),
        "no-rows-at-all": ([0, 0, 0, 0, 0], 6),
        "rows-past-the-groups": ([2, 1, 0, 3, 1], 7 + 7),
    }[case]


def stacks(dtype, f, layers=LAYERS, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    groups = layers * COUNT

    def draw(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(shape[-2])).astype(dtype)

    return {"Eg": draw(keys[0], (groups, D, f)),
            "Eu": draw(keys[1], (groups, D, f)),
            "Ed": draw(keys[2], (groups, f, D))}


def float32_reference(rows, experts, sizes, first):
    """Row by row through its own group's matrices, everything float32 but
    the rounding of ``silu * u`` to the rows' dtype that both paths make."""
    rows32 = np.asarray(rows, np.float32)
    out = np.zeros(rows32.shape, np.float32)
    at = 0
    for g, n in enumerate(np.asarray(sizes)):
        eg, eu, ed = (np.asarray(experts[k][first + g], np.float32)
                      for k in ("Eg", "Eu", "Ed"))
        for i in range(at, at + n):
            a, u = rows32[i] @ eg, rows32[i] @ eu
            h = np.asarray(jnp.asarray(a / (1 + np.exp(-a)) * u).astype(rows.dtype),
                           np.float32)
            out[i] = h @ ed
        at += n
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("f,tile", [(24, 24), (40, 8), (56, 8)])
@pytest.mark.parametrize("case", [
    "empty-groups-first", "empty-groups-last", "empty-groups-in-the-middle",
    "one-row-a-group", "a-window-and-a-row", "all-rows-in-one-group",
    "no-rows-at-all", "rows-past-the-groups"])
def test_the_kernel_against_float32_and_against_ragged_dot(case, f, tile, dtype):
    window = WINDOW[dtype]
    sizes, m = sizes_of(case, window, 3 * window + 5)
    sizes = np.asarray(sizes, np.int32)
    experts = stacks(dtype, f)
    rows = jax.random.normal(jax.random.PRNGKey(7), (m, D), jnp.float32).astype(dtype)
    first = COUNT  # the second layer of the stack
    got = np.asarray(ge.grouped_experts(
        rows, experts["Eg"], experts["Eu"], experts["Ed"], jnp.asarray(sizes),
        jnp.asarray(first, jnp.int32), window=window, tile=tile, interpret=True))
    n = int(sizes.sum())
    want = float32_reference(rows, experts, sizes, first)
    scale = np.abs(want).max() + 1e-6
    assert np.abs(got - want).max() / scale < TOL[dtype]
    np.testing.assert_array_equal(got[n:], 0.0)
    whole = np.zeros((LAYERS * COUNT,), np.int32)
    whole[first:first + COUNT] = sizes
    ragged = np.asarray(moe._ragged_swiglu(rows, experts, jnp.asarray(whole), 128))
    assert np.abs(got[:n] - ragged[:n]).max(initial=0.0) / scale < TOL[dtype]
    # the probe's oracle is the same function
    oracle = np.asarray(ge.grouped_experts_reference(
        rows, experts["Eg"], experts["Eu"], experts["Ed"], sizes, first))
    assert np.abs(got - oracle).max() / scale < TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_stack_of_layers_with_the_layer_traced_inside_a_scan(dtype):
    """``_grouped_swiglu`` as ``moe_dropless_ffn`` calls it in a segment's
    scan: the stack whole, ``first`` = the scan's counter x count."""
    f, m = 40, 20
    experts = stacks(dtype, f)
    rng = np.random.default_rng(2)
    sizes = np.stack([np.bincount(rng.integers(0, COUNT, size=n), minlength=COUNT)
                      for n in (13, 0, 20)]).astype(np.int32)
    rows = jax.random.normal(jax.random.PRNGKey(9), (LAYERS, m, D),
                             jnp.float32).astype(dtype)

    @jax.jit
    def run(experts, rows, sizes):
        def body(carry, x):
            layer, r, s = x
            return carry, moe._grouped_swiglu(r, experts, s, 128, layer * COUNT)

        return jax.lax.scan(body, 0, (jnp.arange(LAYERS), rows, sizes))[1]

    got = np.asarray(run(experts, rows, jnp.asarray(sizes)))
    (key, verdict), = default_kernel_registry().snapshot()[ge.NAME].items()
    assert verdict["enabled"] and key.startswith(f"({D}, {f}, {COUNT}, {m}, ")
    for layer in range(LAYERS):
        want = float32_reference(rows[layer], experts, sizes[layer], layer * COUNT)
        n = int(sizes[layer].sum())
        assert (np.abs(got[layer][:n] - want[:n]).max(initial=0.0)
                / (np.abs(want).max() + 1e-6) < TOL[dtype])


def test_what_the_kernel_declines(monkeypatch):
    """More rows than ``MAX_ROWS``, a mesh in sight, a manual axis, the kill
    switch: ``ragged_dot`` serves, and only the switch is recorded."""
    reg = default_kernel_registry()
    assert ge.grouped_experts_impl(ge.MAX_ROWS + 8, D, 40, COUNT, "float32") is None
    mesh = jax.make_mesh((2,), ("expert",))
    with jax.set_mesh(mesh):
        assert ge.grouped_experts_impl(16, D, 40, COUNT, "float32") is None
    seen = []

    def share(x):
        seen.append(ge.grouped_experts_impl(16, D, 40, COUNT, "float32"))
        return x

    jax.shard_map(share, mesh=mesh, in_specs=jax.P("expert"),
                  out_specs=jax.P("expert"))(jnp.zeros((2, 4)))
    assert seen == [None]
    assert ge.NAME not in reg.snapshot()
    assert ge.grouped_experts_impl(16, D, 40, COUNT, "float32") is not None
    monkeypatch.setenv(ENV_FLAGS[ge.NAME], "0")
    reg.reset(ge.NAME)
    assert ge.grouped_experts_impl(16, D, 40, COUNT, "float32") is None
    assert not list(reg.snapshot()[ge.NAME].values())[0]["enabled"]


def test_chip_smoke_asks_for_the_kernel_and_fails_where_it_fell_back(monkeypatch):
    """``chip_smoke.py``'s ``kernels`` phase resolves the kernel itself at
    the four expert cells' keys, several probes in one process: enabled, it
    passes and says so; a fallback on the TPU platform raises with the
    kernel's name."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert [(k["m"], k["d"], k["f"], k["count"])
            for k in chip_smoke.FULL["grouped_experts"]] == [
        (640, 4096, 768, 36), (512, 4096, 2048, 16), (288, 5120, 1536, 20),
        (256, 6144, 2048, 16)]
    monkeypatch.undo()     # the cells' rule: window 32, lanes of 128
    for name in ("latent_decode_core", "ssm_decode_step", "kv_column_write",
                 "sparse_latent_decode", ge.NAME):
        monkeypatch.setenv(ENV_FLAGS[name], "interpret")
    assert [ge.plan(k["m"], k["d"], k["f"], k["dtype"])
            for k in chip_smoke.FULL["grouped_experts"]] == [
        (32, 768), (32, 512), (32, 512), (32, 512)]
    default_kernel_registry().reset()
    report = chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    verdicts = report["registry"][ge.NAME]
    assert len(verdicts) == len(chip_smoke.TINY["grouped_experts"]) == 2
    assert all(v["enabled"] for v in verdicts.values()) and report["refused"] == []
    monkeypatch.setenv(ENV_FLAGS[ge.NAME], "1")
    default_kernel_registry().reset()
    with pytest.raises(AssertionError, match=ge.NAME):
        chip_smoke.phase_kernels("tpu", chip_smoke.TINY)
    default_kernel_registry().reset()


@pytest.mark.parametrize("routing_test", [
    mimo.test_dropless_layer_matches_the_dense_sum_with_a_bias_that_moves_the_choice,
    mimo.test_a_token_whose_experts_are_all_absent_gets_exactly_zero,
    mimo.test_idle_rows_stay_out_of_the_experts,
    mimo.test_the_four_shares_add_up_to_the_uncut_layer],
    ids=lambda t: t.__name__[5:])
def test_routing_through_the_kernel(routing_test):
    """``tests/test_mimo_lm.py``'s routing tests as they are, every grouped
    product of theirs through the kernel."""
    routing_test()
    verdicts = default_kernel_registry().snapshot()[ge.NAME]
    assert verdicts and all(v["enabled"] for v in verdicts.values())


@pytest.mark.parametrize("kind", ["state-space", "expert"])
def test_a_decode_step_through_the_kernel_is_the_step_without_it(kind, monkeypatch):
    """tiny-granite and tiny-mimo: a prompt prefilled into slot 1, another
    into slot 2, one decode step of both, with the kernel forced and with
    ``ragged_dot`` (float32 parameters: the two differ by summation order)."""
    m = decoder_kinds.decoder_lm(kind)
    cfg = m.cfg
    ids = (np.arange(32, dtype=np.int32) * 7 + 3) % cfg.vocab_size

    def one_step():
        caches = dl.init_cache(cfg, 3, 64)
        for slot, n in ((1, 11), (2, 16)):
            padded = np.zeros((1, 16), np.int32)
            padded[0, :n] = ids[slot:slot + n]
            _logits, caches = jax.jit(lambda p, c, t, n=n, slot=slot: dl.prefill_slot(
                cfg, p, c, t, jnp.asarray(n, jnp.int32),
                jnp.asarray(slot, jnp.int32)))(m.params_, caches, jnp.asarray(padded))
        logits, _caches, counts = jax.jit(lambda p, c: dl.decode_step(
            cfg, p, c, jnp.asarray([3, 4, 5], jnp.int32),
            jnp.asarray([0, 11, 16], jnp.int32),
            jnp.asarray([False, True, True])))(m.params_, caches)
        return np.asarray(logits[1:]), [int(c) for c in counts]

    with_kernel, counts = one_step()
    verdicts = default_kernel_registry().snapshot()[ge.NAME]
    assert verdicts and all(v["enabled"] for v in verdicts.values())
    monkeypatch.setenv(ENV_FLAGS[ge.NAME], "0")
    default_kernel_registry().reset(ge.NAME)
    without, counts_without = one_step()
    assert counts == counts_without and counts[0] > 0
    np.testing.assert_allclose(with_kernel, without, rtol=1e-4,
                               atol=1e-5 * np.abs(without).max())

"""DecoderLM's stack run several times a token over one set of weights
(models/decoder_lm.py: ``passes``, ``sandwich_norm``, ``exit_gate``)
against the plain reference of Ouro (benchmark/reference/ouro.py) at a tiny
size: three full-attention layers run three times, four norms a layer, the
exit gate. Seeded random weights, logits and not tokens. The published
keys are translated by the benchmark's family module, as the cell does.
The logits are ~0.5 in size; float32 parameters give the tolerance of
float32 summation order over 9 block applications (2e-5; ~2e-6 is what the
two sides differ by here), far under what a wrong norm, a missed pass or
another pass's keys gives (0.05 and more).
"""

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

from reference import ouro as ref  # noqa: E402

from deeplearning4j_tpu.models import decoder_lm  # noqa: E402
from deeplearning4j_tpu.models.decoder_lm import (  # noqa: E402
    DecoderConfig,
    DecoderLM,
)
from deeplearning4j_tpu.serving.generate import (  # noqa: E402
    EarlyExitError,
    GenerationEngine,
    generation_memory_report,
)
from tests.decoder_kinds import (  # noqa: E402
    KINDS,
    decoder_lm as kind_model,
    program as kind_program,
)

TOL = 2e-5
SEED = 11
OLD_KINDS = sorted(k for k in KINDS if k != "looped")


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_families_looped_decoder_lm",
        os.path.join(BENCH, "families", "looped_decoder_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


fam = _family()


def tiny(**changes):
    """The rehearsal preset in float32, with ``changes`` to published keys."""
    with open(os.path.join(BENCH, "configs", "tiny-ouro.json")) as f:
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
def model():
    return build(tiny())


def test_forward_agrees_with_the_reference(model):
    cfg = tiny()
    assert (model.cfg.passes, model.cfg.n_layers) == (3, 3)
    assert model.cfg.sandwich_norm and model.cfg.exit_gate
    ids = ids_of(cfg, 40)
    want = np.asarray(ref.logits(cfg, SEED, ids))
    got = model.logits(ids[None])[0]
    assert np.abs(want).mean() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_prefill_then_cached_decode_agrees_with_the_full_forward(model):
    """A prompt through the bucketed prefill, then a token a step through
    the nine (pass, layer) cache entries: each step's logits against the
    reference's ONE forward over prompt + served tokens."""
    cfg = tiny()
    prompt = ids_of(cfg, 13, seed=3)
    out, logits = model.generate_cached(prompt, max_new=20,
                                        return_logits=True)
    want = np.asarray(ref.logits(cfg, SEED, out[:-1]))[len(prompt) - 1:]
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)


def test_engine_tokens_equal_generate_cached_with_a_step_in_flight(model):
    cfg = tiny()
    prompts = [ids_of(cfg, n, seed=n) for n in (5, 9, 14, 22)]
    gen = GenerationEngine(model, n_slots=2, max_length=64,
                           prefill_buckets=[8, 16, 32])
    try:
        requests = [gen.submit(p, max_new=12) for p in prompts]
        served = [np.asarray(r.result(timeout=300)) for r in requests]
        snap = gen.metrics.snapshot()
    finally:
        gen.shutdown()
    for prompt, got in zip(prompts, served):
        alone = model.generate_cached(prompt, max_new=12)
        assert np.array_equal(got[-12:], alone[-12:])
    assert snap["decode_steps_ahead"] > 0
    # every launched step runs the three passes; a step launched for slots
    # that had just ended streams nothing and is not among decode_steps
    assert snap["stack_passes"] % 3 == 0
    assert snap["stack_passes"] >= 3 * snap["decode_steps"] > 0
    assert snap["cache_entries_per_position"] == 9


def test_cache_plan_counts_every_pass_and_is_what_is_allocated(model):
    cfg = model.cfg
    once = DecoderConfig(**{**fam.program_config(tiny()), "passes": 1})
    plan, plan1 = cfg.cache_plan(4, 48), once.cache_plan(4, 48)
    assert [p["passes"] for p in plan] == [3] and plan1[0]["passes"] == 1
    assert plan[0]["layers"] == plan1[0]["layers"] == 3
    assert plan[0]["bytes"] == 3 * plan1[0]["bytes"]
    assert plan[0]["slabs"] == [(9,) + s[1:] for s in plan1[0]["slabs"]]
    cache = decoder_lm.init_cache(cfg, 4, 48)
    assert [tuple(c.shape for c in seg) for seg in cache] == [
        tuple(p["slabs"]) for p in plan]
    assert sum(c.nbytes for seg in cache for c in seg) == plan[0]["bytes"]
    report = generation_memory_report(model, 4, 48)
    assert report["cache_bytes"] == plan[0]["bytes"]
    assert report["cache_plan"][0]["passes"] == 3


def test_a_pass_reads_its_own_cache_entries_only(model):
    """Zero pass 2's entries alone and the next step's logits change; and
    a reference in which the later passes attend to pass 1's keys and
    values (one entry a layer, shared) disagrees with the program."""
    cfg = model.cfg
    prompt = ids_of(tiny(), 16, seed=5)
    cache = decoder_lm.init_cache(cfg, 1, 32)
    _logits, cache = decoder_lm.prefill_slot(
        cfg, model.params_, cache, jnp.asarray(prompt[None]),
        jnp.asarray(16, jnp.int32), jnp.zeros((), jnp.int32))
    tok, pos = jnp.asarray([7], jnp.int32), jnp.asarray([16], jnp.int32)
    sound = decoder_lm.decode_step(cfg, model.params_, cache, tok, pos)[0]
    n = cfg.n_layers
    for r in range(cfg.passes):
        cut = [tuple(c.at[r * n:(r + 1) * n].set(0) for c in seg)
               for seg in cache]
        got = decoder_lm.decode_step(cfg, model.params_, cut, tok, pos)[0]
        assert np.abs(np.asarray(got - sound)).max() > 1e-3, r
    ids = np.concatenate([prompt, [7]])
    own = np.asarray(ref.logits(tiny(), SEED, ids))[-1]
    shared = np.asarray(ref.logits(tiny(), SEED, ids, kv_from="first"))[-1]
    np.testing.assert_allclose(np.asarray(sound[0]), own, atol=TOL, rtol=0)
    assert np.abs(shared - own).max() > 100 * TOL


def looped(kind, passes=3):
    """One of the five existing tiny kinds with its stack run ``passes``
    times: the sandwich norms and, past one pass, a cache entry a pass."""
    return DecoderLM(DecoderConfig(**{
        **kind_program(kind), "passes": passes, "sandwich_norm": True,
        "max_length": 64})).init()


@pytest.mark.parametrize("kind", OLD_KINDS)
def test_every_kind_of_cache_is_kept_a_pass(kind):
    """Window + full layers (rings a pass), latent slabs, an indexer's
    key slab and a state-space segment (a state a pass) under three
    passes: the forward agrees with a loop written out here over the
    ONE-pass program (the same parameters run three times, each pass
    closed by the final norm), and prefill + cached decode with the
    forward."""
    model = looped(kind)
    cfg = model.cfg
    once = DecoderConfig(**{**kind_program(kind), "sandwich_norm": True,
                            "max_length": 64})
    ids = ids_of({"vocab_size": cfg.vocab_size}, 24, seed=2)[None]

    def loop(params, ids):
        q_pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
        x = decoder_lm._embed(once, params, ids)
        for _ in range(3):
            x = decoder_lm._run_stack(once, params, x, q_pos)[0]
            x = decoder_lm._rms_norm(x, params["norm_f"],
                                     once.norm_eps).astype(x.dtype)
        return decoder_lm._head(cfg, params, x)

    want = np.asarray(jax.jit(loop)(model.params_, jnp.asarray(ids)))
    got = model.logits(ids)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)
    plan, plan1 = cfg.cache_plan(2, 64), once.cache_plan(2, 64)
    assert [p["bytes"] for p in plan] == [3 * p["bytes"] for p in plan1]
    out, logits = model.generate_cached(ids[0, :11], max_new=12,
                                        return_logits=True)
    full = model.logits(out[None, :-1])[0, 10:]
    np.testing.assert_allclose(logits, full, atol=2e-5 * scale, rtol=0)


@pytest.mark.parametrize("kind", [k for k in OLD_KINDS if k != "parallel"])
def test_one_pass_without_output_norms_is_what_it_was(kind):
    """``passes`` 1, no sandwich norm, no gate: the five existing kinds'
    logits and cache plans as the tree before ``passes`` gave them
    (``tests/fixtures/decoder_lm/kinds_before_passes.json``, written by
    that tree): the plan value for value, the logits within an ulp of
    their scale (two builds of XLA:CPU may contract multiply-adds
    differently; on the machine that wrote the fixture they are equal bit
    for bit)."""
    with open(os.path.join(ROOT, "tests", "fixtures", "decoder_lm",
                           "kinds_before_passes.json")) as f:
        before = json.load(f)[kind]
    model = kind_model(kind)
    assert (model.cfg.passes, model.cfg.sandwich_norm,
            model.cfg.exit_gate) == (1, False, False)
    ids = (np.arange(24, dtype=np.int32).reshape(2, 12) * 7
           + 3) % model.cfg.vocab_size
    logits = model.logits(ids)
    scale = before["logits_abs_mean"]
    np.testing.assert_allclose(logits[1, -1, :16], before["logits_last"],
                               atol=2e-5 * scale, rtol=0)
    assert abs(float(np.abs(logits).mean()) - scale) < 1e-6 * scale
    keys = ("kind", "layers", "columns", "ring", "values", "row", "index",
            "bytes", "state", "conv")
    plan = [{**{k: (list(p[k]) if isinstance(p[k], tuple) else p[k])
                for k in keys if k in p},
             "slabs": [list(s) for s in p["slabs"]]}
            for p in model.cfg.cache_plan(3, 64)]
    assert plan == before["plan"]
    assert all(p["passes"] == 1 for p in model.cfg.cache_plan(3, 64))


@pytest.mark.parametrize("threshold", [0.3, 0.7, 1.0])
def test_exit_rule_token_by_token(threshold):
    """Each token's logits come from the first pass at which the cumulated
    exit probability reaches the threshold; at 1.0 from the last pass."""
    cfg = tiny(early_exit_threshold=threshold)
    model = build(cfg)
    assert model.cfg.exit_threshold == threshold
    ids = ids_of(cfg, 48, seed=4)
    _streams, leave = ref.passes(cfg, SEED, ids)
    at = np.asarray(ref.exit_pass(leave, threshold))
    # the rule has something to decide: no cumulated probability within
    # rounding of the threshold, and below 1.0 tokens leave at several passes
    cumulated = 1.0 - np.cumprod(1.0 - np.asarray(leave), axis=0)
    assert np.abs(cumulated[:-1] - threshold).min() > 1e-4
    if threshold < 1.0:
        assert len(set(at.tolist())) >= 2, at
    else:
        assert set(at.tolist()) == {2}
    want = np.asarray(ref.logits(cfg, SEED, ids))
    got = model.logits(ids[None])[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    last = np.asarray(ref.logits(cfg, SEED, ids, threshold=1.0))
    assert (np.abs(want - last).max() > 1e-3) == (threshold < 1.0)


def test_the_backend_refuses_an_exit_threshold_under_one():
    model = build(tiny(early_exit_threshold=0.7))
    with pytest.raises(EarlyExitError, match="exit_threshold=0.7"):
        GenerationEngine(model, n_slots=2, max_length=64)
    with pytest.raises(ValueError, match="every pass"):
        model.generate_cached(ids_of(tiny(), 5), max_new=2)
    with pytest.raises(ValueError, match="exit_gate"):
        DecoderConfig(**{**fam.program_config(tiny()), "exit_gate": False,
                         "exit_threshold": 0.5})

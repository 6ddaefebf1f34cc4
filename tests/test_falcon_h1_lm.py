"""DecoderLM's PARALLEL block (models/decoder_lm.py: ``_Parallel``, an entry
made of ``_KeysValues`` and ``_StateSpace``) against the plain reference of
Falcon-H1 (benchmark/reference/falcon_h1.py) at a tiny size with every
mechanism present: three blocks, each an attention (10 query heads on 2 key
heads: FIVE a key head, rotary over the whole head at theta 1e11) and a
Mamba-2 mixer (8 heads of 8, TWO groups, an inner width of 64 that is not
``mamba_expand`` x hidden = 128, a chunk of 8 that every prompt here crosses)
side by side on ONE normed input, their outputs added; every published
multiplier as data; an untied head. Seeded random weights, logits and not
tokens. The published keys are translated by the benchmark's family module,
as the cell does. The reference scans the recurrence one position at a time;
the program prefills by chunks and decodes one step over a cached state.

Tolerances, at logits that spread by 1.0 and reach 4.2: float32 parameters
give that of float32 summation order (3e-5; 3e-6 is what the two sides
differ by here); bfloat16 ones that of its rounding: eight bits of mantissa
lose 0.4 % a rounding and three blocks of three branches round a dozen
times, 1-2 % of the scale (0.12; 0.046 read). Both lie far under what a
missing branch or multiplier gives: the broken-path controls below, held
to the rehearsal preset's limits as a run is, serve tokens 0.64 to 1.77
under the reference's best at the widest (limit 0.036; sound 0.017) and
0.05 to 0.41 on average (limit 4.5e-4; sound 1.2e-4).
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

from lib import compare  # noqa: E402
from reference import falcon_h1 as ref  # noqa: E402

from deeplearning4j_tpu.models import decoder_lm  # noqa: E402

TOL = {"float32": 3e-5, "bfloat16": 0.12}
SEED = 47


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_families_parallel_hybrid_decoder_lm",
        os.path.join(BENCH, "families", "parallel_hybrid_decoder_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


fam = _family()


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def tiny(dtype="float32", **changes):
    """The rehearsal preset, with ``changes`` to published keys."""
    cfg = _json("configs", "tiny-falcon-h1.json")
    cfg["deployment"]["param_dtype"] = dtype
    cfg.update(changes)
    return cfg


def build(cfg, seed=SEED, **program_changes):
    """The family's model of ``cfg``; ``program_changes`` go to the
    parallel kind's statement as ``DecoderConfig`` takes it."""
    program = fam.program_config(cfg)
    program["attn_kinds"][fam.KIND].update(program_changes)
    model = decoder_lm.DecoderLM.from_dict(program)
    model.params_ = fam.program_params(cfg, seed, model.cfg)
    return model


def ids_of(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg["vocab_size"], (n,))


def reference_logits(cfg, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(cfg, SEED, ids))


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both(request):
    cfg = tiny(request.param)
    return cfg, build(cfg), TOL[request.param]


@pytest.fixture(scope="module")
def base():
    cfg = tiny()
    return cfg, build(cfg)


# -- the whole model ----------------------------------------------------------
def test_the_tiny_preset_keeps_what_the_published_sizes_force():
    cfg = tiny()
    assert cfg["num_attention_heads"] // cfg["num_key_value_heads"] == 5
    assert cfg["mamba_n_groups"] == 2
    assert cfg["mamba_d_ssm"] != cfg["mamba_expand"] * cfg["hidden_size"]
    published = _json("configs", "falcon-h1-34b-l6.json")
    for key in ("attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
                "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
                "embedding_multiplier", "lm_head_multiplier", "rope_theta", "rms_norm_eps"):
        assert cfg[key] == published[key], key


def test_forward_matches_reference(both):
    cfg, model, tol = both
    ids = ids_of(cfg, 43)  # five chunks and a part of a sixth
    want = reference_logits(cfg, ids)
    got = model.logits(ids[None])[0]
    assert np.abs(want).max() > 3.0 and 0.7 < want.std() < 1.4   # logits spread by O(1)
    np.testing.assert_allclose(got, want, atol=tol)


@pytest.mark.parametrize("prompt_len", [2, 8, 29],
                         ids=["below-the-tail", "one-chunk", "several-chunks"])
def test_prefill_then_decode_matches_reference(both, prompt_len):
    """Bucketed prefill (the chunked form beside causal attention; the
    prompt of 2 is shorter than the convolution's tail of 3, the one of 29
    sits in a bucket of 32 whose last chunk is padding in part), then 30
    tokens through the cache (one step of the recurrence and one row of
    attention over the slab each). The logits each token was chosen from
    against the reference's full forward over prompt + tokens."""
    cfg, model, tol = both
    out, logits = model.generate_cached(ids_of(cfg, prompt_len), max_new=30,
                                        return_logits=True)
    want = reference_logits(cfg, out[:-1])[prompt_len - 1:]
    np.testing.assert_allclose(logits, want, atol=tol)


def test_each_branch_adds_a_tenth_to_a_half_of_the_stream(base):
    """What the initialisation is held to, layer by layer: with the
    published multipliers and matrices at 0.02 a branch would add a
    thousandth and a program that dropped it would pass."""
    cfg, _model = base
    with jax.default_matmul_precision("highest"):
        shares = ref.branch_shares(cfg, SEED, ids_of(cfg, 40))
    assert len(shares) == 3
    for layer in shares:
        assert all(0.1 < layer[b] < 0.5 for b in "ASM"), shares


def test_ragged_slots_through_one_cache_and_an_idle_slot_left_bit_for_bit(base):
    """Three slots: prompts of 5 and 19 prefilled into slots 0 and 2, slot 1
    idle with NaN planted in all four slabs. Eight decode steps of the two
    live rows at their own positions give the logits of the reference's
    full forward over each row's prompt + tokens; the idle slot's state and
    tail stay bit for bit (NaN and all) and its NaN reaches no live row."""
    cfg, model = base
    dcfg = model.cfg
    prompts = {0: ids_of(cfg, 5, seed=1), 2: ids_of(cfg, 19, seed=2)}
    prefill = jax.jit(lambda p, c, i, n, s: decoder_lm.prefill_slot(dcfg, p, c, i, n, s))
    step = jax.jit(lambda p, c, t, pos, act: decoder_lm.decode_step(dcfg, p, c, t, pos, act))
    caches = [tuple(c.at[:, 1].set(jnp.nan) for c in seg)
              for seg in decoder_lm.init_cache(dcfg, 3, 64)]
    rows, logits = {}, {}
    for slot, prompt in prompts.items():
        padded = np.zeros((1, 32), np.int32)
        padded[0, :len(prompt)] = prompt
        first, caches = prefill(model.params_, caches, jnp.asarray(padded),
                                jnp.asarray(len(prompt), jnp.int32),
                                jnp.asarray(slot, jnp.int32))
        rows[slot], logits[slot] = list(prompt), [np.asarray(first[0])]
    planted = [[np.asarray(c[:, 1]) for c in seg] for seg in caches]
    active = jnp.asarray([True, False, True])
    for _ in range(8):
        toks = [int(logits[s][-1].argmax()) if s in rows else 0 for s in range(3)]
        pos = [len(rows[s]) if s in rows else 7 for s in range(3)]
        out, caches, _counts = step(model.params_, caches, jnp.asarray(toks, jnp.int32),
                                    jnp.asarray(pos, jnp.int32), active)
        for s in rows:
            rows[s].append(toks[s])
            logits[s].append(np.asarray(out[s]))
    for s, prompt in prompts.items():
        want = reference_logits(cfg, np.asarray(rows[s]))[len(prompt) - 1:]
        np.testing.assert_allclose(np.stack(logits[s]), want, atol=TOL["float32"])
    (_k, _v, state, tail), (_k0, _v0, state0, tail0) = caches[0], planted[0]
    for now, was in ((state, state0), (tail, tail0)):
        now = np.asarray(now[:, 1])
        assert np.isnan(now).all()
        np.testing.assert_array_equal(now.view(np.uint8), was.view(np.uint8))


def test_the_entry_is_made_of_the_two_that_exist(base):
    """One ``_Mixer`` entry whose parts ARE a ``_KeysValues`` and a
    ``_StateSpace``: leaves of both (two output projections, one norm1), a
    plan of four slabs with its bytes by half, ``attends`` and
    ``keeps_state`` both counted."""
    _cfg, model = base
    dcfg = model.cfg
    entry = dcfg.mixer("parallel")
    assert type(entry) is decoder_lm._Parallel
    assert type(entry.attn) is decoder_lm._KeysValues
    assert type(entry.ssm) is decoder_lm._StateSpace
    leaves = decoder_lm.segment_shapes(dcfg, "parallel", "dense")
    assert list(leaves) == ["norm1", "norm2", "Wq", "Wk", "Wv", "Wo", "Win", "conv_w", "conv_b",
                            "dt_bias", "A_log", "D", "norm_g", "Wso", "Wg", "Wu", "Wd"]
    assert leaves["Wo"][0] == (160, 64) and leaves["Wso"][0] == (64, 64)
    assert leaves["Win"][0] == (64, 64 + 128 + 8)        # [z | x B C | dt], inner 64 not 128
    (plan,) = dcfg.cache_plan(3, 64)
    assert plan["slabs"] == [(3, 3, 2, 16, 64), (3, 3, 2, 16, 64), (3, 3, 16, 64), (3, 3, 128, 3)]
    assert [jnp.dtype(d).name for d in plan["dtypes"]] == ["float32"] * 4
    assert (plan["k"], plan["v"], plan["state"], plan["conv"]) == tuple(plan["slabs"])
    assert plan["columns"] == 64 and plan["attends"] and plan["keeps_state"]
    assert plan["bytes_columns"] == 2 * 3 * 3 * 2 * 16 * 64 * 4
    assert plan["bytes_state"] == 3 * 3 * (16 * 64 + 128 * 3) * 4
    assert plan["bytes"] == plan["bytes_columns"] + plan["bytes_state"]
    # the state does not grow with the slot's length; the columns do
    longer = dcfg.cache_plan(3, 128)[0]
    assert longer["bytes_state"] == plan["bytes_state"]
    assert longer["bytes_columns"] == 2 * plan["bytes_columns"]
    bf16 = build(tiny("bfloat16")).cfg
    assert [c.dtype.name for c in decoder_lm.init_cache(bf16, 1, 8)[0]] == [
        "bfloat16", "bfloat16", "float32", "bfloat16"]


def test_the_layer_loop_slices_the_columns_and_carries_the_state(base):
    """The decode program's jaxpr: ONE scan for the segment; the state and
    the tail are its carry (and no stacked output of it), K and V are
    scanned over; the norm and the join run under ``mixer_join``, opened at
    one site, and the branches keep their scopes."""
    _cfg, model = base
    dcfg = model.cfg
    caches = decoder_lm.init_cache(dcfg, 3, 64)
    jaxpr = jax.make_jaxpr(lambda p, c: decoder_lm.decode_step(
        dcfg, p, c, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32)))(
            model.params_, caches)
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    n_carry = scan.params["num_carry"]
    outs = [v.aval.shape for v in scan.outvars]
    for shape in ((3, 3, 16, 64), (3, 3, 128, 3)):
        assert shape in outs[:n_carry] and shape not in outs[n_carry:]
    assert (3, 3, 2, 16, 64) not in outs[:n_carry]
    text = jax.jit(lambda p, c: decoder_lm.decode_step(
        dcfg, p, c, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32))).lower(
            model.params_, caches).as_text(debug_info=True)
    for scope in ("mixer_join", "attn_full", "ssm_proj", "ssm_conv", "ssm_scan", "kv_write"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
    assert "mixer_join" in decoder_lm.SCOPES
    with open(decoder_lm.__file__) as f:
        assert f.read().count('"mixer_join"') == 2      # SCOPES, and the one site


def test_a_prefill_writes_both_halves_under_their_scopes(base):
    _cfg, model = base
    dcfg = model.cfg
    text = jax.jit(lambda p, c: decoder_lm.prefill_slot(
        dcfg, p, c, jnp.zeros((1, 16), jnp.int32), jnp.asarray(9, jnp.int32),
        jnp.asarray(1, jnp.int32))).lower(
            model.params_, decoder_lm.init_cache(dcfg, 3, 64)).as_text(debug_info=True)
    assert "kv_write" in text and "state_write" in text


def test_published_cut_by_arithmetic():
    """The configuration at its published widths, from shapes alone: 430.1 M
    parameters a layer, 1,336.9 M in the embedding and as many in the head,
    10.51 GB in bfloat16 with six layers; a slot's state 4.19 MB a layer
    and its K/V 2,048 B a position and layer; 48 slots x 4,096."""
    cfg = _json("configs", "falcon-h1-34b-l6.json")
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert cfg["published"] == {"num_hidden_layers": 72, "max_position_embeddings": 262144}
    dcfg = decoder_lm.DecoderConfig(**fam.program_config(cfg))
    assert dcfg.segments() == [("parallel", "dense", 6)]
    shapes = decoder_lm.segment_shapes(dcfg, "parallel", "dense")
    count = lambda names: sum(int(np.prod(shapes[n][0])) for n in names)  # noqa: E731
    assert count(["Wq", "Wk", "Wv", "Wo"]) == 5120 * (2560 + 512 + 512) + 2560 * 5120
    assert shapes["Win"][0] == (5120, 9248) and shapes["Wso"][0] == (4096, 5120)
    assert count(["Wg", "Wu", "Wd"]) == 3 * 5120 * 21504
    layer = count(shapes)
    assert round(layer / 1e6, 1) == 430.1
    vocab = 261120 * 5120
    assert round((6 * layer + 2 * vocab) * 2 / 1e9, 2) == 10.51
    traffic = _json("traffic", "docqa-steady.json")
    slots = traffic["engine"]["n_slots"]
    assert slots == 48 and traffic["engine"]["max_length"] == 4096
    (plan,) = dcfg.cache_plan(slots, 4096)
    assert plan["state"] == (6, slots, 256, 4096) and plan["conv"] == (6, slots, 5120, 3)
    assert plan["k"] == plan["v"] == (6, slots, 4, 128, 4096)
    assert plan["bytes_state"] == 6 * slots * (4_194_304 + 5120 * 3 * 2)
    assert plan["bytes_columns"] == 6 * slots * 4096 * 2048
    # weights + cache: 14.14 GB of the chip's 15.75
    assert round(((6 * layer + 2 * vocab) * 2 + plan["bytes"]) / 1e9, 1) == 14.1


@pytest.mark.parametrize("changes", [
    {"n_heads": 7}, {"d_inner": 128}, {"multipliers": [1.0, 2.0]}, {"n_groups": 3}],
    ids=lambda c: next(iter(c)))
def test_a_state_space_statement_that_does_not_add_up_is_refused(changes):
    program = fam.program_config(tiny())
    program["attn_kinds"][fam.KIND]["ssm"].update(changes)
    with pytest.raises(ValueError, match="ssm kind"):
        decoder_lm.DecoderConfig(**program)


def test_a_parallel_kind_without_a_state_space_statement_is_refused():
    program = fam.program_config(tiny())
    del program["attn_kinds"][fam.KIND]["ssm"]
    with pytest.raises(ValueError, match="states no ssm"):
        decoder_lm.DecoderConfig(**program)


@pytest.mark.parametrize("key,value", [("hidden_act", "gelu"), ("attention_bias", True),
                                       ("mamba_norm_before_gate", True),
                                       ("tie_word_embeddings", True), ("mamba_conv_bias", False)])
def test_what_is_not_built_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        fam.program_config(tiny(**{key: value}))


# -- the engine ---------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    cfg = tiny("bfloat16")
    model = build(cfg)
    gen = GenerationEngine(model, n_slots=3, max_length=96, prefill_buckets=[8, 16, 32])
    gen.warmup()
    yield cfg, model, gen
    gen.shutdown(drain=False)


def test_engine_serves_what_the_model_generates_alone(served):
    """Five requests over three slots, so slots are claimed again with
    another request's state and columns in them and rows sit idle beside
    live ones; ``alone`` is the model's own cached generation."""
    cfg, model, engine = served
    traced = dict(engine.trace_counts)
    prompts = [ids_of(cfg, n, seed=n) for n in (5, 9, 20, 31, 2)]
    requests = [engine.submit(p, max_new=24) for p in prompts]
    for prompt, req in zip(prompts, requests):
        got = np.asarray(req.result(timeout=120))
        alone = model.generate_cached(prompt, max_new=24)
        np.testing.assert_array_equal(got[-24:], alone[-24:])
    assert engine.trace_counts == traced  # no program traced after warm-up


def test_engine_counts_positions_read_and_state_slots_for_the_one_segment(served):
    cfg, _model, engine = served
    assert engine.backend.attends and engine.backend.keeps_state
    before = engine.metrics.snapshot()
    engine.submit(ids_of(cfg, 6), max_new=10).result(timeout=120)
    after = engine.metrics.snapshot()
    # token 0 comes from the prefill; nine decode steps advance one live slot
    # each, which has 6, 7, ..., 14 positions behind it
    assert after["decode_steps"] - before["decode_steps"] == 9
    assert after["state_slots"] - before["state_slots"] == 9
    assert after["attn_positions_read"] - before["attn_positions_read"] == sum(range(6, 15))
    text = engine.metrics.registry.prometheus_text()
    assert "generation_state_slots_total" in text
    assert "generation_attn_positions_read_total" in text


def test_memory_report_lists_the_entrys_bytes_by_half(served):
    from deeplearning4j_tpu.serving.generate import generation_memory_report

    _cfg, model, engine = served
    report = generation_memory_report(model, n_slots=3, max_length=96)
    state = 3 * 3 * (16 * 64 * 4 + 128 * 3 * 2)           # float32 state, bfloat16 tail
    slab = 2 * 3 * 3 * 2 * 16 * 96 * 2
    assert (report["state_bytes"], report["slab_bytes"]) == (state, slab)
    assert report["cache_bytes"] == state + slab == engine.backend.cache_bytes
    (entry,) = report["cache_plan"]
    assert (entry["kind"], entry["layers"], entry["columns"]) == ("parallel", 3, 96)
    assert (entry["bytes_state"], entry["bytes_columns"]) == (state, slab)
    assert entry["state"] == (3, 3, 16, 64) and entry["conv"] == (3, 3, 128, 3)
    assert engine.describe()["memory"]["cache_plan"] == report["cache_plan"]


@pytest.mark.parametrize("asked", [{"prefix_cache_mb": 1}, {"spec_decode_k": 4}],
                         ids=["prefix-cache", "speculation"])
def test_prefix_cache_and_speculation_are_refused_as_for_any_state(base, asked):
    from deeplearning4j_tpu.serving.generate import GenerationEngine, RecurrentStateError

    _cfg, model = base
    with pytest.raises(RecurrentStateError, match="state"):
        GenerationEngine(model, n_slots=2, max_length=64, **asked)


# -- broken-path controls -----------------------------------------------------
LIMITS = _json("limits", "tiny-falcon-h1.tiny-docqa.json")["limits"]
TRAFFIC = _json("traffic", "tiny-docqa.json")


def served_numbers(model, cfg):
    """What a run compares, from the model's own cached generation of six
    prompts of one bucket (greedy, 24 tokens each), through the family's
    ``reference_serve`` as the kind calls it."""
    samples = []
    for n in (9, 11, 12, 13, 15, 16):
        prompt = ids_of(cfg, n, seed=100 + n)
        out = model.generate_cached(prompt, max_new=24)
        samples.append({"prompt": [int(t) for t in prompt],
                        "tokens": [int(t) for t in out[n:]]})
    with jax.default_matmul_precision("highest"):
        got = fam.reference_serve(cfg, TRAFFIC, SEED, samples)
    return {"requests_failed": 0.0, "served_logit_gap": got["served_logit_gap"],
            "served_logit_gap_mean": got["served_logit_gap_mean"]}


def _without(branch):
    """``branch`` (a ``_Branches.branch``) with its output zeroed: what it
    caches is still made, what it adds to the stream is not."""
    def dropped(self, bp, a_in, q_pos, view, token_mask):
        out, made, wrote = branch(self, bp, a_in, q_pos, view, token_mask)
        return jnp.zeros_like(out), made, wrote
    return dropped


def _norm_over_all_channels(sound):
    """``_rms_norm`` whose grouped call (the gated norm's: x (b, T, groups,
    channels), gain (groups, channels)) runs over all channels at once."""
    def flat(x, g, eps):
        if x.ndim == 4 and getattr(g, "ndim", 0) == 2:
            b, t, groups, c = x.shape
            return sound(x.reshape(b, t, 1, groups * c), g.reshape(1, groups * c),
                         eps).reshape(x.shape)
        return sound(x, g, eps)
    return flat


def test_the_sound_program_is_within_the_tiny_limits():
    cfg = tiny("bfloat16")
    correct, lines = compare.judge(served_numbers(build(cfg), cfg), LIMITS)
    assert correct, lines


@pytest.mark.parametrize("broken", ["no-attention-branch", "no-state-space-branch",
                                    "no-key-multiplier", "gated-norm-over-all-channels"])
def test_a_broken_path_fails_the_tiny_limits(monkeypatch, broken):
    """The ``benchmark/tests/test_broken_path.py`` pattern on this block: a
    program that leaves a branch, a multiplier or the grouping of the gated
    norm out serves tokens whose logits the reference refuses, by the
    limits the rehearsal preset is judged by."""
    cfg = tiny("bfloat16")
    changes = {}
    if broken == "no-attention-branch":
        monkeypatch.setattr(decoder_lm._KeysValues, "branch",
                            _without(decoder_lm._KeysValues.branch))
    elif broken == "no-state-space-branch":
        monkeypatch.setattr(decoder_lm._StateSpace, "branch",
                            _without(decoder_lm._StateSpace.branch))
    elif broken == "no-key-multiplier":
        changes = {"key_multiplier": 1.0}
    else:
        monkeypatch.setattr(decoder_lm, "_rms_norm",
                            _norm_over_all_channels(decoder_lm._rms_norm))
    correct, lines = compare.judge(served_numbers(build(cfg, **changes), cfg), LIMITS)
    assert not correct, lines

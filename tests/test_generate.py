"""Continuous-batching generation tests (serving/generate.py + the
models/transformer_lm.py decode-path rework behind it).

The acceptance spine: a request decoded in the slotted engine among
other requests is BIT-IDENTICAL to the same request decoded alone
(``generate_cached``) and to the full-prefix reference (``generate``);
steady-state decode traces ZERO new XLA programs after warmup; slots
free at token granularity on completion AND mid-decode deadline; the
LSTM carried-state path matches the full-sequence forward. Plus the
satellite contracts: fused on-device sampling parity, bucketed-prefill
retrace guard, the typed context-window error, slab memory validation,
and flight-recorder slot lifecycle events.
"""

import gc
import http.client
import json
import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer_lm import (
    ContextWindowExceeded,
    TransformerLM,
    _sample_next,
    prefill_bucket_lengths,
    sample_next_device,
    sample_next_rows,
    sampling_needs,
)
from deeplearning4j_tpu.serving import (
    GenerationEngine,
    GenerationMemoryError,
    RequestDeadlineExceeded,
    ServerOverloadedError,
    ServerShutdownError,
)


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Same discipline as test_serving.py: drop this module's compiled
    executables when done (short-lived engines on a cramped CPU host).
    The module-shared engines are shut down first: an idle engine's loop
    records a phase every 50 ms into the process-wide ring, which a later
    test file in the same worker reads (``test_obs.py``'s bounded ring)."""
    yield
    for shared in (_ENG, _SPEC, _BF16):
        while shared:
            shared.popitem()[1].shutdown(drain=False)
    gc.collect()
    jax.clear_caches()


_LM = {}


def _lm() -> TransformerLM:
    """Module-shared tiny LM (one build, one compile set)."""
    if "m" not in _LM:
        m = TransformerLM(vocab_size=48, d_model=32, n_heads=2, n_layers=2,
                          max_length=48, seed=5).init()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 48, (4, 24)).astype(np.int32)
        tgt = np.roll(ids, -1, 1).astype(np.int32)
        tgt[:, -1] = -1
        for _ in range(3):
            m.fit_batch(ids, tgt)
        _LM["m"] = m
    return _LM["m"]


def _prompts(n, lens=(3, 21), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 48, (int(rng.integers(*lens)),)).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# in-graph sampler
# ---------------------------------------------------------------------------
# the in-graph sampler as it was before it branched on the batch's
# policies (PR 28), kept as the plain reference: filter always, draw
# always, keep argmax where temperature <= 0
def _unbranched_filter(logits, temperature, top_k, top_p):
    jnp = jax.numpy
    V = logits.shape[-1]

    def col(x):
        return x if jnp.ndim(x) == 0 else x[:, None]

    t = jnp.where(temperature > 0, temperature, 1.0)
    l = logits / col(t)
    k_eff = jnp.clip(top_k, 1, V)
    use_k = (top_k > 0) & (top_k < V)
    sorted_asc = jnp.sort(l, axis=-1)
    kth = jnp.take_along_axis(
        sorted_asc, jnp.broadcast_to(col(V - k_eff), (l.shape[0], 1)),
        axis=-1)
    l = jnp.where(col(use_k) & (l < kth), -jnp.inf, l)
    use_p = (top_p > 0.0) & (top_p < 1.0)
    order = jnp.argsort(-l, axis=-1)
    sl = jnp.take_along_axis(l, order, -1)
    p_sorted = jnp.exp(sl - sl.max(-1, keepdims=True))
    p_sorted = p_sorted / p_sorted.sum(-1, keepdims=True)
    cum = jnp.cumsum(p_sorted, -1)
    cut = cum - p_sorted >= col(top_p)
    sl = jnp.where(col(use_p) & cut, -jnp.inf, sl)
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(sl, inv, -1)


def _unbranched_device(logits, temperature, top_k, top_p, key):
    jnp = jax.numpy
    l = _unbranched_filter(logits, temperature, top_k, top_p)
    key, sub = jax.random.split(key)
    sampled = jax.random.categorical(sub, l)
    nxt = jnp.where(temperature <= 0, jnp.argmax(logits, axis=-1), sampled)
    return nxt.astype(jnp.int32), key


def _unbranched_rows(logits, temperature, top_k, top_p, keys):
    jnp = jax.numpy
    l = _unbranched_filter(logits, temperature, top_k, top_p)
    splits = jax.vmap(jax.random.split)(keys)
    nkeys, subs = splits[:, 0], splits[:, 1]
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row[None])[0])(subs, l)
    nxt = jnp.where(temperature <= 0, jnp.argmax(logits, axis=-1), sampled)
    return nxt.astype(jnp.int32), nkeys


#: id -> per-row (temperature, top_k, top_p, active) over four rows, and
#: what ``sampling_needs`` must say of them: every policy alone, a batch
#: that mixes them, and a filtered row whose result is thrown away
_POLICY_MIXES = {
    "all_greedy": ([0.0] * 4, [0] * 4, [0.0] * 4, [True] * 4,
                   (False, False)),
    "temperature_only": ([0.7, 1.3, 0.5, 0.7], [0] * 4, [0.0] * 4,
                         [True] * 4, (True, False)),
    # a top_k of the whole vocabulary and a top_p of 1 cut nothing
    "filters_that_cut_nothing": ([0.7, 1.3, 0.5, 0.7], [0, 32, 40, 0],
                                 [0.0, 1.0, 0.0, 1.0], [True] * 4,
                                 (True, False)),
    "top_k": ([0.7, 1.3, 0.5, 0.9], [5, 1, 9, 31], [0.0] * 4, [True] * 4,
              (True, True)),
    "top_p": ([0.7, 1.3, 0.5, 0.9], [0] * 4, [0.9, 0.5, 0.1, 0.99],
              [True] * 4, (True, True)),
    "top_k_and_top_p": ([0.7, 1.3, 0.5, 0.9], [5, 3, 9, 31],
                        [0.9, 0.5, 0.1, 0.99], [True] * 4, (True, True)),
    "mixed": ([0.0, 0.8, 1.1, 0.6], [0, 0, 4, 0], [0.0, 0.0, 0.0, 0.8],
              [True] * 4, (True, True)),
    "greedy_and_temperature_only": ([0.0, 0.8, 0.0, 1.2], [0] * 4,
                                    [0.0] * 4, [True] * 4, (True, False)),
    "filtered_row_inactive": ([0.0, 0.0, 0.9, 0.0], [0, 0, 5, 0],
                              [0.0, 0.0, 0.7, 0.0],
                              [True, True, False, True], (False, False)),
    "drawing_row_inactive": ([0.0, 0.9, 0.9, 0.0], [0, 0, 5, 0],
                             [0.0, 0.0, 0.7, 0.0],
                             [True, True, False, True], (True, False)),
}


class TestDeviceSampler:
    def _logits(self, b=3, V=32, seed=4):
        return np.random.default_rng(seed).standard_normal(
            (b, V)).astype(np.float32)

    def test_greedy_matches_host(self):
        logits = self._logits()
        host, _ = _sample_next(logits, 0.0, 0, 0.0, jax.random.PRNGKey(0))
        dev, _ = sample_next_device(jax.numpy.asarray(logits), 0.0, 0, 0.0,
                                    jax.random.PRNGKey(0))
        np.testing.assert_array_equal(host, np.asarray(dev))

    def test_temperature_top_k_matches_host(self):
        logits = self._logits()
        for temp, k in ((0.7, 0), (1.3, 5), (0.5, 1)):
            host, _ = _sample_next(logits.copy(), temp, k, 0.0,
                                   jax.random.PRNGKey(9))
            dev, _ = sample_next_device(jax.numpy.asarray(logits),
                                        temp, k, 0.0, jax.random.PRNGKey(9))
            np.testing.assert_array_equal(host, np.asarray(dev))

    def test_key_chain_matches_host(self):
        # the advanced key must follow the host's split(rng)[0] chain so
        # fused decoding reproduces generate()'s sampled trajectory
        logits = self._logits()
        _, host_rng = _sample_next(logits, 0.8, 0, 0.0,
                                   jax.random.PRNGKey(3))
        _, dev_key = sample_next_device(jax.numpy.asarray(logits), 0.8, 0,
                                        0.0, jax.random.PRNGKey(3))
        np.testing.assert_array_equal(
            np.asarray(host_rng), np.asarray(dev_key))

    def test_top_p_restricts_support(self):
        # tolerance-documented vs host (cumsum order); assert the
        # in-graph nucleus SEMANTICS: tiny p → argmax support only
        logits = self._logits()
        toks = set()
        for s in range(8):
            dev, _ = sample_next_device(jax.numpy.asarray(logits[:1]), 1.0,
                                        0, 1e-6, jax.random.PRNGKey(s))
            toks.add(int(np.asarray(dev)[0]))
        assert toks == {int(logits[0].argmax())}

    @pytest.mark.parametrize("rows", [False, True],
                             ids=["sample_next_device", "sample_next_rows"])
    @pytest.mark.parametrize("mix", sorted(_POLICY_MIXES))
    def test_branches_bit_identical_to_unbranched(self, mix, rows):
        # the sampler runs only what the rows in front of it ask for
        # (argmax alone; scale + draw; filter + draw) and must hand every
        # row that is kept the id and the key of the sampler that always
        # did everything, whatever the other rows ask for
        from deeplearning4j_tpu.serving.generate import _counted

        jnp = jax.numpy
        t, k, pp, active, needs = _POLICY_MIXES[mix]
        t, k, pp = (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
                    jnp.asarray(pp, jnp.float32))
        active = jnp.asarray(active)
        # a host copy of the same policies tells the same branch
        assert tuple(map(bool, sampling_needs(
            np.where(np.asarray(active), np.asarray(t), 0.0),
            np.asarray(k), np.asarray(pp), 32))) == needs
        assert tuple(map(bool, sampling_needs(
            _counted(t, active), k, pp, 32))) == needs
        new, old = ((sample_next_rows, _unbranched_rows) if rows
                    else (sample_next_device, _unbranched_device))

        def step(fn, temperature):
            # what the decode programs keep of a step: a row that is
            # not active keeps its token and its key
            def run(logits, toks, keys):
                nxt, nkeys = fn(logits, temperature, k, pp, keys)
                if rows:
                    nkeys = jnp.where(active[:, None], nkeys, keys)
                return jnp.where(active, nxt, toks), nkeys
            return jax.jit(run)

        toks = jnp.arange(4, dtype=jnp.int32)
        branched, unbranched = step(new, _counted(t, active)), step(old, t)
        for seed in range(6):
            logits = jnp.asarray(self._logits(b=4, seed=seed))
            keys = (jax.vmap(jax.random.PRNGKey)(jnp.arange(4) + 10 * seed)
                    if rows else jax.random.PRNGKey(seed))
            got = branched(logits, toks, keys)
            want = unbranched(logits, toks, keys)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("policy", [(0.0, 0, 0.0), (0.8, 0, 0.0),
                                        (0.8, 5, 0.0), (0.8, 0, 0.9),
                                        (0.8, 5, 0.9)],
                             ids=["greedy", "temperature", "top_k", "top_p",
                                  "top_k_top_p"])
    def test_scalar_policy_bit_identical_to_unbranched(self, policy):
        # one policy for the batch, as generate_cached and the prefills
        # hand it: scalars, not rows
        jnp = jax.numpy
        pol = (jnp.asarray(policy[0], jnp.float32),
               jnp.asarray(policy[1], jnp.int32),
               jnp.asarray(policy[2], jnp.float32))
        branched = jax.jit(sample_next_device)
        unbranched = jax.jit(_unbranched_device)
        for seed in range(4):
            logits = jnp.asarray(self._logits(seed=seed))
            key = jax.random.PRNGKey(seed)
            got = branched(logits, *pol, key)
            want = unbranched(logits, *pol, key)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# fused generate_cached (satellites 1-3)
# ---------------------------------------------------------------------------
class TestGenerateCachedFused:
    def test_greedy_parity_across_buckets(self):
        m = _lm()
        for tp in (3, 9, 17, 30):
            prompt = _prompts(1, (tp, tp + 1), seed=tp)[0]
            np.testing.assert_array_equal(
                m.generate(prompt, max_new=6),
                m.generate_cached(prompt, max_new=6))

    def test_prefill_bucketing_bounds_program_count(self):
        # the _jit_cache["prefill"] leak this replaces: one program per
        # DISTINCT prompt length. Now: one per BUCKET.
        m = TransformerLM(vocab_size=32, d_model=32, n_heads=2, n_layers=1,
                          max_length=48, seed=1).init()
        buckets = m.prefill_buckets()
        assert buckets == prefill_bucket_lengths(48, m.serving_seq_buckets)
        for tp in (3, 5, 7, 9, 11, 13):  # all land in the 16 bucket
            m.generate_cached(np.arange(tp, dtype=np.int32), max_new=2)
        assert m.trace_counts.get("prefill") == 1
        assert m.trace_counts.get("decode") == 1
        m.generate_cached(np.arange(20, dtype=np.int32), max_new=2)
        assert m.trace_counts.get("prefill") == 2  # the 32 bucket
        assert m.trace_counts.get("decode") == 1  # decode never re-traces

    def test_context_window_typed_error(self):
        m = _lm()
        with pytest.raises(ContextWindowExceeded, match="max_length") as ei:
            m.generate_cached(np.arange(40, dtype=np.int32), max_new=20)
        assert isinstance(ei.value, ValueError)  # transport maps to 400
        assert ei.value.prompt_len == 40
        assert ei.value.max_new == 20
        assert ei.value.max_length == 48

    def test_window_error_raised_before_sampling_validation(self):
        # the old ordering validated sampling args first, so an
        # overflowing request with bad sampling args reported the wrong
        # failure; the window is the outermost contract
        m = _lm()
        with pytest.raises(ContextWindowExceeded):
            m.generate_cached(np.arange(40, dtype=np.int32), max_new=20,
                              top_k=-3)

    def test_max_new_zero_returns_prompt(self):
        m = _lm()
        prompt = np.arange(5, dtype=np.int32)
        np.testing.assert_array_equal(
            m.generate_cached(prompt, max_new=0), prompt[None])


# ---------------------------------------------------------------------------
# the continuous-batching engine (tentpole)
# ---------------------------------------------------------------------------
_ENG = {}


def _engine() -> GenerationEngine:
    """Module-shared engine over the shared LM, warmed once."""
    if "e" not in _ENG:
        e = GenerationEngine(_lm(), n_slots=3, queue_limit=32,
                             default_timeout_s=120.0)
        e.warmup()
        _ENG["e"] = e
    return _ENG["e"]


class TestGenerationEngine:
    def test_mixed_length_storm_three_way_parity(self):
        # join/leave at token granularity: 8 requests with mixed prompt
        # lengths AND mixed max_new over 3 slots — completions free
        # slots mid-storm and queued requests join between steps. Every
        # output must be bit-identical to solo generate_cached AND to
        # the full-prefix generate reference.
        m, eng = _lm(), _engine()
        rng = np.random.default_rng(7)
        prompts = _prompts(8, (3, 21), seed=7)
        news = [int(rng.integers(3, 12)) for _ in prompts]
        before = dict(eng.trace_counts)
        reqs = [eng.submit(p, max_new=n, timeout=90)
                for p, n in zip(prompts, news)]
        outs = [r.result(timeout=90) for r in reqs]
        assert eng.trace_counts == before  # zero steady-state retraces
        for p, n, out in zip(prompts, news, outs):
            np.testing.assert_array_equal(out, m.generate_cached(
                p, max_new=n)[0])
            np.testing.assert_array_equal(out, m.generate(p, max_new=n)[0])

    def test_sampled_parity_with_solo_by_seed(self):
        m, eng = _lm(), _engine()
        prompt = _prompts(1, seed=3)[0]
        out = eng.submit(prompt, max_new=5, temperature=0.8, top_k=4,
                         seed=13, timeout=90).result(timeout=90)
        solo = m.generate_cached(prompt, max_new=5, temperature=0.8,
                                 top_k=4, rng=jax.random.PRNGKey(13))[0]
        np.testing.assert_array_equal(out, solo)

    def test_sampler_branch_counters_follow_the_active_policies(self):
        # sample_drawn_steps / sample_filtered_steps: the decode steps in
        # which the in-graph sampler had to draw / to sort, counted on
        # the host from the slots' policies
        eng = _engine()

        def counted(run):
            before = eng.metrics.snapshot()
            run()
            after = eng.metrics.snapshot()
            return {k: after[k] - before[k]
                    for k in ("decode_steps", "sample_drawn_steps",
                              "sample_filtered_steps")}

        prompts = _prompts(4, (3, 12), seed=21)

        def greedy_storm():
            for r in [eng.submit(p, max_new=6, timeout=90) for p in prompts]:
                r.result(timeout=90)

        d = counted(greedy_storm)
        assert d["decode_steps"] >= 5
        assert d["sample_drawn_steps"] == d["sample_filtered_steps"] == 0

        def storm_with_one_top_p():
            # three slots: the top-p request decodes 3 steps beside two
            # greedy ones that go on for 8 more; its freed slot keeps
            # its policy on the host and must count for nothing
            reqs = [eng.submit(prompts[0], max_new=4, temperature=0.8,
                               top_p=0.9, seed=3, timeout=90)]
            reqs += [eng.submit(p, max_new=12, timeout=90)
                     for p in prompts[1:3]]
            for r in reqs:
                r.result(timeout=90)

        d = counted(storm_with_one_top_p)
        assert d["sample_drawn_steps"] == d["sample_filtered_steps"] == 3
        assert d["decode_steps"] >= 11

        def temperature_only():
            eng.submit(prompts[3], max_new=5, temperature=0.7, seed=4,
                       timeout=90).result(timeout=90)

        d = counted(temperature_only)
        assert (d["decode_steps"], d["sample_drawn_steps"],
                d["sample_filtered_steps"]) == (4, 4, 0)
        text = eng.metrics.registry.prometheus_text()
        assert "generation_sample_drawn_steps_total" in text
        assert "generation_sample_filtered_steps_total" in text

    def test_streaming_matches_result(self):
        eng = _engine()
        prompt = _prompts(1, seed=5)[0]
        req = eng.submit(prompt, max_new=6, timeout=90)
        streamed = list(req.stream(timeout=90))
        full = req.result(timeout=5)
        assert streamed == full[len(prompt):].tolist()
        assert len(streamed) == 6

    def test_deadline_mid_decode_frees_slot(self):
        eng = _engine()
        prompt = _prompts(1, seed=9)[0]
        max_new = 48 - len(prompt)  # fill the window: a long decode
        # the deadline passes at the third launch: with a step in flight
        # this model's 38 tokens take some 12 ms, so no wall-clock
        # deadline lies surely between the claim and the last token
        real, calls = eng.backend.launch, {"n": 0}

        def launch():
            calls["n"] += 1
            if calls["n"] == 3:
                req.deadline = 0.0
            return real()

        eng.backend.launch = launch
        try:
            with eng._dev_lock:
                req = eng.submit(prompt, max_new=max_new, timeout=90)
            with pytest.raises(RequestDeadlineExceeded):
                req.result(timeout=90)
        finally:
            del eng.backend.launch  # the class's own again
        assert 0 < len(req.tokens) < max_new  # died mid-decode, not queued
        deadline = time.monotonic() + 10
        while eng.active_slots and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.active_slots == 0  # the slot came back
        # and the freed slot serves the next request normally
        out = eng.submit(prompt, max_new=3, timeout=90).result(timeout=90)
        assert out.shape[0] == len(prompt) + 3

    def test_window_overflow_typed_at_submit(self):
        eng = _engine()
        with pytest.raises(ContextWindowExceeded, match="max_length"):
            eng.submit(np.arange(40, dtype=np.int32), max_new=20)

    def test_decode_failure_fails_active_typed_and_engine_survives(self):
        # a decode dispatch blowing up (bad hot-swapped params, device
        # error) must fail the ACTIVE requests typed — not silently
        # kill the worker thread — and the engine must serve the next
        # request normally (slab rebuilt after the donated buffers died
        # with the failed dispatch)
        m = _lm()
        eng = GenerationEngine(m, n_slots=2, queue_limit=8,
                               default_timeout_s=60.0)
        try:
            eng.warmup()
            real = eng.backend.launch
            boom = {"armed": True}

            def exploding(*a, **kw):
                if boom["armed"]:
                    boom["armed"] = False
                    raise RuntimeError("injected decode failure")
                return real(*a, **kw)

            eng.backend.launch = exploding
            prompt = _prompts(1, seed=41)[0]
            with pytest.raises(RuntimeError, match="injected"):
                eng.submit(prompt, max_new=8, timeout=60).result(timeout=60)
            # worker alive, slot freed, slab rebuilt: next request works
            out = eng.submit(prompt, max_new=4, timeout=60).result(timeout=60)
            np.testing.assert_array_equal(
                out, m.generate_cached(prompt, max_new=4)[0])
        finally:
            eng.shutdown()

    def test_decode_watchdog_fails_hung_dispatch_typed(self):
        # a decode dispatch that HANGS (vs one that raises — the case
        # above) wedges the worker thread where the except-clause can
        # never run; the watchdog must fail the active requests typed
        # and record the escalated stall, instead of every caller
        # hanging with the worker
        from deeplearning4j_tpu.obs import flight
        from deeplearning4j_tpu.serving import DecodeStalledError

        m = _lm()
        eng = GenerationEngine(m, n_slots=2, queue_limit=8,
                               default_timeout_s=60.0,
                               watchdog_mult=2.0, watchdog_min_s=0.3)
        try:
            eng.warmup()
            real = eng.backend.launch
            hang = {"armed": True}

            def hung(*a, **kw):
                if hang["armed"]:
                    hang["armed"] = False
                    time.sleep(1.5)  # well past the watchdog limit
                return real(*a, **kw)

            eng.backend.launch = hung
            prompt = _prompts(1, seed=51)[0]
            t0 = time.monotonic()
            with pytest.raises(DecodeStalledError, match="stuck"):
                eng.submit(prompt, max_new=8, timeout=60).result(timeout=60)
            # the caller unblocked while the dispatch was still hung
            assert time.monotonic() - t0 < 1.4
            evs = flight.default_flight_recorder().events()
            assert any(e["kind"] == "decode_stall" and e.get("escalated")
                       for e in evs)
            # engine recovers once the hung dispatch returns: slab
            # rebuilt, next request decodes normally
            out = eng.submit(prompt, max_new=4, timeout=60).result(
                timeout=60)
            np.testing.assert_array_equal(
                out, m.generate_cached(prompt, max_new=4)[0])
        finally:
            eng.shutdown()

    def test_overload_typed(self):
        # 1-slot engine with a 1-deep queue: the third concurrent
        # request must reject typed, not block
        m = _lm()
        eng = GenerationEngine(m, n_slots=1, queue_limit=1,
                               default_timeout_s=60.0)
        try:
            held = []
            for i in range(2):
                held.append(eng.submit(_prompts(1, seed=i)[0], max_new=30,
                                       timeout=60))
                # let the worker drain the queue into the slot before the
                # next submit (admission capacity = slots + queue depth,
                # but only after the pop — don't race it)
                t_end = time.monotonic() + 10
                while (i == 0 and eng.queue_depth()
                       and time.monotonic() < t_end):
                    time.sleep(0.005)
            with pytest.raises(ServerOverloadedError):
                for i in range(20):  # at most 1 admits before the check
                    eng.submit(_prompts(1, seed=90 + i)[0], max_new=30,
                               timeout=60)
            for r in held:
                r.result(timeout=60)
        finally:
            eng.shutdown()

    def test_memory_limit_typed_at_build(self):
        with pytest.raises(GenerationMemoryError, match="n_slots"):
            GenerationEngine(_lm(), n_slots=2, memory_limit_bytes=1)

    def test_memory_report_shape(self):
        rep = _engine().memory_report
        assert rep["cache_bytes"] > 0
        assert rep["param_bytes"] > 0
        assert rep["param_copy_bytes"] == 0  # float32 compute: no copy
        assert rep["total_bytes"] == rep["cache_bytes"] + rep["param_bytes"]
        rep = _bf16_engine().memory_report
        copy = _bf16_engine().backend._params()
        assert rep["param_copy_bytes"] == sum(
            a.nbytes for a in jax.tree_util.tree_leaves(copy)
            if a.dtype != np.float32) > 0
        assert rep["total_bytes"] == (rep["cache_bytes"] + rep["param_bytes"]
                                      + rep["param_copy_bytes"])

    def test_flight_events_slot_lifecycle(self):
        from deeplearning4j_tpu.obs.flight import default_flight_recorder

        rec = default_flight_recorder()
        mark = rec.recorded_total
        eng = _engine()
        eng.submit(_prompts(1, seed=21)[0], max_new=3,
                   timeout=90).result(timeout=90)
        # recorded_total is the NEXT seq to assign: new events are >= it
        new = [e for e in rec.events() if e.get("seq", 0) >= mark]
        kinds = {e["kind"] for e in new}
        assert "slot_claim" in kinds
        assert "slot_free" in kinds
        claim = next(e for e in new if e["kind"] == "slot_claim")
        assert claim["prompt_len"] > 0 and claim["prompt_bucket"] > 0
        free = next(e for e in new if e["kind"] == "slot_free")
        assert free["reason"] == "done" and free["tokens"] == 3

    def test_rtrace_timeline_stages(self):
        eng = _engine()
        req = eng.submit(_prompts(1, seed=23)[0], max_new=3, timeout=90,
                         trace=True)
        req.result(timeout=90)
        tl = req.trace.timeline()
        stages = [s["stage"] for s in tl["stages"]]
        assert stages == ["queue", "prefill", "decode", "respond"]
        assert tl["tokens"] == 3
        assert tl["slot"] is not None
        assert tl["total_ms"] == pytest.approx(
            sum(s["ms"] for s in tl["stages"]), abs=0.1)

    def test_shutdown_drains_then_rejects(self):
        eng = GenerationEngine(_lm(), n_slots=2, queue_limit=8,
                               default_timeout_s=60.0)
        reqs = [eng.submit(_prompts(1, seed=31 + i)[0], max_new=4,
                           timeout=60) for i in range(4)]
        eng.shutdown(drain=True)
        for r in reqs:
            assert r.result(timeout=10).shape[0] > 0  # drained, served
        with pytest.raises(ServerShutdownError):
            eng.submit(_prompts(1, seed=40)[0], max_new=2)

    def test_describe(self):
        d = _engine().describe()
        assert d["backend"] == "transformer"
        assert d["n_slots"] == 3
        assert d["prefill_buckets"][-1] == 48
        assert "generation_decode" in d["trace_counts"]


# ---------------------------------------------------------------------------
# host phases of the worker loop, queue wait, named scopes
# ---------------------------------------------------------------------------
class TestEnginePhases:
    """obs/trace.py phases inside GenerationEngine._loop in its lock-step
    order (``_step``; here an engine with a prefix cache, which keeps a
    ``TransformerLM``'s slots' inputs on the host; the order with a step
    in flight is ``tests/test_generate_ahead.py``'s): they nest or
    follow one another, cover a loop iteration, stay within the budget a
    decode iteration is given, carry their decode step's id as their
    cause, and cost no retrace."""

    @pytest.fixture(scope="class")
    def storm(self):
        from deeplearning4j_tpu.obs import trace as obs_trace

        # large enough that a decode step outweighs the loop's own
        # bookkeeping, as on the chip: some tens of microseconds a step
        # lie between one phase's exit and the next one's entry
        lm = TransformerLM(vocab_size=256, d_model=768, n_heads=4,
                           n_layers=6, max_length=64, seed=3).init()
        eng = GenerationEngine(lm, n_slots=2, queue_limit=16,
                               default_timeout_s=120.0, prefix_cache_mb=4.0)
        assert not eng._ahead
        eng.warmup()
        traced = dict(eng.trace_counts)
        mark = time.time_ns()
        rng = np.random.default_rng(1)
        reqs = [eng.submit(rng.integers(0, 256, (12,)).astype(np.int32),
                           max_new=20, timeout=120, trace=True)
                for _ in range(4)]
        for r in reqs:
            r.result(timeout=120)
        time.sleep(0.05)
        ring = [e for e in obs_trace.phases(mark) if e[0].startswith("gen.")]
        # this engine's own: the module's other engines idle beside it
        caused = [e for e in obs_trace.caused_phases(mark)
                  if e[0].startswith("gen.") and e[3] is not None
                  and e[3] >> 32 == eng._dispatch_gen >> 32]
        yield eng, reqs, ring, traced, caused
        eng.shutdown(drain=False)

    @staticmethod
    def _steps(caused):
        """{step id: [its entries, in ring order]}, the queue waits and
        idle waits left out."""
        steps = {}
        for e in caused:
            if e[0] not in ("gen.queue_wait", "gen.idle_wait"):
                steps.setdefault(e[3], []).append(e)
        return steps

    def test_named_phases_nest_and_cover_the_loop(self, storm):
        from tests.phase_checks import assert_nested_or_disjoint, covered_ns

        _eng, _reqs, ring, _, _ = storm
        loop = [e for e in ring if e[0] not in ("gen.queue_wait",
                                                "gen.idle_wait")]
        assert {e[0] for e in loop} == {
            "gen.admit", "gen.prefill", "gen.prefill.put", "gen.decode.put",
            "gen.decode.dispatch", "gen.decode.fetch", "gen.emit",
            "gen.turn"}
        assert_nested_or_disjoint(loop)
        # the busy stretch: the first step's put to the last token handed
        # out (the claims before the first step follow an idle wait, which
        # is no turn); with ``gen.turn`` the phases tile it
        lo = min(a for n, a, _ in loop if n == "gen.decode.put")
        hi = max(a + d for n, a, d in loop if n == "gen.emit")
        assert covered_ns(loop, lo, hi) >= 0.99 * (hi - lo)

    def test_phase_budget_of_a_decode_iteration(self, storm):
        _eng, _reqs, _ring, _, caused = storm
        # an iteration is what one step id caused: the turn with the
        # claims made in it, then put, dispatch, fetch, emit
        steps = self._steps(caused)
        assert len(steps) >= 20 and None not in steps
        for entries in steps.values():
            names = [e[0] for e in entries]
            claims = names.count("gen.admit")
            assert len(names) <= 5 + 3 * claims, names
            if claims <= 1:
                assert len(names) <= 9, names
            assert names[-4:] == ["gen.decode.put", "gen.decode.dispatch",
                                  "gen.decode.fetch", "gen.emit"]
            assert names.count("gen.turn") <= 1
            if "gen.turn" in names:
                assert names[-5] == "gen.turn"

    def test_a_step_id_joins_a_decode_steps_phases(self, storm):
        eng, _reqs, _ring, _, caused = storm
        steps = self._steps(caused)
        ids = sorted(steps)
        # the engine's own ids, counting up by one from its base
        assert ids == list(range(ids[0], ids[0] + len(ids)))
        assert ids[-1] == eng._dispatch_gen
        for step in ids:
            by_name = {}
            for e in steps[step]:
                by_name.setdefault(e[0], []).append(e)
            for name in ("gen.decode.put", "gen.decode.dispatch",
                         "gen.decode.fetch", "gen.emit"):
                assert len(by_name[name]) == 1, (step, name)
            put, emit = by_name["gen.decode.put"][0], by_name["gen.emit"][0]
            assert put[1] + put[2] <= by_name["gen.decode.dispatch"][0][1]
            assert by_name["gen.decode.fetch"][0][1] <= emit[1]
            # a claim's phases carry the id of the step they precede
            for name in ("gen.admit", "gen.prefill", "gen.prefill.put"):
                for e in by_name.get(name, ()):
                    assert e[1] + e[2] <= put[1], (step, name)
            assert len(by_name.get("gen.admit", ())) == \
                len(by_name.get("gen.prefill", ()))
        waits = [e for e in caused if e[0] == "gen.queue_wait"]
        claims = [e for e in caused if e[0] == "gen.admit"]
        assert sorted(e[3] for e in waits) == sorted(e[3] for e in claims)
        assert len(claims) == 4

    def test_turn_runs_from_an_emit_to_the_next_put(self, storm):
        from tests.phase_checks import assert_nested_or_disjoint

        _eng, _reqs, _ring, _, caused = storm
        steps = self._steps(caused)
        turns = [e for e in caused if e[0] == "gen.turn"]
        assert len(turns) >= 20
        for _, a, d, step in turns:
            # from the end of the step before to this step's put
            emit = [e for e in steps[step - 1] if e[0] == "gen.emit"][0]
            put = [e for e in steps[step] if e[0] == "gen.decode.put"][0]
            assert emit[1] + emit[2] <= a
            assert a + d <= put[1]
            # nothing of the loop's between them but the turn itself
            assert a - (emit[1] + emit[2]) < 200_000
            assert put[1] - (a + d) < 200_000
            # the claims made in a turn lie inside it, whole
            for e in steps[step]:
                if e[0] == "gen.admit":
                    assert a <= e[1] and e[1] + e[2] <= a + d
        # a turn that claimed: slots free up while requests still queue
        assert any(e[0] == "gen.admit" and any(
            t[3] == e[3] for t in turns) for e in caused)
        idle = [e for e in caused if e[0] == "gen.idle_wait"]
        assert_nested_or_disjoint([e[:3] for e in turns + idle])
        for _, a, d, _ in idle:
            assert not any(t[1] < a + d and a < t[1] + t[2] for t in turns)

    def test_queue_wait_is_the_rtrace_queue_stage(self, storm):
        eng, reqs, ring, _, _ = storm
        waits_ms = sorted(r.trace.timeline()["stages"][0]["ms"] for r in reqs)
        assert [r.trace.timeline()["stages"][0]["stage"]
                for r in reqs] == ["queue"] * 4
        # two slots, four requests at once: two waited for a slot to free
        assert waits_ms[-1] > 5 * waits_ms[0]
        snap = eng.metrics.snapshot()
        # 0.95 of four observations is the fourth
        assert snap["queue_wait_p95_ms"] == pytest.approx(waits_ms[-1],
                                                          abs=2e-3)
        assert snap["queue_wait_p50_ms"] == pytest.approx(waits_ms[2],
                                                          abs=2e-3)
        observed = sorted(d * 1e-6 for n, _, d in ring
                          if n == "gen.queue_wait")
        assert observed == pytest.approx(waits_ms, abs=2e-3)
        assert "generation_queue_wait_seconds" in \
            eng.metrics.registry.prometheus_text()

    def test_phases_cost_no_retrace(self, storm):
        eng, _reqs, _ring, traced, _ = storm
        assert eng.trace_counts == traced

    def test_two_engines_do_not_share_step_ids(self, storm):
        from deeplearning4j_tpu.obs import trace as obs_trace

        eng, _reqs, _ring, _, caused = storm
        other = _engine()
        assert other._dispatch_gen >> 32 != eng._dispatch_gen >> 32
        mark = time.time_ns()
        before = other._dispatch_gen
        out = other.submit(_prompts(1, seed=77)[0], max_new=5,
                           timeout=60).result(timeout=60)
        assert out.size > 0
        time.sleep(0.05)
        mine = [e for e in obs_trace.caused_phases(mark)
                if e[0].startswith("gen.decode.")]
        assert mine and all(before < e[3] <= other._dispatch_gen
                            for e in mine)
        assert not {e[3] for e in mine} & {e[3] for e in caused}

    def test_verify_and_draft_carry_the_steps_id(self):
        """K > 1 through the truncated draft: a step's draft rollout and
        its verify are two dispatches of one step, under one id."""
        from deeplearning4j_tpu.obs import trace as obs_trace

        eng = _bf16_engine()
        mark = time.time_ns()
        before = eng._dispatch_gen
        eng.submit(_prompts(1, (8, 12), seed=78)[0], max_new=12,
                   timeout=90).result(timeout=90)
        time.sleep(0.05)
        steps = self._steps(
            [e for e in obs_trace.caused_phases(mark)
             if e[0].startswith("gen.") and e[3] is not None
             and before < e[3] <= eng._dispatch_gen])
        assert steps
        speculated = 0
        for step, entries in steps.items():
            names = [e[0] for e in entries if e[0] != "gen.turn"
                     and not e[0].startswith(("gen.admit", "gen.prefill"))]
            one = ["gen.decode.put", "gen.decode.dispatch",
                   "gen.decode.fetch"]
            assert names in (one + ["gen.emit"], one + one + ["gen.emit"]), (
                step, names)
            speculated += names == one + one + ["gen.emit"]
        assert speculated >= 1

    @pytest.mark.parametrize("inputs", ["on_the_host", "on_the_device"])
    def test_scope_names_in_the_engine_programs(self, storm, inputs):
        import jax.numpy as jnp

        if inputs == "on_the_host":
            b = storm[0].backend
            S = b.n_slots
            small = (jnp.zeros((S,), jnp.float32),
                     jnp.zeros((S,), jnp.int32),
                     jnp.zeros((S,), jnp.float32))
            decode = b._decode_fn.lower(
                b.model.params_, b._kc, b._vc, jnp.zeros((S,), jnp.int32),
                jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool), *small,
                jnp.zeros((S, 2), jnp.uint32)).as_text(debug_info=True)
            prefill = b._prefill_fn.lower(
                b.model.params_, b._kc, b._vc, b._dkc, b._dvc,
                jnp.zeros((1, 16), jnp.int32), jnp.asarray(5, jnp.int32),
                jnp.asarray(0, jnp.int32), jnp.asarray(0.0, jnp.float32),
                jnp.asarray(0, jnp.int32), jnp.asarray(0.0, jnp.float32),
                jnp.zeros((2,), jnp.uint32)).as_text(debug_info=True)
        else:
            # the programs of the backend that launches ahead
            eng = GenerationEngine(_lm(), n_slots=2)
            try:
                b = eng.backend
                assert hasattr(b, "launch")
                state = jnp.zeros((b.n_slots + 1, 8), jnp.int32)
                decode = b._decode_fn.lower(
                    b.model.params_, b._kc, b._vc,
                    state).as_text(debug_info=True)
                prefill = b._prefill_fn.lower(
                    b.model.params_, b._kc, b._vc, state,
                    jnp.zeros((8 + 16,), jnp.int32)).as_text(debug_info=True)
            finally:
                eng.shutdown()
        from tests.phase_checks import scopes_in

        want = {"embed", "attn", "kv_write", "mlp", "head", "sample"}
        assert want <= scopes_in(decode)
        assert want <= scopes_in(prefill)


# ---------------------------------------------------------------------------
# speculative decoding + shared-prefix KV reuse
# ---------------------------------------------------------------------------
_SPEC = {}


def _spec_engine() -> GenerationEngine:
    """Module-shared speculating engine (K=4 proposal lane + prefix
    cache) over the shared LM, warmed once."""
    if "e" not in _SPEC:
        e = GenerationEngine(_lm(), n_slots=3, queue_limit=32,
                             default_timeout_s=120.0, spec_decode_k=4,
                             prefix_cache_mb=2.0)
        e.warmup()
        _SPEC["e"] = e
    return _SPEC["e"]


class TestSpeculativePrefix:
    def test_four_way_greedy_parity_zero_retrace(self):
        # the fourth parity leg: the SPECULATING engine — drafts
        # proposed and sometimes rejected, prefix hits replacing
        # prefills on the repeat round — must stay bit-identical to the
        # plain engine, to solo generate_cached, and to the full-prefix
        # reference, and trace NOTHING after warmup (verify dispatches
        # and prefix-hit restores included)
        m, plain, spec = _lm(), _engine(), _spec_engine()
        prompts = _prompts(6, (3, 21), seed=16)
        news = [9, 5, 12, 7, 4, 10]
        before = dict(spec.trace_counts)
        reqs = [spec.submit(p, max_new=n, timeout=90)
                for p, n in zip(prompts, news)]
        outs = [r.result(timeout=90) for r in reqs]
        # resubmit the same prompts: every admission is now a prefix HIT
        reqs2 = [spec.submit(p, max_new=n, timeout=90)
                 for p, n in zip(prompts, news)]
        outs2 = [r.result(timeout=90) for r in reqs2]
        assert spec.trace_counts == before  # zero retraces, spec on
        assert spec.describe()["prefix_cache"]["hits"] >= len(prompts)
        for p, n, out, out2 in zip(prompts, news, outs, outs2):
            np.testing.assert_array_equal(out, out2)
            np.testing.assert_array_equal(
                out,
                plain.submit(p, max_new=n, timeout=90).result(timeout=90))
            np.testing.assert_array_equal(
                out, m.generate_cached(p, max_new=n)[0])
            np.testing.assert_array_equal(out, m.generate(p, max_new=n)[0])

    @pytest.mark.parametrize("which", ["plain", "ngram_drafts",
                                       "truncated_drafts"])
    def test_last_token_on_the_slab_edge_after_a_longer_occupant(self,
                                                                 which):
        # prompt + max_new == max_length: the last token lands on the
        # slab's column T-1 and, speculating, the last verify blocks
        # reach past it (those columns are dropped, the real write
        # stays; the truncated draft model chains steps over the edge).
        # Every slot was first filled to its end by another request, and
        # prefill writes only the new prompt's bucket of columns: what
        # the earlier occupant left behind it must never be read.
        m = _lm()
        if which == "truncated_drafts":
            eng = GenerationEngine(m, n_slots=3, queue_limit=32,
                                   default_timeout_s=120.0,
                                   spec_decode_k=4, draft_mode="truncated")
            eng.warmup()
        else:
            eng = _engine() if which == "plain" else _spec_engine()
        T = eng.max_length
        try:
            assert eng.backend._kc.shape[-1] == T  # time is the minor axis
            fill = _prompts(3, (30, 40), seed=31)
            for r in [eng.submit(p, max_new=T - p.size, timeout=90)
                      for p in fill]:
                assert r.result(timeout=90).size == T
            before = dict(eng.trace_counts)
            short = _prompts(3, (3, 9), seed=32)
            outs = [r.result(timeout=90) for r in
                    [eng.submit(p, max_new=T - p.size, timeout=90)
                     for p in short]]
            assert eng.trace_counts == before
            for p, out in zip(short, outs):
                assert out.size == T
                np.testing.assert_array_equal(
                    out, m.generate_cached(p, max_new=T - p.size)[0])
                np.testing.assert_array_equal(
                    out, m.generate(p, max_new=T - p.size)[0])
        finally:
            if which == "truncated_drafts":
                eng.shutdown()

    def test_sampled_key_chain_parity_with_rejection(self):
        # sampled path: rejected drafts must not desync the per-slot
        # PRNG chain — the key advances once per EMITTED token, so a
        # seeded spec request reproduces the solo trajectory exactly
        m, spec = _lm(), _spec_engine()
        prompt = _prompts(1, seed=17)[0]
        req = spec.submit(prompt, max_new=8, temperature=0.9, top_k=6,
                          seed=23, timeout=90)
        out = req.result(timeout=90)
        solo = m.generate_cached(prompt, max_new=8, temperature=0.9,
                                 top_k=6, rng=jax.random.PRNGKey(23))[0]
        np.testing.assert_array_equal(out, solo)

    def test_completion_replay_high_acceptance_on_repeat(self):
        # a prefix hit replays the prompt's recorded first greedy
        # completion as its draft source: near-total acceptance on the
        # repeat, far beyond what the n-gram table manages cold
        eng = _spec_engine()
        prompt = _prompts(1, (10, 11), seed=77)[0]
        first = eng.generate(prompt, max_new=16, timeout=90)
        req = eng.submit(prompt, max_new=16, timeout=90)
        np.testing.assert_array_equal(first, req.result(timeout=90))
        assert req.draft_proposed > 0
        assert req.draft_accepted >= 0.8 * req.draft_proposed

    def test_prefix_hit_miss_evict_lifecycle(self):
        from deeplearning4j_tpu.obs.flight import default_flight_recorder

        m = _lm()
        # budget fits exactly ONE bucket-32 KV block: the second
        # distinct prompt LRU-evicts the first, re-requesting the first
        # is a miss again
        eng = GenerationEngine(m, n_slots=2, queue_limit=8,
                               default_timeout_s=60.0,
                               prefix_cache_mb=0.02)
        try:
            eng.warmup()
            rec = default_flight_recorder()
            mark = rec.recorded_total
            p1 = _prompts(1, (20, 21), seed=61)[0]
            p2 = _prompts(1, (20, 21), seed=62)[0]
            a1 = eng.generate(p1, max_new=4, timeout=60)  # miss: captured
            b1 = eng.generate(p1, max_new=4, timeout=60)  # hit
            np.testing.assert_array_equal(a1, b1)
            eng.generate(p2, max_new=4, timeout=60)  # miss: evicts p1
            eng.generate(p1, max_new=4, timeout=60)  # miss again
            d = eng.describe()["prefix_cache"]
            assert (d["lookups"], d["hits"], d["entries"]) == (4, 1, 1)
            assert 0 < d["bytes"] <= d["limit_bytes"]
            new = [e for e in rec.events() if e.get("seq", 0) >= mark]
            assert any(e["kind"] == "prefix_hit" for e in new)
            assert any(e["kind"] == "prefix_evict"
                       and e["reason"] == "lru" for e in new)
            claims = [e for e in new if e["kind"] == "slot_claim"]
            assert [c["prefix_hit"] for c in claims] == [
                False, True, False, False]
        finally:
            eng.shutdown()

    def test_deadline_mid_verify_frees_slot(self):
        # deadline expiry lands between verify dispatches exactly like
        # between plain decode steps: already-accepted tokens kept,
        # slot freed at token granularity, engine serves the next
        # request normally
        eng = _spec_engine()
        prompt = _prompts(1, seed=19)[0]
        max_new = 48 - len(prompt)
        req = eng.submit(prompt, max_new=max_new, timeout=0.02)
        with pytest.raises(RequestDeadlineExceeded):
            req.result(timeout=90)
        assert 0 < len(req.tokens) < max_new  # died mid-decode
        deadline = time.monotonic() + 10
        while eng.active_slots and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.active_slots == 0
        out = eng.submit(prompt, max_new=3, timeout=90).result(timeout=90)
        assert out.shape[0] == len(prompt) + 3


# ---------------------------------------------------------------------------
# the serving copy: weights cast once, not in every program
# ---------------------------------------------------------------------------
_BF16 = {}


def _bf16_lm(seed=5, **kw) -> TransformerLM:
    return TransformerLM(vocab_size=48, d_model=32, n_heads=2, n_layers=2,
                         max_length=48, seed=seed,
                         compute_dtype="bfloat16", **kw).init()


def _bf16_engine() -> GenerationEngine:
    """Module-shared engine on float32 masters under bf16 compute, with
    every program the backend has: K = 3 verify, the truncated draft."""
    if "e" not in _BF16:
        e = GenerationEngine(_bf16_lm(), n_slots=3, queue_limit=32,
                             default_timeout_s=120.0, spec_decode_k=3,
                             draft_mode="truncated")
        e.warmup()
        _BF16["e"] = e
    return _BF16["e"]


def _bf16_ahead_engine() -> GenerationEngine:
    """Module-shared engine on the same masters with K = 1 and no prefix
    cache: the backend that keeps the slots' inputs on the device."""
    if "ahead" not in _BF16:
        e = GenerationEngine(_bf16_lm(), n_slots=3, queue_limit=32,
                             default_timeout_s=120.0)
        e.warmup()
        _BF16["ahead"] = e
    return _BF16["ahead"]


def _casts_to_bf16(jaxpr) -> int:
    """float32 -> bfloat16 ``convert_element_type``s in a jaxpr and the
    jaxprs its equations carry (a scan's body, a jit's)."""
    import jax.numpy as jnp

    n = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "convert_element_type"
                and eqn.params["new_dtype"] == jnp.bfloat16
                and eqn.invars[0].aval.dtype == jnp.float32):
            n += 1
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                n += _casts_to_bf16(inner)
    return n


class TestServingCopy:
    """``_TransformerBackend`` hands its programs ``serving_copy`` (the
    block matrices and the head cast once) in place of the float32
    masters: bitwise the same results, one cast a changed tree."""

    @pytest.mark.parametrize("program", ["prefill", "decode", "verify",
                                         "draft", "prefill_on_device",
                                         "decode_on_device"])
    def test_programs_bitwise_as_on_the_masters(self, program):
        # each program the two backends have, on the copy and on the
        # float32 masters themselves (where it casts per call, as it did
        # for every dispatch before there was a copy), from one slab state
        import jax.numpy as jnp

        on_device = program.endswith("_on_device")
        eng = _bf16_ahead_engine() if on_device else _bf16_engine()
        b, S, K = eng.backend, eng.n_slots, eng.spec_decode_k
        assert hasattr(b, "launch") == on_device
        masters, copy = b.model.params_, b._params()
        assert copy["head"].dtype == jnp.bfloat16
        assert masters["head"].dtype == jnp.float32
        key = np.asarray(jax.random.PRNGKey(0))
        prompts = _prompts(S, (5, 15), seed=11)
        with eng._dev_lock:  # the idle worker dispatches nothing
            for slot, prompt in enumerate(prompts):
                b.prefill(slot, prompt, 0.0, 0, 0.0, key,
                          *((5,) if on_device else ()))
            pos = jnp.asarray([p.shape[0] for p in prompts], jnp.int32)

            def slabs():  # the programs consume the slabs they are given
                return [jnp.copy(a) for a in (b._kc, b._vc, b._dkc, b._dvc)]

            toks = jnp.asarray([7, 8, 9], jnp.int32)
            on = jnp.ones((S,), bool)
            policy = (jnp.zeros((S,), jnp.float32),
                      jnp.zeros((S,), jnp.int32),
                      jnp.zeros((S,), jnp.float32))
            keys = jnp.zeros((S, 2), jnp.uint32)

            def run(p):
                kc, vc, dkc, dvc = slabs()
                if program == "prefill_on_device":
                    req = np.zeros((8 + 16,), np.int32)
                    b._state(req[:8], 1, 11, 5, 0.0, 0, 0.0, key)
                    req[8:] = np.arange(16)
                    return b._prefill_fn(p, kc, vc, b._slots_state,
                                         jnp.asarray(req))
                if program == "decode_on_device":
                    # the three slots' rows as their prefills left them
                    return b._decode_fn(p, kc, vc, b._slots_state)
                if program == "prefill":
                    return b._prefill_fn(
                        p, kc, vc, dkc, dvc,
                        jnp.asarray(np.arange(16)[None], jnp.int32),
                        jnp.asarray(11, jnp.int32),
                        jnp.asarray(1, jnp.int32),
                        *(a[0] for a in policy), keys[0])
                if program == "decode":
                    return b._decode_fn(p, kc, vc, toks, pos, on, *policy,
                                        keys)
                if program == "verify":
                    return b._verify_fn(
                        p, kc, vc,
                        jnp.asarray([[7, 1, 2], [8, 3, 4], [9, 5, 6]],
                                    jnp.int32),
                        jnp.full((S,), K - 1, jnp.int32), pos, on, *policy,
                        keys)
                return b._draft_fn(p, dkc, dvc, toks, pos, on)

            got, want = run(copy), run(masters)
        assert len(got) == len(want)
        for g, w in zip(got, want):  # tokens, logits, keys, slabs
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_a_params_swap_is_one_cast_and_no_retrace(self):
        m, other = _bf16_lm(seed=5), _bf16_lm(seed=6)
        eng = GenerationEngine(m, n_slots=2, default_timeout_s=120.0)
        ref = GenerationEngine(other, n_slots=2, default_timeout_s=120.0)
        try:
            eng.warmup()
            assert eng.metrics.snapshot()["param_casts"] == 1
            traced = dict(eng.trace_counts)
            prompt = _prompts(1, seed=4)[0]
            first = eng.submit(prompt, max_new=8).result(timeout=90)
            eng.submit(prompt, max_new=8).result(timeout=90)
            # sixteen steps and two prefills on unchanged weights
            assert eng.metrics.snapshot()["param_casts"] == 1
            m.params_ = other.params_
            swapped = eng.submit(prompt, max_new=8).result(timeout=90)
            eng.submit(prompt, max_new=8).result(timeout=90)
            assert eng.metrics.snapshot()["param_casts"] == 2
            assert eng.trace_counts == traced
            np.testing.assert_array_equal(
                swapped, ref.submit(prompt, max_new=8).result(timeout=90))
            assert not np.array_equal(swapped, first)
            assert "generation_param_casts_total 2" in \
                eng.metrics.registry.prometheus_text()
        finally:
            eng.shutdown()
            ref.shutdown()

    def test_a_swap_under_a_step_in_flight_is_served_within_two_steps(self):
        """``params_`` is swapped when three tokens are out and step 3 is
        in flight: the next LAUNCH (step 4) reads the new weights, two
        steps after the last token the caller saw, where the lock-step
        loop swapped before its step 4 serves the same: one cast, no
        trace, no second executable."""
        other = _bf16_lm(seed=6)
        prompt = _prompts(1, seed=4)[0]

        def swapped_before_step_four(seam, **engine):
            m = _bf16_lm(seed=5)
            eng = GenerationEngine(m, n_slots=2, default_timeout_s=120.0,
                                   **engine)
            try:
                eng.warmup()
                assert eng._ahead == (seam == "launch")
                first = eng.submit(prompt, max_new=9).result(timeout=90)
                traced, seen = dict(eng.trace_counts), {"calls": 0}
                real = getattr(eng.backend, seam)

                def step(*args):
                    seen["calls"] += 1
                    if seen["calls"] == 4:
                        seen["tokens_out"] = len(req.tokens)
                        m.params_ = other.params_
                    return real(*args)

                setattr(eng.backend, seam, step)
                with eng._dev_lock:
                    req = eng.submit(prompt, max_new=9)
                out = req.result(timeout=90)
                assert eng.metrics.snapshot()["param_casts"] == 2
                assert eng.trace_counts == traced
                assert eng.backend._decode_fn._cache_size() == 1
                return first, out, seen["tokens_out"]
            finally:
                eng.shutdown()

        first, ahead, out_at_swap = swapped_before_step_four("launch")
        assert out_at_swap == 3  # the prefill's and two steps'; one flies
        _, lock_step, out_at_swap = swapped_before_step_four(
            "decode", prefix_cache_mb=1.0)
        assert out_at_swap == 4
        np.testing.assert_array_equal(ahead, lock_step)
        # the prompt, the prefill's token and three steps' on the old
        # weights, then the new weights' tokens
        n = len(prompt) + 4
        np.testing.assert_array_equal(ahead[:n], first[:n])
        assert not np.array_equal(ahead[n:], first[n:])

    @pytest.mark.parametrize("kind", ["dense", "moe", "float32"])
    def test_copy_casts_what_the_block_and_head_cast(self, kind):
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.transformer_lm import (
            TransformerLMConfig,
            forward,
            init_params,
            serving_copy,
        )

        cfg = TransformerLMConfig(
            vocab_size=48, d_model=32, n_heads=2, n_layers=2, max_length=16,
            n_experts=4 if kind == "moe" else 0,
            compute_dtype=None if kind == "float32" else "bfloat16")
        masters = init_params(cfg)
        copy = serving_copy(cfg, masters)
        ids = jnp.asarray(np.arange(12).reshape(2, 6), jnp.int32)

        def casts(p):
            return _casts_to_bf16(
                jax.make_jaxpr(lambda q: forward(cfg, q, ids))(p).jaxpr)

        if kind == "float32":
            assert copy is masters and casts(masters) == 0
            return
        cast = {"Wq", "Wk", "Wv", "Wo", "bo", "W1", "b1", "W2", "b2"} \
            | ({"Wg"} if kind == "moe" else set())
        for name, leaf in masters["blocks"].items():
            if name in cast:
                np.testing.assert_array_equal(
                    np.asarray(copy["blocks"][name]),
                    np.asarray(leaf.astype(jnp.bfloat16)))
            else:
                assert copy["blocks"][name] is leaf, name
        for name in ("embed", "pos", "lnf_g", "lnf_b"):
            assert copy[name] is masters[name], name
        assert copy["head"].dtype == jnp.bfloat16
        assert set(copy) == set(masters)
        assert set(copy["blocks"]) == set(masters["blocks"])
        # each of them is a cast the forward no longer makes, and it
        # makes no other of a parameter: what is left is activations'
        assert casts(masters) - casts(copy) == len(cast) + 1
        assert serving_copy(cfg, copy) is copy
        np.testing.assert_array_equal(
            np.asarray(forward(cfg, copy, ids)),
            np.asarray(forward(cfg, masters, ids)))

    def test_copy_keeps_the_masters_shardings(self):
        from deeplearning4j_tpu.parallel.serving_mesh import ServingMesh
        from deeplearning4j_tpu.serving.sharded import (
            sharded_generation_engine,
        )

        mesh = ServingMesh(batch=2, model=4, devices=jax.devices()[:8])
        lm = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                           max_length=48, seed=9,
                           compute_dtype="bfloat16").init()
        eng = sharded_generation_engine(lm, mesh, n_slots=4, max_length=48)
        try:
            masters = eng.backend.model.params_
            copy = eng.backend._params()
            flat_m = jax.tree_util.tree_leaves_with_path(masters)
            flat_c = jax.tree_util.tree_leaves(copy)
            assert any("model" in str(m.sharding.spec) for _, m in flat_m)
            for (path, m), c in zip(flat_m, flat_c):
                assert c.sharding.is_equivalent_to(m.sharding, m.ndim), path
            assert eng.memory_report["param_copy_bytes"] == sum(
                c.nbytes for (_, m), c in zip(flat_m, flat_c) if c is not m)
            # and the programs take it as placed: traced once each
            prompt = np.asarray([5, 9, 11, 2], np.int32)
            toks = eng.submit(prompt, max_new=4).result(timeout=240)
            traced = dict(eng.trace_counts)
            np.testing.assert_array_equal(
                toks, eng.submit(prompt, max_new=4).result(timeout=240))
            assert eng.trace_counts == traced
            assert eng.metrics.snapshot()["param_casts"] == 1
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# LSTM carried-state backend
# ---------------------------------------------------------------------------
class TestRecurrentGeneration:
    @pytest.fixture(scope="class")
    def net(self):
        from deeplearning4j_tpu.models.textgen_lstm import TextGenerationLSTM

        return TextGenerationLSTM(num_classes=12, units=16).init()

    def _host_greedy(self, net, prompt, max_new):
        """Reference: re-run the FULL sequence forward per token."""
        seq = list(int(t) for t in prompt)
        for _ in range(max_new):
            x = np.zeros((1, len(seq), 12), np.float32)
            x[0, np.arange(len(seq)), seq] = 1.0
            y = net.output(x)
            seq.append(int(y[0, -1].argmax()))
        return np.asarray(seq, np.int32)

    def test_carried_state_parity_vs_full_forward(self, net):
        eng = GenerationEngine(net, n_slots=2, max_length=64,
                               queue_limit=16, default_timeout_s=90.0)
        try:
            eng.warmup()
            before = dict(eng.trace_counts)
            rng = np.random.default_rng(2)
            cases = []
            for i in range(4):
                tp = int(rng.integers(3, 14))
                prompt = rng.integers(0, 12, (tp,)).astype(np.int32)
                mn = int(rng.integers(3, 8))
                cases.append((prompt, mn,
                              eng.submit(prompt, max_new=mn, timeout=90)))
            for prompt, mn, req in cases:
                np.testing.assert_array_equal(
                    req.result(timeout=90),
                    self._host_greedy(net, prompt, mn))
            assert eng.trace_counts == before  # recurrent path: 0 too
            assert eng.backend.kind == "recurrent"
        finally:
            eng.shutdown()

    def test_unsupported_model_typed(self):
        from deeplearning4j_tpu.nn.conf import (
            InputType,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = (NeuralNetConfiguration.builder().seed(1).list()
                .layer(DenseLayer(n_out=4, activation="relu"))
                .layer(OutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(3)).build())
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(TypeError, match="incremental-decode"):
            GenerationEngine(net, n_slots=1)


# ---------------------------------------------------------------------------
# HTTP front-end
# ---------------------------------------------------------------------------
def _http(port, method, path, body=None, timeout=90):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(method, path,
                 None if body is None else json.dumps(body))
    resp = conn.getresponse()
    raw = resp.read()
    conn.close()
    return resp, raw


class TestGenerateHTTP:
    @pytest.fixture(scope="class")
    def served(self):
        from deeplearning4j_tpu.serving import (
            BucketPolicy,
            InferenceEngine,
            InferenceServer,
        )

        m = _lm()
        gen = _engine()
        eng = InferenceEngine(m, buckets=BucketPolicy(batch_buckets=[1]))
        srv = InferenceServer(eng, port=0, generation=gen).start()
        yield srv, m
        # detach the shared engine before server shutdown would drain it
        srv.generation = None
        srv.shutdown()

    def test_generate_non_stream_parity(self, served):
        srv, m = served
        resp, raw = _http(srv.port, "POST", "/generate",
                          {"prompt": [1, 2, 3], "max_new": 5,
                           "stream": False})
        assert resp.status == 200
        body = json.loads(raw)
        solo = m.generate_cached(np.asarray([1, 2, 3], np.int32),
                                 max_new=5)[0]
        assert body["sequence"] == solo.tolist()
        assert body["tokens"] == solo[3:].tolist()

    def test_generate_stream_chunks(self, served):
        srv, m = served
        resp, raw = _http(srv.port, "POST", "/generate",
                          {"prompt": [4, 5, 6, 7], "max_new": 4})
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "application/x-ndjson"
        lines = [json.loads(ln) for ln in
                 raw.decode().strip().split("\n")]
        toks = [ln["token"] for ln in lines[:-1]]
        assert lines[-1]["done"] is True
        assert lines[-1]["tokens"] == toks
        solo = m.generate_cached(np.asarray([4, 5, 6, 7], np.int32),
                                 max_new=4)[0]
        assert toks == solo[4:].tolist()

    def test_generate_window_overflow_400(self, served):
        srv, _ = served
        resp, raw = _http(srv.port, "POST", "/generate",
                          {"prompt": list(range(40)), "max_new": 20,
                           "stream": False})
        assert resp.status == 400
        assert json.loads(raw)["error"] == "ContextWindowExceeded"

    def test_generate_bad_payload_400(self, served):
        srv, _ = served
        resp, raw = _http(srv.port, "POST", "/generate", {"max_new": 3})
        assert resp.status == 400

    def test_healthz_and_metrics_expose_generation(self, served):
        srv, _ = served
        resp, raw = _http(srv.port, "GET", "/healthz")
        info = json.loads(raw)["generation"]
        assert info["backend"] == "transformer"
        resp, raw = _http(srv.port, "GET", "/metrics")
        gen = json.loads(raw)["generation"]
        assert gen["tokens"] > 0
        assert gen["slots"] == 3

    def test_generate_409_without_engine(self):
        from deeplearning4j_tpu.serving import (
            BucketPolicy,
            InferenceEngine,
            InferenceServer,
        )

        eng = InferenceEngine(_lm(), buckets=BucketPolicy(batch_buckets=[1]))
        srv = InferenceServer(eng, port=0).start()
        try:
            resp, raw = _http(srv.port, "POST", "/generate",
                              {"prompt": [1], "max_new": 2})
            assert resp.status == 409
            assert json.loads(raw)["error"] == "NoGenerationEngine"
        finally:
            srv.shutdown()


def teardown_module(module):
    for held in (_ENG, _BF16):
        while held:
            held.popitem()[1].shutdown()
    _LM.clear()

"""The decode loop that keeps one step in flight (``GenerationEngine.
_step_ahead`` over a backend's ``launch`` / ``collect``): the tokens
are the lock-step engine's, which are the model's own; a stop only the
host can decide costs one thrown-away slot-step; a step that raises or
hangs with another in flight fails the active requests typed and the
engine serves on; the pipeline fills at the first claim and drains at
idle. Over ``_DecoderBackend`` (a dense, an expert, a latent, a
sparse-latent and a state-space ``DecoderLM``, ``tests/decoder_kinds.py``)
and over ``_TransformerAheadBackend`` (a dense and an expert
``TransformerLM``); the engines that stay lock-step are the last test
but one."""

import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer_lm import TransformerLM
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.serving import DecodeStalledError
from deeplearning4j_tpu.serving.batcher import (
    RequestDeadlineExceeded,
    ServerShutdownError,
)
from deeplearning4j_tpu.serving.generate import GenerationEngine
from tests.decoder_kinds import KINDS, decoder_lm

POLICIES = {"greedy": {}, "top_k": dict(temperature=0.8, top_k=4),
            "top_p": dict(temperature=1.1, top_p=0.7)}


#: ``TransformerLM``s behind ``_TransformerAheadBackend``; the expert one
#: with room for every routed pair (capacity_factor = n_experts), so that a
#: slot's tokens do not depend on who shares its step
GPT2_KINDS = {"gpt2-dense": {}, "gpt2-expert": dict(n_experts=4, top_k=2,
                                                    capacity_factor=4.0)}


def _model(kind):
    if kind in KINDS:
        return decoder_lm(kind)
    return TransformerLM(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                         max_length=128, seed=3, **GPT2_KINDS[kind]).init()


@pytest.fixture(scope="module", params=sorted(KINDS) + sorted(GPT2_KINDS))
def served(request):
    model = _model(request.param)
    eng = GenerationEngine(model, n_slots=2, max_length=96,
                           prefill_buckets=[8, 16, 32], queue_limit=16,
                           default_timeout_s=120.0)
    eng.warmup()
    yield model, eng
    eng.shutdown(drain=False)


def _prompt(model, n, seed):
    return np.random.default_rng(seed).integers(
        0, model.cfg.vocab_size, (n,)).astype(np.int32)


def _alone(model, prompt, max_new, seed=0, **policy):
    out = model.generate_cached(prompt, max_new=max_new,
                                rng=jax.random.PRNGKey(seed), **policy)
    return np.asarray(out).reshape(-1)[len(prompt):]


def _retraced(model, eng, traced):
    """The programs traced since ``traced`` was taken, less what the
    model's kind may trace in steady state: an expert ``TransformerLM``'s
    prefill is not bucketed (a program a prompt length)."""
    new = {k for k, v in eng.trace_counts.items() if v != traced.get(k, 0)}
    if isinstance(model, TransformerLM) and model.cfg.n_experts > 0:
        new.discard("generation_prefill")
    return new


def _counted(eng, run, keys=("decode_steps", "decode_steps_ahead",
                             "late_slot_steps", "state_slots",
                             "latent_positions_read", "tokens")):
    before = eng.metrics.snapshot()
    out = run()
    after = eng.metrics.snapshot()
    return out, {k: after[k] - before[k] for k in keys}


def _settle(eng, timeout=30.0):
    """Wait until the loop is idle: no slot held, no step in flight."""
    deadline = time.monotonic() + timeout
    while (eng.active_slots or eng._flight) and time.monotonic() < deadline:
        time.sleep(0.005)
    assert not eng.active_slots and not eng._flight


class _Calls:
    """``backend.launch`` / ``.collect`` wrapped: ``on[(name, k)]`` runs
    before the k-th call of ``name`` (1-based); every launch notes what
    the host's copy says it runs."""

    def __init__(self, eng, on=()):
        self.eng, self.on = eng, dict(on)
        self.n = {"launch": 0, "collect": 0}
        self.ran_slots, self.ran_positions = [], []
        self.real = {name: getattr(eng.backend, name) for name in self.n}

    def __enter__(self):
        for name in self.n:
            setattr(self.eng.backend, name, self._wrapped(name))
        return self

    def __exit__(self, *exc):
        for name in self.n:
            delattr(self.eng.backend, name)  # the class's own again

    def _wrapped(self, name):
        def call(*args):
            self.n[name] += 1
            hook = self.on.get((name, self.n[name]))
            if hook is not None:
                hook()
            if name == "launch":
                ran = self.eng._left > 0
                self.ran_slots.append(int(ran.sum()))
                self.ran_positions.append(int(self.eng._pos[ran].sum()))
            return self.real[name](*args)
        return call


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_tokens_are_the_models_own(served, policy):
    """A storm of mixed lengths over two slots, greedy or sampled by
    seed: every request reads what the model generates alone, the steps
    were launched ahead, no slot-step was thrown away and no program was
    traced after warm-up."""
    model, eng = served
    traced = dict(eng.trace_counts)
    shapes = [(5, 9), (11, 17), (20, 4), (31, 12), (3, 1), (8, 2)]
    prompts = [_prompt(model, n, seed=100 + n) for n, _ in shapes]

    def storm():
        reqs = [eng.submit(p, max_new=new, seed=40 + i, **POLICIES[policy])
                for i, (p, (_, new)) in enumerate(zip(prompts, shapes))]
        return [np.asarray(r.result(timeout=120)) for r in reqs]

    outs, d = _counted(eng, storm)
    for i, (prompt, (_, new), out) in enumerate(zip(prompts, shapes, outs)):
        np.testing.assert_array_equal(
            out[len(prompt):],
            _alone(model, prompt, new, seed=40 + i, **POLICIES[policy]))
    assert not _retraced(model, eng, traced)
    assert d["tokens"] == sum(new for _, new in shapes)
    assert d["late_slot_steps"] == 0
    # every step but the first after a drained pipeline
    assert 0.5 * d["decode_steps"] < d["decode_steps_ahead"] < d["decode_steps"]
    _settle(eng)


@pytest.mark.parametrize("stop", ["deadline", "cancel"])
def test_a_stop_the_host_decides_is_one_slot_step_late(served, stop):
    """A's request is stopped by the host (its deadline passes; its
    caller gives up) just before the fourth launch: that launch still
    runs A's slot, the token it makes for A is never pushed, the slot is
    claimed again at once by the queued B behind the step in flight, and
    B's tokens and the bystander C's are right: the stop edited A's row
    alone."""
    model, eng = served
    a, b, c = (_prompt(model, n, seed=200 + n) for n in (7, 12, 5))
    reqs = {}

    def stop_a():
        if stop == "deadline":
            reqs["a"].deadline = 0.0
        else:
            reqs["a"].fail(RequestDeadlineExceeded("the caller gave up"))

    def run():
        with _Calls(eng, {("launch", 4): stop_a}) as calls:
            with eng._dev_lock:  # one admission pass claims A and C
                reqs["a"] = eng.submit(a, max_new=40)
                reqs["c"] = eng.submit(c, max_new=14)
                reqs["b"] = eng.submit(b, max_new=9)
            with pytest.raises(RequestDeadlineExceeded):
                reqs["a"].result(timeout=120)
            outs = [np.asarray(reqs[k].result(timeout=120)) for k in "bc"]
            _settle(eng)
        return calls, outs

    (calls, (out_b, out_c)), d = _counted(eng, run)
    # prefill's token and those of the three steps collected before the
    # stop was seen; the fourth step's is dropped
    assert reqs["a"].tokens == _alone(model, a, 40)[:4].tolist()
    np.testing.assert_array_equal(out_b[len(b):], _alone(model, b, 9))
    np.testing.assert_array_equal(out_c[len(c):], _alone(model, c, 14))
    assert d["late_slot_steps"] == 1
    assert d["decode_steps"] == calls.n["launch"] == calls.n["collect"]
    # what the launched steps were handed, the late slot among it
    assert sum(calls.ran_slots) == 4 + 13 + 8
    if eng.backend.keeps_state:
        assert d["state_slots"] == sum(calls.ran_slots)
    if eng.backend.latent:
        assert d["latent_positions_read"] == sum(calls.ran_positions)


@pytest.mark.parametrize("where", ["launch", "collect"])
def test_a_step_that_raises_with_one_in_flight_fails_the_active_typed(
        served, where):
    model, eng = served
    prompt = _prompt(model, 9, seed=301)

    def boom():
        raise RuntimeError("injected step failure")

    with _Calls(eng, {(where, 3): boom}) as calls:
        with eng._dev_lock:
            doomed = [eng.submit(prompt, max_new=20),
                      eng.submit(prompt[:4], max_new=20)]
        for r in doomed:
            with pytest.raises(RuntimeError, match="injected"):
                r.result(timeout=120)
        _settle(eng)
        # one launched step was dropped uncollected
        assert calls.n["launch"] > calls.n["collect"] - (where == "collect")
        # the backend started over: the next request is served
        out = np.asarray(eng.submit(prompt, max_new=6).result(timeout=120))
    np.testing.assert_array_equal(out[len(prompt):], _alone(model, prompt, 6))
    assert 0 < len(doomed[0].tokens) < 20


def test_the_watchdog_fails_a_hung_collect(served):
    from deeplearning4j_tpu.obs import flight

    model, eng = served
    prompt = _prompt(model, 6, seed=401)
    keep = (eng.watchdog_mult, eng.watchdog_min_s)
    eng.watchdog_mult, eng.watchdog_min_s = 2.0, 0.3
    try:
        # an expert ``TransformerLM`` compiles a prefill a prompt length:
        # not inside the seconds counted below
        eng.submit(prompt, max_new=1).result(timeout=120)
        time.sleep(1.1)  # the watchdog's poll follows its limit
        with _Calls(eng, {("collect", 3): lambda: time.sleep(2.0)}):
            t0 = time.monotonic()
            with pytest.raises(DecodeStalledError, match="stuck"):
                eng.submit(prompt, max_new=20).result(timeout=120)
            # the caller unblocked while the collect still hung
            assert time.monotonic() - t0 < 1.9
            _settle(eng)
        kinds = [e["kind"] for e in flight.default_flight_recorder().events()]
        assert "decode_stall_recovered" in kinds
        out = np.asarray(eng.submit(prompt, max_new=5).result(timeout=120))
        np.testing.assert_array_equal(out[len(prompt):],
                                      _alone(model, prompt, 5))
    finally:
        eng.watchdog_mult, eng.watchdog_min_s = keep


def test_nothing_is_launched_into_an_idle_engine(served):
    """Idle, busy, idle: a request of six tokens is five launches, four
    of them ahead, and five collects; one of a single token is none; an
    idle engine launches nothing."""
    model, eng = served
    prompt = _prompt(model, 10, seed=501)
    _settle(eng)
    with _Calls(eng) as calls:
        out, d = _counted(eng, lambda: np.asarray(
            eng.submit(prompt, max_new=6).result(timeout=120)))
        _settle(eng)
        assert (calls.n["launch"], calls.n["collect"]) == (5, 5)
        assert (d["decode_steps"], d["decode_steps_ahead"]) == (5, 4)
        eng.submit(prompt, max_new=1).result(timeout=120)
        time.sleep(0.2)
        assert (calls.n["launch"], calls.n["collect"]) == (5, 5)
    np.testing.assert_array_equal(out[len(prompt):], _alone(model, prompt, 6))
    text = eng.metrics.registry.prometheus_text()
    assert "generation_decode_steps_ahead_total" in text
    assert "generation_late_slot_steps_total" in text


def _steps_in_ring(eng, mark, before):
    steps = {}
    for e in obs_trace.caused_phases(mark):
        if (e[0].startswith("gen.") and e[3] is not None
                and before < e[3] <= eng._dispatch_gen
                and e[0] not in ("gen.queue_wait", "gen.idle_wait")):
            steps.setdefault(e[3], {}).setdefault(e[0], []).append(e)
    return steps


def test_the_ring_holds_one_id_a_step_and_the_launch_ahead_of_the_fetch(
        served):
    model, eng = served
    _settle(eng)
    mark, before = time.time_ns(), eng._dispatch_gen
    eng.submit(_prompt(model, 9, seed=601), max_new=9).result(timeout=120)
    _settle(eng)
    time.sleep(0.05)
    steps = _steps_in_ring(eng, mark, before)
    ids = sorted(steps)
    assert ids == list(range(before + 1, before + 9))
    for i in ids:
        for name in ("gen.decode.put", "gen.decode.dispatch",
                     "gen.decode.fetch", "gen.emit"):
            assert len(steps[i][name]) == 1, (i, name)
        put, dispatch, fetch, emit = (
            steps[i][n][0] for n in ("gen.decode.put", "gen.decode.dispatch",
                                     "gen.decode.fetch", "gen.emit"))
        assert put[1] + put[2] <= dispatch[1] < fetch[1] <= emit[1]
    for i in ids[:-1]:
        # step i+1 was launched before step i's tokens were fetched
        nxt, fetch = steps[i + 1]["gen.decode.dispatch"][0], \
            steps[i]["gen.decode.fetch"][0]
        assert nxt[1] + nxt[2] <= fetch[1]
    # a turn runs from an emit to the next backend call: the launch two
    # ids on, or the last step's own fetch when nothing is left to launch
    for i in ids[:-1]:
        emit = steps[i]["gen.emit"][0]
        nxt = (steps[i + 2]["gen.decode.put"] if i + 2 in steps
               else steps[i + 1]["gen.decode.fetch"])[0]
        turn = [t for t in steps[nxt[3]]["gen.turn"]
                if emit[1] + emit[2] <= t[1]][0]
        assert turn[1] + turn[2] <= nxt[1]
    assert sum(len(s.get("gen.turn", ())) for s in steps.values()) == 7


def _lock_step_engine(kind):
    if kind == "recurrent":
        from deeplearning4j_tpu.models.textgen_lstm import TextGenerationLSTM

        return GenerationEngine(
            TextGenerationLSTM(num_classes=12, units=16).init(), n_slots=2,
            max_length=48, default_timeout_s=120.0)
    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                       max_length=48, seed=3).init()
    return GenerationEngine(lm, n_slots=2, default_timeout_s=120.0,
                            **{"speculating": dict(spec_decode_k=2),
                               "prefix-cached": dict(prefix_cache_mb=1.0)}[
                                   kind])


@pytest.mark.parametrize("kind", ["speculating", "prefix-cached",
                                  "recurrent"])
def test_the_backend_with_its_state_on_the_host_stays_lock_step(kind):
    """A ``TransformerLM`` engine that speculates (``verify`` reads and
    edits the host's tokens between steps) or keeps a prefix cache (a
    restore fills a slot from the host), and a recurrent
    ``MultiLayerNetwork``'s, offer no launch: put, dispatch, fetch and
    emit of a step end before the next step's put, and no step is counted
    as ahead."""
    eng = _lock_step_engine(kind)
    try:
        assert not eng._ahead and not hasattr(eng.backend, "launch")
        mark, before = time.time_ns(), eng._dispatch_gen
        out = eng.submit(np.arange(7, dtype=np.int32),
                         max_new=8).result(timeout=120)
        assert out.shape == (15,)
        time.sleep(0.05)
        steps = _steps_in_ring(eng, mark, before)
        ids = sorted(steps)
        # a verify may hand out two tokens a step
        assert 4 <= len(ids) <= 7 and (kind == "speculating"
                                       or len(ids) == 7)
        for i in ids[:-1]:
            emit = steps[i]["gen.emit"][0]
            assert emit[1] + emit[2] <= steps[i + 1]["gen.decode.put"][0][1]
        snap = eng.metrics.snapshot()
        assert snap["decode_steps"] == len(ids)
        assert snap["decode_steps_ahead"] == snap["late_slot_steps"] == 0
    finally:
        eng.shutdown(drain=False)


def test_shutdown_drains_the_queue_and_the_step_in_flight(served):
    """Last: it ends the module's engine. Two slots and one queued
    request: ``shutdown(drain=True)`` lets all three run out, collects
    what was launched and lets the caches go."""
    model, eng = served
    shapes = [(6, 12), (14, 7), (4, 10)]
    prompts = [_prompt(model, n, seed=700 + n) for n, _ in shapes]
    reqs = [eng.submit(p, max_new=new) for p, (_, new) in zip(prompts, shapes)]
    eng.shutdown(drain=True, timeout=120)
    assert not eng._worker.is_alive() and not eng._flight
    for prompt, (_, new), req in zip(prompts, shapes, reqs):
        np.testing.assert_array_equal(
            np.asarray(req.result(timeout=1))[len(prompt):],
            _alone(model, prompt, new))
    with pytest.raises(ServerShutdownError):
        eng.submit(prompts[0], max_new=2)

"""One entry a mixer kind (``decoder_lm._Mixer``), over the seven tiny models
of ``tests/decoder_kinds.py``: the entry's plan is what ``init_cache``
allocates; its two writers (a prefill's ``fill``, a decode step's ``put``)
change the claimed slot and nothing else; what the engine counts for a kind
is read from the plan; and a kind that leaves an answer out fails where the
configuration is built."""

import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import decoder_kinds  # noqa: E402

from deeplearning4j_tpu.models import decoder_lm  # noqa: E402
from deeplearning4j_tpu.serving.generate import _DecoderBackend  # noqa: E402

KINDS = list(decoder_kinds.KINDS)
SLOTS, LENGTH = 3, 64

#: what ``_DecoderBackend`` held for each kind before the entries (the
#: parent of PR 45, 3 slots x 64): (latent, attends, index_topk,
#: keeps_state, cache_entries, cache_bytes)
COUNTED = {"dense": (False, True, 0, False, 5, 168960),
           "expert": (False, True, 0, False, 5, 168960),
           "latent": (True, False, 0, False, 3, 73728),
           "sparse-latent": (True, False, 8, False, 4, 442368),
           "state-space": (False, True, 0, True, 1, 140160),
           "looped": (False, True, 0, False, 9, 884736),
           # K and V of 3 layers (2 heads x 16 x 64) + their states (16 x 64
           # float32) and tails (64 + 2 x 2 x 16 channels x 3), 3 slots,
           # float32 parameters
           "parallel": (False, True, 0, True, 3,
                        3 * 3 * 4 * (2 * 2 * 16 * 64 + 16 * 64 + 128 * 3))}


@pytest.fixture(scope="module", params=KINDS)
def model(request):
    return request.param, decoder_kinds.decoder_lm(request.param)


def test_the_plan_is_what_init_cache_allocates(model):
    _kind, m = model
    cfg = m.cfg
    plan = cfg.cache_plan(SLOTS, LENGTH)
    caches = decoder_lm.init_cache(cfg, SLOTS, LENGTH)
    assert [p["kind"] for p in plan] == [k for k, _f, _n in cfg.segments()]
    for p, (kind, _ffn, layers), slabs in zip(plan, cfg.segments(), caches):
        own = cfg.mixer(kind).plan(cfg.passes * layers, SLOTS, LENGTH)
        assert {k: p[k] for k in own} == own and p["layers"] == layers
        assert [tuple(c.shape) for c in slabs] == [tuple(s) for s in p["slabs"]]
        assert [c.dtype for c in slabs] == [jnp.dtype(d) for d in p["dtypes"]]
        assert sum(c.nbytes for c in slabs) == p["bytes"]
        assert all(c.shape[:2] == (cfg.passes * layers, SLOTS) for c in slabs)


def _positions_axis(p, slab):
    """The axis of ONE SLOT's part of a slab (entries, ...) that holds
    positions: the rows of a position-major slab, the columns of a T-minor
    one; None for a kind without columns, and for the state and the tail
    of an entry that keeps both."""
    if not p["columns"] or tuple(slab) in (p.get("state"), p.get("conv")):
        return None
    return 1 if "row" in p else -1


def test_the_two_writers_change_the_claimed_slot_only(model):
    """Every slab full of noise; a prompt prefilled into slot 1: the other
    slots' rows are bit for bit what they were. Then one decode step in
    which slot 1 alone is active: its rows change; an idle slot keeps every
    position but the one column (row) at its own stale position, which is
    never read, and a state-space segment's idle rows keep everything."""
    _kind, m = model
    cfg = m.cfg
    plan = cfg.cache_plan(SLOTS, LENGTH)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    before = [tuple(jax.random.normal(next(keys), s, jnp.float32).astype(d)
                    for s, d in zip(p["slabs"], p["dtypes"])) for p in plan]
    snapshot = [[np.asarray(c) for c in seg] for seg in before]
    ids = np.zeros((1, 16), np.int32)
    ids[0, :11] = (np.arange(11) * 5 + 2) % cfg.vocab_size
    _logits, filled = jax.jit(
        lambda p, c: decoder_lm.prefill_slot(
            cfg, p, c, jnp.asarray(ids), jnp.asarray(11, jnp.int32),
            jnp.asarray(1, jnp.int32)))(m.params_, before)
    for was, now in zip(snapshot, filled):
        for w, n in zip(was, now):
            n = np.asarray(n)
            np.testing.assert_array_equal(n[:, [0, 2]], w[:, [0, 2]])
            assert (n[:, 1] != w[:, 1]).any()
    filled_np = [[np.asarray(c) for c in seg] for seg in filled]
    pos = np.asarray([7, 11, 9], np.int32)
    active = np.asarray([False, True, False])
    _logits, stepped, _counts = jax.jit(
        lambda p, c: decoder_lm.decode_step(
            cfg, p, c, jnp.asarray([3, 4, 5], jnp.int32), jnp.asarray(pos),
            jnp.asarray(active)))(m.params_, filled)
    for p, was, now in zip(plan, filled_np, stepped):
        for w, n, slab in zip(was, now, p["slabs"]):
            axis = _positions_axis(p, slab)
            n = np.asarray(n)
            assert (n[:, 1] != w[:, 1]).any()
            for idle in (0, 2):
                keep = np.ones(w[:, idle].shape, bool)
                if axis is not None:
                    at = [slice(None)] * keep.ndim
                    at[axis] = pos[idle] % p["columns"]
                    keep[tuple(at)] = False
                np.testing.assert_array_equal(n[:, idle][keep],
                                              w[:, idle][keep])


def test_what_the_engine_counts_is_read_from_the_plan(model):
    kind, m = model
    be = _DecoderBackend(SimpleNamespace(cfg=m.cfg), SLOTS, LENGTH, [16],
                         lambda name: None)
    assert (be.latent, be.attends, be.index_topk, be.keeps_state,
            be.cache_entries, be.cache_bytes) == COUNTED[kind]
    plan = m.cfg.cache_plan(SLOTS, LENGTH)
    assert be.cache_entries == sum(p["entries"] for p in plan)
    assert be.keeps_state == any("state" in p for p in plan)


@pytest.mark.parametrize("kind", decoder_kinds.BEFORE_JOIN)
def test_the_kinds_that_were_there_give_what_they_gave_before_the_join_moved(kind):
    """``_KeysValues`` and ``_StateSpace`` gave their norm and their residual
    add to a shared caller (``_Branches.mix``) so that a parallel block can
    be made of them: the six kinds that were there give the logits (full
    forward, prefill, one decode step) and every cache slab that the tree
    before gave (``tests/fixtures/decoder_lm/kinds_before_join.json``,
    written by that tree: ``python tests/decoder_kinds.py <file>``), within
    an ulp of their scale (two builds of XLA:CPU may contract multiply-adds
    differently; on the machine that wrote the fixture every SHA-256 is
    equal, bit for bit: PR 47)."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                           "decoder_lm", "kinds_before_join.json")) as f:
        before = json.load(f)[kind]
    now = decoder_kinds.readings(kind)

    def same(was, got):
        scale = max(was["abs_mean"], 1e-30)
        np.testing.assert_allclose(got["first"], was["first"], atol=2e-5 * scale,
                                   rtol=0)
        assert abs(got["abs_mean"] - was["abs_mean"]) <= 1e-5 * scale

    for part in ("forward", "prefill", "decode"):
        same(before[part], now[part])
    assert [len(seg) for seg in now["caches"]] == [len(seg) for seg in before["caches"]]
    for was_seg, now_seg in zip(before["caches"], now["caches"]):
        for was, got in zip(was_seg, now_seg):
            same(was, got)


ANSWERS = ("leaves", "plan", "open", "mix", "put")


@pytest.mark.parametrize("missing", ANSWERS)
def test_a_kind_without_an_answer_fails_at_configuration(monkeypatch, missing):
    """An entry that leaves ``missing`` out cannot be made: the
    configuration raises, by the answer's name, before anything traces."""
    assert set(ANSWERS) == decoder_lm._Mixer.__abstractmethods__
    partial = type("Partial", (decoder_lm._Mixer,), {
        name: (lambda self, *args: None) for name in ANSWERS
        if name != missing})
    monkeypatch.setattr(decoder_lm._Mixer, "of",
                        staticmethod(lambda cfg, kind: partial(cfg, kind)))
    with pytest.raises(TypeError, match=missing):
        decoder_lm.DecoderConfig(**decoder_kinds.program("dense"))

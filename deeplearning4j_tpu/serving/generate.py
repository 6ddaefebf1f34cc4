"""Continuous-batching autoregressive generation engine.

The /predict path batches REQUESTS; autoregressive generation has to
batch TOKENS. A naive serving loop decodes one request at a time (the
device idles at batch 1) or dispatch-then-waits a fixed batch (every
request waits for the slowest's last token). Continuous batching — the
Orca/vLLM scheduling discipline — keeps ONE fixed-shape decode program
in flight and lets requests join and leave it **between token steps**:

- the engine owns a persistent **slot slab**: for TransformerLM an
  ``(n_layers, n_slots, heads, head_dim, max_length)`` KV cache pair
  (``init_decode_cache``); for recurrent nets (TextGenerationLSTM) the
  per-layer carried (h, c) state stacked to ``(n_slots, units)``; for
  DecoderLM the cache its plan asks for, sized by layer kind (a full
  layer's slab of the slot's length, a window layer's ring);
- a request claims a free slot, **prefills** its prompt at a bucketed
  length (``prefill_bucket_lengths`` — the ``serving_seq_buckets``
  discipline, so prefill compiles a bounded program set), and joins the
  next decode step;
- every token step is ONE jitted dispatch for ALL active slots: the
  per-row-position ``decode_step`` + in-graph ``sample_next_device``
  (greedy/temperature/top-k/top-p as data, not program structure), so
  steady-state decode never recompiles and never round-trips the host
  per request — one small host sync per step streams every slot's new
  token;
- finished or deadline-expired requests free their slot **at token
  granularity**; the freed slot is re-prefilled by the next queued
  request while the other slots keep decoding.

Zero-recompile discipline (1810.09868 fixed-shape rationale) extended
to token granularity: the decode program's shapes are
``(n_slots, ...)`` forever; activity is a boolean mask. Parity: a slot
decoded among other slots is bit-identical to the same request decoded
alone (row-independent attention math — asserted in
tests/test_generate.py), so continuous batching is an *throughput*
optimization, never an output change. Documented tolerances: MoE
routing competes across co-resident slots (capacity effects — same
caveat as ``decode_step``), and top-p nucleus cutoffs can differ from
the host sampler at boundary ties (``sample_next_device``).

Typed failures reuse the batcher vocabulary: queue-full →
:class:`~.batcher.ServerOverloadedError` (HTTP 503), deadline →
:class:`~.batcher.RequestDeadlineExceeded` (504), window overflow →
:class:`~models.transformer_lm.ContextWindowExceeded` (400), slab
memory over budget → :class:`GenerationMemoryError` at build time.

Observability: flight-recorder slot lifecycle events (``slot_claim`` /
``slot_free`` / ``decode_stall``), rtrace stage timelines
(queue → prefill → decode → respond), and a
:class:`~.metrics.GenerationMetrics` registry surface
(``generation_tokens_per_sec``, slot occupancy, prefill/decode split).
"""

from __future__ import annotations

import hashlib
import itertools
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import (
    Callable,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.chaos import hooks as chaos_hooks
from deeplearning4j_tpu.obs import trace as _trace
from deeplearning4j_tpu.obs.lockwitness import witnessed_lock
from deeplearning4j_tpu.serving import rtrace
from deeplearning4j_tpu.serving.batcher import (
    RequestDeadlineExceeded,
    ServerOverloadedError,
    ServerShutdownError,
    ServingError,
)
from deeplearning4j_tpu.serving.metrics import GenerationMetrics

# host phases of the worker loop (obs/trace.py). ``gen.admit`` encloses
# ``gen.prefill``, which encloses ``gen.prefill.put``; ``gen.turn`` (the
# loop's own time from one step's ``gen.emit`` to the next backend call)
# encloses the claims made in it; the others follow one another, so the
# self times sum to a loop iteration. Every entry carries its decode
# step's id as its cause: a claim's, and the turn's, the id of the step
# they precede. Lock-step (a speculating or prefix-cached
# ``TransformerLM`` engine, a recurrent net's) a step's put, dispatch,
# fetch and emit follow one another; where a step is kept in flight
# (``_step_ahead``: every ``DecoderLM`` engine, and a ``TransformerLM``'s
# with K = 1 and no prefix cache) the put and dispatch of step t+1 come
# before the fetch and emit of step t.
_ADMIT = _trace.phase("gen.admit")
_PREFILL = _trace.phase("gen.prefill")
_PREFILL_PUT = _trace.phase("gen.prefill.put")
_DECODE_PUT = _trace.phase("gen.decode.put")
_DECODE_DISPATCH = _trace.phase("gen.decode.dispatch")
_DECODE_FETCH = _trace.phase("gen.decode.fetch")
_EMIT = _trace.phase("gen.emit")
_IDLE_WAIT = _trace.phase("gen.idle_wait")
_QUEUE_WAIT = _trace.phase("gen.queue_wait")
_TURN = _trace.phase("gen.turn")

#: one a GenerationEngine of this process: the high bits of its step ids,
#: so that two engines' phases in the one ring are told apart by cause
_ENGINE_IDS = itertools.count()

class GenerationMemoryError(ServingError):
    """The requested ``n_slots × max_length`` decode slab would not fit
    the memory budget — raised at engine BUILD time (the estimator says
    no before the allocator does)."""


class DecodeStalledError(ServingError):
    """A decode dispatch hung past the watchdog limit (a configurable
    multiple of the rolling per-step time). The engine's worker thread
    is wedged inside the dispatch; the active requests are failed typed
    by the watchdog so their callers unblock instead of hanging with
    it, and the slab is rebuilt when (if) the dispatch returns."""


class RecurrentStateError(ServingError, ValueError):
    """A prefix cache or speculation (K > 1) was asked of a model whose
    layers keep a recurrent state. Both work by dropping or splicing
    COLUMNS of a cache laid out by position; a state-space layer's state
    is one array a slot that every token has been folded into, so it can
    neither be rolled back to an earlier token by dropping columns nor be
    captured for a prefix without a snapshot of its own. Raised at engine
    BUILD time."""


class EarlyExitError(ServingError, ValueError):
    """An exit threshold under 1 was asked of a model whose stack runs
    several passes a token. The served programs run every pass for every
    row, the rule at threshold 1; under a lower one the rows of one
    batched step would leave after different passes, and what a row that
    left owes the later passes' cache entries (which the positions after
    it attend to) is a scheduler's question that no program here answers.
    Raised at engine BUILD time."""


class GenerationRequest:
    """One generation request: prompt + sampling policy + streaming
    output. Completion (``finish``/``fail``) is idempotent first-wins,
    mirroring :class:`~.batcher.InferenceRequest`. Tokens stream into a
    bounded-latency queue as they are decoded (``stream()``); callers
    that want the whole sequence block on ``result()``."""

    _END = object()

    __slots__ = ("prompt", "max_new", "temperature", "top_k", "top_p",
                 "seed", "deadline", "enqueued_at", "trace", "tokens",
                 "slot", "_event", "_lock", "_stream", "result_", "error_",
                 "on_done", "draft_proposed", "draft_accepted")

    def __init__(self, prompt_ids, max_new: int, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0, seed: int = 0,
                 deadline: Optional[float] = None, trace: bool = False):
        self.prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        #: absolute time.monotonic() deadline, or None
        self.deadline = deadline
        self.trace = rtrace.RequestTrace() if trace else None
        #: the trace's ``enqueue`` mark where there is one, so that the
        #: queue wait and the trace's ``queue`` stage are one interval
        self.enqueued_at = (self.trace.marks[0][1] if trace
                            else time.monotonic())
        #: generated token ids, in order (grows as decoding proceeds)
        self.tokens: List[int] = []
        #: speculative-decoding accounting: draft tokens proposed for /
        #: accepted by this request's verify dispatches
        self.draft_proposed = 0
        self.draft_accepted = 0
        #: slot index while decoding, else None
        self.slot: Optional[int] = None
        self._event = threading.Event()
        self._lock = witnessed_lock("generate.request")
        self._stream: "queue.Queue" = queue.Queue()
        self.result_: Optional[np.ndarray] = None
        self.error_: Optional[BaseException] = None
        #: optional completion observer ``fn(request, error_or_None)``,
        #: invoked exactly once (first-wins with the completion) AFTER
        #: the event is set, outside the request lock. The router's
        #: per-version generation counters — the canary metric gate's
        #: /generate leg — hang off this.
        self.on_done: Optional[Callable] = None

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now if now is not None else time.monotonic())
                > self.deadline)

    def done(self) -> bool:
        return self._event.is_set()

    def push_token(self, tok: int) -> None:
        self.tokens.append(int(tok))
        self._stream.put(int(tok))

    def finish(self) -> bool:
        with self._lock:
            if self._event.is_set():
                return False
            self.result_ = np.concatenate(
                [self.prompt, np.asarray(self.tokens, np.int32)])
            self._event.set()
            self._stream.put(self._END)
        self._notify(None)
        return True

    def fail(self, error: BaseException) -> bool:
        with self._lock:
            if self._event.is_set():
                return False
            self.error_ = error
            self._event.set()
            self._stream.put(self._END)
        self._notify(error)
        return True

    def _notify(self, error: Optional[BaseException]) -> None:
        cb = self.on_done
        if cb is None:
            return
        try:
            cb(self, error)
        except Exception:  # noqa: BLE001 — an observer must never fail
            # the completion path (the caller is already unblocked)
            pass

    def stream(self, timeout: Optional[float] = None):
        """Yield token ids as they are decoded; raises the request's
        typed error at the point of failure. ``timeout`` bounds the wait
        for EACH token (a stalled engine raises
        :class:`RequestDeadlineExceeded` instead of hanging the
        consumer)."""
        while True:
            try:
                item = self._stream.get(timeout=timeout)
            except queue.Empty:
                raise RequestDeadlineExceeded(
                    f"no token within timeout={timeout}s") from None
            if item is self._END:
                if self.error_ is not None:
                    raise self.error_
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block for the full sequence (prompt + generated), 1-D int32.
        On timeout the request is failed idempotently (a concurrent
        engine completion wins) and the typed error raises."""
        if not self._event.wait(timeout):
            self.fail(RequestDeadlineExceeded(
                f"request not served within timeout={timeout}s"))
            self._event.wait()
        if self.error_ is not None:
            raise self.error_
        return self.result_


# --------------------------------------------------------------------------
# speculative drafting + shared-prefix KV cache
# --------------------------------------------------------------------------
class _NgramDraft:
    """Per-engine order-2 n-gram draft table for self-speculative
    decoding: ``(t[i-2], t[i-1]) → t[i]`` learned from every prompt and
    every emitted token (last-writer-wins, so the table adapts). Drafts
    are chained lookups from a slot's last two tokens — free to produce,
    and on repetitive traffic (shared-prefix storms, templated output)
    acceptance approaches 1. The table is bounded: crossing ``cap``
    clears it whole (``draft_flush`` flight event) rather than tracking
    per-entry LRU — n-gram stats rebuild in a few hundred tokens."""

    __slots__ = ("cap", "table", "flushes")

    def __init__(self, cap: int = 65536):
        self.cap = int(cap)
        self.table: Dict = {}
        self.flushes = 0

    def learn(self, a: int, b: int, c: int) -> None:
        self.table[(int(a), int(b))] = int(c)
        if len(self.table) > self.cap:
            from deeplearning4j_tpu.obs import flight as _flight

            self.table.clear()
            self.flushes += 1
            _flight.record("draft_flush", entries=self.cap,
                           flushes=self.flushes)

    def learn_seq(self, toks) -> None:
        for i in range(len(toks) - 2):
            self.learn(toks[i], toks[i + 1], toks[i + 2])

    def propose(self, a: int, b: int, n: int) -> List[int]:
        """Up to n draft tokens continuing context (a, b); stops at the
        first context the table has never seen."""
        out: List[int] = []
        a, b = int(a), int(b)
        for _ in range(n):
            c = self.table.get((a, b))
            if c is None:
                break
            out.append(c)
            a, b = b, c
        return out


class PrefixCache:
    """LRU-bytes cache of prefilled prompt state keyed by the EXACT
    prompt (backend kind, length, sha1 of the token bytes). A hit
    replaces the prefill dispatch with a per-bucket KV-block copy into
    the claiming slot plus a (1, V) sample of the STORED last-position
    logits — prefill logits are deterministic for a given prompt, so the
    hit path's first token and key chain are bit-identical to a real
    prefill. Entries are backend-opaque dicts carrying ``bytes`` (device
    memory held) and ``tb`` (the prompt's prefill bucket); eviction is
    LRU by bytes against ``limit_bytes``.

    Flight/metrics contract: every ``lookup`` counts toward the lazily
    created ``generation_prefix_hit_rate`` gauge; ``commit_hit`` (called
    only after the copy-in succeeded) fires ``prefix_hit``; ``drop``
    fires ``prefix_evict`` with the reason (lru / poisoned / cleared).
    Entries hold KV computed by the CURRENT params — a hot params
    reload must ``clear()`` (see ``GenerationEngine.clear_prefix_cache``)."""

    def __init__(self, limit_bytes: int, metrics: GenerationMetrics):
        self.limit_bytes = int(limit_bytes)
        self.metrics = metrics
        self._entries: "OrderedDict" = OrderedDict()
        self._bytes = 0
        self.lookups = 0
        self.hits = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def bytes(self) -> int:
        return self._bytes

    @staticmethod
    def key_for(kind: str, prompt: np.ndarray):
        return (kind, int(prompt.size),
                hashlib.sha1(np.ascontiguousarray(prompt).tobytes())
                .hexdigest())

    def lookup(self, key):
        """One admission-time probe; returns the entry or None. The hit
        is NOT committed here — the caller commits only after the
        copy-in succeeded (a poisoned entry must count as a miss)."""
        self.lookups += 1
        self.metrics.record_prefix_lookup()
        return self._entries.get(key)

    def commit_hit(self, key, prompt_len: int, slot: int,
                   flops_avoided: int = 0) -> None:
        from deeplearning4j_tpu.obs import flight as _flight

        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
        self.hits += 1
        self.metrics.record_prefix_hit(flops_avoided)
        _flight.record("prefix_hit", slot=int(slot),
                       prompt_len=int(prompt_len),
                       bucket=int(entry["tb"]) if entry else -1,
                       flops_avoided=int(flops_avoided))

    def drop(self, key, reason: str) -> None:
        from deeplearning4j_tpu.obs import flight as _flight

        entry = self._entries.pop(key, None)
        if entry is None:
            return
        self._bytes -= int(entry["bytes"])
        self.metrics.record_prefix_evict()
        self.metrics.set_prefix_bytes(self._bytes)
        _flight.record("prefix_evict", reason=reason,
                       bucket=int(entry["tb"]),
                       bytes=int(entry["bytes"]),
                       resident=len(self._entries))

    def put(self, key, entry: dict) -> bool:
        """Insert (replacing any stale entry for the key), evicting LRU
        entries until the budget fits; refuses entries larger than the
        whole budget."""
        if int(entry["bytes"]) > self.limit_bytes:
            return False
        if key in self._entries:
            self.drop(key, reason="replaced")
        while self._bytes + int(entry["bytes"]) > self.limit_bytes \
                and self._entries:
            oldest = next(iter(self._entries))
            self.drop(oldest, reason="lru")
        self._entries[key] = entry
        self._bytes += int(entry["bytes"])
        self.metrics.set_prefix_bytes(self._bytes)
        return True

    def attach_completion(self, key, toks) -> None:
        """Record the prompt's FIRST greedy completion on its entry:
        later hits replay it as the slot's draft source. Only the first
        one sticks (greedy is deterministic, so later ones are
        identical anyway); a handful of host ints, not counted against
        the byte budget."""
        entry = self._entries.get(key)
        if entry is not None and "completion" not in entry:
            entry["completion"] = [int(t) for t in toks]

    def clear(self, reason: str = "cleared") -> int:
        n = len(self._entries)
        for key in list(self._entries):
            self.drop(key, reason=reason)
        return n


# --------------------------------------------------------------------------
# decode backends
# --------------------------------------------------------------------------
def _counted(temperature, active):
    """The slots' temperatures as the in-graph sampler should see them:
    0 (greedy) on a row whose result the decode program throws away. A
    freed slot keeps its last occupant's policy on the host, and the
    sampler branches on the policies it is handed
    (``transformer_lm.sampling_needs``): a sampled request that has
    finished must not make the greedy ones pay for its sorts."""
    return jnp.where(active, temperature, 0.0)


def _prefill_columns(cfg, p, kc, vc, ids, ln, slot):
    """A TransformerLM prompt's (1, bucket) ids through ``prefill_cache``
    and its K and V written into ``slot``'s first columns of the slabs;
    returns (last-position logits (1, V), the slabs, the prompt's own
    cache). Only the bucket's columns are written: what a slot holds
    past them is never read before decode overwrites it."""
    from deeplearning4j_tpu.models.transformer_lm import (
        init_decode_cache,
        prefill_cache,
    )

    tmp = init_decode_cache(cfg, 1, max_length=ids.shape[1])
    logits, tmp = prefill_cache(cfg, p, tmp, ids, length=ln)
    with jax.named_scope("kv_write"):
        kc = jax.lax.dynamic_update_slice(kc, tmp["k"], (0, slot, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, tmp["v"], (0, slot, 0, 0, 0))
    return logits, kc, vc, tmp


class _TransformerBackend:
    """TransformerLM decode backend: fixed (L, S, hn, hd, T) KV slab
    (time minor: ``init_decode_cache``), per-slot positions, per-bucket
    prefill programs. Decode and verify read the slab and write their
    new columns in place after the layer loop; prefill and the prefix
    cache move whole blocks of columns with one slice a slab.

    This class keeps the slots' inputs on the HOST and hands them to a
    step as seven small arrays (``decode``), which is what speculation
    (``verify`` / ``draft`` read and edit the tokens between two steps)
    and the prefix cache (``prefix_restore``, the completion replay)
    work on: the engine runs it lock-step. An engine with neither gets
    ``_TransformerAheadBackend``, below, whose programs
    ``_build_programs`` replaces."""

    #: every layer keeps keys and values a position: the engine counts
    #: the positions a step's slots have behind them
    attends = True

    kind = "transformer"

    def __init__(self, model, n_slots: int, max_length: Optional[int],
                 prefill_buckets: Optional[Sequence[int]], trace_hook,
                 spec_k: int = 1, draft_layers: int = 0,
                 on_param_cast: Callable[[], None] = lambda: None):
        from deeplearning4j_tpu.models.transformer_lm import (
            prefill_bucket_lengths,
        )

        self.model = model
        #: the weights the programs read (:meth:`_params`), the master
        #: leaves they were made from, and who counts a cast
        self._copy = None
        self._copy_of: list = []
        self._on_param_cast = on_param_cast
        cfg = model.cfg
        self.n_slots = int(n_slots)
        self.max_length = (cfg.max_length if max_length is None
                           else min(int(max_length), cfg.max_length))
        self.buckets = prefill_bucket_lengths(
            self.max_length,
            prefill_buckets or getattr(model, "serving_seq_buckets", None))
        self._cfg = cfg
        self.vocab = int(cfg.vocab_size)
        #: speculation lane width K: column 0 is the current token,
        #: columns 1..K-1 draft proposals. MoE pins K=1 — decode_steps'
        #: routing would compete b*K tokens where sequential decode
        #: competes b, so acceptance would no longer be exact.
        self.spec_k = 1 if cfg.n_experts > 0 else max(1, int(spec_k))
        #: truncated-layer draft model depth (0 = n-gram drafting only);
        #: only meaningful with spec_k > 1 and 0 < draft_layers < L
        self.draft_layers = (int(draft_layers)
                             if self.spec_k > 1
                             and 0 < int(draft_layers) < cfg.n_layers
                             else 0)
        self.reset()
        self.cache_bytes = 2 * int(np.prod(self._kc.shape)) * \
            self._kc.dtype.itemsize
        if self.draft_layers:
            self.cache_bytes += 2 * int(np.prod(self._dkc.shape)) * \
                self._dkc.dtype.itemsize
        #: per-bucket prefix-cache copy programs (capture = slab→entry
        #: slice-out, restore = entry→slab splice-in), compiled lazily
        #: and pre-warmed by GenerationEngine.warmup
        self._cap_fns: Dict[int, Callable] = {}
        self._res_fns: Dict[int, Callable] = {}
        self._build_programs(trace_hook)

    def _build_programs(self, trace_hook) -> None:
        """The jitted programs over slots' inputs that live on the HOST:
        seven small arrays a decode step."""
        from deeplearning4j_tpu.models.transformer_lm import (
            decode_step,
            decode_steps,
            prefill_cache,
            sample_next_device,
            sample_next_rows,
        )

        cfg = self._cfg

        def _decode(p, kc, vc, toks, pos, active, t, k, pp, keys):
            trace_hook("generation_decode")
            logits, c = decode_step(cfg, p, {"k": kc, "v": vc, "pos": pos},
                                    toks, active)
            nxt, nkeys = sample_next_rows(logits, _counted(t, active), k,
                                          pp, keys)
            nxt = jnp.where(active, nxt, toks)
            nkeys = jnp.where(active[:, None], nkeys, keys)
            return nxt, nkeys, c["k"], c["v"]

        Ld = self.draft_layers

        def _slice_draft(p):
            return {**p, "blocks": jax.tree_util.tree_map(
                lambda a: a[:Ld], p["blocks"])}

        def _prefill(p, kc, vc, dkc, dvc, ids, ln, slot, t, k, pp, key):
            trace_hook("generation_prefill")
            logits, kc, vc, tmp = _prefill_columns(cfg, p, kc, vc, ids, ln,
                                                   slot)
            if Ld:
                # the truncated draft model prefills its own (shallower)
                # slab from the same prompt
                dp = _slice_draft(p)
                dtmp = {"k": jnp.zeros((Ld,) + tmp["k"].shape[1:],
                                       tmp["k"].dtype),
                        "v": jnp.zeros((Ld,) + tmp["v"].shape[1:],
                                       tmp["v"].dtype),
                        "pos": jnp.zeros((), jnp.int32)}
                _dl, dtmp = prefill_cache(cfg, dp, dtmp, ids, length=ln)
                with jax.named_scope("kv_write"):
                    dkc = jax.lax.dynamic_update_slice(dkc, dtmp["k"],
                                                       (0, slot, 0, 0, 0))
                    dvc = jax.lax.dynamic_update_slice(dvc, dtmp["v"],
                                                       (0, slot, 0, 0, 0))
            tok0, key = sample_next_device(logits, t, k, pp, key)
            return tok0[0], key, kc, vc, dkc, dvc, logits[0]

        self._decode_fn = jax.jit(_decode, donate_argnums=(1, 2))
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1, 2, 3, 4))

        def _sample1(logits, t, k, pp, key):
            trace_hook("generation_prefix_sample")
            tok0, key = sample_next_device(logits, t, k, pp, key)
            return tok0[0], key

        self._sample1_fn = jax.jit(_sample1)

        K = self.spec_k
        if K > 1:
            def _verify(p, kc, vc, toks, dlen, pos, active, t, k, pp,
                        keys):
                """One dispatch verifying K columns per slot. toks
                (S, K): col 0 = current token, cols 1..dlen = drafts.
                Emits s (S, K) — the tokens sequential decode WOULD have
                produced at each column — plus e (S,) the number of
                leading columns that are real output: e = 1 + longest
                draft prefix where draft j == s[j-1] (the exact
                acceptance rule: a draft survives iff the verifier
                sampled exactly it, so the emitted stream and the key
                chain are those of token-by-token decode)."""
                trace_hook("generation_verify")
                logits, c = decode_steps(
                    cfg, p, {"k": kc, "v": vc, "pos": pos}, toks)
                outs, kstack, ks = [], [keys], keys
                tc = _counted(t, active)
                for j in range(K):
                    sj, ks = sample_next_rows(logits[:, j], tc, k, pp, ks)
                    outs.append(sj)
                    kstack.append(ks)
                s = jnp.stack(outs, axis=1)          # (S, K)
                kst = jnp.stack(kstack, axis=1)      # (S, K+1, 2)
                jj = jnp.arange(1, K)
                ok = (s[:, :-1] == toks[:, 1:]) & \
                    (jj[None, :] <= dlen[:, None])
                e = 1 + jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(1)
                # the key chain advanced exactly e times (once per
                # emitted token) — select that state per row
                nkeys = jnp.take_along_axis(
                    kst, e[:, None, None], axis=1)[:, 0]
                last = jnp.take_along_axis(
                    s, (e - 1)[:, None], axis=1)[:, 0]
                last = jnp.where(active, last, toks[:, 0])
                nkeys = jnp.where(active[:, None], nkeys, keys)
                e = jnp.where(active, e, 0)
                return s, e, last, nkeys, c["k"], c["v"]

            self._verify_fn = jax.jit(_verify, donate_argnums=(1, 2))

        if Ld:
            def _draft(p, dkc, dvc, toks, pos, active):
                """K-1 greedy steps of the truncated-layer draft model —
                one dispatch proposing drafts for every slot."""
                trace_hook("generation_draft")
                dp = _slice_draft(p)
                c = {"k": dkc, "v": dvc, "pos": pos}
                tok = toks
                outs = []
                for _ in range(K - 1):
                    logits, c = decode_step(cfg, dp, c, tok)
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    outs.append(tok)
                return jnp.stack(outs, axis=1), c["k"], c["v"]

            self._draft_fn = jax.jit(_draft, donate_argnums=(1, 2))

    def reset(self) -> None:
        """(Re)build the KV slab — at construction, and for engine
        decode-failure recovery (the failed dispatch consumed the
        donated buffers)."""
        from deeplearning4j_tpu.models.transformer_lm import (
            init_decode_cache,
        )

        slab = init_decode_cache(self._cfg, self.n_slots,
                                 max_length=self.max_length)
        self._kc, self._vc = slab["k"], slab["v"]
        if self.draft_layers:
            self._dkc = self._kc[:self.draft_layers]
            self._dvc = self._vc[:self.draft_layers]
        else:
            # zero-size placeholders keep the prefill signature uniform
            self._dkc = self._kc[:0]
            self._dvc = self._vc[:0]

    def release(self) -> None:
        """Let the slabs and the serving copy go (engine shutdown)."""
        self._kc = self._vc = self._dkc = self._dvc = None
        self._copy, self._copy_of = None, []

    def _params(self) -> dict:
        """What every program reads as its weights: the model's
        ``serving_copy`` (block matrices and head in the compute dtype,
        the rest the float32 masters themselves), made by one cast
        program the first time it is asked for (warm-up) and kept
        beside the master leaves it was made from. Re-made when, and
        only when, the leaves of ``model.params_`` are not those objects
        any more: a swap of ``params_`` is served at the next dispatch for
        one cast, no recompile, and a step on unchanged weights compares
        ~16 identities. Under ``compute_dtype=None`` the copy is the
        tree itself and nothing is counted."""
        from deeplearning4j_tpu.models.transformer_lm import serving_copy

        params = self.model.params_
        leaves = jax.tree_util.tree_leaves(params)
        if len(leaves) != len(self._copy_of) or any(
                a is not b for a, b in zip(leaves, self._copy_of)):
            self._copy = None  # the old copy goes before the new is made
            self._copy = serving_copy(self._cfg, params)
            self._copy_of = leaves
            if self._copy is not params:
                self._on_param_cast()
        return self._copy

    def bucket_for(self, prompt_len: int) -> int:
        return next(t for t in self.buckets if t >= prompt_len)

    def prefill(self, slot: int, prompt: np.ndarray, temperature: float,
                top_k: int, top_p: float, key: np.ndarray):
        """Prefill one slot; returns (first token int, advanced key,
        prompt bucket, last-position logits (V,) fp32 device array —
        the prefix cache stores these so a hit can re-sample the first
        token bit-identically under any policy/key). One host sync per
        REQUEST (the first token), amortized over its whole decode. MoE
        prompts skip bucketing — pad tokens would compete for expert
        capacity and perturb real-token logits (same exemption, and the
        same one-program-per-distinct-length cost, as
        ``generate_cached``)."""
        tp = int(prompt.shape[0])
        tb = tp if self._cfg.n_experts > 0 else self.bucket_for(tp)
        with _PREFILL_PUT:
            ids = np.zeros((1, tb), np.int32)
            ids[0, :tp] = prompt
            args = (jnp.asarray(ids), jnp.asarray(tp, jnp.int32),
                    jnp.asarray(int(slot), jnp.int32),
                    jnp.asarray(temperature, jnp.float32),
                    jnp.asarray(int(top_k), jnp.int32),
                    jnp.asarray(top_p, jnp.float32), jnp.asarray(key))
        tok0, key, self._kc, self._vc, self._dkc, self._dvc, logits0 = \
            self._prefill_fn(
                self._params(), self._kc, self._vc, self._dkc,
                self._dvc, *args)
        del args  # see decode
        return int(tok0), np.asarray(key), tb, logits0

    def decode(self, tokens, pos, active, temperature, top_k, top_p, keys):
        """One batched token step for all slots; returns
        (next tokens (S,), advanced keys (S, 2)) as host arrays — the
        single per-token host sync for the whole batch."""
        with _DECODE_PUT:
            args = (jnp.asarray(tokens), jnp.asarray(pos),
                    jnp.asarray(active), jnp.asarray(temperature),
                    jnp.asarray(top_k), jnp.asarray(top_p),
                    jnp.asarray(keys))
        with _DECODE_DISPATCH:
            nxt, nkeys, self._kc, self._vc = self._decode_fn(
                self._params(), self._kc, self._vc, *args)
            # let the step's input buffers go while it runs, as the
            # call temporaries they were before the put had a phase of
            # its own: held until this returns, they are freed on this
            # thread after the fetch
            del args
        with _DECODE_FETCH:
            return np.asarray(nxt), np.asarray(nkeys)

    def verify(self, toks_k, dlen, pos, active, temperature, top_k, top_p,
               keys):
        """One batched draft-verify step (spec_k > 1 only): toks_k
        (S, K) proposal lane, dlen (S,) per-slot draft counts. Returns
        host arrays (emitted (S, K), accepted counts e (S,), new current
        token (S,), advanced keys (S, 2)) — still ONE host sync for up
        to K tokens per slot."""
        with _DECODE_PUT:
            args = (jnp.asarray(toks_k), jnp.asarray(dlen),
                    jnp.asarray(pos), jnp.asarray(active),
                    jnp.asarray(temperature), jnp.asarray(top_k),
                    jnp.asarray(top_p), jnp.asarray(keys))
        with _DECODE_DISPATCH:
            s, e, last, nkeys, self._kc, self._vc = self._verify_fn(
                self._params(), self._kc, self._vc, *args)
            del args  # see decode
        with _DECODE_FETCH:
            return (np.asarray(s), np.asarray(e), np.asarray(last),
                    np.asarray(nkeys))

    def draft(self, tokens, pos, active):
        """Truncated-layer draft proposals: (S, K-1) greedy tokens from
        the first ``draft_layers`` blocks, one dispatch for all slots."""
        with _DECODE_PUT:
            args = (jnp.asarray(tokens), jnp.asarray(pos),
                    jnp.asarray(active))
        with _DECODE_DISPATCH:
            drafts, self._dkc, self._dvc = self._draft_fn(
                self._params(), self._dkc, self._dvc, *args)
            del args  # see decode
        with _DECODE_FETCH:
            return np.asarray(drafts)

    # -- shared-prefix cache hooks ------------------------------------------
    def prefix_capture(self, slot: int, tb: int, logits0) -> dict:
        """Slice the slot's first ``tb`` KV columns (and the truncated
        draft slab's, when speculating through it) out of the
        (L, S, hn, hd, T) slab into a self-contained cache entry of
        (L, 1, hn, hd, tb) blocks. The slab is donated to every decode
        dispatch, so the entry must be a COPY, not a view."""
        fn = self._cap_fns.get(tb)
        if fn is None:
            L, _S, hn, hd, _T = self._kc.shape
            Ld = self.draft_layers

            def _cap(kc, vc, dkc, dvc, slot):
                sl = (0, slot, 0, 0, 0)
                out = (jax.lax.dynamic_slice(kc, sl, (L, 1, hn, hd, tb)),
                       jax.lax.dynamic_slice(vc, sl, (L, 1, hn, hd, tb)))
                if Ld:
                    out += (jax.lax.dynamic_slice(dkc, sl,
                                                  (Ld, 1, hn, hd, tb)),
                            jax.lax.dynamic_slice(dvc, sl,
                                                  (Ld, 1, hn, hd, tb)))
                return out

            fn = self._cap_fns[tb] = jax.jit(_cap)
        blocks = fn(self._kc, self._vc, self._dkc, self._dvc,
                    jnp.asarray(int(slot), jnp.int32))
        nbytes = sum(int(b.size) * b.dtype.itemsize for b in blocks) \
            + int(logits0.size) * 4
        return {"blocks": blocks, "logits": logits0, "tb": int(tb),
                "bytes": int(nbytes)}

    def prefix_restore(self, slot: int, entry: dict, temperature: float,
                       top_k: int, top_p: float, key: np.ndarray):
        """Splice a cached KV block into ``slot`` and sample the first
        token from the STORED prefill logits — bit-identical to the real
        prefill this entry was captured from (same logits, same sampler
        program shape, same key chain)."""
        tb = int(entry["tb"])
        fn = self._res_fns.get(tb)
        if fn is None:
            Ld = self.draft_layers

            def _res(kc, vc, dkc, dvc, blocks, slot):
                sl = (0, slot, 0, 0, 0)
                kc = jax.lax.dynamic_update_slice(kc, blocks[0], sl)
                vc = jax.lax.dynamic_update_slice(vc, blocks[1], sl)
                if Ld:
                    dkc = jax.lax.dynamic_update_slice(dkc, blocks[2], sl)
                    dvc = jax.lax.dynamic_update_slice(dvc, blocks[3], sl)
                return kc, vc, dkc, dvc

            fn = self._res_fns[tb] = jax.jit(_res, donate_argnums=(0, 1,
                                                                   2, 3))
        self._kc, self._vc, self._dkc, self._dvc = fn(
            self._kc, self._vc, self._dkc, self._dvc, entry["blocks"],
            jnp.asarray(int(slot), jnp.int32))
        tok0, key = self._sample1_fn(
            entry["logits"][None],
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(int(top_k), jnp.int32),
            jnp.asarray(top_p, jnp.float32), jnp.asarray(key))
        return int(tok0), np.asarray(key)

    def window_check(self, prompt_len: int, max_new: int) -> None:
        from deeplearning4j_tpu.models.transformer_lm import (
            ContextWindowExceeded,
        )

        if prompt_len + max_new > self.max_length:
            raise ContextWindowExceeded(prompt_len, max_new,
                                        self.max_length)


class _DecoderBackend:
    """``DecoderLM`` decode backend: the cache the model's plan asks
    for, a segment at a time: a full layer's (L, S, hkv, hd, T) slab and
    a window layer's (L, S, hkv, hd, window) ring, K and V of their own
    head sizes; a latent layer's ONE (L, S, kv_rank + rotary_dim, T)
    slab, which its decode reads absorbed (no key or value of a head is
    made over it); a latent layer with an indexer keeps its slabs
    position-major, (L, S, T, 640) rows of latent entries that its decode
    GATHERS by the indexer's selection and, where the layer owns the
    indexer, (L, S, T, indexer head size) rows of indexer keys beside
    them, which every step scores whole; a layer that shares a selection
    owns the latent slab alone. Decode reads them and writes one column
    (or row) a slot in place after the layer loop; prefill writes a
    slot's columns of every slab from one pass, whole (no chunked prefill: it holds the decoding
    slots for its length). Speculation stays at K = 1 (an expert model
    routes per step), and there is no prefix cache: a ring holds a
    prompt's last ``window`` columns only, so a captured prefix could
    not be spliced under a longer prompt's own columns, and a latent
    slab has no capture path yet. A state-space layer keeps no columns
    at all: a float32 state (L, S, state size, heads x head size) and the
    convolution's tail (L, S, channels, d_conv - 1), the same bytes
    whatever the slot's length, read and written in place by
    every decode step inside the layer loop (idle slots bit for bit as
    they were) and written for one slot by a prefill. With such a layer
    K > 1 and a prefix cache are REFUSED (:class:`RecurrentStateError`):
    a state cannot be rolled back by dropping columns. A PARALLEL block
    (attention and a state-space mixer side by side) keeps both in ONE
    plan entry, K and V slabs and the state and tail: its segment counts
    the positions its slots read AND the slots it advances, its slots'
    bytes are both kinds', and it is refused what any state is. A stack that runs
    several passes a token (``DecoderConfig.passes``) keeps all of the
    above a PASS, passes x layers entries a segment, read and written by
    the same programs; an ``exit_threshold`` under 1 is REFUSED
    (:class:`EarlyExitError`).

    The slots' inputs live on the device as one array (``_state``), so
    the step has no ``decode`` that puts, dispatches and fetches in one
    call: it has a ``launch`` and a ``collect``, and the engine, which
    sees the pair, keeps ONE STEP IN FLIGHT (``GenerationEngine.
    _step_ahead``): step t+1 is launched from the array step t will hand
    back before step t's copy of it is fetched, so the device goes from
    one decode program to the next without waiting ~2-3 ms for the
    tokens' way to the host and the launch's way back (PERF.md, PRs 37
    and 39). ``_TransformerAheadBackend`` does the same for a
    ``TransformerLM`` that neither speculates nor keeps a prefix cache
    (PR 41); the backends that keep the slots' inputs on the host
    (``_TransformerBackend`` for K > 1 or a prefix cache,
    ``_RecurrentBackend``) cannot launch ahead and stay lock-step."""

    kind = "decoder"
    spec_k = 1
    draft_layers = 0
    supports_prefix_cache = False

    def __init__(self, model, n_slots: int, max_length: Optional[int],
                 prefill_buckets: Optional[Sequence[int]], trace_hook,
                 spec_k: int = 1):
        from deeplearning4j_tpu.models.decoder_lm import (
            decode_step,
            prefill_slot,
        )
        from deeplearning4j_tpu.models.transformer_lm import (
            prefill_bucket_lengths,
            sample_next_device,
            sample_next_rows,
        )

        self.model = model
        cfg = self._cfg = model.cfg
        self.vocab = int(cfg.vocab_size)
        self.n_slots = int(n_slots)
        self.max_length = (cfg.max_length if max_length is None
                           else min(int(max_length), cfg.max_length))
        self.buckets = prefill_bucket_lengths(
            self.max_length,
            prefill_buckets or getattr(model, "serving_seq_buckets", None))
        plan = cfg.cache_plan(self.n_slots, self.max_length)
        self.cache_bytes = sum(p["bytes"] for p in plan)
        #: what the engine counts of a launched step's slots, as the plan
        #: says of the layer kinds: the positions they have behind them
        #: where some layer keeps a latent cache; the same where some layer
        #: keeps keys and values and reads all that lie behind a slot (no
        #: window); the positions an indexer keeps for its attention (0: no
        #: layer selects), for those they score and those they select; the
        #: slots a step advances where some layer keeps a recurrent state
        #: (then no prefix cache, K = 1)
        self.latent = any(p["latent"] for p in plan)
        self.attends = any(p["attends"] for p in plan)
        self.index_topk = max(p["topk"] for p in plan)
        self.keeps_state = any(p["keeps_state"] for p in plan)
        #: passes over the stack a step runs, and the (pass, layer) pairs
        #: that keep a cache entry a position: the engine's counters
        self.passes = cfg.passes
        self.cache_entries = sum(p["entries"] for p in plan)
        if cfg.exit_threshold < 1.0:
            raise EarlyExitError(
                f"exit_threshold={cfg.exit_threshold}: the decode step runs "
                f"all {cfg.passes} passes for every slot; rows that leave "
                "after different passes, and the later passes' cache "
                "entries of a row that left, have no program here; serve "
                "with exit_threshold=1")
        if self.keeps_state and int(spec_k) > 1:
            raise RecurrentStateError(
                f"spec_decode_k={spec_k}: a rejected draft token would have "
                "to be taken out of the state-space layers' recurrent state, "
                "which cannot be rolled back by dropping columns; set "
                "spec_decode_k=1")
        self.reset()

        def _f32(bits):
            return jax.lax.bitcast_convert_type(bits, jnp.float32)

        def _decode(p, caches, state):
            # the slots' inputs arrive as one array and leave as one, the
            # next step's (see ``_state``)
            trace_hook("generation_decode")
            rows = state[:-1]
            toks, pos, k = rows[:, 0], rows[:, 1], rows[:, 3]
            left = rows[:, 2]
            active = left > 0
            keys = jax.lax.bitcast_convert_type(rows[:, 4:6], jnp.uint32)
            logits, caches, counts = decode_step(cfg, p, caches, toks, pos,
                                                 active)
            nxt, nkeys = sample_next_rows(
                logits, _counted(_f32(rows[:, 6]), active), k,
                _f32(rows[:, 7]), keys)
            nxt = jnp.where(active, nxt, toks)
            nkeys = jnp.where(active[:, None], nkeys, keys)
            after = jnp.concatenate(
                [nxt[:, None], (pos + active)[:, None],
                 (left - active)[:, None], k[:, None],
                 jax.lax.bitcast_convert_type(nkeys, jnp.int32),
                 rows[:, 6:8]], axis=1)
            last = jnp.zeros((1, 8), jnp.int32).at[0, :2].set(
                jnp.stack(counts).astype(jnp.int32))
            return caches, jnp.concatenate([after, last])

        def _prefill(p, caches, state, req):
            # req: the request's row as ``_state`` lays it (its token is
            # still to come: the word holds the slot), then the prompt
            # padded to its bucket
            trace_hook("generation_prefill")
            slot, ln, left, k = req[0], req[1], req[2], req[3]
            key = jax.lax.bitcast_convert_type(req[4:6], jnp.uint32)
            logits, caches = prefill_slot(cfg, p, caches, req[None, 8:], ln,
                                          slot)
            tok0, key = sample_next_device(logits, _f32(req[6]), k,
                                           _f32(req[7]), key)
            row = jnp.concatenate(
                [tok0, ln[None], left[None], k[None],
                 jax.lax.bitcast_convert_type(key, jnp.int32), req[6:8]])
            return caches, state.at[slot].set(row), row, logits[0]

        def _stop(state, stopped):
            # the host's edit of the rows it stopped itself: no step left
            return state.at[:, 2].multiply(1 - stopped)

        self._decode_fn = jax.jit(_decode, donate_argnums=(1,))
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1,))
        self._stop_fn = jax.jit(_stop)

    def reset(self) -> None:
        from deeplearning4j_tpu.models.decoder_lm import init_cache

        self._caches = init_cache(self._cfg, self.n_slots, self.max_length)
        #: what the next launch takes, on the device: the last launch's
        #: or prefill's output, maybe still to be computed (``_state``)
        self._slots_state = jnp.zeros((self.n_slots + 1, 8), jnp.int32)
        #: slots the host stopped since the last launch (``stop``)
        self._stopped: set = set()

    def release(self) -> None:
        """Let the cache go (engine shutdown)."""
        self._caches = self._slots_state = None

    bucket_for = _TransformerBackend.bucket_for
    #: by the slot's length, which an attention layer's columns set. A
    #: stack of state-space layers alone would have no such bound; it is
    #: held to ``max_length`` all the same (the engine's positions and the
    #: model's declared context), so that case is refused, not unbounded
    window_check = _TransformerBackend.window_check

    @staticmethod
    def _state(row, word0, pos, left, temperature, top_k, top_p, key):
        """A slot's inputs as one int32 row: token, position, the decode
        steps the slot has LEFT (active while > 0), top_k, the key's two
        words, and temperature and top_p as their bits; the slots' rows
        and a last row for the step's two expert counters are ONE array
        that never leaves the device. The decode program returns the
        next step's (tokens, positions advanced, steps left counted
        down, keys) and a prefill writes its slot's row, steps left =
        ``max_new - 1`` among it, so a launch needs nothing from the
        host: the loop launches step t+1 from the array step t will
        hand back and only then fetches step t's copy to stream its
        tokens. A request that ends by ``max_new`` stops on the device
        by itself; a stop the host decides (a deadline, a caller that
        gave up) it sees a step late, and ``stop`` zeroes that row's
        steps in the next launch: a whole put would roll the other
        slots back a step. A step costs one fetch and no put; a claim
        one put and one fetch. Seven puts a step took 5.7 ms of 54 on
        the chip among 42 streaming threads (PERF.md, PR 27), and the
        fetch-then-launch order left the device waiting 2-3 ms a step
        (PERF.md, PRs 37 and 39)."""
        row[0], row[1], row[2], row[3] = word0, pos, left, top_k
        row[4:6] = np.asarray(key, np.uint32).reshape(2).view(np.int32)
        row[6:8] = np.asarray([temperature, top_p], np.float32).view(
            np.int32)
        return row

    def prefill(self, slot: int, prompt: np.ndarray, temperature: float,
                top_k: int, top_p: float, key: np.ndarray, steps: int = 0):
        """As ``_TransformerBackend.prefill``; every prompt is bucketed:
        the dropless expert layer has no capacity for padding to take.
        ``steps``: the decode steps the slot runs after its first token.
        Dispatched behind the step in flight, whose caches and state it
        takes as they will be; the fetch of its row waits for both."""
        tp = int(prompt.shape[0])
        tb = self.bucket_for(tp)
        with _PREFILL_PUT:
            req = np.zeros((8 + tb,), np.int32)
            self._state(req[:8], slot, tp, steps, temperature, top_k, top_p,
                        key)
            req[8:8 + tp] = prompt
            req = jnp.asarray(req)
        # the prefill writes the whole row: the host's stop of the slot's
        # last occupant has nothing left to edit
        self._stopped.discard(slot)
        self._caches, self._slots_state, row, logits0 = self._prefill_fn(
            self.model.params_, self._caches, self._slots_state, req)
        del req
        row = np.asarray(row)
        return int(row[0]), row[4:6].view(np.uint32), tb, logits0

    def stop(self, slot: int) -> None:
        """The host ended ``slot``'s request before its steps ran out:
        its row has no step left from the next launch on."""
        self._stopped.add(slot)

    def launch(self):
        """Dispatch one decode step for all slots from the state on the
        device and return what ``collect`` takes: the step's own output
        array, which the NEXT launch reads too (it is not donated)."""
        with _DECODE_PUT:
            state = self._slots_state
            if self._stopped:
                stopped = np.zeros((self.n_slots + 1,), np.int32)
                stopped[list(self._stopped)] = 1
                self._stopped.clear()
                state = self._stop_fn(state, jnp.asarray(stopped))
        with _DECODE_DISPATCH:
            self._caches, state = self._decode_fn(
                self.model.params_, self._caches, state)
        self._slots_state = state
        return state

    @staticmethod
    def collect(state):
        """The tokens of the step ``launch`` returned ``state`` for, as
        host arrays: (next tokens (S,), (expert pairs computed here,
        held experts hit) of the step, all layers)."""
        with _DECODE_FETCH:
            rows = np.asarray(state)
            return rows[:-1, 0], (int(rows[-1, 0]), int(rows[-1, 1]))


class _TransformerAheadBackend(_TransformerBackend):
    """``_TransformerBackend`` for an engine that neither speculates nor
    keeps a prefix cache: the slots' inputs live on the device as the
    ONE array ``_DecoderBackend._state`` lays out (its last row, the
    step's counters, stays zero: a TransformerLM counts none), the step
    is a ``launch`` and a ``collect`` as ``_DecoderBackend``'s are, and
    the engine, which sees the pair, keeps one step in flight
    (``GenerationEngine._step_ahead``). Same slab, same ``decode_step``
    and sampler and key chain over the same serving copy of the weights,
    so the tokens are the lock-step backend's bit for bit; a step costs
    one fetch and no put (seven puts took 2.8 ms of a 19.8 ms step on
    the chip and the fetch-then-launch order another 2.2: PERF.md,
    PRs 37 and 41).

    A speculating engine reads and edits the host's copy of the tokens
    between two steps (``verify`` / ``draft``), and a prefix cache
    restores a slot from the host (``prefix_restore``, the completion
    replay): both stay on ``_TransformerBackend`` and the lock-step
    loop (``_pick_backend``)."""

    latent = False
    index_topk = 0
    keeps_state = False
    passes = 1

    def _build_programs(self, trace_hook) -> None:
        """The jitted programs over slots' inputs that live on the
        DEVICE: one array in, one array out."""
        from deeplearning4j_tpu.models.transformer_lm import (
            decode_step,
            sample_next_device,
            sample_next_rows,
        )

        cfg = self._cfg

        def _f32(bits):
            return jax.lax.bitcast_convert_type(bits, jnp.float32)

        def _decode(p, kc, vc, state):
            trace_hook("generation_decode")
            rows = state[:-1]
            toks, pos, k = rows[:, 0], rows[:, 1], rows[:, 3]
            left = rows[:, 2]
            active = left > 0
            keys = jax.lax.bitcast_convert_type(rows[:, 4:6], jnp.uint32)
            logits, c = decode_step(cfg, p, {"k": kc, "v": vc, "pos": pos},
                                    toks, active)
            nxt, nkeys = sample_next_rows(
                logits, _counted(_f32(rows[:, 6]), active), k,
                _f32(rows[:, 7]), keys)
            nxt = jnp.where(active, nxt, toks)
            nkeys = jnp.where(active[:, None], nkeys, keys)
            after = jnp.concatenate(
                [nxt[:, None], (pos + active)[:, None],
                 (left - active)[:, None], k[:, None],
                 jax.lax.bitcast_convert_type(nkeys, jnp.int32),
                 rows[:, 6:8]], axis=1)
            return c["k"], c["v"], jnp.concatenate([after, state[-1:]])

        def _prefill(p, kc, vc, state, req):
            # req: the request's row as ``_state`` lays it (its token is
            # still to come: the word holds the slot), then the prompt
            # padded to its bucket
            trace_hook("generation_prefill")
            slot, ln, left, k = req[0], req[1], req[2], req[3]
            key = jax.lax.bitcast_convert_type(req[4:6], jnp.uint32)
            logits, kc, vc, _ = _prefill_columns(cfg, p, kc, vc,
                                                 req[None, 8:], ln, slot)
            tok0, key = sample_next_device(logits, _f32(req[6]), k,
                                           _f32(req[7]), key)
            row = jnp.concatenate(
                [tok0, ln[None], left[None], k[None],
                 jax.lax.bitcast_convert_type(key, jnp.int32), req[6:8]])
            return kc, vc, state.at[slot].set(row), row

        def _stop(state, stopped):
            # the host's edit of the rows it stopped itself: no step left
            return state.at[:, 2].multiply(1 - stopped)

        self._decode_fn = jax.jit(_decode, donate_argnums=(1, 2))
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1, 2))
        self._stop_fn = jax.jit(_stop)

    def reset(self) -> None:
        super().reset()
        #: what the next launch takes, on the device: the last launch's
        #: or prefill's output, maybe still to be computed
        self._slots_state = jnp.zeros((self.n_slots + 1, 8), jnp.int32)
        #: slots the host stopped since the last launch (``stop``)
        self._stopped: set = set()

    def release(self) -> None:
        super().release()
        self._slots_state = None

    _state = staticmethod(_DecoderBackend._state)
    stop = _DecoderBackend.stop
    collect = staticmethod(_DecoderBackend.collect)

    def prefill(self, slot: int, prompt: np.ndarray, temperature: float,
                top_k: int, top_p: float, key: np.ndarray, steps: int = 0):
        """As ``_TransformerBackend.prefill`` (an expert model's prompt
        is not bucketed), with ``steps``, the decode steps the slot runs
        after its first token, written into the slot's row on the
        device; no logits come back (there is no prefix cache to keep
        them). One put and one fetch a claim. Dispatched behind the step
        in flight, whose slabs and state it takes as they will be; the
        fetch of its row waits for both."""
        tp = int(prompt.shape[0])
        tb = tp if self._cfg.n_experts > 0 else self.bucket_for(tp)
        with _PREFILL_PUT:
            req = np.zeros((8 + tb,), np.int32)
            self._state(req[:8], slot, tp, steps, temperature, top_k, top_p,
                        key)
            req[8:8 + tp] = prompt
            req = jnp.asarray(req)
        # the prefill writes the whole row: the host's stop of the slot's
        # last occupant has nothing left to edit
        self._stopped.discard(slot)
        self._kc, self._vc, self._slots_state, row = self._prefill_fn(
            self._params(), self._kc, self._vc, self._slots_state, req)
        del req
        row = np.asarray(row)
        return int(row[0]), row[4:6].view(np.uint32), tb, None

    def launch(self):
        """Dispatch one decode step for all slots from the state on the
        device and return what ``collect`` takes: the step's own output
        array, which the NEXT launch reads too (it is not donated; the
        slabs are). The weights are read at each launch: a swap of
        ``model.params_`` is served for one cast and no recompile from
        the next LAUNCH on, which with a step in flight is one step
        later than the lock-step loop would serve it."""
        with _DECODE_PUT:
            state = self._slots_state
            if self._stopped:
                stopped = np.zeros((self.n_slots + 1,), np.int32)
                stopped[list(self._stopped)] = 1
                self._stopped.clear()
                state = self._stop_fn(state, jnp.asarray(stopped))
        with _DECODE_DISPATCH:
            self._kc, self._vc, state = self._decode_fn(
                self._params(), self._kc, self._vc, state)
        self._slots_state = state
        return state


def _cell_decode_supported(model) -> bool:
    """True when the model's layer stack can decode through the direct
    cell path: no preprocessors, every recurrent layer exposes ``_step``
    (the single-timestep cell the fused Pallas kernel backs), and every
    other layer is a rank-polymorphic per-timestep head. Anything else
    (Bidirectional, pooling wrappers, conv stacks) keeps the generic
    ``_forward`` path."""
    from deeplearning4j_tpu.nn.conf.layers.core import (
        ActivationLayer,
        DenseLayer,
        LossLayer,
    )
    from deeplearning4j_tpu.nn.conf.layers.recurrent import (
        BaseRecurrentLayer,
        RnnLossLayer,
        RnnOutputLayer,
    )

    if getattr(model.conf, "preprocessors", None):
        return False
    for layer in model.layers:
        if isinstance(layer, BaseRecurrentLayer):
            if not hasattr(layer, "_step"):
                return False
        elif not isinstance(layer, (RnnOutputLayer, RnnLossLayer,
                                    DenseLayer, ActivationLayer,
                                    LossLayer)):
            return False
    return True


class _RecurrentBackend:
    """Incremental-decode backend for recurrent MultiLayerNetworks
    (TextGenerationLSTM): per-slot carried (h, c) state stacked to
    ``(n_slots, ...)`` leaves. No KV slab — the carry IS the whole
    decode state, so ``max_length`` only bounds the request window, not
    memory.

    Two decode-step programs (PR 9 residue fix):

    - **cell path** (default when the stack supports it): one direct
      ``layer._step`` call per recurrent layer on rank-2 ``(S, d)``
      activations — no ``lax.scan`` machinery, no time-axis reshapes —
      so the per-token program is exactly the fused LSTM cell dispatches
      (Pallas on TPU, the reference composition elsewhere) plus the
      output head and the in-graph sampler;
    - **legacy path** (``cell_path=False`` or unsupported stacks): the
      generic ``_forward`` carry path over a T=1 sequence.

    Both are one jitted dispatch per token for all slots, bit-identical
    outputs (asserted in tests), zero steady-state recompiles."""

    kind = "recurrent"
    #: a carried state, no keys and values a position: nothing to count
    attends = False

    def __init__(self, model, n_slots: int, max_length: Optional[int],
                 prefill_buckets: Optional[Sequence[int]], trace_hook,
                 cell_path: Optional[bool] = None):
        import os as _os

        from deeplearning4j_tpu.models.transformer_lm import (
            prefill_bucket_lengths,
            sample_next_device,
            sample_next_rows,
        )
        from deeplearning4j_tpu.nn.conf.layers.recurrent import (
            BaseRecurrentLayer,
        )

        self.model = model
        self.n_slots = int(n_slots)
        self.max_length = int(max_length) if max_length else 256
        self.buckets = prefill_bucket_lengths(
            self.max_length,
            prefill_buckets or getattr(model, "serving_seq_buckets", None))
        self.vocab = int(model.layers[0].n_in)
        if cell_path is None:
            cell_path = (_os.environ.get("DL4J_TPU_LSTM_DECODE_CELL", "1")
                         != "0")
        self.cell_path = bool(cell_path) and _cell_decode_supported(model)
        self.reset()
        self.cache_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(self._carries))
        V = self.vocab

        def _cell_forward(p, st, carries, x):
            """Direct per-timestep stack: (S, V) one-hot → (S, vocab)
            head output + updated carries. Mirrors ``_forward``'s
            semantics for the supported layer set (train=False: no
            dropout, no weight noise; recurrent masks are irrelevant at
            T=1 with all-real rows)."""
            if model._compute_dtype is not None:
                p = model._cast_for_compute(p)
                x = x.astype(model._compute_dtype)
            nc = [None] * len(model.layers)
            for idx, layer in enumerate(model.layers):
                if isinstance(layer, BaseRecurrentLayer):
                    c_new, x = layer._step(p[idx], carries[idx], x)
                    nc[idx] = c_new
                else:
                    x, _ = layer.apply(p[idx], x, state=st[idx],
                                       train=False)
            return x, nc

        def _decode(p, st, carries, toks, active, t, k, pp, keys):
            trace_hook("generation_decode")
            if self.cell_path:
                x = jax.nn.one_hot(toks, V, dtype=jnp.float32)
                y, nc = _cell_forward(p, st, carries, x)
                logits = jnp.log(jnp.clip(y.astype(jnp.float32),
                                          1e-30, None))
            else:
                x = jax.nn.one_hot(toks, V, dtype=jnp.float32)[:, None, :]
                y, _, _, nc, _ = model._forward(p, st, x, train=False,
                                                rng=None, carries=carries)
                logits = jnp.log(jnp.clip(y[:, -1, :].astype(jnp.float32),
                                          1e-30, None))
            nxt, nkeys = sample_next_rows(logits, _counted(t, active), k,
                                          pp, keys)
            nxt = jnp.where(active, nxt, toks)
            nkeys = jnp.where(active[:, None], nkeys, keys)
            nc = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    active.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
                nc, carries)
            return nxt, nkeys, nc

        def _prefill(p, st, carries, ids, ln, slot, t, k, pp, key):
            trace_hook("generation_prefill")
            tb = ids.shape[0]
            x = jax.nn.one_hot(ids, V, dtype=jnp.float32)[None]
            mask = (jnp.arange(tb) < ln).astype(jnp.float32)[None]
            c1 = model._init_carries(1)
            y, _, _, nc1, _ = model._forward(p, st, x, train=False, rng=None,
                                             fmask=mask, carries=c1)
            y_last = jax.lax.dynamic_index_in_dim(y, ln - 1, axis=1,
                                                  keepdims=False)
            logits = jnp.log(jnp.clip(y_last.astype(jnp.float32),
                                      1e-30, None))
            tok0, key = sample_next_device(logits, t, k, pp, key)
            carries = jax.tree_util.tree_map(
                lambda big, row: big.at[slot].set(row[0]), carries, nc1)
            return tok0[0], key, carries, logits[0]

        self._decode_fn = jax.jit(_decode, donate_argnums=(2,))
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(2,))

        def _sample1(logits, t, k, pp, key):
            trace_hook("generation_prefix_sample")
            tok0, key = sample_next_device(logits, t, k, pp, key)
            return tok0[0], key

        self._sample1_fn = jax.jit(_sample1)

        def _cap(carries, slot):
            trace_hook("generation_prefix_capture")
            return jax.tree_util.tree_map(lambda a: a[slot], carries)

        def _res(carries, rows, slot):
            trace_hook("generation_prefix_restore")
            return jax.tree_util.tree_map(
                lambda big, row: big.at[slot].set(row), carries, rows)

        self._cap_fn = jax.jit(_cap)
        self._res_fn = jax.jit(_res, donate_argnums=(0,))

    def reset(self) -> None:
        """(Re)build the carried state — at construction, and for
        engine decode-failure recovery (the failed dispatch consumed
        the donated carries)."""
        self._carries = self.model._init_carries(self.n_slots)

    def release(self) -> None:
        """Let the carried state go (engine shutdown)."""
        self._carries = None

    def bucket_for(self, prompt_len: int) -> int:
        return next(t for t in self.buckets if t >= prompt_len)

    def prefill(self, slot, prompt, temperature, top_k, top_p, key):
        tp = int(prompt.shape[0])
        tb = self.bucket_for(tp)
        with _PREFILL_PUT:
            ids = np.zeros((tb,), np.int32)
            ids[:tp] = prompt
            args = (jnp.asarray(ids), jnp.asarray(tp, jnp.int32),
                    jnp.asarray(int(slot), jnp.int32),
                    jnp.asarray(temperature, jnp.float32),
                    jnp.asarray(int(top_k), jnp.int32),
                    jnp.asarray(top_p, jnp.float32), jnp.asarray(key))
        tok0, key, self._carries, logits0 = self._prefill_fn(
            self.model.params_, self.model.state_, self._carries, *args)
        del args  # see _TransformerBackend.decode
        return int(tok0), np.asarray(key), tb, logits0

    # -- shared-prefix cache hooks ------------------------------------------
    def prefix_capture(self, slot, tb, logits0) -> dict:
        """The recurrent decode state is the carry, so a prefix entry is
        the slot's carry rows + the stored prefill logits — one gather
        program regardless of bucket."""
        rows = self._cap_fn(self._carries, jnp.asarray(int(slot),
                                                       jnp.int32))
        nbytes = sum(int(a.size) * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(rows)) \
            + int(logits0.size) * 4
        return {"rows": rows, "logits": logits0, "tb": int(tb),
                "bytes": int(nbytes)}

    def prefix_restore(self, slot, entry, temperature, top_k, top_p, key):
        self._carries = self._res_fn(
            self._carries, entry["rows"],
            jnp.asarray(int(slot), jnp.int32))
        tok0, key = self._sample1_fn(
            entry["logits"][None],
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(int(top_k), jnp.int32),
            jnp.asarray(top_p, jnp.float32), jnp.asarray(key))
        return int(tok0), np.asarray(key)

    def decode(self, tokens, pos, active, temperature, top_k, top_p, keys):
        with _DECODE_PUT:
            args = (jnp.asarray(tokens), jnp.asarray(active),
                    jnp.asarray(temperature), jnp.asarray(top_k),
                    jnp.asarray(top_p), jnp.asarray(keys))
        with _DECODE_DISPATCH:
            nxt, nkeys, self._carries = self._decode_fn(
                self.model.params_, self.model.state_, self._carries,
                *args)
            del args  # see _TransformerBackend.decode
        with _DECODE_FETCH:
            return np.asarray(nxt), np.asarray(nkeys)

    def window_check(self, prompt_len: int, max_new: int) -> None:
        from deeplearning4j_tpu.models.transformer_lm import (
            ContextWindowExceeded,
        )

        if prompt_len + max_new > self.max_length:
            raise ContextWindowExceeded(prompt_len, max_new,
                                        self.max_length)


def _pick_backend(model, n_slots, max_length, prefill_buckets, trace_hook,
                  on_param_cast, cell_path: Optional[bool] = None,
                  spec_k: int = 1, draft_layers: int = 0,
                  prefix_cache: bool = False):
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM

    if isinstance(model, TransformerLM):
        if spec_k == 1 and not prefix_cache:
            # nothing reads or edits the host's copy of the slots'
            # inputs between two steps: they live on the device and the
            # loop launches ahead
            return _TransformerAheadBackend(model, n_slots, max_length,
                                            prefill_buckets, trace_hook,
                                            on_param_cast=on_param_cast)
        return _TransformerBackend(model, n_slots, max_length,
                                   prefill_buckets, trace_hook,
                                   spec_k=spec_k,
                                   draft_layers=draft_layers,
                                   on_param_cast=on_param_cast)
    if isinstance(model, DecoderLM):
        return _DecoderBackend(model, n_slots, max_length, prefill_buckets,
                               trace_hook, spec_k=spec_k)
    layers = getattr(model, "layers", None)
    if layers is not None:
        from deeplearning4j_tpu.nn.conf.layers.recurrent import (
            BaseRecurrentLayer,
        )

        if any(isinstance(l, BaseRecurrentLayer) for l in layers):
            return _RecurrentBackend(model, n_slots, max_length,
                                     prefill_buckets, trace_hook,
                                     cell_path=cell_path)
    raise TypeError(
        f"{type(model).__name__} has no incremental-decode path: expected "
        "a TransformerLM (KV-cache slab), a DecoderLM (a cache sized by "
        "layer kind) or a MultiLayerNetwork with recurrent layers "
        "(carried h/c state)")


# --------------------------------------------------------------------------
# memory validation
# --------------------------------------------------------------------------
def generation_memory_report(model, n_slots: int,
                             max_length: Optional[int] = None,
                             draft_layers: int = 0) -> dict:
    """Analytic 'will the decode slab fit' answer BEFORE allocating it —
    the nn/conf/memory.py estimator discipline applied to generation
    state: per-slot cache bytes × n_slots + resident params + for a
    ``TransformerLM`` under a compute dtype ``param_copy_bytes``, the
    copy of the block matrices and the head its programs read
    (``transformer_lm.serving_copy``; the float32 masters stay
    resident beside it). ``draft_layers`` > 0 adds the truncated-layer
    speculation slab (the draft model keeps its own KV over the first
    ``draft_layers`` blocks)."""
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM
    from deeplearning4j_tpu.models.transformer_lm import (
        TransformerLM,
        serving_copy,
    )

    plan = None
    param_copy = 0
    if isinstance(model, (TransformerLM, DecoderLM)):
        cfg = model.cfg
        T = cfg.max_length if max_length is None else min(int(max_length),
                                                          cfg.max_length)
        params = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                     for p in jax.tree_util.tree_leaves(model.params_))
        if isinstance(model, DecoderLM):
            # sized by layer kind: the slot's length for a full layer,
            # a ring of ``window`` columns for a window layer, K and V
            # by head; one entry of kv_rank + rotary_dim values a
            # position for a latent layer (with an indexer: a row of
            # whole lane tiles a position and, where the layer owns the
            # indexer, one indexer key beside it; a layer that shares
            # a selection owns no key slab); a state and a convolution
            # tail a slot, whatever T, for a state-space layer
            plan = cfg.cache_plan(n_slots, T)
            cache = sum(p["bytes"] for p in plan)
        else:
            hd = cfg.d_model // cfg.n_heads
            itemsize = 2 if cfg.compute_dtype == "bfloat16" else 4
            cache = 2 * (cfg.n_layers + int(draft_layers)) * int(n_slots) \
                * cfg.n_heads * T * hd * itemsize
            copy = jax.eval_shape(lambda p: serving_copy(cfg, p),
                                  model.params_)
            param_copy = sum(
                int(np.prod(c.shape)) * c.dtype.itemsize
                for m, c in zip(jax.tree_util.tree_leaves(model.params_),
                                jax.tree_util.tree_leaves(copy))
                if c.dtype != m.dtype)
    else:
        # recurrent nets: the carry is the decode state; lean on the
        # layer-wise estimator for params + per-slot activation state
        from deeplearning4j_tpu.nn.conf.memory import memory_report_mln

        report = memory_report_mln(model.conf)
        params = report.total_params * 4
        cache = report.total_memory_bytes(batch_size=int(n_slots),
                                          training=False) - params
        cache = max(cache, 0)
    out = {"cache_bytes": int(cache), "param_bytes": int(params),
           "param_copy_bytes": int(param_copy),
           "total_bytes": int(cache) + int(params) + int(param_copy),
           "n_slots": int(n_slots), "max_length": max_length}
    if plan is not None:
        out["cache_plan"] = [
            {k: p[k] for k in ("kind", "layers", "passes", "columns", "ring",
                               "values", "row", "index", "bytes", "state",
                               "conv", "bytes_columns", "bytes_state")
             if k in p}
            for p in plan]
        # the recurrent state (and its tails) apart from the slabs of
        # columns: the first does not grow with max_length; an entry that
        # is both (a parallel block's) says its bytes by half
        out["state_bytes"] = sum(p.get("bytes_state", p["bytes"])
                                 for p in plan if "state" in p)
        out["slab_bytes"] = int(cache) - out["state_bytes"]
    return out


def _device_bytes_limit() -> Optional[int]:
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — backend without a memory_stats API
        return None
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------
class _Launched(NamedTuple):
    """A decode step that was launched and not yet collected; beside
    it in ``GenerationEngine._flight`` lies what the backend's
    ``collect`` takes."""

    gen: int                #: the step's id
    t0: float               #: ``time.monotonic()`` at its launch
    ran: np.ndarray         #: (S,) bool: the slots it runs
    slots: list             #: the requests that held the slots then
    drawn: bool             #: the sampler's branches, as ``_step`` counts
    filtered: bool
    latent_positions: int   #: what ``record_latent_positions`` takes
    attn_positions: int     #: what ``record_attn_positions`` takes
    selected_positions: int  #: ``record_selection``'s second (the first
    #: is ``latent_positions``: an indexer scores every position behind)
    state_slots: int        #: what ``record_state_slots`` takes


class GenerationEngine:
    """Slotted continuous-batching decode engine over one model.

    One background worker owns ALL device state (slab / carries, under
    ``_dev_lock``); callers only touch the bounded admission queue and
    their own :class:`GenerationRequest`. Hot params reload composes:
    every dispatch looks at ``model.params_``, so an atomic params swap
    (same shapes) takes effect at the next dispatch (the next token, or
    with a step in flight the one after it), zero recompiles. A
    ``TransformerLM`` under a compute dtype pays one cast program a
    swap, not one a dispatch: its programs read a copy of the block
    matrices and the head in that dtype, re-made when the leaves of
    ``params_`` are other objects (``_TransformerBackend._params``;
    ``param_casts`` in the metrics counts them).

    The loop runs one of two orders, by what the backend offers. Where
    it keeps the slots' inputs on the host (``decode``:
    ``_TransformerBackend``, which an engine built with
    ``spec_decode_k`` > 1 or a prefix cache gets, and
    ``_RecurrentBackend``) the loop is lock-step: put, dispatch, fetch,
    emit, turn. Where they live on the device and the step comes as
    ``launch`` / ``collect`` (``_DecoderBackend``, and
    ``_TransformerAheadBackend`` for a ``TransformerLM`` with K = 1 and
    no prefix cache) the loop keeps one step in flight
    (``_step_ahead``): launch t+1, fetch and emit t, turn, launch t+2.
    The tokens are the same; the device no longer waits for the host
    between two steps, and a stop that only the host can decide
    (deadline, a caller that gave up) is honoured one thrown-away
    slot-step late (``late_slot_steps``).

    ``memory_limit_bytes``: explicit budget, ``"auto"`` (device
    ``bytes_limit`` when the backend reports one, else unchecked), or
    None to skip the check."""

    def __init__(self, model, n_slots: int = 8,
                 max_length: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 queue_limit: int = 64, default_timeout_s: float = 120.0,
                 metrics: Optional[GenerationMetrics] = None,
                 memory_limit_bytes="auto", stall_ms: float = 2000.0,
                 trace_requests: bool = True,
                 traces: Optional["rtrace.TraceBuffer"] = None,
                 watchdog_mult: Optional[float] = 20.0,
                 watchdog_min_s: float = 30.0,
                 decode_cell_path: Optional[bool] = None,
                 spec_decode_k: int = 1, draft_mode: str = "ngram",
                 prefix_cache_mb: float = 0.0):
        self.metrics = metrics if metrics is not None else GenerationMetrics()
        self.trace_requests = bool(trace_requests)
        self.traces = traces
        self.default_timeout_s = float(default_timeout_s)
        self.stall_ms = float(stall_ms)
        #: a decode dispatch in flight longer than
        #: ``max(watchdog_min_s, watchdog_mult × rolling step time)``
        #: trips the watchdog: escalated ``decode_stall`` flight event +
        #: active requests failed typed (:class:`DecodeStalledError`) —
        #: a HUNG dispatch must not wedge every caller the way a FAILED
        #: one already doesn't. None disables the watchdog.
        self.watchdog_mult = (None if watchdog_mult is None
                              else float(watchdog_mult))
        self.watchdog_min_s = float(watchdog_min_s)
        self._step_ewma_s: Optional[float] = None
        self._dispatch_t0: Optional[float] = None
        #: dispatch generation counter + the generation a trip belongs
        #: to: the watchdog tags its trip with the generation it
        #: observed hung, and the worker only honors a trip for the
        #: dispatch it actually fired on — a dispatch that completes
        #: just past the limit must not get its trip charged to the
        #: NEXT, healthy dispatch. It is also the step's id, the cause
        #: of the step's ring entries (obs/trace.py): unique in the
        #: process, an engine's ids counting up from its own base
        self._dispatch_gen = next(_ENGINE_IDS) << 32
        self._stall_gen = -1
        #: ``time.time_ns()`` at the end of the last step's ``gen.emit``,
        #: where ``gen.turn`` begins; None when no step has just ended
        #: (an idle loop, a failed dispatch)
        self._turn_t0: Optional[int] = None
        self._stall_tripped = False
        #: identity tags merged into this engine's chaos seam ctx — the
        #: router tags canary generation engines so a drill can target
        #: exactly the canary's decode dispatches
        self.chaos_ctx: Dict[str, object] = {}
        #: EWMA of tokens decoded per finished request — the
        #: Retry-After estimator's occupancy term (a queued request
        #: holds a slot for ~this many steps, not one)
        self._req_steps_ewma: Optional[float] = None
        #: fn-name → XLA programs traced (retrace-guard instrument)
        self.trace_counts: Dict[str, int] = {}
        self._retrace_counters = {}

        def trace_hook(fn: str) -> None:
            # trace-time side effect (never runs at dispatch time):
            # bump the host count, the registry counter and the flight
            # recorder — a steady-state recompile must be LOUD
            self.trace_counts[fn] = self.trace_counts.get(fn, 0) + 1
            if fn not in self._retrace_counters:
                self._retrace_counters[fn] = self.metrics.registry.counter(
                    "jit_retraces_total",
                    "distinct XLA programs traced per jitted function",
                    labels={"fn": fn})
            self._retrace_counters[fn].inc()
            from deeplearning4j_tpu.obs import flight as _flight

            _flight.record("retrace", fn=fn)

        if draft_mode not in ("ngram", "truncated"):
            raise ValueError(f"draft_mode must be 'ngram' or 'truncated',"
                             f" got {draft_mode!r}")
        if int(spec_decode_k) < 1:
            raise ValueError(
                f"spec_decode_k must be >= 1, got {spec_decode_k}")
        draft_layers = 0
        if draft_mode == "truncated" and int(spec_decode_k) > 1:
            draft_layers = max(
                getattr(getattr(model, "cfg", None), "n_layers", 0) // 2,
                0)
        prefix_cache = bool(prefix_cache_mb and float(prefix_cache_mb) > 0)
        #: None → auto (env ``DL4J_TPU_LSTM_DECODE_CELL``, else on for
        #: supported recurrent stacks); False forces the legacy
        #: ``_forward``-over-T=1 decode program (the bench's reference
        #: leg). Ignored by the transformer backend.
        self.backend = _pick_backend(model, n_slots, max_length,
                                     prefill_buckets, trace_hook,
                                     self.metrics.record_param_cast,
                                     cell_path=decode_cell_path,
                                     spec_k=int(spec_decode_k),
                                     draft_layers=draft_layers,
                                     prefix_cache=prefix_cache)
        self.n_slots = self.backend.n_slots
        self.max_length = self.backend.max_length
        #: effective speculation width: the backend may pin K=1 (MoE,
        #: recurrent stacks) regardless of the requested knob
        self.spec_decode_k = getattr(self.backend, "spec_k", 1)
        self.draft_mode = (
            None if self.spec_decode_k <= 1
            else ("truncated" if getattr(self.backend, "draft_layers", 0)
                  else "ngram"))
        self._draft = (_NgramDraft() if self.draft_mode == "ngram"
                       else None)
        #: per-slot (t[-2], t[-1]) context feeding the n-gram draft
        self._ctx = np.zeros((self.n_slots, 2), np.int64)
        if prefix_cache and getattr(self.backend, "keeps_state", False):
            raise RecurrentStateError(
                f"the {self.backend.kind} backend has no prefix cache for a "
                "model with state-space layers: a prefix's recurrent state "
                "is not a run of columns that could be copied under a longer "
                "prompt's own, and no snapshot of it is kept; set "
                "prefix_cache_mb=0")
        if prefix_cache and not getattr(self.backend,
                                        "supports_prefix_cache", True):
            raise ValueError(
                f"the {self.backend.kind} backend has no prefix cache (a "
                "window layer's ring keeps a prompt's last columns only, "
                "a latent slab has no capture path); set prefix_cache_mb=0")
        self._prefix_cache = (
            PrefixCache(int(float(prefix_cache_mb) * (1 << 20)),
                        self.metrics)
            if prefix_cache else None)
        #: per-slot completion replay: a prefix-cache entry remembers
        #: the prompt's first greedy completion, and later hits replay
        #: it as the slot's draft source (the exact verify rule keeps
        #: correctness — a replayed token is a PROPOSAL, never an
        #: output). Invalidated at the first emitted token that
        #: diverges. _slot_pk remembers the claiming request's cache
        #: key so its finished greedy completion can be attached.
        self._replay: List[Optional[List[int]]] = [None] * self.n_slots
        self._slot_pk: List[Optional[tuple]] = [None] * self.n_slots
        self.metrics.set_slots(self.n_slots)
        self.metrics.set_cache_entries(
            getattr(self.backend, "cache_entries", 0))

        self.memory_report = generation_memory_report(
            model, self.n_slots, self.backend.max_length,
            draft_layers=getattr(self.backend, "draft_layers", 0))
        self._param_count = max(
            self.memory_report["param_bytes"] // 4, 1)
        if self._prefix_cache is not None:
            # the prefix cache's byte budget is device memory too —
            # count it against the same limit the slab answers to
            self.memory_report["prefix_cache_limit_bytes"] = \
                self._prefix_cache.limit_bytes
            self.memory_report["total_bytes"] += \
                self._prefix_cache.limit_bytes
        limit = (_device_bytes_limit() if memory_limit_bytes == "auto"
                 else memory_limit_bytes)
        self.memory_report["limit_bytes"] = limit
        from deeplearning4j_tpu.obs import flight as _flight

        _flight.record("generation_memory_check",
                       **{k: v for k, v in self.memory_report.items()
                          if v is not None})
        if limit is not None and self.memory_report["total_bytes"] > limit:
            raise GenerationMemoryError(
                f"decode slab needs {self.memory_report['cache_bytes']:,} "
                f"cache bytes (+{self.memory_report['param_bytes']:,} "
                f"params, +{self.memory_report['param_copy_bytes']:,} "
                f"their serving copy) for n_slots={self.n_slots} × "
                f"max_length={self.backend.max_length}, over the "
                f"{limit:,}-byte budget; lower n_slots or max_length")

        S = self.n_slots
        self._queue: "queue.Queue[GenerationRequest]" = queue.Queue(
            maxsize=max(int(queue_limit), 1))
        self._slots: List[Optional[GenerationRequest]] = [None] * S
        self._active = np.zeros((S,), bool)
        self._tokens = np.zeros((S,), np.int32)
        self._pos = np.zeros((S,), np.int32)
        self._temp = np.zeros((S,), np.float32)
        self._topk = np.zeros((S,), np.int32)
        self._topp = np.zeros((S,), np.float32)
        self._keys = np.zeros((S, 2), np.uint32)
        #: the backend's step is a launch and a collect: one step is
        #: kept in flight (``_step_ahead``)
        self._ahead = hasattr(self.backend, "launch")
        #: launched, uncollected steps, oldest first, each (what the
        #: backend's ``collect`` takes, the step): at most two, and two
        #: only between a launch and the collect that follows it
        self._flight: "deque[Tuple[object, _Launched]]" = deque()
        #: decode steps each slot has left on the device, which counts
        #: them down itself; the host's copy, counted down at a launch
        self._left = np.zeros((S,), np.int32)
        #: ``time.monotonic()`` when the last collect or claim ended:
        #: where the next collected step's seconds begin
        self._step_ref = 0.0
        self._shutdown = False
        self._dev_lock = witnessed_lock("generate.device")
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="dl4j-tpu-generate")
        self._worker.start()
        self._watchdog: Optional[threading.Thread] = None
        if self.watchdog_mult is not None:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, daemon=True,
                name="dl4j-tpu-generate-watchdog")
            self._watchdog.start()

    # -- client side --------------------------------------------------------
    def submit(self, prompt_ids, max_new: int = 20, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 0.0, seed: int = 0,
               timeout: Optional[float] = None,
               trace: Optional[bool] = None,
               on_done: Optional[Callable] = None) -> GenerationRequest:
        """Enqueue a generation request; returns immediately (consume
        ``req.stream()`` or block on ``req.result()``). Raises the typed
        batcher-vocabulary failures: window overflow, queue-full
        overload, shutdown. ``on_done`` (``fn(request, error_or_None)``)
        is installed BEFORE the request is enqueued, so even a
        completion that races the submit return (instant decode
        failure, an already-expired deadline) is observed — the
        router's canary metric gate depends on every completion being
        counted."""
        from deeplearning4j_tpu.models.transformer_lm import (
            _validate_sampling,
        )

        if self._shutdown:
            raise ServerShutdownError("generation engine is shut down")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        self.backend.window_check(prompt.size, int(max_new))
        _validate_sampling(temperature, top_k, top_p)
        timeout = self.default_timeout_s if timeout is None else timeout
        req = GenerationRequest(
            prompt, max_new, temperature, top_k, top_p, seed,
            deadline=None if timeout is None
            else time.monotonic() + float(timeout),
            trace=self.trace_requests if trace is None else bool(trace))
        req.on_done = on_done
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            self.metrics.record_reject()
            from deeplearning4j_tpu.obs import flight as _flight

            _flight.record("overload_reject", surface="generate",
                           prompt_len=int(prompt.size),
                           queue_limit=self._queue.maxsize)
            err = ServerOverloadedError(
                f"generation queue full ({self._queue.maxsize} requests); "
                "retry with backoff or add slots")
            err.retry_after_s = self.retry_after_s()
            raise err from None
        if self._shutdown and req.fail(
                ServerShutdownError("engine shut down while enqueuing")):
            raise ServerShutdownError("engine shut down while enqueuing")
        self.metrics.record_request()
        return req

    def generate(self, prompt_ids, timeout: Optional[float] = None,
                 **kwargs) -> np.ndarray:
        """Blocking convenience: submit + result."""
        req = self.submit(prompt_ids, timeout=timeout, **kwargs)
        return req.result(timeout=timeout or self.default_timeout_s)

    # -- introspection ------------------------------------------------------
    @property
    def active_slots(self) -> int:
        return int(self._active.sum())

    def queue_depth(self) -> int:
        return self._queue.qsize()

    def inflight(self) -> int:
        """Accepted-but-unfinished requests (decoding slots + queued):
        what a draining replica must let run out before it can be
        retired without dropping a stream."""
        return self.active_slots + self._queue.qsize()

    def retry_after_s(self) -> float:
        """Backoff hint for overloaded clients (the ``Retry-After``
        header on 503s), clamped to [1, 60]s. The batcher's
        depth×per-dispatch formula is wrong for the token loop — one
        decode dispatch retires one TOKEN for every slot, not one
        queued request — so the occupancy term scales by the typical
        tokens-per-request and the slot count: ``queued / n_slots ×
        steps-per-request × step time`` ≈ when a queued request will
        actually have drained."""
        steps = self._req_steps_ewma or 20.0
        waves = self._queue.qsize() / max(self.n_slots, 1)
        est = waves * steps * (self._step_ewma_s or 0.0)
        return min(max(est, 1.0), 60.0)

    def describe(self) -> dict:
        return {
            "backend": self.backend.kind,
            "decode_cell_path": getattr(self.backend, "cell_path", None),
            "n_slots": self.n_slots,
            "active_slots": self.active_slots,
            "max_length": self.backend.max_length,
            "prefill_buckets": list(self.backend.buckets),
            "queue_depth": self.queue_depth(),
            "spec_decode_k": self.spec_decode_k,
            "draft_mode": self.draft_mode,
            "prefix_cache": (None if self._prefix_cache is None else {
                "limit_bytes": self._prefix_cache.limit_bytes,
                "bytes": self._prefix_cache.bytes,
                "entries": len(self._prefix_cache),
                "lookups": self._prefix_cache.lookups,
                "hits": self._prefix_cache.hits,
            }),
            "trace_counts": dict(self.trace_counts),
            "memory": dict(self.memory_report),
        }

    def clear_prefix_cache(self, reason: str = "cleared") -> int:
        """Drop every cached prefix entry; returns the count dropped.
        MUST be called after a hot params reload — entries hold KV
        computed by the OLD weights, and serving them would silently
        change outputs (the one staleness hazard the exact-prompt key
        cannot see)."""
        if self._prefix_cache is None:
            return 0
        with self._dev_lock:
            return self._prefix_cache.clear(reason=reason)

    # -- warmup -------------------------------------------------------------
    def warmup(self, verbose: bool = False) -> dict:
        """Pre-compile the whole program set — one prefill per bucket +
        the single batched decode step — so steady-state generation
        never compiles. Runs on the caller thread under the device lock;
        skipped (returns ``{"skipped": ...}``) while slots are active
        (the programs are then warm by construction)."""
        t0 = time.perf_counter()
        before = dict(self.trace_counts)
        with self._dev_lock:
            if self._active.any() or self._flight:
                return {"skipped": "slots active (already warm)"}
            key = np.asarray(jax.random.PRNGKey(0))
            for tb in self.backend.buckets:
                # a tb-long prompt lands exactly in bucket tb (warmup
                # bypasses the window check — no decode follows)
                prompt = np.zeros((tb,), np.int32)
                _tok, _key, _tb, logits0 = self.backend.prefill(
                    0, prompt, 0.0, 0, 0.0, key)
                if self._prefix_cache is not None:
                    # compile the per-bucket capture/restore copy
                    # programs + the stored-logits sampler (entry
                    # discarded — warmup prompts must not spend budget)
                    entry = self.backend.prefix_capture(0, tb, logits0)
                    self.backend.prefix_restore(0, entry, 0.0, 0, 0.0,
                                                key)
                if verbose:
                    print(f"generation warmup: prefill bucket {tb}",
                          flush=True)
            if self._ahead:
                # no slot has a step left: the step, and the edit of a
                # row the host stopped, run on idle rows
                self.backend.stop(0)
                self.backend.collect(self.backend.launch())
            else:
                self.backend.decode(self._tokens, self._pos,
                                    np.zeros_like(self._active), self._temp,
                                    self._topk, self._topp, self._keys)
            if self.spec_decode_k > 1:
                # the proposal-lane programs: truncated draft rollout
                # (when that mode is on) + the batched verify
                if self.draft_mode == "truncated":
                    self.backend.draft(self._tokens, self._pos,
                                       np.zeros_like(self._active))
                K = self.spec_decode_k
                self.backend.verify(
                    np.zeros((self.n_slots, K), np.int32),
                    np.zeros((self.n_slots,), np.int32), self._pos,
                    np.zeros_like(self._active), self._temp, self._topk,
                    self._topp, self._keys)
        compiles = {k: self.trace_counts.get(k, 0) - before.get(k, 0)
                    for k in self.trace_counts}
        return {"buckets": list(self.backend.buckets),
                "compiles": compiles,
                "seconds": round(time.perf_counter() - t0, 3)}

    # -- worker -------------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [i for i in range(self.n_slots) if self._slots[i] is None]

    def _admit(self, block_s: float) -> None:
        for slot in self._free_slots():
            try:
                req = (self._queue.get(timeout=block_s) if block_s > 0
                       else self._queue.get_nowait())
            except queue.Empty:
                return
            block_s = 0.0
            if req.done():
                continue  # caller-side timeout while queued
            if req.expired():
                self.metrics.record_deadline()
                req.fail(RequestDeadlineExceeded(
                    "request deadline passed while queued"))
                continue
            # a claim's phases belong to the step they precede
            _trace.set_cause(self._dispatch_gen + 1)
            with _ADMIT:
                self._claim(slot, req)

    def _claim(self, slot: int, req: GenerationRequest) -> None:
        """Give ``slot`` to ``req``: first token (prefill, or a
        prefix-cache restore) under ``gen.prefill``, then the slot's
        bookkeeping."""
        from deeplearning4j_tpu.obs import flight as _flight

        t0 = time.monotonic()
        if req.trace is not None:
            req.trace.mark("slot_claimed", t0)
        waited = t0 - req.enqueued_at
        self.metrics.record_queue_wait(waited)
        waited_ns = int(waited * 1e9)
        _QUEUE_WAIT.record(time.time_ns() - waited_ns, waited_ns)
        with _PREFILL:
            key0 = np.asarray(jax.random.PRNGKey(req.seed),
                              np.uint32).reshape(2)
            hit = False
            pk = None
            if self._prefix_cache is not None:
                pk = PrefixCache.key_for(self.backend.kind, req.prompt)
                entry = self._prefix_cache.lookup(pk)
                if entry is not None:
                    try:
                        # chaos seam: a poisoned/stale entry fails typed
                        # here, BEFORE any device copy — the fallback is
                        # a real prefill with the untouched key0, so the
                        # request's output is bit-identical either way
                        chaos_hooks.fire("generate.prefix_cache",
                                         op="hit", slot=slot,
                                         prompt_len=int(req.prompt.size),
                                         **self.chaos_ctx)
                        tok0, key = self.backend.prefix_restore(
                            slot, entry, req.temperature, req.top_k,
                            req.top_p, key0)
                    except BaseException:  # noqa: BLE001 — poisoned entry
                        # dropped + counted; the miss path below re-runs
                        # the REAL prefill with the untouched key0, so
                        # the caller sees a bit-identical result, never
                        # the cache failure
                        self._prefix_cache.drop(pk, reason="poisoned")
                    else:
                        bucket = int(entry["tb"])
                        hit = True
                        self._prefix_cache.commit_hit(
                            pk, prompt_len=int(req.prompt.size),
                            slot=slot,
                            flops_avoided=2 * self._param_count
                            * int(req.prompt.size))
            if not hit:
                try:
                    # the device counts a slot's steps down where the
                    # loop launches ahead of the tokens
                    tok0, key, bucket, logits0 = self.backend.prefill(
                        slot, req.prompt, req.temperature, req.top_k,
                        req.top_p, key0,
                        *((req.max_new - 1,) if self._ahead else ()))
                except BaseException as e:  # keep the worker alive
                    self.metrics.record_error()
                    req.fail(e)
                    return
                if pk is not None:
                    self._prefix_cache.put(
                        pk,
                        self.backend.prefix_capture(slot, bucket,
                                                    logits0))
            dt = time.monotonic() - t0
        if not hit:
            self.metrics.record_prefill(dt)
        self.metrics.record_first_token()
        _flight.record("slot_claim", slot=slot,
                       prompt_len=int(req.prompt.size),
                       prompt_bucket=int(bucket),
                       max_new=req.max_new, prefix_hit=hit)
        self._slot_pk[slot] = pk
        self._replay[slot] = None
        if hit:
            comp = entry.get("completion")
            if comp:
                self._replay[slot] = list(comp)
        self._slots[slot] = req
        req.slot = slot
        self._active[slot] = True
        if self._ahead:
            self._left[slot] = req.max_new - 1
        self._tokens[slot] = tok0
        self._pos[slot] = req.prompt.size
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        self._keys[slot] = key
        if self._draft is not None:
            # teach the n-gram table the prompt + first token; seed
            # this slot's draft context with the last two tokens
            self._draft.learn_seq(req.prompt.tolist() + [int(tok0)])
        self._ctx[slot, 0] = int(req.prompt[-1])
        self._ctx[slot, 1] = int(tok0)
        if req.trace is not None:
            req.trace.mark("prefill_done")
            req.trace.note(slot=slot, prompt_len=int(req.prompt.size),
                           prompt_bucket=int(bucket), prefix_hit=hit)
        req.push_token(tok0)
        self._replay_advance(slot, int(tok0), 1)
        if len(req.tokens) >= req.max_new:
            self._finish_slot(slot, reason="done")
        self._step_ref = time.monotonic()

    def _replay_advance(self, slot: int, tok: int, n: int) -> None:
        """Invalidate the slot's completion replay at the first emitted
        token that diverges from the recorded completion (``n`` = the
        request's emitted-token count AFTER this token)."""
        comp = self._replay[slot]
        if comp is None:
            return
        if n > len(comp) or comp[n - 1] != tok:
            self._replay[slot] = None

    def _finish_slot(self, slot: int, reason: str,
                     error: Optional[BaseException] = None) -> None:
        from deeplearning4j_tpu.obs import flight as _flight

        req = self._slots[slot]
        self._slots[slot] = None
        self._active[slot] = False
        pk = self._slot_pk[slot]
        self._slot_pk[slot] = None
        self._replay[slot] = None
        if req is None:
            return
        req.slot = None
        if self._ahead:
            # steps in flight that still run the slot are thrown away
            late = sum(1 for _, step in self._flight
                       if step.ran[slot] and step.slots[slot] is req)
            if late:
                self.metrics.record_late_slot_steps(late)
            if self._left[slot] > 0:
                self._left[slot] = 0
                self.backend.stop(slot)
        if req.trace is not None:
            req.trace.mark("decode_done")
        n_tok = len(req.tokens)
        if n_tok:
            self._req_steps_ewma = (
                float(n_tok) if self._req_steps_ewma is None
                else 0.8 * self._req_steps_ewma + 0.2 * n_tok)
        if error is not None:
            if isinstance(error, RequestDeadlineExceeded):
                self.metrics.record_deadline()
            else:
                self.metrics.record_error()
            req.fail(error)
        else:
            if req.trace is not None:
                req.trace.mark("respond")
                req.trace.note(tokens=len(req.tokens))
            req.finish()
            self.metrics.record_finish(time.monotonic() - req.enqueued_at)
        if self.traces is not None and req.trace is not None:
            self.traces.add(req.trace)
        if (pk is not None and self._prefix_cache is not None
                and reason == "done" and error is None
                and req.temperature == 0.0):
            # greedy completion for this exact prompt — deterministic,
            # so it doubles as the replay draft for the NEXT hit
            self._prefix_cache.attach_completion(pk, req.tokens)
        if req.draft_proposed:
            _flight.record("draft_accept", slot=slot,
                           proposed=int(req.draft_proposed),
                           accepted=int(req.draft_accepted),
                           rate=round(req.draft_accepted
                                      / req.draft_proposed, 4))
        _flight.record("slot_free", slot=slot, reason=reason,
                       tokens=len(req.tokens))

    def _watchdog_loop(self) -> None:
        """Monitor thread: the decode dispatch runs on the worker
        thread, so a HUNG device call (driver wedge, deadlocked
        collective) freezes the worker where the except-clause recovery
        can never run. The watchdog observes the dispatch start stamp
        from outside, and past the limit fails the active requests
        typed and records the escalated stall — callers unblock, the
        blocked worker performs slab cleanup when (if) the dispatch
        finally returns. With a step kept in flight the stamp is the
        launch of the OLDEST uncollected step, so a hung collect trips
        it as a hung dispatch does."""
        from deeplearning4j_tpu.obs import flight as _flight

        while True:
            if self._shutdown and not self._worker.is_alive():
                return
            poll = min(max(self.watchdog_min_s / 4.0, 0.02), 1.0)
            time.sleep(poll)
            gen = self._dispatch_gen
            t0 = self._dispatch_t0
            if t0 is None or self._stall_tripped:
                continue
            limit = max(self.watchdog_min_s,
                        self.watchdog_mult * (self._step_ewma_s or 0.0))
            elapsed = time.monotonic() - t0
            if elapsed <= limit:
                continue
            if self._dispatch_gen != gen or self._dispatch_t0 != t0:
                continue  # that dispatch completed while we measured
            self._stall_gen = gen
            self._stall_tripped = True
            if self._dispatch_gen != gen or self._dispatch_t0 != t0:
                # completed in the set window: withdraw the trip before
                # failing anyone — these slots now belong to a healthy
                # (or no) dispatch
                self._stall_tripped = False
                continue
            n_active = int(self._active.sum())
            _flight.record("decode_stall", escalated=True,
                           wall_ms=round(elapsed * 1e3, 1),
                           limit_ms=round(limit * 1e3, 1),
                           active=n_active)
            err = DecodeStalledError(
                f"decode dispatch stuck for {elapsed:.1f}s (limit "
                f"{limit:.1f}s = max(watchdog_min_s, watchdog_mult × "
                "rolling step time)); active requests failed, worker "
                "thread still wedged in the dispatch")
            self.metrics.record_error()
            for slot in range(self.n_slots):
                req = self._slots[slot]
                if req is not None:
                    req.fail(err)

    def _build_drafts(self, K: int):
        """Assemble the fixed (S, K) proposal lane: column 0 = each
        slot's current token, columns 1..dlen[s] = draft proposals from
        the active draft source. Draft lengths are DATA (clamped per
        slot to the remaining token budget and the slab window — a
        column past either must never be accepted), shapes never
        change."""
        S = self.n_slots
        toks_k = np.zeros((S, K), np.int32)
        toks_k[:, 0] = self._tokens
        dlen = np.zeros((S,), np.int32)
        rooms: Dict[int, int] = {}
        for slot in range(S):
            if not self._active[slot]:
                continue
            req = self._slots[slot]
            if req is None:
                continue
            room = min(K - 1, req.max_new - len(req.tokens) - 1,
                       self.max_length - 1 - int(self._pos[slot]))
            if room > 0:
                rooms[slot] = room
        if not rooms:
            return toks_k, dlen
        if self.draft_mode == "truncated":
            drafts = self.backend.draft(self._tokens, self._pos,
                                        self._active)
            for slot, room in rooms.items():
                dlen[slot] = room
                toks_k[slot, 1:1 + room] = drafts[slot, :room]
        else:
            for slot, room in rooms.items():
                # replay first: a prefix hit carrying the prompt's
                # recorded greedy completion predicts perfectly as long
                # as the emitted tokens track it (invalidated on the
                # first divergence); n-gram table is the fallback
                ds: List[int] = []
                comp = self._replay[slot]
                if comp is not None:
                    n = len(self._slots[slot].tokens)
                    ds = comp[n:n + room]
                if not ds:
                    ds = self._draft.propose(self._ctx[slot, 0],
                                             self._ctx[slot, 1], room)
                if ds:
                    dlen[slot] = len(ds)
                    toks_k[slot, 1:1 + len(ds)] = ds
        return toks_k, dlen

    def _end_turn(self) -> None:
        """``gen.turn`` ends where the next backend call begins. Entered
        from two clock reads, not a ``with``: the turn crosses the
        device lock's release and re-take in ``_loop``."""
        if self._turn_t0 is not None:
            _TURN.record(self._turn_t0, time.time_ns() - self._turn_t0)
            self._turn_t0 = None

    def _drop_steps(self, reason: str, error: BaseException) -> None:
        """A step failed or hung: what was launched is lost (the donated
        caches went with it), so every active request fails typed and
        the backend starts over; freed slots and a live worker mean the
        next prefill rebuilds per-slot state."""
        self._dispatch_t0 = None
        self._turn_t0 = None
        self._stall_tripped = False
        self._flight.clear()
        for slot in range(self.n_slots):
            if self._slots[slot] is not None:
                self._finish_slot(slot, reason=reason, error=error)
        self.backend.reset()

    def _step(self) -> None:
        from deeplearning4j_tpu.models.transformer_lm import sampling_needs
        from deeplearning4j_tpu.obs import flight as _flight

        n_active = int(self._active.sum())
        K = self.spec_decode_k
        use_spec = False
        t0 = time.monotonic()
        self._dispatch_gen += 1
        gen = self._dispatch_gen
        self._dispatch_t0 = t0
        try:
            # chaos seam: error ≡ decode dispatch failure (typed
            # completion below); delay past the watchdog limit ≡ a hung
            # dispatch — the sleep happens with _dispatch_t0 stamped, so
            # the watchdog observes exactly what a wedged device call
            # looks like
            chaos_hooks.fire("generate.decode_dispatch",
                             active=n_active, **self.chaos_ctx)
            _trace.set_cause(gen)
            self._end_turn()
            if K > 1:
                # draft building may itself dispatch (truncated mode) —
                # keep it inside the watchdog's stamped window
                toks_k, dlen = self._build_drafts(K)
                use_spec = bool(dlen.any())
            if use_spec:
                s_all, e_all, last, keys = self.backend.verify(
                    toks_k, dlen, self._pos, self._active, self._temp,
                    self._topk, self._topp, self._keys)
            else:
                toks, keys = self.backend.decode(
                    self._tokens, self._pos, self._active, self._temp,
                    self._topk, self._topp, self._keys)
        except BaseException as e:  # keep the worker alive: a decode
            # failure (bad hot-swapped params, transient device error)
            # fails the ACTIVE requests typed instead of silently
            # killing the loop and hanging every present and future
            # caller
            _flight.record("decode_error", error=type(e).__name__,
                           active=n_active)
            self._drop_steps("decode_error", e)
            return
        self._dispatch_t0 = None
        dt = time.monotonic() - t0
        if self._stall_tripped:
            self._stall_tripped = False
            if self._stall_gen != gen:
                # a stale trip for an earlier dispatch that completed
                # inside the watchdog's set window — this dispatch is
                # healthy, keep its results
                pass
            else:
                # the watchdog already failed the active requests while
                # this dispatch hung; its result is stale — free the
                # slots and rebuild per-slot state like the
                # decode-failure path
                _flight.record("decode_stall_recovered",
                               wall_ms=round(dt * 1e3, 1), active=n_active)
                self._drop_steps("decode_stall", DecodeStalledError(
                    "decode dispatch exceeded the watchdog limit"))
                return
        with _EMIT:
            self._step_ewma_s = (dt if self._step_ewma_s is None
                                 else 0.8 * self._step_ewma_s + 0.2 * dt)
            # which branch the in-graph sampler took this step, told from
            # the host's copy of the policies it was handed (no fetch)
            drawn, filtered = sampling_needs(
                np.where(self._active, self._temp, 0.0), self._topk,
                self._topp, self.backend.vocab)
            if use_spec:
                emitted = int(e_all.sum())
                self.metrics.record_decode_step(dt, emitted, drawn,
                                                filtered)
                self.metrics.record_draft(int(dlen[self._active].sum()),
                                          emitted - n_active)
            else:
                self.metrics.record_decode_step(dt, n_active, drawn,
                                                filtered)
            if self.backend.attends:
                # before this step moved them: what its slots read
                self.metrics.record_attn_positions(
                    int(self._pos[self._active].sum()))
            if dt * 1e3 > self.stall_ms:
                _flight.record("decode_stall", wall_ms=round(dt * 1e3, 1),
                               active=n_active)
            # copy: np.asarray on a device array is a read-only view, and
            # the admit path writes per-slot lanes into these
            if use_spec:
                self._tokens = np.array(last, np.int32)
                self._keys = np.array(keys, np.uint32)
                # accepted counts are data: each slot advances by its own e
                # (masked to 0 on inactive rows)
                self._pos += e_all.astype(np.int32)
            else:
                self._tokens = np.array(toks, np.int32)
                self._keys = np.array(keys, np.uint32)
                self._pos[self._active] += 1
            now = time.monotonic()
            for slot in range(self.n_slots):
                if not self._active[slot]:
                    continue
                req = self._slots[slot]
                if use_spec:
                    m = int(e_all[slot])
                    req.draft_proposed += int(dlen[slot])
                    req.draft_accepted += m - 1
                    for j in range(m):
                        tok = int(s_all[slot, j])
                        self._learn(slot, tok)
                        req.push_token(tok)
                        self._replay_advance(slot, tok, len(req.tokens))
                else:
                    tok = int(toks[slot])
                    self._learn(slot, tok)
                    req.push_token(tok)
                    self._replay_advance(slot, tok, len(req.tokens))
                if len(req.tokens) >= req.max_new:
                    self._finish_slot(slot, reason="done")
                elif req.expired(now) or req.done():
                    # done() → the caller gave up (result timeout); either
                    # way the slot frees at token granularity (deadline
                    # expiry mid-verify frees it just like mid-decode — the
                    # already-accepted tokens were pushed above)
                    self._finish_slot(
                        slot, reason="deadline",
                        error=RequestDeadlineExceeded(
                            "request deadline passed mid-decode"))
        self._turn_t0 = time.time_ns()

    def _step_ahead(self) -> None:
        """One pass of the loop where the backend's step is a launch and
        a collect: launch the next step from the state on the device,
        THEN fetch the step before it and hand its tokens out, so the
        device runs step t+1 while the host streams step t. The first
        step after an idle stretch is launched and left in flight; with
        nothing left to launch the pass collects alone and the pipeline
        drains. What the host cannot know at a launch is what the tokens
        decide: a slot it stops at step t's emit (deadline, a caller
        that gave up) still runs in step t+1, whose token for it is
        dropped; a request's last step by ``max_new`` the device counts
        down itself."""
        from deeplearning4j_tpu.models.transformer_lm import sampling_needs
        from deeplearning4j_tpu.obs import flight as _flight

        be, flight = self.backend, self._flight
        n_active = int(self._active.sum())
        step = None
        try:
            ran = self._left > 0
            launching = bool(ran.any())
            if launching:
                self._dispatch_gen += 1
                t0 = time.monotonic()
                if not flight:
                    self._dispatch_t0 = t0
                # chaos seam, as in ``_step``: the watchdog's stamp is
                # set (this launch's, or the older step's in flight)
                chaos_hooks.fire("generate.decode_dispatch",
                                 active=n_active, **self.chaos_ctx)
                _trace.set_cause(self._dispatch_gen)
                self._end_turn()
                handle = be.launch()
                self.metrics.record_stack_passes(be.passes)
                if flight:
                    self.metrics.record_step_ahead()
                # what the step was handed, from the host's copy (no
                # fetch): the sampler's branch, the positions and states
                # its slots read, a slot whose stop comes too late among
                # them
                drawn, filtered = sampling_needs(
                    np.where(ran, self._temp, 0.0), self._topk, self._topp,
                    be.vocab)
                flight.append((handle, _Launched(
                    self._dispatch_gen, t0, ran, list(self._slots),
                    drawn, filtered,
                    int(self._pos[ran].sum()) if be.latent else 0,
                    int(self._pos[ran].sum()) if be.attends else 0,
                    int(np.minimum(self._pos[ran], be.index_topk).sum())
                    if be.index_topk else 0,
                    int(ran.sum()) if be.keeps_state else 0)))
                del handle
                self._left[ran] -= 1
                self._pos[ran] += 1
            # the first step after an idle stretch stays in flight: the
            # pipeline fills; with nothing launched it drains
            if len(flight) > 1 or not launching:
                handle, step = flight[0]
                _trace.set_cause(step.gen)
                self._end_turn()
                toks, counts = be.collect(handle)
                flight.popleft()
                # the step's array goes HERE, where no stream waits for
                # the interpreter: let go after the emit has woken them,
                # the release hands it to every one of them in the turn
                # (1.98 ms a turn among 33 streams on the chip; PERF.md,
                # PR 39)
                del handle
                self._dispatch_t0 = flight[0][1].t0 if flight else None
        except BaseException as e:  # keep the worker alive, as ``_step``
            _flight.record("decode_error", error=type(e).__name__,
                           active=n_active)
            self._drop_steps("decode_error", e)
            return
        now = time.monotonic()
        if self._stall_tripped:
            self._stall_tripped = False
            if self._stall_gen == self._dispatch_gen:
                # the watchdog failed the active requests while this
                # pass hung (no launch since): see ``_step``
                _flight.record("decode_stall_recovered", active=n_active)
                self._drop_steps("decode_stall", DecodeStalledError(
                    "decode step exceeded the watchdog limit"))
                return
        if step is None:
            return
        # what this step's tokens cost the loop: from the collect (or
        # claim) before it, or from its launch after an idle stretch
        dt = now - max(step.t0, self._step_ref)
        self._step_ref = now
        with _EMIT:
            self._step_ewma_s = (dt if self._step_ewma_s is None
                                 else 0.8 * self._step_ewma_s + 0.2 * dt)
            if dt * 1e3 > self.stall_ms:
                _flight.record("decode_stall", wall_ms=round(dt * 1e3, 1),
                               active=n_active)
            pushed = 0
            for slot in np.flatnonzero(step.ran):
                req = step.slots[slot]
                if self._slots[slot] is not req:
                    continue  # stopped since the launch: a late slot-step
                req.push_token(int(toks[slot]))
                pushed += 1
                if len(req.tokens) >= req.max_new:
                    self._finish_slot(slot, reason="done")
                elif req.expired(now) or req.done():
                    self._finish_slot(
                        slot, reason="deadline",
                        error=RequestDeadlineExceeded(
                            "request deadline passed mid-decode"))
            self.metrics.record_decode_step(dt, pushed, step.drawn,
                                            step.filtered)
            self.metrics.record_moe_step(*counts)
            self.metrics.record_latent_positions(step.latent_positions)
            self.metrics.record_attn_positions(step.attn_positions)
            if be.index_topk:
                self.metrics.record_selection(step.latent_positions,
                                              step.selected_positions)
            self.metrics.record_state_slots(step.state_slots)
        self._turn_t0 = time.time_ns()

    def _learn(self, slot: int, tok: int) -> None:
        """Advance the slot's 2-token draft context and teach the n-gram
        table (ngram mode) each emitted token."""
        if self._draft is not None:
            self._draft.learn(self._ctx[slot, 0], self._ctx[slot, 1], tok)
        self._ctx[slot, 0] = self._ctx[slot, 1]
        self._ctx[slot, 1] = tok

    def _loop(self) -> None:
        step = self._step_ahead if self._ahead else self._step
        while True:
            with self._dev_lock:
                self._admit(block_s=0.0)
                # a step in flight whose slots were all stopped is
                # still to be collected
                any_active = self._active.any() or bool(self._flight)
                if any_active:
                    step()
            self.metrics.set_active_slots(int(self._active.sum()))
            if not any_active:
                self._turn_t0 = None  # an idle loop is no turn
                if self._shutdown and self._queue.empty():
                    return
                # idle: wait for work without holding the device lock
                try:
                    with _IDLE_WAIT:
                        req = self._queue.get(timeout=0.05)
                except queue.Empty:
                    continue
                # put it back and admit under the lock (single admission
                # path keeps slot bookkeeping in one place)
                self._requeue_front(req)

    def _requeue_front(self, req: GenerationRequest) -> None:
        # queue.Queue has no putleft; a transient overflow past the
        # bound here is acceptable (the request was already admitted
        # once) — deque directly to preserve order
        with self._queue.mutex:
            self._queue.queue.appendleft(req)
            self._queue.not_empty.notify()

    # -- lifecycle ----------------------------------------------------------
    def shutdown(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop accepting work; ``drain=True`` finishes active and
        queued requests first, else they fail typed. Idempotent."""
        self._shutdown = True
        if not drain:
            self._fail_queued()
            with self._dev_lock:
                for slot in range(self.n_slots):
                    if self._slots[slot] is not None:
                        self._finish_slot(
                            slot, reason="shutdown",
                            error=ServerShutdownError(
                                "engine shut down mid-decode"))
        self._worker.join(timeout=timeout)
        self._fail_queued()
        if not self._worker.is_alive():
            # the slab goes with the worker that owned it
            with self._dev_lock:
                self.backend.release()

    def _fail_queued(self) -> None:
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            req.fail(ServerShutdownError(
                "engine shut down before serving request"))

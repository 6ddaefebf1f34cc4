"""Serving metrics, rebased onto the unified observability registry
(obs/metrics.MetricsRegistry) — counters, per-bucket hits, latency
quantiles from a fixed-size ring buffer.

The public surface is unchanged from the original serving-only
implementation (``record_*`` methods, attribute-style counter reads,
``snapshot()`` with the same JSON keys for the ``/metrics`` endpoint).
What changed underneath: every value now lives in a
:class:`MetricsRegistry`, so (1) ``prometheus_text()`` exposes the whole
family in Prometheus text format for scrapers, and (2) an engine handed
the process-wide default registry (``cli.py serve`` does this) shares
ONE metrics surface with training — the 1605.08695 train-and-serve
pairing applied to monitoring. By default each instance owns a private
registry, so independent engines (tests run dozens) never double-count.

The ring buffer bounds memory under sustained traffic (millions of
requests must not grow a list); quantiles are computed over the last
``ring_size`` completed requests, which is the window that matters for
a live /metrics endpoint. Everything here is plain Python under
fine-grained locks — the costs are nanoseconds against a device
dispatch.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from deeplearning4j_tpu.obs.metrics import Histogram, MetricsRegistry


class ServingMetrics:
    def __init__(self, ring_size: int = 2048,
                 registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        reg = self.registry
        self._requests = reg.counter(
            "serving_requests_total", "requests accepted into the queue")
        self._examples = reg.counter(
            "serving_examples_total", "rows across accepted requests")
        self._rejects = reg.counter(
            "serving_rejects_total", "ServerOverloadedError rejections")
        self._deadline = reg.counter(
            "serving_deadline_exceeded_total", "requests past their deadline")
        self._errors = reg.counter(
            "serving_errors_total", "dispatch failures propagated to callers")
        self._dispatches = reg.counter(
            "serving_dispatches_total", "device batches launched")
        self._reloads = reg.counter(
            "serving_reloads_total", "model hot reloads")
        self._latency = reg.histogram(
            "serving_latency_seconds", "request latency (ring-buffer window)",
            ring_size=ring_size)
        self.started_at = time.time()
        reg.gauge("serving_uptime_seconds", "seconds since metrics start",
                  fn=lambda: time.time() - self.started_at)
        # real-rows-per-dispatch ring: the observed mix an adaptive
        # bucket tuner learns from (bounded, like the latency ring)
        self._rows_window: deque = deque(maxlen=ring_size)
        self._rows_lock = threading.Lock()
        reg.gauge(
            "serving_latency_p99_ms",
            "p99 request latency over the ring window, milliseconds "
            "(0 before any request) — the latency-SLO alert input",
            fn=lambda: round((self.latency_quantile(0.99) or 0.0) * 1e3, 3))

    # -- recording ----------------------------------------------------------
    def record_request(self, rows: int) -> None:
        self._requests.inc()
        self._examples.inc(int(rows))

    def record_reject(self) -> None:
        self._rejects.inc()

    def record_deadline(self) -> None:
        self._deadline.inc()

    def record_error(self) -> None:
        self._errors.inc()

    def record_dispatch(self, bucket: int,
                        real_rows: Optional[int] = None) -> None:
        """One device batch launched at ``bucket`` padded rows;
        ``real_rows`` (when the caller knows it — the engine does)
        splits the bucket's rows into real vs padding so the per-bucket
        pad-waste ratio is a first-class metric instead of a number the
        dispatch path computed and threw away."""
        self._dispatches.inc()
        lbl = {"bucket": str(int(bucket))}
        self.registry.counter(
            "serving_bucket_hits_total", "dispatches per bucket size",
            labels=lbl).inc()
        if real_rows is not None:
            real = min(max(int(real_rows), 0), int(bucket))
            with self._rows_lock:
                self._rows_window.append(real)
            self.registry.counter(
                "serving_real_samples_total",
                "real (request) rows dispatched, per bucket",
                labels=lbl).inc(real)
            self.registry.counter(
                "serving_padded_samples_total",
                "padding rows dispatched (bucket quantization waste), "
                "per bucket", labels=lbl).inc(int(bucket) - real)

    def record_reload(self) -> None:
        self._reloads.inc()

    def record_latency(self, seconds: float) -> None:
        self._latency.observe(float(seconds))

    # -- attribute-style reads (original public surface) ---------------------
    @property
    def requests(self) -> int:
        return int(self._requests.value())

    @property
    def examples(self) -> int:
        return int(self._examples.value())

    @property
    def rejects(self) -> int:
        return int(self._rejects.value())

    @property
    def deadline_exceeded(self) -> int:
        return int(self._deadline.value())

    @property
    def errors(self) -> int:
        return int(self._errors.value())

    @property
    def dispatches(self) -> int:
        return int(self._dispatches.value())

    @property
    def reloads(self) -> int:
        return int(self._reloads.value())

    @property
    def bucket_hits(self) -> Dict[int, int]:
        fam = self.registry.family_values("serving_bucket_hits_total")
        return {int(label.split("=", 1)[1]): int(v)
                for label, v in fam.items()}

    def pad_waste(self) -> Dict[int, dict]:
        """bucket → {real, padded, waste_ratio}: cumulative rows split
        into request rows vs bucket-quantization padding. waste_ratio is
        padding over total dispatched rows — the fraction of device work
        burned on padding at that bucket (the signal that says WHICH
        bucket list to retune)."""
        real = self.registry.family_values("serving_real_samples_total")
        padded = self.registry.family_values("serving_padded_samples_total")
        out: Dict[int, dict] = {}
        for label in set(real) | set(padded):
            bucket = int(label.split("=", 1)[1])
            r = int(real.get(label, 0))
            p = int(padded.get(label, 0))
            out[bucket] = {
                "real": r, "padded": p,
                "waste_ratio": round(p / (r + p), 4) if (r + p) else 0.0,
            }
        return out

    def dispatch_rows_window(self) -> List[int]:
        """Real rows per dispatch over the last ``ring_size`` device
        batches — the observed mix :func:`~.buckets.propose_buckets`
        turns into a learned bucket list."""
        with self._rows_lock:
            return list(self._rows_window)

    # -- reading ------------------------------------------------------------
    def latency_quantile(self, q: float) -> Optional[float]:
        """q in [0, 1] over the ring window; None before any request."""
        return self._latency.quantile(q)

    def snapshot(self, queue_depth: Optional[int] = None) -> dict:
        """One JSON-ready dict for the /metrics endpoint (keys unchanged
        from the pre-registry implementation)."""
        window = self._latency.window()
        n = len(window)
        out = {
            "requests": self.requests,
            "examples": self.examples,
            "rejects": self.rejects,
            "deadline_exceeded": self.deadline_exceeded,
            "errors": self.errors,
            "dispatches": self.dispatches,
            "reloads": self.reloads,
            "bucket_hits": {str(k): v
                            for k, v in sorted(self.bucket_hits.items())},
            "pad_waste": {str(k): v
                          for k, v in sorted(self.pad_waste().items())},
            "uptime_s": round(time.time() - self.started_at, 3),
            "latency_window": n,
        }
        for name, q in (("p50", 0.50), ("p90", 0.90), ("p99", 0.99)):
            out[f"latency_{name}_ms"] = (
                None if n == 0
                else round(window[min(int(q * n), n - 1)] * 1e3, 3))
        if queue_depth is not None:
            out["queue_depth"] = int(queue_depth)
            self.registry.gauge("serving_queue_depth",
                                "pending requests in the batcher queue"
                                ).set(int(queue_depth))
        return out

    def prometheus_text(self, queue_depth: Optional[int] = None) -> str:
        """Prometheus text exposition of the backing registry."""
        if queue_depth is not None:
            self.registry.gauge("serving_queue_depth",
                                "pending requests in the batcher queue"
                                ).set(int(queue_depth))
        return self.registry.prometheus_text()


class GenerationMetrics:
    """Metrics surface for the continuous-batching generation engine
    (serving/generate.py) — same registry discipline as
    :class:`ServingMetrics`: every value lives in a
    :class:`MetricsRegistry` (private by default; hand it the
    process-wide default registry to share one Prometheus surface with
    training and /predict serving).

    The headline gauges the ISSUE names: ``generation_tokens_per_sec``
    (scrape-to-scrape rate of the token counter),
    ``generation_active_slots`` / ``generation_slots`` (occupancy), and
    the prefill/decode wall-time split (two monotonic seconds counters —
    the ratio is the split)."""

    def __init__(self, ring_size: int = 2048,
                 registry: Optional[MetricsRegistry] = None):
        from deeplearning4j_tpu.obs.cost import value_rate_fn

        self.registry = registry if registry is not None else MetricsRegistry()
        reg = self.registry
        self._requests = reg.counter(
            "generation_requests_total",
            "generation requests accepted into the queue")
        self._rejects = reg.counter(
            "generation_rejects_total",
            "generation requests rejected (queue full / invalid window)")
        self._deadline = reg.counter(
            "generation_deadline_total",
            "generation requests past their deadline (queued or mid-decode)")
        self._errors = reg.counter(
            "generation_errors_total",
            "generation failures propagated to callers")
        self._tokens = reg.counter(
            "generation_tokens_total", "tokens generated across requests")
        self._prefills = reg.counter(
            "generation_prefills_total", "prompt prefills (slot claims)")
        self._decode_steps = reg.counter(
            "generation_decode_steps_total",
            "batched decode dispatches (one per token for ALL slots)")
        # what the in-graph sampler had to do (it branches on the
        # batch's policies: transformer_lm.sampling_needs)
        self._sample_drawn = reg.counter(
            "generation_sample_drawn_steps_total",
            "decode steps in which some active slot drew (temperature "
            "> 0): the sampler scaled and drew, not argmax alone")
        self._sample_filtered = reg.counter(
            "generation_sample_filtered_steps_total",
            "decode steps in which some active slot that drew had a "
            "top-k or top-p that cuts: the sampler sorted and gathered "
            "over every slot's logits")
        self._prefill_s = reg.counter(
            "generation_prefill_seconds_total",
            "wall seconds spent in prompt prefill")
        self._decode_s = reg.counter(
            "generation_decode_seconds_total",
            "wall seconds spent in batched decode steps")
        self._latency = reg.histogram(
            "generation_request_seconds",
            "end-to-end request latency (ring-buffer window)",
            ring_size=ring_size)
        self._queue_wait = reg.histogram(
            "generation_queue_wait_seconds",
            "enqueue to slot claim, per claimed request (ring-buffer "
            "window)", ring_size=ring_size)
        self._slots = reg.gauge(
            "generation_slots", "decode slots in the engine slab")
        self._active = reg.gauge(
            "generation_active_slots", "slots currently decoding")
        reg.gauge("generation_tokens_per_sec",
                  "generated tokens/sec (scrape-to-scrape rate)",
                  fn=value_rate_fn(lambda: self._tokens.value()))
        # speculative decoding: proposed vs accepted draft tokens (the
        # acceptance ratio is the speedup knob's health signal)
        self._draft_proposed = reg.counter(
            "generation_draft_proposed_total",
            "draft tokens proposed to verify dispatches")
        self._draft_accepted = reg.counter(
            "generation_draft_accepted_total",
            "draft tokens accepted by verify dispatches")
        # shared-prefix KV cache: lookup/hit/evict counters + resident
        # bytes. The hit-rate gauge is created LAZILY once lookups cross
        # a floor (see record_prefix_lookup) so the `prefix_hit_rate_low`
        # alert stays inert on engines without prefix traffic — the
        # evaluator's no-data-is-no-verdict contract does the rest.
        self._prefix_lookups = reg.counter(
            "generation_prefix_lookups_total",
            "prefix-cache lookups (one per admitted request when enabled)")
        self._prefix_hits = reg.counter(
            "generation_prefix_hits_total",
            "prefix-cache hits (prefill replaced by a KV block copy)")
        self._prefix_evicts = reg.counter(
            "generation_prefix_evictions_total",
            "prefix-cache entries evicted (lru / poisoned / cleared)")
        self._prefix_bytes = reg.gauge(
            "generation_prefix_cache_bytes",
            "resident bytes held by the shared-prefix KV cache")
        self._flops_avoided = reg.counter(
            "generation_prefill_flops_avoided_total",
            "analytic prefill FLOPs avoided by prefix-cache hits")
        # expert layers (DecoderLM): what this holder's experts computed
        self._moe_pairs = reg.counter(
            "generation_moe_pairs_local_total",
            "(token, expert) pairs computed by the experts held here, "
            "all layers, over decode steps")
        self._moe_hit = reg.counter(
            "generation_moe_experts_hit_total",
            "held experts with at least one pair, summed over layers "
            "and decode steps")
        self._latent_positions = reg.counter(
            "generation_latent_positions_read_total",
            "cache positions the active slots had behind them, summed "
            "over decode steps, where a layer keeps a latent cache")
        self._attn_positions = reg.counter(
            "generation_attn_positions_read_total",
            "cache positions the active slots had behind them, summed "
            "over decode steps, where a layer keeps keys and values a "
            "position: what attention over the live tiles reads, against "
            "slots x slot length a step for attention over the whole slab")
        self._index_scored = reg.counter(
            "generation_index_positions_scored_total",
            "cache positions the active slots had behind them, summed "
            "over decode steps, where a layer's indexer scores them all "
            "to select the ones its attention reads")
        self._sparse_read = reg.counter(
            "generation_sparse_positions_read_total",
            "cache positions selected for the active slots (each slot's "
            "positions behind it, at most the indexer's top-k), summed "
            "over decode steps: what the attention over a selection reads")
        self._state_slots = reg.counter(
            "generation_state_slots_total",
            "slots whose recurrent state a decode step advanced, summed "
            "over decode steps, where a layer keeps one (state-space "
            "layers): each is read whole and written whole a layer")
        self._stack_passes = reg.counter(
            "generation_stack_passes_total",
            "passes over the layer stack that the launched decode steps "
            "ran: a step of a model whose stack is applied several times "
            "a token reads the layers' weights that many times (counted "
            "by the loop that launches steps; 0 on a lock-step backend)")
        self._cache_entries = reg.gauge(
            "generation_cache_entries_per_position",
            "(pass, layer) pairs that keep a cache entry for every "
            "position of a slot: passes x the layers that keep columns")
        self._steps_ahead = reg.counter(
            "generation_decode_steps_ahead_total",
            "decode steps launched while the step before them was not "
            "yet collected (a loop that keeps one step in flight; over "
            "generation_decode_steps_total near 1 when busy, 0 on a "
            "lock-step backend)")
        self._late_slot_steps = reg.counter(
            "generation_late_slot_steps_total",
            "slot-steps the device ran for a slot whose request the "
            "host had already ended (a stop seen one step late under a "
            "step in flight); their tokens are never streamed")
        self._param_casts = reg.counter(
            "generation_param_casts_total",
            "times the transformer backend made its compute-dtype copy "
            "of the weights: 1 after warm-up, +1 a swap of params_")
        self._hit_rate_gauge = None
        #: lookups before the hit-rate gauge materializes (and the
        #: prefix_hit_rate_low rule can fire)
        self.prefix_gauge_floor = 8

    # -- recording ----------------------------------------------------------
    def set_slots(self, n: int) -> None:
        self._slots.set(int(n))

    def set_active_slots(self, n: int) -> None:
        self._active.set(int(n))

    def set_cache_entries(self, n: int) -> None:
        self._cache_entries.set(int(n))

    def record_request(self) -> None:
        self._requests.inc()

    def record_reject(self) -> None:
        self._rejects.inc()

    def record_deadline(self) -> None:
        self._deadline.inc()

    def record_error(self) -> None:
        self._errors.inc()

    def record_prefill(self, seconds: float) -> None:
        self._prefills.inc()
        self._prefill_s.inc(float(seconds))

    def record_decode_step(self, seconds: float, tokens: int,
                           drawn: bool = False,
                           filtered: bool = False) -> None:
        self._decode_steps.inc()
        self._decode_s.inc(float(seconds))
        if tokens:
            self._tokens.inc(int(tokens))
        if drawn:
            self._sample_drawn.inc()
        if filtered:
            self._sample_filtered.inc()

    def record_moe_step(self, pairs_local: int, experts_hit: int) -> None:
        if pairs_local:
            self._moe_pairs.inc(int(pairs_local))
        if experts_hit:
            self._moe_hit.inc(int(experts_hit))

    def record_latent_positions(self, positions: int) -> None:
        if positions:
            self._latent_positions.inc(int(positions))

    def record_attn_positions(self, positions: int) -> None:
        if positions:
            self._attn_positions.inc(int(positions))

    def record_selection(self, scored: int, read: int) -> None:
        """A decode step of a model whose attention reads a selection:
        the positions its indexer scored and those it selected."""
        if scored:
            self._index_scored.inc(int(scored))
            self._sparse_read.inc(int(read))

    def record_state_slots(self, slots: int) -> None:
        if slots:
            self._state_slots.inc(int(slots))

    def record_stack_passes(self, passes: int) -> None:
        """A decode step was launched: the passes over the stack it runs."""
        self._stack_passes.inc(int(passes))

    def record_step_ahead(self) -> None:
        self._steps_ahead.inc()

    def record_late_slot_steps(self, n: int) -> None:
        self._late_slot_steps.inc(int(n))

    def record_param_cast(self) -> None:
        self._param_casts.inc()

    def record_first_token(self) -> None:
        self._tokens.inc()

    def record_finish(self, latency_seconds: float) -> None:
        self._latency.observe(float(latency_seconds))

    def record_queue_wait(self, seconds: float) -> None:
        self._queue_wait.observe(float(seconds))

    def record_draft(self, proposed: int, accepted: int) -> None:
        if proposed:
            self._draft_proposed.inc(int(proposed))
        if accepted:
            self._draft_accepted.inc(int(accepted))

    def _update_hit_rate(self) -> None:
        lookups = int(self._prefix_lookups.value())
        if lookups < self.prefix_gauge_floor:
            return
        if self._hit_rate_gauge is None:
            self._hit_rate_gauge = self.registry.gauge(
                "generation_prefix_hit_rate",
                "prefix-cache hits / lookups (created after the lookup "
                "floor so the low-hit-rate alert never fires on idle "
                "or prefix-less engines)")
        self._hit_rate_gauge.set(
            int(self._prefix_hits.value()) / max(lookups, 1))

    def record_prefix_lookup(self) -> None:
        self._prefix_lookups.inc()
        self._update_hit_rate()

    def record_prefix_hit(self, flops_avoided: int = 0) -> None:
        self._prefix_hits.inc()
        if flops_avoided:
            self._flops_avoided.inc(int(flops_avoided))
        self._update_hit_rate()

    def record_prefix_evict(self, n: int = 1) -> None:
        self._prefix_evicts.inc(int(n))

    def set_prefix_bytes(self, n: int) -> None:
        self._prefix_bytes.set(int(n))

    # -- reading ------------------------------------------------------------
    @property
    def tokens(self) -> int:
        return int(self._tokens.value())

    @property
    def requests(self) -> int:
        return int(self._requests.value())

    @property
    def rejects(self) -> int:
        return int(self._rejects.value())

    @property
    def deadline_exceeded(self) -> int:
        return int(self._deadline.value())

    def snapshot(self) -> dict:
        """JSON-ready dict merged into the server's /metrics body."""
        window = self._latency.window()
        n = len(window)
        prefill_s = self._prefill_s.value()
        decode_s = self._decode_s.value()
        out = {
            "requests": self.requests,
            "rejects": self.rejects,
            "deadline_exceeded": self.deadline_exceeded,
            "errors": int(self._errors.value()),
            "tokens": self.tokens,
            "prefills": int(self._prefills.value()),
            "decode_steps": int(self._decode_steps.value()),
            "sample_drawn_steps": int(self._sample_drawn.value()),
            "sample_filtered_steps": int(self._sample_filtered.value()),
            "prefill_seconds": round(prefill_s, 4),
            "decode_seconds": round(decode_s, 4),
            "prefill_fraction": (
                round(prefill_s / (prefill_s + decode_s), 4)
                if (prefill_s + decode_s) > 0 else None),
            "slots": int(self._slots.value()),
            "active_slots": int(self._active.value()),
            "draft_proposed": int(self._draft_proposed.value()),
            "draft_accepted": int(self._draft_accepted.value()),
            "draft_acceptance": (
                round(self._draft_accepted.value()
                      / self._draft_proposed.value(), 4)
                if self._draft_proposed.value() > 0 else None),
            "prefix_lookups": int(self._prefix_lookups.value()),
            "prefix_hits": int(self._prefix_hits.value()),
            "prefix_evictions": int(self._prefix_evicts.value()),
            "prefix_cache_bytes": int(self._prefix_bytes.value()),
            "prefill_flops_avoided": int(self._flops_avoided.value()),
            "moe_pairs_local": int(self._moe_pairs.value()),
            "moe_experts_hit": int(self._moe_hit.value()),
            "latent_positions_read": int(self._latent_positions.value()),
            "attn_positions_read": int(self._attn_positions.value()),
            "index_positions_scored": int(self._index_scored.value()),
            "sparse_positions_read": int(self._sparse_read.value()),
            "state_slots": int(self._state_slots.value()),
            "stack_passes": int(self._stack_passes.value()),
            "cache_entries_per_position": int(self._cache_entries.value()),
            "decode_steps_ahead": int(self._steps_ahead.value()),
            "late_slot_steps": int(self._late_slot_steps.value()),
            "param_casts": int(self._param_casts.value()),
            "latency_window": n,
        }
        for name, q in (("p50", 0.50), ("p90", 0.90), ("p99", 0.99)):
            out[f"latency_{name}_ms"] = (
                None if n == 0
                else round(window[min(int(q * n), n - 1)] * 1e3, 3))
        for name, q in (("p50", 0.50), ("p95", 0.95)):
            wait = self._queue_wait.quantile(q)
            out[f"queue_wait_{name}_ms"] = (
                None if wait is None else round(wait * 1e3, 3))
        return out


# re-exported for API continuity: callers that sized the ring via the
# original module keep working
__all__ = ["ServingMetrics", "GenerationMetrics", "Histogram"]

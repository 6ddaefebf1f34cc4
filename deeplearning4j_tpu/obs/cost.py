"""Hardware-efficiency profiling: static cost analysis → MFU gauges.

The fixed-shape whole-program discipline (arXiv 1810.09868) has a payoff
beyond zero steady-state recompiles: because every train/serve step is
ONE compiled XLA program of known shapes, its FLOPs, bytes accessed and
peak memory are **statically computable** from the compiled executable —
``jax.stages.Compiled.cost_analysis()`` / ``memory_analysis()`` — with
no instrumentation on the hot path. This module pulls those numbers off
the already-jitted steps, publishes them as gauges, and combines them
with the measured throughput (steps/sec from MetricsListener, or the
serving examples counter) into **model-FLOPs-utilization** and bytes/sec
gauges — the utilization baseline the fused-kernel roadmap item needs to
beat.

Caveats, documented rather than hidden:

- ``cost_analysis`` counts the FLOPs the *compiled program* executes
  (after fusion/CSE), which is the standard MFU numerator here; it is
  not the "6·N·D" analytic transformer count.
- On the CPU backend the "peak" is a nominal placeholder
  (:data:`DEFAULT_CPU_PEAK_FLOPS`, overridable via the
  ``DL4J_TPU_PEAK_FLOPS`` env var) — CPU MFU is only meaningful as a
  *relative* number across runs on the same box. TPU peaks come from a
  per-generation bf16 table; fp32-only programs overstate utilization
  headroom accordingly.
- Lowering an already-jitted function again (``fn.lower(...).compile()``)
  re-traces it (bumping ``jit_retraces_total`` — honest accounting: it
  IS a trace) and compiles outside the jit's C++ fast cache. Publish
  cost once per shape, not per step.

Also here: the on-demand ``jax.profiler`` capture behind the
``/debug/profile?ms=`` endpoints, guarded against concurrent captures
(the profiler is process-global state — two overlapping ``start_trace``
calls corrupt both traces).
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Callable, Dict, Optional

from deeplearning4j_tpu.obs.metrics import (
    Gauge,
    MetricsRegistry,
    default_registry,
)

#: nominal CPU "peak" (100 GFLOP/s) — a placeholder so CPU MFU is a
#: well-defined relative number; override with DL4J_TPU_PEAK_FLOPS
DEFAULT_CPU_PEAK_FLOPS = 1.0e11

#: per-chip bf16 peak FLOPs by TPU generation (device_kind substring,
#: checked in order — first match wins)
TPU_PEAK_FLOPS = (
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def hardware_peak_flops(devices=None) -> Dict[str, object]:
    """Total peak FLOPs across ``devices`` (default: all local devices)
    plus provenance: ``{"peak_flops", "per_device", "n_devices",
    "source"}``. ``DL4J_TPU_PEAK_FLOPS`` (per device) overrides any
    table/default."""
    import jax

    devices = list(devices if devices is not None else jax.local_devices())
    n = max(len(devices), 1)
    env = os.environ.get("DL4J_TPU_PEAK_FLOPS")
    if env:
        per = float(env)
        return {"peak_flops": per * n, "per_device": per, "n_devices": n,
                "source": "env:DL4J_TPU_PEAK_FLOPS"}
    kind = (getattr(devices[0], "device_kind", "") or "").lower()
    platform = getattr(devices[0], "platform", "cpu")
    if platform == "tpu":
        for sub, per in TPU_PEAK_FLOPS:
            if sub in kind:
                return {"peak_flops": per * n, "per_device": per,
                        "n_devices": n, "source": f"table:{sub} (bf16)"}
        raise ValueError(
            f"no peak FLOP/s known for TPU device_kind {kind!r}: add it "
            "to TPU_PEAK_FLOPS with its source (a utilization against an "
            "assumed peak is not a measurement)")
    per = DEFAULT_CPU_PEAK_FLOPS
    return {"peak_flops": per * n, "per_device": per, "n_devices": n,
            "source": f"nominal:{platform} (placeholder — relative MFU "
                      "only; set DL4J_TPU_PEAK_FLOPS)"}


# --------------------------------------------------------------------------
# compiled-program analysis
# --------------------------------------------------------------------------
def _shape_structs(tree):
    """Pytree of arrays → pytree of ShapeDtypeStructs (lowering needs
    shapes/dtypes only; never materialize copies of the params)."""
    import jax
    import jax.numpy as jnp

    def struct(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        a = jnp.asarray(x)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree_util.tree_map(struct, tree)


def _normalize_cost(raw) -> Dict[str, float]:
    if not isinstance(raw, dict):
        return {}
    out = {}
    if "flops" in raw:
        out["flops"] = float(raw["flops"])
    if "bytes accessed" in raw:
        out["bytes_accessed"] = float(raw["bytes accessed"])
    if "transcendentals" in raw:
        out["transcendentals"] = float(raw["transcendentals"])
    return out


def compiled_analysis(jitted_fn, *args, **kwargs) -> Dict[str, object]:
    """Lower+compile ``jitted_fn`` for the given example args (arrays or
    ShapeDtypeStructs; pytrees fine) and return its static cost sheet:
    ``flops``, ``bytes_accessed``, ``peak_memory_bytes`` (argument +
    output + temp + generated code), and the raw memory breakdown.
    Backends that cannot answer a question simply omit the key — callers
    and the gauges treat "absent" as "not supported here", never as 0."""
    structs = [_shape_structs(a) if a is not None else None for a in args]
    out: Dict[str, object] = {}
    try:
        compiled = jitted_fn.lower(*structs, **kwargs).compile()
    except Exception as e:  # non-jitted callable / backend refusal
        return {"error": f"{type(e).__name__}: {e}"}
    try:
        out.update(_normalize_cost(compiled.cost_analysis()))
    except Exception as e:  # noqa: BLE001 — absent analysis keys are reported, never fatal
        out["cost_error"] = f"{type(e).__name__}: {e}"
    try:
        mem = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001 — absent analysis keys are reported, never fatal
        mem = None
        out["memory_error"] = f"{type(e).__name__}: {e}"
    if mem is not None:
        breakdown = {}
        for key in ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "alias_size_in_bytes",
                    "generated_code_size_in_bytes"):
            v = getattr(mem, key, None)
            if v is not None:
                breakdown[key] = int(v)
        if breakdown:
            out["memory"] = breakdown
            out["peak_memory_bytes"] = (
                breakdown.get("argument_size_in_bytes", 0)
                + breakdown.get("output_size_in_bytes", 0)
                + breakdown.get("temp_size_in_bytes", 0)
                + breakdown.get("generated_code_size_in_bytes", 0)
                - breakdown.get("alias_size_in_bytes", 0))
    return out


# --------------------------------------------------------------------------
# gauges
# --------------------------------------------------------------------------
def publish_step_cost(registry: MetricsRegistry, step: str,
                      analysis: Dict[str, object],
                      labels: Optional[Dict[str, str]] = None) -> None:
    """Static per-dispatch gauges: ``step_flops`` / ``step_bytes_accessed``
    / ``step_peak_memory_bytes``, labeled ``{step=...}`` (+ caller
    labels)."""
    lbl = {"step": step}
    lbl.update(labels or {})
    if "flops" in analysis:
        registry.gauge("step_flops",
                       "XLA-reported FLOPs of one compiled dispatch",
                       labels=lbl).set(float(analysis["flops"]))
    if "bytes_accessed" in analysis:
        registry.gauge("step_bytes_accessed",
                       "XLA-reported bytes accessed by one dispatch",
                       labels=lbl).set(float(analysis["bytes_accessed"]))
    if "peak_memory_bytes" in analysis:
        registry.gauge("step_peak_memory_bytes",
                       "argument+output+temp+code bytes of the compiled "
                       "program", labels=lbl).set(
                           float(analysis["peak_memory_bytes"]))


#: evaluations closer together than this reuse the previous rate — one
#: Prometheus scrape renders several gauges back-to-back off ONE shared
#: rate closure (MFU + bytes/sec), and the second evaluation must not
#: consume a microsecond delta and read ~0
_RATE_MIN_WINDOW_S = 0.25


def value_rate_fn(value_fn: Callable[[], float]) -> Callable[[], float]:
    """Scrape-to-scrape rate of a monotonic value: each call returns
    ``delta(value)/delta(time)`` since the previous WINDOW (0 on the
    first scrape or after a reset/stall). Calls within
    ``_RATE_MIN_WINDOW_S`` of the last window boundary return the same
    rate — gauges sharing one closure all see one consistent number per
    scrape."""
    state = {"t": None, "v": 0.0, "rate": 0.0}
    lock = threading.Lock()

    def rate() -> float:
        now = time.monotonic()
        with lock:
            t0 = state["t"]
            if t0 is not None and now - t0 < _RATE_MIN_WINDOW_S:
                return state["rate"]
            v = float(value_fn())
            v0 = state["v"]
            state["t"], state["v"] = now, v
            if t0 is None or now <= t0 or v < v0:
                state["rate"] = 0.0
            else:
                state["rate"] = (v - v0) / (now - t0)
            return state["rate"]

    return rate


def counter_rate_fn(registry: MetricsRegistry, name: str,
                    labels: Optional[Dict[str, str]] = None
                    ) -> Callable[[], float]:
    """Scrape-to-scrape rate of one counter. The registry stays the
    single source of truth — no side channel between recorder and
    gauge."""

    def value() -> float:
        m = registry.get(name, labels)
        return float(m.value()) if m is not None else 0.0

    return value_rate_fn(value)


def family_rate_fn(registry: MetricsRegistry, name: str
                   ) -> Callable[[], float]:
    """Scrape-to-scrape rate of a LABELED counter family, summed over
    all label sets (e.g. per-bucket ``serving_real_samples_total`` → the
    engine's total real rows/sec). Uses ``registry.family_sum`` — NOT
    ``snapshot()``, which evaluates every callback gauge and would
    recurse when this rate feeds one of those gauges."""
    return value_rate_fn(lambda: registry.family_sum(name))


def publish_utilization(registry: MetricsRegistry, step: str,
                        flops_per_unit: float, bytes_per_unit: float,
                        units_per_sec: Callable[[], float],
                        peak: Optional[Dict[str, object]] = None
                        ) -> Gauge:
    """Register the MFU gauge ``model_flops_utilization{step=}`` (0..1)
    and ``step_bytes_per_sec{step=}``, both computed at scrape time from
    a throughput callback: utilization = flops_per_unit × units/sec ÷
    peak. Returns the MFU gauge."""
    pk = peak or hardware_peak_flops()
    peak_flops = float(pk["peak_flops"])
    registry.gauge("hardware_peak_flops",
                   f"assumed peak FLOPs ({pk['source']})",
                   labels={"step": step}).set(peak_flops)
    registry.gauge(
        "step_bytes_per_sec",
        "achieved memory traffic: bytes_accessed × measured rate",
        labels={"step": step},
        fn=lambda: float(bytes_per_unit) * max(units_per_sec(), 0.0))
    return registry.gauge(
        "model_flops_utilization",
        "measured FLOPs/sec over assumed hardware peak (see "
        "hardware_peak_flops source label for the peak's provenance)",
        labels={"step": step},
        fn=lambda: (float(flops_per_unit) * max(units_per_sec(), 0.0)
                    / peak_flops))


# --------------------------------------------------------------------------
# train-step integration
# --------------------------------------------------------------------------
def train_step_analysis(model, ds, steps_per_call: Optional[int] = None
                        ) -> Dict[str, object]:
    """Static cost of the model's OWN jitted train step (the exact
    callable the fit loop dispatches — same jit-cache keys, telemetry
    conf and fault guard as ``fit`` would use) for a batch shaped like
    ``ds``. ``steps_per_call`` > 1 analyzes the bundled lax.scan step;
    ``flops_per_step`` is then the bundle total over K."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.obs import telemetry as _telemetry
    from deeplearning4j_tpu.train import pipeline as _pipeline

    if not hasattr(model, "_make_train_step"):
        return {"error": f"{type(model).__name__} has no functional train "
                         "step to analyze"}
    k = int(steps_per_call
            or getattr(model.conf.global_conf, "steps_per_call", 1) or 1)
    tconf = _telemetry.resolve(model)
    tkey = None if tconf is None else str(sorted(tconf.to_dict().items()))
    if k > 1:
        step = model._get_jit(
            ("train_bundle_telem", tkey) if tconf else "train_bundle",
            lambda: _pipeline.make_bundled_step(model, telemetry=tconf))
    else:
        step = model._get_jit(
            ("train_telem", tkey) if tconf else "train",
            lambda: model._make_train_step(telemetry=tconf))

    def batched(x, stack):
        if x is None:
            return None
        a = jnp.asarray(x)
        return jax.ShapeDtypeStruct((k,) + a.shape, a.dtype) if stack \
            else a

    stack = k > 1
    f = batched(ds.features, stack)
    l = batched(ds.labels, stack)
    fm = batched(getattr(ds, "features_mask", None), stack)
    lm = batched(getattr(ds, "labels_mask", None), stack)
    rng = jax.random.PRNGKey(0)
    rngs = jnp.stack([rng] * k) if stack else rng
    it = jnp.asarray(0, jnp.int32)
    ep = jnp.asarray(0, jnp.int32)
    policy = model._active_fault_policy()
    if policy is not None:
        fstate = model._ensure_fault_state(policy)
        args = (model.params_, model.opt_state_, model.state_, fstate,
                f, l, fm, lm, rngs, it, ep)
    else:
        args = (model.params_, model.opt_state_, model.state_,
                f, l, fm, lm, rngs, it, ep)
    out = compiled_analysis(step, *args)
    out["steps_per_call"] = k
    if "flops" in out:
        out["flops_per_step"] = float(out["flops"]) / k
    if "bytes_accessed" in out:
        out["bytes_per_step"] = float(out["bytes_accessed"]) / k
    return out


def publish_train_cost(model, ds, steps_per_call: Optional[int] = None,
                       registry: Optional[MetricsRegistry] = None
                       ) -> Dict[str, object]:
    """Analyze the train step (:func:`train_step_analysis`) and publish
    the full gauge set: static ``step_*{step="train"}`` plus the MFU and
    bytes/sec gauges driven by the ``train_steps_per_sec`` gauge the
    MetricsListener maintains in the same registry. Returns the
    analysis."""
    reg = registry if registry is not None else default_registry()
    out = train_step_analysis(model, ds, steps_per_call)
    if "error" in out:
        return out
    publish_step_cost(reg, "train", out,
                      labels={"k": str(out["steps_per_call"])})

    def steps_per_sec() -> float:
        g = reg.get("train_steps_per_sec")
        return float(g.value()) if g is not None else 0.0

    publish_utilization(reg, "train",
                        flops_per_unit=out.get("flops_per_step", 0.0),
                        bytes_per_unit=out.get("bytes_per_step", 0.0),
                        units_per_sec=steps_per_sec)
    from deeplearning4j_tpu.obs import flight as _flight

    _flight.record("cost_published", step="train",
                   k=out["steps_per_call"],
                   flops_per_step=out.get("flops_per_step"))
    return out


# --------------------------------------------------------------------------
# on-demand profiler capture (/debug/profile)
# --------------------------------------------------------------------------
class ProfilerBusyError(RuntimeError):
    """A capture (or a ProfilerListener window) is already running —
    the jax profiler is process-global, concurrent traces corrupt each
    other. HTTP maps this to 409."""


_capture_lock = threading.Lock()
MAX_CAPTURE_MS = 60_000.0


def profiler_capture(ms: float, log_dir: Optional[str] = None
                     ) -> Dict[str, object]:
    """Capture a ``jax.profiler`` trace for ``ms`` milliseconds into
    ``log_dir`` (default: a fresh temp dir); returns ``{log_dir, ms}``.
    Exactly one capture at a time process-wide (non-blocking — a second
    caller gets :class:`ProfilerBusyError` immediately, the contract a
    debug endpoint needs under retry storms)."""
    import jax

    ms = min(max(float(ms), 1.0), MAX_CAPTURE_MS)
    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusyError("a profiler capture is already running")
    try:
        log_dir = log_dir or tempfile.mkdtemp(prefix="dl4j_tpu_profile_")
        try:
            jax.profiler.start_trace(log_dir)
        except Exception as e:
            # ProfilerListener (or an external tool) holds the global
            # trace — same contract as a concurrent capture
            raise ProfilerBusyError(
                f"jax profiler unavailable: {e}") from e
        try:
            time.sleep(ms / 1e3)
        finally:
            jax.profiler.stop_trace()
        from deeplearning4j_tpu.obs import flight as _flight

        _flight.record("profiler_capture", ms=ms, log_dir=log_dir)
        return {"log_dir": log_dir, "ms": ms}
    finally:
        _capture_lock.release()

"""Unified observability layer: metrics registry, in-graph training
telemetry, trace spans and the jit retrace monitor.

TensorFlow's production experience (arXiv 1605.08695) pairs training and
serving under ONE monitoring surface; the fixed-shape whole-program
rationale (arXiv 1810.09868) dictates HOW telemetry is computed here:
inside the jitted program, host-fetched at most once per dispatch, so
turning monitoring on never re-introduces the per-step host syncs the
pipelined training loop (train/pipeline.py) removed.

- :mod:`obs.metrics` — thread-safe :class:`MetricsRegistry` (counters,
  gauges, bounded histograms) with Prometheus text exposition + JSON
  snapshot; serving and training publish into the same registry type
  (and, via the CLI, the same default registry).
- :mod:`obs.telemetry` — opt-in :class:`TelemetryConf`: per-step
  gradient/parameter global norms, update:param ratio and loss scale
  computed INSIDE the jitted train step, stacked by the ``lax.scan``
  bundle and delivered to listeners via ``telemetry_done``.
- :mod:`obs.trace` — host phases at the phase boundaries of the hot
  paths (one call site: a ``jax.profiler`` annotation, a bounded ring on
  the profiler's clock and per-name counters), the bare span
  annotations, plus a registry-backed per-function jit cache-miss
  counter so steady-state recompiles surface as a metric instead of a
  mystery slowdown.
- :mod:`obs.exporter` — stdlib HTTP endpoint exposing a registry
  (content-negotiated Prometheus text / JSON) during training, plus the
  ``/debug/flight`` and ``/debug/profile`` forensic endpoints.
- :mod:`obs.flight` — the forensic half: a bounded ring of structured
  events (steps, NaN-skips, loss-scale changes, checkpoints, reloads,
  rejections, retraces) dumped atomically to JSON on divergence, fit
  exceptions, SIGTERM, a wall-clock cadence, or on demand.
- :mod:`obs.cost` — hardware-efficiency profiling: static
  FLOPs/bytes/peak-memory off the compiled steps
  (``Compiled.cost_analysis``), model-FLOPs-utilization and bytes/sec
  gauges against the measured throughput, and the guarded on-demand
  ``jax.profiler`` capture.
- :mod:`obs.alerts` / :mod:`obs.slo` — the detection half: declarative
  alert rules (threshold / rate / absence / multi-window SLO burn
  rate) with a pending→firing→resolved hysteresis machine, evaluated
  against the registry + flight ring on injected-clock ticks; the
  default rule pack codifies the stack's known failure smells, the
  canary gate runs on the same engine, and ``/alerts`` + the
  verdict-enriched ``/healthz`` expose the firing set.
"""

from deeplearning4j_tpu.obs.alerts import (  # noqa: F401
    AlertEvaluator,
    AlertRule,
    HealthVerdict,
    SLOObjective,
)
from deeplearning4j_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsListener,
    MetricsRegistry,
    default_registry,
)
from deeplearning4j_tpu.obs.flight import (  # noqa: F401
    FlightRecorder,
    FlightRecorderListener,
    default_flight_recorder,
    install_signal_dump,
)
from deeplearning4j_tpu.obs.telemetry import (  # noqa: F401
    BundleTelemetry,
    TelemetryConf,
)
from deeplearning4j_tpu.obs.trace import (  # noqa: F401
    RetraceMonitor,
    count_retraces,
    observe,
    phase,
    phases,
    retrace_counts,
    span,
    step_span,
)

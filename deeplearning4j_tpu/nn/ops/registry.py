"""Kernel registry: the single availability decision point for every
in-tree Pallas kernel (flash attention, fused conv, fused LSTM cell,
fused ZeRO-1 update, int8 serving matmul, latent decode core, state-space
decode step, cache column write, decode attention over the live tiles,
the expert layers' grouped products at few rows a group, latent attention
over a selection at a decode step).

Before this module each kernel carried its own ad-hoc probe cache
(``attention._FLASH_PROBE_CACHE``, ``fused_conv._PROBE_CACHE``) and its
own ``probe_with_retry`` call site. The registry unifies the contract:

- **probe once per process per (kernel, instantiation key)** — Mosaic
  lowering varies with shapes/dtypes, so availability is keyed, not
  global; a resolved key is a dict hit forever after;
- every resolution is **observable**: a failed (or skipped) probe emits
  ONE ``kernel_fallback`` flight event naming the kernel, key and
  reason, and a ``kernel_enabled{name=}`` gauge on the default metrics
  registry tracks whether any instantiation of that kernel is live —
  "why is this hot path on the slow route" is answerable from the
  black box and the scrape surface, not just process logs;
- one **mode switch per kernel** via environment:
  ``DL4J_TPU_<KERNEL>`` = ``0`` (off), ``1``/unset (auto: probe on the
  TPU backend, fall back elsewhere), or ``interpret`` (force the Pallas
  interpreter — the CPU testing/bench mode; slow, but executes the real
  kernel math). ``interpret`` is honored by the kernels that resolve
  through :meth:`KernelRegistry.resolve` (fused_lstm, fused_zero1,
  int8_matmul, latent_decode_core, ssm_decode_step, kv_column_write,
  decode_attention, grouped_experts, sparse_latent_decode);
  flash_attention and fused_conv predate it and support
  ``0``/``1`` only (their layers call the compiled kernels directly —
  tests drive their ``interpret=`` arguments explicitly).

The probes themselves stay in the kernel modules (each knows its own
reference oracle and tolerance); the registry owns caching, retry
(``kernel_compat.probe_with_retry`` — a transiently crashed compile
service gets one retry, deterministic rejects cost one attempt) and
reporting. A fallback is reported, never hidden: on the chip
``chip_smoke.py`` fails when a kernel its phases asked for resolved to
the reference path.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Callable, Dict, Optional, Tuple

from deeplearning4j_tpu.nn.ops.kernel_compat import probe_with_retry

log = logging.getLogger(__name__)

#: kernel name → environment kill/mode switch
ENV_FLAGS = {
    "flash_attention": "DL4J_TPU_FLASH_ATTENTION",
    "fused_conv": "DL4J_TPU_FUSED_CONV",
    "fused_lstm": "DL4J_TPU_FUSED_LSTM",
    "fused_zero1": "DL4J_TPU_FUSED_ZERO1",
    "int8_matmul": "DL4J_TPU_INT8_MATMUL",
    "latent_decode_core": "DL4J_TPU_LATENT_DECODE_CORE",
    "ssm_decode_step": "DL4J_TPU_SSM_DECODE_STEP",
    "kv_column_write": "DL4J_TPU_KV_COLUMN_WRITE",
    "decode_attention": "DL4J_TPU_DECODE_ATTENTION",
    "grouped_experts": "DL4J_TPU_GROUPED_EXPERTS",
    "sparse_latent_decode": "DL4J_TPU_SPARSE_LATENT_DECODE",
}


class KernelRegistry:
    """Probe-once-per-process kernel availability cache + reporter."""

    def __init__(self):
        self._lock = threading.RLock()
        #: (name, key) -> (ok: bool, reason: str)
        self._resolved: Dict[Tuple[str, tuple], Tuple[bool, str]] = {}
        #: (name, key) -> Event while a probe for that key is running —
        #: probes compile for SECONDS and must not hold the registry
        #: lock (concurrent engine warmups resolving other kernels would
        #: re-serialize); same-key racers wait on the event instead of
        #: probing twice
        self._inflight: Dict[Tuple[str, tuple], threading.Event] = {}

    # -- mode ----------------------------------------------------------------
    def mode(self, name: str) -> str:
        """'off' | 'auto' | 'interpret' for ``name`` (see module doc)."""
        raw = os.environ.get(ENV_FLAGS.get(name, ""), "1").strip().lower()
        if raw in ("0", "off", "false"):
            return "off"
        if raw == "interpret":
            return "interpret"
        return "auto"

    # -- resolution ----------------------------------------------------------
    def enabled(self, name: str, key: tuple) -> Optional[bool]:
        """Cached verdict for (name, key); None when never probed."""
        with self._lock:
            got = self._resolved.get((name, tuple(key)))
        return None if got is None else got[0]

    def route(self, name: str, key: tuple) -> Optional[bool]:
        """The mode/backend gate that runs before any probe: None when
        the kernel must not be used (kill switch, or auto mode off the
        TPU backend — recorded as a fallback), else the ``interpret``
        flag to build the probe/impl with."""
        import jax

        mode = self.mode(name)
        if mode == "off":
            self.disable(name, key,
                         f"disabled via {ENV_FLAGS.get(name)}=0")
            return None
        if mode == "interpret":
            return True
        try:
            backend = jax.default_backend()
        except Exception:  # noqa: BLE001 — backend query failed: treat as non-TPU
            backend = "unknown"
        if backend != "tpu":
            self.disable(name, key,
                         f"non-TPU backend ({backend}); reference path "
                         "serves this instantiation")
            return None
        return False

    def resolve(self, name: str, key: tuple,
                probe_factory: Callable[[bool], Callable[[], None]]
                ) -> Optional[bool]:
        """The whole resolution protocol in one place: cached verdict →
        mode/backend gate → probe. Returns the ``interpret`` flag when
        the kernel may be used, None for the reference path.
        ``probe_factory(interpret)`` builds the zero-arg probe."""
        key = tuple(key)
        cached = self.enabled(name, key)
        if cached is False:
            return None
        interpret = self.route(name, key)
        if interpret is None:
            return None
        if cached is None and not self.probe(name, key,
                                             probe_factory(interpret)):
            return None
        return interpret

    def probe(self, name: str, key: tuple, probe_fn: Callable[[], None]
              ) -> bool:
        """Resolve (name, key): run ``probe_fn`` (raises on failure)
        through the shared transient-crash retry, cache the verdict, and
        report it (flight event on fallback, gauge either way). The
        probe itself runs OUTSIDE the registry lock; concurrent callers
        of the same key wait for the one in-flight probe. Safe to call
        from inside an ambient trace as long as ``probe_fn`` uses AOT
        lower+compile (the discipline every in-tree probe follows)."""
        key = tuple(key)
        while True:
            with self._lock:
                got = self._resolved.get((name, key))
                if got is not None:
                    return got[0]
                ev = self._inflight.get((name, key))
                if ev is None:
                    ev = threading.Event()
                    self._inflight[(name, key)] = ev
                    break
            ev.wait()  # another thread is probing this exact key

        failure = {}

        def on_fail(e, will_retry):
            failure["error"] = f"{type(e).__name__}: " \
                f"{str(e).splitlines()[0] if str(e) else ''}"
            log.info(
                "kernel %s unavailable for %s (%s)%s", name, key,
                failure["error"],
                " — transient remote-compile crash, retrying once"
                if will_retry else "")

        def probing():
            # chaos seam: mode 'transient_compile' carries the compile-
            # service-crash signature, so the drill exercises the REAL
            # probe_with_retry transient-retry path (one crash, then
            # the genuine probe runs)
            from deeplearning4j_tpu.chaos import hooks as _chaos

            _chaos.fire("kernel.probe", kernel=name)
            probe_fn()

        ok = False
        try:
            ok = probe_with_retry(probing, on_fail)
        finally:
            with self._lock:
                self._record(name, key, ok,
                             "probe ok" if ok
                             else failure.get("error", "probe failed"))
                self._inflight.pop((name, key), None)
            ev.set()
        return ok

    def disable(self, name: str, key: tuple, reason: str) -> None:
        """Cache (name, key) as unavailable WITHOUT probing — the
        backend/mode/shape gate said no before a compile was attempted
        (e.g. non-TPU backend in auto mode). Reported exactly like a
        probe failure so the fallback is visible."""
        key = tuple(key)
        with self._lock:
            if (name, key) in self._resolved:
                return
            self._record(name, key, False, reason)

    def _record(self, name: str, key: tuple, ok: bool, reason: str) -> None:
        # caller holds the lock
        self._resolved[(name, key)] = (ok, reason)
        try:
            from deeplearning4j_tpu.obs import flight as _flight
            from deeplearning4j_tpu.obs.metrics import default_registry

            if not ok:
                _flight.record("kernel_fallback", kernel=name,
                               key=repr(key), reason=reason)
            any_on = any(v for (n, _), (v, _r) in self._resolved.items()
                         if n == name)
            default_registry().gauge(
                "kernel_enabled",
                "1 when any instantiation of the named Pallas kernel "
                "probed OK this process, 0 when every resolution fell "
                "back to the reference path",
                labels={"name": name}).set(1.0 if any_on else 0.0)
        except Exception:  # reporting must never break the compute path
            log.debug("kernel registry reporting failed", exc_info=True)

    # -- introspection / tests ----------------------------------------------
    def snapshot(self) -> Dict[str, Dict[str, dict]]:
        """{kernel: {key-repr: {enabled, reason}}} — debugging surface."""
        with self._lock:
            out: Dict[str, Dict[str, dict]] = {}
            for (name, key), (ok, reason) in self._resolved.items():
                out.setdefault(name, {})[repr(key)] = {
                    "enabled": ok, "reason": reason}
            return out

    def reset(self, name: Optional[str] = None) -> None:
        """Drop cached verdicts (all, or one kernel's) — test hook for
        exercising probe/fallback paths repeatedly in one process."""
        with self._lock:
            if name is None:
                self._resolved.clear()
            else:
                for k in [k for k in self._resolved if k[0] == name]:
                    del self._resolved[k]


_default: Optional[KernelRegistry] = None
_default_lock = threading.Lock()


def default_kernel_registry() -> KernelRegistry:
    global _default
    with _default_lock:
        if _default is None:
            _default = KernelRegistry()
        return _default


def kernel_route(name: str, key: tuple) -> Optional[bool]:
    """:meth:`KernelRegistry.route` on the default registry."""
    return default_kernel_registry().route(name, key)

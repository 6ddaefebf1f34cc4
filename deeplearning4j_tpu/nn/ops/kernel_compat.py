"""Shared compiler-compatibility bits for the in-tree Pallas kernels:

1. ``PRECISION``: every dot inside a Mosaic kernel pins
   ``precision=DEFAULT``. The package sets
   ``jax_default_matmul_precision="highest"`` (fp32-means-fp32 for the
   XLA paths); inherited inside a Mosaic kernel that flag makes a bf16
   matmul request a multi-pass algorithm, which Mosaic has refused.
   DEFAULT loses nothing there: operands are explicitly bf16 (one MXU
   pass is exact for them) and accumulation stays f32 via
   ``preferred_element_type``.

2. ``probe_with_retry``: a compile service that is restarting can fail
   a probe TRANSIENTLY, and a one-shot compile-probe would then pin the
   slow fallback for the whole process. Genuine rejects are
   deterministic, so only failures carrying the crash signature are
   retried — a plain lowering error (or any failure on a non-TPU
   backend) still costs exactly one attempt.

3. ``mesh_in_sight``: a Mosaic call cannot be partitioned automatically,
   so a kernel whose operand may be sharded declines where the ambient
   mesh (multi-device callers make theirs visible, ``jax.set_mesh``) has
   an axis larger than one that is not manual (``shard_map``).
"""

from __future__ import annotations

import time

import jax

#: precision for every dot inside a Mosaic kernel (see module docstring)
PRECISION = jax.lax.Precision.DEFAULT

#: substrings identifying a compile service falling over, as opposed
#: to a deterministic Mosaic lowering reject
_TRANSIENT_MARKERS = ("remote_compile", "tpu_compile_helper", "HTTP 500")


def mesh_in_sight() -> bool:
    ambient = jax.sharding.get_abstract_mesh()
    return any(size > 1 and name not in ambient.manual_axes
               for name, size in ambient.shape.items())


def is_transient_compile_error(e: Exception) -> bool:
    msg = str(e)
    return any(m in msg for m in _TRANSIENT_MARKERS)


def probe_with_retry(probe, on_fail, retry_delay_s: float = 2.0):
    """Run ``probe()``; retry once (after ``retry_delay_s``) iff the
    failure looks like a transient remote-compile crash. ``on_fail``
    receives ``(exception, will_retry)`` for logging. Returns True when
    a probe attempt succeeded."""
    for attempt in range(2):
        try:
            probe()
            return True
        except Exception as e:  # noqa: BLE001 — ANY probe failure selects the fallback, reported via on_fail
            will_retry = attempt == 0 and is_transient_compile_error(e)
            on_fail(e, will_retry)
            if not will_retry:
                return False
            time.sleep(retry_delay_s)
    return False

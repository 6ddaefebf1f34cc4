"""The two mixers' share of their roofline in decode, which memory sets: over
the traced span, the bytes they REQUIRE (``lib/work_parallel.py``: both
mixers' stored weights of every layer once a step; a live slot's state and
tail read and written in every layer, from the span's ``state_slots``; the
keys and values of every position behind a live slot read in every layer,
from its ``attn_positions_read``) at the HBM peak, over the device seconds
under ``mixer_join`` + ``attn_full`` + ``ssm_proj`` + ``ssm_conv`` +
``ssm_scan`` in the decode program. No clamp: what bounds the existing
kernels at this block's keys.

The counters run from the span's opening to its close on the host's clock and
the device seconds are of the decode executions inside the trace: a step cut
by either edge is a part in some hundreds of the span."""

from lib import parallel_read, work


def read(run):
    w = run["work"].get("parallel")
    scopes = parallel_read.scope_seconds(run["work"].get("decode_program")) if w else None
    cache = parallel_read.span_cache_bytes(w) if scopes else None
    if cache is None:
        return None
    by_scope, steps = scopes
    return work.share(steps * w["mixer_weight_bytes"] + cache[0],
                      sum(by_scope.get(s, 0.0) for s in parallel_read.MIXER_SCOPES),
                      run["peaks"]["hbm_bytes_per_s"])

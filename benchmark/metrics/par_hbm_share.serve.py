"""The whole decode step's share of its roofline, which memory sets: over the
traced span, the bytes its steps REQUIRE (``lib/work_parallel.py``: every
stored weight but the embedding once a step, one embedding row a live slot,
and the cache bytes of ``par_mixer_hbm_share.serve``: the live slots' state
and tail read and written, the live positions' keys and values read) at the
HBM peak, over the decode program's device seconds. No clamp. The share that
a later claim on this cell is bounded by: in a dense decoder at tens of slots
the MLP's and the head's weights cannot be made small.

The counters run from the span's opening to its close on the host's clock and
the device seconds are of the decode executions inside the trace: a step cut
by either edge is a part in some hundreds of the span."""

from lib import parallel_read, work


def read(run):
    t, w = run["trace"], run["work"].get("parallel")
    if not t or not w or parallel_read.scope_seconds(run["work"]["decode_program"]) is None:
        return None
    secs, steps = t["programs"].get(run["work"]["decode_program"], (0.0, 0))
    cache = parallel_read.span_cache_bytes(w)
    if not steps or cache is None:
        return None
    return work.share(steps * w["step_weight_bytes"] + cache[1] * w["embed_row_bytes"] + cache[0],
                      secs, run["peaks"]["hbm_bytes_per_s"])

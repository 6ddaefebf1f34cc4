"""The decode step's share of its roofline, which memory sets: over the
traced span, the bytes its steps REQUIRE (``lib/work_looped.py``: passes x
the layers' stored weights, the head, one embedding row a live slot, and for
every live position behind a streaming slot the keys and values of every
(pass, layer); not the dead columns of a slab) at the HBM peak, over the
decode program's device seconds. No clamp: the program reads each slab whole
whatever its slots hold and writes the new columns after the loop, so it
reads under what a program that moved the required bytes alone would.

The live positions are the kind's count over the traced span's steps; the
live slots are the tokens those steps delivered. The counters run from the
span's opening to its close on the host's clock and the device seconds are
of the decode executions inside the trace: a step cut by either edge is a
part in some hundreds of the span."""

from lib import decoder_read, work


def read(run):
    t, traced = run["trace"], run["counters"].get("traced")
    w = run["work"].get("looped")
    passes = decoder_read.counter_delta("stack_passes", span=True)
    if not t or not traced or not w or not passes:
        return None
    secs, steps = t["programs"].get(run["work"]["decode_program"], (0.0, 0))
    if not steps:
        return None
    tokens = decoder_read.counter_delta("tokens", span=True) or 0
    prefills = decoder_read.counter_delta("prefills", span=True) or 0
    required = (passes * w["layer_weight_bytes"] + steps * w["head_bytes"]
                + max(tokens - prefills, 0) * w["embed_row_bytes"]
                + traced["live_kv_positions"] * w["cache_bytes_per_position"])
    return work.share(required, secs, run["peaks"]["hbm_bytes_per_s"])

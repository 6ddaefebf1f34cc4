"""Share of the HBM peak that the decode program of the traced window
reached: least bytes of a step (weights once at their stored width + the
live part of the KV slab, ``lib/work.py``) over the program's device
seconds per step times the peak."""

from lib import work


def read(run):
    t, traced = run["trace"], run["counters"].get("traced")
    w = run["work"]
    if not t or not traced or "decode_program" not in w:
        return None
    secs, steps = t["programs"].get(w["decode_program"], (0.0, 0))
    if not steps:
        return None
    required = steps * w["decode_weight_bytes"] + traced["live_kv_positions"] * w["kv_bytes_per_position"]
    return work.share(required, secs, run["peaks"]["hbm_bytes_per_s"])

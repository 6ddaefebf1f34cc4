"""Share of the bf16 matrix peak that the training steps of the traced
window reached: operations the algorithm requires (``lib/work.py``) over
device-busy seconds times the peak. Busy time, not wall time: idle time
has a metric of its own."""

from lib import work


def read(run):
    t, c = run["trace"], run["counters"]
    if not t or not t.get("steps") or "flops_per_item" not in run["work"]:
        return None
    required = run["work"]["flops_per_item"] * c["items_per_step"] * t["steps"]
    return work.share(required, t["busy_s"], run["peaks"]["bf16_flops_per_s"] * run["chips"])

"""The grouped expert products' share of their roofline in decode: the
bytes of the experts that got a token in the traced span (``moe_experts_hit``
x one expert's three matrices, ``lib/work_decoder.py``) over the device
seconds under ``moe_experts`` times the HBM peak. The products are bound by
reading weights (a held expert sees one or two rows a step). No clamp.

The counter runs from the span's opening to its close on the host's clock
and the device seconds are of the decode executions inside the trace: a
step cut by either edge is a part in some hundreds of the span."""

from lib import decoder_read, work


def read(run):
    hit = decoder_read.counter_delta("moe_experts_hit", span=True)
    w = run["work"]
    scopes = decoder_read.scope_seconds(w.get("decode_program")) if "expert_bytes" in w else None
    if not hit or scopes is None:
        return None
    return work.share(hit * w["expert_bytes"], scopes[0].get("moe_experts", 0.0),
                      run["peaks"]["hbm_bytes_per_s"])

"""The recurrence's share of its roofline in decode: over the traced span, the
least time the chip could take to read and write the LIVE slots' state
(``state_slots`` of the span, the live slots summed over its decode steps, x
the bytes one live slot's state and tail are read and written with through
all state-space layers, ``lib/work_ssm.py``, at the HBM peak) over the device
seconds under ``ssm_scan`` in the decode program. The recurrence is bound by
bytes: two operations a state value and step against eight bytes. A program
that updates idle slots too, or reads the state twice, reads lower. No clamp.

The counter runs from the span's opening to its close on the host's clock and
the device seconds are of the decode executions inside the trace: a step cut
by either edge is a part in some hundreds of the span."""

from lib import decoder_read, ssm_read, work


def read(run):
    w = run["work"]
    live = decoder_read.counter_delta("state_slots", span=True)
    scopes = ssm_read.scope_seconds(w.get("decode_program")) if "ssm_state" in w else None
    if not live or scopes is None:
        return None
    return work.share(live * w["ssm_state"]["bytes_per_live_slot"],
                      scopes[0].get("ssm_scan", 0.0), run["peaks"]["hbm_bytes_per_s"])

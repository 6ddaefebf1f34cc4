"""The absorbed latent attention's share of its roofline in decode: over
the traced span, the least time the chip could take for the LIVE positions
(``counters.traced.live_kv_positions`` x layers: the larger of the
absorbed form's operations at the FLOP peak and the cache entries' bytes
at the HBM peak, ``lib/work_latent.py``) over the device seconds under
``attn_latent_core`` in the decode program. The program's einsums read the
whole slab, live or not, so the share says how much of that time the live
part needed. No clamp.

The positions are counted on the host's clock from the span's opening to
its close and the device seconds are of the decode executions inside the
trace: a step cut by either edge is a part in some hundreds of the span."""

from lib import latent_read


def read(run):
    w = run["work"]
    live = (run["counters"].get("traced") or {}).get("live_kv_positions")
    scopes = latent_read.scope_seconds(w.get("decode_program")) if "latent_core" in w else None
    seconds = scopes[0].get("attn_latent_core", 0.0) if scopes else 0.0
    if not live or seconds <= 0:
        return None
    core = w["latent_core"]
    least = max(live * core["flops_per_position"] / run["peaks"]["bf16_flops_per_s"],
                live * core["bytes_per_position"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds

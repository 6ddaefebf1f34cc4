"""Device milliseconds a decode step spends in the expert layers (scopes
``moe_route``: router, choice, sort, gather and the weighted sum back;
``moe_experts``: the three grouped products), all layers: self time inside
the decode program's executions of the traced window over their number."""

from lib import decoder_read


def read(run):
    return decoder_read.scope_ms(("moe_route", "moe_experts"), run["work"].get("decode_program"))

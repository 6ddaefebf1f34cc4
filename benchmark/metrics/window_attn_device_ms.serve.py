"""Device milliseconds a decode step spends under ``attn_window`` (the
window layers' projections, the scores over their 128-column rings, the
softmax with its sink, the output projection), all window layers."""

from lib import decoder_read


def read(run):
    return decoder_read.scope_ms(("attn_window",), run["work"].get("decode_program"))

"""Device milliseconds a decode step spends attending to the selection, all
layers: ``attn_sparse_core`` (the gather of the selected cache entries, the
absorbed scores over them, the softmax and the weighted sum of latents).
Self time inside the decode program's executions of the traced window over
their number."""

from lib import sparse_read


def read(run):
    return sparse_read.scope_ms(("attn_sparse_core",), run["work"].get("decode_program"))

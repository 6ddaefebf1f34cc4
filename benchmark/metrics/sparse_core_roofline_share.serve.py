"""The attention over the selection's share of its roofline in decode: over
the traced span, the least time the chip could take for the entries that
were attended to (``sparse_positions_read`` x all layers: the larger of the
entries' bytes at the HBM peak and the absorbed form's operations at the
FLOP peak, ``lib/work_sparse.py``) over the device seconds under
``attn_sparse_core`` in the decode program. No clamp.

The counter runs from the span's opening to its close on the host's clock
and the device seconds are of the decode executions inside the trace: a step
cut by either edge is a part in some hundreds of the span."""

from lib import sparse_read


def read(run):
    return sparse_read.roofline_share(run, "sparse_positions_read", "sparse_core",
                                      ("attn_sparse_core",))

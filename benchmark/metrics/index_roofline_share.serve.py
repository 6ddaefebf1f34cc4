"""The indexer's scoring and selection's share of their roofline in decode:
over the traced span, the least time the chip could take to score the
positions that were scored (``index_positions_scored`` x the layers that own
an indexer: the larger of one key's bytes at the HBM peak and the heads'
operations at the FLOP peak, ``lib/work_sparse.py``) over the device seconds
under ``attn_index_score`` + ``attn_index_select`` in the decode program.
The selection needs no byte of its own beyond the scores it is handed, so
its time counts against the scoring's least. No clamp.

The counter runs from the span's opening to its close on the host's clock
and the device seconds are of the decode executions inside the trace: a step
cut by either edge is a part in some hundreds of the span."""

from lib import sparse_read


def read(run):
    return sparse_read.roofline_share(run, "index_positions_scored", "index",
                                      ("attn_index_score", "attn_index_select"))

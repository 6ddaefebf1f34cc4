"""Device milliseconds a decode step spends in the indexer, the layers that
own one: ``attn_index_proj`` (its three projections, the key's norm, the
rotations: bound by weights), ``attn_index_score`` (its heads' scores over
the key cache, ReLU, the sum over heads: bound by the key cache's bytes) and
``attn_index_select`` (the exact top-k). Self time inside the decode
program's executions of the traced window over their number."""

from lib import sparse_read


def read(run):
    return sparse_read.scope_ms(sparse_read.INDEX_SCOPES, run["work"].get("decode_program"))

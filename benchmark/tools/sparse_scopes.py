"""Device time by scope of a ``sparse_latent_decoder_lm`` cell's programs,
from the trace a ``--trace 1`` run of the cell left under
``.bench_trace/<cell>``: ``tools/decoder_scopes.py`` run with the scopes of
``lib/sparse_read.py`` (``attn_latent_proj``, the three ``attn_index_*``,
``attn_sparse_core``, ``moe_shared`` beside the others).

    python3 benchmark/run.py --workload <cell> --seed 1 --trace 1
    python3 benchmark/tools/sparse_scopes.py --workload <cell> [--top 14]

Needs no chip.
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

if __name__ == "__main__":
    from lib import sparse_read
    from tools import decoder_scopes

    sparse_read.with_scopes(decoder_scopes.main)

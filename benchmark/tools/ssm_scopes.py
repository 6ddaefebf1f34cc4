"""Device time by scope of a ``hybrid_decoder_lm`` cell's programs, from the
trace a ``--trace 1`` run of the cell left under ``.bench_trace/<cell>``:
``tools/decoder_scopes.py`` run with the scopes of ``lib/ssm_read.py``
(``ssm_proj``, ``ssm_conv``, ``ssm_scan``, ``state_write``, ``moe_shared``
beside the others).

    python3 benchmark/run.py --workload <cell> --seed 1 --trace 1
    python3 benchmark/tools/ssm_scopes.py --workload <cell> [--top 14]

Needs no chip.
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

if __name__ == "__main__":
    from lib import ssm_read
    from tools import decoder_scopes

    ssm_read.with_scopes(decoder_scopes.main)

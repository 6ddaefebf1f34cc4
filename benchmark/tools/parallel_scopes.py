"""Device time by scope of a ``parallel_hybrid_decoder_lm`` cell's programs,
from the trace a ``--trace 1`` run of the cell left under
``.bench_trace/<cell>``: ``tools/decoder_scopes.py`` run with the scopes of
``lib/parallel_read.py`` (``mixer_join``, ``ssm_proj``, ``ssm_conv``,
``ssm_scan``, ``state_write`` beside the others), then the decode step's two
mixers by branch.

    python3 benchmark/run.py --workload <cell> --seed 1 --trace 1
    python3 benchmark/tools/parallel_scopes.py --workload <cell> [--top 14]

Needs no chip.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def by_branch(by_scope, runs):
    """The decode step's mixers by branch, from its seconds by scope over
    ``runs`` executions: milliseconds a step under each mixer scope, the
    attention branch, the state-space branch, the join."""
    from lib import parallel_read

    ms = {s: 1e3 * by_scope.get(s, 0.0) / runs for s in parallel_read.MIXER_SCOPES}
    return {"program": "jit__decode",
            "ms_per_step": round(1e3 * sum(by_scope.values()) / runs, 4),
            "ms_per_step_mixers": round(sum(ms.values()), 4),
            "ms_per_step_attention": round(ms["attn_full"], 4),
            "ms_per_step_state_space": round(ms["ssm_proj"] + ms["ssm_conv"] + ms["ssm_scan"], 4),
            "ms_per_step_join": round(ms["mixer_join"], 4),
            "ms_per_step_by_scope": {k: round(1e3 * v / runs, 4) for k, v in sorted(by_scope.items())}}


if __name__ == "__main__":
    from lib import parallel_read
    from tools import decoder_scopes

    parallel_read.with_scopes(decoder_scopes.main)
    read = parallel_read.scope_seconds("jit__decode")
    if read is not None:
        print(json.dumps(by_branch(*read)))

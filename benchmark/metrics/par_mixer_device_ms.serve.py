"""Device milliseconds a decode step spends in the two mixers of every
parallel block and their join: ``mixer_join`` (the one norm, the sum into the
residual), ``attn_full`` (the attention branch: projections, rotation, the
attention over the slot's keys and values), ``ssm_proj``, ``ssm_conv`` and
``ssm_scan`` (the state-space branch: its projections and gated norm, the
convolution's step, the recurrence over the cached state). Self time inside
the decode program's executions of the traced window over their number."""

from lib import parallel_read


def read(run):
    return parallel_read.scope_ms(parallel_read.MIXER_SCOPES, run["work"].get("decode_program"))

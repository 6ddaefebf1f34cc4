"""Device milliseconds a decode step spends in the state-space mixers, all
layers: ``ssm_proj`` (the norm, the two projections and the gated norm: bound
by weights), ``ssm_conv`` (the causal convolution's one step and its tail)
and ``ssm_scan`` (the recurrence over the cached state: bound by the state's
bytes). Self time inside the decode program's executions of the traced window
over their number."""

from lib import ssm_read


def read(run):
    return ssm_read.scope_ms(ssm_read.SSM_SCOPES, run["work"].get("decode_program"))

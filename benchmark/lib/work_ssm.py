"""Bytes that one decode step of a ``hybrid_decoder_lm`` configuration has to
move, from shapes alone (``lib/work.py``'s conventions): a live slot's
recurrent state, read once and written once in every state-space layer, with
its convolution tail; the weights of each held expert that got a token. Counted from the configuration whatever implements
the scopes, at the stored width (the state is float32 by the configuration's
``deployment.state_dtype``); activations are not counted, so a share computed
from these cannot honestly pass 100 %.
"""

from reference import granite_hybrid as ref

STATE_BYTES = {"float32": 4, "bfloat16": 2}


def expert_bytes(cfg, bytes_per_weight):
    """One routed expert's three matrices (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] * bytes_per_weight


def ssm_layers(cfg):
    return sum(1 for i in range(ref.n_layers(cfg)) if ref.is_ssm(cfg, i))


def state_values(cfg):
    """Values of one slot's recurrent state in one layer: heads x head size
    x state size."""
    h, p, n, _g, _inner, _conv, _k = ref.ssm_dims(cfg)
    return h * p * n


def tail_values(cfg):
    """Values of one slot's convolution tail in one layer: the last
    ``d_conv - 1`` inputs of every convolved channel."""
    _h, _p, _n, _g, _inner, conv, k = ref.ssm_dims(cfg)
    return conv * (k - 1)


def state_bytes_per_live_slot(cfg, bytes_per_value):
    """What a decode step has to move for ONE live slot through all
    state-space layers: the state read and written (a recurrence has to do
    both), and the tail read and written, at their stored widths."""
    state = STATE_BYTES[cfg["deployment"].get("state_dtype", "float32")]
    return ssm_layers(cfg) * 2 * (state_values(cfg) * state + tail_values(cfg) * bytes_per_value)

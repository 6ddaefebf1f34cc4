"""Bytes that one decode step of a ``parallel_hybrid_decoder_lm`` configuration
has to move, from shapes alone (``lib/work.py``'s conventions): the stored
weights of every layer (both mixers', then the MLP's) and the head, once a
step; one row of the embedding a live slot; a live slot's recurrent state,
read once and written once in every layer, with its convolution tail; the keys
and values of every live position behind a slot, read once in every layer.
Counted from the configuration whatever implements the scopes, at the stored
width (the state is float32 by the configuration's ``deployment.state_dtype``);
activations, the one new column a slot and the dead columns of a slab are not
counted, so a share computed from these cannot honestly pass 100 %.
"""

from reference import falcon_h1 as ref

FLOAT32 = 4
STATE_BYTES = {"float32": 4, "bfloat16": 2}


def mixer_param_count(cfg):
    """(matrix parameters, float32 values) of ONE layer's two mixers: Wq, Wk,
    Wv, Wo; the input and output projections, the convolution's weights and
    bias; the shared norm's gain, the gated norm's, dt_bias, A_log, D."""
    d = cfg["hidden_size"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h, _p, _n, _g, inner, conv, k = ref.ssm_dims(cfg)
    attention = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    ssm = d * (inner + conv + h) + inner * d + conv * k + conv
    return attention + ssm, d + inner + 3 * h


def mlp_param_count(cfg):
    """(gate, up, down; the norm's gain) of ONE layer's feed-forward half."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"], cfg["hidden_size"]


def mixer_weight_bytes(cfg, bytes_per_weight):
    """Both mixers' weights of all layers as stored."""
    matrices, gains = mixer_param_count(cfg)
    return ref.n_layers(cfg) * (matrices * bytes_per_weight + gains * FLOAT32)


def step_weight_bytes(cfg, bytes_per_weight):
    """Every weight a decode step reads whole: the layers, the final norm's
    gain and the untied head; not the embedding, of which a step reads one
    row a live slot."""
    d = cfg["hidden_size"]
    matrices, gains = mlp_param_count(cfg)
    return (mixer_weight_bytes(cfg, bytes_per_weight)
            + ref.n_layers(cfg) * (matrices * bytes_per_weight + gains * FLOAT32)
            + d * cfg["vocab_size"] * bytes_per_weight + d * FLOAT32)


def embed_row_bytes(cfg, bytes_per_weight):
    return cfg["hidden_size"] * bytes_per_weight


def state_values(cfg):
    """Values of one slot's recurrent state in one layer: heads x head size
    x state size."""
    h, p, n, _g, _inner, _conv, _k = ref.ssm_dims(cfg)
    return h * p * n


def tail_values(cfg):
    """Values of one slot's convolution tail in one layer."""
    _h, _p, _n, _g, _inner, conv, k = ref.ssm_dims(cfg)
    return conv * (k - 1)


def state_bytes_per_live_slot(cfg, bytes_per_value):
    """What a decode step has to move for ONE live slot through all layers:
    the state read and written (a recurrence has to do both), and the tail
    read and written, at their stored widths."""
    state = STATE_BYTES[cfg["deployment"].get("state_dtype", "float32")]
    return ref.n_layers(cfg) * 2 * (state_values(cfg) * state
                                    + tail_values(cfg) * bytes_per_value)


def cache_bytes_per_position(cfg, bytes_per_value):
    """Keys and values of one position through every layer, read once."""
    return ref.n_layers(cfg) * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def mixer_step_bytes(cfg, live_slots, live_positions, bytes_per_weight=2, bytes_per_value=2):
    """Least bytes of the two mixers' part of one decode step over
    ``live_slots`` slots with ``live_positions`` positions behind them."""
    return (mixer_weight_bytes(cfg, bytes_per_weight)
            + live_slots * state_bytes_per_live_slot(cfg, bytes_per_value)
            + live_positions * cache_bytes_per_position(cfg, bytes_per_value))


def decode_step_bytes(cfg, live_slots, live_positions, bytes_per_weight=2, bytes_per_value=2):
    """Least bytes of one whole decode step."""
    return (step_weight_bytes(cfg, bytes_per_weight)
            + live_slots * (embed_row_bytes(cfg, bytes_per_weight)
                            + state_bytes_per_live_slot(cfg, bytes_per_value))
            + live_positions * cache_bytes_per_position(cfg, bytes_per_value))

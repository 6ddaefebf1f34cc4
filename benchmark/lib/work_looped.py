"""Bytes that one decode step of a ``looped_decoder_lm`` configuration has to
move, from shapes alone (``lib/work.py``'s conventions): the layers' stored
weights once a PASS (the same weights, read again by every pass: a step
cannot keep 4.9 GB on the chip between passes), the head once, one row of the
embedding a live slot, and for every live position behind a slot the keys and
values of every (pass, layer). Counted at the stored width; activations, the
closing norms' gain and the dead columns of a slab are not counted, so a
share computed from these cannot honestly pass 100 %.
"""

from reference import ouro as ref

FLOAT32 = 4


def layer_param_count(cfg):
    """(matrix parameters, float32 gains) of one layer: Wq, Wk, Wv, Wo,
    gate, up, down; the four norms."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f, 4 * d


def layer_weight_bytes(cfg, bytes_per_weight):
    """All layers' weights as stored: what ONE pass reads."""
    matrices, gains = layer_param_count(cfg)
    return ref.n_layers(cfg) * (matrices * bytes_per_weight + gains * FLOAT32)


def head_bytes(cfg, bytes_per_weight):
    """The output head and the final norm's gain (the head is not tied)."""
    d = cfg["hidden_size"]
    return d * cfg["vocab_size"] * bytes_per_weight + d * FLOAT32


def embed_row_bytes(cfg, bytes_per_weight):
    return cfg["hidden_size"] * bytes_per_weight


def model_bytes(cfg, bytes_per_weight):
    """The whole model as stored: layers, embedding, head, final norm, gate."""
    d = cfg["hidden_size"]
    return (layer_weight_bytes(cfg, bytes_per_weight) + head_bytes(cfg, bytes_per_weight)
            + cfg["vocab_size"] * d * bytes_per_weight + (d + 1) * FLOAT32)


def cache_entries_per_position(cfg):
    """(pass, layer) pairs that keep keys and values of a position."""
    return ref.n_passes(cfg) * ref.n_layers(cfg)


def cache_bytes_per_position(cfg, bytes_per_value):
    """Keys and values of one position through every (pass, layer)."""
    per_entry = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value
    return cache_entries_per_position(cfg) * per_entry


def decode_step_bytes(cfg, live_slots, live_positions, bytes_per_weight=2, bytes_per_value=2):
    """Least bytes of one decode step over ``live_slots`` slots with
    ``live_positions`` positions behind them in all."""
    return (ref.n_passes(cfg) * layer_weight_bytes(cfg, bytes_per_weight)
            + head_bytes(cfg, bytes_per_weight)
            + live_slots * embed_row_bytes(cfg, bytes_per_weight)
            + live_positions * cache_bytes_per_position(cfg, bytes_per_value))

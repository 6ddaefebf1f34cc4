"""Operations and bytes that the algorithm *requires*, from shapes alone.

These are the numerators of every roofline share the benchmark reports.
They count only what the mathematics needs (recomputation, padding and
the masked half of causal attention do not count), so a share computed
from them cannot honestly pass 100 %.

Conventions (the arithmetic of ``bench.py::_bench_transformer``, with one
correction): a multiply-accumulate is 2 operations; the backward pass is
twice the forward pass; causal attention needs half of the full T x T
scores, so it is counted at half (``bench.py`` counts it whole).
"""


def lm_forward_flops_per_token(cfg, seq_len):
    """Forward operations for one token of a ``seq_len``-long sequence of a
    GPT-2-shaped decoder: per layer the Q, K, V and output projections
    (4 d^2 MACs), the MLP (2 * ratio * d^2 MACs) and causal attention
    (scores and values: 2 * T * d MACs, halved for causality), then the
    output head (d * V MACs). Embedding look-ups and norms are not counted.
    """
    d, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    ratio = cfg.get("mlp_ratio", 4)
    per_layer = 2 * (4 + 2 * ratio) * d * d + 2 * seq_len * d
    return layers * per_layer + 2 * d * vocab


def lm_train_flops_per_token(cfg, seq_len):
    """Forward plus backward (backward = 2 x forward)."""
    return 3 * lm_forward_flops_per_token(cfg, seq_len)


def lm_param_count(cfg):
    """Parameters of the block stack as the program stores it: no Q/K/V
    biases, an output head that is not tied to the embedding."""
    d, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    h = cfg.get("mlp_ratio", 4) * d
    block = 4 * d * d + d + 2 * d * h + h + d + 4 * d
    return vocab * d + cfg["n_positions"] * d + layers * block + 2 * d + d * vocab


def lm_decode_weight_bytes(cfg, bytes_per_weight):
    """Bytes of weights that one decode step has to read once: everything
    but the embedding and position tables, of which a step reads one row
    per slot."""
    d, vocab = cfg["n_embd"], cfg["vocab_size"]
    tables = (vocab + cfg["n_positions"]) * d
    return (lm_param_count(cfg) - tables) * bytes_per_weight


def lm_kv_bytes_per_position(cfg, bytes_per_value):
    """Keys and values of one position through all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_value


def lm_decode_step_bytes(cfg, live_positions, bytes_per_weight, bytes_per_kv):
    """Least bytes one decode step moves: the weights once at their stored
    width plus the live part of the KV slab (``live_positions`` is the sum
    over active slots of the positions each has filled)."""
    return (lm_decode_weight_bytes(cfg, bytes_per_weight)
            + live_positions * lm_kv_bytes_per_position(cfg, bytes_per_kv))


def share(required, seconds, peak_per_second):
    """Percent of the roofline: the least time the chip could take over
    the time it took. No clamp: a reading over 100 means the work is
    over-counted or the time leaves work out, and has to be seen."""
    if seconds <= 0:
        return None
    return 100.0 * (required / peak_per_second) / seconds

"""Bytes that one decode step of a ``decoder_lm`` configuration has to
move, from shapes alone (``lib/work.py``'s conventions): the weights
every step reads, the weights of each held expert that got a token, and
the cache positions read, by layer kind. Counted at the stored width; the
embedding (one row a slot) and activations are not counted, so a share
computed from these cannot honestly pass 100 %.
"""

from reference import mimo_v2 as ref

FLOAT32 = 4


def expert_bytes(cfg, bytes_per_weight):
    """One expert's three matrices (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * bytes_per_weight


def attention_weight_count(cfg, layer):
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, hd, vd = ref.kv_heads(cfg, layer), cfg["head_dim"], cfg["v_head_dim"]
    return d * hq * hd + d * hkv * hd + d * hkv * vd + hq * vd * d


def decode_fixed_weight_bytes(cfg, bytes_per_weight):
    """What every decode step reads whatever the routing: attention and
    dense-MLP matrices and the head at the stored width; norm gains, sinks
    and the router (weight and correction bias) in float32."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"] * bytes_per_weight + d * FLOAT32  # head, final norm
    for layer in range(ref.n_layers(cfg)):
        total += attention_weight_count(cfg, layer) * bytes_per_weight + 2 * d * FLOAT32
        if ref.has_sink(cfg, layer):
            total += cfg["num_attention_heads"] * FLOAT32
        if ref.is_dense(cfg, layer):
            total += 3 * d * cfg["intermediate_size"] * bytes_per_weight
        else:
            total += (d + 1) * ref.router_width(cfg) * FLOAT32
    return total


def cache_bytes_per_position(cfg, bytes_per_value):
    """(full, window): keys and values of one position through all layers
    of that kind."""
    per_head = (cfg["head_dim"] + cfg["v_head_dim"]) * bytes_per_value
    full = window = 0
    for layer in range(ref.n_layers(cfg)):
        if ref.is_window(cfg, layer):
            window += ref.kv_heads(cfg, layer) * per_head
        else:
            full += ref.kv_heads(cfg, layer) * per_head
    return full, window


def cache_bytes_read(cfg, positions, bytes_per_value):
    """Cache bytes a step over slots at ``positions`` (each slot's count of
    positions behind it) has to read: all of them in a full layer, the
    last ``sliding_window - 1`` in a window layer."""
    full, window = cache_bytes_per_position(cfg, bytes_per_value)
    reach = cfg["sliding_window"] - 1
    return sum(p * full + min(p, reach) * window for p in positions)


def decode_step_bytes(cfg, experts_hit, positions, bytes_per_weight=2, bytes_per_value=2):
    """Least bytes of one decode step: the fixed weights, ``experts_hit``
    experts (held experts with a token, summed over layers) and the cache."""
    return (decode_fixed_weight_bytes(cfg, bytes_per_weight)
            + experts_hit * expert_bytes(cfg, bytes_per_weight)
            + cache_bytes_read(cfg, positions, bytes_per_value))

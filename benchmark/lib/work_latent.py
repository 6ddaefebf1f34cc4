"""Bytes and operations that one decode step of a ``latent_decoder_lm``
configuration has to move and make, from shapes alone (``lib/work.py``'s
conventions): the weights every step reads, the weights of each held
expert that got a token, the latent cache's bytes and the absorbed
attention's operations a live position. Counted at the stored width; the
embedding (one row a slot) and activations are not counted, so a share
computed from these cannot honestly pass 100 %.
"""

from reference import deepseek_v2 as ref

FLOAT32 = 4


def expert_bytes(cfg, bytes_per_weight):
    """One routed expert's three matrices (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * bytes_per_weight


def attention_weight_count(cfg):
    """The five projections of a latent layer: query down and up, the
    compressed key/value with its rotary key, its up-projection, output."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = ref.head_dims(cfg)
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return d * qr + qr * h * (nope + rope) + d * (kr + rope) + kr * h * (nope + vd) + h * vd * d


def decode_fixed_weight_bytes(cfg, bytes_per_weight):
    """What every decode step reads whatever the routing: attention,
    dense-MLP and shared-expert matrices and the head at the stored width;
    norm gains (two a layer, the two of the latents, the final one) and
    the router in float32. No metric reads it yet: the whole step's share
    of HBM speed (``hbm_share.serve``) is not joined by this family's cell,
    whose experts read depend on routing."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"] * bytes_per_weight + d * FLOAT32
    for layer in range(ref.n_layers(cfg)):
        total += attention_weight_count(cfg) * bytes_per_weight
        total += (2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]) * FLOAT32
        if ref.is_dense(cfg, layer):
            total += 3 * d * cfg["intermediate_size"] * bytes_per_weight
        else:
            total += d * ref.router_width(cfg) * FLOAT32
            total += 3 * d * ref.shared_width(cfg) * bytes_per_weight
    return total


def latent_values_per_position(cfg):
    """What a layer caches a position: the latent and the one rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_bytes_per_position(cfg, bytes_per_value):
    """One position's cache entries through all layers."""
    return ref.n_layers(cfg) * latent_values_per_position(cfg) * bytes_per_value


def absorbed_flops_per_position(cfg):
    """Operations the absorbed decode makes a live position and LAYER: every
    head's score over the entry (kv_lora_rank + rope multiply-adds) and its
    share of the weighted sum of latents (kv_lora_rank)."""
    h, kr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    return 2 * h * (kr + cfg["qk_rope_head_dim"] + kr)

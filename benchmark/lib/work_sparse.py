"""Bytes and operations that one decode step of a
``sparse_latent_decoder_lm`` configuration has to move and make, from shapes
alone (``lib/work.py``'s conventions): the weights of each held expert that
got a token, and, a cached position and ALL the layers that do it, what the
indexer's scoring and the attention over the selection need. The numerators
count the least work of the mathematics (an indexer key read once by the
layer that scores it, a selected entry read once by the layer that attends
to it), whatever implements it; the step's own key and entry are at hand and
are not counted, nor are activations, so a share computed from these cannot
honestly pass 100 %.
"""

from reference import glm_dsa as ref


def expert_bytes(cfg, bytes_per_weight):
    """One routed expert's three matrices (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * bytes_per_weight


def index_layers(cfg):
    """Layers built that own an indexer (score, select, cache a key)."""
    return sum(ref.owns_indexer(cfg, i) for i in range(ref.n_layers(cfg)))


def index_bytes_per_position(cfg, bytes_per_value):
    """One cached position's indexer keys, through the layers that score."""
    return index_layers(cfg) * cfg["index_head_dim"] * bytes_per_value


def index_flops_per_position(cfg):
    """Operations of scoring one cached position, through the layers that
    score: every indexer head's dot product with the one key, then its
    ReLU-weighted term of the sum (one multiply-add a head)."""
    heads, head_dim, _topk = ref.index_dims(cfg)
    return index_layers(cfg) * 2 * heads * (head_dim + 1)


def sparse_values_per_position(cfg):
    """What a layer caches a position for the attention: the latent and the
    one rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def sparse_bytes_per_position(cfg, bytes_per_value):
    """One selected position's cache entries through ALL layers (every layer
    attends to the selection, whoever made it)."""
    return ref.n_layers(cfg) * sparse_values_per_position(cfg) * bytes_per_value


def sparse_flops_per_position(cfg):
    """Operations the absorbed attention makes a selected position, through
    all layers: every head's score over the entry (kv_lora_rank + rope
    multiply-adds) and its share of the weighted sum of latents
    (kv_lora_rank)."""
    h, kr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    return ref.n_layers(cfg) * 2 * h * (kr + cfg["qk_rope_head_dim"] + kr)


def least_seconds(positions, work, peaks):
    """The least time the chip could take for ``positions`` of ``work``
    ({"flops_per_position", "bytes_per_position"}): the larger of its
    operations at the bfloat16 peak and its bytes at the HBM peak."""
    return max(positions * work["flops_per_position"] / peaks["bf16_flops_per_s"],
               positions * work["bytes_per_position"] / peaks["hbm_bytes_per_s"])

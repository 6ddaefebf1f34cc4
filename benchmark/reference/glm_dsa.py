"""Plain reference for GLM-5.2 (``model_type: glm_moe_dsa``,
https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json): the forward
pass in straightforward float32 ``jax.numpy`` at matmul precision "highest".
Latent attention in the EXPANDED form only (keys and values of every head
made from the latents), over the positions a learned indexer selects. No
cache, no kernels, no batching (one sequence at a time), no scan; it imports
nothing of ``deeplearning4j_tpu``.

The layers built are the published layers ``deployment.layers`` names, in
order; ``mlp_layer_types`` and ``indexer_types`` are kept whole as published
and read at those indices. Layer ``l``:

    h = x + Attn_l(RMSNorm(x; g1));   y = h + FFN_l(RMSNorm(h; g2))

with, for ``a = RMSNorm(x)`` (d = ``hidden_size``, H heads):

    c_q = RMSNorm(a W_qa; g_q);  [q_nope | q_pe]_h = c_q W_qb      (H x (nope + rope))
    [c | k_pe] = a W_kva;  c_kv = RMSNorm(c; g_kv);  [k_nope | v]_h = c_kv W_kvb
    s_h[t, s] = (q_nope_h[t] . k_nope_h[s] + rot(q_pe_h[t]) . rot(k_pe[s])) (nope + rope)^-1/2
    o_h[t] = softmax over s in S_t of s_h[t, s], applied to v_h;   Attn = concat_h(o_h) W_o

``S_t`` is the indexer's selection for position t. A layer whose
``indexer_types`` entry is ``full`` makes it (J = ``index_n_heads`` heads of
``index_head_dim``):

    q^I[t, j] = rot(c_q[t] W^I_q)_j;   k^I[s] = rot(LayerNorm(a[s] W^I_k));   w[t] = a[t] W^I_w
    I[t, s] = sum_j w[t, j] J^-1/2 index_head_dim^-1/2 ReLU(q^I[t, j] . k^I[s])      for s <= t
    S_t = the positions of the min(index_topk, t + 1) largest I[t, s], ties to the lower position

(DeepSeek Sparse Attention's lightning indexer as published with
DeepSeek-V3.2-Exp, which ``glm_moe_dsa`` names.) A layer whose entry is
``shared`` has no indexer and attends to the ``S_t`` of the nearest ``full``
layer before it. Where ``t + 1 <= index_topk`` the layer is the dense latent
layer.

    FFN = 2.5 sum_{e in top 8} w_e SwiGLU_e(r) + SwiGLU_shared(r)

sigmoid scores, the choice by score + correction bias over all
``published.n_routed_experts`` experts, the chosen scores renormalised
(``norm_topk_prob``) and then times ``routed_scaling_factor``.

What is NOT in the published config and was set here (the configuration
file lists each under ``assumed``):

- the indexer's form: LayerNorm WITH a bias on ``k^I`` (eps ``rms_norm_eps``),
  rotation on the FIRST ``qk_rope_head_dim`` of an indexer head's
  ``index_head_dim`` dimensions, the two scale factors on the weights; its
  Hadamard rotation is orthogonal and drops out of the dot product, and its
  FP8 quantisation is an inference economy that is not built (bfloat16 keys,
  float32 scores);
- ``shared`` = the nearest ``full`` layer before;
- the rotary pairing is half-split (dimension i turns with i + rope/2) where
  the checkpoint interleaves (``rope_interleave``,
  ``indexer_rope_interleave``): a column permutation random weights do not
  see;
- the config's ``head_dim`` 192 is not read: a head is ``qk_nope_head_dim +
  qk_rope_head_dim`` wide and the softmax scale is that width's;
- the initialisation; the multi-token-prediction layer, its index sharing
  and the auxiliary losses are not built.

The chip's share of a deployment: the router scores all
``published.n_routed_experts`` experts, the sum runs over the chosen experts
that are HELD (``n_routed_experts`` of them from
``deployment.experts_offset``), the shared expert is whole, and the
vocabulary is the held slice.

``mode`` selects the arithmetic of the matrix products that the
configuration states in bfloat16: ``"float32"`` is the reference, ``"int8"``
(both operands rounded to 127 levels of their largest magnitude) the control
that ``correct`` has to refuse. The router's product and the indexer's
scores (their ReLU, weights and sum over heads) are stated in float32 and
stay there in both; the indexer's projections and its query-key products are
among the bfloat16 ones.

Attention and the indexer go a block of ``QUERY_BLOCK`` queries at a time
over all keys (the scores of 64 heads over a 14,336-token sample are 52 GB
in one piece); everything else is whole.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: queries a block of the attention and of the indexer; a sequence is padded
#: to a multiple (64 heads x 128 x 14,336 float32 scores are 0.47 GB, beside
#: 3.3 GB of a layer's weights and the samples' rows)
QUERY_BLOCK = 128


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


# -- the configuration, read ---------------------------------------------------
def n_layers(cfg):
    return cfg["num_hidden_layers"]


def published_layer(cfg, layer):
    """The published index of the ``layer``-th layer built."""
    return cfg["deployment"]["layers"][layer]


def is_dense(cfg, layer):
    return cfg["mlp_layer_types"][published_layer(cfg, layer)] == "dense"


def owns_indexer(cfg, layer):
    kind = cfg["indexer_types"][published_layer(cfg, layer)]
    if kind not in ("full", "shared"):
        raise ValueError(f"indexer type {kind!r} is not built")
    return kind == "full"


def router_width(cfg):
    return cfg["published"]["n_routed_experts"]


def experts_held(cfg):
    return cfg["deployment"]["experts_offset"], cfg["n_routed_experts"]


def shared_width(cfg):
    return (cfg["n_shared_experts"] or 0) * cfg["moe_intermediate_size"]


def head_dims(cfg):
    """(without position, rotated, value) sizes of a head."""
    return cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]


def index_dims(cfg):
    """(heads, head size, positions kept) of the indexer."""
    return cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"]


def rope_frequencies(cfg):
    dim, params = cfg["qk_rope_head_dim"], cfg["rope_parameters"]
    if params["rope_type"] != "default":
        raise ValueError(f"rope_type {params['rope_type']!r} is not built")
    return (float(params["rope_theta"])
            ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def softmax_scale(cfg):
    nope, rope, _ = head_dims(cfg)
    return (nope + rope) ** -0.5


# -- weights -------------------------------------------------------------------
def layer_shapes(cfg, layer):
    """Leaf name -> (shape, std, mean) of one layer, in the published
    layouts (``q_b``: heads x [nope | rope]; ``kv_a``: [latent | rotary
    key]; ``kv_b``: heads x [k_nope | v]; ``indexer.q_b``: indexer heads x
    head size). Expert leaves lead with the experts held here."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = head_dims(cfg)
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    # assumed: output projections of the residual branches scaled by depth
    res = 0.02 / math.sqrt(2 * cfg["published"]["num_hidden_layers"])
    out = {"norm1": ((d,), 0.02, 1.0), "norm2": ((d,), 0.02, 1.0),
           "attn.q_a": ((d, qr), 0.02, 0.0), "attn.q_norm": ((qr,), 0.02, 1.0),
           "attn.q_b": ((qr, h * (nope + rope)), 0.02, 0.0),
           "attn.kv_a": ((d, kr + rope), 0.02, 0.0), "attn.kv_norm": ((kr,), 0.02, 1.0),
           "attn.kv_b": ((kr, h * (nope + vd)), 0.02, 0.0),
           "attn.o": ((h * vd, d), res, 0.0)}
    if owns_indexer(cfg, layer):
        ih, idim, _ = index_dims(cfg)
        out.update({"indexer.q_b": ((qr, ih * idim), 0.02, 0.0),
                    "indexer.k": ((d, idim), 0.02, 0.0),
                    "indexer.k_norm.g": ((idim,), 0.02, 1.0),
                    "indexer.k_norm.b": ((idim,), 0.02, 0.0),
                    "indexer.w": ((d, ih), 0.02, 0.0)})
    if is_dense(cfg, layer):
        f = cfg["intermediate_size"]
        out.update({"mlp.gate": ((d, f), 0.02, 0.0), "mlp.up": ((d, f), 0.02, 0.0),
                    "mlp.down": ((f, d), res, 0.0)})
    else:
        f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        out.update({"router.w": ((d, router_width(cfg)), 0.02, 0.0),
                    "router.bias": ((router_width(cfg),), 0.02, 0.0),
                    "experts.gate": ((held, d, f), 0.02, 0.0),
                    "experts.up": ((held, d, f), 0.02, 0.0),
                    "experts.down": ((held, f, d), res, 0.0)})
        fs = shared_width(cfg)
        if fs:
            out.update({"shared.gate": ((d, fs), 0.02, 0.0), "shared.up": ((d, fs), 0.02, 0.0),
                        "shared.down": ((fs, d), res, 0.0)})
    return out


TOP_LEAVES = ("embed", "norm_f", "head")
LAYER_LEAVES = ("norm1", "norm2", "attn.q_a", "attn.q_norm", "attn.q_b", "attn.kv_a",
                "attn.kv_norm", "attn.kv_b", "attn.o", "indexer.q_b", "indexer.k",
                "indexer.k_norm.g", "indexer.k_norm.b", "indexer.w", "mlp.gate", "mlp.up",
                "mlp.down", "router.w", "router.bias", "experts.gate", "experts.up",
                "experts.down", "shared.gate", "shared.up", "shared.down")
FLOAT32_LEAVES = ("norm1", "norm2", "norm_f", "attn.q_norm", "attn.kv_norm",
                  "indexer.k_norm.g", "indexer.k_norm.b", "router.w", "router.bias")


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, std, mean, round_bf16):
    x = mean + std * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32) if round_bf16 else x


@functools.partial(jax.jit, static_argnums=(2, 3))
def _draw_experts(key, ids, shape, std):
    """One key an EXPERT (by its published index), so that a chip's share
    holds the same experts whichever experts its neighbours hold."""
    x = jax.vmap(lambda e: std * jax.random.normal(
        jax.random.fold_in(key, e), shape, jnp.float32))(ids)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def make_leaf(cfg, seed, layer, name):
    """One leaf of the ``layer``-th layer built (-1: the top leaves) as
    float32, from a key of its own (by the layer's PUBLISHED index, so a
    layer is the same whichever others are kept). Leaves that the
    configuration stores in bfloat16 are rounded to it, so that program and
    reference hold the same values and the comparison reads arithmetic."""
    index = (TOP_LEAVES + LAYER_LEAVES).index(name)
    at = -1 if layer < 0 else published_layer(cfg, layer)
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), at + 1), index)
    round_bf16 = name not in FLOAT32_LEAVES
    if layer < 0:
        d, vocab = cfg["hidden_size"], cfg["vocab_size"]
        shape, std, mean = {"embed": ((vocab, d), 0.02, 0.0), "norm_f": ((d,), 0.02, 1.0),
                            "head": ((d, vocab), 0.02, 0.0)}[name]
        return _draw(key, shape, std, mean, round_bf16)
    shape, std, mean = layer_shapes(cfg, layer)[name]
    if name.startswith("experts."):
        offset, held = experts_held(cfg)
        return _draw_experts(key, jnp.arange(offset, offset + held), shape[1:], std)
    return _draw(key, shape, std, mean, round_bf16)


def make_layer(cfg, seed, layer):
    return {name: make_leaf(cfg, seed, layer, name) for name in layer_shapes(cfg, layer)}


def make_top(cfg, seed):
    return {name: make_leaf(cfg, seed, -1, name) for name in TOP_LEAVES}


# -- arithmetic ----------------------------------------------------------------
def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def prod(a, b, mode):
    if mode == "int8":
        a, b = _int8(a), _int8(b)
    elif mode != "float32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def rotate(x, inv):
    """Rotary positions 0..T-1 on the FIRST ``2 len(inv)`` dimensions of x
    (T, heads, size); the rest pass through. assumed: half-split pairing
    (dimension i turns with i + rope/2)."""
    half = len(inv)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def _query_blocks(q, t):
    """q (T, ...) padded to whole blocks -> (blocks, QUERY_BLOCK, ...) and
    each block's first position."""
    pad = -t % QUERY_BLOCK
    blocks = jnp.pad(q, ((0, pad),) + ((0, 0),) * (q.ndim - 1)).reshape(
        (-1, QUERY_BLOCK) + q.shape[1:])
    return blocks, jnp.arange(blocks.shape[0]) * QUERY_BLOCK


def top_positions(scores, k):
    """scores (rows, T) float32, -inf where a position cannot be chosen ->
    bool (rows, T): the ``k`` largest of each row, ties to the lower
    position; every choosable one where there are fewer than ``k``. The
    k-th largest value of the sorted row is the threshold; the ties at it
    are counted off from the left."""
    valid = scores > -jnp.inf
    if scores.shape[-1] <= k:
        return valid
    kth = -jnp.sort(-scores, axis=-1)[:, k - 1:k]
    above, ties = scores > kth, scores == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))) & valid


def select(cfg, w, x, c_q, mode):
    """The indexer's selection: x (T, d) the normed input, c_q (T, q_lora)
    the normed query latent -> bool (T, T), row t true at S_t; None where
    T <= index_topk (every position selects all before it)."""
    t = x.shape[0]
    ih, idim, topk = index_dims(cfg)
    if t <= topk:
        return None
    inv = rope_frequencies(cfg)
    q = rotate(prod(c_q, w["indexer.q_b"], mode).reshape(t, ih, idim), inv)
    k = layer_norm(prod(x, w["indexer.k"], mode), w["indexer.k_norm.g"],
                   w["indexer.k_norm.b"], cfg["rms_norm_eps"])
    k = rotate(k[:, None, :], inv)[:, 0].T                                     # (idim, T)
    weights = prod(x, w["indexer.w"], mode) * (ih ** -0.5 * idim ** -0.5)      # (T, ih)
    q_blocks, starts = _query_blocks(q, t)
    w_blocks, _ = _query_blocks(weights, t)

    def one_block(args):
        qb, wb, start = args
        dots = prod(qb.transpose(1, 0, 2), k, mode)                            # (ih, block, T)
        scores = jnp.sum(jax.nn.relu(dots) * wb.T[:, :, None], axis=0)         # float32, as stated
        scores = jnp.where(scores == 0, 0.0, scores)                           # -0 is 0
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        return top_positions(jnp.where(seen, scores, -jnp.inf), topk)

    return jax.lax.map(one_block, (q_blocks, w_blocks, starts)).reshape(-1, t)[:t]


def attention(cfg, owns, w, x, mode, selected):
    """x (T, d) -> ((T, d), the selection attended by), the expanded form
    over the selected positions; no biases but the indexer key norm's.
    ``owns``: the layer has an indexer and makes the selection."""
    t = x.shape[0]
    h, kr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = head_dims(cfg)
    eps = cfg["rms_norm_eps"]
    inv = rope_frequencies(cfg)
    c_q = rms_norm(prod(x, w["attn.q_a"], mode), w["attn.q_norm"], eps)
    if owns:
        selected = select(cfg, w, x, c_q, mode)
    q = prod(c_q, w["attn.q_b"], mode).reshape(t, h, nope + rope)
    latent = prod(x, w["attn.kv_a"], mode)
    kv = prod(rms_norm(latent[:, :kr], w["attn.kv_norm"], eps),
              w["attn.kv_b"], mode).reshape(t, h, nope + vd)
    # ONE rotary key a position, shared by every head
    k_pe = jnp.broadcast_to(rotate(latent[:, None, kr:], inv), (t, h, rope))
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], inv)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1).transpose(1, 2, 0)   # (h, dk, T)
    v = kv[..., nope:].transpose(1, 0, 2)                                      # (h, T, vd)
    scale = softmax_scale(cfg)
    blocks, starts = _query_blocks(q, t)
    sparse = selected is not None
    rows = _query_blocks(selected, t)[0] if sparse else jnp.zeros((blocks.shape[0], 0))

    def one_block(args):
        qb, start, allowed = args
        s = prod(qb.transpose(1, 0, 2), k, mode) * scale                       # (h, block, T)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        if sparse:
            # a padded query row selects nothing: keep it finite
            seen = seen & (allowed | ~allowed.any(-1, keepdims=True))
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return prod(p, v, mode).transpose(1, 0, 2)                             # (block, h, vd)

    o = jax.lax.map(one_block, (blocks, starts, rows)).reshape(-1, h * vd)[:t]
    return prod(o, w["attn.o"], mode), selected


def route(cfg, w, x):
    """(T, router width) weights: w_e over the chosen ``num_experts_per_tok``,
    0 elsewhere. Sigmoid scores over every expert; the choice is by score +
    correction bias (``noaux_tc`` with one group: the largest over all), the
    weights are the chosen plain scores, renormalised under
    ``norm_topk_prob``, then times ``routed_scaling_factor``."""
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("only sigmoid scores under noaux_tc are built")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("grouped sigmoid routing is not built")
    s = jax.nn.sigmoid(jnp.matmul(x, w["router.w"], precision=HIGHEST))
    t = s.shape[0]
    _, chosen = jax.lax.top_k(s + w["router.bias"], cfg["num_experts_per_tok"])
    picked = jnp.zeros_like(s).at[jnp.arange(t)[:, None], chosen].set(1.0)
    weights = s * picked
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights * cfg["routed_scaling_factor"]


def swiglu(x, gate, up, down, mode):
    return prod(jax.nn.silu(prod(x, gate, mode)) * prod(x, up, mode), down, mode)


def routed(cfg, w, x, mode):
    """The held experts' part of sum_{e chosen} w_e SwiGLU_e(x), each held
    expert applied to every token and weighted (0 where not chosen)."""
    offset, held = experts_held(cfg)
    weights = route(cfg, w, x)
    y = jnp.zeros_like(x)
    for e in range(held):
        y = y + weights[:, offset + e, None] * swiglu(
            x, w["experts.gate"][e], w["experts.up"][e], w["experts.down"][e], mode)
    return y


def experts(cfg, w, x, mode):
    """The expert layer as this chip computes it: its held experts' part
    and the shared expert, which every chip of the layer computes alike."""
    y = routed(cfg, w, x, mode)
    if shared_width(cfg):
        y = y + swiglu(x, w["shared.gate"], w["shared.up"], w["shared.down"], mode)
    return y


def layer_of_kind(cfg, dense, owns, w, x, selected=None, mode="float32"):
    """A layer by what it is (a dense MLP or experts; an indexer of its own
    or a selection handed on) -> (the layer's output, the selection its
    attention went by)."""
    eps = cfg["rms_norm_eps"]
    a, selected = attention(cfg, owns, w, rms_norm(x, w["norm1"], eps), mode, selected)
    h = x + a
    m = rms_norm(h, w["norm2"], eps)
    if dense:
        return h + swiglu(m, w["mlp.gate"], w["mlp.up"], w["mlp.down"], mode), selected
    return h + experts(cfg, w, m, mode), selected


def layer(cfg, index, w, x, selected=None, mode="float32"):
    """The ``index``-th layer built -> (its output, the selection its
    attention went by: its own where it has an indexer, ``selected`` handed
    on where it shares)."""
    return layer_of_kind(cfg, is_dense(cfg, index), owns_indexer(cfg, index), w, x, selected,
                         mode)


def head_logits(cfg, top, x, mode="float32"):
    return prod(rms_norm(x, top["norm_f"], cfg["rms_norm_eps"]), top["head"], mode)


class _Frozen(dict):
    """A configuration as a static jit argument (hashed by its content)."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 6))
def _kind_jit(cfg, dense, owns, w, x, selected, mode):
    return layer_of_kind(cfg, dense, owns, w, x, selected, mode)


def _layer_jit(cfg, index, w, x, selected, mode):
    """One program a KIND of layer, not a layer: the three sharing expert
    layers of a period are one compile (each takes minutes at 14,336
    positions)."""
    return _kind_jit(cfg, is_dense(cfg, index), owns_indexer(cfg, index), w, x, selected, mode)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head_jit(cfg, top, x, mode):
    return head_logits(cfg, top, x, mode)


def logits(cfg, seed, ids, mode="float32", weights=None, selections=None):
    """Logits (T, V) of one sequence ids (T,), a layer at a time; each
    layer's weights are made, applied and dropped. ``weights`` (a list of
    layers, then the top) replaces the generator (the share test).
    ``selections``: a list that takes each layer's selection (T, T) bool
    (None: all before it)."""
    cfg = _Frozen(cfg)
    top = weights[-1] if weights else make_top(cfg, seed)
    x = top["embed"][jnp.asarray(ids, jnp.int32)]
    selected = None
    for i in range(n_layers(cfg)):
        w = weights[i] if weights else make_layer(cfg, seed, i)
        x, selected = _layer_jit(cfg, i, w, x, selected, mode)
        if selections is not None:
            selections.append(None if selected is None else np.asarray(selected))
    return _head_jit(cfg, top, x, mode)


def served_token_gaps(cfg, seed, samples, pad_to, answers_pad, mode="float32", control_mode=None):
    """For served requests (dicts with ``prompt`` and ``tokens``): at each
    position where the program produced a token, how far that token's
    logit lies below the reference's best, from one forward pass over
    prompt + tokens, IN BLOCKS: one layer's weights are made, applied to
    every sample, and dropped (a float32 expert layer is 3.3 GB). With
    ``control_mode`` also the same gap for the token a pass in that mode
    puts first. Rows are padded at the end to ``pad_to`` positions (causal
    attention and a causal selection: padding after a row's end cannot
    reach it), so every call has one shape. Returns arrays over all served
    tokens."""
    cfg = _Frozen(cfg)
    top = make_top(cfg, seed)
    rows = []
    for s in samples:
        full = list(s["prompt"]) + list(s["tokens"])
        n, first = len(s["tokens"]), len(s["prompt"]) - 1
        if len(full) - 1 > pad_to or n > answers_pad:
            raise ValueError("a served request is longer than the padding")
        seq = np.zeros((pad_to,), np.int32)
        seq[: len(full) - 1] = full[:-1]
        at = np.zeros((answers_pad,), np.int32)
        at[:n] = np.arange(first, first + n)
        rows.append({"ids": jnp.asarray(seq), "at": at, "n": n, "served": np.asarray(s["tokens"])})
    # one mode at a time: a row's stream and selection are 0.56 GB at 14,336
    # positions, beside 9 GB of a layer's weights and temporaries
    ends = {}
    for m in [mode] + ([control_mode] if control_mode else []):
        xs = [top["embed"][r["ids"]] for r in rows]
        selections = [None] * len(rows)
        for i in range(n_layers(cfg)):
            w = make_layer(cfg, seed, i)
            for j in range(len(rows)):
                xs[j], selections[j] = _layer_jit(cfg, i, w, xs[j], selections[j], m)
            del w
        ends[m] = [x[r["at"]] for x, r in zip(xs, rows)]
        del xs, selections
    served, control = [], []
    for j, r in enumerate(rows):
        n = r["n"]
        ref = _head_jit(cfg, top, ends[mode][j], mode)[:n]
        best = ref.max(-1)
        served.append(np.asarray(best - ref[np.arange(n), r["served"]]))
        if control_mode:
            first = _head_jit(cfg, top, ends[control_mode][j], control_mode)[:n].argmax(-1)
            control.append(np.asarray(best - ref[np.arange(n), first]))
    out = {"served": np.concatenate(served)}
    if control_mode:
        out["control"] = np.concatenate(control)
    return out

"""Plain reference for DeepSeek-V2 (``model_type: deepseek_v2``,
https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json): the
forward pass in straightforward float32 ``jax.numpy`` at matmul precision
"highest". The attention is the EXPANDED form only (keys and values of every
head made from the latents; never the absorbed form the program decodes
with). No cache, no kernels, no batching (one sequence at a time), no scan;
it imports nothing of ``deeplearning4j_tpu``.

Layer ``l`` (dense MLP where ``l < first_k_dense_replace`` or ``l`` is no
multiple of ``moe_layer_freq``, experts otherwise):

    h = x + Attn_l(RMSNorm(x; g1));   y = h + FFN_l(RMSNorm(h; g2))

with, for ``a = RMSNorm(x)`` (d = ``hidden_size``, H heads):

    c_q = RMSNorm(a W_qa; g_q);  [q_nope | q_pe]_h = c_q W_qb      (H x (nope + rope))
    [c | k_pe] = a W_kva;  c_kv = RMSNorm(c; g_kv);  [k_nope | v]_h = c_kv W_kvb
    s_h = (q_nope_h . k_nope_h + rot(q_pe_h) . rot(k_pe)) (nope + rope)^-1/2 m^2
    o_h = causal softmax(s_h) v_h;   Attn = concat_h(o_h) W_o

``k_pe`` is ONE rotary key shared by all heads; ``rot`` uses YaRN's
frequencies (``yarn``) and ``m = 0.1 mscale_all_dim ln(factor) + 1``.

    FFN = sum_{e chosen} w_e SwiGLU_e(r) + SwiGLU_shared(r)

where the choice is group-limited (``route``) and the shared expert is
``n_shared_experts x moe_intermediate_size`` wide.

What is NOT in the published config and was set here (the configuration
file lists each under ``assumed``):

- the rotary pairing is half-split (dimension i turns with i + rope/2); the
  published checkpoint interleaves the pairs, a permutation of the columns
  of ``W_qb`` and ``W_kva`` that random weights do not see;
- the initialisation; ``seq_aux`` and the auxiliary losses are training's
  and are not built.

The chip's share of a deployment: the router scores all
``published.n_routed_experts`` experts, the sum runs over the chosen
experts that are HELD (``n_routed_experts`` of them from
``deployment.experts_offset``: one routing group), the shared expert is
whole, and the vocabulary is the held slice.

``mode`` selects the arithmetic of the matrix products that the
configuration states in bfloat16: ``"float32"`` is the reference,
``"int8"`` (both operands rounded to 127 levels of their largest
magnitude) the control that ``correct`` has to refuse. The router's
product is stated in float32 and stays there in both.

Attention goes a block of ``QUERY_BLOCK`` queries at a time over all keys
(the scores of 128 heads over a 10,240-token sample are 53 GB in one
piece); everything else is whole.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: queries a block of the attention; a sequence is padded to a multiple
QUERY_BLOCK = 256


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


# -- the configuration, read ---------------------------------------------------
def n_layers(cfg):
    return cfg["num_hidden_layers"]


def is_dense(cfg, layer):
    return layer < cfg["first_k_dense_replace"] or layer % cfg["moe_layer_freq"] != 0


def router_width(cfg):
    return cfg["published"]["n_routed_experts"]


def experts_held(cfg):
    return cfg["deployment"]["experts_offset"], cfg["n_routed_experts"]


def shared_width(cfg):
    return (cfg["n_shared_experts"] or 0) * cfg["moe_intermediate_size"]


def head_dims(cfg):
    """(without position, rotated, value) sizes of a head."""
    return cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(cfg):
    """(inverse frequencies (rope/2,), amplitude of cos and sin) of
    ``rope_scaling`` type ``yarn``; plain rotary frequencies without it.
    Pair i turns ``theta^(-2i/rope)`` a position; pairs that complete more
    than ``beta_fast`` turns inside the original context keep that, pairs
    that complete fewer than ``beta_slow`` take it divided by ``factor``,
    and the pairs between blend linearly."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    sc = cfg.get("rope_scaling")
    if not sc:
        return plain.astype(np.float32), 1.0
    if sc["type"] != "yarn":
        raise ValueError(f"rope_scaling type {sc['type']!r} is not built")
    orig = sc["original_max_position_embeddings"]

    def pair_with_turns(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_with_turns(sc["beta_fast"])), 0)
    high = min(math.ceil(pair_with_turns(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    slowed = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = plain / sc["factor"] * slowed + plain * (1.0 - slowed)
    amp = (yarn_mscale(sc["factor"], sc.get("mscale", 1.0))
           / yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0.0)))
    return inv.astype(np.float32), amp


def softmax_scale(cfg):
    nope, rope, _ = head_dims(cfg)
    scale = (nope + rope) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


# -- weights -------------------------------------------------------------------
def layer_shapes(cfg, layer):
    """Leaf name -> (shape, std, mean) of one layer, in the published
    layouts (``q_b``: heads x [nope | rope]; ``kv_a``: [latent | rotary
    key]; ``kv_b``: heads x [k_nope | v]). Expert leaves lead with the
    experts held here."""
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
    if is_dense(cfg, layer):
        f = cfg["intermediate_size"]
        out.update({"mlp.gate": ((d, f), 0.02, 0.0), "mlp.up": ((d, f), 0.02, 0.0),
                    "mlp.down": ((f, d), res, 0.0)})
    else:
        f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        out.update({"router.w": ((d, router_width(cfg)), 0.02, 0.0),
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
                "attn.kv_norm", "attn.kv_b", "attn.o", "mlp.gate", "mlp.up", "mlp.down",
                "router.w", "experts.gate", "experts.up", "experts.down",
                "shared.gate", "shared.up", "shared.down")
FLOAT32_LEAVES = ("norm1", "norm2", "norm_f", "attn.q_norm", "attn.kv_norm", "router.w")


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
    """One leaf of one layer (``layer`` -1: the top leaves) as float32,
    from a key of its own. Leaves that the configuration stores in
    bfloat16 are rounded to it, so that program and reference hold the
    same values and the comparison reads arithmetic."""
    index = (TOP_LEAVES + LAYER_LEAVES).index(name)
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), layer + 1), index)
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


def rotate(x, inv, amp):
    """Rotary positions 0..T-1 on ALL dimensions of x (T, heads, rope).
    assumed: half-split pairing (dimension i turns with i + rope/2)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = amp * jnp.cos(ang)[:, None, :], amp * jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, w, x, mode):
    """x (T, d) -> (T, d), the expanded form; no biases anywhere."""
    t = x.shape[0]
    h, kr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = head_dims(cfg)
    eps = cfg["rms_norm_eps"]
    inv, amp = yarn(cfg)
    q = prod(rms_norm(prod(x, w["attn.q_a"], mode), w["attn.q_norm"], eps),
             w["attn.q_b"], mode).reshape(t, h, nope + rope)
    latent = prod(x, w["attn.kv_a"], mode)
    kv = prod(rms_norm(latent[:, :kr], w["attn.kv_norm"], eps),
              w["attn.kv_b"], mode).reshape(t, h, nope + vd)
    # ONE rotary key a position, shared by every head
    k_pe = jnp.broadcast_to(rotate(latent[:, None, kr:], inv, amp), (t, h, rope))
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], inv, amp)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1).transpose(1, 2, 0)   # (h, dk, T)
    v = kv[..., nope:].transpose(1, 0, 2)                                      # (h, T, vd)
    scale = softmax_scale(cfg)
    pad = -t % QUERY_BLOCK
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_BLOCK, h, nope + rope)
    starts = jnp.arange(blocks.shape[0]) * QUERY_BLOCK

    def one_block(args):
        qb, start = args
        s = prod(qb.transpose(1, 0, 2), k, mode) * scale                       # (h, block, T)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return prod(p, v, mode).transpose(1, 0, 2)                             # (block, h, vd)

    o = jax.lax.map(one_block, (blocks, starts)).reshape(-1, h * vd)[:t]
    return prod(o, w["attn.o"], mode)


def route(cfg, w, x):
    """(T, router width) weights: w_e over the chosen ``num_experts_per_tok``,
    0 elsewhere. Softmax scores over every expert; ``group_limited_greedy``:
    the experts are ``n_group`` groups of consecutive experts, a group's
    score is its largest probability, the ``topk_group`` best groups stay
    and the choice is the largest probabilities inside them (``greedy``: all
    groups stay). The weights are those probabilities, renormalised only
    under ``norm_topk_prob``; the published code applies
    ``routed_scaling_factor`` where it does not renormalise."""
    if cfg["scoring_func"] != "softmax":
        raise ValueError(f"scoring_func {cfg['scoring_func']!r} is not built")
    p = jax.nn.softmax(jnp.matmul(x, w["router.w"], precision=HIGHEST), axis=-1)
    t, e = p.shape
    allowed = p
    if cfg["topk_method"] == "group_limited_greedy":
        g = cfg["n_group"]
        best = p.reshape(t, g, e // g).max(-1)
        _, groups = jax.lax.top_k(best, cfg["topk_group"])
        kept = jnp.zeros((t, g)).at[jnp.arange(t)[:, None], groups].set(1.0)
        allowed = p * jnp.repeat(kept, e // g, axis=1)
    elif cfg["topk_method"] != "greedy":
        raise ValueError(f"topk_method {cfg['topk_method']!r} is not built")
    _, chosen = jax.lax.top_k(allowed, cfg["num_experts_per_tok"])
    picked = jnp.zeros_like(p).at[jnp.arange(t)[:, None], chosen].set(1.0)
    weights = p * picked
    if cfg["norm_topk_prob"]:
        return weights / jnp.sum(weights, -1, keepdims=True)
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


def layer(cfg, index, w, x, mode="float32"):
    eps = cfg["rms_norm_eps"]
    h = x + attention(cfg, w, rms_norm(x, w["norm1"], eps), mode)
    m = rms_norm(h, w["norm2"], eps)
    if is_dense(cfg, index):
        return h + swiglu(m, w["mlp.gate"], w["mlp.up"], w["mlp.down"], mode)
    return h + experts(cfg, w, m, mode)


def head_logits(cfg, top, x, mode="float32"):
    return prod(rms_norm(x, top["norm_f"], cfg["rms_norm_eps"]), top["head"], mode)


class _Frozen(dict):
    """A configuration as a static jit argument (hashed by its content)."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer_jit(cfg, index, w, x, mode):
    return layer(cfg, index, w, x, mode)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head_jit(cfg, top, x, mode):
    return head_logits(cfg, top, x, mode)


def logits(cfg, seed, ids, mode="float32", weights=None):
    """Logits (T, V) of one sequence ids (T,), a layer at a time; each
    layer's weights are made, applied and dropped. ``weights`` (a list of
    layers, then the top) replaces the generator (the share test)."""
    cfg = _Frozen(cfg)
    top = weights[-1] if weights else make_top(cfg, seed)
    x = top["embed"][jnp.asarray(ids, jnp.int32)]
    for i in range(n_layers(cfg)):
        w = weights[i] if weights else make_layer(cfg, seed, i)
        x = _layer_jit(cfg, i, w, x, mode)
    return _head_jit(cfg, top, x, mode)


def served_token_gaps(cfg, seed, samples, pad_to, answers_pad, mode="float32", control_mode=None):
    """For served requests (dicts with ``prompt`` and ``tokens``): at each
    position where the program produced a token, how far that token's
    logit lies below the reference's best, from one forward pass over
    prompt + tokens, IN BLOCKS: one layer's weights are made, applied to
    every sample, and dropped (a float32 expert layer is 2.7 GB). With
    ``control_mode`` also the same gap for the token a pass in that mode
    puts first. Rows are padded at the end to ``pad_to`` positions (causal
    attention: padding after a row's end cannot reach it), so every call
    has one shape. Returns arrays over all served tokens."""
    cfg = _Frozen(cfg)
    modes = [mode] + ([control_mode] if control_mode else [])
    top = make_top(cfg, seed)
    rows = []
    for s in samples:
        full = list(s["prompt"]) + list(s["tokens"])
        n, first = len(s["tokens"]), len(s["prompt"]) - 1
        if len(full) - 1 > pad_to or n > answers_pad:
            raise ValueError("a served request is longer than the padding")
        seq = np.zeros((pad_to,), np.int32)
        seq[: len(full) - 1] = full[:-1]
        rows.append({"x": {m: top["embed"][jnp.asarray(seq)] for m in modes},
                     "at": np.arange(first, first + n), "served": np.asarray(s["tokens"])})
    for i in range(n_layers(cfg)):
        w = make_layer(cfg, seed, i)
        for r in rows:
            r["x"] = {m: _layer_jit(cfg, i, w, x, m) for m, x in r["x"].items()}
        del w
    served, control = [], []
    for r in rows:
        at = np.zeros((answers_pad,), np.int32)
        at[: len(r["at"])] = r["at"]
        n = len(r["at"])
        ref = _head_jit(cfg, top, r["x"][mode][at], mode)[:n]
        best = ref.max(-1)
        served.append(np.asarray(best - ref[np.arange(n), r["served"]]))
        if control_mode:
            first = _head_jit(cfg, top, r["x"][control_mode][at], control_mode)[:n].argmax(-1)
            control.append(np.asarray(best - ref[np.arange(n), first]))
    out = {"served": np.concatenate(served)}
    if control_mode:
        out["control"] = np.concatenate(control)
    return out

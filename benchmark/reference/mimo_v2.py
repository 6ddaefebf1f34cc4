"""Plain reference for MiMo-V2.5's language model (``model_type: mimo_v2``,
https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json): the
forward pass in straightforward float32 ``jax.numpy`` at matmul precision
"highest". No cache, no kernels, no batching (one sequence at a time), no
scan; it imports nothing of ``deeplearning4j_tpu``.

Layer ``l`` of kind ``hybrid_layer_pattern[l]`` (0 full, 1 window) and
``moe_layer_freq[l]`` (0 dense MLP, 1 experts):

    h = x + Attn_l(RMSNorm(x; g1));   y = h + FFN_l(RMSNorm(h; g2))

as ``layer`` below writes them out. What is NOT in the published config
and was set here (the configuration file lists each under ``assumed``):

- the vision and audio towers and the multi-token-prediction layers are
  not built (the catalog's ``config`` holds the language model only);
- ``attention_value_scale`` multiplies the values before the weighted sum
  (a reading of the key's name);
- the initialisation, and a router correction bias drawn with std 0.02.

The chip's share of a deployment: the router scores all
``published.n_routed_experts`` experts, the sum runs over the chosen
experts that are HELD (``n_routed_experts`` of them from
``deployment.experts_offset``), and the vocabulary is the held slice.

``mode`` selects the arithmetic of the matrix products that the
configuration states in bfloat16: ``"float32"`` is the reference,
``"int8"`` (both operands rounded to 127 levels of their largest
magnitude) the control that ``correct`` has to refuse. The router's
product is stated in float32 and stays there in both.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


# -- the configuration, read ---------------------------------------------------
def n_layers(cfg):
    return cfg["num_hidden_layers"]


def is_window(cfg, layer):
    return cfg["hybrid_layer_pattern"][layer] == 1


def is_dense(cfg, layer):
    return cfg["moe_layer_freq"][layer] == 0


def kv_heads(cfg, layer):
    return cfg["swa_num_key_value_heads"] if is_window(cfg, layer) else cfg["num_key_value_heads"]


def has_sink(cfg, layer):
    return cfg["add_swa_attention_sink_bias" if is_window(cfg, layer)
               else "add_full_attention_sink_bias"]


def rotary_dim(cfg):
    """``partial_rotary_factor`` x head size, rounded down to an even number."""
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2


def router_width(cfg):
    return cfg["published"]["n_routed_experts"]


def experts_held(cfg):
    return cfg["deployment"]["experts_offset"], cfg["n_routed_experts"]


# -- weights -------------------------------------------------------------------
def layer_shapes(cfg, layer):
    """Leaf name -> (shape, std, mean) of one layer. Expert leaves lead
    with the experts held here."""
    d, hq, hd, vd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    hkv = kv_heads(cfg, layer)
    res = 0.02 / math.sqrt(2 * cfg["published"]["num_hidden_layers"])
    out = {"norm1": ((d,), 0.02, 1.0), "norm2": ((d,), 0.02, 1.0),
           "attn.q": ((d, hq * hd), 0.02, 0.0), "attn.k": ((d, hkv * hd), 0.02, 0.0),
           "attn.v": ((d, hkv * vd), 0.02, 0.0), "attn.o": ((hq * vd, d), res, 0.0)}
    if has_sink(cfg, layer):
        out["attn.sink"] = ((hq,), 0.5, 0.0)
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
    return out


TOP_LEAVES = ("embed", "norm_f", "head")
LAYER_LEAVES = ("norm1", "norm2", "attn.q", "attn.k", "attn.v", "attn.o", "attn.sink",
                "mlp.gate", "mlp.up", "mlp.down", "router.w", "router.bias",
                "experts.gate", "experts.up", "experts.down")
FLOAT32_LEAVES = ("norm1", "norm2", "norm_f", "attn.sink", "router.w", "router.bias")


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


def rotate(x, theta, rot):
    """Rotary positions (``rope_type: default``, half-split pairing) on the
    first ``rot`` dimensions of each head of x (T, heads, hd)."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def attention(cfg, layer, w, x, mode):
    """x (T, d) -> (T, d); no biases anywhere."""
    t = x.shape[0]
    hq, hd, vd = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    hkv, window = kv_heads(cfg, layer), is_window(cfg, layer)
    theta = cfg["swa_rope_theta"] if window else cfg["rope_theta"]
    q = rotate(prod(x, w["attn.q"], mode).reshape(t, hq, hd), theta, rotary_dim(cfg))
    k = rotate(prod(x, w["attn.k"], mode).reshape(t, hkv, hd), theta, rotary_dim(cfg))
    # assumed: attention_value_scale multiplies v before the weighted sum
    v = prod(x, w["attn.v"], mode).reshape(t, hkv, vd) * cfg["attention_value_scale"]
    # query head i reads key/value head i // (hq / hkv)
    k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
    s = prod(q.transpose(1, 0, 2), k.transpose(1, 2, 0), mode) / math.sqrt(hd)  # (hq, T, T)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window:
        seen &= (i - j) < cfg["sliding_window"]
    s = jnp.where(seen[None], s, -jnp.inf)
    if has_sink(cfg, layer):
        # one learned logit a head joins the softmax and carries no value
        s = jnp.concatenate([s, jnp.broadcast_to(w["attn.sink"][:, None, None], (hq, t, 1))], -1)
        p = jax.nn.softmax(s, axis=-1)[..., :-1]
    else:
        p = jax.nn.softmax(s, axis=-1)
    o = prod(p, v.transpose(1, 0, 2), mode).transpose(1, 0, 2).reshape(t, hq * vd)
    return prod(o, w["attn.o"], mode)


def route(cfg, w, x):
    """(T, router width) weights: w_e over the chosen 8, 0 elsewhere.
    Sigmoid scores; the choice by score + correction bias (noaux_tc, one
    group); weights the chosen scores renormalised; scaling factor 1."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router.w"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(s + w["router.bias"], cfg["num_experts_per_tok"])
    picked = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], chosen].set(1.0)
    return s * picked / jnp.sum(s * picked, -1, keepdims=True)


def experts(cfg, w, x, mode):
    """The held experts' part of sum_{e in S(x)} w_e SwiGLU_e(x), each
    held expert applied to every token and weighted (0 where not chosen)."""
    offset, held = experts_held(cfg)
    weights = route(cfg, w, x)
    y = jnp.zeros_like(x)
    for e in range(held):
        hidden = jax.nn.silu(prod(x, w["experts.gate"][e], mode)) * prod(x, w["experts.up"][e], mode)
        y = y + weights[:, offset + e, None] * prod(hidden, w["experts.down"][e], mode)
    return y


def layer(cfg, index, w, x, mode="float32"):
    eps = cfg["layernorm_epsilon"]
    h = x + attention(cfg, index, w, rms_norm(x, w["norm1"], eps), mode)
    m = rms_norm(h, w["norm2"], eps)
    if is_dense(cfg, index):
        return h + prod(jax.nn.silu(prod(m, w["mlp.gate"], mode)) * prod(m, w["mlp.up"], mode),
                        w["mlp.down"], mode)
    return h + experts(cfg, w, m, mode)


def head_logits(cfg, top, x, mode="float32"):
    return prod(rms_norm(x, top["norm_f"], cfg["layernorm_epsilon"]), top["head"], mode)


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
    every sample, and dropped (a float32 layer is 2 GB, the model 21.7).
    With ``control_mode`` also the same gap for the token a pass in that
    mode puts first. Rows are padded at the end to ``pad_to`` positions
    (causal attention: padding after a row's end cannot reach it), so
    every call has one shape. Returns arrays over all served tokens."""
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

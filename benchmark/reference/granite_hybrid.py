"""Plain reference for Granite-4.0-H (``model_type: granitemoehybrid``,
https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json):
the forward pass in straightforward float32 ``jax.numpy`` at matmul precision
"highest". The state-space layers run as a SEQUENTIAL ``lax.scan`` over time,
one position a step (never the chunked dual form the program prefills with,
nor its one-step cached form: the two sides meet from different ends). No
cache, no kernels, no batching (one sequence at a time); it imports nothing
of ``deeplearning4j_tpu``.

With d = ``hidden_size``, r = ``residual_multiplier`` and ``g*`` norm gains:

    x = embedding_multiplier * E[ids]
    h = x + r * Mixer_l(RMSNorm(x; g1));   y = h + r * (Experts_l(m) + Shared_l(m)),  m = RMSNorm(h; g2)
    logits = RMSNorm(x; g_f) E^T / logits_scaling          (tie_word_embeddings: one matrix)

Mixer of a ``"mamba"`` layer (Mamba-2; H = ``mamba_n_heads`` heads of P =
``mamba_d_head``, inner width I = H P = ``mamba_expand`` d, state N =
``mamba_d_state``, G = ``mamba_n_groups``, convolved channels C = I + 2 G N,
K = ``mamba_d_conv``), for ``a = RMSNorm(x)``:

    [z | xBC | dt] = a W_in                                  (I | C | H)
    xBC'_t = silu(b + sum_{j<K} w_j * xBC_{t-K+1+j})          (zeros before the sequence)
    [x | B | C] = xBC'  (I | G N | G N);   dt_t = softplus(dt_t + dt_bias);   A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t;   y_t = h_t C_t + D x_t     (per head; B, C of the head's group)
    out = RMSNorm(y * silu(z); g_n) W_out                     (the norm over a group's I / G channels)

Mixer of an ``"attention"`` layer: ``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of d / heads, no bias, NO positions
(``position_embedding_type: nope``), scores times ``attention_multiplier``
(not 1/sqrt(head)), causal softmax, ``W_o``.

Experts (every layer): ``z = m W_r`` over ``published.num_local_experts``,
the ``num_experts_per_tok`` largest z, weights softmax over THOSE z; expert e
is ``(silu(m G_e) * (m U_e)) D_e`` of width ``intermediate_size``; the shared
expert the same form of width ``shared_intermediate_size``, every token.

What is NOT in the published config and was set here (the configuration
file lists each under ``assumed``): the width of one expert is read from
``intermediate_size``; no ``time_step_limit`` is applied to dt; the
initialisation (Mamba-2's published defaults for A, dt and D; the embedding
at 0.02 / ``embedding_multiplier``; no depth scaling of the branches' output
projections, which ``residual_multiplier`` damps).

The chip's share of a deployment: the router scores all
``published.num_local_experts`` experts, the sum runs over the chosen experts
that are HELD (``num_local_experts`` of them from
``deployment.experts_offset``), the mixers and the shared expert are whole,
and the vocabulary is the held slice.

``mode`` selects the arithmetic of the matrix products that the configuration
states in bfloat16: ``"float32"`` is the reference, ``"int8"`` (both operands
rounded to 127 levels of their largest magnitude) the control that ``correct``
has to refuse. The router's product and the recurrence are stated in float32
and stay there in both.
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


def is_ssm(cfg, layer):
    kind = cfg["layer_types"][layer]
    if kind not in ("mamba", "attention"):
        raise ValueError(f"layer type {kind!r} is not built")
    return kind == "mamba"


def router_width(cfg):
    return cfg["published"]["num_local_experts"]


def experts_held(cfg):
    return cfg["deployment"]["experts_offset"], cfg["num_local_experts"]


def ssm_dims(cfg):
    """(heads, head size, state size, groups, inner width, convolved channels, d_conv)."""
    h, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
                  cfg["mamba_n_groups"])
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    if inner != h * p:
        raise ValueError("mamba_expand x hidden_size must be mamba_n_heads x mamba_d_head")
    return h, p, n, g, inner, inner + 2 * g * n, cfg["mamba_d_conv"]


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# -- weights -------------------------------------------------------------------
def layer_shapes(cfg, layer):
    """Leaf name -> (shape, how it is drawn) of one layer, in the published
    layouts (``mamba.in_proj``: [z | xBC | dt]). Expert leaves lead with the
    experts held here. Draws: ("normal", std, mean), ("uniform", lo, hi),
    ("a_log",), ("dt_bias",), ("ones",)."""
    d = cfg["hidden_size"]
    # assumed: the residual branches' output projections at 0.02 like every
    # matrix; residual_multiplier is the architecture's own damping of them
    normal, gain = ("normal", 0.02, 0.0), ("normal", 0.02, 1.0)
    out = {"norm1": ((d,), gain), "norm2": ((d,), gain)}
    if is_ssm(cfg, layer):
        h, _p, _n, _g, inner, conv, k = ssm_dims(cfg)
        out.update({"mamba.in_proj": ((d, inner + conv + h), normal),
                    "mamba.conv_w": ((conv, k), ("uniform", -0.5, 0.5)),
                    "mamba.conv_b": ((conv,), normal),
                    "mamba.dt_bias": ((h,), ("dt_bias",)),
                    "mamba.A_log": ((h,), ("a_log",)),
                    "mamba.D": ((h,), ("ones",)),
                    "mamba.norm": ((inner,), gain),
                    "mamba.out_proj": ((inner, d), normal)})
    else:
        hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
        out.update({"attn.q": ((d, hq * hd), normal), "attn.k": ((d, hkv * hd), normal),
                    "attn.v": ((d, hkv * hd), normal),
                    "attn.o": ((hq * hd, d), normal)})
    f, held, fs = cfg["intermediate_size"], cfg["num_local_experts"], cfg["shared_intermediate_size"]
    out.update({"router.w": ((d, router_width(cfg)), normal),
                "experts.gate": ((held, d, f), normal), "experts.up": ((held, d, f), normal),
                "experts.down": ((held, f, d), normal),
                "shared.gate": ((d, fs), normal), "shared.up": ((d, fs), normal),
                "shared.down": ((fs, d), normal)})
    return out


TOP_LEAVES = ("embed", "norm_f")
LAYER_LEAVES = ("norm1", "norm2", "mamba.in_proj", "mamba.conv_w", "mamba.conv_b",
                "mamba.dt_bias", "mamba.A_log", "mamba.D", "mamba.norm", "mamba.out_proj",
                "attn.q", "attn.k", "attn.v", "attn.o", "router.w", "experts.gate",
                "experts.up", "experts.down", "shared.gate", "shared.up", "shared.down")
FLOAT32_LEAVES = ("norm1", "norm2", "norm_f", "mamba.norm", "mamba.dt_bias", "mamba.A_log",
                  "mamba.D", "router.w")


def _sample(key, shape, how):
    """assumed: Mamba-2's published defaults make the dynamics real: A
    uniform in [1, 16] (``A_log`` its log), dt log-uniform in [1e-3, 1e-1]
    (``dt_bias`` its inverse softplus), D = 1."""
    if how[0] == "normal":
        return how[2] + how[1] * jax.random.normal(key, shape, jnp.float32)
    if how[0] == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, how[1], how[2])
    if how[0] == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if how[0] == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if how[0] == "ones":
        return jnp.ones(shape, jnp.float32)
    raise ValueError(f"unknown draw {how!r}")


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, how, round_bf16):
    x = _sample(key, shape, how)
    return x.astype(jnp.bfloat16).astype(jnp.float32) if round_bf16 else x


@functools.partial(jax.jit, static_argnums=(2, 3))
def _draw_experts(key, ids, shape, how):
    """One key an EXPERT (by its published index), so that a chip's share
    holds the same experts whichever experts its neighbours hold."""
    x = jax.vmap(lambda e: _sample(jax.random.fold_in(key, e), shape, how))(ids)
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
        # assumed: the embedding at 0.02 / embedding_multiplier, so that the
        # multiplied embedding enters the stream at the other matrices' scale
        # (drawn at 0.02 and read back by the tied head, every position's
        # best logit would be its own input token, whatever the layers compute)
        shape, how = {"embed": ((vocab, d), ("normal", 0.02 / cfg["embedding_multiplier"], 0.0)),
                      "norm_f": ((d,), ("normal", 0.02, 1.0))}[name]
        return _draw(key, shape, how, round_bf16)
    shape, how = layer_shapes(cfg, layer)[name]
    if name.startswith("experts."):
        offset, held = experts_held(cfg)
        return _draw_experts(key, jnp.arange(offset, offset + held), shape[1:], how)
    return _draw(key, shape, how, round_bf16)


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


def mamba(cfg, w, x, mode):
    """x (T, d) -> (T, d): the Mamba-2 mixer, the recurrence one position
    at a time. assumed: no ``time_step_limit`` (the config gives none)."""
    t = x.shape[0]
    h, p, n, g, inner, conv, k = ssm_dims(cfg)
    proj = prod(x, w["mamba.in_proj"], mode)
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + conv], proj[:, inner + conv:]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))               # zeros before the sequence
    u = w["mamba.conv_b"] + sum(padded[j:j + t] * w["mamba.conv_w"][:, j] for j in range(k))
    u = jax.nn.silu(u)
    xs = u[:, :inner].reshape(t, h, p)
    # a head reads the B and C of its group
    bm = jnp.repeat(u[:, inner:inner + g * n].reshape(t, g, n), h // g, axis=1)   # (T, H, N)
    cm = jnp.repeat(u[:, inner + g * n:].reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])             # (T, H)
    a = -jnp.exp(w["mamba.A_log"])                            # (H,)

    def step(state, now):
        x_t, b_t, c_t, dt_t = now
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32), (xs, bm, cm, dt))
    y = y + w["mamba.D"][:, None] * xs
    gated = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    gated = rms_norm(gated, w["mamba.norm"].reshape(g, inner // g), cfg["rms_norm_eps"])
    return prod(gated.reshape(t, inner), w["mamba.out_proj"], mode)


def attention(cfg, w, x, mode):
    """x (T, d) -> (T, d): grouped-query attention with no positions and
    the configuration's own scale; no biases anywhere."""
    if cfg["position_embedding_type"] != "nope":
        raise ValueError(f"position_embedding_type {cfg['position_embedding_type']!r} is not built")
    t = x.shape[0]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q = prod(x, w["attn.q"], mode).reshape(t, hq, hd)
    # query head i reads key/value head i // (hq / hkv)
    k = jnp.repeat(prod(x, w["attn.k"], mode).reshape(t, hkv, hd), hq // hkv, axis=1)
    v = jnp.repeat(prod(x, w["attn.v"], mode).reshape(t, hkv, hd), hq // hkv, axis=1)
    k, v = k.transpose(1, 2, 0), v.transpose(1, 0, 2)         # (h, hd, T), (h, T, hd)
    pad = -t % QUERY_BLOCK
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_BLOCK, hq, hd)
    starts = jnp.arange(blocks.shape[0]) * QUERY_BLOCK

    def one_block(args):
        qb, start = args
        s = prod(qb.transpose(1, 0, 2), k, mode) * cfg["attention_multiplier"]   # (h, block, T)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return prod(pr, v, mode).transpose(1, 0, 2)                                # (block, h, hd)

    o = jax.lax.map(one_block, (blocks, starts)).reshape(-1, hq * hd)[:t]
    return prod(o, w["attn.o"], mode)


def route(cfg, w, x):
    """(T, router width) weights: softmax over the ``num_experts_per_tok``
    largest router outputs, 0 elsewhere."""
    z = jnp.matmul(x, w["router.w"], precision=HIGHEST)
    top, chosen = jax.lax.top_k(z, cfg["num_experts_per_tok"])
    return jnp.zeros_like(z).at[jnp.arange(z.shape[0])[:, None], chosen].set(
        jax.nn.softmax(top, axis=-1))


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


def shared(cfg, w, x, mode):
    return swiglu(x, w["shared.gate"], w["shared.up"], w["shared.down"], mode)


def experts(cfg, w, x, mode):
    """The expert layer as this chip computes it: its held experts' part
    and the shared expert, which every chip of the layer computes alike."""
    return routed(cfg, w, x, mode) + shared(cfg, w, x, mode)


def layer(cfg, index, w, x, mode="float32"):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = mamba if is_ssm(cfg, index) else attention
    h = x + r * mixer(cfg, w, rms_norm(x, w["norm1"], eps), mode)
    return h + r * experts(cfg, w, rms_norm(h, w["norm2"], eps), mode)


def embed(cfg, top, ids):
    return cfg["embedding_multiplier"] * top["embed"][jnp.asarray(ids, jnp.int32)]


def head_logits(cfg, top, x, mode="float32"):
    """tie_word_embeddings: the head is the embedding, transposed."""
    if not cfg["tie_word_embeddings"]:
        raise ValueError("an untied head is not built")
    return prod(rms_norm(x, top["norm_f"], cfg["rms_norm_eps"]), top["embed"].T,
                mode) / cfg["logits_scaling"]


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
    x = embed(cfg, top, ids)
    for i in range(n_layers(cfg)):
        w = weights[i] if weights else make_layer(cfg, seed, i)
        x = _layer_jit(cfg, i, w, x, mode)
    return _head_jit(cfg, top, x, mode)


def served_token_gaps(cfg, seed, samples, pad_to, answers_pad, mode="float32", control_mode=None):
    """For served requests (dicts with ``prompt`` and ``tokens``): at each
    position where the program produced a token, how far that token's
    logit lies below the reference's best, from one forward pass over
    prompt + tokens, IN BLOCKS: one layer's weights are made, applied to
    every sample, and dropped (a float32 layer is 1.8 GB). With
    ``control_mode`` also the same gap for the token a pass in that mode
    puts first. Rows are padded at the end to ``pad_to`` positions (both
    mixers are causal: padding after a row's end cannot reach it), so every
    call has one shape. Returns arrays over all served tokens."""
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
        rows.append({"x": {m: embed(cfg, top, seq) for m in modes},
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

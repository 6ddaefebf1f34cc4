"""Plain reference for Falcon-H1 (``model_type: falcon_h1``,
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json):
the forward pass in straightforward float32 ``jax.numpy`` at matmul precision
"highest". Every block has an attention AND a Mamba-2 mixer SIDE BY SIDE: both
read the same normed input and their outputs are added into the residual; the
recurrence runs as a SEQUENTIAL ``lax.scan`` over time, one position a step
(never the chunked dual form the program prefills with, nor its one-step
cached form: the two sides meet from different ends). No cache, no kernels, no
batching (one sequence at a time); it imports nothing of
``deeplearning4j_tpu``.

With d = ``hidden_size`` and ``g*`` norm gains, a block on the stream x:

    h = RMSNorm(x; g_in)                                         ONE norm for both branches
    a = h * attention_in_multiplier
    q = a W_q;  k = (a W_k) * key_multiplier;  v = a W_v         (H_q | H_kv | H_kv heads of head_dim; no biases)
    q, k rotated over the whole head (half-split pairing, ``rope_theta``, no scaling)
    A = (softmax(q k^T / sqrt(head_dim), causal) v  W_o) * attention_out_multiplier
    s = h * ssm_in_multiplier
    [z | x' | B | C | dt] = (s W_in) * mup                       (I | I | G N | G N | H; mup = ssm_multipliers[0..4] spread over them)
    [x' | B | C]_t = silu(b + sum_{j<K} w_j * [x' | B | C]_{t-K+1+j})     (zeros before the sequence)
    dt_t = softplus(dt_t + dt_bias);   a_h = -exp(A_log)
    H_t = exp(dt_t a_h) H_{t-1} + dt_t x'_t (outer) B_t;   y_t = H_t C_t + D x'_t     (per head; B, C of the head's group)
    S = (RMSNorm over each group's I / G channels of (y * silu(z)); g_n) W_out * ssm_out_multiplier
    x = x + (S + A)
    m = RMSNorm(x; g_ff)
    x = x + ((silu((m W_g) * mlp_multipliers[0]) * (m W_u)) W_d) * mlp_multipliers[1]

(H = ``mamba_n_heads`` heads of P = ``mamba_d_head``, inner width I =
``mamba_d_ssm`` = H P, which is NOT ``mamba_expand`` x d here; state N =
``mamba_d_state``, G = ``mamba_n_groups``, K = ``mamba_d_conv``.) The stream
starts as ``E[ids] * embedding_multiplier``; after the last block the final
RMSNorm, then ``logits = (h W_head) * lm_head_multiplier``; the head is not
tied. Every multiplier is applied where the family's published modelling code
applies it and none is folded into a weight.

Written from memory of ``transformers``' ``modeling_falcon_h1.py`` (the file
is not on this machine). What the published keys do not settle was set here,
and the configuration file lists each under ``assumed``: the gated norm runs
by GROUP (``mamba_rms_norm`` true, ``mamba_norm_before_gate`` false: gate,
then norm); ``mamba_use_mlp`` true is read as "the block has its feed-forward
half"; ``projectors_bias`` / ``attention_bias`` / ``mlp_bias`` false mean no
bias anywhere but the convolution's (``mamba_conv_bias`` true); no
``time_step_limit`` is applied to dt; the initialisation: every matrix
normal(0, ``init_std[leaf]``) with the standard deviations of the
configuration file (chosen so that, at the seeded weights, each of the three
branches adds a tenth to a half of the stream's RMS: the published multipliers
are small because trained weights are large), the mixer's scalars at Mamba-2's
published defaults (A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1], D =
1, convolution weights uniform in +-1/2).

``mode`` selects the arithmetic of the matrix products that the configuration
states in bfloat16: ``"float32"`` is the reference, ``"int8"`` (both operands
rounded to 127 levels of their largest magnitude) the control that ``correct``
has to refuse. The recurrence is stated in float32 and stays there in both.
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
#: positions whose logits over the whole vocabulary are made at a time
#: (512 x 261,120 float32 are 0.53 GB beside a 5.35 GB head)
HEAD_BLOCK = 512


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


# -- the configuration, read ---------------------------------------------------
#: what the published keys must say for this block to be the model's
BUILT = {"hidden_act": "silu", "attention_bias": False, "mlp_bias": False,
         "projectors_bias": False, "mamba_proj_bias": False, "mamba_conv_bias": True,
         "mamba_rms_norm": True, "mamba_norm_before_gate": False, "mamba_use_mlp": True,
         "rope_scaling": None, "tie_word_embeddings": False, "attn_layer_indices": None}


def check(cfg):
    for key, value in BUILT.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]!r} is not built")


def n_layers(cfg):
    return cfg["num_hidden_layers"]


def ssm_dims(cfg):
    """(heads, head size, state size, groups, inner width, convolved channels, d_conv)."""
    h, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
                  cfg["mamba_n_groups"])
    inner = cfg["mamba_d_ssm"] or cfg["mamba_expand"] * cfg["hidden_size"]
    if inner != h * p or h % g:
        raise ValueError("mamba_d_ssm must be mamba_n_heads x mamba_d_head, groups dividing heads")
    return h, p, n, g, inner, inner + 2 * g * n, cfg["mamba_d_conv"]


def mup_vector(cfg):
    """``ssm_multipliers`` spread over the input projection's columns
    [z | x | B | C | dt]."""
    h, _p, n, g, inner, _conv, _k = ssm_dims(cfg)
    return np.repeat(np.asarray(cfg["ssm_multipliers"], np.float32),
                     [inner, inner, g * n, g * n, h])


# -- weights -------------------------------------------------------------------
LAYER_LEAVES = ("norm1", "norm2", "attn.q", "attn.k", "attn.v", "attn.o", "mamba.in_proj",
                "mamba.conv_w", "mamba.conv_b", "mamba.dt_bias", "mamba.A_log", "mamba.D",
                "mamba.norm", "mamba.out_proj", "mlp.gate", "mlp.up", "mlp.down")
TOP_LEAVES = ("embed", "norm_f", "head")
FLOAT32_LEAVES = ("norm1", "norm2", "norm_f", "mamba.norm", "mamba.dt_bias", "mamba.A_log",
                  "mamba.D")


def shapes(cfg):
    """Leaf name -> (shape, how it is drawn), the layers' leaves (all layers
    alike) and the top ones, in the published layouts (``mamba.in_proj``:
    [z | x | B | C | dt]). Draws: ("normal", std, mean), ("uniform", lo, hi),
    ("a_log",), ("dt_bias",), ("ones",). assumed: each matrix's standard
    deviation is the configuration file's ``init_std`` of that leaf."""
    d, f, vocab = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h, _p, _n, _g, inner, conv, k = ssm_dims(cfg)
    gain = ("normal", 0.02, 1.0)
    matrices = {"attn.q": (d, hq * hd), "attn.k": (d, hkv * hd), "attn.v": (d, hkv * hd),
                "attn.o": (hq * hd, d), "mamba.in_proj": (d, inner + conv + h),
                "mamba.conv_b": (conv,), "mamba.out_proj": (inner, d),
                "mlp.gate": (d, f), "mlp.up": (d, f), "mlp.down": (f, d),
                "embed": (vocab, d), "head": (d, vocab)}
    out = {name: (shape, ("normal", cfg["init_std"][name], 0.0))
           for name, shape in matrices.items()}
    out.update({"norm1": ((d,), gain), "norm2": ((d,), gain), "norm_f": ((d,), gain),
                "mamba.norm": ((inner,), gain),
                "mamba.conv_w": ((conv, k), ("uniform", -0.5, 0.5)),
                "mamba.dt_bias": ((h,), ("dt_bias",)), "mamba.A_log": ((h,), ("a_log",)),
                "mamba.D": ((h,), ("ones",))})
    return out


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
def _draw(key, shape, how, dtype):
    return _sample(key, shape, how).astype(dtype)


def make_leaf(cfg, seed, layer, name, stored=False):
    """One leaf of one layer (``layer`` -1: the top leaves), from a key of
    its own, as float32. Leaves that the configuration stores in bfloat16
    are rounded to it, so that program and reference hold the same values
    and the comparison reads arithmetic; with ``stored`` such a leaf comes
    back AS bfloat16, the same values at half the bytes (the embedding and
    the head are 5.35 GB each in float32)."""
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), layer + 1),
                             (TOP_LEAVES + LAYER_LEAVES).index(name))
    shape, how = shapes(cfg)[name]
    if name in FLOAT32_LEAVES:
        return _draw(key, shape, how, jnp.float32)
    leaf = _draw(key, shape, how, jnp.bfloat16)
    return leaf if stored else leaf.astype(jnp.float32)


def make_layer(cfg, seed, layer):
    return {name: make_leaf(cfg, seed, layer, name) for name in LAYER_LEAVES}


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


def rotate(x, theta):
    """Rotary positions over the whole head, half-split pairing (dimension
    i turns with i + head/2); x (T, heads, head) at positions 0..T-1."""
    t, _h, hd = x.shape
    inv = float(theta) ** (-jnp.arange(hd // 2, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, w, h, mode):
    """The attention branch on the normed input h (T, d) -> (T, d):
    grouped-query attention, rotary over the whole head, the key and the two
    branch multipliers where the published code has them."""
    t = h.shape[0]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    a = h * cfg["attention_in_multiplier"]
    q = rotate(prod(a, w["attn.q"], mode).reshape(t, hq, hd), cfg["rope_theta"])
    k = rotate((prod(a, w["attn.k"], mode) * cfg["key_multiplier"]).reshape(t, hkv, hd),
               cfg["rope_theta"])
    v = prod(a, w["attn.v"], mode).reshape(t, hkv, hd)
    # query head i reads key/value head i // (hq / hkv)
    k = jnp.repeat(k, hq // hkv, axis=1).transpose(1, 2, 0)   # (h, hd, T)
    v = jnp.repeat(v, hq // hkv, axis=1).transpose(1, 0, 2)   # (h, T, hd)
    pad = -t % QUERY_BLOCK
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_BLOCK, hq, hd)
    starts = jnp.arange(blocks.shape[0]) * QUERY_BLOCK

    def one_block(args):
        qb, start = args
        s = prod(qb.transpose(1, 0, 2), k, mode) / math.sqrt(hd)        # (h, block, T)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return prod(pr, v, mode).transpose(1, 0, 2)                      # (block, h, hd)

    o = jax.lax.map(one_block, (blocks, starts)).reshape(-1, hq * hd)[:t]
    return prod(o, w["attn.o"], mode) * cfg["attention_out_multiplier"]


def mamba(cfg, w, h_in, mode, norm_by_group=True):
    """The state-space branch on the normed input h_in (T, d) -> (T, d): the
    Mamba-2 mixer, the recurrence one position at a time. assumed: no
    ``time_step_limit``; the gated norm by group (``norm_by_group`` False,
    over all channels at once, is what a test holds the limits against)."""
    t = h_in.shape[0]
    h, p, n, g, inner, conv, k = ssm_dims(cfg)
    proj = prod(h_in * cfg["ssm_in_multiplier"], w["mamba.in_proj"], mode) * mup_vector(cfg)
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
    groups = g if norm_by_group else 1
    gated = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    gated = rms_norm(gated, w["mamba.norm"].reshape(groups, inner // groups),
                     cfg["rms_norm_eps"])
    return prod(gated.reshape(t, inner), w["mamba.out_proj"], mode) * cfg["ssm_out_multiplier"]


def mlp(cfg, w, m, mode):
    gate, down = cfg["mlp_multipliers"]
    hidden = jax.nn.silu(prod(m, w["mlp.gate"], mode) * gate) * prod(m, w["mlp.up"], mode)
    return prod(hidden, w["mlp.down"], mode) * down


def branches(cfg, w, x, mode="float32"):
    """What one block adds to the stream x (T, d), branch by branch: (A, S,
    M), the feed-forward's M of the stream after A and S joined it."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, w["norm1"], eps)
    a, s = attention(cfg, w, h, mode), mamba(cfg, w, h, mode)
    return a, s, mlp(cfg, w, rms_norm(x + (s + a), w["norm2"], eps), mode)


def layer(cfg, w, x, mode="float32"):
    a, s, m = branches(cfg, w, x, mode)
    return (x + (s + a)) + m


def embed(cfg, table, ids):
    return cfg["embedding_multiplier"] * table[jnp.asarray(ids, jnp.int32)].astype(jnp.float32)


def head_logits(cfg, norm_f, head, x, mode="float32"):
    return prod(rms_norm(x, norm_f, cfg["rms_norm_eps"]), head, mode) * cfg["lm_head_multiplier"]


class _Frozen(dict):
    """A configuration as a static jit argument (hashed by its content)."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_jit(cfg, w, x, mode):
    return layer(cfg, w, x, mode)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head_jit(cfg, norm_f, head, x, mode):
    return head_logits(cfg, norm_f, head, x, mode)


def logits(cfg, seed, ids, mode="float32", weights=None):
    """Logits (T, V) of one sequence ids (T,), a layer at a time; each
    layer's weights are made, applied and dropped. ``weights`` (a list of
    layers, then {"embed", "norm_f", "head"}) replaces the generator."""
    cfg = _Frozen(cfg)
    check(cfg)
    top = weights[-1] if weights else None
    x = embed(cfg, top["embed"] if top else make_leaf(cfg, seed, -1, "embed", stored=True), ids)
    for i in range(n_layers(cfg)):
        x = _layer_jit(cfg, weights[i] if weights else make_layer(cfg, seed, i), x, mode)
    norm_f, head = ((top["norm_f"], top["head"]) if top else
                    (make_leaf(cfg, seed, -1, "norm_f"), make_leaf(cfg, seed, -1, "head")))
    return _head_jit(cfg, norm_f, head, x, mode)


def branch_shares(cfg, seed, ids):
    """Per layer, the RMS of what each branch (A, S, M) adds over the RMS of
    the stream it is added to: what the initialisation is held to (each
    between a tenth and a half)."""
    cfg = _Frozen(cfg)
    x = embed(cfg, make_leaf(cfg, seed, -1, "embed", stored=True), ids)
    rms = lambda v: float(jnp.sqrt(jnp.mean(v * v)))  # noqa: E731
    one_layer = jax.jit(branches, static_argnums=(0, 3))
    out = []
    for i in range(n_layers(cfg)):
        a, s, m = one_layer(cfg, make_layer(cfg, seed, i), x, "float32")
        out.append({"A": rms(a) / rms(x), "S": rms(s) / rms(x), "M": rms(m) / rms(x + (s + a))})
        x = (x + (s + a)) + m
    return out


def served_token_gaps(cfg, seed, samples, pad_to, answers_pad, mode="float32", control_mode=None):
    """For served requests (dicts with ``prompt`` and ``tokens``): at each
    position where the program produced a token, how far that token's
    logit lies below the reference's best, from one forward pass over
    prompt + tokens, IN BLOCKS: the embedding is made, read and dropped; one
    layer's weights are made, applied to every sample, and dropped (a float32
    layer is 1.7 GB); the head (5.35 GB in float32) is made last and the
    logits over the whole vocabulary ``HEAD_BLOCK`` positions at a time. With
    ``control_mode`` also the same gap for the token a pass in that mode puts
    first. Rows are padded at the end to ``pad_to`` positions (both mixers
    are causal: padding after a row's end cannot reach it), so every call has
    one shape. Returns arrays over all served tokens."""
    cfg = _Frozen(cfg)
    check(cfg)
    modes = [mode] + ([control_mode] if control_mode else [])
    table = make_leaf(cfg, seed, -1, "embed", stored=True)
    rows = []
    for s in samples:
        full = list(s["prompt"]) + list(s["tokens"])
        n, first = len(s["tokens"]), len(s["prompt"]) - 1
        if len(full) - 1 > pad_to or n > answers_pad:
            raise ValueError("a served request is longer than the padding")
        seq = np.zeros((pad_to,), np.int32)
        seq[: len(full) - 1] = full[:-1]
        rows.append({"x": {m: embed(cfg, table, seq) for m in modes},
                     "at": np.arange(first, first + n), "served": np.asarray(s["tokens"])})
    del table
    for i in range(n_layers(cfg)):
        w = make_layer(cfg, seed, i)
        for r in rows:
            r["x"] = {m: _layer_jit(cfg, w, x, m) for m, x in r["x"].items()}
        del w
    norm_f, head = make_leaf(cfg, seed, -1, "norm_f"), make_leaf(cfg, seed, -1, "head")
    served, control = [], []
    for r in rows:
        n = len(r["at"])
        for lo in range(0, n, HEAD_BLOCK):
            at = np.zeros((HEAD_BLOCK,), np.int32)
            part = r["at"][lo:lo + HEAD_BLOCK]
            at[: len(part)] = part
            k, tokens = len(part), r["served"][lo:lo + HEAD_BLOCK]
            ref = _head_jit(cfg, norm_f, head, r["x"][mode][at], mode)[:k]
            best = ref.max(-1)
            served.append(np.asarray(best - ref[np.arange(k), tokens]))
            if control_mode:
                first = _head_jit(cfg, norm_f, head, r["x"][control_mode][at],
                                  control_mode)[:k].argmax(-1)
                control.append(np.asarray(best - ref[np.arange(k), first]))
    out = {"served": np.concatenate(served)}
    if control_mode:
        out["control"] = np.concatenate(control)
    return out

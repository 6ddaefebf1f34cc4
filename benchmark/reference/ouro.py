"""Plain reference for Ouro's language model (``model_type: ouro``,
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json; the
LoopLM of "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): the forward pass in straightforward float32
``jax.numpy`` at matmul precision "highest". No cache, no kernels, no
batching (one sequence at a time), no scan; it imports nothing of
``deeplearning4j_tpu``.

For a token stream ``x = E[ids]`` (no embedding scale), passes r = 1..R
(``total_ut_steps``) and layers l = 1..L, ONE set of layer weights used by
every pass (``layer`` and ``close`` below write them out):

    a = RMSNorm(x; g1);  q, k, v = a Wq, a Wk, a Wv   (no bias)
    q, k = rope(q), rope(k)                            (whole head)
    o = softmax(q k^T / sqrt(head), causal) v          (this pass's keys)
    x = x + RMSNorm(o Wo; g2)                          (sandwich norm)
    m = RMSNorm(x; g3)
    x = x + RMSNorm((silu(m Wg) * (m Wu)) Wd; g4)
    after layer L:  h_r = RMSNorm(x; g_f);  x <- h_r;
                    lambda_r = sigmoid(h_r w_e + b_e)

    p_r = lambda_r prod_{j<r} (1 - lambda_j) for r < R, p_R the remainder;
    a token leaves at the first r whose cumulated p reaches
    ``early_exit_threshold``; logits = h_exit W_head. Published threshold
    1: every token takes all R passes.

Each (pass, layer) attends to the keys and values that THIS pass's stream
gives: a pass never reads another pass's. ``kv_from="first"`` is the
variant that does (passes 2..R attend to pass 1's keys and values, one
cache entry a layer shared by all passes): kept so that a test can show
the program does NOT compute it.

What is NOT in the published ``config.json`` and was set here (the
configuration file lists each under ``assumed`` with its origin):

- the sandwich norms (each sub-layer's output normed before it joins the
  residual: four gains a layer), that the final norm closes EVERY pass and
  the next starts from the normed stream, and the gate's shape (one output
  and a bias, read from the closed stream): from the model's published
  description and modelling code as ISSUE 42's writer knows them;
- ``attention_bias`` false and ``mlp_bias`` false (no bias anywhere);
- the initialisation: normal(0, 0.02) matrices (the output projections
  too: their outputs are normed, so their scale does not reach the
  stream), gains drawn around 1 (std 0.02), the gate's weight at
  1/sqrt(hidden) so that its pre-activation is of order 1 at any width,
  its bias 0.

``mode`` selects the arithmetic of the matrix products that the
configuration states in bfloat16: ``"float32"`` is the reference,
``"int8"`` (both operands rounded to 127 levels of their largest
magnitude) the control that ``correct`` has to refuse. The gate's product
is stated in float32 and stays there in both.
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


def n_passes(cfg):
    return cfg["total_ut_steps"]


# -- weights -------------------------------------------------------------------
def layer_shapes(cfg):
    """Leaf name -> (shape, std, mean) of one layer (every layer alike)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    out = {name: ((d,), 0.02, 1.0) for name in ("norm1", "norm1b", "norm2", "norm2b")}
    out.update({"attn.q": ((d, hq * hd), 0.02, 0.0), "attn.k": ((d, hkv * hd), 0.02, 0.0),
                "attn.v": ((d, hkv * hd), 0.02, 0.0), "attn.o": ((hq * hd, d), 0.02, 0.0),
                "mlp.gate": ((d, f), 0.02, 0.0), "mlp.up": ((d, f), 0.02, 0.0),
                "mlp.down": ((f, d), 0.02, 0.0)})
    return out


def top_shapes(cfg):
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": ((vocab, d), 0.02, 0.0), "norm_f": ((d,), 0.02, 1.0),
            "head": ((d, vocab), 0.02, 0.0), "gate.w": ((d,), 1.0 / math.sqrt(d), 0.0),
            "gate.b": ((), 0.0, 0.0)}


TOP_LEAVES = ("embed", "norm_f", "head", "gate.w", "gate.b")
LAYER_LEAVES = ("norm1", "norm1b", "norm2", "norm2b", "attn.q", "attn.k", "attn.v", "attn.o",
                "mlp.gate", "mlp.up", "mlp.down")
FLOAT32_LEAVES = ("norm1", "norm1b", "norm2", "norm2b", "norm_f", "gate.w", "gate.b")


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, std, mean, round_bf16):
    x = mean + std * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32) if round_bf16 else x


def make_leaf(cfg, seed, layer, name):
    """One leaf of one layer (``layer`` -1: the top leaves) as float32,
    from a key of its own. Leaves that the configuration stores in
    bfloat16 are rounded to it, so that program and reference hold the
    same values and the comparison reads arithmetic."""
    index = (TOP_LEAVES + LAYER_LEAVES).index(name)
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), layer + 1), index)
    shape, std, mean = (top_shapes(cfg) if layer < 0 else layer_shapes(cfg))[name]
    return _draw(key, shape, std, mean, name not in FLOAT32_LEAVES)


def make_layer(cfg, seed, layer):
    return {name: make_leaf(cfg, seed, layer, name) for name in LAYER_LEAVES}


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


def rotate(x, theta):
    """Rotary positions (``rope_scaling: null``, half-split pairing) over
    the whole head of x (T, heads, hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def keys_values(cfg, w, a, mode):
    """The rotated keys and the values of a normed stream a (T, d):
    (T, kv heads, hd) each."""
    t = a.shape[0]
    hkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    k = rotate(prod(a, w["attn.k"], mode).reshape(t, hkv, hd), cfg["rope_theta"])
    return k, prod(a, w["attn.v"], mode).reshape(t, hkv, hd)


def attention(cfg, w, a, mode, kv=None):
    """a (T, d), the normed stream -> ((T, d), the keys and values it
    attended to). ``kv``: another stream's, in their stead."""
    t = a.shape[0]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = rotate(prod(a, w["attn.q"], mode).reshape(t, hq, hd), cfg["rope_theta"])
    kv = keys_values(cfg, w, a, mode) if kv is None else kv
    # query head i reads key/value head i // (hq / hkv)
    k, v = (jnp.repeat(z, hq // hkv, axis=1) for z in kv)
    s = prod(q.transpose(1, 0, 2), k.transpose(1, 2, 0), mode) / math.sqrt(hd)  # (hq, T, T)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    o = prod(p, v.transpose(1, 0, 2), mode).transpose(1, 0, 2).reshape(t, hq * hd)
    return prod(o, w["attn.o"], mode), kv


def layer(cfg, w, x, mode="float32", kv=None):
    """One layer on x (T, d) -> (x, the keys and values it attended to)."""
    eps = cfg["rms_norm_eps"]
    o, kv = attention(cfg, w, rms_norm(x, w["norm1"], eps), mode, kv)
    x = x + rms_norm(o, w["norm1b"], eps)
    m = rms_norm(x, w["norm2"], eps)
    y = prod(jax.nn.silu(prod(m, w["mlp.gate"], mode)) * prod(m, w["mlp.up"], mode),
             w["mlp.down"], mode)
    return x + rms_norm(y, w["norm2b"], eps), kv


def close(cfg, top, x):
    """What closes a pass: (the normed stream h, the gate's probability of
    leaving here (T,))."""
    h = rms_norm(x, top["norm_f"], cfg["rms_norm_eps"])
    return h, jax.nn.sigmoid(jnp.sum(h * top["gate.w"], axis=-1) + top["gate.b"])


def exit_pass(leave, threshold):
    """leave (R, T), the gate's probabilities after each pass -> (T,) the
    pass (from 0) each token's logits are read from: the first at which
    the cumulated exit probability reaches ``threshold``; the last pass
    takes what the others left."""
    cumulated = 1.0 - jnp.cumprod(1.0 - leave, axis=0)
    cumulated = cumulated.at[-1].set(1.0)
    return jnp.argmax(cumulated >= threshold, axis=0)


class _Frozen(dict):
    """A configuration as a static jit argument (hashed by its content)."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_jit(cfg, w, x, mode, kv=None):
    return layer(cfg, w, x, mode, kv)


@functools.partial(jax.jit, static_argnums=(0,))
def _close_jit(cfg, top, x):
    return close(cfg, top, x)


@functools.partial(jax.jit, static_argnums=(2,))
def _head_jit(top, h, mode):
    return prod(h, top["head"], mode)


def passes(cfg, seed, ids, mode="float32", weights=None, kv_from="own"):
    """(the closed streams (R, T, d), the gate's probabilities (R, T)) of
    one sequence ids (T,), a layer at a time; each layer's weights are
    made, applied and dropped. ``weights`` (a list of layers, then the
    top) replaces the generator. ``kv_from="first"``: passes after the
    first attend to the first pass's keys and values (the module's
    docstring)."""
    cfg = _Frozen(cfg)
    top = weights[-1] if weights else make_top(cfg, seed)
    x = top["embed"][jnp.asarray(ids, jnp.int32)]
    streams, leaves, first = [], [], {}
    for r in range(n_passes(cfg)):
        for i in range(n_layers(cfg)):
            w = weights[i] if weights else make_layer(cfg, seed, i)
            x, kv = _layer_jit(cfg, w, x, mode, first.get(i) if kv_from == "first" else None)
            if kv_from == "first":
                first.setdefault(i, kv)
        x, leave = _close_jit(cfg, top, x)
        streams.append(x)
        leaves.append(leave)
    return jnp.stack(streams), jnp.stack(leaves)


def logits(cfg, seed, ids, mode="float32", weights=None, threshold=None, kv_from="own"):
    """Logits (T, V) of one sequence under the exit rule at ``threshold``
    (None: the configuration's ``early_exit_threshold``)."""
    top = weights[-1] if weights else make_top(cfg, seed)
    streams, leave = passes(cfg, seed, ids, mode, weights, kv_from)
    at = exit_pass(leave, cfg["early_exit_threshold"] if threshold is None else threshold)
    h = jnp.take_along_axis(streams, at[None, :, None], axis=0)[0]
    return _head_jit(top, h, mode)


def served_token_gaps(cfg, seed, samples, pad_to, answers_pad, mode="float32", control_mode=None):
    """For served requests (dicts with ``prompt`` and ``tokens``): at each
    position where the program produced a token, how far that token's
    logit lies below the reference's best, from one forward pass over
    prompt + tokens under the SERVED rule (every pass for every token:
    threshold 1), IN BLOCKS: one layer's float32 weights (0.2 GB of the
    10.7 GB) are made, applied to every sample, and dropped, every pass
    anew. With ``control_mode`` also the same gap for the token a pass in
    that mode puts first. Rows are padded at the end to ``pad_to``
    positions (causal attention: padding after a row's end cannot reach
    it), so every call has one shape. Returns arrays over all served
    tokens."""
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
    for _r in range(n_passes(cfg)):
        for i in range(n_layers(cfg)):
            w = make_layer(cfg, seed, i)
            for row in rows:
                row["x"] = {m: _layer_jit(cfg, w, x, m)[0] for m, x in row["x"].items()}
            del w
        for row in rows:
            row["x"] = {m: _close_jit(cfg, top, x)[0] for m, x in row["x"].items()}
    served, control = [], []
    for row in rows:
        at = np.zeros((answers_pad,), np.int32)
        at[: len(row["at"])] = row["at"]
        n = len(row["at"])
        ref = _head_jit(top, row["x"][mode][at], mode)[:n]
        best = ref.max(-1)
        served.append(np.asarray(best - ref[np.arange(n), row["served"]]))
        if control_mode:
            first = _head_jit(top, row["x"][control_mode][at], control_mode)[:n].argmax(-1)
            control.append(np.asarray(best - ref[np.arange(n), first]))
    out = {"served": np.concatenate(served)}
    if control_mode:
        out["control"] = np.concatenate(control)
    return out

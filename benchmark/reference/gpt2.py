"""Plain reference for GPT-2-shaped decoders: forward pass, loss, gradients
and Adam in straightforward float32 ``jax.numpy`` at matmul precision
"highest". No kernels, no cache, no scan; layer by layer and in blocks of
rows, so that it fits beside nothing else on one chip.

It imports nothing of ``deeplearning4j_tpu`` and takes nothing the program
made: ``make_weights`` draws the weights from the seed, the family module
hands the same values to the program.

Follows Radford et al. 2019 / ``openai-community/gpt2`` (pre-LN blocks,
learned positions, tanh GELU, MLP 4x) with the two departures of the
repo's block, listed under ``assumed`` in the configuration files: Q, K
and V have no bias, and the output head is its own matrix, not the
embedding transposed.

``mode`` selects the arithmetic of every matrix product: ``"float32"`` is
the reference; ``"int8"`` (both operands rounded to 127 levels of their
largest magnitude, forward and backward) is the control that ``correct``
has to refuse.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_LEAVES = ("ln_1.g", "ln_1.b", "attn.q", "attn.k", "attn.v", "attn.proj.w",
                "attn.proj.b", "ln_2.g", "ln_2.b", "mlp.fc.w", "mlp.fc.b",
                "mlp.proj.w", "mlp.proj.b")


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(cfg, seed):
    """Float32 weights from the seed in one jitted call on the device.
    GPT-2's initialisation (normal, std 0.02; output projections of the
    residual branches scaled by 1/sqrt(2 L)), except that gains and biases
    are drawn around 1 and 0 with std 0.02 instead of set there, so that
    no leaf is idle in a comparison."""
    d, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    h = cfg.get("mlp_ratio", 4) * d

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 32))

        def n(shape, std=0.02, mean=0.0):
            return mean + std * jax.random.normal(next(ks), shape, jnp.float32)

        res = 0.02 / math.sqrt(2 * layers)
        blocks = {
            "ln_1.g": n((layers, d), mean=1.0), "ln_1.b": n((layers, d)),
            "attn.q": n((layers, d, d)), "attn.k": n((layers, d, d)),
            "attn.v": n((layers, d, d)),
            "attn.proj.w": n((layers, d, d), res), "attn.proj.b": n((layers, d)),
            "ln_2.g": n((layers, d), mean=1.0), "ln_2.b": n((layers, d)),
            "mlp.fc.w": n((layers, d, h)), "mlp.fc.b": n((layers, h)),
            "mlp.proj.w": n((layers, h, d), res), "mlp.proj.b": n((layers, d)),
        }
        return {"wte": n((vocab, d)), "wpe": n((cfg["n_positions"], d), 0.01),
                "blocks": blocks, "ln_f.g": n((d,), mean=1.0), "ln_f.b": n((d,)),
                "head": n((d, vocab))}

    return make(seed_key(seed))


# -- arithmetic ----------------------------------------------------------------
def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _prod(a, b, mode):
    if mode == "int8":
        a, b = _int8(a), _int8(b)
    elif mode != "float32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def bmm(a, b, mode):
    """a @ b over equal leading dimensions, in ``mode`` both ways."""
    return _prod(a, b, mode)


def _bmm_fwd(a, b, mode):
    return _prod(a, b, mode), (a, b)


def _bmm_bwd(mode, saved, g):
    a, b = saved
    return (_prod(g, jnp.swapaxes(b, -1, -2), mode),
            _prod(jnp.swapaxes(a, -1, -2), g, mode))


bmm.defvjp(_bmm_fwd, _bmm_bwd)


def linear(x, w, mode):
    """x (..., n) @ w (n, m)"""
    return bmm(x.reshape(-1, x.shape[-1]), w, mode).reshape(x.shape[:-1] + (w.shape[-1],))


def layer_norm(x, g, b, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(bp, x, n_head, mode):
    """One pre-LN block on x (rows, T, d)."""
    rows, T, d = x.shape
    a = layer_norm(x, bp["ln_1.g"], bp["ln_1.b"])

    def heads(w):
        return linear(a, w, mode).reshape(rows, T, n_head, d // n_head).transpose(0, 2, 1, 3)

    q, k, v = heads(bp["attn.q"]), heads(bp["attn.k"]), heads(bp["attn.v"])
    scores = bmm(q, jnp.swapaxes(k, -1, -2), mode) / math.sqrt(d // n_head)
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = bmm(probs, v, mode).transpose(0, 2, 1, 3).reshape(rows, T, d)
    x = x + linear(out, bp["attn.proj.w"], mode) + bp["attn.proj.b"]
    m = layer_norm(x, bp["ln_2.g"], bp["ln_2.b"])
    hidden = gelu(linear(m, bp["mlp.fc.w"], mode) + bp["mlp.fc.b"])
    return x + linear(hidden, bp["mlp.proj.w"], mode) + bp["mlp.proj.b"]


def embed(w, ids):
    return w["wte"][ids] + w["wpe"][: ids.shape[1]][None]


def head_logits(w, x, mode):
    return linear(layer_norm(x, w["ln_f.g"], w["ln_f.b"]), w["head"], mode)


def head_nll_sum(top, x, targets, mode):
    """Sum of next-token negative log-likelihoods; targets -1 are skipped."""
    logits = head_logits(top, x, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(targets >= 0, lse - picked, 0.0))


@functools.lru_cache(maxsize=None)
def _programs(n_head, mode):
    fwd = jax.jit(lambda bp, x: block(bp, x, n_head, mode))

    def bwd(bp, x, g):
        _, pull = jax.vjp(lambda bp, x: block(bp, x, n_head, mode), bp, x)
        return pull(g)

    top = jax.jit(jax.value_and_grad(
        lambda top, x, t: head_nll_sum(top, x, t, mode), argnums=(0, 1)))
    logits = jax.jit(lambda top, x: head_logits(top, x, mode))
    return fwd, jax.jit(bwd), top, logits


def _layer(blocks, i):
    return {k: v[i] for k, v in blocks.items()}


def _top(w):
    return {k: w[k] for k in ("ln_f.g", "ln_f.b", "head")}


def hidden_states(cfg, w, ids, mode="float32"):
    """Final residual stream (rows, T, d) of ids (rows, T), layer by layer."""
    fwd = _programs(cfg["n_head"], mode)[0]
    x = embed(w, jnp.asarray(ids, jnp.int32))
    for i in range(cfg["n_layer"]):
        x = fwd(_layer(w["blocks"], i), x)
    return x


def logits(cfg, w, ids, mode="float32"):
    """Logits (rows, T, V) of ids (rows, T)."""
    return _programs(cfg["n_head"], mode)[3](_top(w), hidden_states(cfg, w, ids, mode))


def loss_and_grads(cfg, w, ids, targets, mode="float32", rows_per_block=4):
    """Mean next-token loss over the batch and its gradient, by the chain
    rule layer by layer, accumulated over blocks of rows."""
    fwd, bwd, top, _ = _programs(cfg["n_head"], mode)
    ids = np.asarray(ids, np.int32)
    targets = np.asarray(targets, np.int32)
    count = max(int((targets >= 0).sum()), 1)
    layers = cfg["n_layer"]
    total = 0.0
    grads = None
    for r in range(0, ids.shape[0], rows_per_block):
        rows, tgt = jnp.asarray(ids[r:r + rows_per_block]), jnp.asarray(targets[r:r + rows_per_block])
        xs = [embed(w, rows)]
        for i in range(layers):
            xs.append(fwd(_layer(w["blocks"], i), xs[-1]))
        nll, (g_top, g_x) = top(_top(w), xs[-1], tgt)
        total += float(nll)
        g_layers = [None] * layers
        for i in reversed(range(layers)):
            g_layers[i], g_x = bwd(_layer(w["blocks"], i), xs[i], g_x)
            xs[i + 1] = None
        part = {"wte": jnp.zeros_like(w["wte"]).at[rows].add(g_x),
                "wpe": jnp.zeros_like(w["wpe"]).at[: rows.shape[1]].add(g_x.sum(0)),
                "blocks": {k: jnp.stack([g[k] for g in g_layers]) for k in BLOCK_LEAVES},
                **g_top}
        grads = part if grads is None else jax.tree_util.tree_map(jnp.add, grads, part)
    return total / count, jax.tree_util.tree_map(lambda g: g / count, grads)


@jax.jit
def _adam(w, m, v, g, t, lr, b1, b2, eps):
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    alpha = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    w = jax.tree_util.tree_map(lambda w, m, v: w - alpha * m / (jnp.sqrt(v) + eps), w, m, v)
    return w, m, v


def leaf_norms(tree):
    """{"blocks/attn.q": norm, ...} as floats"""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                                    for a in leaves])([a for _, a in flat])
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(n)
            for (path, _), n in zip(flat, norms)}


def train_steps(cfg, seed, batches, optimizer, mode="float32"):
    """Drive the reference through ``batches`` (a list of (ids, targets))
    with Adam as the configuration states it. Returns the loss of each
    step, the leaf norms of the first gradient and the leaf norms of the
    parameters' change over all the steps."""
    w0 = make_weights(cfg, seed)
    w = w0
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first = [], None
    for t, (ids, targets) in enumerate(batches, start=1):
        loss, g = loss_and_grads(cfg, w, ids, targets, mode)
        if first is None:
            first = leaf_norms(g)
        losses.append(loss)
        w, m, v = _adam(w, m, v, g, jnp.float32(t), optimizer["learning_rate"],
                        optimizer["beta1"], optimizer["beta2"], optimizer["epsilon"])
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, w, w0))
    return {"losses": losses, "grad_norms": first, "update_norms": change}


def served_token_gaps(cfg, w, samples, pad_to, answers_pad, mode="float32",
                      control_mode=None):
    """For served requests (dicts with ``prompt`` and ``tokens``): at each
    position where the program produced a token, how far that token's logit
    lies below the reference's best, from one forward pass over prompt +
    tokens. With ``control_mode`` also the same gap for the token that a
    pass in that mode puts first. Rows are padded at the end to ``pad_to``
    positions and ``answers_pad`` answers (causal attention: padding after
    a row's end cannot reach it), so every call has one shape. Returns
    arrays over all served tokens."""
    rows = len(samples)
    seq = np.zeros((rows, pad_to), np.int32)
    at = np.zeros((rows, answers_pad), np.int32)
    served = np.zeros((rows, answers_pad), np.int32)
    valid = np.zeros((rows, answers_pad), bool)
    for i, s in enumerate(samples):
        full = list(s["prompt"]) + list(s["tokens"])
        n, first = len(s["tokens"]), len(s["prompt"]) - 1
        if len(full) - 1 > pad_to or n > answers_pad:
            raise ValueError("a served request is longer than the padding")
        seq[i, : len(full) - 1] = full[:-1]
        at[i, :n] = np.arange(first, first + n)
        served[i, :n] = s["tokens"]
        valid[i, :n] = True

    def rows_logits(m):
        hidden = hidden_states(cfg, w, seq, m)
        picked = hidden[np.arange(rows)[:, None], at]
        return _programs(cfg["n_head"], m)[3](_top(w), picked)

    ref = rows_logits(mode)
    best = ref.max(-1)
    out = {"served": np.asarray(best - jnp.take_along_axis(
        ref, jnp.asarray(served)[..., None], -1)[..., 0])[valid]}
    if control_mode:
        first = rows_logits(control_mode).argmax(-1)
        out["control"] = np.asarray(best - jnp.take_along_axis(
            ref, first[..., None], -1)[..., 0])[valid]
    return out

"""Plain reference for ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1,
50-layer): forward pass, loss, gradients and the Nesterov update in
straightforward float32 ``jax.numpy``/``lax`` at precision "highest". No
kernels and no fusion tricks; unit by unit (stem, 16 bottlenecks, head)
with each unit's backward pass recomputing its forward, so that a batch of
128 at 224 x 224 fits beside nothing else on one chip. Batch
normalisation uses the batch's own statistics, so rows cannot be split.

It imports nothing of ``deeplearning4j_tpu`` and takes nothing the program
made: ``make_weights`` draws the weights from the seed, the family module
hands the same values to the program.

As ``models/resnet50.py`` builds it (each departure from the paper noted
in the configuration file): NHWC, SAME padding, the stride of a
downsampling bottleneck on its first 1x1, no convolution biases, batch
norm after every convolution (eps 1e-5), projection shortcuts at each
stage's first block, softmax cross-entropy averaged over the batch, L2
1e-4 on weights and gains (not on biases and shifts) added to the
gradient before the updater, Nesterov momentum in DL4J's form:
v' = mu v - lr g; parameter += -mu v + (1 + mu) v'.

``mode`` selects the arithmetic of every convolution and matrix product:
``"float32"`` is the reference; ``"int8"`` (both operands rounded to 127
levels of their largest magnitude, forward and backward) is the control
that ``correct`` has to refuse.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))


def seed_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def units(cfg=None):
    """[(unit name, [(conv name, kernel, c_in, c_out)], stride, project)] in order."""
    out = [("stem", [("stem", 7, 3, 64)], 2, False)]
    c_in = 64
    for si, (blocks, mid) in enumerate(STAGES):
        for bi in range(blocks):
            name = f"s{si}b{bi}"
            convs = [(f"{name}.a", 1, c_in, mid), (f"{name}.b", 3, mid, mid),
                     (f"{name}.c", 1, mid, 4 * mid)]
            if bi == 0:
                convs.append((f"{name}.proj", 1, c_in, 4 * mid))
            out.append((name, convs, 2 if (bi == 0 and si > 0) else 1, bi == 0))
            c_in = 4 * mid
    return out


def make_weights(cfg, seed):
    """{"stem.conv": {"W"}, "stem.bn": {"gamma", "beta"}, ..., "fc": {"W", "b"}}
    in float32, in one jitted call from the seed. He-normal convolutions,
    gains around 1 and shifts around 0 (std 0.02, so that no leaf is
    idle), a small classifier."""
    classes = cfg["num_classes"]

    @jax.jit
    def make(key):
        w = {}
        for _, convs, _, _ in units():
            for name, k, c_in, c_out in convs:
                key_w, key_g, key_b, key = jax.random.split(key, 4)
                std = math.sqrt(2.0 / (k * k * c_in))
                w[f"{name}.conv"] = {"W": std * jax.random.normal(key_w, (k, k, c_in, c_out), jnp.float32)}
                w[f"{name}.bn"] = {"gamma": 1.0 + 0.02 * jax.random.normal(key_g, (c_out,), jnp.float32),
                                   "beta": 0.02 * jax.random.normal(key_b, (c_out,), jnp.float32)}
        key_w, key_b = jax.random.split(key)
        w["fc"] = {"W": 0.01 * jax.random.normal(key_w, (2048, classes), jnp.float32),
                   "b": 0.01 * jax.random.normal(key_b, (classes,), jnp.float32)}
        return w

    return make(seed_key(seed))


# -- arithmetic ----------------------------------------------------------------
def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _q(x, mode):
    if mode == "int8":
        return _int8(x)
    if mode != "float32":
        raise ValueError(f"unknown mode {mode!r}")
    return x


def _conv_plain(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv(x, w, stride, mode):
    return _conv_plain(_q(x, mode), _q(w, mode), stride)


def _conv_fwd(x, w, stride, mode):
    return conv(x, w, stride, mode), (x, w)


def _conv_bwd(stride, mode, saved, g):
    x, w = saved
    _, pull = jax.vjp(lambda x, w: _conv_plain(x, w, stride), _q(x, mode), _q(w, mode))
    return pull(_q(g, mode))


conv.defvjp(_conv_fwd, _conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul(a, b, mode):
    return jnp.matmul(_q(a, mode), _q(b, mode), precision=HIGHEST)


def _matmul_fwd(a, b, mode):
    return matmul(a, b, mode), (a, b)


def _matmul_bwd(mode, saved, g):
    a, b = saved
    g = _q(g, mode)
    return (jnp.matmul(g, _q(b, mode).T, precision=HIGHEST),
            jnp.matmul(_q(a, mode).T, g, precision=HIGHEST))


matmul.defvjp(_matmul_fwd, _matmul_bwd)


def batch_norm(x, p, eps=1e-5):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean((x - mean) ** 2, (0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


def conv_bn(w, name, x, stride, mode, relu=True):
    y = batch_norm(conv(x, w[f"{name}.conv"]["W"], stride, mode), w[f"{name}.bn"])
    return jnp.maximum(y, 0.0) if relu else y


def stem(w, x, mode):
    y = conv_bn(w, "stem", x, 2, mode)
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


def bottleneck(w, name, x, stride, project, mode):
    y = conv_bn(w, f"{name}.a", x, stride, mode)
    y = conv_bn(w, f"{name}.b", y, 1, mode)
    y = conv_bn(w, f"{name}.c", y, 1, mode, relu=False)
    shortcut = conv_bn(w, f"{name}.proj", x, stride, mode, relu=False) if project else x
    return jnp.maximum(y + shortcut, 0.0)


def head_loss(fc, x, labels, mode):
    """Mean over the batch of softmax cross-entropy against one-hot labels."""
    logits = matmul(jnp.mean(x, (1, 2)), fc["W"], mode) + fc["b"]
    return -jnp.mean(jnp.sum(labels * jax.nn.log_softmax(logits, -1), -1))


def _unit_fn(name, stride, project, mode):
    if name == "stem":
        return lambda w, x: stem(w, x, mode)
    return lambda w, x: bottleneck(w, name, x, stride, project, mode)


@functools.lru_cache(maxsize=None)
def _programs(name, stride, project, mode):
    fn = _unit_fn(name, stride, project, mode)

    def bwd(w, x, g):
        return jax.vjp(fn, w, x)[1](g)

    return jax.jit(fn), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _head_program(mode):
    return jax.jit(jax.value_and_grad(lambda fc, x, y: head_loss(fc, x, y, mode), argnums=(0, 1)))


def _unit_weights(w, convs):
    return {k: w[k] for name, *_ in convs for k in (f"{name}.conv", f"{name}.bn")}


def loss_and_grads(cfg, w, images, labels, mode="float32"):
    """Data loss of one batch and its gradient, unit by unit."""
    plan = units()
    xs = [jnp.asarray(images, jnp.float32)]
    for name, convs, stride, project in plan:
        xs.append(_programs(name, stride, project, mode)[0](_unit_weights(w, convs), xs[-1]))
    loss, (g_fc, g_x) = _head_program(mode)(w["fc"], xs[-1], jnp.asarray(labels, jnp.float32))
    grads = {"fc": g_fc}
    for i in reversed(range(len(plan))):
        name, convs, stride, project = plan[i]
        g_w, g_x = _programs(name, stride, project, mode)[1](_unit_weights(w, convs), xs[i], g_x)
        xs[i + 1] = None
        grads.update(g_w)
    return float(loss), grads


def _decays(leaf_name):
    return leaf_name in ("W", "gamma")


@jax.jit
def _l2_score(w, l2):
    return 0.5 * l2 * sum(jnp.sum(a ** 2) for layer in w.values()
                          for k, a in layer.items() if _decays(k))


@jax.jit
def _nesterov(w, v, g, lr, mu, l2):
    def leaf(k, p, v, g):
        g = g + l2 * p if _decays(k) else g
        v_new = mu * v - lr * g
        return p - (mu * v - (1.0 + mu) * v_new), v_new, g

    out = {name: {k: leaf(k, w[name][k], v[name][k], g[name][k]) for k in w[name]} for name in w}
    pick = lambda i: {n: {k: t[i] for k, t in layer.items()} for n, layer in out.items()}  # noqa: E731
    return pick(0), pick(1), pick(2)


def leaf_norms(tree):
    """{"stem.conv/W": norm, ...} as floats"""
    flat = [(f"{name}/{k}", a) for name, layer in tree.items() for k, a in layer.items()]
    norms = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                                    for a in leaves])([a for _, a in flat])
    return {k: float(n) for (k, _), n in zip(flat, norms)}


def train_steps(cfg, seed, batches, optimizer, mode="float32"):
    """Drive the reference through ``batches`` (a list of (images, one-hot
    labels)). Returns each step's score (data loss + L2 term, as the
    program reports it), the leaf norms of the first gradient as the
    updater gets it (with the L2 term) and the leaf norms of the
    parameters' change over all the steps."""
    lr, mu, l2 = optimizer["learning_rate"], optimizer["momentum"], optimizer["l2"]
    w0 = make_weights(cfg, seed)
    w = w0
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first = [], None
    for images, labels in batches:
        loss, g = loss_and_grads(cfg, w, images, labels, mode)
        losses.append(loss + float(_l2_score(w, l2)))
        w, v, g_eff = _nesterov(w, v, g, lr, mu, l2)
        if first is None:
            first = leaf_norms(g_eff)
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, w, w0))
    return {"losses": losses, "grad_norms": first, "update_norms": change}


# -- required work -------------------------------------------------------------
def forward_macs(height=224, width=224, classes=1000):
    """Forward multiply-accumulates of one image, counted layer by layer
    from Table 1: 7x7/2 stem, four stages of [3, 4, 6, 3] bottlenecks, the
    projection shortcuts, the classifier. Batch norm, ReLU, pooling and
    the shortcuts' additions are not counted."""
    h, w = height // 2, width // 2
    macs = h * w * 64 * 7 * 7 * 3             # stem
    h, w = h // 2, w // 2                     # max pool
    for _, convs, stride, _ in units()[1:]:
        h, w = h // stride, w // stride       # the stride sits on the first 1x1
        macs += sum(h * w * k * k * c_in * c_out for _, k, c_in, c_out in convs)
    return macs + 4 * STAGES[-1][1] * classes


def train_flops_per_item(cfg):
    """Operations one image of a training step requires: a
    multiply-accumulate is 2, the backward pass twice the forward."""
    return 3 * 2 * forward_macs(cfg["image_size"], cfg["image_size"], cfg["num_classes"])

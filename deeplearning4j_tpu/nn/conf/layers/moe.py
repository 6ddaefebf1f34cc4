"""Mixture-of-Experts layers — a NEW capability of this stack (the
reference predates MoE; SURVEY.md §2.5 lists expert parallelism as
ABSENT there and a required addition here, like TP/PP/SP).

TPU-first design: GShard/Switch-style *dense dispatch* — routing is
expressed as one-hot einsums with a static per-expert capacity, so the
whole layer is three MXU matmul chains with fixed shapes (no gather/
scatter, no dynamic shapes; XLA tiles it like any other matmul). Expert
parallelism = shard the leading expert dim of the FFN params over the
mesh "expert" axis (`parallel/moe.py`); GSPMD inserts the token
all-to-all from the shardings alone.

Beside it, for serving (PR 27): ``moe_dropless_ffn``, the expert layer
as today's open models deploy it: a sigmoid router over every expert,
the choice by score + correction bias, no capacity and no dropped token,
the (token, expert) pairs sorted by expert and run as grouped products
(few rows a group: ``nn/ops/grouped_experts.py``; many: ``ragged_dot``),
and the layer TOLD WHICH EXPERTS IT HOLDS, computing their share of the
result (``models/decoder_lm.py``; manual expert parallelism in
``parallel/moe.py``). The GShard layers below are unchanged.

Load-balancing: the Switch-Transformer auxiliary loss
``E * Σ_e f_e · P_e`` (f_e = fraction of tokens routed to expert e,
P_e = mean router probability) is returned through the layer-state
pytree under ``"aux_loss"`` and added to the training loss by the
network (`nn/multilayer.py` / `nn/graph.py` `_loss_and_new_state`).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import serde
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import TransformerBlock, _layer_norm
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer
from deeplearning4j_tpu.nn.ops.grouped_experts import grouped_experts_impl
from deeplearning4j_tpu.nn.ops.kernel_compat import PRECISION


def moe_capacity(n_tokens: int, capacity_factor: float, top_k: int,
                 n_experts: int) -> int:
    """GShard per-expert slot count: ceil(tokens * cf * k / E), min 1 —
    the ONE place the capacity policy lives (layers and TransformerLM
    both route through it)."""
    return max(1, math.ceil(n_tokens * capacity_factor * top_k / n_experts))


def _moe_dispatch(probs, capacity: int, top_k: int, valid=None):
    """Top-k dense dispatch (GShard): returns (dispatch [S,E,C] 0/1,
    combine [S,E,C] gate-weighted, aux_loss scalar fp32).

    Token order is assignment priority within each expert (tokens past
    capacity are dropped for that expert — their residual path carries
    them, the standard Switch behaviour). ``valid`` ([S] 0/1) excludes
    padding tokens: they take no capacity slots and don't bias the
    load-balancing statistics."""
    S, E = probs.shape
    # aux statistics at >= fp32; fp64 inputs (gradient checker) keep fp64
    sd = jnp.float64 if probs.dtype == jnp.float64 else jnp.float32
    f32 = probs.astype(sd)
    if valid is not None:
        valid = valid.reshape(S).astype(probs.dtype)

    dispatch = jnp.zeros((S, E, capacity), probs.dtype)
    gates = []
    disps = []
    remaining = probs
    prev_count = jnp.zeros((E,), jnp.int32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        oh = jax.nn.one_hot(idx, E, dtype=probs.dtype)
        if valid is not None:
            oh = oh * valid[:, None]
        gates.append((remaining * oh).sum(-1))
        remaining = remaining * (1.0 - oh)
        oh_i = oh.astype(jnp.int32)
        pos_in_e = jnp.cumsum(oh_i, axis=0) - oh_i + prev_count[None, :]
        prev_count = prev_count + oh_i.sum(0)
        keep = (pos_in_e < capacity).astype(probs.dtype) * oh
        pos = (pos_in_e * oh_i).sum(-1)
        disp = keep[:, :, None] * jax.nn.one_hot(pos, capacity, dtype=probs.dtype)[:, None, :]
        disps.append(disp)
        dispatch = dispatch + disp

    # normalize the kept top-k gate values to sum to 1 per token
    denom = sum(gates) + 1e-9
    combine = sum(d * (g / denom)[:, None, None] for d, g in zip(disps, gates))

    # Switch aux loss on the top-1 assignment, over valid tokens only
    top1 = jax.nn.one_hot(jnp.argmax(f32, -1), E, dtype=sd)
    if valid is None:
        f_e = top1.mean(0)
        p_e = f32.mean(0)
    else:
        v32 = valid.astype(sd)
        n_valid = jnp.maximum(v32.sum(), 1.0)
        f_e = (top1 * v32[:, None]).sum(0) / n_valid
        p_e = (f32 * v32[:, None]).sum(0) / n_valid
    aux = E * jnp.sum(f_e * p_e)
    # f_e doubles as the expert-load observability signal (fraction of
    # tokens whose top-1 choice is each expert)
    return dispatch, combine, aux, f_e


def _moe_ffn(params, x2, act_fn, capacity: int, top_k: int, valid=None,
             expert_axis=None, tp_axis=None):
    """Token-level MoE FFN: x2 [S, d] → (y [S, d], aux_loss). Router
    softmax precision floors at fp32 (GShard convention — routing is
    precision-sensitive): bf16/f16 upcast, f32/f64 pass through.

    ``expert_axis``/``tp_axis`` engage MANUAL expert/tensor parallelism
    inside a fully-manual shard_map region: W1/b1/W2/b2 arrive with
    their expert dim pre-sliced over ``expert_axis`` (and the hidden
    dim over ``tp_axis``) while Wg stays replicated — routing,
    dispatch/combine tensors, capacity and the aux loss are computed
    over the GLOBAL expert count on every shard (bit-identical to the
    single-device math), each shard runs only its local expert block
    of the FFN einsums, and the outputs psum back: over ``tp_axis``
    before the (expert-sliced, tp-replicated) b2 bias add, over
    ``expert_axis`` after the combine (each token's experts live on
    exactly one expert shard, so the psum is a sum of disjoint
    contributions)."""
    logits = x2 @ params["Wg"]
    # router at >= fp32 (GShard convention); fp64 inputs (gradient
    # checker) keep fp64 — only low precision is upcast
    rd = jnp.float32 if logits.dtype in (jnp.bfloat16, jnp.float16) \
        else logits.dtype
    probs = jax.nn.softmax(logits.astype(rd), axis=-1).astype(x2.dtype)
    dispatch, combine, aux, load = _moe_dispatch(probs, capacity, top_k, valid)
    if expert_axis is not None:
        # slice this shard's expert block of the global dispatch/combine
        e_local = params["W1"].shape[0]
        e0 = jax.lax.axis_index(expert_axis) * e_local
        dispatch = jax.lax.dynamic_slice_in_dim(dispatch, e0, e_local, 1)
        combine = jax.lax.dynamic_slice_in_dim(combine, e0, e_local, 1)
    # [S,E,C]x[S,d] -> [E,C,d]: the tensor GSPMD all-to-alls under EP
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, x2)
    h = act_fn(jnp.einsum("ecd,edh->ech", expert_in, params["W1"])
               + params["b1"][:, None, :])
    out = jnp.einsum("ech,ehd->ecd", h, params["W2"])
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    out = out + params["b2"][:, None, :]
    y = jnp.einsum("sec,ecd->sd", combine, out)
    if expert_axis is not None:
        y = jax.lax.psum(y, expert_axis)
    return y, aux, load


def sigmoid_topk_route(z, bias, top_k: int, scale: float = 1.0):
    """Sigmoid-scored routing over every expert of the layer: z (N, E)
    float32 router outputs -> (chosen (N, k) expert ids, weights (N, k)
    float32). The k experts are the largest of ``sigmoid(z) + bias``
    (the correction bias enters the CHOICE only); the weights are the
    chosen experts' plain scores, renormalised to sum to one, and
    ``scale`` (a published ``routed_scaling_factor``) multiplies them
    AFTER the renormalisation: they then sum to ``scale``."""
    s = jax.nn.sigmoid(z.astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), w if scale == 1.0 else w * scale


def group_limited_softmax_route(z, bias, top_k: int, n_group: int,
                                topk_group: int, renormalise: bool = False,
                                scale: float = 1.0):
    """Group-limited greedy routing on softmax scores: z (N, E) float32
    router outputs -> (chosen (N, k), weights (N, k) float32). The E
    experts are ``n_group`` groups of consecutive experts (a group is
    what one device holds); a group's score is its largest softmax
    probability, the ``topk_group`` best groups stay, and the k experts
    are the largest probabilities inside them. The weights are those
    probabilities, renormalised to sum to one only where ``renormalise``,
    times ``scale``. This rule has no correction bias (``bias`` is not
    read)."""
    del bias
    p = jax.nn.softmax(z.astype(jnp.float32), axis=-1)
    n, e = p.shape
    by_group = p.reshape(n, n_group, e // n_group)
    _, best = jax.lax.top_k(by_group.max(-1), topk_group)       # (N, g)
    kept = (best[:, :, None] == jnp.arange(n_group)[None, None, :]).any(1)
    w, chosen = jax.lax.top_k(
        jnp.where(kept[:, :, None], by_group, 0.0).reshape(n, e), top_k)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), w * scale


def shared_swiglu(x, params):
    """The shared expert, which every token takes and every holder of a
    layer computes alike: ``(silu(x Sg) * (x Su)) Sd`` -> (N, d)
    float32."""
    with jax.named_scope("moe_shared"):
        h = jax.nn.silu(x @ params["Sg"]) * (x @ params["Su"])
        return (h @ params["Sd"]).astype(jnp.float32)


def _ragged_swiglu(rows, experts, sizes, chunk: int):
    """:func:`_grouped_swiglu` as three ``jax.lax.ragged_dot`` (a Mosaic
    kernel of XLA's own on the TPU, its tiles (128, 512, 512)): ``sizes``
    names every group of the stack.

    The rows go ``chunk`` at a time, for as many chunks as the groups
    fill: the grouped kernel multiplies a whole tile of rows for every
    tile of weights it reads, so at M = slots x k rows of which a
    sixteenth are held it was bound by multiplying empty rows, not by
    reading weights (512-row tiles: 39 % of the HBM roofline on the
    chip, PR 27)."""
    # bfloat16 operands take one exact MXU pass; the package-wide
    # "highest" would ask the grouped kernel for a multi-pass
    # algorithm, which Mosaic refuses (nn/ops/kernel_compat.py)
    kw = {"preferred_element_type": jnp.float32,
          "precision": PRECISION if rows.dtype == jnp.bfloat16 else None}

    def products(r, part):
        g = jax.lax.ragged_dot(r, experts["Eg"], part, **kw)
        u = jax.lax.ragged_dot(r, experts["Eu"], part, **kw)
        h = (jax.nn.silu(g) * u).astype(r.dtype)
        return jax.lax.ragged_dot(h, experts["Ed"], part, **kw)

    m, d = rows.shape
    if m <= chunk:
        return products(rows, sizes)
    pad = -m % chunk
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, d), rows.dtype)])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes

    def body(c, out):
        lo = c * chunk
        part = jnp.clip(ends, lo, lo + chunk) - jnp.clip(starts, lo, lo + chunk)
        r = jax.lax.dynamic_slice(rows, (lo, 0), (chunk, d))
        return jax.lax.dynamic_update_slice(out, products(r, part), (lo, 0))

    out = jax.lax.fori_loop(0, (ends[-1] + chunk - 1) // chunk, body,
                            jnp.zeros(rows.shape, jnp.float32))
    return out[:m]


def _grouped_swiglu(rows, experts, sizes, chunk: int, first=None):
    """rows (M, d), sorted by group, through each row's expert:
    ``(silu(r Eg_e) * (r Eu_e)) Ed_e`` as grouped products -> (M, d)
    float32. ``experts``: the stacks ``Eg``, ``Eu`` (groups, d, f) and
    ``Ed`` (groups, f, d); ``sizes`` (count,): the rows of the groups
    ``first .. first + count`` (``first`` None: the stack is those groups;
    else it may be traced, and no other group has rows). Rows past
    ``sum(sizes)`` belong to no group and come back unspecified.

    Two paths, chosen by what is known at trace time (M, the widths, the
    dtype, the backend, an ambient mesh): at a decode step's row counts
    on the TPU one kernel of the repo's own that reads each hit group's
    matrices once, in wide tiles, against a short window of its rows
    (``nn/ops/grouped_experts.py``: M <= its ``MAX_ROWS``; a group
    without rows costs neither a DMA nor a product); else three
    ``ragged_dot``, ``chunk`` rows at a time (:func:`_ragged_swiglu`: a
    prefill's hundreds of rows a group, a mesh, the CPU)."""
    groups, d, f = experts["Eg"].shape
    kernel = grouped_experts_impl(rows.shape[0], d, f, sizes.shape[0],
                                  rows.dtype)
    if kernel is not None:
        return kernel(rows, experts["Eg"], experts["Eu"], experts["Ed"],
                      sizes, 0 if first is None else first)
    if first is not None:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((groups,), jnp.int32), sizes, (first,))
    return _ragged_swiglu(rows, experts, sizes, chunk)


def moe_dropless_ffn(x, router_in, params, top_k: int, experts_held,
                     token_mask=None, layer=None, chunk: int = 128,
                     route=sigmoid_topk_route, shared: bool = False):
    """Dropless expert FFN, this holder's part of it: x (N, d) ->
    (y (N, d) float32, pairs computed here, held experts with a pair).

    The router (``Wr`` (d, E), float32, over ALL E experts of the layer)
    reads ``router_in`` (N, d) float32 and ``route`` (router outputs,
    ``br`` (E,) or None where the rule has no bias, k) -> (chosen (N, k),
    weights (N, k)) makes the choice: ``sigmoid_topk_route`` or
    ``group_limited_softmax_route`` with its groups bound. With
    ``shared`` the shared expert (``Sg``, ``Su`` (d, fs), ``Sd`` (fs, d):
    ``shared_swiglu``) is added to every token, under its own scope and
    outside the grouped products; a caller that sums the shares of
    several holders leaves it out and adds it once. ``experts_held`` =
    (offset, count) names the experts whose weights ``Eg``/``Eu``
    (count, d, f) and ``Ed`` (count, f, d) are in ``params`` (offset may
    be traced: ``axis_index * count`` under manual expert parallelism).
    Of the N * k (token, expert) pairs those whose expert is held are
    sorted by expert, their rows gathered, and the three products run as
    grouped products over the ragged groups (``_grouped_swiglu``: at a
    decode step's row counts on the TPU the repo's own kernel,
    ``nn/ops/grouped_experts.py``, else ``jax.lax.ragged_dot``, ``chunk``
    rows at a time; on the TPU a group without rows reads no weights on
    either path); each result is
    scaled by its routing weight and summed into its token. No capacity,
    no dropped token, no [S, E, C] tensor; what absent experts would add
    is left out, so the shares of all holders sum to the whole layer.
    ``token_mask`` (N,) bool leaves rows (padding, idle slots) out.

    ``layer``: where the expert weights of SEVERAL layers are stacked
    (``Eg`` (L, count, d, f), ...), the layer to use, which may be
    traced (a scan's counter). The stack goes to the grouped product
    whole, as L * count groups of which only this layer's have rows (the
    kernel is told the layer's first group, ``ragged_dot`` the sizes of
    all L * count): a
    layer sliced out of the stack inside a loop would be copied for the
    kernel every time (3 x 268 MB a layer at MiMo's widths, a third of
    a decode step on the chip: PERF.md, PR 27)."""
    n, d = x.shape
    offset, count = experts_held
    experts = {k: params[k] for k in ("Eg", "Eu", "Ed")}
    if layer is not None:
        experts = {k: v.reshape((-1,) + v.shape[2:])
                   for k, v in experts.items()}
    with jax.named_scope("moe_route"):
        z = jnp.matmul(router_in.astype(jnp.float32), params["Wr"],
                       precision=jax.lax.Precision.HIGHEST)
        chosen, w = route(z, params.get("br"), top_k)
        local = chosen - offset                      # (N, k)
        held = (local >= 0) & (local < count)
        if token_mask is not None:
            held &= token_mask[:, None]
        key = jnp.where(held, local, count).reshape(-1)     # (N k,)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :],
                        axis=0).astype(jnp.int32)
        n_local = jnp.sum(sizes)
        hit = jnp.sum(sizes > 0).astype(jnp.int32)
        rows = jnp.take(x, order // top_k, axis=0)          # (N k, d)
    with jax.named_scope("moe_experts"):
        out = _grouped_swiglu(rows, experts, sizes, chunk,
                              None if layer is None else layer * count)
        # rows past the held pairs belong to no group: whatever the
        # grouped product left there is not a number to keep
        out = jnp.where((jnp.arange(n * top_k) < n_local)[:, None], out, 0.0)
    with jax.named_scope("moe_route"):
        back = jnp.argsort(order)                    # pair -> sorted row
        out = jnp.take(out, back, axis=0).reshape(n, top_k, d)
        y = jnp.sum(out * jnp.where(held, w, 0.0)[:, :, None], axis=1)
    if shared:
        y = y + shared_swiglu(x, params)
    return y, n_local, hit


class _MoEParamsMixin:
    def _init_moe_params(self, rng, d: int, dtype):
        E, h = self.n_experts, self.n_hidden
        kg, k1, k2 = jax.random.split(rng, 3)
        return {
            "Wg": self._draw_weight(kg, (d, E), d, E, dtype),
            "W1": self._draw_weight(k1, (E, d, h), d, h, dtype),
            "b1": jnp.zeros((E, h), dtype),
            "W2": self._draw_weight(k2, (E, h, d), h, d, dtype),
            "b2": jnp.zeros((E, d), dtype),
        }

    def _capacity(self, n_tokens: int) -> int:
        return moe_capacity(n_tokens, self.capacity_factor, self.top_k,
                            self.n_experts)

    def _moe_state(self, aux, load, train: bool) -> dict:
        """Layer-state payload: weighted aux loss (fp64 preserved for the
        gradient checker) + per-expert top-1 routing fraction (inspect
        via net.state_ to see expert balance)."""
        aux_dt = aux.dtype if aux.dtype == jnp.float64 else jnp.float32
        return {
            "aux_loss": (self.aux_loss_weight * aux).astype(aux_dt)
            if train else jnp.zeros((), jnp.float32),
            "expert_load": load.astype(jnp.float32),
        }


@serde.register
class MixtureOfExpertsLayer(FeedForwardLayer, _MoEParamsMixin):
    """Standalone MoE FFN over tokens; accepts [B, d] or [B, T, d] input
    (output type mirrors the input). ``n_out`` must equal ``n_in`` when a
    residual wrapper is used; here it is the FFN output width d."""

    def __init__(self, n_experts: int = 4, top_k: int = 2,
                 capacity_factor: float = 1.25, hidden_ratio: int = 4,
                 aux_loss_weight: float = 1e-2, **kwargs):
        kwargs.setdefault("activation", "relu")
        super().__init__(**kwargs)
        self.n_experts = int(n_experts)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.hidden_ratio = int(hidden_ratio)
        self.aux_loss_weight = float(aux_loss_weight)
        self.n_hidden: Optional[int] = None

    def initialize(self, input_type):
        super().initialize(input_type)
        if self.n_out is None:
            self.n_out = self.n_in
        if self.n_out != self.n_in:
            raise ValueError("MixtureOfExpertsLayer requires n_in == n_out "
                             f"(got {self.n_in} != {self.n_out})")
        self.n_hidden = self.n_in * self.hidden_ratio

    def get_output_type(self, input_type):
        return input_type

    def init_params(self, rng, input_type, dtype=jnp.float32):
        assert self.n_in
        return self._init_moe_params(rng, self.n_in, dtype)

    def init_layer_state(self, input_type, dtype=jnp.float32):
        return {"aux_loss": jnp.zeros((), jnp.float32),
                "expert_load": jnp.zeros((self.n_experts,), jnp.float32)}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        valid = None
        if mask is not None and x.ndim == 3:
            valid = mask.reshape(-1)
        y2, aux, load = _moe_ffn(params, x2, self.act_fn(),
                                 self._capacity(x2.shape[0]), self.top_k,
                                 valid)
        y = y2.reshape(shape)
        if mask is not None and y.ndim == 3:
            y = y * mask[..., None]
        return y, self._moe_state(aux, load, train)


@serde.register
class MoETransformerBlock(TransformerBlock, _MoEParamsMixin):
    """Pre-LN transformer block whose FFN sublayer is a mixture of
    experts: x + MHA(LN(x)), then x + MoE(LN(x))."""

    def __init__(self, n_experts: int = 4, top_k: int = 2,
                 capacity_factor: float = 1.25,
                 aux_loss_weight: float = 1e-2, **kwargs):
        super().__init__(**kwargs)
        self.n_experts = int(n_experts)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.aux_loss_weight = float(aux_loss_weight)
        self.n_hidden: Optional[int] = None

    def initialize(self, input_type):
        super().initialize(input_type)
        self.n_hidden = self.n_out * self.mlp_ratio

    def init_params(self, rng, input_type, dtype=jnp.float32):
        assert self.n_in and self.n_out
        d = self.n_out
        base = TransformerBlock.init_params(self, rng, input_type, dtype)
        for k in ("W1", "b1", "W2", "b2"):
            del base[k]
        base.update(self._init_moe_params(jax.random.fold_in(rng, 17), d, dtype))
        return base

    def init_layer_state(self, input_type, dtype=jnp.float32):
        return {"aux_loss": jnp.zeros((), jnp.float32),
                "expert_load": jnp.zeros((self.n_experts,), jnp.float32)}

    def mlp(self, params, x):
        raise NotImplementedError(
            "MoETransformerBlock has no dense FFN; its expert FFN needs the "
            "aux-loss return — use apply()"
        )

    def block_apply(self, params, x, mask=None, attn_fn=None):
        raise NotImplementedError(
            "MoETransformerBlock is not supported by the pipeline-parallel "
            "block scan (block_apply cannot carry the MoE aux loss and the "
            "expert params are not stackable with dense blocks) — use "
            "apply(), or expert-shard via parallel.moe.ExpertParallelWrapper"
        )

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        a_in = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        x = x + self.attention(params, a_in, mask=mask)
        m_in = _layer_norm(x, params["ln2_g"], params["ln2_b"])
        b, T, d = m_in.shape
        valid = mask.reshape(-1) if mask is not None else None
        y2, aux, load = _moe_ffn(params, m_in.reshape(-1, d), self.act_fn(),
                                 self._capacity(b * T), self.top_k, valid)
        y = x + y2.reshape(b, T, d)
        if mask is not None:
            y = y * mask[..., None]
        return y, self._moe_state(aux, load, train)

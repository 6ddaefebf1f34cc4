"""TransformerLM: GPT-style causal language model — the flagship
distributed-training model.

No reference analog (the reference predates transformers; SURVEY.md §2.5);
this is the mandated new long-context/distributed capability. The model is
deliberately built on an explicit stacked-parameter pytree rather than the
layer-list runtime:

- blocks are IDENTICAL TransformerBlocks whose params are stacked along a
  leading (n_layers,) axis → single-device forward is one ``lax.scan``
  (compile time O(1) in depth), and the same stacked axis shards over the
  mesh "pipe" axis for pipeline parallelism;
- the time axis shards over "seq" (ring attention), batch over "data",
  head/FFN dims over "model" (Megatron column→row split);
- see parallel/transformer.py for the distributed step.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.obs import trace as _trace
from deeplearning4j_tpu.nn.conf.layers.attention import (
    TransformerBlock,
    _layer_norm,
    dense_attention,
)
from deeplearning4j_tpu.nn.ops.decode_attention import (
    decode_attention_impl,
    live_tiles,
)
from deeplearning4j_tpu.nn.ops.kv_column_write import kv_column_write_impl
from deeplearning4j_tpu.nn.ops.ssm_decode import live_table

Array = jax.Array

#: the model phases that device time is read by (``jax.named_scope``:
#: nothing at run time). The benchmark's ``lib/phases.py`` groups a
#: trace's operations by the innermost of them. For XLA's own operations
#: a scope is metadata only, and the persistent compile cache leaves it
#: out of its key. A Pallas custom call is NAMED after its innermost
#: scope (the flash kernel reads ``attn.27`` in a trace where it read
#: ``closed_call.82``), so a program that holds one (the LM step, the
#: prefill buckets) gets a new cache key when a scope around the kernel
#: is added, moved or renamed.
SCOPES = ("embed", "attn", "kv_write", "mlp", "head", "loss", "sample",
          "update")
_scope = jax.named_scope

_PUT_BATCH = _trace.phase("train.put_batch")
_DISPATCH = _trace.phase("train.dispatch")
_FETCH_LOSS = _trace.phase("train.fetch_loss")


class TransformerLMConfig:
    def __init__(self, vocab_size: int, d_model: int = 256, n_heads: int = 4,
                 n_layers: int = 4, mlp_ratio: int = 4, max_length: int = 512,
                 seed: int = 0, n_experts: int = 0, top_k: int = 2,
                 capacity_factor: float = 1.25, aux_loss_weight: float = 1e-2,
                 compute_dtype: Optional[str] = None,
                 fused_qkv: bool = False):
        if d_model % n_heads:
            raise ValueError("d_model must be divisible by n_heads")
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.n_layers = int(n_layers)
        self.mlp_ratio = int(mlp_ratio)
        self.max_length = int(max_length)
        self.seed = int(seed)
        # MoE: n_experts > 0 replaces every block's dense FFN with a
        # GShard dense-dispatch mixture (homogeneous stack keeps the
        # scan/pipeline param layout); 0 = dense
        self.n_experts = int(n_experts)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.aux_loss_weight = float(aux_loss_weight)
        # mixed precision (same scheme as the layer stack's compute_dtype:
        # fp32 master params/updater/layernorm/softmax, bf16 matmuls and
        # carried activations). None/"float32" = uniform fp32.
        if compute_dtype not in (None, "float32", "bfloat16"):
            raise ValueError(
                f"compute_dtype must be None, 'float32' or 'bfloat16', got "
                f"{compute_dtype!r}"
            )
        self.compute_dtype = None if compute_dtype == "float32" else compute_dtype
        # fused_qkv: compute Q,K,V as ONE (d, 3d) matmul per block instead
        # of three (d, d) dots — bitwise-identical outputs (each output
        # column block sees only its own weight block), but the activation
        # is read from HBM once instead of three times. Param layout is
        # UNCHANGED (Wq/Wk/Wv stay separate; the concat happens in-step),
        # so checkpoints, TP pspecs and the decode path are unaffected.
        # Opt-in pending hardware measurement (scripts/lm_perf_sweep.py).
        self.fused_qkv = bool(fused_qkv)

    def to_dict(self):
        return dict(self.__dict__)

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


def init_params(cfg: TransformerLMConfig, rng: Optional[Array] = None,
                dtype=jnp.float32) -> Dict[str, Array]:
    """Stacked-parameter pytree: block params have leading (n_layers,)."""
    rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
    d, h = cfg.d_model, cfg.d_model * cfg.mlp_ratio
    L, V = cfg.n_layers, cfg.vocab_size
    ks = jax.random.split(rng, 9)

    def w(key, shape, fan_in):
        return jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)

    blocks = {
        "ln1_g": jnp.ones((L, d), dtype), "ln1_b": jnp.zeros((L, d), dtype),
        "Wq": w(ks[2], (L, d, d), d), "Wk": w(ks[3], (L, d, d), d),
        "Wv": w(ks[4], (L, d, d), d), "Wo": w(ks[5], (L, d, d), d),
        "bo": jnp.zeros((L, d), dtype),
        "ln2_g": jnp.ones((L, d), dtype), "ln2_b": jnp.zeros((L, d), dtype),
    }
    if cfg.n_experts > 0:
        E = cfg.n_experts
        kg, k1, k2 = jax.random.split(ks[6], 3)
        blocks.update({
            "Wg": w(kg, (L, d, E), d),
            "W1": w(k1, (L, E, d, h), d), "b1": jnp.zeros((L, E, h), dtype),
            "W2": w(k2, (L, E, h, d), h), "b2": jnp.zeros((L, E, d), dtype),
        })
    else:
        blocks.update({
            "W1": w(ks[6], (L, d, h), d), "b1": jnp.zeros((L, h), dtype),
            "W2": w(ks[7], (L, h, d), h), "b2": jnp.zeros((L, d), dtype),
        })
    return {
        "embed": 0.02 * jax.random.normal(ks[0], (V, d), dtype),
        "pos": 0.02 * jax.random.normal(ks[1], (cfg.max_length, d), dtype),
        "blocks": blocks,
        "lnf_g": jnp.ones((d,), dtype), "lnf_b": jnp.zeros((d,), dtype),
        "head": w(ks[8], (d, V), d),
    }


def _moe_capacity(cfg: TransformerLMConfig, n_tokens: int) -> int:
    from deeplearning4j_tpu.nn.conf.layers.moe import moe_capacity

    return moe_capacity(n_tokens, cfg.capacity_factor, cfg.top_k,
                        cfg.n_experts)


def _cdtype(cfg: TransformerLMConfig):
    return jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else None


def _matmul_leaves(level: Dict) -> list:
    """THE rule of which parameters the matmuls read in the compute
    dtype, as the keys of one level of a params tree (the top level, or
    a block's dict, stacked or one layer's): a block's ``W*`` and ``b*``
    (``Wq Wk Wv Wo bo W1 b1 W2 b2``, ``Wg`` for MoE) and ``head``. Norm
    gains and shifts, ``embed`` and ``pos`` stay float32: the embedding
    adds two float32 rows and casts the sum, so a bf16 table would be a
    different result."""
    return [k for k, v in level.items()
            if not isinstance(v, dict) and (k == "head" or k[0] in "Wb")]


def _cast_matmul_leaves(cd, level: Dict) -> Dict:
    """``level`` with its :func:`_matmul_leaves` in ``cd``, every other
    entry the same object; ``level`` itself for ``cd`` None. A leaf that
    already has the dtype comes back as itself (``astype`` to an array's
    own dtype is the identity, traced or not), so a program handed the
    :func:`serving_copy` casts nothing."""
    if cd is None:
        return level
    return {**level, **{k: level[k].astype(cd) for k in _matmul_leaves(level)}}


_astype_tree = jax.jit(
    lambda tree, cd: jax.tree_util.tree_map(lambda a: a.astype(cd), tree),
    static_argnums=1)


def serving_copy(cfg: TransformerLMConfig, params: Dict) -> Dict:
    """The params tree a serving program is handed in place of the
    float32 masters: the leaves :func:`_block` and :func:`_head` would
    cast on every call (:func:`_matmul_leaves`) cast ONCE, by the same
    ``astype``, in one jitted program (elementwise: a sharded master's
    copy keeps its sharding); every other leaf (``embed``, ``pos``, the
    norms) the master itself, no second buffer. Bitwise what the
    programs computed from the masters. Under ``compute_dtype=None``, or
    with nothing left to cast, the tree itself. The masters are neither
    altered nor dropped: whoever keeps the copy re-makes it when
    ``params`` changes (``serving/generate.py``:
    ``_TransformerBackend._params``)."""
    cd = _cdtype(cfg)
    if cd is None:
        return params

    def masters(level):
        return {k: level[k] for k in _matmul_leaves(level)
                if level[k].dtype != cd}

    blocks = params["blocks"]
    stale = masters(params), masters(blocks)
    if not any(stale):
        return params
    top, inner = _astype_tree(stale, cd)
    return {**params, **top, "blocks": {**blocks, **inner}}


def _ln(x, g, b, cd):
    """LayerNorm with fp32 statistics under mixed precision (the same
    exemption the layer stack's norm layers use)."""
    if cd is None:
        return _layer_norm(x, g, b)
    return _layer_norm(x.astype(jnp.float32), g, b).astype(cd)


def _block(cfg: TransformerLMConfig, bp: Dict[str, Array], x: Array, attend,
           tp_axis: Optional[str] = None, expert_axis: Optional[str] = None):
    """THE pre-LN block, for every caller: x (b, T, d) with T the
    sequence (``forward``, ``prefill_cache``), 1 (``decode_step``) or K
    (``decode_steps``); bp holds UNSTACKED (single-layer) params.
    ``attend(q, k, v) -> (o, handed_out)`` is the attention core, all
    four arrays (b, heads, T, head_dim): :func:`_attend_causal`,
    :func:`_attend_prefill` or :func:`_attend_cached`. The block never
    writes a cache: what the core hands out is returned for the caller
    to drop, write whole or append. Returns (x, handed_out, MoE aux
    loss; a float32 zero for a dense FFN).
    Under compute_dtype="bfloat16": matmul operands and the carried
    activation are bf16; layernorm statistics fp32.

    ``tp_axis``/``expert_axis`` engage MANUAL tensor/expert parallelism
    for use inside a fully-manual shard_map region (parallel/transformer
    ``_blocks_fn``): bp arrives pre-sliced per param_pspecs — Wq/Wk/Wv/W1
    column-sliced and Wo/W2 row-sliced over ``tp_axis`` (Megatron
    column→row: one psum per sublayer, placed BEFORE the replicated bias
    add), MoE expert dim sliced over ``expert_axis``. Local head count is
    derived from the sliced Wq width, so the same code serves any tp
    degree (a size-1 axis psum is a no-op)."""
    b, T, d = x.shape
    cd = _cdtype(cfg)
    if cd is not None:
        x = x.astype(cd)
    bp = _cast_matmul_leaves(cd, bp)
    # under manual TP the head projections are column slices: this
    # shard owns d_local/head_dim of the hn heads
    d_local = bp["Wq"].shape[-1]
    hn_local = cfg.n_heads * d_local // d
    with _scope("attn"):
        a_in = _ln(x, bp["ln1_g"], bp["ln1_b"], cd)

        def heads(y):
            return y.reshape(b, T, hn_local, -1).transpose(0, 2, 1, 3)

        if cfg.fused_qkv:
            qkv = a_in @ jnp.concatenate(
                [bp["Wq"], bp["Wk"], bp["Wv"]], axis=-1)  # (b, T, 3*d_local)
            q, k, v = (heads(y) for y in jnp.split(qkv, 3, axis=-1))
        else:
            q, k, v = (heads(a_in @ bp[w]) for w in ("Wq", "Wk", "Wv"))
        o, handed_out = attend(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, T, d_local).astype(x.dtype)
        om = o @ bp["Wo"]
        if tp_axis is not None:
            om = jax.lax.psum(om, tp_axis)
        x = x + om + bp["bo"]
    with _scope("mlp"):
        m_in = _ln(x, bp["ln2_g"], bp["ln2_b"], cd)
        if cfg.n_experts > 0:
            from deeplearning4j_tpu.nn.conf.layers.moe import _moe_ffn

            y2, aux, _load = _moe_ffn(
                {k2: bp[k2] for k2 in ("Wg", "W1", "b1", "W2", "b2")},
                m_in.reshape(b * T, d), jax.nn.gelu,
                _moe_capacity(cfg, b * T), cfg.top_k,
                expert_axis=expert_axis, tp_axis=tp_axis,
            )
            x = x + y2.reshape(b, T, d).astype(x.dtype)
        else:
            h = jax.nn.gelu(m_in @ bp["W1"] + bp["b1"])
            hm = h @ bp["W2"]
            if tp_axis is not None:
                hm = jax.lax.psum(hm, tp_axis)
            x = x + hm + bp["b2"]
            aux = jnp.zeros((), jnp.float32)
    return x, handed_out, aux


def _attend_causal(attn_fn=None):
    """Attention core of ``forward`` and training: causal over the
    block's own keys, through ``attn_fn`` (default dense attention, which
    routes to the flash kernel; ring under SP). Hands out nothing."""
    fn = attn_fn if attn_fn is not None else dense_attention

    def attend(q, k, v):
        return fn(q, k, v, causal=True, mask=None), None

    return attend


def _attend_prefill(kv_dtype):
    """Attention core of ``prefill_cache``: the causal core, and the
    block's keys and values handed out as the cache holds them:
    ``kv_dtype``, (b, hn, hd, Tp)."""
    causal = _attend_causal()

    def attend(q, k, v):
        with _scope("kv_write"):
            kt = k.astype(kv_dtype).transpose(0, 1, 3, 2)
            vt = v.astype(kv_dtype).transpose(0, 1, 3, 2)
        return causal(q, k, v)[0], (kt, vt)

    return attend


def _attend_cached(kc, vc, live, causal):
    """Attention core of ``decode_step`` (K = 1) and ``decode_steps``:
    the step's K queries (b, hn, K, hd) attend, under one softmax
    (:func:`_joint_softmax`), to the cache columns ``live`` marks (kc,
    vc: one layer's (b, hn, hd, T); live (b, 1, 1, T): below the row's
    position) and, through ``causal`` (K, K), to columns 0..j of their
    own block. Hands out the step's new keys and values, (b, hn, K, hd)
    in the cache's dtype.

    The cache contract, for every caller. The cache is READ-ONLY inside
    the layer loop, and both einsums read it where it lies (time minor).
    The caller stacks what each layer hands out and writes it AFTER the
    loop, in place, one column a row and position (:func:`_put_columns`)
    at ``min(position, T-1)``: a write inside the loop, or a column that
    first reads its old value, makes XLA convert each layer's slice to
    the write's layout and back and copy the whole stacked cache twice
    more, every step (PERF.md section 5). A column whose position falls
    past the cache is DROPPED by that write, never left clipped over the
    real write of a row whose last token sits exactly at the edge; its
    position embedding was clipped, so callers never ACCEPT one. Stale
    columns at and past a row's position (a rejected draft, a prefill's
    padding) are never read and are overwritten as the row advances:
    rolling back is free. The math is row-independent, so a row decoded
    among other slots is bit-identical to the same row decoded alone
    (parity-asserted in tests/test_generate.py). The engine's one-token
    step takes :func:`_attend_live_tiles` in this core's stead where the
    kernel registry admits the slabs (:func:`_decode_columns`); this is
    the reference it is held to, and every other caller's path.

    MoE: the block routes only the b * K tokens of the step (per-step
    capacity) where the full forward competes all window tokens, so when
    training-time capacity BINDS (dropped tokens) cached decoding can
    legitimately differ from ``generate``; and K > 1 would compete b * K
    where sequential decode competes b, so ``decode_steps`` refuses MoE."""
    kvd = kc.dtype

    def attend(q, k, v):
        scale = 1.0 / math.sqrt(q.shape[-1])
        kn, vn = k.astype(kvd), v.astype(kvd)
        s_slab = jnp.einsum("bhkd,bhdt->bhkt", q,
                            kc).astype(jnp.float32) * scale
        s_slab = jnp.where(live, s_slab, -1e30)
        s_new = jnp.einsum("bhkd,bhjd->bhkj", q,
                           kn).astype(jnp.float32) * scale
        s_new = jnp.where(causal, s_new, -1e30)
        p_slab, p_new = _joint_softmax(s_slab, s_new, kvd)
        o = jnp.einsum("bhkt,bhdt->bhkd", p_slab, vc,
                       preferred_element_type=jnp.float32)
        o = o + jnp.einsum("bhkj,bhjd->bhkd", p_new, vn,
                           preferred_element_type=jnp.float32)
        return o, (kn, vn)

    return attend


def _attend_live_tiles(core, k_slab, v_slab, layer, table):
    """:func:`_attend_cached` for ONE query a row (K = 1) through the
    kernel ``core`` (``nn/ops/decode_attention.py``): layer ``layer`` of
    the whole stacked slabs (L, b, hn, hd, T), read where they lie and only
    in the column tiles ``table`` (``live_tiles`` of the rows' lengths)
    names, under the same joint softmax with the step's own key and value.
    Hands out what :func:`_attend_cached` does."""
    kvd = k_slab.dtype

    def attend(q, k, v):
        kn, vn = k.astype(kvd), v.astype(kvd)
        # (b, hn, 1, hd) is (slots, key heads, one query head each, hd)
        o = core(q, kn[:, :, 0], vn[:, :, 0], k_slab, v_slab, layer, table,
                 scale=1.0 / math.sqrt(q.shape[-1]))
        return o, (kn, vn)

    return attend


def block_apply(cfg: TransformerLMConfig, bp: Dict[str, Array], x: Array,
                attn_fn=None, tp_axis: Optional[str] = None,
                expert_axis: Optional[str] = None):
    """:func:`_block` with the causal core, for callers that scan the
    stack themselves (the pipeline stages of parallel/transformer.py).
    Dense FFN → returns x. MoE (cfg.n_experts > 0) → returns (x, aux)."""
    x, _, aux = _block(cfg, bp, x, _attend_causal(attn_fn),
                       tp_axis=tp_axis, expert_axis=expert_axis)
    return (x, aux) if cfg.n_experts > 0 else x


def _embed(cfg: TransformerLMConfig, params: Dict[str, Array], ids: Array,
           positions):
    """Token rows + position rows, in the compute dtype (the stable
    scan-carry dtype; the blocks keep it). ``positions`` indexes the
    position table's rows: a static slice for a whole sequence, or an
    int array shaped like ids (cached decode: each token's own). An
    array is CLIPPED to the table, not filled with ``jnp.take``'s
    default NaN: a column past the table is dropped from the cache but
    still sits in its block's softmax at probability 0, and 0 * NaN
    would reach its row."""
    with _scope("embed"):
        ptab = params["pos"].at[positions].get(mode="clip")
        x = params["embed"][ids] + ptab
        cd = _cdtype(cfg)
        return x if cd is None else x.astype(cd)


def _head(cfg: TransformerLMConfig, params: Dict[str, Array], x: Array,
          cast_logits: bool = True):
    """Final norm + vocabulary projection on x (..., d). Logits are fp32
    for the inference APIs; ``cast_logits=False`` keeps them in the
    compute dtype — the loss path's choice, so no full-vocab fp32 tensor
    is materialized (see ``token_nll``)."""
    cd = _cdtype(cfg)
    with _scope("head"):
        x = _ln(x, params["lnf_g"], params["lnf_b"], cd)
        logits = x @ _cast_matmul_leaves(cd, params)["head"]
        return logits.astype(jnp.float32) if cast_logits else logits


class ContextWindowExceeded(ValueError):
    """prompt_len + max_new would overflow the model's fixed
    ``max_length`` context window (the KV cache slab / positional table
    bound). Typed so serving layers can reject with a 4xx naming the
    limit instead of a bare ValueError; carries the numbers as
    attributes for programmatic handling."""

    def __init__(self, prompt_len: int, max_new: int, max_length: int):
        self.prompt_len = int(prompt_len)
        self.max_new = int(max_new)
        self.max_length = int(max_length)
        super().__init__(
            f"prompt ({prompt_len}) + max_new ({max_new}) exceeds the "
            f"model's max_length context window ({max_length}); shorten "
            f"the prompt, reduce max_new, or use generate() (which "
            f"windows to the most recent max_length tokens)")


def _validate_sampling(temperature: float, top_k: int, top_p: float) -> None:
    if (top_k or top_p) and temperature <= 0:
        raise ValueError("top_k/top_p sampling requires temperature > 0")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if top_p and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def _sample_next(logits: np.ndarray, temperature: float, top_k: int,
                 top_p: float, rng):
    """(b, V) logits → ((b,) int32 next ids, new rng). Greedy at
    temperature<=0; otherwise temperature + optional top-k then nucleus
    filtering (the shared sampler behind generate/generate_cached)."""
    if temperature <= 0:
        return logits.argmax(-1).astype(np.int32), rng
    logits = logits / temperature
    if top_k and top_k < logits.shape[-1]:
        kth = np.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = np.where(logits < kth, -np.inf, logits)
    if top_p and 0.0 < top_p < 1.0:
        order = np.argsort(-logits, axis=-1)
        sorted_l = np.take_along_axis(logits, order, -1)
        p_sorted = np.exp(sorted_l - sorted_l.max(-1, keepdims=True))
        p_sorted /= p_sorted.sum(-1, keepdims=True)
        cum = np.cumsum(p_sorted, -1)
        # keep tokens up to AND including the one crossing p
        cut = cum - p_sorted >= top_p
        sorted_l = np.where(cut, -np.inf, sorted_l)
        inv = np.argsort(order, axis=-1)
        logits = np.take_along_axis(sorted_l, inv, -1)
    rng, k = jax.random.split(rng)
    nxt = np.asarray(
        jax.random.categorical(k, jnp.asarray(logits))
    ).astype(np.int32)
    return nxt, rng


def sampling_needs(temperature, top_k, top_p, vocab: int):
    """What a batch's sampling policies ask of the sampler: ``(draws,
    filters)``, two scalar booleans. *draws*: some row has
    ``temperature > 0`` (a categorical draw is read); *filters*: some
    row draws AND has a filter that cuts (``0 < top_k < vocab`` or
    ``0 < top_p < 1``). A row whose result is thrown away is handed in
    with ``temperature`` 0. Operators and ``.any()`` only, so NumPy and
    JAX arrays (scalar or per row) serve alike: the device sampler
    branches on it and ``GenerationEngine`` counts with it from its
    host copy of the slots' policies."""
    draws = temperature > 0
    filters = draws & (((top_k > 0) & (top_k < vocab))
                       | ((top_p > 0) & (top_p < 1)))
    return draws.any(), filters.any()


def _col(x):  # a scalar knob stays scalar; (b,) broadcasts per row
    return x if jnp.ndim(x) == 0 else x[:, None]


def _scale_logits(logits, temperature):
    return logits / _col(jnp.where(temperature > 0, temperature, 1.0))


def _filter_logits(logits, temperature, top_k, top_p):
    """Shared in-graph sampling filter: (b, V) fp32 logits →
    temperature-scaled, top-k- and nucleus-filtered logits. The policy
    knobs may be scalars (one policy for the batch — the solo fused
    decode) or per-row (b,) arrays (the continuous-batching engine: each
    slot its own policy); every op is row-wise either way, so a row
    filtered among other slots is bit-identical to the same row filtered
    alone. All policy decisions INSIDE are data-dependent ``where``
    selects, so a row whose filters are off comes back as exactly its
    scaled logits, permuted and permuted back. Its one sort, two
    argsorts and three gathers over (b, V) run only in the third branch
    of :func:`_sample_branches`: when some row that draws has a filter
    that cuts."""
    V = logits.shape[-1]
    l = _scale_logits(logits, temperature)
    # top-k: keep the k highest (filter active only for 0 < k < V)
    k_eff = jnp.clip(top_k, 1, V)
    use_k = (top_k > 0) & (top_k < V)
    sorted_asc = jnp.sort(l, axis=-1)
    kth = jnp.take_along_axis(
        sorted_asc, jnp.broadcast_to(_col(V - k_eff),
                                     (l.shape[0], 1)), axis=-1)
    l = jnp.where(_col(use_k) & (l < kth), -jnp.inf, l)
    # nucleus: smallest prefix of descending-prob tokens reaching top_p
    use_p = (top_p > 0.0) & (top_p < 1.0)
    order = jnp.argsort(-l, axis=-1)
    sl = jnp.take_along_axis(l, order, -1)
    p_sorted = jnp.exp(sl - sl.max(-1, keepdims=True))
    p_sorted = p_sorted / p_sorted.sum(-1, keepdims=True)
    cum = jnp.cumsum(p_sorted, -1)
    # keep tokens up to AND including the one crossing p (host parity)
    cut = cum - p_sorted >= _col(top_p)
    sl = jnp.where(_col(use_p) & cut, -jnp.inf, sl)
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(sl, inv, -1)


def _sample_branches(logits, temperature, top_k, top_p, draw):
    """The one sampler body behind :func:`sample_next_device` and
    :func:`sample_next_rows`: ONE compiled program, three branches
    chosen on the device by :func:`sampling_needs` of the rows in front
    of it, so a step pays only for what its policies read:

    0. no row draws: ``argmax`` alone — no scale, sort, gather or draw;
    1. rows draw, none filters: scale by temperature and ``draw``;
    2. some row filters: :func:`_filter_logits`, then ``draw``.

    ``draw`` maps (b, V) filtered logits to (b,) sampled ids (the
    callers' key handling differs, nothing else). Every row's id is
    bit-identical to the unbranched sampler's (always filter, always
    draw, keep ``argmax`` where ``temperature <= 0``) for every policy
    and every mix of policies in a batch: a greedy row never read the
    draw, and with both filters off ``_filter_logits`` returns the
    scaled logits themselves. Do not ``vmap`` this function: a batched
    predicate turns the conditional into a select that runs every
    branch."""
    temperature, top_k, top_p = (jnp.asarray(temperature),
                                 jnp.asarray(top_k), jnp.asarray(top_p))
    greedy = jnp.argmax(logits, axis=-1)
    draws, filters = sampling_needs(temperature, top_k, top_p,
                                    logits.shape[-1])
    sampled = jax.lax.switch(
        draws.astype(jnp.int32) + filters.astype(jnp.int32),
        (lambda: greedy,
         lambda: draw(_scale_logits(logits, temperature)),
         lambda: draw(_filter_logits(logits, temperature, top_k, top_p))))
    return jnp.where(temperature <= 0, greedy, sampled).astype(jnp.int32)


def sample_next_device(logits, temperature, top_k, top_p, key):
    """In-graph mirror of :func:`_sample_next`: (b, V) fp32 logits →
    ((b,) int32 next ids, advanced key). One key chain for the whole
    batch, exactly like the host sampler — the solo
    ``generate_cached`` fused path. One program, three branches
    (:func:`_sample_branches`): a greedy call runs an ``argmax`` and
    nothing else.

    Parity: greedy and temperature/top-k outputs are bit-identical to
    the host sampler for the same key (sort/compare/divide are exact and
    the categorical draw uses the same key chain). top-p's cumsum may
    differ from NumPy's in reduction order, so nucleus CUTOFFS can
    differ at ties on the boundary — tolerance documented in
    ARCHITECTURE § Continuous batching. The key is split every call,
    outside the branches (a data-independent chain), even under greedy,
    which ignores it."""
    with _scope("sample"):
        key, sub = jax.random.split(key)
        nxt = _sample_branches(
            logits, temperature, top_k, top_p,
            lambda l: jax.random.categorical(sub, l))
        return nxt, key


def sample_next_rows(logits, temperature, top_k, top_p, keys):
    """Per-row variant for the continuous-batching engine: (b, V)
    logits, per-row policy knobs (b,) and per-row keys (b, 2) → ((b,)
    ids, advanced keys). The filter is the shared BATCHED implementation
    (vmapping the sorts is ruinously slow on XLA:CPU); only the
    per-key split + categorical draw are vmapped, and the draw uses a
    (1, V) lane exactly like a solo b=1 call — so lane s is bit-
    identical to ``sample_next_device(logits[s:s+1], ..., keys[s])``
    (counter-based PRNG + vmap semantics), which is what makes engine
    output ≡ solo output. The branch (:func:`_sample_branches`) is
    taken for the batch: one row that filters makes every row pay the
    sorts, and none changes what it gets. A caller that throws rows
    away (inactive slots) hands them ``temperature`` 0, so that a freed
    slot's last policy opens no branch."""
    with _scope("sample"):
        splits = jax.vmap(jax.random.split)(keys)  # (b, 2, 2)
        nkeys, subs = splits[:, 0], splits[:, 1]
        nxt = _sample_branches(
            logits, temperature, top_k, top_p,
            lambda l: jax.vmap(
                lambda k, row: jax.random.categorical(k, row[None])[0])(
                    subs, l))
        return nxt, nkeys


def init_decode_cache(cfg: TransformerLMConfig, batch: int,
                      max_length: Optional[int] = None) -> Dict:
    """Preallocated per-layer KV cache for single-token decoding: static
    (L, b, heads, head_dim, max_length) buffers + a position counter —
    TPU-friendly (no growing shapes; writes are dynamic_update slices).
    Time is the MINOR axis for K and V alike: it is the layout both
    decode einsums (``bhd,bhdt->bht``, ``bht,bhdt->bhd``) read without a
    conversion, and head_dim 64 as the minor axis would pad every bf16
    tile to 128 lanes. Every reader and writer of the cache (prefill,
    decode, speculative verify, the serving engine's slab and its prefix
    cache) uses this one layout.
    ``max_length`` overrides the slab's time extent (the continuous-
    batching engine sizes its slots independently of the model's full
    window); default is ``cfg.max_length``."""
    cd = _cdtype(cfg) or jnp.float32
    hd = cfg.d_model // cfg.n_heads
    T = cfg.max_length if max_length is None else int(max_length)
    shape = (cfg.n_layers, batch, cfg.n_heads, hd, T)
    return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd),
            "pos": jnp.zeros((), jnp.int32)}


def prefill_cache(cfg: TransformerLMConfig, params: Dict[str, Array],
                  cache: Dict, ids: Array, length=None):
    """Batched prompt prefill: ids (b, Tp) int32 into a fresh cache →
    (last-position logits (b, V) fp32, cache with pos=Tp). One device
    launch regardless of prompt length (:func:`_attend_prefill`: causal
    attention within the prompt; all layers' Tp columns are written
    after the loop as one slice); MoE routing competes all b*Tp prompt
    tokens, exactly like ``forward``.

    ``length`` (traced scalar int32, <= Tp) marks the REAL prompt length
    when ids is right-padded up to a bucketed Tp: logits are gathered at
    position length-1 and the cache's pos is set to length. Causal
    attention makes end-padding exact for dense models — position i
    attends only to <= i, so pad positions can never influence real
    ones, and their K/V is stale past pos (:func:`_attend_cached`). The
    one exception is MoE (cfg.n_experts > 0), where pad tokens compete
    for expert capacity — callers keep MoE prefill unbucketed (see
    ``TransformerLM.generate_cached``)."""
    Tp = ids.shape[1]
    x = _embed(cfg, params, ids, slice(0, Tp))
    attend = _attend_prefill(cache["k"].dtype)

    def body(x, bp):
        x, kv, _aux = _block(cfg, bp, x, attend)
        return x, kv

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    with _scope("kv_write"):
        origin = (0, 0, 0, 0, 0)
        new_k = jax.lax.dynamic_update_slice(cache["k"], ks, origin)
        new_v = jax.lax.dynamic_update_slice(cache["v"], vs, origin)
    if length is None:
        x_last = x[:, -1]
        pos_out = jnp.asarray(Tp, jnp.int32)
    else:
        pos_out = jnp.asarray(length, jnp.int32)
        x_last = jax.lax.dynamic_index_in_dim(x, pos_out - 1, axis=1,
                                              keepdims=False)
    return _head(cfg, params, x_last), {"k": new_k, "v": new_v,
                                        "pos": pos_out}


def _joint_softmax(s_slab, s_new, dtype):
    """One softmax over the cache's scores (..., T) and the step's own
    (..., K), which are never concatenated: the same max and the same
    sum for both parts, probabilities cast to ``dtype`` (the cache's).
    The step's own scores always hold a live entry, so a row that reads
    nothing of the cache (pos 0) is finite."""
    m = jnp.maximum(s_slab.max(axis=-1), s_new.max(axis=-1))[..., None]
    e_slab, e_new = jnp.exp(s_slab - m), jnp.exp(s_new - m)
    z = (e_slab.sum(axis=-1) + e_new.sum(axis=-1))[..., None]
    return (e_slab / z).astype(dtype), (e_new / z).astype(dtype)


def _put_columns(slab, new, wp, active=None):
    """The after-loop cache write: new (L, b, hn, K, hd), row s's column
    j → slab[:, s, :, :, wp[s, j]], in place, in the slab's own layout.

    One column a row (K = 1) goes through ONE Pallas call a slab where
    the kernel registry admits the slab (``nn/ops/kv_column_write.py``:
    a TPU, whole blocks of 128 columns, no mesh): it reads and rewrites
    only the 128-column blocks that hold the positions of the rows
    ``active`` (b,) bool names (all of them when None); an idle row's
    column, stale at or past its position, is never read
    (:func:`_attend_cached`), so it is not written.

    Everywhere else each column is ONE ``dynamic_update_slice`` on the
    (donated) slab, every row's. A scatter here picks its layout for the
    whole slab and brings two slab-sized copies a step back; so does a
    column that first reads its old value (the (L, 1, hn, hd, 1) read;
    along the slot axis it also gathers a slot-sharded slab), and a
    K-wide update clamps its start and shifts the block (PERF.md
    section 5).

    ``wp`` is clamped to T-1 by the caller, and a row's columns go
    last-first: every column past the end lands on T-1 BEFORE the
    column that belongs there (there is one whenever the row's first
    position is <= T-1), so columns past the end are dropped, never
    left clipped over a real write."""
    if new.shape[3] == 1:
        put = kv_column_write_impl(*slab.shape, slab.dtype)
        if put is not None:
            live = (jnp.ones(new.shape[1], bool) if active is None
                    else active)
            return put(slab, new[:, :, :, 0], wp[:, 0], live_table(live))
    for s in range(new.shape[1]):
        for j in reversed(range(new.shape[3])):
            slab = jax.lax.dynamic_update_slice(
                slab, new[:, s:s + 1, :, j, :, None], (0, s, 0, 0, wp[s, j]))
    return slab


def _decode_columns(cfg: TransformerLMConfig, params: Dict[str, Array],
                    cache: Dict, ids_k: Array, pos: Array, lengths=None):
    """The layer loop of cached decoding: ids_k (b, K) with row s's
    column j at position pos[s] + j (pos (b,)) → (x (b, K, d) before the
    head, the layers' new keys and values (L, b, hn, K, hd), the clamped
    write positions (b, K)). The cache is only read
    (:func:`_attend_cached`); the caller writes.

    ``lengths`` (b,), the columns each row reads (its position; 0 for a
    row that does not stream), comes with the engine's one-token step:
    where the kernel registry admits the slabs
    (``nn/ops/decode_attention.py``: a TPU, a tile that divides T, no
    mesh, a layer's K + V worth a call) the layers attend through ONE
    Pallas call each that reads the live column tiles only
    (:func:`_attend_live_tiles`). The slabs are then closed over whole and
    the loop scans the layer's index (a custom call cannot read through a
    scan's slice as a fusion does: it would be handed a copy of it), and
    the walk over the live tiles is made once, here, for every layer."""
    L, b, hn, hd, T = cache["k"].shape
    K = ids_k.shape[1]
    cols = pos[:, None] + jnp.arange(K)[None, :]  # (b, K) absolute pos
    x = _embed(cfg, params, ids_k, cols)
    core = None
    if lengths is not None and K == 1:
        core = decode_attention_impl(b, hn, 1, hd, hd, T, cache["k"].dtype)
    if core is not None:
        attend_tiles, tile = core
        table = live_tiles(lengths, T, tile)

        def at_layer(x, xs):
            bp, layer = xs
            x, kv, _aux = _block(cfg, bp, x, _attend_live_tiles(
                attend_tiles, cache["k"], cache["v"], layer, table))
            return x, kv

        x, (ks, vs) = jax.lax.scan(
            at_layer, x, (params["blocks"], jnp.arange(L, dtype=jnp.int32)))
        return x, ks, vs, jnp.minimum(cols, T - 1)
    live = (jnp.arange(T)[None, :] < pos[:, None])[:, None, None, :]
    causal = jnp.arange(K)[None, :] <= jnp.arange(K)[:, None]  # (K, K)

    def body(x, xs):
        bp, kc, vc = xs  # kc/vc: (b, hn, hd, T), never written here
        x, kv, _aux = _block(cfg, bp, x, _attend_cached(kc, vc, live, causal))
        return x, kv

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]))
    return x, ks, vs, jnp.minimum(cols, T - 1)


def decode_step(cfg: TransformerLMConfig, params: Dict[str, Array],
                cache: Dict, ids_1: Array, active: Optional[Array] = None):
    """One autoregressive step: ids_1 (b,) int32 at position cache["pos"]
    → (logits (b, V) fp32, new cache). Attention reads the cached K/V
    instead of re-running the prefix — O(T) decoding vs the O(T²)
    full-forward loop; greedy-parity tested against ``forward`` in
    tests/test_moe.py. The K = 1 case of ``decode_steps``; the cache
    contract and the MoE caveat are :func:`_attend_cached`'s.

    ``cache["pos"]`` may be a scalar (every row at the same position —
    the single-request path: one column written for all rows) or a
    per-row (b,) vector (the continuous-batching engine: each slot
    carries its own position and its column is written there; with
    ``active`` (b,) bool, only the live rows' need be written,
    :func:`_put_columns`, and only theirs are read where the layers
    attend through the live-tile kernel, :func:`_decode_columns`)."""
    pos = cache["pos"]
    per_row = getattr(pos, "ndim", 0) == 1
    if per_row:  # the engine's step: a row reads what lies behind it
        x, ks, vs, wp = _decode_columns(
            cfg, params, cache, ids_1[:, None], pos,
            pos if active is None else jnp.where(active, pos, 0))
    else:
        x, ks, vs, wp = _decode_columns(
            cfg, params, cache, ids_1[:, None],
            jnp.broadcast_to(pos, ids_1.shape))
    with _scope("kv_write"):
        if per_row:  # one in-place column a slot
            new_k = _put_columns(cache["k"], ks, wp, active)
            new_v = _put_columns(cache["v"], vs, wp, active)
        else:  # one column for all rows
            at = (0, 0, 0, 0, wp[0, 0])
            new_k = jax.lax.dynamic_update_slice(
                cache["k"], ks[:, :, :, 0, :, None], at)
            new_v = jax.lax.dynamic_update_slice(
                cache["v"], vs[:, :, :, 0, :, None], at)
    return _head(cfg, params, x[:, 0]), {"k": new_k, "v": new_v,
                                         "pos": pos + 1}


def decode_steps(cfg: TransformerLMConfig, params: Dict[str, Array],
                 cache: Dict, ids_k: Array):
    """K-column decode for speculative verification: ids_k (b, K) int32
    where column 0 sits at per-row position ``cache["pos"]`` (a (b,)
    vector) and column j at pos+j → (logits (b, K, V) fp32, new cache).
    One dispatch scores all K positions: column j's logits are the
    model's next-token distribution AFTER consuming ids_k[:, :j+1], so a
    draft token at column j+1 is verified against logits[:, j] — exactly
    the distribution token-by-token decode would have produced, which is
    what makes speculative acceptance exact.

    All K columns are written after the loop; the cache contract is
    :func:`_attend_cached`'s. Callers must never ACCEPT a column at
    pos+j > T-1; the engine clamps draft lengths to the window. A row at
    pos >= T is outside the contract, as in ``decode_step``: its clamped
    write stays on T-1. MoE is unsupported; callers keep MoE engines at
    k=1."""
    if cfg.n_experts > 0:
        raise ValueError("decode_steps does not support MoE models "
                         "(per-step routing capacity differs from "
                         "sequential decode); use decode_step")
    pos = cache["pos"]
    x, ks, vs, wp = _decode_columns(cfg, params, cache, ids_k, pos)
    with _scope("kv_write"):
        new_k = _put_columns(cache["k"], ks, wp)
        new_v = _put_columns(cache["v"], vs, wp)
    return _head(cfg, params, x), {"k": new_k, "v": new_v,
                                   "pos": pos + ids_k.shape[1]}


def prefill_bucket_lengths(max_length: int, hint=None):
    """Ascending prompt-length bucket list for prefill padding — the
    ``serving_seq_buckets`` discipline applied to the decode path: every
    prefill pads its prompt up to one of these lengths, so the jitted
    prefill compiles a BOUNDED program set instead of one program per
    distinct prompt length. ``hint`` (a model's ``serving_seq_buckets``)
    is filtered to <= max_length; default is powers of two from 8. The
    list always ends at ``max_length`` so any window-legal prompt has a
    bucket."""
    max_length = int(max_length)
    if hint:
        bs = sorted({int(t) for t in hint if 0 < int(t) <= max_length})
    else:
        bs, b = [], 8
        while b < max_length:
            bs.append(b)
            b *= 2
    if not bs or bs[-1] != max_length:
        bs.append(max_length)
    return bs


def forward(cfg: TransformerLMConfig, params: Dict[str, Array], ids: Array,
            attn_fn=None, pos_offset: int = 0, return_aux: bool = False,
            cast_logits: bool = True):
    """ids (b, T) int32 → logits (b, T, V) [, total MoE aux loss].
    Single-device path: blocks via lax.scan over the stacked layer axis.
    ``cast_logits``: see :func:`_head`."""
    x = _embed(cfg, params, ids,
               slice(pos_offset, pos_offset + ids.shape[1]))
    attend = _attend_causal(attn_fn)

    def body(carry, bp):
        x, aux = carry
        x, _kv, a = _block(cfg, bp, x, attend)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    logits = _head(cfg, params, x, cast_logits)
    if return_aux:
        return logits, aux
    return logits


def token_nll(logits, targets):
    """Per-token next-token NLL in the logsumexp - target-logit form:
    ``nll = lse(logits) - logits[target]``. Unlike
    ``log_softmax + gather``, no full-vocab log-prob tensor exists — the
    fp32 cast feeds only reductions and a gather, which XLA fuses, so at
    V=32k the loss head's HBM traffic drops by two full-vocab fp32
    passes per step (the LM step's single largest activation).
    logits (..., V) any float dtype; targets (...) int32, -1 = ignore.
    Returns (mean_nll, valid_count)."""
    with _scope("loss"):
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        tgt = jnp.maximum(targets, 0)
        tgt_logit = jnp.take_along_axis(lf, tgt[..., None], axis=-1)[..., 0]
        valid = (targets >= 0).astype(jnp.float32)
        nll = (lse - tgt_logit) * valid
        count = jnp.maximum(jnp.sum(valid), 1.0)
        return jnp.sum(nll) / count, count


def lm_loss(cfg: TransformerLMConfig, params, ids, targets, attn_fn=None,
            segment_ids=None):
    """Mean next-token cross-entropy (+ weighted MoE aux loss when MoE).
    targets (b, T) int32 (-1 = ignore).

    ``segment_ids``: optional (b, T) int array for PACKED-sequence
    training (multiple documents per row): attention stays within each
    segment (dense_attention routes to the Pallas flash kernel's
    segment path when available). Cross-segment next-token targets
    should carry -1 so the boundary token doesn't predict into the next
    document."""
    if segment_ids is not None:
        if attn_fn is not None:
            raise ValueError("pass segment_ids OR a custom attn_fn, "
                             "not both")
        seg = segment_ids

        def attn_fn(q, k, v, *, causal, mask=None):
            return dense_attention(q, k, v, causal=causal, mask=mask,
                                   segment_ids=seg)

    logits, aux = forward(cfg, params, ids, attn_fn=attn_fn, return_aux=True,
                          cast_logits=False)
    loss, _ = token_nll(logits, targets)
    if cfg.n_experts > 0:
        with _scope("loss"):
            loss = loss + cfg.aux_loss_weight * aux
    return loss


class TransformerLM(ZooModel):
    """Zoo wrapper with a simple single-device fit/generate surface; the
    distributed path is parallel/transformer.py's DistributedLMTrainer."""

    name = "transformerlm"

    #: prompt-length buckets for KV-cache prefill (filtered to the
    #: instance's max_length at use; see ``prefill_bucket_lengths``) —
    #: the generation counterpart of the forward path's seq buckets
    serving_seq_buckets = (16, 32, 64, 128, 256, 512)

    def __init__(self, vocab_size: int = 1000, d_model: int = 256,
                 n_heads: int = 4, n_layers: int = 4, mlp_ratio: int = 4,
                 max_length: int = 512, seed: int = 123, n_experts: int = 0,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 aux_loss_weight: float = 1e-2,
                 compute_dtype: Optional[str] = None,
                 fused_qkv: bool = False, **kwargs):
        super().__init__(num_classes=vocab_size, seed=seed, **kwargs)
        self.cfg = TransformerLMConfig(
            vocab_size, d_model, n_heads, n_layers, mlp_ratio, max_length,
            seed=seed, n_experts=n_experts, top_k=top_k,
            capacity_factor=capacity_factor, aux_loss_weight=aux_loss_weight,
            compute_dtype=compute_dtype, fused_qkv=fused_qkv,
        )
        self.params_: Optional[Dict] = None
        self.opt_state_: Optional[Dict] = None
        #: no layer running-state (the InferenceEngine snapshot surface
        #: reads this attribute on every served model)
        self.state_ = None
        self._jit_cache: Dict = {}
        #: fn-name → number of XLA programs traced (bumped at trace time
        #: inside the jitted callables — the retrace-guard instrument,
        #: same pattern as InferenceEngine.compile_count)
        self.trace_counts: Dict[str, int] = {}
        self.iteration = 0
        self.score_ = None

    def _bump_trace(self, key: str) -> None:
        counts = getattr(self, "trace_counts", None)
        if counts is None:  # models deserialized from older checkpoints
            counts = self.trace_counts = {}
        counts[key] = counts.get(key, 0) + 1

    def init(self):
        self.params_ = init_params(self.cfg)
        from deeplearning4j_tpu.updaters import Adam

        self.updater = self.kwargs.get("updater", Adam(3e-4))
        self.opt_state_ = jax.tree_util.tree_map(
            lambda a: self.updater.init_state(a), self.params_
        )
        return self

    def _make_step(self, with_seg: bool = False):
        cfg, upd = self.cfg, self.updater

        def step(params, opt_state, ids, targets, t, seg=None):
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(cfg, p, ids, targets,
                                  segment_ids=seg if with_seg else None)
            )(params)

            flat_p, treedef = jax.tree_util.tree_flatten(params)
            flat_g = treedef.flatten_up_to(grads)
            flat_o = treedef.flatten_up_to(opt_state)
            new_p, new_o = [], []
            with _scope("update"):
                for p, g, o in zip(flat_p, flat_g, flat_o):
                    delta, o2 = upd.apply(g, o, t, t, 0)
                    new_p.append(p - delta)
                    new_o.append(o2)
            return (jax.tree_util.tree_unflatten(treedef, new_p),
                    jax.tree_util.tree_unflatten(treedef, new_o), loss)

        return jax.jit(step, donate_argnums=(0, 1))

    def fit_batch(self, ids: np.ndarray, targets: np.ndarray,
                  segment_ids: Optional[np.ndarray] = None) -> float:
        """One train step. ``segment_ids`` (b, T) int enables
        packed-sequence training (see ``lm_loss``)."""
        key = "step_seg" if segment_ids is not None else "step"
        if key not in self._jit_cache:
            self._jit_cache[key] = self._make_step(
                with_seg=segment_ids is not None)
        self.iteration += 1
        _trace.set_cause(self.iteration)
        with _PUT_BATCH:
            args = [self.params_, self.opt_state_,
                    jnp.asarray(ids, jnp.int32),
                    jnp.asarray(targets, jnp.int32),
                    jnp.asarray(self.iteration, jnp.int32)]
            if segment_ids is not None:
                args.append(jnp.asarray(segment_ids, jnp.int32))
        with _DISPATCH:
            self.params_, self.opt_state_, self.score_ = \
                self._jit_cache[key](*args)
        with _FETCH_LOSS:
            return float(self.score_)

    def logits(self, ids: np.ndarray) -> np.ndarray:
        if "fwd" not in self._jit_cache:
            self._jit_cache["fwd"] = jax.jit(
                lambda p, i: forward(self.cfg, p, i)
            )
        return np.asarray(self._jit_cache["fwd"](self.params_,
                                                 jnp.asarray(ids, jnp.int32)))

    def output(self, x, mask=None) -> np.ndarray:
        """Generic serving surface (the InferenceEngine fallback path —
        lets ``cli serve --model transformerlm`` stand up /predict next
        to /generate): token ids (b, T) → fp32 logits (b, T, V)."""
        return self.logits(np.asarray(x).astype(np.int32))

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params_))

    def generate(self, prompt_ids: np.ndarray, max_new: int = 20,
                 temperature: float = 0.0, rng=None, top_k: int = 0,
                 top_p: float = 0.0) -> np.ndarray:
        """Greedy/temperature sampling continuation (host loop; each step
        re-runs the jitted forward on the growing prefix). Contexts longer
        than ``cfg.max_length`` are windowed to the most recent
        ``max_length`` tokens — the positional table bounds the forward.

        ``top_k`` > 0 restricts sampling to the k highest-probability
        tokens; ``top_p`` in (0, 1] to the smallest nucleus whose
        cumulative probability reaches p. Both require temperature > 0
        and compose (top-k filter, then nucleus)."""
        ids = np.asarray(prompt_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        _validate_sampling(temperature, top_k, top_p)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        for _ in range(max_new):
            window = ids[:, -self.cfg.max_length:]
            logits = self.logits(window)[:, -1]
            nxt, rng = _sample_next(logits, temperature, top_k, top_p, rng)
            ids = np.concatenate([ids, nxt[:, None]], axis=1)
        return ids

    def prefill_buckets(self):
        """The bounded prefill program set: prompt lengths pad up to
        these (class hint filtered to this instance's max_length)."""
        return prefill_bucket_lengths(self.cfg.max_length,
                                      self.serving_seq_buckets)

    def generate_cached(self, prompt_ids: np.ndarray, max_new: int = 20,
                        temperature: float = 0.0, rng=None, top_k: int = 0,
                        top_p: float = 0.0) -> np.ndarray:
        """KV-cache decoding: the prompt prefills per-layer K/V buffers,
        then each new token is one O(T) ``decode_step`` instead of the
        O(T²) full-forward loop of ``generate`` (identical outputs —
        parity-tested; see ``sample_next_device`` for the one documented
        top-p tolerance). Raises :class:`ContextWindowExceeded` (a
        ValueError naming the limit) when prompt_len + max_new would
        overflow ``max_length`` — ``generate``'s windowing cannot apply
        here, the KV slab is the window.

        Zero host round-trips in the decode loop: sampling is fused into
        the jitted prefill/decode programs (``sample_next_device``), the
        sampled token feeds the next step as a device array, and the
        token stack is read back ONCE at the end. Prompt lengths pad up
        to ``prefill_buckets()`` so prefill compiles a bounded program
        set (the dense causal math is padding-exact; MoE prompts skip
        bucketing because pad tokens would compete for expert capacity —
        that path keeps one program per distinct prompt length).
        ``trace_counts`` records programs traced per function — the
        retrace-guard instrument."""
        ids = np.asarray(prompt_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        if ids.shape[1] + max_new > self.cfg.max_length:
            raise ContextWindowExceeded(ids.shape[1], max_new,
                                        self.cfg.max_length)
        _validate_sampling(temperature, top_k, top_p)
        if max_new <= 0:
            return ids
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if "decode_s" not in self._jit_cache:
            cfg = self.cfg

            def _dec(p, c, tok, t, k, pp, key):
                self._bump_trace("decode")
                logits, c = decode_step(cfg, p, c, tok)
                nxt, key = sample_next_device(logits, t, k, pp, key)
                return nxt, c, key

            def _pre(p, c, i, ln, t, k, pp, key):
                self._bump_trace("prefill")
                logits, c = prefill_cache(cfg, p, c, i, length=ln)
                nxt, key = sample_next_device(logits, t, k, pp, key)
                return nxt, c, key

            self._jit_cache["decode_s"] = jax.jit(_dec, donate_argnums=(1,))
            self._jit_cache["prefill_s"] = jax.jit(_pre, donate_argnums=(1,))
        b, Tp = ids.shape
        if self.cfg.n_experts > 0:
            ids_in = ids  # MoE: padding would perturb routing capacity
        else:
            Tb = next(t for t in self.prefill_buckets() if t >= Tp)
            ids_in = np.zeros((b, Tb), np.int32)
            ids_in[:, :Tp] = ids
        t_ = jnp.asarray(float(temperature), jnp.float32)
        k_ = jnp.asarray(int(top_k), jnp.int32)
        p_ = jnp.asarray(float(top_p), jnp.float32)
        cache = init_decode_cache(self.cfg, b)
        tok, cache, key = self._jit_cache["prefill_s"](
            self.params_, cache, jnp.asarray(ids_in),
            jnp.asarray(Tp, jnp.int32), t_, k_, p_, rng)
        toks = [tok]
        step = self._jit_cache["decode_s"]
        for _ in range(max_new - 1):
            tok, cache, key = step(self.params_, cache, tok, t_, k_, p_, key)
            toks.append(tok)
        gen = np.stack([np.asarray(tk) for tk in toks], axis=1)
        return np.concatenate([ids, gen.astype(np.int32)], axis=1)

    def perplexity(self, ids: np.ndarray, targets: np.ndarray) -> float:
        """exp(mean next-token NLL) over valid targets (-1 = ignore) —
        the LM evaluation counterpart of Evaluation.accuracy()."""
        if "ppl" not in self._jit_cache:
            self._jit_cache["ppl"] = jax.jit(
                lambda p, i, t: lm_loss(
                    TransformerLMConfig(**{**self.cfg.to_dict(),
                                           "aux_loss_weight": 0.0}),
                    p, i, t)
            )
        nll = self._jit_cache["ppl"](
            self.params_, jnp.asarray(ids, jnp.int32),
            jnp.asarray(targets, jnp.int32))
        return float(np.exp(np.asarray(nll)))

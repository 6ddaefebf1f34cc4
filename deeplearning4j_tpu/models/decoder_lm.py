"""DecoderLM: a causal decoder whose stack is DATA, served through
``GenerationEngine``.

``TransformerLM`` is one GPT-2 block scanned L times. Today's open
decoders are not that: RMSNorm, no biases, rotary positions on part of a
head, fewer key/value heads than query heads, query/key heads wider than
value heads, window layers (with a learned softmax sink) among full
layers, latent attention (alone or over an indexer's selection),
state-space layers, a leading dense layer and then expert layers of which
a chip holds its share, with or without a shared expert, a stack that runs
several times a token over one set of weights. Here all of that is
configuration (:class:`DecoderConfig`):

- ``attn_kinds`` names the kinds of MIXER, a layer's first half, and
  ``layers`` each layer's mixer kind and FFN kind; consecutive layers of
  one pair form a SEGMENT whose parameters are stacked and scanned;
- ONE block function (:func:`block`) serves the full forward, prefill
  and decode: the kind's mixer, then the FFN. Every decision about a
  mixer kind has ONE home, its entry (:class:`_Mixer`, resolved once by
  ``DecoderConfig.mixer``): its leaves, its part of the cache plan, how
  the layer loop reads its cache, the mixer itself and the two writes.
  ``_run_pass``, ``decode_step``, ``prefill_slot`` and the engine ask the
  entry and test no kind:

  entry           cache a segment               read in the layer loop
  --------------  ----------------------------  ------------------------------
  _KeysValues     K, V by head, T-minor, the    sliced by the scan (*)
                  slot's length
  _Ring           K, V rings of ``window``      sliced by the scan (*)
                  columns (window, sink)
  _Latent         one slab of kv_rank +         sliced by the scan (*)
                  rotary_dim values, T-minor
  _IndexedLatent  position-major rows (and an   whole by index, CARRIED; the
                  owner's indexer keys)         selection carried beside them
  _StateSpace     float32 state, the            CARRIED and WRITTEN in the
                  convolution's tail            loop
  _Parallel       K, V AND state, tail: one     K, V as _KeysValues; state and
                  entry of four slabs           tail as _StateSpace, at once

  entry           decode write         prefill write          decode kernel
  --------------  -------------------  ---------------------  ----------------------
  _KeysValues     a column a live row  the bucket's columns   decode_attention: K, V
                  (_put_columns)                              whole, live tiles' walk
  _Ring           one select over the  the last ``window``    none
                  whole ring           real columns
  _Latent         a column, the slab   the bucket's columns   latent_decode: the slab
                  as one head                                 whole, rows' lengths
  _IndexedLatent  a row a slot         the bucket's rows      sparse_latent_decode:
                                                              the live rows under the
                                                              selection's bias (else
                                                              a gather)
  _StateSpace     none (in the loop)   the state after the    ssm_decode: the live
                                       real tokens            slots' table
  _Parallel       K, V columns; the    both, each under its   both, by the two
                  state as the loop    own scope              entries it is made of
                  left it

  ``_KeysValues``, ``_StateSpace`` and ``_Parallel`` are BRANCHES off one
  normed input (:class:`_Branches`): the norm before and the join into the
  residual after lie with the caller, so that a block whose attention and
  state-space mixer read the SAME normed input and are ADDED
  (``_Parallel``) is made of the two entries, not of copies of them;
  (*) where the stack runs more than once, carried whole and a layer's
  entry taken by index; a kernel reads the slabs whole by index;
- expert layers route over every expert of the layer and compute the
  part of the result their held experts give (:func:`_experts`); the
  vocabulary may be the chip's slice of the published one;
- the cached programs of a stack that runs several times run every pass
  for every token, the exit rule at threshold 1; the serving backend
  refuses a lower one (a scheduler's question: ROADMAP, Queue R).

Serving only: there is no training step for this block yet (ROADMAP M1).
"""

from __future__ import annotations

import abc
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.transformer_lm import (
    ContextWindowExceeded,
    _put_columns,
    _validate_sampling,
    prefill_bucket_lengths,
    sample_next_device,
)
from deeplearning4j_tpu.nn.conf.layers.moe import (
    group_limited_softmax_route,
    moe_dropless_ffn,
    sigmoid_topk_route,
)
from deeplearning4j_tpu.nn.ops.decode_attention import (
    decode_attention_impl,
    live_tiles,
)
from deeplearning4j_tpu.nn.ops.latent_decode import latent_decode_impl
from deeplearning4j_tpu.nn.ops.sparse_latent_decode import (
    live_walk,
    selection_bias,
    sparse_latent_decode_impl,
)
from deeplearning4j_tpu.nn.ops.ssm_decode import live_table, ssm_decode_impl

Array = jax.Array

#: device-time scopes of this model, beside ``transformer_lm.SCOPES``
#: (``embed``, ``kv_write``, ``head``, ``sample`` are shared), each opened
#: at one site (what each covers: PERF.md section 3): attention by kind
#: (a latent layer's ``attn_latent_proj``, bound by weights, around
#: ``attn_latent_core``, the scores over the cache and the weighted sum;
#: with an indexer ``attn_index_proj`` / ``_score`` / ``_select`` inside it
#: and ``attn_sparse_core`` for the core over the selection), the dense
#: FFN, the two halves of an expert layer and its shared expert, a
#: state-space mixer's three and ``state_write`` (a prefill's write of its
#: slot's state and tail), ``pass_close`` (the norm that closes each
#: pass of a stack that runs more than once, and the exit gate's reading),
#: and ``mixer_join`` (a parallel block's one norm before its two branches
#: and their sum's way into the residual)
SCOPES = ("attn_full", "attn_window", "attn_latent_proj", "attn_latent_core",
          "mlp", "moe_route", "moe_experts", "moe_shared",
          "ssm_proj", "ssm_conv", "ssm_scan", "state_write",
          "attn_index_proj", "attn_index_score", "attn_index_select",
          "attn_sparse_core", "pass_close", "mixer_join")
_scope = jax.named_scope
_NEG = -1e30
#: queries and keys a block of a latent layer's prefill attention
#: (``_causal_blocked``): the score tensor a program plans is (heads,
#: block, block) float32, 128 MB at 128 heads, whatever the bucket
PREFILL_BLOCK = 512
#: a full layer without a cache (forward, prefill) attends by those blocks
#: too where the float32 scores of the whole bucket, heads x T x T, would
#: be larger than this (32 heads at 4,096 positions: 2.1 GB beside 13 GB
#: of weights and cache); under it the scores are one tensor, as they
#: were before there was a bucket that long
BLOCKED_SCORE_BYTES = 1 << 30
#: tokens an expert layer takes at a time (``_experts``): the gathered
#: rows of a longer prefill, N x k x d in float32, would not fit beside
#: the weights
EXPERT_TOKEN_CHUNK = 2048


#: what a state-space kind states (``DecoderConfig.attn_kinds[...]["ssm"]``)
_SSM_FIELDS = ("n_heads", "head_dim", "d_state", "n_groups", "d_conv",
               "expand", "chunk")


def _ssm_kind(m: dict, d_model: int) -> dict:
    """A state-space kind's statement, read: ``_SSM_FIELDS`` as integers;
    ``d_inner`` (``expand x d_model`` unless stated); the three muP
    multipliers, 1 / None unless stated."""
    out = {f: int(m[f]) for f in _SSM_FIELDS}
    out["d_inner"] = int(m.get("d_inner") or out["expand"] * d_model)
    out["in_multiplier"] = float(m.get("in_multiplier", 1.0))
    out["out_multiplier"] = float(m.get("out_multiplier", 1.0))
    out["multipliers"] = (None if m.get("multipliers") is None
                          else tuple(float(v) for v in m["multipliers"]))
    return out


class DecoderConfig:
    """The decoder as data. ``attn_kinds``: name -> a kind of MIXER (the
    name stays from when every mixer attended). An attention kind is
    {"n_kv_heads", "rope_theta", "window" (None = full), "sink" (bool)} and,
    optional, "rope_scaling" (YaRN: ``factor``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim``,
    ``original_max_position_embeddings``) and "latent" = {"q_rank",
    "kv_rank"}: a latent kind, whose heads are ``head_dim`` = (``head_dim -
    rotary_dim`` without position | ``rotary_dim`` rotated) wide and share
    ONE rotary key. A latent kind may state "index" = {"heads", "head_dim",
    "topk", "own"}: its attention reads only the ``topk`` positions an
    indexer of ``heads`` heads of ``head_dim`` picks for the query (the
    first ``rotary_dim`` of an indexer head are rotated). ``own`` true: the
    layer has the indexer's weights and makes the selection; ``own`` false:
    it has none and attends to the selection of the nearest owning layer
    before it (there must be one). A state-space kind is {"ssm": {"n_heads",
    "head_dim", "d_state", "n_groups", "d_conv", "expand", "chunk"}}
    (Mamba-2: ``expand x d_model`` = ``n_heads x head_dim`` inner channels,
    a state of ``head_dim x d_state`` a head, B and C shared by the heads of
    a group, a causal depthwise convolution ``d_conv`` wide, prefill by
    chunks of ``chunk``; ``d_inner`` where the inner width is stated apart
    from ``expand``; ``in_multiplier`` on the mixer's input,
    ``out_multiplier`` on its output and ``multipliers``, five, on the [z |
    x | B | C | dt] parts of its input projection: muP's fixed scalars,
    applied as the published code applies them); it takes none of the
    attention keys, unless ``"parallel"`` is true: the kind is then BOTH,
    a full attention (``n_kv_heads``, ``rope_theta``; no window, sink or
    latent) and the state-space mixer side by side on one normed input,
    their outputs added (:class:`_Parallel`). An attention kind may state
    ``in_multiplier``, ``key_multiplier`` (on the keys, before rotation)
    and ``out_multiplier`` likewise. ``layers``:
    one (mixer kind, "dense" | "experts") pair a layer. ``experts_held`` =
    (offset, count): which of the ``n_experts`` the router scores have their
    weights here. ``routing``: {"scoring": "sigmoid", "scale"} (sigmoid
    scores, a correction bias in the choice, weights renormalised, then
    times ``scale``; None reads as this rule with ``scale`` 1) or
    {"n_group", "topk_group", "renormalise", "scale"} (softmax scores,
    group-limited: no bias). ``shared_width``: the shared expert's width (0:
    none). ``vocab_size`` is what is held here (the chip's slice, where the
    vocabulary is sliced). ``rotary_dim`` 0: no positions.
    ``embedding_multiplier`` scales the embedded tokens,
    ``residual_multiplier`` every residual branch (mixer and FFN),
    ``attention_multiplier`` the attention scores (None: ``1/sqrt(head)``),
    ``mlp_multipliers`` (gate, down) the dense MLP's gate before its
    activation and its output, and ``logits_scaling`` divides the logits;
    ``tied_head``: the head is
    the embedding, one leaf, read transposed. ``passes``: how many times the
    whole stack is applied to every token, with ONE set of weights: each
    pass closes with the final norm, the next starts from the normed stream,
    and every (pass, layer) keeps a cache entry of its own
    (:meth:`cache_plan`). ``sandwich_norm``: a layer has four norms, the two
    outputs (mixer and FFN) being normed again (``norm1b``, ``norm2b``)
    before they join the residual. ``exit_gate``: a gate of one output
    (``gate_w`` (d,), ``gate_b``) reads each pass's normed stream and gives
    the probability of leaving there; ``exit_threshold`` q: a token's logits
    come from the first pass at which the cumulative exit probability
    reaches q (:func:`forward`; 1.0: from the last pass, whatever the gate
    says)."""

    def __init__(self, vocab_size: int, d_model: int, n_heads: int,
                 head_dim: int, v_head_dim: int, rotary_dim: int,
                 attn_kinds: Dict[str, dict],
                 layers: Sequence[Sequence[str]], dense_width: int,
                 expert_width: int = 0, n_experts: int = 0, top_k: int = 0,
                 experts_held: Optional[Sequence[int]] = None,
                 value_scale: float = 1.0, norm_eps: float = 1e-5,
                 max_length: int = 2048, param_dtype: str = "bfloat16",
                 seed: int = 0, routing: Optional[dict] = None,
                 shared_width: int = 0, embedding_multiplier: float = 1.0,
                 residual_multiplier: float = 1.0,
                 attention_multiplier: Optional[float] = None,
                 logits_scaling: float = 1.0, tied_head: bool = False,
                 passes: int = 1, sandwich_norm: bool = False,
                 exit_gate: bool = False, exit_threshold: float = 1.0,
                 mlp_multipliers: Optional[Sequence[float]] = None):
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.v_head_dim = int(v_head_dim)
        self.rotary_dim = int(rotary_dim)
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError("rotary_dim must be even and <= head_dim")
        self.attn_kinds = {
            name: {"n_kv_heads": int(k.get("n_kv_heads", n_heads)),
                   "rope_theta": float(k.get("rope_theta", 0.0)
                                       if k.get("ssm") else k["rope_theta"]),
                   "window": None if k.get("window") is None
                   else int(k["window"]),
                   "sink": bool(k.get("sink", False)),
                   "rope_scaling": (dict(k["rope_scaling"])
                                    if k.get("rope_scaling") else None),
                   "latent": ({"q_rank": int(k["latent"]["q_rank"]),
                               "kv_rank": int(k["latent"]["kv_rank"])}
                              if k.get("latent") else None),
                   "index": ({"heads": int(k["index"]["heads"]),
                              "head_dim": int(k["index"]["head_dim"]),
                              "topk": int(k["index"]["topk"]),
                              "own": bool(k["index"]["own"])}
                             if k.get("index") else None),
                   "ssm": (_ssm_kind(k["ssm"], self.d_model)
                           if k.get("ssm") else None),
                   "parallel": bool(k.get("parallel", False)),
                   **{m: float(k.get(m, 1.0)) for m in
                      ("in_multiplier", "key_multiplier", "out_multiplier")}}
            for name, k in attn_kinds.items()}
        for name, k in self.attn_kinds.items():
            if k["latent"] and (k["window"] is not None or k["sink"]):
                raise ValueError(f"latent kind {name!r} takes no window or "
                                 "sink")
            if k["index"] and (not k["latent"] or k["index"]["topk"] < 1
                               or self.rotary_dim > k["index"]["head_dim"]):
                raise ValueError(
                    f"kind {name!r}: an indexer goes with a latent kind, "
                    "picks at least one position and has heads no "
                    "narrower than rotary_dim")
            if k["ssm"]:
                m = k["ssm"]
                if (k["latent"] or k["window"] is not None or k["sink"]
                        or m["n_heads"] * m["head_dim"] != m["d_inner"]
                        or m["n_heads"] % m["n_groups"]
                        or (m["multipliers"] and len(m["multipliers"]) != 5)):
                    raise ValueError(
                        f"ssm kind {name!r}: n_heads x head_dim = d_inner "
                        "(expand x d_model unless stated), n_groups dividing "
                        "n_heads, five multipliers or none, and no window, "
                        "sink or latent")
            elif k["parallel"]:
                raise ValueError(f"parallel kind {name!r} states no ssm")
        self.layers = [(str(a), str(f)) for a, f in layers]
        owner = False
        for a, f in self.layers:
            if a not in self.attn_kinds or f not in ("dense", "experts"):
                raise ValueError(f"unknown layer ({a!r}, {f!r})")
            if self.n_heads % self.attn_kinds[a]["n_kv_heads"]:
                raise ValueError("n_heads must be a multiple of n_kv_heads")
            index = self.attn_kinds[a]["index"]
            owner = owner or bool(index and index["own"])
            if index and not owner:
                raise ValueError(f"layer kind {a!r} shares a selection and "
                                 "no layer before it makes one")
        self.dense_width = int(dense_width)
        self.expert_width = int(expert_width)
        self.n_experts = int(n_experts)
        self.top_k = int(top_k)
        held = (0, self.n_experts) if experts_held is None else experts_held
        self.experts_held = (int(held[0]), int(held[1]))
        if self.experts_held[0] + self.experts_held[1] > self.n_experts:
            raise ValueError("experts_held reaches past n_experts")
        self.value_scale = float(value_scale)
        self.norm_eps = float(norm_eps)
        self.max_length = int(max_length)
        if param_dtype not in ("float32", "bfloat16"):
            raise ValueError("param_dtype must be 'float32' or 'bfloat16'")
        self.param_dtype = param_dtype
        self.seed = int(seed)
        routing = routing or {"scoring": "sigmoid"}
        if routing.get("scoring") == "sigmoid":
            self.routing = {"scoring": "sigmoid",
                            "scale": float(routing.get("scale", 1.0))}
        else:
            self.routing = {
                "n_group": int(routing.get("n_group", 1)),
                "topk_group": int(routing.get("topk_group", 1)),
                "renormalise": bool(routing.get("renormalise", False)),
                "scale": float(routing.get("scale", 1.0))}
            if self.n_experts % self.routing["n_group"]:
                raise ValueError("routing: n_group groups that divide "
                                 "n_experts, or None")
        self.shared_width = int(shared_width)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attention_multiplier = (None if attention_multiplier is None
                                     else float(attention_multiplier))
        self.logits_scaling = float(logits_scaling)
        self.tied_head = bool(tied_head)
        self.mlp_multipliers = (
            (1.0, 1.0) if mlp_multipliers is None
            else tuple(float(v) for v in mlp_multipliers))
        if len(self.mlp_multipliers) != 2:
            raise ValueError("mlp_multipliers: (gate, down) or None")
        self.passes = int(passes)
        self.sandwich_norm = bool(sandwich_norm)
        self.exit_gate = bool(exit_gate)
        self.exit_threshold = float(exit_threshold)
        if self.passes < 1 or not 0.0 <= self.exit_threshold <= 1.0:
            raise ValueError("passes >= 1 and 0 <= exit_threshold <= 1")
        if self.exit_threshold < 1.0 and not self.exit_gate:
            raise ValueError("an exit_threshold under 1 needs the exit_gate "
                             "whose probabilities it is held against")
        self._mixers = {name: _Mixer.of(self, name)
                        for name in self.attn_kinds}

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def dtype(self):
        return jnp.bfloat16 if self.param_dtype == "bfloat16" else jnp.float32

    @property
    def sigmoid_routing(self) -> bool:
        """The router scores by sigmoid and has a correction bias
        (``br``); otherwise by softmax, group-limited, without one."""
        return self.routing.get("scoring") == "sigmoid"

    def segments(self) -> List[Tuple[str, str, int]]:
        """Runs of consecutive layers of one kind: (mixer kind, FFN
        kind, layers in the run). Each is one ``lax.scan``."""
        out: List[List] = []
        for a, f in self.layers:
            if out and out[-1][0] == a and out[-1][1] == f:
                out[-1][2] += 1
            else:
                out.append([a, f, 1])
        return [tuple(s) for s in out]

    def mixer(self, kind: str) -> "_Mixer":
        """The entry of mixer kind ``kind`` (:class:`_Mixer`), resolved once
        from ``attn_kinds[kind]``."""
        return self._mixers[kind]

    def route(self):
        """The expert layers' routing rule, as ``moe_dropless_ffn`` takes
        it: (router outputs, bias, k) -> (chosen, weights)."""
        r = self.routing
        if self.sigmoid_routing:
            return functools.partial(sigmoid_topk_route, scale=r["scale"])
        return functools.partial(
            group_limited_softmax_route, n_group=r["n_group"],
            topk_group=r["topk_group"], renormalise=r["renormalise"],
            scale=r["scale"])

    def cache_plan(self, n_slots: int, max_length: int) -> List[dict]:
        """What the engine allocates, a segment at a time: ``kind``,
        ``layers``, ``passes`` and the kind's own part (``_Mixer.plan``):
        ``slabs``, ``dtypes`` and ``bytes`` as allocated; the report's
        fields (``columns``, ``ring``, ``values``, ``row``, ``index``,
        ``state``, ``conv``); what the engine counts (``latent``,
        ``attends``, ``topk``, ``keeps_state``, ``entries``). Every shape
        leads with passes x layers, pass-major (pass r's layer i is entry
        r x layers + i); ``layers`` stays the segment's."""
        return [{"kind": kind, "layers": layers, "passes": self.passes,
                 **self.mixer(kind).plan(self.passes * layers, int(n_slots),
                                         int(max_length))}
                for kind, _ffn, layers in self.segments()]


# -- parameters ---------------------------------------------------------------
def segment_shapes(cfg: DecoderConfig, kind: str, ffn: str) -> Dict[str, tuple]:
    """Leaf name -> (shape of ONE layer, dtype): the norms' gains, the
    mixer kind's leaves (``_Mixer.leaves``), then the FFN's. Norm gains,
    sinks and the router stay float32 whatever the parameter dtype. With
    ``sandwich_norm`` every kind of layer has ``norm1b`` and ``norm2b``,
    the gains of the norms its two outputs go through."""
    d = cfg.d_model
    pd, f32 = cfg.dtype, jnp.float32
    out = {"norm1": ((d,), f32), "norm2": ((d,), f32)}
    if cfg.sandwich_norm:
        out.update({"norm1b": ((d,), f32), "norm2b": ((d,), f32)})
    out.update(cfg.mixer(kind).leaves())
    if ffn == "dense":
        out.update({"Wg": ((d, cfg.dense_width), pd),
                    "Wu": ((d, cfg.dense_width), pd),
                    "Wd": ((cfg.dense_width, d), pd)})
    else:
        held, f = cfg.experts_held[1], cfg.expert_width
        out["Wr"] = ((d, cfg.n_experts), f32)
        if cfg.sigmoid_routing:
            out["br"] = ((cfg.n_experts,), f32)
        out.update({"Eg": ((held, d, f), pd), "Eu": ((held, d, f), pd),
                    "Ed": ((held, f, d), pd)})
        if cfg.shared_width:
            out.update({"Sg": ((d, cfg.shared_width), pd),
                        "Su": ((d, cfg.shared_width), pd),
                        "Sd": ((cfg.shared_width, d), pd)})
    return out


def init_params(cfg: DecoderConfig, rng: Optional[Array] = None) -> Dict:
    """{"embed", "segments": [stacked leaves a segment], "norm_f",
    "head"} (no "head" where it is tied to the embedding; "gate_w" and
    "gate_b", float32, where there is an exit gate); normal(0, 0.02)
    matrices, unit gains, zero sinks, a small router bias; a state-space
    mixer's scalars at Mamba-2's defaults (``A`` uniform in [1, 16],
    ``dt`` log-uniform in [1e-3, 1e-1] with ``dt_bias`` its inverse
    softplus, ``D`` 1)."""
    rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
    # 16 a segment covers every kind but the one that owns an indexer
    per = max([16] + [len(segment_shapes(cfg, kind, ffn))
                      for kind, ffn, _n in cfg.segments()])
    keys = iter(jax.random.split(rng, per * len(cfg.segments()) + 4))
    pd = cfg.dtype

    def normal(shape, dtype, std=0.02):
        return (std * jax.random.normal(next(keys), shape,
                                        jnp.float32)).astype(dtype)

    segments = []
    for kind, ffn, n in cfg.segments():
        seg = {}
        for name, (shape, dtype) in segment_shapes(cfg, kind, ffn).items():
            full = (n,) + shape
            if name.startswith("norm") or name == "D":
                seg[name] = jnp.ones(full, dtype)
            elif name == "sink":
                seg[name] = jnp.zeros(full, dtype)
            elif name == "A_log":
                seg[name] = jnp.log(jax.random.uniform(
                    next(keys), full, dtype, 1.0, 16.0))
            elif name == "conv_w":
                seg[name] = jax.random.uniform(
                    next(keys), full, jnp.float32, -0.5, 0.5).astype(dtype)
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    next(keys), full, dtype, math.log(1e-3), math.log(1e-1)))
                seg[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                seg[name] = normal(full, dtype)
        segments.append(seg)
    # the multiplied embedding enters the stream at the matrices' scale
    out = {"embed": normal((cfg.vocab_size, cfg.d_model), pd,
                           0.02 / cfg.embedding_multiplier),
           "segments": segments,
           "norm_f": jnp.ones((cfg.d_model,), jnp.float32)}
    if not cfg.tied_head:
        out["head"] = normal((cfg.d_model, cfg.vocab_size), pd)
    if cfg.exit_gate:
        out["gate_w"] = normal((cfg.d_model,), jnp.float32)
        out["gate_b"] = jnp.zeros((), jnp.float32)
    return out


# -- the block ----------------------------------------------------------------
def _rms_norm(x, g, eps):
    """float32 statistics; returns float32 (the caller casts)."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * g


def _rotate(x, pos, rotary_dim: int, theta: float, scaling=None):
    """Rotary positions on the first ``rotary_dim`` of each head
    (half-split pairing: dimension i turns with i + rotary_dim/2); the
    rest pass through. x (b, T, h, hd), pos (b, T) absolute. With
    ``scaling`` the frequencies and the amplitude are YaRN's
    (:func:`yarn_frequencies`). ``rotary_dim`` 0: no positions, x as it
    came."""
    if rotary_dim == 0:
        return x
    half = rotary_dim // 2
    if scaling is None:
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
        amp = 1.0
    else:
        inv, amp = yarn_frequencies(rotary_dim, theta, scaling)
        inv = jnp.asarray(inv, jnp.float32)
    ang = pos.astype(jnp.float32)[..., None] * inv          # (b, T, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:rotary_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, xf[..., rotary_dim:]],
        axis=-1).astype(x.dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(rotary_dim: int, theta: float, scaling: dict):
    """(inverse frequencies (rotary_dim / 2,) float32, amplitude of cos
    and sin) of YaRN: pair i keeps its frequency ``theta^(-2i/dim)``
    below the correction dimension of ``beta_fast`` turns within the
    original context, takes it divided by ``factor`` above that of
    ``beta_slow``, and a linear blend between the two."""
    dim, factor = rotary_dim, float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra / factor * ramp + extra * (1 - ramp)
    amp = (_yarn_mscale(factor, float(scaling.get("mscale", 1.0)))
           / _yarn_mscale(factor, float(scaling.get("mscale_all_dim", 0.0))))
    return inv.astype(np.float32), float(amp)


def softmax_scale(cfg: "DecoderConfig", kind: str) -> float:
    """1 / sqrt(head size), or the configuration's
    ``attention_multiplier`` where it states one, times YaRN's
    ``mscale_all_dim`` factor squared where the kind's rotary scaling has
    one."""
    scale = (1.0 / math.sqrt(cfg.head_dim)
             if cfg.attention_multiplier is None else cfg.attention_multiplier)
    sc = cfg.attn_kinds[kind]["rope_scaling"]
    if sc and sc.get("mscale_all_dim"):
        scale *= _yarn_mscale(float(sc["factor"]),
                              float(sc["mscale_all_dim"])) ** 2
    return scale


def _residual(cfg: "DecoderConfig", x, branch):
    """x + ``residual_multiplier`` x branch, in x's dtype."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    return x + branch.astype(x.dtype)


def _branch(cfg: "DecoderConfig", bp, gain: str, y):
    """A layer's output on its way to the residual: under
    ``sandwich_norm`` normed once more, by the gain ``bp[gain]``."""
    return _rms_norm(y, bp[gain], cfg.norm_eps) if cfg.sandwich_norm else y


def _visible(q_pos, k_pos, window):
    """(b, Tq, Tk): key position visible from query position: held
    (>= 0), not later than the query and, in a window layer, fewer than
    ``window`` positions back."""
    qp, kp = q_pos[:, :, None], k_pos[:, None, :]
    ok = (kp >= 0) & (kp <= qp)
    if window is not None:
        ok &= (qp - kp) < window
    return ok


def _causal_blocked(q, k, v, scale: float, block: int, n_real=None,
                    allowed=None):
    """Causal attention of q (b, T, h, dk) over k (b, T, h, dk) and
    v (b, T, h, dv) -> (b, T, h, dv), by blocks of ``block`` queries and
    ``block`` keys under one running softmax: the largest score tensor is
    (b, h, block, block) whatever T, and the key blocks above a query
    block's diagonal are not visited. With ``n_real`` (traced) the query
    blocks past the first ``n_real`` positions are not computed either
    (a bucket's padding): their rows come back zero. With ``allowed``
    (b, T, T) bool a query sees, of the keys not after it, those alone
    that its row allows (a selection; every row allows at least one, so
    what a key block without any left in the running sums is wiped by
    the first real score)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    pad = -t % block
    if pad:  # padded keys lie after every real query: causality hides them
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        if allowed is not None:
            allowed = jnp.pad(allowed, ((0, 0), (0, pad), (0, pad)))
    at = jnp.arange(block)

    def q_block(i, out):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)

        def k_block(j, carry):
            m, z, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                           preferred_element_type=f32) * scale
            s = jnp.where((j * block + at)[None, :] <= (i * block + at)[:, None],
                          s, _NEG)
            if allowed is not None:
                s = jnp.where(jax.lax.dynamic_slice(
                    allowed, (0, i * block, j * block),
                    (b, block, block))[:, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            keep = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new[..., None])
            acc = acc * keep[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", e.astype(q.dtype), vj,
                preferred_element_type=f32)
            return m_new, z * keep + e.sum(-1), acc

        # key block 0 holds position 0, which every query sees: the
        # running maximum is a real score from the first block on
        m, z, acc = jax.lax.fori_loop(
            0, i + 1, k_block,
            (jnp.full((b, h, block), _NEG, f32), jnp.zeros((b, h, block), f32),
             jnp.zeros((b, h, block, dv), f32)))
        o = (acc / z[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
        return jax.lax.dynamic_update_slice_in_dim(out, o, i * block, axis=1)

    n_blocks = (t + pad) // block
    if n_real is not None:
        n_blocks = jnp.minimum(n_blocks, (n_real + block - 1) // block)
    out = jax.lax.fori_loop(0, n_blocks, q_block,
                            jnp.zeros((b, t + pad, h, dv), q.dtype))
    return out[:, :t]


def _latent_project(cfg: DecoderConfig, kind: str, bp: Dict[str, Array],
                    x: Array, q_pos: Array):
    """What every form of a latent layer's attention starts from, x
    (b, Tq, d) at q_pos (b, Tq): (the normed input (b, Tq, d), the normed
    query latent (b, Tq, q_rank), the heads' queries without position
    (b, Tq, h, head - rotary) and rotated (b, Tq, h, rotary), the normed
    key/value latent (b, Tq, kv_rank), the one rotated key (b, Tq,
    rotary), the cache entry [latent | rotated key])."""
    mixer = cfg.mixer(kind)
    rot = cfg.rotary_dim
    nope, kr = cfg.head_dim - rot, mixer.kv_rank
    theta, scaling = mixer.theta, mixer.scaling
    dt = x.dtype
    a_in = _rms_norm(x, bp["norm1"], cfg.norm_eps).astype(dt)
    c_q = _rms_norm(a_in @ bp["Wqa"], bp["norm_q"], cfg.norm_eps).astype(dt)
    q = jnp.einsum("btr,rhk->bthk", c_q, bp["Wqb"])
    # the rotated part of a head is its LAST rotary_dim columns
    q_nope = q[..., :nope]
    q_pe = _rotate(q[..., nope:], q_pos, rot, theta, scaling)
    ckv = a_in @ bp["Wkva"]
    c = _rms_norm(ckv[..., :kr], bp["norm_kv"], cfg.norm_eps).astype(dt)
    k_pe = _rotate(ckv[:, :, None, kr:], q_pos, rot, theta, scaling)[:, :, 0]
    new = jnp.concatenate([c, k_pe], axis=-1)            # (b, Tq, kr + rot)
    return a_in, c_q, q_nope, q_pe, c, k_pe, new


def _latent_expand(cfg: DecoderConfig, bp: Dict[str, Array], q_nope, q_pe,
                   c, k_pe):
    """The EXPANDED form's operands: every head's query (b, T, h, head),
    key (the head's own part from the latent, the one rotated key
    repeated) and value (b, T, h, value size)."""
    b, tq = c.shape[:2]
    k = jnp.concatenate(
        [jnp.einsum("btc,chn->bthn", c, bp["Wuk"]),
         jnp.broadcast_to(k_pe[:, :, None],
                          (b, tq, cfg.n_heads, cfg.rotary_dim))], axis=-1)
    v = jnp.einsum("btc,chv->bthv", c, bp["Wuv"])
    return jnp.concatenate([q_nope, q_pe], axis=-1), k, v


def _latent_attention(cfg: DecoderConfig, kind: str, bp: Dict[str, Array],
                      x: Array, q_pos: Array, cache=None, n_real=None):
    """A latent layer's attention on x (b, Tq, d): returns (x + its
    output, the (b, Tq, kv_rank + rotary_dim) cache entries of the step's
    own positions: the compressed key/value latent after its norm, then
    the one rotary key after rotation).

    Without a cache (forward, prefill) the EXPANDED form: keys and values
    of every head are made from the positions' own latents and attention
    goes by blocks (``_causal_blocked``). With ``cache`` (a view made by
    ``_Latent.open``) the ABSORBED form: with the up-projection split by
    head into ``Wuk`` and ``Wuv``, a head's query is taken into the latent
    space (``q_nope Wuk^T``), scored against the cached latents and the
    rotary key as they lie, the softmax weights sum the LATENTS, and
    ``Wuv`` then ``Wo`` bring that sum out: each cached position is read
    once for all heads and no key or value of a head is ever made over the
    cache. Over ("columns", slab (b, width, Tc), c_pos (b, Tc)) both
    einsums take the slab whole (the weighted sum over all its rows, the
    rotary key's dropped after: a slice of it would be copied) and every
    column of every row, whatever it holds. Over ("kernel", the segment's
    slabs (entries, b, width, Tc), entry, lengths (b,)) and Tq = 1 the
    kernel of ``nn/ops/latent_decode.py`` gives the same sums, reading row
    s of that entry in its first ``lengths[s]`` columns, once."""
    b, tq, _d = x.shape
    hq, rot, vd = cfg.n_heads, cfg.rotary_dim, cfg.v_head_dim
    kr = cfg.mixer(kind).kv_rank
    scale = softmax_scale(cfg, kind)
    f32, dt = jnp.float32, x.dtype
    with _scope("attn_latent_proj"):
        _a_in, _c_q, q_nope, q_pe, c, k_pe, new = _latent_project(
            cfg, kind, bp, x, q_pos)
        if cache is None:
            q, k, v = _latent_expand(cfg, bp, q_nope, q_pe, c, k_pe)
            with _scope("attn_latent_core"):
                o = _causal_blocked(q, k, v, scale, PREFILL_BLOCK, n_real)
        else:
            q_lat = jnp.concatenate(
                [jnp.einsum("bqhn,chn->bqhc", q_nope, bp["Wuk"]), q_pe], axis=-1)
            if cache[0] == "kernel":
                _how, slabs, layer, lengths = cache
                core = latent_decode_impl(hq, kr + rot, slabs.shape[-1],
                                          slabs.dtype, kr)
                with _scope("attn_latent_core"):
                    lat = core(q_lat[:, 0], new[:, 0], slabs, layer, lengths,
                               scale=scale)[:, None]
            else:
                _how, slab, c_pos = cache
                with _scope("attn_latent_core"):
                    s_own = jnp.einsum("bqhc,bkc->bqhk", q_lat, new,
                                       preferred_element_type=f32) * scale
                    s_own = jnp.where(_visible(q_pos, q_pos, None)[:, :, None],
                                      s_own, _NEG)
                    s_c = jnp.einsum("bqhc,bct->bqht", q_lat, slab,
                                     preferred_element_type=f32) * scale
                    s_c = jnp.where(_visible(q_pos, c_pos, None)[:, :, None],
                                    s_c, _NEG)
                    m = jnp.maximum(s_own.max(-1), s_c.max(-1))[..., None]
                    e_own, e_c = jnp.exp(s_own - m), jnp.exp(s_c - m)
                    lat = (jnp.einsum("bqhk,bkc->bqhc", e_own.astype(dt), new,
                                      preferred_element_type=f32)
                           + jnp.einsum("bqht,bct->bqhc", e_c.astype(dt), slab,
                                        preferred_element_type=f32))
                    z = (e_own.sum(-1) + e_c.sum(-1))[..., None]
                    lat = (lat[..., :kr] / z).astype(dt)
            o = jnp.einsum("bqhc,chv->bqhv", lat, bp["Wuv"])
        if cfg.value_scale != 1.0:
            o = o * cfg.value_scale
        x = _residual(cfg, x, _branch(
            cfg, bp, "norm1b", o.reshape(b, tq, hq * vd).astype(dt) @ bp["Wo"]))
    return x, new


def _layer_norm(x, g, bias, eps):
    """LayerNorm over the last axis with float32 statistics; returns
    float32 (the caller casts)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    return (xf - mean) * jax.lax.rsqrt(var + eps) * g + bias


def _index_scores(q_i, w_i, k_i):
    """The indexer's scores of queries over keys, float32: q_i (b, Tq, h,
    d) the indexer heads' queries, w_i (b, Tq, h) float32 their weights
    (the two scale factors in them), k_i (b, Tk, d) ONE key a position ->
    (b, Tq, Tk): ``sum_h w_h ReLU(q_h . k)``. The products accumulate in
    float32 and the ReLU, the weights and the sum over heads are float32
    elementwise (a float32 matrix product would round its operands on
    the chip)."""
    s = jnp.einsum("bqhd,bkd->bqhk", q_i, k_i,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w_i[..., None], axis=2)


def _select_mask(scores, k: int):
    """The EXACT top-k of each row as a mask: scores (..., n) float32 with
    -inf where a position cannot be chosen -> bool (..., n), true at the
    ``k`` largest, ties to the lower position, and at every choosable one
    where there are fewer than ``k``. No sort: the k-th largest value is
    found by bisection on the scores' bits (float32 read as an unsigned
    integer that orders as the numbers do), 32 passes that compare and
    count, and the ties at that value are counted off from the left
    (the set a stable descending sort's first ``k`` are)."""
    n = scores.shape[-1]
    valid = scores > -jnp.inf
    if n <= k:
        return valid
    # -0 orders as +0 does
    bits = jax.lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores),
                                        jnp.uint32)
    top = jnp.uint32(1 << 31)
    u = jnp.where(bits >= top, ~bits, bits | top)

    def one_bit(i, thr):
        cand = thr | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, one_bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = u > thr[..., None]
    ties = u == thr[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    first = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room[..., None]
    return (above | (ties & first)) & valid


def _select_indices(scores, own, lengths, k: int, as_bias: bool = False):
    """A decode step's selection: scores (b, Tc) float32 of the cached
    positions (-inf from a row's ``lengths`` on), ``own`` (b,) the score of
    the step's own position, which lies outside the slab -> (the columns
    of the ``k`` best cached positions (b, k), best first; how many of
    them are in the selection (b,); whether the own position is (b,); with
    ``as_bias`` also the same set of cached positions as the bias (b, 1,
    Tc) a kernel that walks the rows takes: ``selection_bias``).
    The selection is the ``min(k, lengths + 1)`` largest of cached and own
    together, ties to the lower position, the set ``_select_mask`` gives
    with the own score at column ``lengths``: the own position, the
    highest, is in where there is room for all or where it beats the k-th
    best cached one outright, and then takes that one's place. By
    ``lax.top_k`` (exact, equal values lower index first): on the chip a
    sort of 32 rows of 14,336 takes 0.47 ms where the bisection and a
    compaction of its mask into columns took 1.13 (PERF.md, PR 40)."""
    # -0 orders as +0 does, as in ``_select_mask``
    scores, own = (jnp.where(a == 0, 0.0, a) for a in (scores, own))
    vals, idx = jax.lax.top_k(scores, k)
    own_in = (lengths < k) | (own > vals[:, -1])
    n_sel = jnp.where(own_in, jnp.minimum(lengths, k - 1), k).astype(jnp.int32)
    sel = idx.astype(jnp.int32), n_sel, own_in
    if as_bias:
        sel += (selection_bias(scores, vals, sel[0], n_sel, lengths),)
    return sel


def _index_mask(q_i, w_i, k_i, topk: int, block: int, n_real=None):
    """The selection of every query of a sequence as a mask (b, T, T)
    bool: row t true at the ``min(topk, t + 1)`` positions not after t
    whose indexer scores are largest. By blocks of ``block`` queries: a
    block's scores over the key blocks up to its own (``_index_scores``),
    then the exact select over the row (``_select_mask``); the largest
    tensor beside the mask is (b, block, heads, block) float32. With
    ``n_real`` the query blocks past it are not visited: rows all
    false."""
    b, t = q_i.shape[:2]
    pad = -t % block
    if pad:
        q_i, w_i, k_i = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q_i, w_i, k_i))
    tp = t + pad
    at = jnp.arange(block)

    def q_block(i, allowed):
        qi, wi = (jax.lax.dynamic_slice_in_dim(a, i * block, block, axis=1)
                  for a in (q_i, w_i))

        def k_block(j, row):
            kj = jax.lax.dynamic_slice_in_dim(k_i, j * block, block, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                row, _index_scores(qi, wi, kj), j * block, axis=2)

        with _scope("attn_index_score"):
            row = jax.lax.fori_loop(
                0, i + 1, k_block, jnp.zeros((b, block, tp), jnp.float32))
            seen = jnp.arange(tp)[None, :] <= (i * block + at)[:, None]
            row = jnp.where(seen, row, -jnp.inf)
        with _scope("attn_index_select"):
            chosen = _select_mask(row, topk)
        return jax.lax.dynamic_update_slice_in_dim(allowed, chosen, i * block,
                                                   axis=1)

    n_blocks = tp // block
    if n_real is not None:
        n_blocks = jnp.minimum(n_blocks, (n_real + block - 1) // block)
    allowed = jax.lax.fori_loop(0, n_blocks, q_block,
                                jnp.zeros((b, tp, tp), bool))
    return allowed[:, :t, :t] if pad else allowed


def _sparse_latent_attention(cfg: DecoderConfig, kind: str,
                             bp: Dict[str, Array], x: Array, q_pos: Array,
                             cache=None, sel=None, n_real=None):
    """A latent layer's attention over a SELECTION of positions, x
    (b, Tq, d): returns (x + its output, what the layer caches of the
    step's positions, the selection it attended by).

    The attention is ``_latent_attention``'s, expanded without a cache and
    absorbed over one, with the softmax over the selected positions only.
    A layer that OWNS the indexer makes the selection: the indexer heads'
    queries from the query latent (``Iq``, rotated on their first
    ``rotary_dim``), ONE key a position (``Ik``, LayerNorm, rotated alike;
    cached), the heads' weights (``Iw``, times heads^-1/2 x head_dim^-1/2),
    the score ``sum_h w_h ReLU(q_h . k)`` in float32 of every position not
    after the query, and the ``topk`` largest, exactly. It returns (latent
    entries (b, Tq, ``_IndexedLatent.row``), key entries (b, Tq, indexer
    head size)). A layer that SHARES attends by ``sel``, the selection the
    nearest owner before it made for the same tokens, and returns the
    latent entries alone.

    Without a cache (forward, prefill) a selection is a mask (b, Tq, Tq)
    on the blocked attention's scores (``_index_mask``,
    ``_causal_blocked``), or None where Tq <= topk: every query then
    attends to all before it, the dense latent layer. With ``cache`` (a
    view made by ``_IndexedLatent.open``) = ("rows", the segment's slabs,
    position-major: (latents (entries, b, Tc, row), and an owner's keys),
    entry, lengths (b,)) and Tq = 1 it is (columns (b, K), how many of them
    count (b,), whether the step's own position is in (b,))
    (``_select_indices``; K = min(topk, Tc)): the own entry lies outside
    the slab, so it is scored beside the cached ones and a sharer is told
    whether it was chosen. The chosen rows are gathered from the slab as
    it lies, K rows a slot, and the absorbed scores, the softmax and the
    weighted sum of latents run over them and the own entry. Over
    ("kernel", slabs, entry, lengths (0 for an idle row), the kernel of
    ``nn/ops/sparse_latent_decode.py``, its walk over the live tiles) the
    selection has a fourth part, the same set as a bias over the slot's
    positions, and the kernel gives the same sums from the live rows read
    where they lie, once."""
    mixer = cfg.mixer(kind)
    index = mixer.index
    b, tq, _d = x.shape
    hq, rot, vd = cfg.n_heads, cfg.rotary_dim, cfg.v_head_dim
    kr, theta, scaling = mixer.kv_rank, mixer.theta, mixer.scaling
    scale = softmax_scale(cfg, kind)
    f32, dt = jnp.float32, x.dtype
    with _scope("attn_latent_proj"):
        a_in, c_q, q_nope, q_pe, c, k_pe, new = _latent_project(
            cfg, kind, bp, x, q_pos)
        # the entry as a row of the slab: whole tiles, the tail zero
        tail = mixer.row - new.shape[-1]
        new = jnp.pad(new, ((0, 0), (0, 0), (0, tail)))
        made = (new,)
        if index["own"]:
            with _scope("attn_index_proj"):
                q_i = _rotate(jnp.einsum("btr,rhk->bthk", c_q, bp["Iq"]),
                              q_pos, rot, theta, scaling)
                k_i = _layer_norm(a_in @ bp["Ik"], bp["norm_ik"],
                                  bp["bias_ik"], cfg.norm_eps)
                k_i = _rotate(k_i[:, :, None], q_pos, rot, theta,
                              scaling)[:, :, 0].astype(dt)
                w_i = jnp.matmul(
                    a_in, bp["Iw"], preferred_element_type=f32) * (
                        index["heads"] ** -0.5 * index["head_dim"] ** -0.5)
            made = (new, k_i)
        selects = cache is not None or tq > index["topk"]
        if selects and not index["own"] and sel is None:
            raise ValueError(f"kind {kind!r} shares a selection and was "
                             "handed none")
        if cache is None:
            if not selects:
                sel = None
            elif index["own"]:
                sel = _index_mask(q_i, w_i, k_i, index["topk"], PREFILL_BLOCK,
                                  n_real)
            q, k, v = _latent_expand(cfg, bp, q_nope, q_pe, c, k_pe)
            with _scope("attn_sparse_core"):
                o = _causal_blocked(q, k, v, scale, PREFILL_BLOCK, n_real, sel)
        else:
            how, slabs, layer, lengths, *core = cache
            q_lat = jnp.concatenate(
                [jnp.einsum("bqhn,chn->bqhc", q_nope, bp["Wuk"]), q_pe,
                 jnp.zeros((b, tq, hq, tail), dt)], axis=-1)[:, 0]
            if index["own"]:
                columns = slabs[1].shape[2]
                with _scope("attn_index_score"):
                    keys = jax.lax.dynamic_index_in_dim(slabs[1], layer, 0,
                                                        keepdims=False)
                    s_i = _index_scores(q_i, w_i, keys)[:, 0]
                    s_i = jnp.where(
                        jnp.arange(columns)[None, :] < lengths[:, None], s_i,
                        -jnp.inf)
                    s_own = _index_scores(q_i, w_i, k_i)[:, 0, 0]
                with _scope("attn_index_select"):
                    sel = _select_indices(s_i, s_own, lengths,
                                          min(index["topk"], columns),
                                          as_bias=how == "kernel")
            if how == "kernel":
                (kernel, walk), (_idx, _n_sel, own_in, bias) = core, sel
                with _scope("attn_sparse_core"):
                    lat = kernel(q_lat, new[:, 0], slabs[0], layer, lengths,
                                 bias, own_in, walk, scale=scale)[:, None]
            else:
                idx, n_sel, own_in = sel
                with _scope("attn_sparse_core"):
                    rows = slabs[0].at[
                        layer, jnp.arange(b)[:, None], idx].get(
                            mode="promise_in_bounds")         # (b, K, width)
                    s_c = jnp.einsum("bhc,bkc->bhk", q_lat, rows,
                                     preferred_element_type=f32) * scale
                    counts = jnp.arange(idx.shape[1])[None, :] < n_sel[:, None]
                    s_c = jnp.where(counts[:, None], s_c, _NEG)
                    s_own = jnp.einsum("bhc,bc->bh", q_lat, new[:, 0],
                                       preferred_element_type=f32) * scale
                    s_own = jnp.where(own_in[:, None], s_own, _NEG)
                    m = jnp.maximum(s_c.max(-1), s_own)
                    e_c, e_own = jnp.exp(s_c - m[..., None]), jnp.exp(s_own - m)
                    lat = (jnp.einsum("bhk,bkc->bhc", e_c.astype(dt), rows,
                                      preferred_element_type=f32)
                           + e_own[..., None] * new[:, 0, None].astype(f32))
                    z = (e_c.sum(-1) + e_own)[..., None]
                    lat = (lat[..., :kr] / z).astype(dt)[:, None]
            o = jnp.einsum("bqhc,chv->bqhv", lat, bp["Wuv"])
        if cfg.value_scale != 1.0:
            o = o * cfg.value_scale
        x = _residual(cfg, x, _branch(
            cfg, bp, "norm1b", o.reshape(b, tq, hq * vd).astype(dt) @ bp["Wo"]))
    return x, made, sel


def _ssm_chunked(x, dt, a, bmat, cmat, chunk: int, n_real=None):
    """The state-space recurrence ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t
    (outer) B_t``, ``y_t = h_t C_t`` from a zero state over a whole
    sequence, in the chunked dual form: x (b, T, G, R, P) (G groups of R
    heads), dt (b, T, G, R) (0 where a position is padding: decay 1 and no
    input, so it leaves the state alone), a (G, R) negative, bmat and cmat
    (b, T, G, N), all float32 -> (y (b, T, G, R, P), the state after the
    last position (b, G, R, P, N)). Inside a chunk of ``chunk`` positions
    the outputs are ``((C B^T) * L) (dt x)`` with ``L[i, j]`` the product
    of the decays from j + 1 to i (lower triangle), as matrix products;
    between chunks the recurrence runs on the chunks' states: the same
    numbers as the step-by-step scan up to summation order. With
    ``n_real`` (traced) the chunks past the first ``n_real`` positions are
    not visited (a bucket's padding): their rows of y come back zero."""
    b, t = x.shape[:2]
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, bmat, cmat = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                             for v in (x, dt, bmat, cmat))
    lower = jnp.tril(jnp.ones((q, q), bool))

    def one_chunk(c, carry):
        h, out = carry
        xc, dc, bc, cc = (jax.lax.dynamic_slice_in_dim(v, c * q, q, axis=1)
                          for v in (x, dt, bmat, cmat))
        cum = jnp.cumsum(dc * a, axis=1)                      # (b, q, G, R), <= 0
        cum_t = cum.transpose(0, 2, 3, 1)                     # (b, G, R, q)
        # decays from j + 1 to i; the upper triangle never leaves the mask
        span = jnp.exp(jnp.where(lower, cum_t[..., :, None] - cum_t[..., None, :],
                                 -jnp.inf))
        cb = jnp.einsum("bqgn,bkgn->bgqk", cc, bc)
        w = cb[:, :, None] * span * dc.transpose(0, 2, 3, 1)[..., None, :]
        y = jnp.einsum("bgrqk,bkgrp->bqgrp", w, xc)
        y = y + jnp.einsum("bqgn,bgrpn->bqgrp", cc, h) * jnp.exp(cum)[..., None]
        to_end = jnp.exp(cum[:, -1:] - cum) * dc               # (b, q, G, R)
        h = (h * jnp.exp(cum_t[..., -1])[..., None, None]
             + jnp.einsum("bkgrp,bkgn->bgrpn", xc * to_end[..., None], bc))
        return h, jax.lax.dynamic_update_slice_in_dim(out, y, c * q, axis=1)

    n_chunks = (t + pad) // q
    if n_real is not None:
        n_chunks = jnp.minimum(n_chunks, (n_real + q - 1) // q)
    h, y = jax.lax.fori_loop(
        0, n_chunks, one_chunk,
        (jnp.zeros(x.shape[:1] + x.shape[2:] + bmat.shape[-1:], jnp.float32),
         jnp.zeros(x.shape, jnp.float32)))
    return y[:, :t], h


def _ssm_step(h, x, dt, a, bvec, cvec):
    """One step of the recurrence over a cached state: h (b, G, R, P, N),
    x (b, G, R, P), dt (b, G, R), a (G, R), bvec and cvec (b, G, N), all
    float32 -> (y (b, G, R, P), the new state). The readout is taken from
    the OLD state, ``y = decay (h C) + dt x (B . C)``, which is ``h_new C``
    written out: the state is then read once by a reduction and once by
    the update, both elementwise over it, and never made a second time."""
    decay = jnp.exp(dt * a)
    y = (decay[..., None] * jnp.sum(h * cvec[:, :, None, None, :], axis=-1)
         + (dt * jnp.sum(bvec * cvec, axis=-1)[:, :, None])[..., None] * x)
    h_new = (h * decay[..., None, None]
             + (dt[..., None] * x)[..., None] * bvec[:, :, None, None, :])
    return y, h_new


def _ssm_mixer(m: "_StateSpace", bp: Dict[str, Array], a_in: Array,
               cache=None, token_mask=None):
    """A state-space mixer (Mamba-2), entry ``m``, as a BRANCH: on a_in
    (b, Tq, d), the block's normed input, returns (its output (b, Tq, d),
    what the layer keeps); the norm before it and the join into the
    residual are the caller's (``_Branches.mix``).

    ``[z | xBC | dt] = (a_in Win)``, times the kind's ``in_multiplier`` on
    a_in and its five ``multipliers`` on the parts [z | x | B | C | dt] of
    the product where it states them; a causal depthwise convolution
    ``d_conv`` wide over time on ``xBC``, plus bias, then SiLU; ``[x | B |
    C]`` cut from it; ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``
    a head; the recurrence (:func:`_ssm_chunked`, :func:`_ssm_step`) with
    the skip ``D x``; the gated norm ``RMSNorm(y * silu(z))`` over a
    group's channels; ``Wo``, times ``out_multiplier``. Everything between
    the two projections is float32 but ``xBC`` itself, which is rounded to
    the parameter dtype before the convolution, as the cached tail is.

    Without a cache (forward, prefill) the whole sequence goes through the
    chunked form from a zero state, padding (``token_mask`` False) gets
    ``dt = 0``, and what is kept is (the state after the last real token
    (b, state size, heads x head size), the last ``d_conv - 1`` REAL inputs
    of the convolution (b, channels, d_conv - 1), zeros before a short
    prompt). With
    ``cache`` = (the segment's states (entries, b, state size, heads x head
    size), its tails (entries, b, channels, d_conv - 1), entry, table) and
    Tq = 1 one step of the recurrence: the layer's state and tail are read
    at ``entry`` and written back there, rows where ``token_mask`` is
    False bit for bit as they were, and what is kept is the two arrays
    whole (the layer loop's carry). With ``table``, the live slots'
    (``nn/ops/ssm_decode.live_table``), the state goes through the kernel
    that visits those slots alone, each block once, in ``_ssm_step``'s
    stead."""
    cfg = m.cfg
    heads, p, n, inner, conv = m.n_heads, m.head_dim, m.d_state, m.inner, m.conv
    g, k = m.n_groups, m.d_conv
    r = heads // g
    b, tq, _d = a_in.shape
    f32, dt_ = jnp.float32, a_in.dtype
    with _scope("ssm_proj"):
        if m.in_multiplier != 1.0:
            a_in = a_in * m.in_multiplier
        proj = jnp.matmul(a_in, bp["Win"], preferred_element_type=f32)
        if m.multipliers:
            proj = proj * m.mup
        z = proj[..., :inner]
        xbc = proj[..., inner:inner + conv].astype(dt_)
        dt = proj[..., inner + conv:]
    with _scope("ssm_conv"):
        w, bias = bp["conv_w"].astype(f32), bp["conv_b"].astype(f32)
        if cache is None:
            padded = jnp.pad(xbc.astype(f32), ((0, 0), (k - 1, 0), (0, 0)))
            u = bias + sum(padded[:, j:j + tq] * w[:, j] for j in range(k))
            lengths = (jnp.full((b,), tq, jnp.int32) if token_mask is None
                       else jnp.sum(token_mask, axis=-1).astype(jnp.int32))
            at = lengths[:, None] - (k - 1) + jnp.arange(k - 1)[None]
            tail = jnp.take_along_axis(xbc, jnp.maximum(at, 0)[:, :, None],
                                       axis=1)
            tail = jnp.where(at[:, :, None] >= 0, tail, 0).transpose(0, 2, 1)
        else:
            states, tails, layer, table = cache
            old = jax.lax.dynamic_index_in_dim(tails, layer, 0, keepdims=False)
            window = jnp.concatenate([old, xbc[:, 0, :, None]], axis=-1)
            u = (bias + jnp.sum(window.astype(f32) * w, axis=-1))[:, None]
            tail = window[..., 1:]
            if token_mask is not None:
                tail = jnp.where(token_mask[:, :, None], tail, old)
            tails = jax.lax.dynamic_update_index_in_dim(tails, tail, layer, 0)
        u = jax.nn.silu(u)
        xs = u[..., :inner].reshape(b, tq, g, r, p)
        bm = u[..., inner:inner + g * n].reshape(b, tq, g, n)
        cm = u[..., inner + g * n:].reshape(b, tq, g, n)
    with _scope("ssm_scan"):
        dt = jax.nn.softplus(dt + bp["dt_bias"]).reshape(b, tq, g, r)
        a = -jnp.exp(bp["A_log"]).reshape(g, r)
        if cache is None:
            if token_mask is not None:
                dt = jnp.where(token_mask[:, :, None, None], dt, 0.0)
            y, h = _ssm_chunked(xs, dt, a, bm, cm, m.chunk,
                                None if token_mask is None else jnp.max(lengths))
            made = (h.reshape(b, inner, n).transpose(0, 2, 1), tail)
        elif table is not None:
            step = ssm_decode_impl(heads, p, n, g, b, states.dtype)
            x1, d1, b1, c1 = xs[:, 0], dt[:, 0], bm[:, 0], cm[:, 0]
            decay = jnp.exp(d1 * a)
            hc, states = step(
                states, layer, table, (d1[..., None] * x1).reshape(b, inner),
                jnp.broadcast_to(decay[..., None], x1.shape).reshape(b, inner),
                b1, c1)
            # ``_ssm_step``'s readout, on the kernel's sum over the old state
            y = (decay[..., None] * hc.reshape(b, g, r, p)
                 + (d1 * jnp.sum(b1 * c1, axis=-1)[:, :, None])[..., None] * x1)
            y, made = y[:, None], (states, tails)
        else:
            # ``_ssm_step`` takes the state size minor
            old = jax.lax.dynamic_index_in_dim(
                states, layer, 0, keepdims=False).transpose(0, 2, 1).reshape(
                    b, g, r, p, n)
            y, h = _ssm_step(old, xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
            if token_mask is not None:
                h = jnp.where(token_mask[:, :, None, None, None], h, old)
            states = jax.lax.dynamic_update_index_in_dim(
                states, h.reshape(b, inner, n).transpose(0, 2, 1), layer, 0)
            y, made = y[:, None], (states, tails)
        y = y + bp["D"].reshape(g, r, 1) * xs
    with _scope("ssm_proj"):
        gated = y.reshape(b, tq, g, inner // g) * jax.nn.silu(
            z.reshape(b, tq, g, inner // g))
        gated = _rms_norm(gated, bp["norm_g"].reshape(g, inner // g),
                          cfg.norm_eps).reshape(b, tq, inner).astype(dt_)
        out = gated @ bp["Wo"]
        if m.out_multiplier != 1.0:
            out = out * m.out_multiplier
    return out, made


def _experts(cfg: DecoderConfig, bp: Dict[str, Array], r_in: Array, dtype,
             token_mask, layer):
    """The expert layer on r_in (N, d) float32 (the normed residual):
    ``moe_dropless_ffn`` with the configuration's routing rule and shared
    expert, ``EXPERT_TOKEN_CHUNK`` tokens at a time where there are more
    (each chunk reads the held experts' weights again, which a prefill of
    thousands of tokens can afford and a plan of N x k gathered rows in
    float32 cannot)."""
    def run(r, mask):
        return moe_dropless_ffn(r.astype(dtype), r, bp, cfg.top_k,
                                cfg.experts_held, mask, layer,
                                route=cfg.route(), shared=bool(cfg.shared_width))

    n, d = r_in.shape
    chunk = EXPERT_TOKEN_CHUNK
    if n <= chunk:
        return run(r_in, token_mask)
    pad = -n % chunk
    mask = (jnp.ones((n,), bool) if token_mask is None else token_mask)
    r_in = jnp.pad(r_in, ((0, pad), (0, 0))).reshape(-1, chunk, d)
    mask = jnp.pad(mask, (0, pad)).reshape(-1, chunk)
    y, pairs, hit = jax.lax.map(lambda rm: run(*rm), (r_in, mask))
    return y.reshape(-1, d)[:n], pairs.sum(), hit.sum()


#: the leaves of an expert layer that stay stacked through a segment's scan
EXPERT_STACKS = ("Eg", "Eu", "Ed")


# -- the mixer kinds ----------------------------------------------------------
def _live_lengths(q_pos, token_mask):
    """What a decode kernel reads a row by: its positions, 0 where idle."""
    return q_pos[:, 0] if token_mask is None else jnp.where(
        token_mask[:, 0], q_pos[:, 0], 0)


def _n_real(token_mask):
    """The real positions of the longest row: the blocked forms stop there."""
    return None if token_mask is None else jnp.max(jnp.sum(token_mask, axis=-1))


def _write_slot(slab, new, slot):
    """new (entries, 1, ...) -> row ``slot`` of the (donated) slab."""
    if any(n > held for n, held in zip(new.shape[2:], slab.shape[2:])):
        raise ValueError("prefill bucket longer than the slot")
    return jax.lax.dynamic_update_slice(
        slab, new, (0, slot) + (0,) * (slab.ndim - 2))


class _Mixer(abc.ABC):
    """ONE kind of mixer: every decision this file and the engine need
    about the kind, resolved once from ``attn_kinds[kind]`` (:meth:`of`). A
    kind that leaves an answer out fails where the configuration is built,
    not inside a trace."""

    #: the scope a prefill's write of the kind's cache runs under
    fill_scope = "kv_write"
    #: what the engine counts for the kind (``plan``; ``_DecoderBackend``)
    latent = attends = keeps_state = False
    topk = 0

    def __init__(self, cfg: "DecoderConfig", kind: str):
        self.cfg, self.kind = cfg, kind
        ak = cfg.attn_kinds[kind]
        self.theta, self.scaling = ak["rope_theta"], ak["rope_scaling"]

    @staticmethod
    def of(cfg: "DecoderConfig", kind: str) -> "_Mixer":
        """The entry that ``attn_kinds[kind]`` describes."""
        ak = cfg.attn_kinds[kind]
        if ak["ssm"]:
            return (_Parallel if ak["parallel"] else _StateSpace)(cfg, kind)
        if ak["latent"]:
            return (_IndexedLatent if ak["index"] else _Latent)(cfg, kind)
        return (_KeysValues if ak["window"] is None else _Ring)(cfg, kind)

    @abc.abstractmethod
    def leaves(self) -> Dict[str, tuple]:
        """The mixer half of :func:`segment_shapes`: leaf -> (ONE layer's
        shape, dtype), in the order ``init_params`` draws them."""

    @abc.abstractmethod
    def plan(self, entries: int, slots: int, max_length: int) -> dict:
        """The kind's part of a ``cache_plan`` entry (:meth:`_plan`) for
        ``entries`` = passes x layers."""

    def _plan(self, slabs, entries=0, dtypes=None, **report) -> dict:
        """``slabs`` as allocated, ``dtypes`` (the parameters' unless given)
        and ``bytes``; the report's fields; what the engine counts."""
        dtypes = dtypes or [self.cfg.dtype] * len(slabs)
        return {**report, "slabs": slabs, "dtypes": dtypes,
                "bytes": sum(int(np.prod(s)) * jnp.dtype(d).itemsize
                             for s, d in zip(slabs, dtypes)),
                "latent": self.latent, "attends": self.attends,
                "topk": self.topk, "keeps_state": self.keeps_state,
                "entries": entries}

    def positions(self, pos: Array, slabs) -> Optional[Array]:
        """(b, Tc): the absolute position each column of ``slabs`` holds
        for a row with ``pos`` positions behind it, -1 where none; None
        for a kind that reads its cache by no map."""
        return None

    @abc.abstractmethod
    def open(self, slabs, q_pos, c_pos, token_mask, looped: bool):
        """How the layer loop reads the segment's ``slabs``, decided ONCE a
        segment and step, before the scan: (the slabs the scan SLICES a
        layer at a time or None; those it CARRIES whole or None;
        ``look(sliced, carried, entry)`` -> the layer's view of the cache,
        which :meth:`mix` alone unpacks). What is made once lies in
        ``look``'s closure: the rows' lengths, the live slots' table, the
        walk over the live tiles, the kernel the registry admitted, slabs
        a kernel reads whole by the entry's index. ``c_pos``: the position
        maps by kind; ``looped``: the slabs hold several passes' entries."""

    def _by_layer(self, slabs, looped: bool, view):
        """:meth:`open`'s answer for slabs read a layer at a time: sliced
        by the scan or, where they hold every pass's entries, carried whole
        (no layer changes them) and a layer's entry taken by index: a
        pass's part cut out of a slab for a scan would be a copy of it."""
        if not looped:
            return slabs, None, lambda sliced, _held, _at: view(sliced)
        return None, slabs, lambda _sliced, held, at: view(tuple(
            jax.lax.dynamic_index_in_dim(c, at, 0, keepdims=False)
            for c in held))

    def first(self, sel, b: int, tq: int, slabs):
        """The selection a segment's scan starts from (``sel``: the last)."""
        return sel

    @abc.abstractmethod
    def mix(self, bp, x, q_pos, view, token_mask, sel):
        """The mixer on x (b, Tq, d) at absolute positions q_pos (b, Tq),
        bp ONE layer's leaves, ``view`` the layer's cache (:meth:`open`)
        or None (full forward, prefill) -> (x + its output; what the layer
        made to cache of the step's own positions; the selection it hands
        the next layer, ``sel`` as it came where it selects nothing; the
        carried slabs where it WROTE them, else None)."""

    @abc.abstractmethod
    def put(self, slabs, new, q_pos, active):
        """A decode step's after-loop write of ``new`` (entries, b, ...),
        made at q_pos (b, 1), into the (donated) slabs as the loop handed
        them on; ``active`` (b,) bool or None."""

    def after(self, slabs, held):
        """A segment's slabs as the layer loop hands them on, for the
        after-loop write: those it carried (``held``, :meth:`open`'s second
        answer as the scan's end has it), else ``slabs`` as they came."""
        return slabs if held is None else held

    def fill(self, slabs, new, slot, length):
        """A prefill's write of ``new`` (entries, 1, ...), made of a bucket's
        positions, ``length`` real, into row ``slot`` of the (donated)
        slabs, under the kind's ``fill_scope``."""
        with _scope(self.fill_scope):
            return self._fill(slabs, new, slot, length)

    def _fill(self, slabs, new, slot, length):
        """:meth:`fill`'s write: as made, unless the kind lays its slabs
        out otherwise."""
        return tuple(_write_slot(c, n, slot) for c, n in zip(slabs, new))


class _Branches(_Mixer):
    """A mixer that is one BRANCH, or several, off the block's one normed
    input: :meth:`mix` is the norm (``norm1``), the branches
    (:meth:`branch`) and the join of what they give into the residual
    (``residual_multiplier``, ``norm1b`` under ``sandwich_norm``), the norm
    and the join under the kind's ``join_scope``. An entry made of other
    entries (:class:`_Parallel`) hands the one normed input to each."""

    #: the scope the norm and the join run under
    join_scope: str

    def mix(self, bp, x, q_pos, view, token_mask, sel):
        cfg = self.cfg
        with _scope(self.join_scope):
            a_in = _rms_norm(x, bp["norm1"], cfg.norm_eps).astype(x.dtype)
        out, made, wrote = self.branch(bp, a_in, q_pos, view, token_mask)
        with _scope(self.join_scope):
            x = _residual(cfg, x, _branch(cfg, bp, "norm1b", out))
        return x, made, sel, wrote

    @abc.abstractmethod
    def branch(self, bp, a_in, q_pos, view, token_mask):
        """The kind's branch on the normed input a_in (b, Tq, d) -> (its
        output (b, Tq, d); what it made to cache of the step's own
        positions; the carried slabs where it WROTE them, else None)."""


class _KeysValues(_Branches):
    """Keys and values by head over the slot's whole length, T-minor: K
    (entries, slots, hkv, head, T) and V. The queries attend, under one
    softmax (with the learned ``sink`` where the kind has one), to the
    step's own keys and to the cache columns: by two whole-slab einsums
    or by the live-tile kernel (``nn/ops/decode_attention.py``)."""

    attends = True

    def __init__(self, cfg, kind):
        super().__init__(cfg, kind)
        ak = cfg.attn_kinds[kind]
        self.hkv, self.sink, self.window = (ak["n_kv_heads"], ak["sink"],
                                            ak["window"])
        self.in_multiplier, self.key_multiplier, self.out_multiplier = (
            ak["in_multiplier"], ak["key_multiplier"], ak["out_multiplier"])
        self.join_scope = ("attn_full" if self.window is None
                           else "attn_window")

    def leaves(self):
        """``Wq`` is stored by head, (d, heads, head size): flat, the TPU
        compiler re-laid its 100 MB out in every layer of a decode step to
        split a product 12,288 wide into heads of 192 (by compile, PR 27)."""
        c, pd = self.cfg, self.cfg.dtype
        out = {"Wq": ((c.d_model, c.n_heads, c.head_dim), pd),
               "Wk": ((c.d_model, self.hkv * c.head_dim), pd),
               "Wv": ((c.d_model, self.hkv * c.v_head_dim), pd),
               "Wo": ((c.n_heads * c.v_head_dim, c.d_model), pd)}
        if self.sink:
            out["sink"] = ((c.n_heads,), jnp.float32)
        return out

    def plan(self, entries, slots, max_length):
        c = self.cfg
        cols = max_length if self.window is None else min(self.window,
                                                         max_length)
        k = (entries, slots, self.hkv, c.head_dim, cols)
        v = (entries, slots, self.hkv, c.v_head_dim, cols)
        return self._plan([k, v], entries, columns=cols,
                          ring=self.window is not None, k=k, v=v,
                          values=self.hkv * (c.head_dim + c.v_head_dim))

    def positions(self, pos, slabs):
        """Column p holds position p."""
        c = jnp.arange(slabs[0].shape[-1], dtype=jnp.int32)[None, :]
        return jnp.where(c <= pos.astype(jnp.int32)[:, None] - 1, c, -1)

    def kernel(self, k_slab, v_slab):
        """The live-tile kernel for a decode step over the slabs and its
        tile, by the registry's verdict for these shapes (a TPU, the probe
        passed, a layer's K + V worth a call); None where the einsums
        serve, as they do for a kind with a sink or a window."""
        if self.sink or self.window is not None:
            return None
        _entries, b, hkv, hd, t = k_slab.shape
        return decode_attention_impl(b, hkv, self.cfg.n_heads // hkv, hd,
                                     v_slab.shape[3], t, k_slab.dtype)

    def open(self, slabs, q_pos, c_pos, token_mask, looped):
        core = self.kernel(*slabs) if q_pos.shape[1] == 1 else None
        if core is None:
            return self._by_layer(
                slabs, looped, lambda kv: ("columns", *kv, c_pos[self.kind]))
        # a custom call's operand is made whole, so the scan's slice of a
        # slab would be copied a layer: K and V whole, the entry's index
        # and the walk over the rows' live tiles, made here once
        walk = live_tiles(_live_lengths(q_pos, token_mask),
                          slabs[0].shape[-1], core[1])
        return None, None, lambda _sliced, _held, at: (
            "tiles", *slabs, at, core[0], walk)

    def branch(self, bp, a_in, q_pos, view, token_mask):
        """Makes the layer's new (b, hkv, Tq, head) keys and (b, hkv, Tq,
        value size) values. Without a cache, a sink or a window, a bucket
        whose float32 scores would pass ``BLOCKED_SCORE_BYTES`` attends by
        blocks. The kind's multipliers, where it states them: on the
        input, on the keys before they are rotated, on the output."""
        cfg, window = self.cfg, self.window
        x = a_in  # the stream's dtype
        b, tq, _d = x.shape
        hq, hkv, hd, vd = cfg.n_heads, self.hkv, cfg.head_dim, cfg.v_head_dim
        grp = hq // hkv
        scale = softmax_scale(cfg, self.kind)
        with _scope(self.join_scope):
            if self.in_multiplier != 1.0:
                a_in = a_in * self.in_multiplier
            q = jnp.einsum("btd,dhk->bthk", a_in, bp["Wq"])
            k = (a_in @ bp["Wk"]).reshape(b, tq, hkv, hd)
            if self.key_multiplier != 1.0:
                k = k * self.key_multiplier
            v = (a_in @ bp["Wv"]).reshape(b, tq, hkv, vd)
            q = _rotate(q, q_pos, cfg.rotary_dim, self.theta)
            k = _rotate(k, q_pos, cfg.rotary_dim, self.theta)
            if view is not None and view[0] == "tiles":
                _how, k_slab, v_slab, at, core, walk = view
                kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
                # query head i reads key/value head i // grp
                o = core(q.reshape(b, hkv, grp, hd), kh[:, :, 0], vh[:, :, 0],
                         k_slab, v_slab, at, walk, scale=scale)
                if cfg.value_scale != 1.0:
                    o = o * cfg.value_scale
                o = o.reshape(b, tq, hq * vd).astype(x.dtype)
            elif (view is None and window is None and not self.sink
                    and hq * tq * tq * 4 > BLOCKED_SCORE_BYTES):
                kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
                o = _causal_blocked(
                    q, jnp.repeat(k, grp, axis=2), jnp.repeat(v, grp, axis=2),
                    scale, PREFILL_BLOCK, _n_real(token_mask))
                if cfg.value_scale != 1.0:
                    o = o * cfg.value_scale
                o = o.reshape(b, tq, hq * vd)
            else:
                # query head i reads key/value head i // grp
                qg = q.reshape(b, tq, hkv, grp, hd).transpose(0, 2, 3, 1, 4)
                kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
                f32 = jnp.float32
                s_own = jnp.einsum("bkgqd,bktd->bkgqt", qg, kh,
                                   preferred_element_type=f32) * scale
                s_own = jnp.where(_visible(q_pos, q_pos, window)[:, None, None],
                                  s_own, _NEG)
                m = s_own.max(-1)
                if view is not None:
                    _how, kc, vc, c_pos = view
                    s_c = jnp.einsum("bkgqd,bkdt->bkgqt", qg, kc,
                                     preferred_element_type=f32) * scale
                    s_c = jnp.where(_visible(q_pos, c_pos, window)[:, None, None],
                                    s_c, _NEG)
                    m = jnp.maximum(m, s_c.max(-1))
                if self.sink:
                    sink = bp["sink"].astype(f32).reshape(1, hkv, grp, 1)
                    m = jnp.maximum(m, sink)
                e_own = jnp.exp(s_own - m[..., None])
                z = e_own.sum(-1)
                o = jnp.einsum("bkgqt,bktd->bkgqd", e_own.astype(x.dtype), vh,
                               preferred_element_type=f32)
                if view is not None:
                    e_c = jnp.exp(s_c - m[..., None])
                    z = z + e_c.sum(-1)
                    o = o + jnp.einsum("bkgqt,bkdt->bkgqd", e_c.astype(x.dtype),
                                       vc, preferred_element_type=f32)
                if self.sink:
                    z = z + jnp.exp(sink - m)  # the sink takes weight, adds no value
                o = o * (cfg.value_scale / z[..., None])
                o = o.transpose(0, 3, 1, 2, 4).reshape(b, tq, hq * vd).astype(x.dtype)
            out = o @ bp["Wo"]
            if self.out_multiplier != 1.0:
                out = out * self.out_multiplier
        return out, (kh, vh), None

    def put(self, slabs, new, q_pos, active):
        """One in-place column a live row (``_put_columns``: one kernel
        call a slab where the registry admits it)."""
        wp = jnp.minimum(q_pos, slabs[0].shape[-1] - 1)
        return tuple(_put_columns(c, n, wp, active)
                     for c, n in zip(slabs, new))

    def _fill(self, slabs, new, slot, length):
        """The bucket's columns at 0..Tb-1: padding follows the real
        tokens, so causal attention keeps it from them."""
        return tuple(_write_slot(c, n.transpose(0, 1, 2, 4, 3), slot)
                     for c, n in zip(slabs, new))


class _Ring(_KeysValues):
    """Keys and values of a WINDOW layer: a ring of ``window`` columns
    whatever the slot's length, position p in column p mod window, read by
    the same einsums under another position map."""

    attends = False

    def positions(self, pos, slabs):
        """Column c holds the latest position below ``pos`` that is
        congruent to c (< 0: not yet written)."""
        cols = slabs[0].shape[-1]
        c = jnp.arange(cols, dtype=jnp.int32)[None, :]
        last = pos.astype(jnp.int32)[:, None] - 1
        return last - jnp.mod(last - c, cols)

    def put(self, slabs, new, q_pos, active):
        """Row s's column -> ring[:, s, :, :, pos[s] mod window], as ONE
        select over the whole (donated) ring: a fixed pass whatever the
        slot's length, where ``_put_columns`` costs an operation a slot."""
        def select(ring, n):
            cols = ring.shape[4]
            at = jnp.mod(q_pos[:, 0], cols)[None, :, None, None, None]
            here = jnp.arange(cols, dtype=at.dtype)[None, None, None, None, :]
            return jnp.where(here == at, n.transpose(0, 1, 2, 4, 3), ring)

        return tuple(select(c, n) for c, n in zip(slabs, new))

    def _fill(self, slabs, new, slot, length):
        """Column c gets the latest real position congruent to c: the
        prompt's last ``window`` columns where it is longer than the ring."""
        cols = slabs[0].shape[-1]
        new = tuple(n.transpose(0, 1, 2, 4, 3) for n in new)
        if new[0].shape[-1] > cols:
            c = jnp.arange(cols, dtype=jnp.int32)
            src = jnp.maximum(length - 1 - jnp.mod(length - 1 - c, cols), 0)
            new = tuple(jnp.take(n, src, axis=4) for n in new)
        return tuple(_write_slot(c, n, slot) for c, n in zip(slabs, new))


class _Latent(_Mixer):
    """Latent attention (:func:`_latent_attention`): ONE slab (entries,
    slots, kv_rank + rotary_dim, T) of the compressed key/value entry and
    the one rotated key a position, T-minor, mapped as a full K/V slab."""

    latent = True
    positions = _KeysValues.positions

    def __init__(self, cfg, kind):
        super().__init__(cfg, kind)
        latent = cfg.attn_kinds[kind]["latent"]
        self.q_rank, self.kv_rank = latent["q_rank"], latent["kv_rank"]
        #: values a position and layer caches
        self.width = self.kv_rank + cfg.rotary_dim

    def leaves(self):
        """The up-projections are by head (``Wq``'s reason), the key/value
        one in its two halves, ``Wuk`` and ``Wuv``, which the absorbed
        decode contracts on opposite sides and never together."""
        c, pd, f32 = self.cfg, self.cfg.dtype, jnp.float32
        d, hq, qr, kr = c.d_model, c.n_heads, self.q_rank, self.kv_rank
        return {"Wqa": ((d, qr), pd), "norm_q": ((qr,), f32),
                "Wqb": ((qr, hq, c.head_dim), pd),
                "Wkva": ((d, kr + c.rotary_dim), pd),
                "norm_kv": ((kr,), f32),
                "Wuk": ((kr, hq, c.head_dim - c.rotary_dim), pd),
                "Wuv": ((kr, hq, c.v_head_dim), pd),
                "Wo": ((hq * c.v_head_dim, d), pd)}

    def plan(self, entries, slots, max_length):
        return self._plan([(entries, slots, self.width, max_length)], entries,
                          columns=max_length, ring=False, values=self.width)

    def kernel(self, slab):
        """The length-aware kernel for a decode step over ``slab``, by the
        registry's verdict for its shape (None: the einsums serve)."""
        return latent_decode_impl(self.cfg.n_heads, slab.shape[2],
                                  slab.shape[3], slab.dtype, self.kv_rank)

    def open(self, slabs, q_pos, c_pos, token_mask, looped):
        if q_pos.shape[1] != 1 or self.kernel(slabs[0]) is None:
            return self._by_layer(
                slabs, looped, lambda kv: ("columns", *kv, c_pos[self.kind]))
        # the slab whole (``_KeysValues.open``'s reason), by the rows'
        # lengths: a row that is not active has none
        lengths = _live_lengths(q_pos, token_mask)
        return None, None, lambda _sliced, _held, at: (
            "kernel", slabs[0], at, lengths)

    def mix(self, bp, x, q_pos, view, token_mask, sel):
        x, entries = _latent_attention(
            self.cfg, self.kind, bp, x, q_pos, view,
            _n_real(token_mask) if view is None else None)
        return x, (entries,), sel, None

    def put(self, slabs, new, q_pos, active):
        """``_KeysValues.put``'s column write, on the slab seen as one
        head whose "head size" is the entry."""
        wp = jnp.minimum(q_pos, slabs[0].shape[-1] - 1)
        return (_put_columns(slabs[0][:, :, None], new[0][:, :, None], wp,
                             active)[:, :, 0],)

    def _fill(self, slabs, new, slot, length):
        return (_write_slot(slabs[0], new[0].transpose(0, 1, 3, 2), slot),)


class _IndexedLatent(_Latent):
    """Latent attention over an indexer's SELECTION
    (:func:`_sparse_latent_attention`). The slabs are POSITION-MAJOR,
    (entries, slots, T, row): a decode step gathers ``topk`` chosen
    positions a slot, and a chosen position is then one row whose values
    lie together, where a T-minor slab would put the gather on the minor
    axis (PERF.md, PR 40). An owner has a second slab, its indexer's keys
    (entries, slots, T, indexer head size). The layer loop carries the
    selection, and the slabs whole for the after-loop write to take from
    its end: closed over, the loop's copy of them and the donated buffer
    the write updates were two, a slab-sized copy a step (PR 40)."""

    def __init__(self, cfg, kind):
        super().__init__(cfg, kind)
        self.index = cfg.attn_kinds[kind]["index"]
        self.topk = self.index["topk"]
        #: values a ROW of the latent slab holds: ``width`` in whole tiles
        #: of 128 lanes, the tail zero. At 576 values a row the TPU
        #: compiler copies the whole slab, padded, before every gather from
        #: it (528 MB a layer and step, by compile, PR 40); at 640 it does not
        self.row = -(-self.width // 128) * 128

    def leaves(self):
        """An owner adds the indexer's matrices (``Iq`` by head, ``Ik``
        with its LayerNorm, ``Iw``); a sharer has none."""
        out = super().leaves()
        if self.index["own"]:
            c, pd, f32 = self.cfg, self.cfg.dtype, jnp.float32
            ih, idim = self.index["heads"], self.index["head_dim"]
            wo = out.pop("Wo")
            out.update({"Iq": ((self.q_rank, ih, idim), pd),
                        "Ik": ((c.d_model, idim), pd),
                        "norm_ik": ((idim,), f32), "bias_ik": ((idim,), f32),
                        "Iw": ((c.d_model, ih), pd), "Wo": wo})
        return out

    def plan(self, entries, slots, max_length):
        """``row``: a latent row as stored; ``index``: the key slab's width;
        ``values``: what the mathematics keeps a position and layer."""
        slabs, report = [(entries, slots, max_length, self.row)], {}
        if self.index["own"]:
            report["index"] = self.index["head_dim"]
            slabs.append((entries, slots, max_length, report["index"]))
        return self._plan(slabs, entries, columns=max_length, ring=False,
                          row=self.row, **report,
                          values=self.width + report.get("index", 0))

    positions = _Mixer.positions  # read by the rows' lengths, not by a map

    def kernel(self, slabs):
        """(The kernel that walks the live rows under the selection's bias
        for a decode step over ``slabs``, its tile), by the registry's
        verdict for their shape (None: the gathered rows serve)."""
        rows = slabs[0]
        return sparse_latent_decode_impl(
            self.cfg.n_heads, rows.shape[3], rows.shape[2],
            min(self.topk, rows.shape[2]), rows.dtype, self.kv_rank)

    def open(self, slabs, q_pos, c_pos, token_mask, looped):
        admitted = self.kernel(slabs) if q_pos.shape[1] == 1 else None
        if admitted is None:
            lengths = q_pos[:, 0]
            return None, slabs, lambda _sliced, held, at: (
                "rows", held, at, lengths)
        # by the rows' lengths: a row that is not active has none, and the
        # walk over the live tiles is every layer's
        kernel, tile = admitted
        lengths = _live_lengths(q_pos, token_mask)
        walk = live_walk(lengths, slabs[0].shape[2], tile)
        return None, slabs, lambda _sliced, held, at: (
            "kernel", held, at, lengths, kernel, walk)

    def first(self, sel, b, tq, slabs):
        """An owner's scan starts from a selection's shapes with nothing
        in them: a mask without a cache (none up to ``topk`` positions);
        over one the columns, their count and whether the own position is
        in, and for the kernel their bias."""
        if not self.index["own"]:
            return sel
        if slabs is None:
            return jnp.zeros((b, tq, tq), bool) if tq > self.topk else None
        t_c = slabs[0].shape[2]
        sel = (jnp.zeros((b, min(self.topk, t_c)), jnp.int32),
               jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool))
        if tq == 1 and self.kernel(slabs) is not None:
            sel += (jnp.zeros((b, 1, t_c), jnp.float32),)
        return sel

    def mix(self, bp, x, q_pos, view, token_mask, sel):
        x, entries, sel = _sparse_latent_attention(
            self.cfg, self.kind, bp, x, q_pos, view, sel,
            _n_real(token_mask) if view is None else None)
        return x, entries, sel, None

    def put(self, slabs, new, q_pos, active):
        """Row s's entry -> slab[:, s, pos[s], :], latents and keys, each
        ONE in-place ``dynamic_update_slice`` (``_put_columns``'s reasons)."""
        wp = jnp.minimum(q_pos, slabs[0].shape[2] - 1)
        out = []
        for slab, n in zip(slabs, new):
            for s in range(n.shape[1]):
                slab = jax.lax.dynamic_update_slice(slab, n[:, s:s + 1],
                                                    (0, s, wp[s, 0], 0))
            out.append(slab)
        return tuple(out)

    _fill = _Mixer._fill  # the bucket's rows, as made


class _StateSpace(_Branches):
    """A state-space (Mamba-2) mixer (:func:`_ssm_mixer`): no columns, no
    position map. Its cache is ``state`` (entries, slots, state size, heads
    x head size) in float32 (a bfloat16 state would round at every step of
    a recurrence thousands long; the state size major, so that the decode
    kernel's per-channel scalars are lane vectors) and ``conv`` (entries,
    slots, convolved channels, d_conv - 1), the convolution's last inputs,
    in the parameter dtype. A decode step WRITES both in the layer loop,
    which carries them, each layer's entry in place on the donated buffer
    (a scan's stacked output would be a second copy of the state)."""

    fill_scope = "state_write"
    join_scope = "ssm_proj"
    keeps_state = True

    def __init__(self, cfg, kind):
        super().__init__(cfg, kind)
        for field, value in cfg.attn_kinds[kind]["ssm"].items():
            setattr(self, field, value)
        #: inner channels, and the convolved ones ([x | B | C])
        self.inner = self.d_inner
        self.conv = self.inner + 2 * self.n_groups * self.d_state
        if self.multipliers:
            #: the five multipliers spread over the input projection's
            #: columns [z | x | B | C | dt], float32 as the product is
            bc = self.n_groups * self.d_state
            self.mup = np.repeat(
                np.asarray(self.multipliers, np.float32),
                [self.inner, self.inner, bc, bc, self.n_heads])

    def leaves(self):
        """The input projection is ONE leaf, ``Win`` (d, inner + convolved
        + heads) with its columns in the published order [z | xBC | dt]:
        one product reads the weights once, and its three parts are cut
        from the RESULT at offsets that are multiples of 128 lanes at the
        published widths (8,192 and 16,640), so no part of the weight is
        ever sliced or re-laid (by compile). The recurrence's per-head
        scalars and the gated norm's gain are float32."""
        d, pd, f32 = self.cfg.d_model, self.cfg.dtype, jnp.float32
        h, inner, conv = self.n_heads, self.inner, self.conv
        return {"Win": ((d, inner + conv + h), pd),
                "conv_w": ((conv, self.d_conv), pd), "conv_b": ((conv,), pd),
                "dt_bias": ((h,), f32), "A_log": ((h,), f32), "D": ((h,), f32),
                "norm_g": ((inner,), f32), "Wo": ((inner, d), pd)}

    def plan(self, entries, slots, max_length):
        state = (entries, slots, self.d_state, self.inner)
        taps = (entries, slots, self.conv, self.d_conv - 1)
        return self._plan([state, taps], dtypes=[jnp.float32, self.cfg.dtype],
                          columns=0, state=state, conv=taps)

    def kernel(self, states):
        """The live-slot kernel for a decode step over ``states``, by the
        registry's verdict for their shape (None: ``_ssm_step`` serves)."""
        return ssm_decode_impl(self.n_heads, self.head_dim, self.d_state,
                               self.n_groups, states.shape[1], states.dtype)

    def open(self, slabs, q_pos, c_pos, token_mask, looped):
        table = None
        if q_pos.shape[1] == 1 and self.kernel(slabs[0]) is not None:
            table = live_table(jnp.ones(q_pos.shape[:1], bool)
                               if token_mask is None else token_mask[:, 0])
        return None, slabs, lambda _sliced, held, at: (*held, at, table)

    def branch(self, bp, a_in, q_pos, view, token_mask):
        """Over a cache nothing is made for an after-loop write: the two
        arrays come back WRITTEN, the loop's carry."""
        out, kept = _ssm_mixer(self, bp, a_in, view, token_mask)
        return (out, kept, None) if view is None else (out, (), kept)

    def put(self, slabs, new, q_pos, active):
        """Written inside the loop, in place: as they are."""
        return tuple(slabs)


class _Parallel(_Branches):
    """Attention AND a state-space mixer in one block (Falcon-H1): both
    read the block's one normed input and their outputs are added before
    the sum joins the residual. The entry is MADE OF the two that exist, a
    :class:`_KeysValues` and a :class:`_StateSpace` of the same kind's
    statement, and keeps no decision of theirs a second time: its leaves
    are both sets (the state-space output projection as ``Wso``, beside
    the attention's ``Wo``; one ``norm1``), its cache entry FOUR slabs (K,
    V, ``state``, ``conv``), K and V read and written as ``_KeysValues``
    says and the state and tail as ``_StateSpace`` says, in the same layer
    loop: the scan slices K and V (or a kernel reads them whole by index)
    while it carries, and writes, the state."""

    attends = keeps_state = True
    join_scope = "mixer_join"

    def __init__(self, cfg, kind):
        super().__init__(cfg, kind)
        self.attn, self.ssm = _KeysValues(cfg, kind), _StateSpace(cfg, kind)

    def positions(self, pos, slabs):
        return self.attn.positions(pos, slabs[:2])

    def leaves(self):
        ssm = self.ssm.leaves()
        return {**self.attn.leaves(), **{k: v for k, v in ssm.items()
                                         if k != "Wo"}, "Wso": ssm["Wo"]}

    def plan(self, entries, slots, max_length):
        """``bytes_columns`` and ``bytes_state``: the entry's bytes by half,
        what grows with the slot's length and what does not."""
        a = self.attn.plan(entries, slots, max_length)
        s = self.ssm.plan(entries, slots, max_length)
        return self._plan(a["slabs"] + s["slabs"], entries,
                          a["dtypes"] + s["dtypes"], columns=a["columns"],
                          ring=False, k=a["k"], v=a["v"], values=a["values"],
                          state=s["state"], conv=s["conv"],
                          bytes_columns=a["bytes"], bytes_state=s["bytes"])

    def open(self, slabs, q_pos, c_pos, token_mask, looped):
        """Both entries' answers at once: what the attention has the scan
        slice; carried, (what the attention has it carry or None, the
        state and tail); a layer's view, the two views and the
        attention's carried slabs, which :meth:`branch` hands on as they
        came beside the state-space branch's written ones."""
        sliced, a_held, a_look = self.attn.open(slabs[:2], q_pos, c_pos,
                                                token_mask, looped)
        _none, s_held, s_look = self.ssm.open(slabs[2:], q_pos, c_pos,
                                              token_mask, looped)
        return sliced, (a_held, s_held), lambda cut, held, at: (
            a_look(cut, held[0], at), s_look(None, held[1], at), held[0])

    def after(self, slabs, held):
        a_held, s_held = held
        return (*(slabs[:2] if a_held is None else a_held), *s_held)

    def branch(self, bp, a_in, q_pos, view, token_mask):
        a_view, s_view, a_held = view or (None, None, None)
        a, kv, _ = self.attn.branch(bp, a_in, q_pos, a_view, token_mask)
        s, kept, wrote = self.ssm.branch({**bp, "Wo": bp["Wso"]}, a_in, q_pos,
                                         s_view, token_mask)
        return a + s, kv + kept, None if view is None else (a_held, wrote)

    def put(self, slabs, new, q_pos, active):
        """A column a live row for K and V; the state as the loop wrote it."""
        return (*self.attn.put(slabs[:2], new, q_pos, active),
                *self.ssm.put(slabs[2:], (), q_pos, active))

    def fill(self, slabs, new, slot, length):
        """Both writes, each under its own scope."""
        return (*self.attn.fill(slabs[:2], new[:2], slot, length),
                *self.ssm.fill(slabs[2:], new[2:], slot, length))


def block(cfg: DecoderConfig, kind: str, ffn: str, bp: Dict[str, Array],
          x: Array, q_pos: Array, view=None, token_mask=None, layer=None,
          sel=None):
    """One layer on x (b, Tq, d) at absolute positions q_pos (b, Tq); bp
    holds ONE layer's leaves: the kind's mixer (``_Mixer.mix``, which says
    what ``view`` and ``sel`` are and what it keeps), then :func:`_ffn`.
    With ``layer`` the expert weights in ``bp`` are a segment's whole
    stacks and ``layer`` the one to use. Returns (x, (made to cache,
    selection handed on, carried slabs where written), expert counters)."""
    x, *kept = cfg.mixer(kind).mix(bp, x, q_pos, view, token_mask, sel)
    x, counts = _ffn(cfg, ffn, bp, x, token_mask, layer)
    return x, tuple(kept), counts


def _ffn(cfg: DecoderConfig, ffn: str, bp: Dict[str, Array], x: Array,
         token_mask, layer):
    """The second half of a layer: x + the dense MLP or the expert layer
    of the normed x -> (x, (expert pairs computed here, held experts
    hit))."""
    b, tq, d = x.shape
    if ffn == "dense":
        with _scope("mlp"):
            m_in = _rms_norm(x, bp["norm2"], cfg.norm_eps).astype(x.dtype)
            gate, down = cfg.mlp_multipliers
            h = m_in @ bp["Wg"]
            if gate != 1.0:
                h = h * gate
            h = (jax.nn.silu(h) * (m_in @ bp["Wu"])) @ bp["Wd"]
            if down != 1.0:
                h = h * down
            x = _residual(cfg, x, _branch(cfg, bp, "norm2b", h))
        counts = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    else:
        with _scope("moe_route"):
            r_in = _rms_norm(x, bp["norm2"], cfg.norm_eps).reshape(b * tq, d)
        y, pairs, hit = _experts(
            cfg, bp, r_in, x.dtype,
            None if token_mask is None else token_mask.reshape(b * tq), layer)
        x = _residual(cfg, x, _branch(cfg, bp, "norm2b", y.reshape(b, tq, d)))
        counts = (pairs.astype(jnp.int32), hit)
    return x, counts


def _run_pass(cfg: DecoderConfig, params: Dict, x: Array, q_pos: Array,
              caches=None, c_pos=None, token_mask=None, r=None):
    """Every segment in order ONCE, each ONE ``lax.scan`` of :func:`block`
    over its stacked layers. ``caches``: the slabs a segment, read as the
    kind's entry says (``_Mixer.open``, with ``c_pos`` the position maps
    by kind); None without a cache. The scan carries (x, the selection a
    layer hands the next, which a segment hands the next too and which
    starts afresh with the pass, the slabs the entry has carried) over (the
    layer's leaves, the slabs the entry has sliced, the layer's index); a
    part the segment's kind does not use is None. ``r`` (traced) is the
    pass, where the stack runs more than once: the caches then hold passes
    x layers entries and layer i reads (a state-space layer: writes) entry
    ``r x layers + i`` of its segment's.

    Returns (x; per segment what the layers made to cache, stacked
    (layers, b, ...); summed expert counters; per segment the slabs as the
    loops hand them on, for the after-loop write to take, None without a
    cache: ``_Mixer.after``, which a kind whose entry is carried in part
    puts together from what came and what the loop carried)."""
    made, held_out = [], []
    pairs = hit = jnp.zeros((), jnp.int32)
    sel = None
    for i, (kind, ffn, n) in enumerate(cfg.segments()):
        seg = params["segments"][i]
        # the expert stacks are not sliced by the scan: the grouped
        # product takes them whole and the layer's index
        stacks = {k: seg[k] for k in EXPERT_STACKS if k in seg}
        scanned = {k: v for k, v in seg.items() if k not in stacks}
        mixer = cfg.mixer(kind)
        slabs = sliced = held = look = None
        if caches is not None:
            slabs = tuple(caches[i])
            sliced, held, look = mixer.open(slabs, q_pos, c_pos, token_mask,
                                            r is not None)
        # a layer's entry in the segment's cache: this pass's run of them
        base = None if r is None or slabs is None else r * n
        sel = mixer.first(sel, x.shape[0], x.shape[1], slabs)

        def layer(carry, xs, kind=kind, ffn=ffn, stacks=stacks, look=look,
                  base=base):
            x, sel, held = carry
            bp, sliced, at = xs
            view = None if look is None else look(
                sliced, held, at if base is None else base + at)
            x, (knew, sel, wrote), counts = block(
                cfg, kind, ffn, {**bp, **stacks}, x, q_pos, view, token_mask,
                at if stacks else None, sel)
            return (x, sel, held if wrote is None else wrote), (knew, counts)

        (x, sel, held), (knew, counts) = jax.lax.scan(
            layer, (x, sel, held),
            (scanned, sliced, jnp.arange(n, dtype=jnp.int32)))
        made.append(knew)
        # the loop's own hand-on where it carried the slabs, else as given
        held_out.append(None if slabs is None else mixer.after(slabs, held))
        pairs, hit = pairs + counts[0].sum(), hit + counts[1].sum()
    return x, made, (pairs, hit), None if caches is None else held_out


def _run_stack(cfg: DecoderConfig, params: Dict, x: Array, q_pos: Array,
               caches=None, c_pos=None, token_mask=None):
    """The stack, ``cfg.passes`` times over the SAME ``params``
    (:func:`_run_pass` is one time). With one pass that is all, and the
    stream comes back as the last layer left it (:func:`_head` norms it).
    With more, the passes are ONE ``lax.scan`` (its body, the segments'
    scans, is compiled once however many passes there are) that carries
    the caches whole, pass ``r`` reading its own entries; every pass closes
    under ``pass_close`` with the final norm, the next starting from the
    NORMED stream, and with the exit gate's reading of it where there is
    one; what the layers made to cache comes back stacked passes x layers,
    pass-major, as the cache plan lays the slabs out. Returns
    (:func:`_run_pass`'s four, x closed by the norm where passes > 1;
    (every pass's closed stream (passes, b, Tq, d), the gate's
    probabilities (passes, b, Tq) float32 or None), None where the stack
    runs once)."""
    if cfg.passes == 1:
        return *_run_pass(cfg, params, x, q_pos, caches, c_pos,
                          token_mask), None

    def one_pass(carry, r):
        x, held = carry
        x, made, counts, held = _run_pass(cfg, params, x, q_pos, held, c_pos,
                                          token_mask, r)
        with _scope("pass_close"):
            h = _rms_norm(x, params["norm_f"], cfg.norm_eps)
            leave = (jax.nn.sigmoid(jnp.sum(h * params["gate_w"], axis=-1)
                                    + params["gate_b"])
                     if cfg.exit_gate else None)
            x = h.astype(x.dtype)
        return (x, held), (made, counts, x, leave)

    (x, held), (made, counts, closed, leave) = jax.lax.scan(
        one_pass, (x, caches), jnp.arange(cfg.passes, dtype=jnp.int32))
    made = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), made)
    return (x, made, (counts[0].sum(), counts[1].sum()), held,
            (closed, leave))


def _exit_stream(cfg: DecoderConfig, closed: Array, leave: Array):
    """The stream each token's logits are read from under the exit rule:
    ``closed`` (passes, b, T, d), the passes' normed streams, ``leave``
    (passes, b, T), the gate's probability of leaving after each. The
    probability of leaving at pass r is ``leave_r x prod_{j<r} (1 -
    leave_j)``, the last pass taking what is left; a token leaves at the
    first pass where the cumulated probability reaches
    ``exit_threshold``."""
    reached = 1.0 - jnp.cumprod(1.0 - leave, axis=0) >= cfg.exit_threshold
    at = jnp.argmax(reached.at[-1].set(True), axis=0)        # the first
    return jnp.take_along_axis(closed, at[None, ..., None], axis=0)[0]


def _head(cfg: DecoderConfig, params: Dict, x: Array):
    """Logits over the held vocabulary. A tied head is the embedding
    (V, d) read transposed, by a product that contracts the minor
    dimension of both: one leaf, no second copy. A stack that runs more
    than once hands the stream over closed by the final norm, as every
    pass closes (``_run_stack``): it is not normed a second time."""
    with _scope("head"):
        if cfg.passes == 1:
            x = _rms_norm(x, params["norm_f"], cfg.norm_eps).astype(cfg.dtype)
        if cfg.tied_head:
            logits = jax.lax.dot_general(
                x, params["embed"], (((x.ndim - 1,), (1,)), ((), ())))
        else:
            logits = x @ params["head"]
        logits = logits.astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits


def _embed(cfg: DecoderConfig, params: Dict, ids: Array):
    with _scope("embed"):
        x = params["embed"][ids].astype(cfg.dtype)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        return x


def forward(cfg: DecoderConfig, params: Dict, ids: Array):
    """ids (b, T) -> float32 logits (b, T, V) over the held vocabulary.
    Where the stack runs more than once and ``exit_threshold`` is under 1,
    each token's are read from the pass it leaves at (``_exit_stream``);
    the cached programs (:func:`prefill_slot`, :func:`decode_step`) run
    every pass for every token, the rule at threshold 1."""
    b, t = ids.shape
    q_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    x, _made, _counts, _held, passes = _run_stack(
        cfg, params, _embed(cfg, params, ids), q_pos)
    if passes is not None and cfg.exit_threshold < 1.0:
        x = _exit_stream(cfg, *passes)
    return _head(cfg, params, x)


# -- the cache ----------------------------------------------------------------
def init_cache(cfg: DecoderConfig, n_slots: int, max_length: int):
    """Zeroed slabs a segment, as the cache plan has them."""
    return [tuple(jnp.zeros(shape, dtype)
                  for shape, dtype in zip(p["slabs"], p["dtypes"]))
            for p in cfg.cache_plan(n_slots, max_length)]


def cache_positions(cfg: DecoderConfig, pos: Array, caches):
    """Per mixer kind, the position map of its slabs in ``caches`` for
    rows that have ``pos`` positions behind them (``_Mixer.positions``;
    None for a kind that reads its cache by no map)."""
    out = {}
    for (kind, _f, _n), slabs in zip(cfg.segments(), caches):
        if kind not in out:
            out[kind] = cfg.mixer(kind).positions(pos, slabs)
    return out


def decode_step(cfg: DecoderConfig, params: Dict, caches, ids_1: Array,
                pos: Array, active: Optional[Array] = None):
    """One token a row: ids_1 (b,) at per-row positions pos (b,) ->
    (logits (b, V), caches, (expert pairs, experts hit)). The caches are
    read inside the layer loop and written after it, each segment's as its
    kind's entry says (``_Mixer.put``), from the slabs as the loop hands
    them on. ``active`` (b,) bool keeps idle rows out of the expert layers
    (and their counters) and leaves their state and tail bit for bit alone."""
    q_pos = pos.astype(jnp.int32)[:, None]
    x, new_kv, counts, held, _passes = _run_stack(
        cfg, params, _embed(cfg, params, ids_1[:, None]), q_pos, caches,
        cache_positions(cfg, pos, caches),
        None if active is None else active[:, None])
    with _scope("kv_write"):
        out = [cfg.mixer(kind).put(tuple(slabs), new, q_pos, active)
               for (kind, _f, _n), slabs, new
               in zip(cfg.segments(), held, new_kv)]
    return _head(cfg, params, x[:, 0]), out, counts


def prefill_slot(cfg: DecoderConfig, params: Dict, caches, ids: Array,
                 length: Array, slot: Array):
    """One prompt, right-padded to a bucket: ids (1, Tb), ``length`` real
    tokens, into row ``slot`` of every slab, from ONE pass, each segment's
    write as its kind's entry says (``_Mixer.fill``), under its scope (a
    parallel block's two, each under its own).
    Padding follows the real tokens: causal attention keeps it from them,
    the expert layers leave it out and a state-space layer's state passes
    it by. Returns (logits (1, V) at length-1, caches)."""
    _b, tb = ids.shape
    q_pos = jnp.arange(tb, dtype=jnp.int32)[None]
    real = q_pos < length
    x, new_kv, _counts, _held, _passes = _run_stack(
        cfg, params, _embed(cfg, params, ids), q_pos, token_mask=real)
    out = []
    for (kind, _f, _n), slabs, new in zip(cfg.segments(), caches, new_kv):
        out.append(cfg.mixer(kind).fill(tuple(slabs), new, slot, length))
    x_last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                          keepdims=False)
    return _head(cfg, params, x_last), out


# -- the model ----------------------------------------------------------------
class DecoderLM:
    """The serving surface ``GenerationEngine`` and ``InferenceEngine``
    read: ``cfg``, ``params_``, ``state_``, ``output``, and a solo cached
    generation that the engine's output is tested against."""

    name = "decoderlm"
    serving_seq_buckets = (16, 32, 64, 128, 256, 512)

    def __init__(self, cfg: DecoderConfig):
        self.cfg = cfg
        self.params_: Optional[Dict] = None
        self.state_ = None
        self._jit_cache: Dict = {}

    @classmethod
    def from_dict(cls, d: dict) -> "DecoderLM":
        return cls(DecoderConfig(**d))

    def init(self):
        self.params_ = init_params(self.cfg)
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params_))

    def logits(self, ids) -> np.ndarray:
        if "fwd" not in self._jit_cache:
            self._jit_cache["fwd"] = jax.jit(
                lambda p, i: forward(self.cfg, p, i))
        return np.asarray(self._jit_cache["fwd"](
            self.params_, jnp.asarray(ids, jnp.int32)))

    def output(self, x, mask=None) -> np.ndarray:
        """Token ids (b, T) -> float32 logits (b, T, V): the generic
        ``/predict`` surface."""
        return self.logits(np.asarray(x).astype(np.int32))

    def prefill_buckets(self, max_length: Optional[int] = None):
        return prefill_bucket_lengths(max_length or self.cfg.max_length,
                                      self.serving_seq_buckets)

    def generate_cached(self, prompt_ids, max_new: int = 20,
                        temperature: float = 0.0, rng=None, top_k: int = 0,
                        top_p: float = 0.0, return_logits: bool = False):
        """One prompt through bucketed prefill and then the cache, a
        token a step, on a one-slot cache of ``max_length``: what a slot
        of the engine computes, alone. ``return_logits`` also returns the
        (max_new, V) logits each token was sampled from."""
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        cfg = self.cfg
        if ids.size + max_new > cfg.max_length:
            raise ContextWindowExceeded(ids.size, max_new, cfg.max_length)
        _validate_sampling(temperature, top_k, top_p)
        if cfg.exit_threshold < 1.0:
            raise ValueError(
                f"exit_threshold={cfg.exit_threshold}: the cached programs "
                "run every pass for every token (the rule at threshold 1)")
        if "prefill" not in self._jit_cache:
            self._jit_cache["prefill"] = jax.jit(
                lambda p, c, i, n: prefill_slot(cfg, p, c, i, n,
                                                jnp.zeros((), jnp.int32)),
                donate_argnums=(1,))
            self._jit_cache["decode"] = jax.jit(
                lambda p, c, tok, pos: decode_step(cfg, p, c, tok, pos)[:2],
                donate_argnums=(1,))
            self._jit_cache["sample"] = jax.jit(sample_next_device)
        tb = next(t for t in self.prefill_buckets() if t >= ids.size)
        padded = np.zeros((1, tb), np.int32)
        padded[0, :ids.size] = ids
        pol = (jnp.asarray(float(temperature), jnp.float32),
               jnp.asarray(int(top_k), jnp.int32),
               jnp.asarray(float(top_p), jnp.float32))
        key = rng if rng is not None else jax.random.PRNGKey(0)
        logits, cache = self._jit_cache["prefill"](
            self.params_, init_cache(cfg, 1, cfg.max_length),
            jnp.asarray(padded), jnp.asarray(ids.size, jnp.int32))
        toks, all_logits = [], []
        for step in range(max_new):
            tok, key = self._jit_cache["sample"](logits, *pol, key)
            toks.append(int(tok[0]))
            all_logits.append(np.asarray(logits[0]))
            if step + 1 < max_new:
                logits, cache = self._jit_cache["decode"](
                    self.params_, cache, tok,
                    jnp.asarray([ids.size + step], jnp.int32))
        out = np.concatenate([ids, np.asarray(toks, np.int32)])
        return (out, np.stack(all_logits)) if return_logits else out

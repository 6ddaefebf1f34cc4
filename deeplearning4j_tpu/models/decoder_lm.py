"""DecoderLM: a causal decoder whose stack is DATA, served through
``GenerationEngine``.

``TransformerLM`` is one GPT-2 block scanned L times. Today's open
decoders are not that: RMSNorm, no biases, rotary positions on part of a
head (plain or with scaled frequencies), fewer key/value heads than query
heads, query/key heads wider than value heads, window layers (with a
learned softmax sink) among full layers, latent attention (low-rank
queries and one compressed key/value entry a position shared by all
heads), a leading dense layer and then expert layers of which a chip
holds its share, routed by sigmoid or by group-limited softmax scores,
with or without a shared expert. Here all of that is configuration:

- ``attn_kinds`` names the kinds of MIXER, a layer's first half: the
  kinds of attention layer (key/value heads, rotary base and scaling,
  window, sink, or the ranks of a latent kind) and, since a layer need
  not attend at all, the state-space kind (``"ssm"``: a Mamba-2 mixer's
  heads, head size, state size, groups, convolution width, expansion and
  chunk); ``layers`` gives each layer's mixer kind and FFN kind
  (``"dense"`` / ``"experts"``), in order;
- consecutive layers of one (mixer, FFN) pair form a SEGMENT whose
  parameters are stacked and scanned; the stack is the list of segments;
- ONE block function (:func:`block`) serves the full forward, prefill
  and decode. It attends over the step's own keys and, when given one,
  over a cache described by the absolute position each of its columns
  holds, so a full layer's slab and a window layer's ring are the same
  code with different position maps;
- the cache is sized by layer kind (:meth:`DecoderConfig.cache_plan`):
  the slot's length for a full layer, a ring of ``window`` columns for a
  window layer, K and V by head; ONE slab of ``kv_rank + rotary_dim``
  values a position for a latent layer; T-minor and written in place
  after the layer loop, as ``TransformerLM``'s slab is
  (``_put_columns``);
- a latent layer decodes ABSORBED (the queries are taken into the latent
  space, so a step reads each cached position once for all heads and
  never expands K or V) and prefills EXPANDED, by blocks of queries and
  keys under one running softmax (``_causal_blocked``): no program plans
  a score tensor of a whole bucket;
- a latent kind with an INDEXER attends to a selection: a layer that
  owns one scores every position behind a query with a few small heads
  against ONE cached key a position (its second slab), keeps the
  ``topk`` largest scores exactly (:func:`_select_mask`) and attends,
  under the same softmax, to those positions alone; a layer that shares has no indexer and no key slab and
  attends to the selection of the nearest owner before it, which
  ``_run_stack`` carries from a layer to the next and from a segment to
  the next. Decode gathers the chosen entries from a position-major slab
  (a row an entry); prefill and the forward put the selection as a mask
  on the blocked attention's scores;
- expert layers route over every expert of the layer (the rule is the
  configuration's ``routing``) and compute the part of the result their
  held experts give, plus the shared expert where there is one
  (``nn/conf/layers/moe.moe_dropless_ffn``); the vocabulary may be the
  chip's slice of the published one;
- a state-space layer keeps NO columns: its cache is a recurrent state
  (layers, slots, state size, heads x head size) in float32 (the state
  size major, so that the decode kernel's per-channel scalars are lane
  vectors) and the last
  ``d_conv - 1`` inputs of its convolution, whatever the slot's length.
  A decode step reads and writes a layer's state where it lies, so the
  state goes through the layer loop as a CARRY updated in place on the
  donated buffer (a scan's stacked output would be a second copy of it):
  by a kernel that visits the live slots alone, each block once
  (``nn/ops/ssm_decode.py``), where the kernel registry admits the
  shapes, by :func:`_ssm_step` over all slots elsewhere;
  prefill is the chunked dual form (:func:`_ssm_chunked`), whose padding
  leaves the state alone;
- four scalars of the configuration scale the embedding, every residual
  branch, the attention scores and the logits (each 1, or
  ``1/sqrt(head)``, by default), ``rotary_dim`` 0 means no positions at
  all, and the head may be the embedding read transposed (``tied_head``);
- the stack may run SEVERAL TIMES a token over one set of weights
  (``passes``; a looped model, which buys depth with passes instead of
  parameters): the final norm closes every pass and the next starts from
  the normed stream; each (pass, layer) attends to keys and values of its
  own, so every slab, ring and state of the cache plan leads with passes x
  layers, pass-major, and ``_run_stack`` runs the passes as one scan that
  carries the slabs whole and hands pass r the entries ``r x layers + i``;
  a layer's two outputs may be normed again before they join the residual
  (``sandwich_norm``: four norms a layer); and a gate of one output may
  read each pass's closed stream for the probability of leaving there
  (``exit_gate``), which :func:`forward` holds against ``exit_threshold``
  a token at a time. The cached programs run every pass for every token,
  the rule at threshold 1, and the serving backend refuses a lower one:
  rows of one batched step that leave after different passes, and what a
  row that left owes the later passes' cache entries, are a scheduler's
  question (ROADMAP, Queue R).

Serving only: there is no training step for this block yet (ROADMAP M1).
"""

from __future__ import annotations

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
from deeplearning4j_tpu.nn.ops.ssm_decode import live_table, ssm_decode_impl

Array = jax.Array

#: device-time scopes of this model, beside ``transformer_lm.SCOPES``
#: (``embed``, ``kv_write``, ``head``, ``sample`` are shared): attention
#: by layer kind (a latent layer's in two: ``attn_latent_proj``, the norms,
#: projections, rotation, absorption and output projection, bound by
#: weights, around ``attn_latent_core``, the scores over the latent cache,
#: the softmax and the weighted sum of latents; in prefill the blocked
#: attention), the dense FFN, the two halves of an expert layer and its
#: shared expert; a state-space mixer's in three (``ssm_proj``: the norm,
#: the two projections and the gated norm, bound by weights; ``ssm_conv``:
#: the causal convolution and its tail; ``ssm_scan``: the recurrence, one
#: step over the cached state in decode, the chunked form in prefill) and
#: ``state_write``, a prefill's write of its slot's state and tail; a
#: latent layer with an indexer has, inside ``attn_latent_proj``,
#: ``attn_index_proj`` (the indexer's three projections, the key's norm,
#: the rotations), ``attn_index_score`` (its heads' scores over the key
#: cache, ReLU, the weighted sum over heads), ``attn_index_select`` (the
#: exact top-k) and, owner or sharer, ``attn_sparse_core`` (the gather of
#: the selected entries, the scores, softmax and weighted sum over them;
#: in prefill the blocked attention under the selection's mask); where the
#: stack runs more than once, ``pass_close``: the final norm that closes
#: each pass, and the exit gate's reading of the closed stream
SCOPES = ("attn_full", "attn_window", "attn_latent_proj", "attn_latent_core",
          "mlp", "moe_route", "moe_experts", "moe_shared",
          "ssm_proj", "ssm_conv", "ssm_scan", "state_write",
          "attn_index_proj", "attn_index_score", "attn_index_select",
          "attn_sparse_core", "pass_close")
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


class DecoderConfig:
    """The decoder as data. ``attn_kinds``: name -> a kind of MIXER (the
    name stays from when every mixer attended). An attention kind is
    {"n_kv_heads", "rope_theta", "window" (None = full), "sink" (bool)} and, optional,
    "rope_scaling" (YaRN: ``factor``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``, ``original_max_position_embeddings``)
    and "latent" = {"q_rank", "kv_rank"}: a latent kind,
    whose heads are ``head_dim`` = (``head_dim - rotary_dim`` without
    position | ``rotary_dim`` rotated) wide and share ONE rotary key, and
    whose cache entry is ``kv_rank + rotary_dim`` values a position. A
    latent kind may state "index" = {"heads", "head_dim", "topk", "own"}:
    its attention reads only the ``topk`` positions an indexer of
    ``heads`` heads of ``head_dim`` picks for the query (the first
    ``rotary_dim`` of an indexer head are rotated). ``own`` true: the
    layer has the indexer's weights, caches ONE indexer key of
    ``head_dim`` a position in a second slab beside the latent one, and
    makes the selection; ``own`` false: it has neither and attends to the
    selection of the nearest owning layer before it (there must be one).
    Both keep their slabs position-major (:meth:`cache_plan`). A
    state-space kind is {"ssm": {"n_heads", "head_dim", "d_state",
    "n_groups", "d_conv", "expand", "chunk"}} (Mamba-2: ``expand x
    d_model`` = ``n_heads x head_dim`` inner channels, a state of
    ``head_dim x d_state`` a head, B and C shared by the heads of a group,
    a causal depthwise convolution ``d_conv`` wide, prefill by chunks of
    ``chunk``); it keeps no columns and takes none of the attention keys.
    ``layers``: one (mixer kind, "dense" | "experts") pair a layer.
    ``experts_held`` = (offset, count): which of the ``n_experts`` the
    router scores have their weights here. ``routing``:
    {"scoring": "sigmoid", "scale"} (sigmoid scores, a correction bias in
    the choice, weights renormalised, then times ``scale``; None reads as
    this rule with ``scale`` 1) or {"n_group", "topk_group", "renormalise", "scale"}
    (softmax scores, group-limited: no bias). ``shared_width``: the shared
    expert's width (0: none). ``vocab_size`` is what is held here (the chip's slice,
    where the vocabulary is sliced). ``rotary_dim`` 0: no positions.
    ``embedding_multiplier`` scales the embedded tokens,
    ``residual_multiplier`` every residual branch (mixer and FFN),
    ``attention_multiplier`` the attention scores (None: ``1/sqrt(head)``)
    and ``logits_scaling`` divides the logits; ``tied_head``: the head is
    the embedding, one leaf, read transposed. ``passes``: how many times
    the whole stack is applied to every token, with ONE set of weights:
    each pass closes with the final norm, the next starts from the normed
    stream, and every (pass, layer) keeps a cache entry of its own
    (:meth:`cache_plan`). ``sandwich_norm``: a layer has four norms, the
    two outputs (mixer and FFN) being normed again (``norm1b``,
    ``norm2b``) before they join the residual. ``exit_gate``: a gate of
    one output (``gate_w`` (d,), ``gate_b``) reads each pass's normed
    stream and gives the probability of leaving there;
    ``exit_threshold`` q: a token's logits come from the first pass at
    which the cumulative exit probability reaches q (:func:`forward`;
    1.0: from the last pass, whatever the gate says)."""

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
                 exit_gate: bool = False, exit_threshold: float = 1.0):
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
                   "ssm": ({f: int(k["ssm"][f]) for f in _SSM_FIELDS}
                           if k.get("ssm") else None)}
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
                        or m["n_heads"] * m["head_dim"]
                        != m["expand"] * self.d_model
                        or m["n_heads"] % m["n_groups"]):
                    raise ValueError(
                        f"ssm kind {name!r}: n_heads x head_dim = expand x "
                        "d_model, n_groups dividing n_heads, and none of "
                        "the attention keys")
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
        self.passes = int(passes)
        self.sandwich_norm = bool(sandwich_norm)
        self.exit_gate = bool(exit_gate)
        self.exit_threshold = float(exit_threshold)
        if self.passes < 1 or not 0.0 <= self.exit_threshold <= 1.0:
            raise ValueError("passes >= 1 and 0 <= exit_threshold <= 1")
        if self.exit_threshold < 1.0 and not self.exit_gate:
            raise ValueError("an exit_threshold under 1 needs the exit_gate "
                             "whose probabilities it is held against")

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

    def cache_columns(self, kind: str, max_length: int) -> int:
        """Columns a layer of ``kind`` keeps a slot: a ring of ``window``
        for a window layer, the slot's length for a full one."""
        window = self.attn_kinds[kind]["window"]
        return int(max_length) if window is None else min(window,
                                                          int(max_length))

    def ssm_dims(self, kind: str) -> Tuple[int, int, int, int, int]:
        """(heads H, head size P, state size N, inner channels H x P,
        convolved channels H x P + 2 x groups x N) of a state-space kind."""
        m = self.attn_kinds[kind]["ssm"]
        inner = m["n_heads"] * m["head_dim"]
        return (m["n_heads"], m["head_dim"], m["d_state"], inner,
                inner + 2 * m["n_groups"] * m["d_state"])

    def latent_width(self, kind: str) -> int:
        """Values a latent kind caches a position and layer: the
        compressed key/value entry and the one rotated key."""
        return self.attn_kinds[kind]["latent"]["kv_rank"] + self.rotary_dim

    def latent_row(self, kind: str) -> int:
        """Values a ROW of a position-major latent slab holds (a latent
        kind with an indexer): ``latent_width`` rounded up to whole tiles
        of 128 lanes, the tail zero. At 576 values a row the TPU compiler
        copies the whole slab, padded, before every gather from it (528 MB
        a layer and step at 32 slots x 14,336, by compile for a described
        v5e, PR 40); at 640 the gather reads the rows where they lie."""
        return -(-self.latent_width(kind) // 128) * 128

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
        """What the engine allocates, a segment at a time: ``slabs``, the
        shapes (layers, slots, kv heads, head size, columns) of K and V
        or, for a latent segment, ONE slab (layers, slots, kv_rank +
        rotary_dim, columns); ``values``: what a position and layer
        keeps. A latent segment with an indexer keeps its slabs
        POSITION-MAJOR, (layers, slots, columns, row): its decode
        gathers ``topk`` chosen positions a slot, and a chosen position
        is then one row whose values lie together, where the T-minor
        slab would put the gather on the minor axis, a value a lane
        apart (PERF.md, PR 40: what the gather read on the chip). ``row``
        is the kv_rank + rotary_dim values of the entry in whole tiles of
        128 lanes (:meth:`latent_row`: 576 in 640, the tail zero). A
        segment whose layers OWN the indexer has TWO slabs of different
        widths, the latent one and the indexer's keys (rows of the
        indexer's head size; ``index``: that width), and ``values``, what
        the mathematics keeps a position and layer, is kv_rank +
        rotary_dim + that; a segment whose layers share a selection has
        the latent slab alone. ``bytes`` counts the rows as stored. A
        state-space segment keeps no columns: ``state`` (layers,
        slots, state size, heads x head size) in float32 (a bfloat16
        state would round at every step of a recurrence thousands long)
        and ``conv`` (layers, slots, convolved channels, d_conv - 1), the
        convolution's last inputs, in the parameter dtype: the same bytes
        whatever ``max_length``. ``dtypes`` goes with ``slabs``. A stack
        that runs ``passes`` times keeps all of that a PASS: every shape
        leads with passes x layers, pass-major (pass r's layer i is entry
        r x layers + i), and ``bytes`` counts them; ``layers`` stays the
        segment's and ``passes`` stands beside it."""
        item = jnp.dtype(self.dtype).itemsize
        plan = []
        for kind, _ffn, layers in self.segments():
            n = self.passes * layers
            if self.attn_kinds[kind]["ssm"]:
                h, p, ns, _inner, conv = self.ssm_dims(kind)
                tail = self.attn_kinds[kind]["ssm"]["d_conv"] - 1
                state = (n, int(n_slots), ns, h * p)
                taps = (n, int(n_slots), conv, tail)
                plan.append({
                    "kind": kind, "layers": layers, "passes": self.passes,
                    "columns": 0, "state": state, "conv": taps,
                    "slabs": [state, taps],
                    "dtypes": [jnp.float32, self.dtype],
                    "bytes": int(np.prod(state)) * 4
                    + int(np.prod(taps)) * item})
                continue
            cols = self.cache_columns(kind, max_length)
            entry = {"kind": kind, "layers": layers, "passes": self.passes,
                     "columns": cols,
                     "ring": self.attn_kinds[kind]["window"] is not None}
            index = self.attn_kinds[kind]["index"]
            if index:
                width = self.latent_width(kind)
                entry["row"] = self.latent_row(kind)
                slabs = [(n, int(n_slots), cols, entry["row"])]
                if index["own"]:
                    entry["index"] = index["head_dim"]
                    slabs.append((n, int(n_slots), cols, index["head_dim"]))
                    width += index["head_dim"]
            elif self.attn_kinds[kind]["latent"]:
                width = self.latent_width(kind)
                slabs = [(n, int(n_slots), width, cols)]
            else:
                hkv = self.attn_kinds[kind]["n_kv_heads"]
                slabs = [(n, int(n_slots), hkv, self.head_dim, cols),
                         (n, int(n_slots), hkv, self.v_head_dim, cols)]
                entry["k"], entry["v"] = slabs
                width = hkv * (self.head_dim + self.v_head_dim)
            entry.update(slabs=slabs, dtypes=[self.dtype] * len(slabs),
                         values=width, bytes=sum(
                int(np.prod(shape)) for shape in slabs) * item)
            plan.append(entry)
        return plan


# -- parameters ---------------------------------------------------------------
def segment_shapes(cfg: DecoderConfig, kind: str, ffn: str) -> Dict[str, tuple]:
    """Leaf name -> (shape of ONE layer, dtype). Norm gains, sinks and
    the router stay float32 whatever the parameter dtype. ``Wq`` is
    stored by head, (d, heads, head size): flat, the TPU compiler
    re-laid its 100 MB out in every layer of a decode step to split a
    product 12,288 wide into heads of 192 (by compile, PR 27). A latent
    kind's up-projections are by head for the same reason: ``Wqb``
    (q_rank, heads, head size), and the key/value one in its two halves,
    ``Wuk`` (kv_rank, heads, head size - rotary_dim) and ``Wuv`` (kv_rank,
    heads, value size), which the absorbed decode contracts on opposite
    sides and never together. A latent kind that OWNS an indexer adds its
    four matrices: ``Iq`` (q_rank, indexer heads, indexer head size), the
    indexer's queries from the query latent, by head; ``Ik`` (d, indexer
    head size), its one key a position, with the key's LayerNorm
    (``norm_ik`` gain and ``bias_ik``, float32); ``Iw`` (d, indexer
    heads), the heads' weights. A kind that shares a selection has none
    of them. A state-space kind's input projection is
    ONE leaf, ``Win`` (d, inner + convolved + heads) with its columns in
    the published order [z | xBC | dt]: one product reads the weights
    once, and its three parts are cut from the RESULT at offsets that are
    multiples of 128 lanes at the published widths (8,192 and 16,640), so
    no part of the weight is ever sliced or re-laid (by compile:
    ``tests/test_tpu_compile.py``). ``conv_w`` (channels, d_conv) and
    ``conv_b`` are the depthwise convolution, ``dt_bias``, ``A_log`` and
    ``D`` (heads,) the recurrence's per-head scalars and ``norm_g`` the
    gated norm's gain, float32; ``Wo`` (inner, d) brings the result
    out. With ``sandwich_norm`` every kind of layer has ``norm1b`` and
    ``norm2b``, the gains of the norms its two outputs go through."""
    d, hq = cfg.d_model, cfg.n_heads
    ak = cfg.attn_kinds[kind]
    pd, f32 = cfg.dtype, jnp.float32
    out = {"norm1": ((d,), f32), "norm2": ((d,), f32)}
    if cfg.sandwich_norm:
        out.update({"norm1b": ((d,), f32), "norm2b": ((d,), f32)})
    if ak["ssm"]:
        h, _p, _n, inner, conv = cfg.ssm_dims(kind)
        out.update({"Win": ((d, inner + conv + h), pd),
                    "conv_w": ((conv, ak["ssm"]["d_conv"]), pd),
                    "conv_b": ((conv,), pd), "dt_bias": ((h,), f32),
                    "A_log": ((h,), f32), "D": ((h,), f32),
                    "norm_g": ((inner,), f32), "Wo": ((inner, d), pd)})
    elif ak["latent"]:
        qr, kr = ak["latent"]["q_rank"], ak["latent"]["kv_rank"]
        out.update({"Wqa": ((d, qr), pd), "norm_q": ((qr,), f32),
                    "Wqb": ((qr, hq, cfg.head_dim), pd),
                    "Wkva": ((d, kr + cfg.rotary_dim), pd),
                    "norm_kv": ((kr,), f32),
                    "Wuk": ((kr, hq, cfg.head_dim - cfg.rotary_dim), pd),
                    "Wuv": ((kr, hq, cfg.v_head_dim), pd)})
        if ak["index"] and ak["index"]["own"]:
            ih, idim = ak["index"]["heads"], ak["index"]["head_dim"]
            out.update({"Iq": ((qr, ih, idim), pd), "Ik": ((d, idim), pd),
                        "norm_ik": ((idim,), f32), "bias_ik": ((idim,), f32),
                        "Iw": ((d, ih), pd)})
    else:
        hkv = ak["n_kv_heads"]
        out.update({"Wq": ((d, hq, cfg.head_dim), pd),
                    "Wk": ((d, hkv * cfg.head_dim), pd),
                    "Wv": ((d, hkv * cfg.v_head_dim), pd)})
    out.setdefault("Wo", ((hq * cfg.v_head_dim, d), pd))
    if ak["sink"]:
        out["sink"] = ((hq,), f32)
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
    ak = cfg.attn_kinds[kind]
    rot = cfg.rotary_dim
    nope, kr = cfg.head_dim - rot, ak["latent"]["kv_rank"]
    theta, scaling = ak["rope_theta"], ak["rope_scaling"]
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
    goes by blocks (``_causal_blocked``). With ``cache`` = (slab
    (b, kv_rank + rotary_dim, Tc), c_pos (b, Tc)) the ABSORBED form: with
    the up-projection split by head into ``Wuk`` and ``Wuv``, a head's
    query is taken into the latent space (``q_nope Wuk^T``), scored
    against the cached latents and the rotary key as they lie, the
    softmax weights sum the LATENTS, and ``Wuv`` then ``Wo`` bring that
    sum out: each cached position is read once for all heads and no key
    or value of a head is ever made over the cache. Both einsums take the
    slab whole (the weighted sum over all its rows, the rotary key's
    dropped after): a slice of it would be copied. They also take every
    column of every row, whatever it holds. With ``cache`` = (the
    segment's slabs (layers, b, kv_rank + rotary_dim, Tc), layer,
    lengths (b,)) and Tq = 1 the same sums come from the kernel of
    ``nn/ops/latent_decode.py``, which reads row s of layer ``layer`` in
    its first ``lengths[s]`` columns, once (``_run_stack`` hands the cache
    over in this form where the kernel registry admits the shapes)."""
    ak = cfg.attn_kinds[kind]
    b, tq, _d = x.shape
    hq, rot, vd = cfg.n_heads, cfg.rotary_dim, cfg.v_head_dim
    kr = ak["latent"]["kv_rank"]
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
            if len(cache) == 3:
                slabs, layer, lengths = cache
                core = latent_decode_impl(hq, kr + rot, slabs.shape[-1],
                                          slabs.dtype, kr)
                with _scope("attn_latent_core"):
                    lat = core(q_lat[:, 0], new[:, 0], slabs, layer, lengths,
                               scale=scale)[:, None]
            else:
                slab, c_pos = cache
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


def _select_indices(scores, own, lengths, k: int):
    """A decode step's selection: scores (b, Tc) float32 of the cached
    positions (-inf from a row's ``lengths`` on), ``own`` (b,) the score of
    the step's own position, which lies outside the slab -> (the columns
    of the ``k`` best cached positions (b, k), best first; how many of
    them are in the selection (b,); whether the own position is (b,)).
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
    n_sel = jnp.where(own_in, jnp.minimum(lengths, k - 1), k)
    return idx.astype(jnp.int32), n_sel.astype(jnp.int32), own_in


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


def _no_selection(cfg: DecoderConfig, kind: str, b: int, tq: int, columns):
    """A selection's shapes with nothing in them: what a segment of layers
    that own an indexer starts its scan's carry from (each layer puts its
    own in its place). ``columns``: the slab's, in decode; None without a
    cache, where there is a selection only past ``topk`` positions."""
    topk = cfg.attn_kinds[kind]["index"]["topk"]
    if columns is None:
        return jnp.zeros((b, tq, tq), bool) if tq > topk else None
    return (jnp.zeros((b, min(topk, columns)), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool))


def _sparse_latent_attention(cfg: DecoderConfig, kind: str,
                             bp: Dict[str, Array], x: Array, q_pos: Array,
                             cache=None, sel=None, n_real=None):
    """A latent layer's attention over a SELECTION of positions, x
    (b, Tq, d): returns (x + its output, what the layer caches of the
    step's positions, the selection it attended by).

    The block's attention is ``_latent_attention``'s, expanded without a
    cache and absorbed over one, with the softmax over the selected
    positions only. A layer that OWNS the indexer makes the selection: the
    indexer heads' queries from the query latent (``Iq``, rotated on their
    first ``rotary_dim``), ONE key a position (``Ik``, LayerNorm, rotated
    alike; cached), the heads' weights (``Iw``, times heads^-1/2 x
    head_dim^-1/2), the score ``sum_h w_h ReLU(q_h . k)`` in float32 of
    every position not after the query, and the ``topk`` largest, exactly.
    It returns (latent entries (b, Tq, row) (``latent_row``: the kv_rank +
    rotary_dim values and a zero tail), key entries (b, Tq, indexer head
    size)). A layer that SHARES has none of that: it
    attends by ``sel``, the selection the nearest owner before it made for
    the same tokens, and returns the latent entries alone.

    Without a cache (forward, prefill) a selection is a mask (b, Tq, Tq)
    on the blocked attention's scores (``_index_mask``,
    ``_causal_blocked``), or None where Tq <= topk: every query then
    attends to all before it, the dense latent layer. With ``cache`` =
    (the segment's slabs, position-major: (latents (layers, b, Tc, row),
    and an owner's keys (layers, b, Tc, indexer head
    size)), layer, lengths (b,)) and Tq = 1 it is (columns (b, K), how
    many of them count (b,), whether the step's own position is in (b,))
    (``_select_indices``; K = min(topk, Tc)): the own entry lies outside
    the slab, so it is scored beside the cached ones and a sharer is told
    whether it was chosen. The chosen rows are gathered from the slab as
    it lies, K rows a slot, and the absorbed scores, the softmax and the
    weighted sum of latents run over them and the own entry."""
    ak = cfg.attn_kinds[kind]
    index = ak["index"]
    b, tq, _d = x.shape
    hq, rot, vd = cfg.n_heads, cfg.rotary_dim, cfg.v_head_dim
    kr = ak["latent"]["kv_rank"]
    theta, scaling = ak["rope_theta"], ak["rope_scaling"]
    scale = softmax_scale(cfg, kind)
    f32, dt = jnp.float32, x.dtype
    with _scope("attn_latent_proj"):
        a_in, c_q, q_nope, q_pe, c, k_pe, new = _latent_project(
            cfg, kind, bp, x, q_pos)
        # the entry as a row of the slab: whole tiles, the tail zero
        tail = cfg.latent_row(kind) - new.shape[-1]
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
            slabs, layer, lengths = cache
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
                                          min(index["topk"], columns))
            idx, n_sel, own_in = sel
            with _scope("attn_sparse_core"):
                rows = slabs[0].at[
                    layer, jnp.arange(b)[:, None], idx].get(
                        mode="promise_in_bounds")             # (b, K, width)
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


def _ssm_mixer(cfg: DecoderConfig, kind: str, bp: Dict[str, Array], x: Array,
               cache=None, token_mask=None):
    """A state-space layer's first half (Mamba-2) on x (b, Tq, d): returns
    (x + its output, what the layer keeps).

    ``[z | xBC | dt] = RMSNorm(x) Win``; a causal depthwise convolution
    ``d_conv`` wide over time on ``xBC``, plus bias, then SiLU; ``[x | B |
    C]`` cut from it; ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``
    a head; the recurrence (:func:`_ssm_chunked`, :func:`_ssm_step`) with
    the skip ``D x``; the gated norm ``RMSNorm(y * silu(z))`` over a
    group's channels; ``Wo``. Everything between the two projections is
    float32 but ``xBC`` itself, which is rounded to the parameter dtype
    before the convolution, as the cached tail is.

    Without a cache (forward, prefill) the whole sequence goes through the
    chunked form from a zero state, positions where ``token_mask`` is
    False (padding after the real tokens) get ``dt = 0``, and what is kept
    is (the state after the last real token (b, state size, heads x head
    size), the last ``d_conv - 1`` REAL inputs of the convolution
    (b, channels, d_conv - 1), zeros where the prompt is shorter). With
    ``cache`` = (the segment's states (layers, b, state size, heads x head
    size), its tails (layers, b, channels, d_conv - 1), layer) and Tq = 1
    one step of the recurrence: the layer's state and tail are read at
    ``layer`` and written back there, rows where ``token_mask`` is False
    bit for bit as they were, and what is kept is the two arrays whole
    (the layer loop's carry: ``_run_stack``). With a fourth entry, the
    live slots' table (``nn/ops/ssm_decode.live_table``), the state goes
    through the kernel that visits those slots alone, each block once, in
    ``_ssm_step``'s stead."""
    m = cfg.attn_kinds[kind]["ssm"]
    heads, p, n, inner, conv = cfg.ssm_dims(kind)
    g, k = m["n_groups"], m["d_conv"]
    r = heads // g
    b, tq, _d = x.shape
    f32, dt_ = jnp.float32, x.dtype
    with _scope("ssm_proj"):
        a_in = _rms_norm(x, bp["norm1"], cfg.norm_eps).astype(dt_)
        proj = jnp.matmul(a_in, bp["Win"], preferred_element_type=f32)
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
            states, tails, layer, *table = cache
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
            y, h = _ssm_chunked(xs, dt, a, bm, cm, m["chunk"],
                                None if token_mask is None else jnp.max(lengths))
            made = (h.reshape(b, inner, n).transpose(0, 2, 1), tail)
        elif table:
            step = ssm_decode_impl(heads, p, n, g, b, states.dtype)
            x1, d1, b1, c1 = xs[:, 0], dt[:, 0], bm[:, 0], cm[:, 0]
            decay = jnp.exp(d1 * a)
            hc, states = step(
                states, layer, table[0], (d1[..., None] * x1).reshape(b, inner),
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
        x = _residual(cfg, x, _branch(cfg, bp, "norm1b", gated @ bp["Wo"]))
    return x, made


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


def _latent_kernel_admits(cfg: DecoderConfig, kind: str, slab: Array) -> bool:
    """Whether a decode step over ``slab`` (layers, b, width, Tc) of
    attention kind ``kind`` goes through the length-aware kernel: a latent
    kind, and the kernel registry's verdict for these shapes (a TPU, the
    probe passed; elsewhere the einsums serve)."""
    latent = cfg.attn_kinds[kind]["latent"]
    return bool(latent) and latent_decode_impl(
        cfg.n_heads, slab.shape[2], slab.shape[3], slab.dtype,
        latent["kv_rank"]) is not None


def _attention_kernel(cfg: DecoderConfig, kind: str, k_slab: Array,
                      v_slab: Array):
    """The live-tile kernel for a decode step over ``k_slab`` (layers, b,
    hkv, hd, Tc) and ``v_slab`` of attention kind ``kind``, and its tile:
    a kind that keeps every position (no window) and has no sink, and the
    kernel registry's verdict for these shapes (a TPU, the probe passed,
    a layer's K + V worth a call); None where the einsums serve."""
    ak = cfg.attn_kinds[kind]
    if ak["latent"] or ak["ssm"] or ak["window"] is not None or ak["sink"]:
        return None
    _layers, b, hkv, hd, t = k_slab.shape
    return decode_attention_impl(b, hkv, cfg.n_heads // hkv, hd,
                                 v_slab.shape[3], t, k_slab.dtype)


def _ssm_kernel_admits(cfg: DecoderConfig, kind: str, states: Array) -> bool:
    """Whether a decode step over ``states`` (layers, b, state size, heads x
    head size) of a state-space kind goes through the live-slot kernel:
    the kernel registry's verdict for these shapes (a TPU, the probe
    passed; elsewhere ``_ssm_step`` serves)."""
    heads, p, n, _inner, _conv = cfg.ssm_dims(kind)
    return ssm_decode_impl(
        heads, p, n, cfg.attn_kinds[kind]["ssm"]["n_groups"],
        states.shape[1], states.dtype) is not None


def block(cfg: DecoderConfig, kind: str, ffn: str, bp: Dict[str, Array],
          x: Array, q_pos: Array, cache=None, token_mask=None, layer=None,
          sel=None):
    """One layer on x (b, Tq, d) at absolute positions q_pos (b, Tq);
    bp holds ONE layer's leaves. The queries attend, under one softmax,
    to the layer's own Tq keys and, if ``cache`` = (kc (b, hkv, hd, Tc),
    vc (b, hkv, vd, Tc), c_pos (b, Tc)) is given, to the cache columns,
    each of which holds absolute position ``c_pos`` (< 0: nothing); or, for
    the live-tile kernel of a decode step, ``cache`` = (the segment's K
    slabs, its V slabs, layer, (the kernel, its walk over the rows' live
    tiles)) (``nn/ops/decode_attention.py``). The
    cache is only READ: the layer's new (b, hkv, Tq, hd) keys and
    (b, hkv, Tq, vd) values are returned for the caller to drop (full
    forward), write whole (prefill) or append (decode). A latent kind's
    cache is (slab (b, kv_rank + rotary_dim, Tc), c_pos), or (the
    segment's slabs, layer, lengths) for the decode kernel, and what it
    returns in their place is ((b, Tq, kv_rank + rotary_dim) entries,)
    (:func:`_latent_attention`). A latent kind with an indexer takes
    ``cache`` = (the segment's position-major slabs, layer, lengths) and
    ``sel``, the selection of the nearest owning layer before it (an owner
    makes its own), and returns in their place ((latent entries, and an
    owner's indexer keys), the selection it attended by)
    (:func:`_sparse_latent_attention`). A state-space kind attends to nothing:
    its cache is (the segment's states, its tails, layer), or those and
    the live slots' table for the decode kernel, WRITTEN here at
    ``layer``, and it returns what it keeps in their place
    (:func:`_ssm_mixer`). With ``layer``
    the expert weights in ``bp`` are a segment's whole stacks and
    ``layer`` the one to use (``moe_dropless_ffn``). Returns
    (x, (k, v), (expert pairs computed here, held experts hit))."""
    ak = cfg.attn_kinds[kind]
    b, tq, d = x.shape
    if ak["ssm"]:
        x, made = _ssm_mixer(cfg, kind, bp, x, cache, token_mask)
        x, counts = _ffn(cfg, ffn, bp, x, token_mask, layer)
        return x, made, counts
    if ak["latent"]:
        n_real = (None if token_mask is None or cache is not None
                  else jnp.max(jnp.sum(token_mask, axis=-1)))
        if ak["index"]:
            x, entries, sel = _sparse_latent_attention(
                cfg, kind, bp, x, q_pos, cache, sel, n_real)
            x, counts = _ffn(cfg, ffn, bp, x, token_mask, layer)
            return x, (entries, sel), counts
        x, entries = _latent_attention(cfg, kind, bp, x, q_pos, cache, n_real)
        x, counts = _ffn(cfg, ffn, bp, x, token_mask, layer)
        return x, (entries,), counts
    hq, hkv, hd, vd = cfg.n_heads, ak["n_kv_heads"], cfg.head_dim, cfg.v_head_dim
    grp = hq // hkv
    window = ak["window"]
    with _scope("attn_window" if window is not None else "attn_full"):
        a_in = _rms_norm(x, bp["norm1"], cfg.norm_eps).astype(x.dtype)
        q = jnp.einsum("btd,dhk->bthk", a_in, bp["Wq"])
        k = (a_in @ bp["Wk"]).reshape(b, tq, hkv, hd)
        v = (a_in @ bp["Wv"]).reshape(b, tq, hkv, vd)
        q = _rotate(q, q_pos, cfg.rotary_dim, ak["rope_theta"])
        k = _rotate(k, q_pos, cfg.rotary_dim, ak["rope_theta"])
        if cache is not None and len(cache) == 4:
            k_slab, v_slab, at, (core, table) = cache
            kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            # query head i reads key/value head i // grp
            o = core(q.reshape(b, hkv, grp, hd), kh[:, :, 0], vh[:, :, 0],
                     k_slab, v_slab, at, table,
                     scale=softmax_scale(cfg, kind))
            if cfg.value_scale != 1.0:
                o = o * cfg.value_scale
            o = o.reshape(b, tq, hq * vd).astype(x.dtype)
        elif (cache is None and window is None and not ak["sink"]
                and hq * tq * tq * 4 > BLOCKED_SCORE_BYTES):
            kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            o = _causal_blocked(
                q, jnp.repeat(k, grp, axis=2), jnp.repeat(v, grp, axis=2),
                softmax_scale(cfg, kind), PREFILL_BLOCK,
                None if token_mask is None
                else jnp.max(jnp.sum(token_mask, axis=-1)))
            if cfg.value_scale != 1.0:
                o = o * cfg.value_scale
            o = o.reshape(b, tq, hq * vd)
        else:
            # query head i reads key/value head i // grp
            qg = q.reshape(b, tq, hkv, grp, hd).transpose(0, 2, 3, 1, 4)
            kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            scale = softmax_scale(cfg, kind)
            f32 = jnp.float32
            s_own = jnp.einsum("bkgqd,bktd->bkgqt", qg, kh,
                               preferred_element_type=f32) * scale
            s_own = jnp.where(_visible(q_pos, q_pos, window)[:, None, None],
                              s_own, _NEG)
            m = s_own.max(-1)
            if cache is not None:
                kc, vc, c_pos = cache
                s_c = jnp.einsum("bkgqd,bkdt->bkgqt", qg, kc,
                                 preferred_element_type=f32) * scale
                s_c = jnp.where(_visible(q_pos, c_pos, window)[:, None, None],
                                s_c, _NEG)
                m = jnp.maximum(m, s_c.max(-1))
            if ak["sink"]:
                sink = bp["sink"].astype(f32).reshape(1, hkv, grp, 1)
                m = jnp.maximum(m, sink)
            e_own = jnp.exp(s_own - m[..., None])
            z = e_own.sum(-1)
            o = jnp.einsum("bkgqt,bktd->bkgqd", e_own.astype(x.dtype), vh,
                           preferred_element_type=f32)
            if cache is not None:
                e_c = jnp.exp(s_c - m[..., None])
                z = z + e_c.sum(-1)
                o = o + jnp.einsum("bkgqt,bkdt->bkgqd", e_c.astype(x.dtype),
                                   vc, preferred_element_type=f32)
            if ak["sink"]:
                z = z + jnp.exp(sink - m)  # the sink takes weight, adds no value
            o = o * (cfg.value_scale / z[..., None])
            o = o.transpose(0, 3, 1, 2, 4).reshape(b, tq, hq * vd).astype(x.dtype)
        x = _residual(cfg, x, _branch(cfg, bp, "norm1b", o @ bp["Wo"]))
    x, counts = _ffn(cfg, ffn, bp, x, token_mask, layer)
    return x, (kh, vh), counts


def _ffn(cfg: DecoderConfig, ffn: str, bp: Dict[str, Array], x: Array,
         token_mask, layer):
    """The second half of a layer: x + the dense MLP or the expert layer
    of the normed x -> (x, (expert pairs computed here, held experts
    hit))."""
    b, tq, d = x.shape
    if ffn == "dense":
        with _scope("mlp"):
            m_in = _rms_norm(x, bp["norm2"], cfg.norm_eps).astype(x.dtype)
            h = jax.nn.silu(m_in @ bp["Wg"]) * (m_in @ bp["Wu"])
            x = _residual(cfg, x, _branch(cfg, bp, "norm2b", h @ bp["Wd"]))
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
    """Every segment in order ONCE, each one ``lax.scan`` of :func:`block`
    over its stacked layers. ``caches``: per segment the slabs to read,
    (K, V) (layers, b, hkv, hd, Tc) or a latent segment's one
    (layers, b, width, Tc), with ``c_pos`` the position map of each
    attention kind (a latent segment's decode step where the kernel
    registry admits it: the slab whole, the layer's index and the rows'
    lengths instead; a full-attention segment's likewise: K and V whole,
    the layer's index and the walk over the rows' live tiles). A
    state-space segment's cache is (states, tails),
    WRITTEN in the loop: the two arrays go through the scan as its carry,
    each layer reading and writing its own index in place, and come back
    whole in the cache's stead (stacked as a scan's output they would be a
    second copy of the state); a decode step where the kernel registry
    admits it also hands each layer the table of the rows that are
    active. A segment of latent layers with an indexer reads its
    position-major slabs whole, by the layer's index (the decode gathers
    rows of them), and the SELECTION goes through its scan as a carry and
    on to the next segment: a layer that owns the indexer puts its own in
    the carry's place, a layer that shares reads what the last owner left,
    be it a layer or a segment back; it starts afresh with the pass.

    ``r`` (traced) is the pass, where the stack runs more than once: the
    caches then hold every pass's entries, passes x layers, and layer i
    reads (a state-space layer: writes) entry ``r x layers + i`` of its
    segment's. No pass's part is cut out of a slab on the way: a slice of
    it handed to a scan is a copy of it. Every slab goes through the
    segment's scan whole, as a carry that no attention layer changes, and
    a layer takes its own entry by index inside the loop, as a scan takes
    a layer's from what it scans over.

    Returns (x, per segment what the layers made to cache, (k, v) stacks
    (layers, b, hkv, Tq, hd) or (entries (layers, b, Tq, width),) (with an
    indexer: and an owner's keys) or, of a state-space segment without a
    cache, (states (layers, b, state size, heads x head size), tails
    (layers, b, channels, d_conv - 1)) and nothing over one; summed expert
    counters; per segment the slabs as the loops hand them on, a
    state-space segment's written, the after-loop write's to take)."""
    made, held_out = [], []
    pairs = hit = jnp.zeros((), jnp.int32)
    sel = None
    for i, (kind, ffn, n) in enumerate(cfg.segments()):
        seg = params["segments"][i]
        # the expert stacks are not sliced by the scan: the grouped
        # product takes them whole and the layer's index
        stacks = {k: seg[k] for k in EXPERT_STACKS if k in seg}
        scanned = {k: v for k, v in seg.items() if k not in stacks}
        kv = held = None if caches is None else caches[i]
        # a layer's entry in the segment's cache: this pass's run of them
        base = None if r is None or kv is None else r * n

        def entry(layer, base=base):
            return layer if base is None else base + layer

        index = cfg.attn_kinds[kind]["index"]
        if index:
            lengths = None if kv is None else q_pos[:, 0]
            if index["own"]:
                sel = _no_selection(cfg, kind, x.shape[0], x.shape[1],
                                    None if kv is None else kv[0].shape[2])

            # the slabs go through the scan as a carry that no layer
            # changes, and the after-loop write takes them from its end:
            # closed over, the loop's copy of them and the donated buffer
            # the write updates were two, a slab-sized copy a step (by
            # compile, PR 40)
            def chosen(carry, xs, kind=kind, ffn=ffn, stacks=stacks,
                       lengths=lengths, entry=entry):
                x, sel, kv = carry
                bp, layer = xs
                x, (knew, sel), counts = block(
                    cfg, kind, ffn, {**bp, **stacks}, x, q_pos,
                    None if kv is None else (kv, entry(layer), lengths),
                    token_mask, layer if stacks else None, sel)
                return (x, sel, kv), (knew, counts)

            (x, sel, kv), (knew, counts) = jax.lax.scan(
                chosen, (x, sel, kv),
                (scanned, jnp.arange(n, dtype=jnp.int32)))
            made.append(knew)
            held_out.append(kv)
            pairs, hit = pairs + counts[0].sum(), hit + counts[1].sum()
            continue
        if kv is not None and cfg.attn_kinds[kind]["ssm"]:
            table = ()
            if x.shape[1] == 1 and _ssm_kernel_admits(cfg, kind, kv[0]):
                table = (live_table(
                    jnp.ones(x.shape[:1], bool) if token_mask is None
                    else token_mask[:, 0]),)

            def step(carry, xs, kind=kind, ffn=ffn, stacks=stacks,
                     table=table, entry=entry):
                x, held = carry
                bp, layer = xs
                x, held, counts = block(cfg, kind, ffn, {**bp, **stacks}, x,
                                        q_pos, (*held, entry(layer), *table),
                                        token_mask, layer if stacks else None)
                return (x, held), counts

            (x, held), counts = jax.lax.scan(
                step, (x, tuple(kv)),
                (scanned, jnp.arange(n, dtype=jnp.int32)))
            made.append(())
            held_out.append(held)
            pairs, hit = pairs + counts[0].sum(), hit + counts[1].sum()
            continue
        # nor is a latent segment's slab where the decode kernel reads it
        # (a custom call's operand is made whole: the scan's slice of the
        # slab would be copied a layer), by the rows' lengths: a row that
        # is not active has none; nor are a full layer's K and V where the
        # live-tile kernel reads them, by a walk over the rows' live tiles
        # made here, once for the segment's layers
        whole = walk = every = None
        if kv is not None and x.shape[1] == 1:
            lengths = q_pos[:, 0] if token_mask is None else jnp.where(
                token_mask[:, 0], q_pos[:, 0], 0)
            if _latent_kernel_admits(cfg, kind, kv[0]):
                whole, kv, walk = tuple(kv), None, lengths
            elif len(kv) == 2:
                core = _attention_kernel(cfg, kind, *kv)
                if core is not None:
                    whole, kv = tuple(kv), None
                    walk = (core[0], live_tiles(lengths, whole[0].shape[-1],
                                                core[1]))
        if whole is None and kv is not None and r is not None:
            every, kv = tuple(kv), None  # all passes' entries: by index

        def body(carry, xs, kind=kind, ffn=ffn, stacks=stacks, whole=whole,
                 walk=walk, entry=entry):
            x, every = carry
            bp, kv, layer = xs
            if every is not None:
                kv = tuple(jax.lax.dynamic_index_in_dim(
                    c, entry(layer), 0, keepdims=False) for c in every)
            cache = None if kv is None else (*kv, c_pos[kind])
            if whole is not None:
                cache = (*whole, entry(layer), walk)
            x, knew, counts = block(cfg, kind, ffn, {**bp, **stacks}, x,
                                    q_pos, cache, token_mask,
                                    layer if stacks else None)
            return (x, every), (knew, counts)

        (x, every), (knew, counts) = jax.lax.scan(
            body, (x, every),
            (scanned, kv, jnp.arange(n, dtype=jnp.int32)))
        made.append(knew)
        # the loop's own hand-on where it carried the slabs, else as given
        held_out.append(held if every is None else every)
        pairs, hit = pairs + counts[0].sum(), hit + counts[1].sum()
    return x, made, (pairs, hit), None if caches is None else held_out


def _run_stack(cfg: DecoderConfig, params: Dict, x: Array, q_pos: Array,
               caches=None, c_pos=None, token_mask=None):
    """The stack, ``cfg.passes`` times over the SAME ``params``
    (:func:`_run_pass` is one time). With one pass that is all, and the
    stream comes back as the last layer left it (:func:`_head` norms it).
    With more, the passes are ONE ``lax.scan`` (its body, the segments'
    scans, is compiled once however many passes there are): the caches
    hold passes x layers entries a segment and go through it whole as its
    carry, pass ``r`` reading its own (``_run_pass``); every pass closes
    under ``pass_close`` with the final norm, the next starting from the
    NORMED stream, and with the exit gate's reading of it where there is
    one; what the layers made to cache comes back stacked passes x layers,
    pass-major, as the cache plan lays the slabs out, so that the
    after-loop writes are what they are for one pass on a longer leading
    axis. Returns (x, closed by the norm where passes > 1; per segment
    what was made to cache; summed expert counters; per segment the slabs
    for the after-loop write to take, None without a cache; (every pass's
    closed stream (passes, b, Tq, d), the gate's probabilities (passes, b,
    Tq) float32 or None), None where the stack runs once)."""
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
    """Zeroed slabs a segment, by the cache plan: (K, V), a latent
    segment's one (with an indexer: position-major, and the keys' beside
    it where the layers own it), or a state-space segment's (states in
    float32, tails)."""
    return [tuple(jnp.zeros(shape, dtype)
                  for shape, dtype in zip(p["slabs"], p["dtypes"]))
            for p in cfg.cache_plan(n_slots, max_length)]


def cache_positions(cfg: DecoderConfig, pos: Array, max_length: int):
    """Per attention kind, (b, Tc): the absolute position each cache
    column holds for a row that has ``pos`` positions behind it; -1
    where it holds none. A full layer keeps position p in column p; a
    ring keeps p in column p mod window, so column c holds the latest
    position below ``pos`` that is congruent to c. A state-space kind has
    no columns and no map."""
    out = {}
    for kind in cfg.attn_kinds:
        if cfg.attn_kinds[kind]["ssm"]:
            continue
        cols = cfg.cache_columns(kind, max_length)
        c = jnp.arange(cols, dtype=jnp.int32)[None, :]
        last = pos.astype(jnp.int32)[:, None] - 1
        if cfg.attn_kinds[kind]["window"] is None:
            out[kind] = jnp.where(c <= last, c, -1)
        else:
            out[kind] = last - jnp.mod(last - c, cols)  # < 0: not yet written
    return out


def _put_ring(ring, new, pos):
    """The after-loop write of a ring: new (L, b, hkv, 1, hd), row s's
    column -> ring[:, s, :, :, pos[s] mod window], as ONE select over the
    whole (donated) ring. A ring is ``window`` columns whatever the
    slot's length, so rewriting it costs a fixed pass over a few hundred
    megabytes, where a column update a slot (``_put_columns``, what a
    full layer's slab takes) costs an operation a slot and slab."""
    cols = ring.shape[4]
    at = jnp.mod(pos[:, 0], cols)[None, :, None, None, None]
    here = jnp.arange(cols, dtype=at.dtype)[None, None, None, None, :] == at
    return jnp.where(here, new.transpose(0, 1, 2, 4, 3), ring)


def _put_rows(slab, new, wp):
    """The after-loop write of a position-major slab: new (L, b, 1,
    width), row s's entry -> slab[:, s, wp[s, 0], :], each as ONE
    ``dynamic_update_slice`` on the (donated) slab, in place
    (``_put_columns``'s reasons)."""
    for s in range(new.shape[1]):
        slab = jax.lax.dynamic_update_slice(slab, new[:, s:s + 1],
                                            (0, s, wp[s, 0], 0))
    return slab


def decode_step(cfg: DecoderConfig, params: Dict, caches, ids_1: Array,
                pos: Array, active: Optional[Array] = None):
    """One token a row: ids_1 (b,) at per-row positions pos (b,) ->
    (logits (b, V), caches, (expert pairs, experts hit)). The caches are
    read inside the layer loop and written after it: a full layer's slab
    (a latent layer's too) by one in-place column a live row at ``pos``
    (``_put_columns``: one kernel call a slab where the registry admits
    it), a ring by one select at ``pos mod window``
    (``_put_ring``), a position-major slab of a latent layer with an
    indexer (its latents and, where it owns the indexer, its keys) by one
    in-place row a slot (``_put_rows``). A state-space segment's states
    and tails were
    written inside the loop, in place, and come back as they are.
    ``active`` (b,) bool keeps idle rows out of the expert layers (and of
    their counters) and leaves their state and tail bit for bit alone."""
    t_max = max([p[0].shape[-1] for (kind, _f, _n), p
                 in zip(cfg.segments(), caches)
                 if not cfg.attn_kinds[kind]["ssm"]
                 and not cfg.attn_kinds[kind]["index"]], default=0)
    q_pos = pos.astype(jnp.int32)[:, None]
    x, new_kv, counts, held, _passes = _run_stack(
        cfg, params, _embed(cfg, params, ids_1[:, None]), q_pos, caches,
        cache_positions(cfg, pos, t_max),
        None if active is None else active[:, None])
    out = []
    with _scope("kv_write"):
        # the slabs as the layer loop hands them on: with one pass, the
        # arguments themselves but where a loop carried them
        for (kind, _f, _n), slabs, new in zip(cfg.segments(), held, new_kv):
            if cfg.attn_kinds[kind]["ssm"]:
                out.append(tuple(slabs))
                continue
            if cfg.attn_kinds[kind]["index"]:
                wp = jnp.minimum(q_pos, slabs[0].shape[2] - 1)
                out.append(tuple(_put_rows(c, n, wp)
                                 for c, n in zip(slabs, new)))
                continue
            wp = jnp.minimum(q_pos, slabs[0].shape[-1] - 1)
            if cfg.attn_kinds[kind]["latent"]:
                # as a slab of one head whose "head size" is the entry
                out.append((_put_columns(slabs[0][:, :, None],
                                         new[0][:, :, None], wp,
                                         active)[:, :, 0],))
            elif cfg.attn_kinds[kind]["window"] is None:
                out.append(tuple(_put_columns(c, n, wp, active)
                                 for c, n in zip(slabs, new)))
            else:
                out.append(tuple(_put_ring(c, n, q_pos)
                                 for c, n in zip(slabs, new)))
    return _head(cfg, params, x[:, 0]), out, counts


def prefill_slot(cfg: DecoderConfig, params: Dict, caches, ids: Array,
                 length: Array, slot: Array):
    """One prompt, right-padded to a bucket: ids (1, Tb), ``length`` real
    tokens, into row ``slot`` of every slab, from ONE pass. A full layer
    (or a latent one, with its indexer's keys where it has them) gets the
    bucket's columns at 0..Tb-1; a ring gets, in column c, the
    latest real position congruent to c, i.e. the prompt's last
    ``window`` columns when it is longer than the window. Padding follows
    the real tokens, so causal attention keeps it from them, and the
    expert layers leave it out. A state-space segment gets the state
    after the ``length`` real tokens (padding has ``dt = 0``) and the last
    ``d_conv - 1`` real inputs of its convolution, under ``state_write``.
    Returns (logits (1, V) at length-1, caches)."""
    _b, tb = ids.shape
    q_pos = jnp.arange(tb, dtype=jnp.int32)[None]
    real = q_pos < length
    x, new_kv, _counts, _held, _passes = _run_stack(
        cfg, params, _embed(cfg, params, ids), q_pos, token_mask=real)
    out = []
    for (kind, _f, _n), slabs, new in zip(cfg.segments(), caches, new_kv):
        if cfg.attn_kinds[kind]["ssm"]:
            with _scope("state_write"):
                out.append(tuple(
                    jax.lax.dynamic_update_slice(
                        c, n, (0, slot) + (0,) * (c.ndim - 2))
                    for c, n in zip(slabs, new)))
            continue
        with _scope("kv_write"):
            position_major = bool(cfg.attn_kinds[kind]["index"])
            cols = slabs[0].shape[2 if position_major else -1]
            if cfg.attn_kinds[kind]["window"] is None and tb > cols:
                raise ValueError("prefill bucket longer than the slot")
            if position_major:  # (L, 1, Tb, width) entries, as they lie
                out.append(tuple(
                    jax.lax.dynamic_update_slice(c, n, (0, slot, 0, 0))
                    for c, n in zip(slabs, new)))
                continue
            if cfg.attn_kinds[kind]["latent"]:
                out.append((jax.lax.dynamic_update_slice(
                    slabs[0], new[0].transpose(0, 1, 3, 2), (0, slot, 0, 0)),))
                continue
            # (L, 1, hkv, hd, Tb)
            new = tuple(n.transpose(0, 1, 2, 4, 3) for n in new)
            if tb > cols:  # a ring shorter than the bucket
                c = jnp.arange(cols, dtype=jnp.int32)
                src = jnp.maximum(length - 1 - jnp.mod(length - 1 - c, cols), 0)
                new = tuple(jnp.take(n, src, axis=4) for n in new)
            out.append(tuple(
                jax.lax.dynamic_update_slice(c, n, (0, slot, 0, 0, 0))
                for c, n in zip(slabs, new)))
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

"""Family ``sparse_latent_decoder_lm``: configurations of latent attention
over an indexer's selection (``glm_moe_dsa``'s published keys) on
``deeplearning4j_tpu.models.decoder_lm.DecoderLM``, served through
``GenerationEngine`` behind ``InferenceServer`` as the ``decoder_lm``
family's are, with ``reference/glm_dsa.py`` as the plain reference.

This module translates the published keys into the program's own: the
layers built are the published layers ``deployment.layers`` names, a layer
whose ``indexer_types`` entry is ``full`` is of the mixer kind that owns an
indexer (``indexed``) and one whose entry is ``shared`` of the kind that
attends to a selection it is handed (``shared``). It writes the reference's
weights, a leaf of a layer at a time (from ``--seed``), into the program's
stacked leaves in place: 7.8 GB of bfloat16 leave no room for a second copy.
The published layouts are kept in the reference; the program stores the
up-projections by head, the key/value one in its two halves (``Wuk``,
``Wuv``), and the indexer's query projection by indexer head (``Iq``). What
serves, counts and closes is ``families/decoder_lm.py``'s ``Server``, whose
counter snapshots stay in ``lib/decoder_read.py`` for the ``moe_*`` and the
sparse readers.
"""

import jax
import jax.numpy as jnp

from families import decoder_lm as base
from lib import work_sparse
from reference import glm_dsa as ref

#: program leaf <- reference leaf
LAYER = {"norm1": "norm1", "norm2": "norm2", "Wqa": "attn.q_a", "norm_q": "attn.q_norm",
         "Wqb": "attn.q_b", "Wkva": "attn.kv_a", "norm_kv": "attn.kv_norm",
         "Wuk": "attn.kv_b", "Wuv": "attn.kv_b", "Wo": "attn.o",
         "Iq": "indexer.q_b", "Ik": "indexer.k", "norm_ik": "indexer.k_norm.g",
         "bias_ik": "indexer.k_norm.b", "Iw": "indexer.w",
         "Wg": "mlp.gate", "Wu": "mlp.up", "Wd": "mlp.down", "Wr": "router.w",
         "br": "router.bias", "Eg": "experts.gate", "Eu": "experts.up", "Ed": "experts.down",
         "Sg": "shared.gate", "Su": "shared.up", "Sd": "shared.down"}
TOP = base.TOP


def program_config(config, max_length=None):
    """The published keys as ``DecoderConfig`` takes them."""
    nope, rope, vd = ref.head_dims(config)
    heads, head_dim, topk = ref.index_dims(config)
    layers = [("indexed" if ref.owns_indexer(config, i) else "shared",
               "dense" if ref.is_dense(config, i) else "experts")
              for i in range(ref.n_layers(config))]
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]:
        raise ValueError("only renormalised sigmoid routing is built")
    latent = {"q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"]}
    kinds = {name: {"rope_theta": config["rope_parameters"]["rope_theta"],
                    "latent": latent,
                    "index": {"heads": heads, "head_dim": head_dim, "topk": topk, "own": own}}
             for name, own in (("indexed", True), ("shared", False))}
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=nope + rope, v_head_dim=vd,
        rotary_dim=rope,
        attn_kinds={k: v for k, v in kinds.items() if any(a == k for a, _ in layers)},
        layers=layers, dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"], n_experts=ref.router_width(config),
        top_k=config["num_experts_per_tok"], experts_held=ref.experts_held(config),
        routing={"scoring": "sigmoid", "scale": config["routed_scaling_factor"]},
        shared_width=ref.shared_width(config), norm_eps=config["rms_norm_eps"],
        max_length=max_length or config["max_position_embeddings"],
        param_dtype=config["deployment"]["param_dtype"])


def _model(config, max_length=None):
    """The program's model; refused at once, before any weight is drawn,
    where the program's ``DecoderConfig`` does not know an indexer (an
    older tree under this family's files would drop the key and serve a
    dense latent model under this configuration's name)."""
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    model = DecoderLM.from_dict(program_config(config, max_length))
    if not all(kind.get("index") for kind in model.cfg.attn_kinds.values()):
        raise SystemExit("sparse_latent_decoder_lm: this program's DecoderLM has no latent "
                         "kind with an indexer (models/decoder_lm.py before PR 40)")
    return model


def _as_stored(config, leaf, row):
    """A reference leaf in the program's layout: the key/value
    up-projection (kv_rank, heads x [k_nope | v]) goes by head and in its
    two halves; the others only change shape."""
    if leaf in ("Wuk", "Wuv"):
        nope, _rope, vd = ref.head_dims(config)
        by_head = row.reshape(row.shape[0], config["num_attention_heads"], nope + vd)
        return by_head[..., :nope] if leaf == "Wuk" else by_head[..., nope:]
    return row


def program_params(config, seed, cfg):
    """The reference's weights under the program's leaf names, stacked a
    segment at a time. Each (layer, leaf) is drawn on its own, cast to the
    leaf's stored dtype (exact: the generator rounded it already) and put
    into its row of the segment's buffer, which is donated."""
    from deeplearning4j_tpu.models.decoder_lm import segment_shapes

    put = jax.jit(lambda buf, row, i: jax.lax.dynamic_update_index_in_dim(
        buf, row.astype(buf.dtype).reshape(buf.shape[1:]), i, 0), donate_argnums=(0,))
    segments, first = [], 0
    for kind, ffn, n in cfg.segments():
        seg = {}
        for leaf, (shape, dtype) in segment_shapes(cfg, kind, ffn).items():
            buf = jnp.zeros((n,) + shape, dtype)
            for j in range(n):
                row = ref.make_leaf(config, seed, first + j, LAYER[leaf])
                buf = put(buf, _as_stored(config, leaf, row), j)
            seg[leaf] = buf
        segments.append(seg)
        first += n
    top = {p: ref.make_leaf(config, seed, -1, r) for p, r in TOP.items()}
    return {"embed": top["embed"].astype(cfg.dtype), "segments": segments,
            "norm_f": top["norm_f"], "head": top["head"].astype(cfg.dtype)}


vocab_size = base.vocab_size


class Server(base.Server):
    """``families/decoder_lm.Server`` around this family's model."""

    def __init__(self, config, traffic, seed):
        from deeplearning4j_tpu.serving import BucketPolicy, InferenceEngine, InferenceServer
        from deeplearning4j_tpu.serving.generate import GenerationEngine

        self.model = _model(config, traffic["engine"].get("max_length"))
        self.model.params_ = program_params(config, seed, self.model.cfg)
        self.gen = GenerationEngine(self.model, **traffic["engine"])
        predict = InferenceEngine(self.model, buckets=BucketPolicy(batch_buckets=[1]))
        self.server = InferenceServer(predict, port=0, generation=self.gen).start()
        self.warmup = self.gen.warmup()
        self._traced = dict(self.gen.trace_counts)
        self.port = self.server.port
        self.slots = self.gen.n_slots


def reference_serve(config, traffic, seed, samples, control_mode=None):
    """As ``families/decoder_lm.reference_serve``, through this family's
    reference (the same share: the held experts, the vocabulary slice;
    indexer and attention by blocks of queries)."""
    longest = traffic["prompt_len"]["max"] + traffic["answer_len"]["max"]
    gaps = ref.served_token_gaps(
        config, seed, samples, pad_to=-(-longest // ref.QUERY_BLOCK) * ref.QUERY_BLOCK,
        answers_pad=traffic["answer_len"]["max"], control_mode=control_mode)
    out = {"served_logit_gap": float(gaps["served"].max()),
           "served_logit_gap_mean": float(gaps["served"].mean()),
           "tokens_compared": int(gaps["served"].size),
           "tokens_below_best": int((gaps["served"] > 0).sum())}
    if control_mode:
        out["control_logit_gap"] = float(gaps["control"].max())
        out["control_logit_gap_mean"] = float(gaps["control"].mean())
    return out


def work_model(config, traffic):
    """What the roofline readers divide by (``lib/work_sparse.py``)."""
    stored = 2 if config["deployment"]["param_dtype"] == "bfloat16" else 4
    return {"decode_program": "jit__decode",
            "expert_bytes": work_sparse.expert_bytes(config, stored),
            "index": {
                "flops_per_position": work_sparse.index_flops_per_position(config),
                "bytes_per_position": work_sparse.index_bytes_per_position(config, stored)},
            "sparse_core": {
                "flops_per_position": work_sparse.sparse_flops_per_position(config),
                "bytes_per_position": work_sparse.sparse_bytes_per_position(config, stored)}}

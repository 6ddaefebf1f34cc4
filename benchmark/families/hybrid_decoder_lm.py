"""Family ``hybrid_decoder_lm``: configurations whose layers are state-space
mixers among attention layers (``granitemoehybrid``'s published keys) on
``deeplearning4j_tpu.models.decoder_lm.DecoderLM``, served through
``GenerationEngine`` behind ``InferenceServer`` as the ``decoder_lm``
family's are, with ``reference/granite_hybrid.py`` as the plain reference.

This module translates the published keys into the program's own and writes
the reference's weights, a leaf of a layer at a time (from ``--seed``), into
the program's stacked leaves in place: 9.5 GB of bfloat16 leave no room for
a second copy. The published layouts are the program's but for the query
projection, which the program stores by head. What serves, counts and closes
is ``families/decoder_lm.py``'s ``Server``, whose counter snapshots stay in
``lib/decoder_read.py`` for the ``moe_*`` readers and for ``lib/ssm_read.py``.
"""

import jax
import jax.numpy as jnp

from families import decoder_lm as base
from lib import work_ssm
from reference import granite_hybrid as ref

#: program leaf <- reference leaf, by the layer's mixer ("Wo" is each mixer's
#: output projection)
LAYER = {"norm1": "norm1", "norm2": "norm2", "Win": "mamba.in_proj", "conv_w": "mamba.conv_w",
         "conv_b": "mamba.conv_b", "dt_bias": "mamba.dt_bias", "A_log": "mamba.A_log",
         "D": "mamba.D", "norm_g": "mamba.norm", "Wq": "attn.q", "Wk": "attn.k", "Wv": "attn.v",
         "Wr": "router.w", "Eg": "experts.gate", "Eu": "experts.up", "Ed": "experts.down",
         "Sg": "shared.gate", "Su": "shared.up", "Sd": "shared.down"}
OUT = {"ssm": "mamba.out_proj", "attention": "attn.o"}
TOP = {"embed": "embed", "norm_f": "norm_f"}
#: what the published keys must say for this family's block to be the model's
BUILT = {"hidden_act": "silu", "normalization_function": "rmsnorm", "attention_bias": False,
         "mamba_proj_bias": False, "mamba_conv_bias": True, "position_embedding_type": "nope",
         "tie_word_embeddings": True}


def program_config(config, max_length=None):
    """The published keys as ``DecoderConfig`` takes them. The gate (softmax
    over the ``num_experts_per_tok`` largest router outputs) is the program's
    group-limited softmax rule with ONE group, renormalised: softmax over all
    then renormalised over the chosen equals softmax over the chosen
    (``tests/test_granite_lm.py`` proves it)."""
    for key, value in BUILT.items():
        if config[key] != value:
            raise ValueError(f"{key} {config[key]!r} is not built")
    h, p, n, g, _inner, _conv, k = ref.ssm_dims(config)
    kinds = {
        "ssm": {"ssm": {"n_heads": h, "head_dim": p, "d_state": n, "n_groups": g, "d_conv": k,
                        "expand": config["mamba_expand"], "chunk": config["mamba_chunk_size"]}},
        "attention": {"n_kv_heads": config["num_key_value_heads"],
                      "rope_theta": config["rope_theta"], "window": None, "sink": False}}
    layers = [("ssm" if ref.is_ssm(config, i) else "attention", "experts")
              for i in range(ref.n_layers(config))]
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=ref.head_dim(config),
        v_head_dim=ref.head_dim(config), rotary_dim=0,
        attn_kinds={k: v for k, v in kinds.items() if any(a == k for a, _ in layers)},
        layers=layers, dense_width=0, expert_width=config["intermediate_size"],
        n_experts=ref.router_width(config), top_k=config["num_experts_per_tok"],
        experts_held=ref.experts_held(config),
        routing={"n_group": 1, "topk_group": 1, "renormalise": True, "scale": 1.0},
        shared_width=config["shared_intermediate_size"], norm_eps=config["rms_norm_eps"],
        max_length=max_length or config["max_position_embeddings"],
        param_dtype=config["deployment"]["param_dtype"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"], tied_head=config["tie_word_embeddings"])


def _model(config, max_length=None):
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    return DecoderLM.from_dict(program_config(config, max_length))


def program_params(config, seed, cfg):
    """The reference's weights under the program's leaf names, stacked a
    segment at a time. Each (layer, leaf) is drawn on its own, cast to the
    leaf's stored dtype (exact: the generator rounded it already) and put
    into its row of the segment's buffer, which is donated. The head is the
    embedding: no leaf of its own."""
    from deeplearning4j_tpu.models.decoder_lm import segment_shapes

    put = jax.jit(lambda buf, row, i: jax.lax.dynamic_update_index_in_dim(
        buf, row.astype(buf.dtype).reshape(buf.shape[1:]), i, 0), donate_argnums=(0,))
    segments, first = [], 0
    for kind, ffn, n in cfg.segments():
        seg = {}
        for leaf, (shape, dtype) in segment_shapes(cfg, kind, ffn).items():
            buf = jnp.zeros((n,) + shape, dtype)
            for j in range(n):
                buf = put(buf, ref.make_leaf(config, seed, first + j,
                                             OUT[kind] if leaf == "Wo" else LAYER[leaf]), j)
            seg[leaf] = buf
        segments.append(seg)
        first += n
    top = {p: ref.make_leaf(config, seed, -1, r) for p, r in TOP.items()}
    return {"embed": top["embed"].astype(cfg.dtype), "segments": segments,
            "norm_f": top["norm_f"]}


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
    reference (the same share: the held experts, the vocabulary slice; the
    state-space layers a position at a time, attention by blocks of
    queries)."""
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
    """What the roofline readers divide by (``lib/work_ssm.py``)."""
    stored = 2 if config["deployment"]["param_dtype"] == "bfloat16" else 4
    return {"decode_program": "jit__decode",
            "expert_bytes": work_ssm.expert_bytes(config, stored),
            "ssm_state": {"bytes_per_live_slot": work_ssm.state_bytes_per_live_slot(config, stored)}}

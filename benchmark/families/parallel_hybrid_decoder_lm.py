"""Family ``parallel_hybrid_decoder_lm``: configurations whose every block has
an attention AND a state-space mixer side by side on one normed input
(``falcon_h1``'s published keys) on
``deeplearning4j_tpu.models.decoder_lm.DecoderLM``, served through
``GenerationEngine`` behind ``InferenceServer`` as the ``decoder_lm`` family's
are, with ``reference/falcon_h1.py`` as the plain reference.

This module translates the published keys into the program's own (every muP
multiplier as data of ``DecoderConfig``, none folded into a weight) and writes
the reference's weights, a leaf of a layer at a time (from ``--seed``), into
the program's stacked leaves in place: 10.5 GB of bfloat16 leave no room for a
second copy, and the embedding and the head are drawn AS bfloat16 (5.35 GB
each in float32). The published layouts are the program's but for the query
projection, which the program stores by head. What serves, counts and closes
is ``families/decoder_lm.py``'s ``Server``, whose counter snapshots stay in
``lib/decoder_read.py`` for ``lib/parallel_read.py``.
"""

import jax
import jax.numpy as jnp

from families import decoder_lm as base
from lib import work_parallel
from reference import falcon_h1 as ref

#: the one mixer kind, and program leaf <- reference leaf ("Wo" is the
#: attention's output projection, "Wso" the state-space mixer's)
KIND = "parallel"
LAYER = {"norm1": "norm1", "norm2": "norm2", "Wq": "attn.q", "Wk": "attn.k", "Wv": "attn.v",
         "Wo": "attn.o", "Win": "mamba.in_proj", "conv_w": "mamba.conv_w",
         "conv_b": "mamba.conv_b", "dt_bias": "mamba.dt_bias", "A_log": "mamba.A_log",
         "D": "mamba.D", "norm_g": "mamba.norm", "Wso": "mamba.out_proj", "Wg": "mlp.gate",
         "Wu": "mlp.up", "Wd": "mlp.down"}


def program_config(config, max_length=None):
    """The published keys as ``DecoderConfig`` takes them.
    ``lm_head_multiplier`` is handed over as ``logits_scaling``, which
    DIVIDES, at its inverse: 0.0078125 is 2^-7, so dividing by 128 gives the
    same float32 bits as multiplying by it."""
    ref.check(config)
    h, p, n, g, inner, _conv, k = ref.ssm_dims(config)
    kind = {"parallel": True, "n_kv_heads": config["num_key_value_heads"],
            "rope_theta": config["rope_theta"], "window": None, "sink": False,
            "in_multiplier": config["attention_in_multiplier"],
            "key_multiplier": config["key_multiplier"],
            "out_multiplier": config["attention_out_multiplier"],
            "ssm": {"n_heads": h, "head_dim": p, "d_state": n, "n_groups": g, "d_conv": k,
                    "expand": config["mamba_expand"], "chunk": config["mamba_chunk_size"],
                    "d_inner": inner, "in_multiplier": config["ssm_in_multiplier"],
                    "out_multiplier": config["ssm_out_multiplier"],
                    "multipliers": config["ssm_multipliers"]}}
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        v_head_dim=config["head_dim"], rotary_dim=config["head_dim"],
        attn_kinds={KIND: kind}, layers=[(KIND, "dense")] * ref.n_layers(config),
        dense_width=config["intermediate_size"], norm_eps=config["rms_norm_eps"],
        max_length=max_length or config["max_position_embeddings"],
        param_dtype=config["deployment"]["param_dtype"],
        embedding_multiplier=config["embedding_multiplier"],
        mlp_multipliers=config["mlp_multipliers"],
        logits_scaling=1.0 / config["lm_head_multiplier"],
        tied_head=config["tie_word_embeddings"])


def _model(config, max_length=None):
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    return DecoderLM.from_dict(program_config(config, max_length))


def program_params(config, seed, cfg):
    """The reference's weights under the program's leaf names, stacked a
    segment at a time. Each (layer, leaf) is drawn on its own in its stored
    dtype (the generator rounds a bfloat16 leaf itself) and put into its row
    of the segment's buffer, which is donated."""
    from deeplearning4j_tpu.models.decoder_lm import segment_shapes

    put = jax.jit(lambda buf, row, i: jax.lax.dynamic_update_index_in_dim(
        buf, row.astype(buf.dtype).reshape(buf.shape[1:]), i, 0), donate_argnums=(0,))
    segments, first = [], 0
    for kind, ffn, n in cfg.segments():
        seg = {}
        for leaf, (shape, dtype) in segment_shapes(cfg, kind, ffn).items():
            buf = jnp.zeros((n,) + shape, dtype)
            for j in range(n):
                buf = put(buf, ref.make_leaf(config, seed, first + j, LAYER[leaf], stored=True), j)
            seg[leaf] = buf
        segments.append(seg)
        first += n
    top = {name: ref.make_leaf(config, seed, -1, name, stored=True).astype(
        jnp.float32 if name == "norm_f" else cfg.dtype) for name in ref.TOP_LEAVES}
    return {**top, "segments": segments}


vocab_size = base.vocab_size


class Server(base.Server):
    """``families/decoder_lm.Server`` around this family's model."""

    def __init__(self, config, traffic, seed):
        from deeplearning4j_tpu.serving import BucketPolicy, InferenceEngine, InferenceServer
        from deeplearning4j_tpu.serving.generate import GenerationEngine

        self.model = _model(config, traffic["engine"].get("max_length"))
        self.model.params_ = program_params(config, seed, self.model.cfg)
        # the programs that drew and stacked the weights plan outputs of up to
        # 2.67 GB (the embedding; a layer's stacked MLP leaf 1.32 GB); left
        # loaded, the largest would be the plan ``memory_peak_bytes`` adds to
        # the live bytes, though none of them runs beside the cache
        jax.clear_caches()
        self.gen = GenerationEngine(self.model, **traffic["engine"])
        predict = InferenceEngine(self.model, buckets=BucketPolicy(batch_buckets=[1]))
        self.server = InferenceServer(predict, port=0, generation=self.gen).start()
        self.warmup = self.gen.warmup()
        self._traced = dict(self.gen.trace_counts)
        self.port = self.server.port
        self.slots = self.gen.n_slots


def reference_serve(config, traffic, seed, samples, control_mode=None):
    """As ``families/decoder_lm.reference_serve``, through this family's
    reference (the recurrence a position at a time, attention by blocks of
    queries, the head ``ref.HEAD_BLOCK`` positions at a time)."""
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
    """What the ``par_*`` readers divide by (``lib/work_parallel.py``)."""
    stored = 2 if config["deployment"]["param_dtype"] == "bfloat16" else 4
    return {"decode_program": "jit__decode",
            "parallel": {"mixer_weight_bytes": work_parallel.mixer_weight_bytes(config, stored),
                         "step_weight_bytes": work_parallel.step_weight_bytes(config, stored),
                         "embed_row_bytes": work_parallel.embed_row_bytes(config, stored),
                         "state_bytes_per_live_slot":
                             work_parallel.state_bytes_per_live_slot(config, stored),
                         "cache_bytes_per_position":
                             work_parallel.cache_bytes_per_position(config, stored)}}

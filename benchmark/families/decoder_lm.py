"""Family ``decoder_lm``: configurations that run on
``deeplearning4j_tpu.models.decoder_lm.DecoderLM`` (a decoder whose stack
is data: attention kinds, a layer pattern, dense or expert FFNs, the
chip's share of experts and vocabulary), served through
``GenerationEngine`` behind ``InferenceServer``, with
``reference/mimo_v2.py`` as the plain reference.

The configuration file keeps the published ``config.json`` keys; this
module translates them into the program's own. The weights come from the
reference's generator, a leaf of a layer at a time (from ``--seed``), and
are written into the program's stacked leaves in place: 10.8 GB of
bfloat16 leave no room for a second copy.
"""

import gc

import jax
import jax.numpy as jnp

from lib import decoder_read, work_decoder
from reference import mimo_v2 as ref

#: program leaf <- reference leaf
LAYER = {"norm1": "norm1", "norm2": "norm2", "Wq": "attn.q", "Wk": "attn.k", "Wv": "attn.v",
         "Wo": "attn.o", "sink": "attn.sink", "Wg": "mlp.gate", "Wu": "mlp.up",
         "Wd": "mlp.down", "Wr": "router.w", "br": "router.bias", "Eg": "experts.gate",
         "Eu": "experts.up", "Ed": "experts.down"}
TOP = {"embed": "embed", "norm_f": "norm_f", "head": "head"}


def program_config(config, max_length=None):
    """The published keys as ``DecoderConfig`` takes them."""
    layers = [("window" if ref.is_window(config, i) else "full",
               "dense" if ref.is_dense(config, i) else "experts")
              for i in range(ref.n_layers(config))]
    kinds = {
        "full": {"n_kv_heads": config["num_key_value_heads"], "rope_theta": config["rope_theta"],
                 "window": None, "sink": config["add_full_attention_sink_bias"]},
        "window": {"n_kv_heads": config["swa_num_key_value_heads"],
                   "rope_theta": config["swa_rope_theta"], "window": config["sliding_window"],
                   "sink": config["add_swa_attention_sink_bias"]}}
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        v_head_dim=config["v_head_dim"], rotary_dim=ref.rotary_dim(config),
        attn_kinds={k: v for k, v in kinds.items() if any(a == k for a, _ in layers)},
        layers=layers, dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"], n_experts=ref.router_width(config),
        top_k=config["num_experts_per_tok"], experts_held=ref.experts_held(config),
        value_scale=config["attention_value_scale"], norm_eps=config["layernorm_epsilon"],
        max_length=max_length or config["max_position_embeddings"],
        param_dtype=config["deployment"]["param_dtype"])


def _model(config, max_length=None):
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    return DecoderLM.from_dict(program_config(config, max_length))


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
                buf = put(buf, ref.make_leaf(config, seed, first + j, LAYER[leaf]), j)
            seg[leaf] = buf
        segments.append(seg)
        first += n
    top = {p: ref.make_leaf(config, seed, -1, r) for p, r in TOP.items()}
    return {"embed": top["embed"].astype(cfg.dtype), "segments": segments,
            "norm_f": top["norm_f"], "head": top["head"].astype(cfg.dtype)}


def vocab_size(config):
    """The slice held here: the traffic draws its ids from it."""
    return config["vocab_size"]


class Server:
    """``GenerationEngine`` behind ``InferenceServer`` on loopback, warmed."""

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

    def counters(self):
        """The engine's own counters (``GenerationMetrics.snapshot``), and a
        copy for the readers that the kind hands no counter of theirs."""
        return decoder_read.record(self.gen.metrics.snapshot())

    def retraces(self):
        return sum(v - self._traced.get(k, 0) for k, v in self.gen.trace_counts.items())

    def close(self):
        """Everything on the device goes: the reference needs the room."""
        self.server.generation = None
        self.server.shutdown()
        self.gen.shutdown(drain=False)  # lets the cache go
        self.server.engine.release()    # the /predict snapshot holds the weights
        self.model.params_ = None
        self.gen = self.server = self.model = None
        gc.collect()


def reference_serve(config, traffic, seed, samples, control_mode=None):
    """As ``families/transformer_lm.reference_serve``: over the served
    tokens of ``samples``, the gap by which a served token's logit lies
    below the reference's best, the widest and the mean; with
    ``control_mode`` the same for the tokens a pass in that mode would
    serve. The reference is given the same share (experts held, vocabulary
    slice) and runs in blocks."""
    longest = traffic["prompt_len"]["max"] + traffic["answer_len"]["max"]
    gaps = ref.served_token_gaps(
        config, seed, samples, pad_to=-(-longest // 128) * 128,
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
    """What the roofline readers divide by (``lib/work_decoder.py``)."""
    stored = 2 if config["deployment"]["param_dtype"] == "bfloat16" else 4
    full, window = work_decoder.cache_bytes_per_position(config, stored)
    return {"decode_program": "jit__decode",
            "decode_fixed_weight_bytes": work_decoder.decode_fixed_weight_bytes(config, stored),
            "expert_bytes": work_decoder.expert_bytes(config, stored),
            "cache_bytes_per_position": {"full": full, "window": window}}

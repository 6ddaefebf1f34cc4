"""Family ``looped_decoder_lm``: configurations whose whole stack runs
several times a token over one set of weights (``ouro``'s published keys:
``total_ut_steps``, ``early_exit_threshold``) on
``deeplearning4j_tpu.models.decoder_lm.DecoderLM``, served through
``GenerationEngine`` behind ``InferenceServer`` as the ``decoder_lm``
family's are, with ``reference/ouro.py`` as the plain reference.

This module translates the published keys into the program's own and writes
the reference's weights, a leaf of a layer at a time (from ``--seed``), into
the program's stacked leaves in place. The published layouts are the
program's but for the query projection, which the program stores by head.
The MODEL is built before a weight is drawn, so a tree whose
``DecoderConfig`` knows no ``passes`` fails in its first second. What
serves, counts and closes is ``families/decoder_lm.py``'s ``Server``, whose
counter snapshots stay in ``lib/decoder_read.py`` for ``lib/looped_read.py``.
"""

import jax
import jax.numpy as jnp

from families import decoder_lm as base
from lib import work_looped
from reference import ouro as ref

#: program leaf <- reference leaf
LAYER = {"norm1": "norm1", "norm1b": "norm1b", "norm2": "norm2", "norm2b": "norm2b",
         "Wq": "attn.q", "Wk": "attn.k", "Wv": "attn.v", "Wo": "attn.o",
         "Wg": "mlp.gate", "Wu": "mlp.up", "Wd": "mlp.down"}
TOP = {"embed": "embed", "norm_f": "norm_f", "head": "head", "gate_w": "gate.w",
       "gate_b": "gate.b"}
#: what the published keys must say for this family's block to be the model's
BUILT = {"hidden_act": "silu", "tie_word_embeddings": False, "rope_scaling": None,
         "use_sliding_window": False}


def program_config(config, max_length=None):
    """The published keys as ``DecoderConfig`` takes them: every layer a
    full-attention layer over a dense MLP, rotary positions over the whole
    head, the stack run ``total_ut_steps`` times with the sandwich norms
    and the exit gate the configuration file lists under ``assumed``."""
    for key, value in BUILT.items():
        if config[key] != value:
            raise ValueError(f"{key} {config[key]!r} is not built")
    if set(config["layer_types"]) != {"full_attention"}:
        raise ValueError("layer_types other than full_attention are not built")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        v_head_dim=config["head_dim"], rotary_dim=config["head_dim"],
        attn_kinds={"full": {"n_kv_heads": config["num_key_value_heads"],
                             "rope_theta": config["rope_theta"], "window": None,
                             "sink": False}},
        layers=[("full", "dense")] * ref.n_layers(config),
        dense_width=config["intermediate_size"], norm_eps=config["rms_norm_eps"],
        max_length=max_length or config["max_position_embeddings"],
        param_dtype=config["deployment"]["param_dtype"],
        passes=ref.n_passes(config), sandwich_norm=True, exit_gate=True,
        exit_threshold=config["early_exit_threshold"])


def _model(config, max_length=None):
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    return DecoderLM.from_dict(program_config(config, max_length))


def program_params(config, seed, cfg):
    """The reference's weights under the program's leaf names, the layers
    stacked. Each (layer, leaf) is drawn on its own, cast to the leaf's
    stored dtype (exact: the generator rounded it already) and put into its
    row of the stack's buffer, which is donated."""
    from deeplearning4j_tpu.models.decoder_lm import segment_shapes

    put = jax.jit(lambda buf, row, i: jax.lax.dynamic_update_index_in_dim(
        buf, row.astype(buf.dtype).reshape(buf.shape[1:]), i, 0), donate_argnums=(0,))
    (kind, ffn, n), = cfg.segments()
    seg = {}
    for leaf, (shape, dtype) in segment_shapes(cfg, kind, ffn).items():
        buf = jnp.zeros((n,) + shape, dtype)
        for j in range(n):
            buf = put(buf, ref.make_leaf(config, seed, j, LAYER[leaf]), j)
        seg[leaf] = buf
    top = {p: ref.make_leaf(config, seed, -1, r) for p, r in TOP.items()}
    return {**top, "embed": top["embed"].astype(cfg.dtype), "segments": [seg],
            "head": top["head"].astype(cfg.dtype)}


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
    reference: every pass for every token, the served rule."""
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
    """What the roofline readers divide by (``lib/work_looped.py``)."""
    stored = 2 if config["deployment"]["param_dtype"] == "bfloat16" else 4
    return {"decode_program": "jit__decode",
            "looped": {"passes": ref.n_passes(config),
                       "layer_weight_bytes": work_looped.layer_weight_bytes(config, stored),
                       "head_bytes": work_looped.head_bytes(config, stored),
                       "embed_row_bytes": work_looped.embed_row_bytes(config, stored),
                       "cache_bytes_per_position":
                           work_looped.cache_bytes_per_position(config, stored)}}

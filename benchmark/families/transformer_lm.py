"""Family ``transformer_lm``: configurations that run on
``deeplearning4j_tpu.models.transformer_lm.TransformerLM`` (training through
``fit_batch``, serving through ``GenerationEngine`` behind
``InferenceServer``), with ``reference/gpt2.py`` as the plain reference.

The weights come from the reference's ``make_weights`` (the benchmark's own
generator, from ``--seed``) and are handed to the program under its leaf
names; the program's own ``init`` never supplies them.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np

from lib import work
from reference import gpt2

#: program leaf <- reference leaf
TOP = {"embed": "wte", "pos": "wpe", "lnf_g": "ln_f.g", "lnf_b": "ln_f.b", "head": "head"}
BLOCK = {"ln1_g": "ln_1.g", "ln1_b": "ln_1.b", "Wq": "attn.q", "Wk": "attn.k",
         "Wv": "attn.v", "Wo": "attn.proj.w", "bo": "attn.proj.b",
         "ln2_g": "ln_2.g", "ln2_b": "ln_2.b", "W1": "mlp.fc.w", "b1": "mlp.fc.b",
         "W2": "mlp.proj.w", "b2": "mlp.proj.b"}


def program_tree(weights):
    out = {p: weights[r] for p, r in TOP.items()}
    out["blocks"] = {p: weights["blocks"][r] for p, r in BLOCK.items()}
    return out


def _to_program_names(norms):
    """{"blocks/attn.q": x} -> {"blocks/Wq": x}"""
    back = {r: p for p, r in TOP.items()}
    back.update({"blocks/" + r: "blocks/" + p for p, r in BLOCK.items()})
    return {back[k]: v for k, v in norms.items()}


def _model(config):
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.updaters import Adam

    opt = config["optimizer"]
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        mlp_ratio=config.get("mlp_ratio", 4), max_length=config["n_positions"],
        compute_dtype=config["compute_dtype"],
        updater=Adam(opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"]))


def vocab_size(config):
    return config["vocab_size"]


def item_shape(config, traffic):
    """Items (tokens) in one training step."""
    return traffic["batch"] * traffic["seq_len"]


def batch_source(config, traffic, rng):
    """``next_batch()``: a fresh host batch of distinct rows every call,
    ids and next-token targets."""

    def next_batch():
        ids = rng.integers(0, config["vocab_size"],
                           (traffic["batch"], traffic["seq_len"])).astype(np.int32)
        targets = np.roll(ids, -1, axis=1)
        targets[:, -1] = -1
        return ids, targets

    return next_batch


class Trainer:
    """One model with its compiled step and state, from set-up through the
    window: ``step`` is the public ``fit_batch``."""

    def __init__(self, config, traffic, seed):
        self.config, self.seed = config, seed
        self.model = _model(config).init()
        self.model.params_ = program_tree(gpt2.make_weights(config, seed))
        self.items_per_step = item_shape(config, traffic)

    def step(self, batch):
        return self.model.fit_batch(*batch)

    def steps_taken(self):
        return int(self.model.iteration)

    def first_gradient_norms(self):
        """Leaf norms of the gradient the optimizer got in step 1, from
        Adam's first moment after that step: m1 = (1 - beta1) g."""
        m = {k: (v["m"] if k != "blocks" else {b: s["m"] for b, s in v.items()})
             for k, v in self.model.opt_state_.items()}
        scale = 1.0 - self.config["optimizer"]["beta1"]
        return {k: n / scale for k, n in gpt2.leaf_norms(m).items()}

    def update_norms(self):
        """Leaf norms of (parameters now - parameters from the seed)."""
        start = program_tree(gpt2.make_weights(self.config, self.seed))
        return gpt2.leaf_norms(jax.tree_util.tree_map(jnp.subtract, self.model.params_, start))

    def retraces(self):
        """The trainer keeps no count of its own; JAX's compile events in
        the window are what ``window_compiles`` reads."""
        return 0

    def close(self):
        self.model.params_ = self.model.opt_state_ = None
        self.model = None
        gc.collect()


def reference_train(config, seed, batches, mode="float32"):
    out = gpt2.train_steps(config, seed, batches, config["optimizer"], mode)
    return {"losses": out["losses"],
            "grad_norms": _to_program_names(out["grad_norms"]),
            "update_norms": _to_program_names(out["update_norms"])}


class Server:
    """``GenerationEngine`` behind ``InferenceServer`` on loopback, warmed."""

    def __init__(self, config, traffic, seed):
        from deeplearning4j_tpu.serving import BucketPolicy, InferenceEngine, InferenceServer
        from deeplearning4j_tpu.serving.generate import GenerationEngine

        self.model = _model(config)
        self.model.params_ = program_tree(gpt2.make_weights(config, seed))
        self.gen = GenerationEngine(self.model, **traffic["engine"])
        predict = InferenceEngine(self.model, buckets=BucketPolicy(batch_buckets=[1]))
        self.server = InferenceServer(predict, port=0, generation=self.gen).start()
        self.warmup = self.gen.warmup()
        self._traced = dict(self.gen.trace_counts)
        self.port = self.server.port
        self.slots = self.gen.n_slots

    def counters(self):
        """The engine's own counters (``GenerationMetrics.snapshot``)."""
        return self.gen.metrics.snapshot()

    def retraces(self):
        return sum(v - self._traced.get(k, 0) for k, v in self.gen.trace_counts.items())

    def close(self):
        self.server.generation = None
        self.server.shutdown()
        self.gen.shutdown(drain=False)
        self.model.params_ = None
        self.gen = self.server = self.model = None
        gc.collect()


def reference_serve(config, traffic, seed, samples, control_mode=None):
    """Over the served tokens of ``samples`` (dicts with ``prompt`` and
    ``tokens``), the gap by which a served token's logit lies below the
    reference's best: the widest, and the mean over all served tokens (a
    max over some hundreds of tokens swings from seed to seed; the mean
    grows with the square of the arithmetic's noise and is steady). With
    ``control_mode`` the same for the tokens a pass in that mode would
    serve."""
    w = gpt2.make_weights(config, seed)
    longest = traffic["prompt_len"]["max"] + traffic["answer_len"]["max"]
    gaps = gpt2.served_token_gaps(
        config, w, samples, pad_to=-(-longest // 128) * 128,
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
    """What the roofline readers divide by."""
    out = {}
    if "seq_len" in traffic:
        out["flops_per_item"] = work.lm_train_flops_per_token(config, traffic["seq_len"])
    out["decode_weight_bytes"] = work.lm_decode_weight_bytes(config, 4)  # float32 masters
    out["kv_bytes_per_position"] = work.lm_kv_bytes_per_position(
        config, 2 if config["compute_dtype"] == "bfloat16" else 4)
    out["decode_program"] = "jit__decode"
    return out

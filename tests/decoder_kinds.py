"""Seven tiny ``DecoderLM`` models, one a kind of layer the serving engine
has a cache for: dense (full and window attention over a dense MLP),
expert (the same attention over routed experts), latent (one latent
cache, group-limited routing, a shared expert), sparse-latent (latent
attention over an indexer's selection: a key slab beside the latent one,
layers that share a selection), state-space (Mamba-2 mixers around one
attention layer), looped (three full-attention layers run three times a
token over one set of weights, a cache entry a (pass, layer), four norms a
layer) and parallel (attention and a Mamba-2 mixer side by side in every
block: K, V, state and tail in one cache entry). Built from the benchmark's rehearsal
presets through their family modules, as the cells build theirs, with
float32 parameters drawn by ``init_params``."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

KINDS = {"dense": ("decoder_lm", "tiny-mimo"),
         "expert": ("decoder_lm", "tiny-mimo"),
         "latent": ("latent_decoder_lm", "tiny-deepseek"),
         "sparse-latent": ("sparse_latent_decoder_lm", "tiny-glm"),
         "state-space": ("hybrid_decoder_lm", "tiny-granite"),
         "looped": ("looped_decoder_lm", "tiny-ouro"),
         "parallel": ("parallel_hybrid_decoder_lm", "tiny-falcon-h1")}
#: the kinds that were there before a mixer's norm and residual add moved to
#: a shared caller (``tests/fixtures/decoder_lm/kinds_before_join.json``)
BEFORE_JOIN = ("dense", "expert", "latent", "sparse-latent", "state-space",
               "looped")


def _family(name):
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = importlib.util.spec_from_file_location(
        f"decoder_kinds_{name}", os.path.join(BENCH, "families", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def program(kind):
    """``DecoderConfig``'s keyword arguments of ``kind`` (a key of
    ``KINDS``), float32 parameters, context 128."""
    family, preset = KINDS[kind]
    with open(os.path.join(BENCH, "configs", f"{preset}.json")) as f:
        config = json.load(f)
    config["deployment"]["param_dtype"] = "float32"
    out = _family(family).program_config(config)
    if kind == "dense":
        out["layers"] = [(mixer, "dense") for mixer, _ in out["layers"]]
    return out


def decoder_lm(kind):
    """An initialised float32 ``DecoderLM`` of ``kind`` (a key of
    ``KINDS``), context 128."""
    from deeplearning4j_tpu.models.decoder_lm import DecoderLM

    return DecoderLM.from_dict(program(kind)).init()


def readings(kind):
    """What a fixture keeps of ``kind``'s tiny model: the logits of a full
    forward, and the logits and every cache slab after a prompt of 11 is
    prefilled into slot 1 of a 3 x 64 cache and slot 1 alone takes one
    decode step: 16 values, the mean magnitude and the SHA-256 of each."""
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    m = decoder_lm(kind)
    from deeplearning4j_tpu.models import decoder_lm as dl

    def keep(a):
        a = np.ascontiguousarray(np.asarray(a, np.float32))
        return {"first": a.reshape(-1)[:16].tolist(),
                "abs_mean": float(np.abs(a).mean()),
                "sha256": hashlib.sha256(a.tobytes()).hexdigest()}

    cfg = m.cfg
    ids = (np.arange(24, dtype=np.int32).reshape(2, 12) * 7
           + 3) % cfg.vocab_size
    out = {"forward": keep(m.logits(ids)[1, -1])}
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = ids.reshape(-1)[:11]
    logits, caches = jax.jit(lambda p, c: dl.prefill_slot(
        cfg, p, c, jnp.asarray(padded), jnp.asarray(11, jnp.int32),
        jnp.asarray(1, jnp.int32)))(m.params_, dl.init_cache(cfg, 3, 64))
    out["prefill"] = keep(logits)
    logits, caches, _counts = jax.jit(lambda p, c: dl.decode_step(
        cfg, p, c, jnp.asarray([3, 4, 5], jnp.int32),
        jnp.asarray([0, 11, 0], jnp.int32),
        jnp.asarray([False, True, False])))(m.params_, caches)
    out["decode"] = keep(logits[1])
    out["caches"] = [[keep(c) for c in seg] for seg in caches]
    return out


if __name__ == "__main__":
    # python tests/decoder_kinds.py <fixture.json>: the readings of the six
    # kinds that were there before the join moved, by the tree this is run on
    sys.path.insert(0, ROOT)
    with open(sys.argv[1], "w") as f:
        json.dump({kind: readings(kind) for kind in BEFORE_JOIN}, f, indent=1)

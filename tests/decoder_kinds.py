"""Six tiny ``DecoderLM`` models, one a kind of layer the serving engine
has a cache for: dense (full and window attention over a dense MLP),
expert (the same attention over routed experts), latent (one latent
cache, group-limited routing, a shared expert), sparse-latent (latent
attention over an indexer's selection: a key slab beside the latent one,
layers that share a selection), state-space (Mamba-2 mixers around one
attention layer) and looped (three full-attention layers run three times a
token over one set of weights, a cache entry a (pass, layer), four norms a
layer). Built from the benchmark's rehearsal
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
         "looped": ("looped_decoder_lm", "tiny-ouro")}


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

#!/usr/bin/env python
"""The serving programs of the decoder cells, compiled for a DESCRIBED TPU
v5e (no chip attached) at published widths and a cut depth, one line a
program: what two trees must agree on to be the same programs.

    chat      gpt2-large.chat: ``TransformerLM``'s decode program, 4 layers
    mimo      mimo-v2.5-ep16: a full dense layer, two window expert layers
    deepseek  deepseek-v2-ep8: the dense latent layer and one expert layer
    glm       glm-5.2-ep16: the whole cut (owner, three sharers, owner)
    granite   granite-4.0-h-small-ep2: the whole cut (5 + 1 + 4 layers)
    ouro      ouro-2.6b: 12 of the 48 layers, four passes
    falcon    falcon-h1-34b-l6: the whole cut (six parallel blocks)

Each ``DecoderLM`` cell gives its decode program and one prefill program;
the registry's verdicts are steered as the chip's probes give them (this
process's backend is the CPU). ``tests/test_tpu_compile.py`` takes its
programs from here (``build``), so there is ONE definition of "the cell's
program at a cut depth".

    python scripts/decoder_programs.py --out /root/scratch/programs.jsonl
    python scripts/decoder_programs.py --tiny      # tests/decoder_kinds, CPU

A line holds the opcode histogram of the optimised HLO, the custom-call
targets (a Mosaic kernel by its name), temporary / argument / output /
alias bytes of the plan, and a hash of the HLO text without its metadata
(source lines move with every edit; instruction names do not). Nothing
runs: a compile that passes is not a chip run. ``--tiny`` compiles the
tiny models of ``tests/decoder_kinds.py`` for the CPU in seconds instead.
"""

import argparse
import base64
import collections
import contextlib
import functools
import hashlib
import json
import os
import re
import sys
from types import SimpleNamespace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

BF16 = jnp.bfloat16


def described_chip():
    """The sharding of one chip of a described v5e 2x2 (raises where the
    topology cannot be described). Call it from a fixture or from
    ``main``, never at import: one process at a time loads the TPU
    library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # the TPU compiler would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _attends(slots, hkv, grp, hd, vd, t, dtype):
    from deeplearning4j_tpu.nn.ops import decode_attention as da

    tile = da.tile_for(t)
    return functools.partial(da.decode_attention, tile=tile), tile


@contextlib.contextmanager
def steered(*names):
    """The kernel registry's verdicts for ``names`` as the chip's probes
    give them, for the programs lowered inside; yields {name: the keys it
    was asked at}."""
    from deeplearning4j_tpu.models import decoder_lm, transformer_lm
    from deeplearning4j_tpu.nn.conf.layers import moe
    from deeplearning4j_tpu.nn.ops import grouped_experts as ge
    from deeplearning4j_tpu.nn.ops import kv_column_write as kcw
    from deeplearning4j_tpu.nn.ops import latent_decode, ssm_decode
    from deeplearning4j_tpu.nn.ops import sparse_latent_decode as sld

    asked = {name: [] for name in names}

    def column_write(entries, slots, heads, head_size, t, dtype):
        asked["kv_column_write"].append(
            (entries, slots, heads, head_size, t, jnp.dtype(dtype).name))
        return functools.partial(kcw.kv_column_write, lb=kcw.entries_a_block(
            entries, heads, head_size, jnp.dtype(dtype).itemsize))

    def latent(heads, width, t_c, dtype, kv_rank):
        asked["latent_decode"].append(
            (heads, width, t_c, jnp.dtype(dtype).name, kv_rank))
        return functools.partial(latent_decode.latent_decode_core,
                                 kv_rank=kv_rank, tile=latent_decode.TILE)

    def state(heads, p, n, groups, slots, dtype):
        asked["ssm_decode"].append(
            (heads, p, n, groups, slots, jnp.dtype(dtype).name))
        return functools.partial(ssm_decode.ssm_decode_step,
                                 tile=ssm_decode._tile(heads, p, n, groups))

    def products(m, d, f, count, dtype):
        planned = ge.plan(m, d, f, dtype)   # a prefill's rows decline
        if planned is None:
            return None
        window, tile = planned
        asked["grouped_experts"].append(
            (d, f, count, m, window, tile, jnp.dtype(dtype).name))
        return functools.partial(ge.grouped_experts, window=window, tile=tile)

    def selected(heads, width, t_c, topk, dtype, kv_rank):
        tile = sld.plan(t_c, topk)
        asked["sparse_latent_decode"].append(
            (heads, width, t_c, topk, tile, jnp.dtype(dtype).name))
        return functools.partial(sld.sparse_latent_decode, kv_rank=kv_rank,
                                 tile=tile), tile

    seams = {"grouped_experts": [(moe, "grouped_experts_impl", products)],
             "sparse_latent_decode": [(decoder_lm, "sparse_latent_decode_impl",
                                       selected)],
             "kv_column_write": [(transformer_lm, "kv_column_write_impl",
                                  column_write)],
             "decode_attention": [(transformer_lm, "decode_attention_impl",
                                   _attends),
                                  (decoder_lm, "decode_attention_impl",
                                   _attends)],
             "latent_decode": [(decoder_lm, "latent_decode_impl", latent)],
             "ssm_decode": [(decoder_lm, "ssm_decode_impl", state)]}
    undo = []
    try:
        for name in names:
            for module, attr, verdict in seams[name]:
                undo.append((module, attr, getattr(module, attr)))
                setattr(module, attr, verdict)
        yield asked
    finally:
        for module, attr, was in undo:
            setattr(module, attr, was)


def _chat():
    from deeplearning4j_tpu.models.transformer_lm import TransformerLMConfig

    return TransformerLMConfig(vocab_size=50257, max_length=1024,
                               d_model=1280, n_heads=20, n_layers=4,
                               compute_dtype="bfloat16")


def _decoder(**kw):
    from deeplearning4j_tpu.models.decoder_lm import DecoderConfig

    return DecoderConfig(**kw)


def _mimo():
    return _decoder(
        vocab_size=19072, d_model=4096, n_heads=64, head_dim=192,
        v_head_dim=128, rotary_dim=64,
        attn_kinds={"full": {"n_kv_heads": 4, "rope_theta": 1e7,
                             "window": None, "sink": False},
                    "window": {"n_kv_heads": 8, "rope_theta": 1e4,
                               "window": 128, "sink": True}},
        layers=[("full", "dense"), ("window", "experts"),
                ("window", "experts")],
        dense_width=16384, expert_width=2048, n_experts=256, top_k=8,
        experts_held=(0, 16), value_scale=0.707, max_length=1536)


def _deepseek():
    return _decoder(
        vocab_size=12800, d_model=5120, n_heads=128, head_dim=192,
        v_head_dim=128, rotary_dim=64,
        attn_kinds={"latent": {
            "rope_theta": 1e4,
            "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                             "beta_slow": 1, "mscale": 0.707,
                             "mscale_all_dim": 0.707,
                             "original_max_position_embeddings": 4096},
            "latent": {"q_rank": 1536, "kv_rank": 512}}},
        layers=[("latent", "dense"), ("latent", "experts")],
        dense_width=12288, expert_width=1536, n_experts=160, top_k=6,
        experts_held=(0, 20), norm_eps=1e-6, max_length=10240,
        routing={"n_group": 8, "topk_group": 3, "renormalise": False,
                 "scale": 16.0},
        shared_width=3072)


def _glm():
    latent = {"q_rank": 2048, "kv_rank": 512}
    return _decoder(
        vocab_size=19360, d_model=6144, n_heads=64, head_dim=256,
        v_head_dim=256, rotary_dim=64,
        attn_kinds={
            kind: {"rope_theta": 8e6, "latent": latent,
                   "index": {"heads": 32, "head_dim": 128, "topk": 2048,
                             "own": own}}
            for kind, own in (("indexed", True), ("shared", False))},
        layers=[("indexed", "dense")] + [("shared", "experts")] * 3
        + [("indexed", "experts")],
        dense_width=12288, expert_width=2048, n_experts=256, top_k=8,
        experts_held=(0, 16), norm_eps=1e-5, max_length=14336,
        routing={"scoring": "sigmoid", "scale": 2.5}, shared_width=2048)


def _granite():
    ssm = {"ssm": dict(n_heads=128, head_dim=64, d_state=128, n_groups=1,
                       d_conv=4, expand=2, chunk=256)}
    return _decoder(
        vocab_size=50176, d_model=4096, n_heads=32, head_dim=128,
        v_head_dim=128, rotary_dim=0,
        attn_kinds={"ssm": ssm, "attention": {"n_kv_heads": 8,
                                              "rope_theta": 1e4}},
        layers=[("ssm", "experts")] * 5 + [("attention", "experts")]
        + [("ssm", "experts")] * 4,
        dense_width=0, expert_width=768, n_experts=72, top_k=10,
        experts_held=(0, 36), shared_width=1536, max_length=4096,
        routing={"n_group": 1, "topk_group": 1, "renormalise": True},
        embedding_multiplier=12, residual_multiplier=0.22,
        attention_multiplier=0.0078125, logits_scaling=16, tied_head=True)


def _ouro():
    return _decoder(
        vocab_size=49152, d_model=2048, n_heads=16, head_dim=128,
        v_head_dim=128, rotary_dim=128,
        attn_kinds={"full": {"n_kv_heads": 16, "rope_theta": 1e6}},
        layers=[("full", "dense")] * 12, dense_width=5632, norm_eps=1e-6,
        max_length=896, passes=4, sandwich_norm=True, exit_gate=True)


def _falcon():
    ssm = dict(n_heads=32, head_dim=128, d_state=256, n_groups=2, d_conv=4,
               expand=2, chunk=128, d_inner=4096, in_multiplier=0.25,
               out_multiplier=0.08838834764831845,
               multipliers=[0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738])
    return _decoder(
        vocab_size=261120, d_model=5120, n_heads=20, head_dim=128,
        v_head_dim=128, rotary_dim=128,
        attn_kinds={"parallel": {
            "parallel": True, "n_kv_heads": 4, "rope_theta": 1e11,
            "key_multiplier": 0.011048543456039804,
            "out_multiplier": 0.0375, "ssm": ssm}},
        layers=[("parallel", "dense")] * 6, dense_width=21504,
        max_length=4096, embedding_multiplier=5.656854249492381,
        mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
        logits_scaling=128.0)


#: cell -> (its configuration at the cut depth, slots, the prefill bucket
#: compiled (None: the cell has a decode program only), the verdicts
#: steered: the kernels the chip's registry admits in that cell's programs
#: and ``tests/test_tpu_compile.py`` asserts on)
CELLS = {
    "chat": (_chat, 24, None, ("kv_column_write", "decode_attention")),
    "mimo": (_mimo, 64, 512, ("decode_attention", "grouped_experts")),
    "deepseek": (_deepseek, 48, 8192, ("latent_decode", "grouped_experts")),
    "glm": (_glm, 32, 14336, ("grouped_experts", "sparse_latent_decode")),
    "granite": (_granite, 64, 4096, ("ssm_decode", "decode_attention",
                                     "grouped_experts")),
    "ouro": (_ouro, 5, 256, ("kv_column_write", "decode_attention")),
    "falcon": (_falcon, 48, 4096, ("ssm_decode", "decode_attention",
                                   "kv_column_write")),
}


def build(cell, sharding, without=()):
    """``cell``'s programs as the engine builds them, lowered on shapes
    described for ``sharding``: a namespace of ``cfg``, ``slots``,
    ``length``, ``caches`` (shapes: K and V for the chat cell, a tuple a
    segment for a decoder cell), ``bucket``, ``asked`` (the keys the
    steered verdicts were asked at, filled as programs compile),
    ``decode()`` and ``prefill()`` -> the compiled programs (each compile
    made once). ``without``: the cell's verdicts NOT steered on (the
    programs where the registry declines those kernels)."""
    from deeplearning4j_tpu.serving import generate

    make, slots, bucket, verdicts = CELLS[cell]
    verdicts = tuple(v for v in verdicts if v not in without)
    cfg = make()
    length = cfg.max_length

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), tree)

    # a program takes its shapes from its arguments: the backend itself is
    # built small, so no slab is allocated here
    if cell == "chat":
        from deeplearning4j_tpu.models import transformer_lm as tlm

        be = generate._TransformerAheadBackend(
            SimpleNamespace(cfg=cfg), 2, length, None, lambda name: None)
        masters = jax.eval_shape(
            lambda: tlm.init_params(cfg, jax.random.PRNGKey(0)))
        # what the backend hands its programs: the weights' serving copy
        params = described(jax.eval_shape(
            lambda p: tlm.serving_copy(cfg, p), masters))
        slab = arg((cfg.n_layers, slots, cfg.n_heads,
                    cfg.d_model // cfg.n_heads, length), BF16)
        caches, operands = (slab, slab), (slab, slab)
    else:
        from deeplearning4j_tpu.models import decoder_lm

        be = generate._DecoderBackend(SimpleNamespace(cfg=cfg), 1, 128, [32],
                                      lambda name: None)
        params = described(jax.eval_shape(
            lambda: decoder_lm.init_params(cfg)))
        caches = described(jax.eval_shape(
            lambda: decoder_lm.init_cache(cfg, slots, length)))
        operands = (caches,)
    state = arg((slots + 1, 8), jnp.int32)
    asked = {name: [] for name in verdicts}

    def compiled(fn, *rest):
        with steered(*verdicts) as keys:
            out = fn.lower(params, *operands, state, *rest).compile()
        for name, at in keys.items():
            asked[name] += at
        return out

    return SimpleNamespace(
        cfg=cfg, slots=slots, length=length, caches=caches, asked=asked,
        bucket=bucket,
        decode=functools.cache(lambda: compiled(be._decode_fn)),
        prefill=functools.cache(lambda: compiled(
            be._prefill_fn, arg((8 + bucket,), jnp.int32))))


def _decoder_kinds():
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import decoder_kinds

    return decoder_kinds


def tiny(kind):
    """The decode and one prefill program of a tiny model of
    ``tests/decoder_kinds.py``, compiled for this process's backend."""
    from deeplearning4j_tpu.models import decoder_lm
    from deeplearning4j_tpu.serving import generate

    cfg = decoder_lm.DecoderConfig(**_decoder_kinds().program(kind))
    slots, bucket = 3, 32
    be = generate._DecoderBackend(SimpleNamespace(cfg=cfg), slots, 128,
                                  [bucket], lambda name: None)
    params = jax.eval_shape(lambda: decoder_lm.init_params(cfg))
    caches = jax.eval_shape(lambda: decoder_lm.init_cache(cfg, slots, 128))
    state = jax.ShapeDtypeStruct((slots + 1, 8), jnp.int32)
    req = jax.ShapeDtypeStruct((8 + bucket,), jnp.int32)
    return SimpleNamespace(
        bucket=bucket,
        decode=lambda: be._decode_fn.lower(params, caches, state).compile(),
        prefill=lambda: be._prefill_fn.lower(params, caches, state,
                                             req).compile())


def _kernel_body(match):
    """A Pallas kernel's payload in a ``tpu_custom_call`` (MLIR bytecode,
    base64) carries the files and lines of the Python stack that called
    it; in its place, the hash of the module printed without them."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    raw = base64.b64decode(match.group(1))
    if not raw.startswith(b"ML\xefR"):
        return match.group(0)
    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    with context:
        asm = ir.Module.parse(raw).operation.get_asm(enable_debug_info=False)
    return f'"body":"sha256:{hashlib.sha256(asm.encode()).hexdigest()}"'


def _bare(text):
    """HLO text without what moves with every edit of a source file: the
    instructions' ``metadata={...}``, the module's tables of files,
    functions, locations and stack frames, and the locations inside a
    Pallas kernel's payload."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:\d+ .*\n)*", "", text, flags=re.M)
    return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', _kernel_body, text)


def summary(name, compiled, keep=None):
    """One program's line: what a refactor must leave as it was."""
    text = compiled.as_text()
    bare = _bare(text)
    ops = collections.Counter(
        m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(", bare, re.M))
    calls = collections.Counter()
    for line in text.splitlines():
        target = re.search(r'custom_call_target="([^"]+)"', line)
        if target:
            # a Mosaic kernel by the scopes it runs under and its name
            kernel = re.search(r'op_name="([^"]+)"', line)
            mosaic = target.group(1) == "tpu_custom_call" and kernel
            calls[target.group(1)
                  + (f":{kernel.group(1)}" if mosaic else "")] += 1
    if keep:
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, f"{name}.hlo.txt"), "w") as f:
            f.write(bare)
    plan = compiled.memory_analysis()
    return {"program": name, "instructions": sum(ops.values()),
            "ops": dict(sorted(ops.items())),
            "custom_calls": dict(sorted(calls.items())),
            "temp_bytes": plan.temp_size_in_bytes,
            "argument_bytes": plan.argument_size_in_bytes,
            "output_bytes": plan.output_size_in_bytes,
            "alias_bytes": plan.alias_size_in_bytes,
            "hlo_sha256": hashlib.sha256(bare.encode()).hexdigest()[:16]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="the six tiny models, for this process's backend")
    ap.add_argument("--only", nargs="*", help="cells (or tiny kinds) to build")
    ap.add_argument("--out", help="also write the lines, as JSON, here")
    ap.add_argument("--keep", help="a directory for each program's HLO text "
                    "as it was hashed (to explain a hash that differs)")
    args = ap.parse_args()
    if args.tiny:
        built = {kind: tiny(kind)
                 for kind in args.only or _decoder_kinds().KINDS}
    else:
        from jax.experimental.compilation_cache import compilation_cache

        # an executable compiled for a described chip cannot be read back
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        chip = described_chip()
        built = {cell: build(cell, chip) for cell in args.only or CELLS}
    lines = []

    def report(name, compiled):
        line = summary(name, compiled, args.keep)
        lines.append(line)
        ops = hashlib.sha256(json.dumps(line["ops"]).encode()).hexdigest()[:8]
        print(f"{name:24s} {line['instructions']:6d} instructions (histogram "
              f"{ops}) calls {line['custom_calls']} temp {line['temp_bytes']} "
              f"arg {line['argument_bytes']} out {line['output_bytes']} alias "
              f"{line['alias_bytes']} hlo {line['hlo_sha256']}", flush=True)

    for name, programs in built.items():
        report(f"{name}.decode", programs.decode())
        if programs.bucket is not None:
            report(f"{name}.prefill@{programs.bucket}", programs.prefill())
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()

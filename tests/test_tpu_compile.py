"""The in-tree Pallas kernels, compiled by the installed TPU compiler for
a DESCRIBED v5e (no chip attached) at the main path's real widths.

Interpret-mode tests prove the kernel math; they cannot see what Mosaic
refuses (tiling, fast-memory budget). These compiles can, in about two
seconds each, so every later PR is guarded at no chip time. Nothing
runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or a ``parametrize`` argument, and never in a
child process: one process at a time may load the TPU library, and
under xdist every worker imports this file but only one runs it.
"""

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.ops.flash_attention import flash_attention
from deeplearning4j_tpu.nn.ops.fused_conv import conv3x3, pw_conv
from deeplearning4j_tpu.nn.ops.fused_lstm import fused_lstm_cell
from deeplearning4j_tpu.nn.ops.fused_update import fused_adam_apply
from deeplearning4j_tpu.nn.ops.int8_matmul import int8_matmul

BF16, F32 = jnp.bfloat16, jnp.float32

# the cells' serving programs at published widths and a cut depth are
# built in ONE place, which also prints them a line a program
_spec = importlib.util.spec_from_file_location(
    "decoder_programs", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "decoder_programs.py"))
programs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(programs)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache

    try:
        chip = programs.described_chip()
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip; keep
    # the cache out of these compiles so reruns stay silent
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield chip
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """AOT-compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _custom_calls(text: str) -> int:
    return text.count("tpu_custom_call")


def _grouped_products(built, segments, key):
    """The decode program takes its expert layers' grouped products through
    the repo's own kernel (``nn/ops/grouped_experts.py``, the verdict
    steered: one custom call a segment with expert layers, under
    ``moe_experts``, at ``key`` = (d, f, held experts, slots x top-k, window,
    tile, dtype), and no ``ragged-dot`` left); the prefill's rows are over the
    kernel's threshold and keep ``ragged_dot``."""
    from deeplearning4j_tpu.nn.ops import grouped_experts

    text = built.decode().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "moe_experts" in line]
    assert len(calls) == segments, calls
    assert all(grouped_experts.NAME in line for line in calls), calls
    assert "ragged-dot" not in text
    text = built.prefill().as_text()
    assert "ragged-dot" in text and not [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and grouped_experts.NAME in line]
    assert set(built.asked["grouped_experts"]) == {key}


# lm_train's attention instantiation: batch 16, 12 heads, T 512, head 64
_QKV = [((16, 12, 512, 64), BF16)] * 3


@pytest.mark.parametrize("with_seg", [False, True],
                         ids=["causal", "causal+segment_ids"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_lm_width(one_chip, grad, with_seg):
    def attn(q, k, v, *seg):
        return flash_attention(q, k, v, causal=True,
                               segment_ids=seg[0] if seg else None)

    def loss(q, k, v, *seg):
        return jnp.sum(attn(q, k, v, *seg).astype(F32) ** 2)

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else attn
    shapes = _QKV + ([((16, 512), jnp.int32)] if with_seg else [])
    text = _compile(fn, one_chip, *shapes)
    # forward is one kernel; backward adds the dq and dkv kernels
    assert _custom_calls(text) >= (3 if grad else 1)


def test_fused_lstm_cell_textgenlstm_width(one_chip):
    # zoo textgenlstm: GravesLSTM (peephole) n_in = n_out = 256
    B, n = 32, 256
    mat, vec = ((n, 4 * n), F32), ((n,), F32)
    text = _compile(
        fused_lstm_cell, one_chip,
        ((B, n), F32), ((B, n), F32), ((B, n), F32), mat, mat,
        ((4 * n,), F32), vec, vec, vec)
    assert _custom_calls(text) >= 1


@pytest.mark.parametrize("shape", [(768, 768), (768, 3072), (3072, 768),
                                   (32000, 768)],
                         ids=lambda s: "x".join(map(str, s)))
def test_fused_adam_apply_d768_block_shapes(one_chip, shape):
    fn = functools.partial(fused_adam_apply,
                           b1=0.9, b2=0.999, eps=1e-8)
    text = _compile(fn, one_chip, *([(shape, F32)] * 4), ((), F32))
    assert _custom_calls(text) >= 1


@pytest.mark.parametrize("kn", [(768, 3072), (3072, 768)],
                         ids=lambda s: "x".join(map(str, s)))
def test_int8_matmul_ffn_width(one_chip, kn):
    K, N = kn
    text = _compile(int8_matmul, one_chip,
                    ((128, K), F32), ((K, N), jnp.int8), ((N,), F32))
    assert _custom_calls(text) >= 1


@pytest.mark.parametrize("shape", [
    (36, 24, 20, 64, 1024), (192, 5, 16, 128, 896), (27, 48, 1, 576, 10240),
    (37, 3, 20, 64, 256), (6, 40, 4, 128, 4096)],
    ids=["chat", "ouro", "latent", "a-block-cut-short", "falcon"])
def test_kv_column_write_at_the_cells_slabs(one_chip, shape):
    """The cache's column write (``nn/ops/kv_column_write.py``) at the
    gpt2-large.chat cell's slab, the ouro-2.6b cell's and a latent slab as
    one head of 576, with the block the shapes choose (6, 4 and 14
    entries), the slab donated: one custom call, the slab aliased through
    it, and no copy as large as one entry's part of the slab."""
    import re

    from deeplearning4j_tpu.nn.ops import kv_column_write as kcw
    from deeplearning4j_tpu.nn.ops.ssm_decode import live_table

    entries, slots, heads, hd, _t = shape
    lb = kcw.entries_a_block(entries, heads, hd, 2)

    def put(slab, new, wp, active):
        return kcw.kv_column_write(slab, new, wp, live_table(active),
                                   lb=lb)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (shape, BF16), (shape[:4], BF16), ((slots,), jnp.int32),
        ((slots,), jnp.bool_))]
    compiled = jax.jit(put, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert _custom_calls(text) == 1 and kcw.NAME in text
    assert compiled.memory_analysis().alias_size_in_bytes == math.prod(shape) * 2
    copies = [dims for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
              if math.prod(map(int, dims.split(","))) >= math.prod(shape[1:])]
    assert not copies, copies


@pytest.mark.parametrize("shape", [
    (4, 24, 20, 1, 64, 64, 1024), (1, 64, 8, 4, 128, 128, 4096),
    (1, 64, 4, 16, 192, 128, 1536), (6, 40, 4, 5, 128, 128, 4096)],
    ids=["chat", "granite", "mimo", "falcon"])
def test_decode_attention_at_the_cells_slabs(one_chip, shape):
    """Decode attention over the live tiles (``nn/ops/decode_attention.py``)
    at the gpt2-large.chat cell's slabs (a cut depth), the
    granite-4.0-h-small-ep2 cell's and the mimo-v2.5-ep16 cell's, with the
    tile the slot length chooses, all layers' calls in one loop over the
    layer's index with the slabs closed over whole: one custom call in the
    loop's body and no copy as large as one layer's part of a slab."""
    import re

    from deeplearning4j_tpu.nn.ops import decode_attention as da

    layers, slots, hkv, grp, hd, vd, t = shape
    tile = da.tile_for(t)

    def attend(q, k_new, v_new, k_slab, v_slab, lengths):
        table = da.live_tiles(lengths, t, tile)

        def layer(carry, i):
            return carry, da.decode_attention(
                q, k_new, v_new, k_slab, v_slab, i, table, scale=0.125,
                tile=tile)

        return jax.lax.scan(layer, 0, jnp.arange(layers, dtype=jnp.int32))[1]

    text = _compile(
        attend, one_chip, ((slots, hkv, grp, hd), BF16),
        ((slots, hkv, hd), BF16), ((slots, hkv, vd), BF16),
        ((layers, slots, hkv, hd, t), BF16),
        ((layers, slots, hkv, vd, t), BF16), ((slots,), jnp.int32))
    assert tile == 128 and _custom_calls(text) == 1 and da.NAME in text
    copies = [dims for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
              if math.prod(map(int, dims.split(","))) >= slots * hkv * vd * t]
    assert not copies, copies


@pytest.mark.parametrize("shape", [
    (4096, 768, 36, 5, 640), (4096, 2048, 16, 2, 512), (5120, 1536, 20, 1, 288),
    (6144, 2048, 16, 3, 256)], ids=["granite", "mimo", "deepseek", "glm"])
def test_grouped_experts_at_the_cells_keys(one_chip, shape):
    """The grouped SwiGLU products (``nn/ops/grouped_experts.py``) at the four
    expert cells' decode keys (d, f, held experts, layers of a segment, slots
    x top-k), with the window and tile the rule chooses, all layers' calls in
    one loop over the layer's index with the stacks closed over whole: one
    custom call in the loop's body and no copy as large as one expert's
    matrix (Mosaic takes 12-19 MB of weight tiles a grid step, double-
    buffered, beside the rows and the float32 output)."""
    import re

    from deeplearning4j_tpu.nn.ops import grouped_experts as ge

    d, f, count, layers, m = shape
    window, tile = ge.plan(m, d, f, BF16)

    def products(rows, eg, eu, ed, sizes):
        def layer(total, x):
            i, s = x
            return total + ge.grouped_experts(rows, eg, eu, ed, s, i * count,
                                              window=window, tile=tile), None

        return jax.lax.scan(
            layer, jnp.zeros((m, d), F32),
            (jnp.arange(layers, dtype=jnp.int32), sizes))[0]

    groups = layers * count
    text = _compile(
        products, one_chip, ((m, d), BF16), ((groups, d, f), BF16),
        ((groups, d, f), BF16), ((groups, f, d), BF16),
        ((layers, count), jnp.int32))
    assert (window, tile) == (32, 768 if f == 768 else 512)
    assert _custom_calls(text) == 1 and ge.NAME in text
    copies = [dims for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
              if math.prod(map(int, dims.split(","))) >= d * f]
    assert not copies, copies


def test_sparse_latent_decode_at_the_cells_key(one_chip):
    """A latent layer's attention over a selection
    (``nn/ops/sparse_latent_decode.py``) at the glm-5.2-ep16 cell's key (64
    heads over rows of 640, 32 slots of 14,336, 2,048 kept), with the tile
    the rule chooses, all layers' calls in one loop over the layer's index
    with the position-major slab closed over whole and the walk made once:
    one custom call in the loop's body and no copy as large as one slot's
    part of the slab."""
    import re

    from deeplearning4j_tpu.nn.ops import sparse_latent_decode as sld

    layers, slots, heads, width, t, topk = 3, 32, 64, 640, 14336, 2048
    tile = sld.plan(t, topk)

    def attend(q, new, slab, lengths, bias, own_in):
        walk = sld.live_walk(lengths, t, tile)

        def layer(carry, i):
            return carry, sld.sparse_latent_decode(
                q, new, slab, i, lengths, bias, own_in, walk, scale=0.0625,
                kv_rank=512, tile=tile)

        return jax.lax.scan(layer, 0, jnp.arange(layers, dtype=jnp.int32))[1]

    text = _compile(
        attend, one_chip, ((slots, heads, width), BF16), ((slots, width), BF16),
        ((layers, slots, t, width), BF16), ((slots,), jnp.int32),
        ((slots, 1, t), F32), ((slots,), jnp.bool_))
    assert tile == sld.TILE and _custom_calls(text) == 1 and sld.NAME in text
    copies = [dims for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
              if math.prod(map(int, dims.split(","))) >= t * width]
    assert not copies, copies


def _conv_loss(conv):
    def loss(x, s, t, w):
        y, st = conv(x, s, t, w, True)
        return jnp.sum(y.astype(F32)) + jnp.sum(st)

    return loss


# ResNet-50 stage 2 at batch 128: 28x28 maps, bottleneck width 128,
# block width 512 — the 1x1 reduce and the 3x3
_PW = [((128 * 28 * 28, 512), BF16), ((512,), F32), ((512,), F32),
       ((512, 128), BF16)]
_C3 = [((128, 28, 28, 128), BF16), ((128,), F32), ((128,), F32),
       ((3, 3, 128, 128), BF16)]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("kernel,shapes", [
    (pw_conv, _PW), (conv3x3, _C3),
], ids=["pointwise", "conv3x3"])
def test_fused_conv_resnet50_stage(one_chip, kernel, shapes, grad):
    loss = _conv_loss(kernel)
    fn = jax.grad(loss, argnums=(0, 3)) if grad else loss
    text = _compile(fn, one_chip, *shapes)
    # backward adds the dx and dw kernels to the forward one
    assert _custom_calls(text) >= (3 if grad else 1)


def _always_run(text: str) -> dict:
    """{computation: its instruction lines} of the computations of an
    HLO module that run whenever the program does: reachable from ENTRY
    by ``calls`` / ``to_apply`` / ``body`` / ``condition``, but not
    through the branches of a ``conditional``."""
    import re

    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    assert entry is not None
    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            line = re.sub(r"branch_computations=\{[^}]*\}"
                          r"|(?:true|false)_computation=%?[\w.\-]+", "", line)
            todo += [c for c in re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
                if c in comps]
    return {name: comps[name] for name in seen}


def _sampler_work_outside_a_conditional(text: str, slots: int,
                                        vocab: int) -> list:
    """The ``sort`` and ``gather`` instructions with a (slots, vocab)
    result (the sampler's: a row of logits a slot) that the program runs
    whatever the slots' policies are. The in-graph sampler keeps them in
    the branch of a ``conditional`` that a batch of greedy slots does
    not take (``transformer_lm._sample_branches``)."""
    import re

    logits = re.compile(rf"\w+\[{slots},{vocab}\]")
    found = []
    for name, lines in _always_run(text).items():
        for line in lines:
            op = re.search(r"^\s*(?:ROOT )?%?(\S+) = (.*?) (sort|gather)\(",
                           line)
            if op and logits.search(op.group(2)):
                found.append((name, op.group(1), op.group(3)))
    return found


@pytest.fixture(scope="module")
def chat_decode(one_chip):
    """The serving engine's decode program (``_decode``) as the chat
    cell runs it (``_TransformerAheadBackend``: K = 1, no prefix cache,
    the slots' inputs one int32 array on the device) at the
    gpt2-large.chat cell's widths (d 1280, 20 heads, 24 slots x 1024,
    bf16) and a cut depth (4 layers; ~35 s of compile), lowered on what
    the backend hands it: the shapes of the weights' serving copy, the
    registry's verdicts steered as the chip's probes give them.
    Returns (compiled, cfg, slots, the slab's shape)."""
    built = programs.build("chat", one_chip)
    return built.decode(), built.cfg, built.slots, built.caches[0].shape


def test_decode_program_keeps_the_kv_slab_in_place(chat_decode):
    """The slab is read where it lies and written in place, so the plan
    holds no temporary near a layer's slice and no ``copy`` of one. With
    the cache written inside the layer loop this program planned 1.29 GB
    of temporaries and six such copies: 59 of a decode step's 113 ms on
    the chip."""
    import re

    compiled, cfg, S, slab_shape = chat_decode
    layer_slice = math.prod(slab_shape[1:])  # elements of one layer's K
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 2 * layer_slice * 2, temporaries  # 126 MB
    copies = [
        (name, dims) for name, dims in re.findall(
            r"%(\S+) = \w+\[([\d,]+)\]\S* copy\(", compiled.as_text())
        if math.prod(map(int, dims.split(","))) >= layer_slice]
    assert not copies, copies
    # the after-loop write: one call of the column kernel a slab, the
    # slabs aliased through the program (since PR 43; 48 updates before)
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "kv_write" in line]
    assert len(kernels) == 2 and all("kv_column_write" in k for k in kernels)
    assert not [line for line in text.splitlines()
                if "dynamic-update-slice(" in line and "kv_write" in line]
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        2 * math.prod(slab_shape) * 2
    # the layers attend through the live-tile kernel (since PR 44; two
    # whole-slab einsums a layer before): one call in the layer loop's
    # body, under the block's ``attn`` scope, and no score temporary of
    # slots x heads x T
    attends = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "decode_attention" in line]
    assert len(attends) == 1 and "/attn/" in attends[0], attends
    assert not re.findall(rf"f32\[{S},{cfg.n_heads},1,{slab_shape[-1]}\]", text)
    # all 24 slots greedy is the common step: its sorts and gathers over
    # 24 x 50257 logits (27 of 49 ms a step on the chip) wait in a branch
    assert " conditional(" in text and " sort(" in text
    assert not _sampler_work_outside_a_conditional(text, S,
                                                   cfg.vocab_size)


def test_decode_program_casts_no_weights(chat_decode):
    """Handed the serving copy, the decode program reads its block
    matrices and head as bfloat16 arguments and re-makes none of them:
    in the computations that always run no ``convert`` has a bfloat16
    result of a stacked block matrix's shape or the head's, and no
    float32 argument has one. On float32 masters it made six such
    converts every step, 0.94 + 0.47 GB read and written for ``W1`` and
    ``W2`` alone at 36 layers: 6.3 of a decode step's 22.4 ms on the
    chip."""
    import re

    compiled, cfg, _S, _slab = chat_decode
    L, d = cfg.n_layers, cfg.d_model
    matrices = {(L, d, d), (L, d, cfg.mlp_ratio * d),
                (L, cfg.mlp_ratio * d, d), (d, cfg.vocab_size)}

    def shape(dims):
        return tuple(map(int, dims.split(",")))

    text = compiled.as_text()
    converts = [
        (name, op.group(1), op.group(2))
        for name, lines in _always_run(text).items()
        for op in (re.search(
            r"^\s*(?:ROOT )?%?(\S+) = bf16\[([\d,]+)\]\S* convert\(", line)
            for line in lines)
        if op and shape(op.group(2)) in matrices]
    assert not converts, converts
    arguments = {(dtype, shape(dims)) for dtype, dims in re.findall(
        r" = (\w+)\[([\d,]+)\]\S* parameter\(\d+\)", text)}
    assert {("bf16", m) for m in matrices} <= arguments
    assert not {("f32", m) for m in matrices} & arguments


def test_decoder_decode_program_compiles_at_published_widths(one_chip):
    """``DecoderLM``'s decode program as the engine builds it, at the
    mimo-v2.5-ep16 cell's widths (hidden 4096, 64 heads of 192/128, 4 and
    8 key/value heads, 16 of 256 experts of 2048, 64 slots x 1536,
    bfloat16) and a cut depth (a dense full layer and two window expert
    layers). What a CPU run cannot show: the grouped expert product is a
    Mosaic kernel on the TPU (the repo's own in decode, ``_grouped_products``;
    XLA's ``ragged_dot`` in the prefill) and refuses the package-wide
    "highest" precision (both pin DEFAULT for bfloat16 operands);
    the rings and the full layer's slab stay in place (no temporary as
    large as a slab, no copy of one), the full layer attending through the
    live-tile kernel (``nn/ops/decode_attention.py``, the verdict steered:
    16 query heads a key head, keys of 192 and values of 128)."""
    import re

    built = programs.build("mimo", one_chip)
    cfg, S, caches = built.cfg, built.slots, built.caches
    compiled = built.decode()
    text = compiled.as_text()
    _grouped_products(built, 1, (4096, 2048, 16, S * 8, 32, 512, "bfloat16"))
    attends = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "attn_full" in line]
    assert len(attends) == 1 and "decode_attention" in attends[0], attends
    ring = math.prod(caches[1][1].shape)  # the smallest slab: a ring's V
    # 275 MB planned: a 100 MB relayout of Wq, the temporaries of the
    # sampler's filtering branch; the four slabs are 335 MB, and a
    # second copy of each would pass this
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6
    assert " conditional(" in text and " sort(" in text
    assert not _sampler_work_outside_a_conditional(text, S,
                                                   cfg.vocab_size)
    copies = [
        (name, dims) for name, dims in re.findall(
            r"%(\S+) = bf16\[([\d,]+)\]\S* copy\(", text)
        if len(dims.split(",")) == 5
        and math.prod(map(int, dims.split(","))) >= ring]
    assert not copies, copies


def test_latent_decoder_programs_compile_at_published_widths(one_chip):
    """``DecoderLM`` with latent attention as the engine builds its
    programs, at the deepseek-v2-ep8 cell's widths (hidden 5120, 128 heads
    of 128 + 64 / 128 over a 1536-wide query and a 512 + 64-wide key/value
    latent, 20 of 160 experts of 1536 by group-limited routing, two shared
    experts, 48 slots x 10,240, bfloat16) and a cut depth (the dense layer
    and one expert layer). What a CPU run cannot show: the decode program
    reads the cache through the length-aware kernel
    (``nn/ops/latent_decode.py``: Mosaic takes it at 128 heads x 576 x
    10,240; the registry's verdict is steered here, since this process's
    backend is the CPU) and keeps the latent slab in place (no copy of
    one, no per-head key or value over the cache, no float32 scores of a
    whole slab), and the prefill at the 8,192 bucket attends by blocks, so
    its plan stays far under the 4.7 GB that weights and cache leave (128
    heads x 8,192^2 float32 scores in one piece would be 34 GB)."""
    import re

    from deeplearning4j_tpu.nn.ops import latent_decode

    built = programs.build("deepseek", one_chip)
    S, T, caches = built.slots, built.length, built.caches
    slab = math.prod(caches[0][0].shape)        # one layer's: 283 M values
    assert [tuple(c.shape for c in seg) for seg in caches] == [
        ((1, S, 576, T),), ((1, S, 576, T),)]

    def slab_copies(text):
        return [(name, dims) for name, dims in re.findall(
            r"%(\S+) = bf16\[([\d,]+)\]\S* copy\(", text)
            if math.prod(map(int, dims.split(","))) >= slab // 2]

    decode = built.decode()
    text = decode.as_text()
    assert not slab_copies(text)
    _grouped_products(built, 1, (5120, 1536, 20, S * 6, 32, 512, "bfloat16"))
    assert set(built.asked["latent_decode"]) == {(128, 576, T, "bfloat16", 512)}
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "attn_latent_core" in line]
    assert len(kernels) == 2, kernels                   # one a segment
    assert all(latent_decode.NAME in line for line in kernels)
    # the einsum path planned 0.27 GB: one layer's float32 scores (48 x 128
    # x 10,240 x 4 B = 252 MB) and small change; a head's keys or values
    # over the cache would be 48 x 128 x 128 x 10,240 x 2 B = 16 GB
    assert not re.search(rf"f32\[{S},(1,)?128,(1,)?{T}\]", text)
    assert decode.memory_analysis().temp_size_in_bytes < 100e6
    prefill = built.prefill()
    assert built.bucket == 8192 and not slab_copies(prefill.as_text())
    # 2.1 GB planned at six layers (the plan is a layer's, not the stack's)
    assert prefill.memory_analysis().temp_size_in_bytes < 3.0e9


@pytest.mark.parametrize("core", ["gathered", "kernel"])
def test_sparse_latent_decoder_programs_compile_at_published_widths(one_chip,
                                                                    capsys,
                                                                    core):
    """``DecoderLM`` with latent attention over an indexer's selection as
    the engine builds its programs, at the glm-5.2-ep16 cell's widths and
    its whole cut (hidden 6144, 64 heads of 192 + 64 / 256 over a 2048-wide
    query and a 512 + 64-wide key/value latent, 32 indexer heads of 128
    that keep 2,048 positions, 16 of 256 sigmoid-routed experts of 2048
    with a shared expert; a dense layer that owns the indexer, three expert
    layers that share its selection, an expert layer that owns one; 32
    slots x 14,336, bfloat16). What a CPU run cannot show: the decode
    program gathers the chosen rows from the position-major slabs WHERE
    THEY LIE (at rows of 576 values the compiler copied a whole slab,
    padded to 640, before every gather: 528 MB a layer and step; at 640
    there is none, and the slabs that go through the three-layer scan as
    its carry are not copied for the after-loop write either), plans no
    float32 score tensor of a whole slab, and the prefill at the longest
    bucket attends and selects by blocks, so its plan stays under the
    4.8 GB that weights and cache leave. The plans are printed. With the
    verdict of ``nn/ops/sparse_latent_decode.py`` steered on (``kernel``:
    what the chip's probe gives; this process's backend is the CPU) the
    decode program has one custom call a latent segment under
    ``attn_sparse_core``, no gather there and no larger a plan; the prefill
    program is the same one and is compiled once, where the registry
    declines (``gathered``)."""
    import re

    from deeplearning4j_tpu.nn.ops import sparse_latent_decode as sld

    built = programs.build(
        "glm", one_chip, without=(sld.NAME,) if core == "gathered" else ())
    S, T, caches = built.slots, built.length, built.caches
    assert [tuple(c.shape for c in seg) for seg in caches] == [
        ((1, S, T, 640), (1, S, T, 128)), ((3, S, T, 640),),
        ((1, S, T, 640), (1, S, T, 128))]
    keys = math.prod(caches[0][1].shape)        # the smallest slab: 59 M values

    def slab_copies(text):
        # a cache slab has four dimensions and slots second; what is left
        # under that size are three re-laid weights a layer (``Wqb``,
        # ``Wuv``, ``Iq``: 100 MB a layer and step, PERF.md section 7)
        return [(name, dims) for name, dims in re.findall(
            r"%(\S+) = bf16\[([\d,]+)\]\S* copy\(", text)
            if math.prod(map(int, dims.split(","))) >= keys // 2
            and dims.split(",")[1:2] == [str(S)]]

    decode = built.decode()
    text = decode.as_text()
    assert not slab_copies(text), slab_copies(text)
    _grouped_products(built, 2, (6144, 2048, 16, S * 8, 32, 512, "bfloat16"))
    gathers = [line for line in text.splitlines()
               if " gather(" in line and "attn_sparse_core" in line]
    plan = decode.memory_analysis()
    if core == "kernel":
        assert set(built.asked[sld.NAME]) == {
            (64, 640, T, 2048, sld.TILE, "bfloat16")}
        kernels = [line for line in text.splitlines()
                   if "tpu_custom_call" in line and "attn_sparse_core" in line]
        assert len(kernels) == 3, kernels               # one a segment
        assert all(sld.NAME in line for line in kernels) and not gathers
        # the gathered rows (84 MB) and their float32 scores leave the plan
        assert plan.temp_size_in_bytes < 40e6           # the gathered: 47.2 MB
        with capsys.disabled():
            print(f"\nglm-5.2-ep16 decode through {sld.NAME}: temporaries "
                  f"{plan.temp_size_in_bytes / 1e9:.3f} GB")
        return
    assert gathers and all(f"bf16[{S},2048,640]" in g for g in gathers), gathers
    # 31 MB read: the indexer's scores of a slot's whole key slab, 32 heads
    # in float32, would be 58.7 MB a layer; they are fused into the sum over
    # heads and never planned
    assert plan.temp_size_in_bytes < 200e6
    prefill = built.prefill()
    assert built.bucket == T and not slab_copies(prefill.as_text())
    longest = prefill.memory_analysis()
    # 2.65 GB read: the expanded keys and values of 14,336 positions (0.47
    # GB each), the selection's mask (0.21 GB) and the blocks' scores
    assert longest.temp_size_in_bytes < 3.3e9
    with capsys.disabled():
        for name, m in (("decode", plan), ("prefill@14336", longest)):
            print(f"\nglm-5.2-ep16 {name}: arguments "
                  f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
                  f"{m.temp_size_in_bytes / 1e9:.3f} GB, code "
                  f"{m.generated_code_size_in_bytes / 1e6:.1f} MB")


def test_hybrid_decoder_programs_compile_at_published_widths(one_chip):
    """``DecoderLM`` with state-space layers as the engine builds its
    programs, at the granite-4.0-h-small-ep2 cell's widths and FULL cut
    depth (five Mamba-2 layers of 128 heads x 64 x 128 state over 8,448
    convolved channels, one NoPE attention layer of 32 / 8 heads of 128,
    four more Mamba-2 layers; 36 of 72 top-10 experts of 768 and a shared
    expert of 1,536 in every layer; half of the vocabulary, tied; 64 slots
    x 4,096; bfloat16 with a float32 state). What a CPU run cannot show:
    weights and cache are 13.0 GB of arguments; the decode program takes a
    layer's state through the live-slot kernel (``nn/ops/ssm_decode.py``:
    Mosaic takes it at 128 x 8,192 a slot in blocks of 2 MB; the
    registry's verdict is steered here, since this process's backend is the
    CPU), one custom call a segment under ``ssm_scan`` with the segment's
    states aliased through it, and so updates the 2.4 GB of state IN PLACE
    as the layer loop's carry (its plan holds no temporary of a layer's
    state over the slots, 268 MB, let alone a segment's; the caches come
    back aliased to their arguments; nothing copies a layer's state or the
    attention slab; no fusion selects over a layer's state); the input
    projection reads its stacked leaf where it lies (no slice or re-layout of a
    16,768-wide matrix); and the largest prefill, the 4,096 bucket the
    engine appends, attends by blocks (32 heads x 4,096^2 float32 scores in
    one piece would be 2.1 GB), so plan + arguments stay under the chip's
    15.75 GB."""
    import re

    from deeplearning4j_tpu.nn.ops import ssm_decode

    built = programs.build("granite", one_chip)
    cfg, S, T, caches = built.cfg, built.slots, built.length, built.caches
    assert [tuple((c.shape, c.dtype.name) for c in seg) for seg in caches] == [
        (((5, S, 128, 8192), "float32"), ((5, S, 8448, 3), "bfloat16")),
        (((1, S, 8, 128, T), "bfloat16"),) * 2,
        (((4, S, 128, 8192), "float32"), ((4, S, 8448, 3), "bfloat16"))]
    cache_bytes = sum(math.prod(c.shape) * c.dtype.itemsize
                      for seg in caches for c in seg)
    one_layer_state = S * 128 * 64 * 128                    # 67 M values
    slab = S * 8 * 128 * T                                  # 268 M values

    def big_copies(text):
        found = []
        for dtype, dims in re.findall(r"= (\w+)\[([\d,]+)\]\S* copy\(", text):
            size = math.prod(map(int, dims.split(",")))
            # a cache has four or five dimensions; the prefill re-lays
            # (1, 4096, 8192) activations out, three a layer
            if (len(dims.split(",")) >= 4
                    and size >= min(one_layer_state, slab) // 2):
                found.append((dtype, dims))
        return found

    decode = built.decode()
    text, plan = decode.as_text(), decode.memory_analysis()
    assert 12.9e9 < plan.argument_size_in_bytes < 13.1e9
    assert not big_copies(text)
    _grouped_products(built, 3, (4096, 768, 36, S * 10, 32, 768, "bfloat16"))
    assert set(built.asked["ssm_decode"]) == {(128, 64, 128, 1, S, "float32")}
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "ssm_scan" in line]
    assert len(kernels) == 2, kernels                   # one a segment
    assert all(ssm_decode.NAME in line for line in kernels)
    assert not re.search(rf"f32\[(\d+,)?{S},128,8192\]\S* (fusion|select)\(",
                         text)
    attends = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "attn_full" in line]
    assert len(attends) == 1 and "decode_attention" in attends[0], attends
    # 32 MB planned; one layer's state over the slots would be 268 MB
    assert plan.temp_size_in_bytes < 0.1e9
    assert abs(plan.alias_size_in_bytes - cache_bytes) < 1e6
    assert not re.search(r"= bf16\[(\d+,)?4096,16768\]\S* (copy|slice)\(", text)
    assert " conditional(" in text
    assert not _sampler_work_outside_a_conditional(text, S, cfg.vocab_size)

    prefill = built.prefill()
    assert built.bucket == T
    text, plan = prefill.as_text(), prefill.memory_analysis()
    assert not big_copies(text)
    assert abs(plan.alias_size_in_bytes - cache_bytes) < 1e6
    # 1.6 GB planned (2.2 GB before a bucket this long attended by blocks)
    assert plan.temp_size_in_bytes < 2.0e9
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes < 15.75e9
    assert not re.search(rf"f32\[(1,)?32,(1,)?{T},(1,)?{T}\]", text)


def test_compiled_for_the_described_chip(one_chip):
    """The guard on the guard: the sharding these tests compile for is a
    TPU v5e, not the CPU the suite runs on."""
    (dev,) = one_chip.device_set
    assert dev.platform == "tpu"
    assert "v5" in dev.device_kind.lower()


def test_looped_decoder_decode_program_compiles_at_published_widths(one_chip):
    """``DecoderLM`` with a stack that runs four times a token as the
    engine builds its decode program, at the ouro-2.6b cell's widths
    (hidden 2048, 16 heads of 128 with as many key/value heads, MLP 5632,
    49,152 ids, 5 slots x 896, bfloat16, sandwich norms, the exit gate) and
    a cut depth (12 of the 48 layers: 48 cache entries a position). What a
    CPU run cannot show: the passes are one loop around the layers' loop,
    and no pass's part of a slab is cut out on the way into it: no
    temporary as large as ONE pass's slab (a quarter of K or of V), no copy
    of a slab or of a pass's part of one. What the plan does hold is two
    relayouts of a weight stack that the compiler hoists out of the pass
    loop (``Wk`` to contraction-minor, ``Wq`` by head: 101 MB each here;
    a one-pass program of this block makes them a layer at a time)."""
    import re

    from deeplearning4j_tpu.nn.ops import kv_column_write as kcw

    built = programs.build("ouro", one_chip)
    S, T, caches = built.slots, built.length, built.caches
    L, R = built.cfg.n_layers, built.cfg.passes
    assert caches[0][0].shape == (R * L, S, 16, 128, T)
    compiled = built.decode()
    text = compiled.as_text()
    a_pass = math.prod(caches[0][0].shape) // R        # elements
    # 0.10 GB planned (the relayouts); a pass's part of K is 0.55 GB
    assert compiled.memory_analysis().temp_size_in_bytes < a_pass * 2 * 0.6
    copies = [
        (name, dims) for name, dims in re.findall(
            r"%(\S+) = bf16\[([\d,]+)\]\S* copy\(", text)
        if math.prod(map(int, dims.split(","))) >= a_pass // 2]
    assert not copies, copies
    # one loop over the passes around one over the layers: the layer body
    # is compiled once, whatever the passes
    assert text.count(" while(") == 2
    # the after-loop write: one call of the column kernel a slab (since
    # PR 43; an update a slot and slab before)
    assert set(built.asked["kv_column_write"]) == {
        (R * L, S, 16, 128, T, "bfloat16")}
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "kv_write" in line]
    assert len(kernels) == 2 and all(kcw.NAME in k for k in kernels)
    assert not [line for line in text.splitlines()
                if "dynamic-update-slice(" in line and "kv_write" in line]
    # the layers attend through the live-tile kernel (since PR 44), the
    # slabs whole from the pass loop's carry: one call in the layer body
    attends = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "attn_full" in line]
    assert len(attends) == 1 and "decode_attention" in attends[0], attends


def test_parallel_decoder_programs_compile_at_published_widths(one_chip):
    """``DecoderLM`` with PARALLEL blocks as the engine builds its programs,
    at the falcon-h1-34b-l6 cell's widths and FULL cut depth (six blocks of
    20 / 4 attention heads of 128 beside a Mamba-2 mixer of 32 heads x 128
    over a state of 256 in two groups, inner width 4,096, MLP 21,504, the
    whole vocabulary of 261,120, 48 slots x 4,096, bfloat16 with a float32
    state). What a CPU run cannot show: weights and cache are 14.1 GB of
    arguments; ONE loop over the six layers takes, a layer, the live-tile
    attention kernel under ``attn_full`` (five query heads a key head) and
    the live-slot state kernel under ``ssm_scan`` (a state of 256: Mosaic
    takes it in blocks of 256 x 2,048), K and V closed over whole while
    the state and the tail are the loop's carry, updated IN PLACE (no
    temporary or copy of a layer's state over the slots, 201 MB, or of a
    layer's part of a slab); after the loop one column-write call a slab;
    the norm and the join run under ``mixer_join``; and the largest
    prefill, the 4,096 bucket the engine appends, stays with the arguments
    under the chip's 15.75 GB."""
    import re

    from deeplearning4j_tpu.nn.ops import kv_column_write as kcw
    from deeplearning4j_tpu.nn.ops import ssm_decode

    built = programs.build("falcon", one_chip)
    S, T, caches = built.slots, built.length, built.caches
    assert [tuple((c.shape, c.dtype.name) for c in seg) for seg in caches] == [
        (((6, S, 4, 128, T), "bfloat16"),) * 2
        + (((6, S, 256, 4096), "float32"), ((6, S, 5120, 3), "bfloat16"))]
    cache_bytes = sum(math.prod(c.shape) * c.dtype.itemsize
                      for seg in caches for c in seg)
    one_layer_state = S * 256 * 4096                        # 50 M values
    one_layer_slab = S * 4 * 128 * T                        # 101 M values

    def big_copies(text):
        return [(dtype, dims) for dtype, dims in re.findall(
            r"= (\w+)\[([\d,]+)\]\S* copy\(", text)
            if len(dims.split(",")) >= 4 and math.prod(
                map(int, dims.split(","))) >= one_layer_state // 2]

    decode = built.decode()
    text, plan = decode.as_text(), decode.memory_analysis()
    assert 14.0e9 < plan.argument_size_in_bytes < 14.3e9
    assert not big_copies(text) and text.count(" while(") == 1
    assert set(built.asked["ssm_decode"]) == {(32, 128, 256, 2, S, "float32")}
    assert set(built.asked["kv_column_write"]) == {(6, S, 4, 128, T, "bfloat16")}
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    states = [line for line in calls if "ssm_scan" in line]
    assert len(states) == 1 and ssm_decode.NAME in states[0], states
    attends = [line for line in calls if "attn_full" in line]
    assert len(attends) == 1 and "decode_attention" in attends[0], attends
    writes = [line for line in calls if "kv_write" in line]
    assert len(writes) == 2 and all(kcw.NAME in w for w in writes), writes
    assert "mixer_join" in text
    assert not re.search(rf"f32\[(\d+,)?{S},256,4096\]\S* (fusion|select)\(", text)
    # 0.20 GB planned (the logits over the slots and the sampler's part of
    # them); one layer's state over the slots alone is 0.20 GB and one
    # layer's K as much, and neither is among the temporaries
    assert plan.temp_size_in_bytes < 0.25e9 < one_layer_slab * 2 * 2
    assert abs(plan.alias_size_in_bytes - cache_bytes) < 1e6

    prefill = built.prefill()
    assert built.bucket == T
    text, plan = prefill.as_text(), prefill.memory_analysis()
    assert not big_copies(text)
    assert abs(plan.alias_size_in_bytes - cache_bytes) < 1e6
    # 0.67 GB planned: the bucket attends by blocks (20 heads x 4,096^2
    # float32 scores in one piece would be 1.3 GB)
    assert plan.temp_size_in_bytes < 1.0e9
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes < 15.0e9
    assert not re.search(rf"f32\[(1,)?20,(1,)?{T},(1,)?{T}\]", text)

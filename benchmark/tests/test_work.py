"""The FLOP and byte functions against hand counts. Run by hand:

    python3 -m pytest benchmark/tests/test_work.py -q
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import work  # noqa: E402

SMALL = {"n_embd": 768, "n_layer": 12, "vocab_size": 50257, "n_positions": 1024}
LARGE = {"n_embd": 1280, "n_layer": 36, "vocab_size": 50257, "n_positions": 1024}


def test_gpt2_small_train_flops_per_token():
    # per layer: Q, K, V, O = 4 d^2 MACs, MLP = 8 d^2 MACs -> 24 d^2 FLOPs;
    # causal attention: QK^T and PV, T*d MACs each, halved -> 2 T d FLOPs
    per_layer = 24 * 768 ** 2 + 2 * 1024 * 768
    forward = 12 * per_layer + 2 * 768 * 50257
    assert work.lm_forward_flops_per_token(SMALL, 1024) == forward == 265_938_432
    assert work.lm_train_flops_per_token(SMALL, 1024) == 3 * forward
    assert abs(work.lm_train_flops_per_token(SMALL, 1024) / 1e9 - 0.80) < 0.005


def test_causal_attention_is_counted_at_half():
    full = 12 * (24 * 768 ** 2 + 4 * 1024 * 768) + 2 * 768 * 50257  # bench.py's count
    assert work.lm_forward_flops_per_token(SMALL, 1024) == full - 12 * 2 * 1024 * 768


def test_param_counts():
    # block: 4 d^2 + d (out bias) + 8 d^2 + 4d + d (MLP) + 4 d (two norms)
    d = 768
    block = 12 * d * d + 10 * d
    assert work.lm_param_count(SMALL) == 50257 * d + 1024 * d + 12 * block + 2 * d + d * 50257
    assert round(work.lm_param_count(SMALL) / 1e6, 1) == 163.0
    assert round(work.lm_param_count(LARGE) / 1e6, 1) == 838.2


def test_gpt2_large_decode_step_bytes():
    # weights a step must read once: all but the two tables, float32
    weights = (work.lm_param_count(LARGE) - (50257 + 1024) * 1280) * 4
    assert work.lm_decode_weight_bytes(LARGE, 4) == weights
    assert round(weights / 1e9, 2) == 3.09
    # keys and values of one position, 36 layers, bfloat16
    assert work.lm_kv_bytes_per_position(LARGE, 2) == 2 * 36 * 1280 * 2 == 184_320
    # 32 slots, each 200 positions deep
    live = 32 * 200
    assert work.lm_decode_step_bytes(LARGE, live, 4, 2) == weights + live * 184_320


def test_resnet50_macs():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from reference import resnet50

    # He et al. 2015 Table 1 gives 3.8e9 FLOPs (multiply-adds) for the 50-layer net
    assert 3.8e9 < resnet50.forward_macs() < 4.2e9
    # by hand: the stem, and the first bottleneck of the first stage with its projection
    stem = 112 * 112 * 64 * 7 * 7 * 3
    s0b0 = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert resnet50.forward_macs(224, 224, 1000) > stem + s0b0
    assert stem == 118_013_952 and s0b0 == 231_211_008
    cfg = {"image_size": 224, "num_classes": 1000}
    assert resnet50.train_flops_per_item(cfg) == 6 * resnet50.forward_macs() == 23_147_839_488


def test_a_share_cannot_pass_100_by_construction():
    # the least time the chip could take is required / peak; any real time
    # is at least that, so the share is at most 100; and there is no clamp:
    # an impossible time reads over 100 and shows
    peak = 197e12
    required = work.lm_train_flops_per_token(SMALL, 1024) * 8192
    least = required / peak
    assert work.share(required, least, peak) == 100.0
    assert work.share(required, 2 * least, peak) == 50.0
    assert work.share(required, least / 2, peak) == 200.0
    assert work.share(required, 0.0, peak) is None

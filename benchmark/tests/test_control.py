"""The control of ``correct`` has to come out as not correct: the plain
reference, put in the program's place and computed in int8 (the precision
below the bfloat16 the configurations state), must pass some limit that
the sound program stays within. Here at the tiny preset on the CPU, three
seeds each and both families; on the chip at the cells' own sizes by
``benchmark/tools/calibrate.py`` (readings in PERF.md). Run by hand:

    python3 -m pytest benchmark/tests/test_control.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
from lib import compare  # noqa: E402

CONFIG = bench_run.load_json(HERE, "configs", "tiny-lm.json")
FAMILY = bench_run.load_module("families", CONFIG["family"])
SEEDS = (41, 42, 43)


def judged(numbers, traffic_name, config_name="tiny-lm"):
    limits = bench_run.load_limits({"config": config_name, "traffic": traffic_name})
    return compare.judge(numbers, {k: limits[k] for k in numbers})[0]


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_training_is_refused_and_bfloat16_is_not(seed):
    traffic = bench_run.load_json(HERE, "traffic", "tiny-lm-train.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    out = kind.calibrate(FAMILY, CONFIG, traffic, seed, "int8")
    assert judged(out["program"], "tiny-lm-train"), out
    assert not judged(out["control"], "tiny-lm-train"), out


def test_int8_serving_is_refused_and_bfloat16_is_not():
    traffic = bench_run.load_json(HERE, "traffic", "tiny-chat.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    # at this width int8 seldom flips a first token: these seeds stand clear
    # (limits/tiny-lm.tiny-chat.json)
    for seed in (43, 44, 45):
        out = kind.calibrate(FAMILY, CONFIG, traffic, seed, "int8", seconds=3.0)
        assert out["tokens_compared"] >= 600
        assert judged(out["program"], "tiny-chat"), out
        assert not judged(out["control"], "tiny-chat"), out


@pytest.mark.parametrize("seed", (11, 12, 13))
def test_int8_graph_training_is_refused_and_bfloat16_is_not(seed):
    """The zoo_graph family: at 64 x 64 and batch 16 only the first
    gradient of the weights separates the two (limits/tiny-resnet...)."""
    config = bench_run.load_json(HERE, "configs", "tiny-resnet.json")
    family = bench_run.load_module("families", config["family"])
    traffic = bench_run.load_json(HERE, "traffic", "tiny-image-train.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    out = kind.calibrate(family, config, traffic, seed, "int8")
    assert judged(out["program"], "tiny-image-train", "tiny-resnet"), out
    assert not judged(out["control"], "tiny-image-train", "tiny-resnet"), out

"""The reduction from trace to numbers, on a recorded trace.

``benchmark/testdata/tiny_lm_trace.json`` is cut from a profiler trace of
three steps of the tiny LM preset on a v5e (PR 23,
``benchmark/tests/record_testdata.py``): of the first step the first five
operations and the first ``while`` with four operations of its body, of the
second step the first three operations, the four program executions around
them, and the host spans of the benchmark with the loss fetches. Every
expected value below is worked out by hand from the numbers in that file
(nanoseconds). Run by hand:

    python3 -m pytest benchmark/tests/test_trace_reduce.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import trace  # noqa: E402


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "testdata", "tiny_lm_trace.json")) as f:
        return trace.reduce(json.load(f), chips=1, window_span="bench.window")


def test_window_is_the_benchmarks_host_span(reduced):
    # bench.window: start 45,643,066, duration 7,898,410
    assert reduced["window_s"] == pytest.approx(7_898_410e-9, rel=1e-12)


def test_busy_is_the_union_of_operation_intervals(reduced):
    # step 1: 6 + 461 + 3 + 98 + 10 = 578; the while covers its body: 9,126
    # (its four children, 7 + 12 + 15 + 155, lie inside it and add nothing);
    # step 2: 6 + 309 + 3 = 318
    assert reduced["busy_s"] == pytest.approx((578 + 9_126 + 318) * 1e-9, rel=1e-9)
    assert reduced["idle_share"] == pytest.approx(1 - 10_022 / 7_898_410, rel=1e-9)


def test_an_enclosing_operation_keeps_only_its_self_time(reduced):
    # while.9 lasts 9,126 and encloses 7 + 12 + 15 + 155 = 189
    assert reduced["ops"]["while"] == pytest.approx((9_126 - 189) * 1e-9, rel=1e-9)
    assert reduced["ops"]["copy-done"] == pytest.approx((461 + 309) * 1e-9, rel=1e-9)
    assert reduced["ops"]["fusion"] == pytest.approx((7 + 12) * 1e-9, rel=1e-9)
    assert reduced["device_ops"][0] == ["while.9 while", pytest.approx(8_937e-9, rel=1e-9)]
    assert reduced["device_ops"][1][0] == "copy-done.77 copy-done"


def test_program_totals(reduced):
    # jit_step ran twice: 49,896 + 50,103; the batch's conversion twice: 692 + 594
    assert reduced["programs"]["jit_step"] == [pytest.approx(99_999e-9, rel=1e-9), 2]
    assert reduced["programs"]["jit_convert_element_type"] == [pytest.approx(1_286e-9, rel=1e-9), 2]


def test_gaps_are_named_by_the_host_span_that_covers_them(reduced):
    # long gaps: window start -> first operation 1,767,050; end of the while ->
    # second step's first operation 2,395,525; last operation -> window end
    # 3,721,885. Each midpoint lies in a bench.step span and in no span inside it.
    assert reduced["gaps"]["bench.step"] == pytest.approx(7_884_460e-9, rel=1e-9)
    # short gaps: 1 + 1 + 1 + 2 between the first five operations, 3,921 up to
    # the while, 1 + 1 in the second step
    assert reduced["gaps"]["between operations (<50us)"] == pytest.approx(3_928e-9, rel=1e-9)
    assert sum(reduced["gaps"].values()) + reduced["busy_s"] == pytest.approx(reduced["window_s"], rel=1e-9)


def test_innermost_span_and_unattributed():
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%a.1 = f32[] add(...)", 1_000_000.0, 100_000.0],
            ["%b.2 = f32[] multiply(...)", 1_400_000.0, 100_000.0],
            ["%c.3 = f32[] add(...)", 2_000_000.0, 100_000.0]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.window", 1_000_000.0, 1_100_000.0],
            ["outer", 1_050_000.0, 500_000.0],
            ["inner", 1_200_000.0, 100_000.0]]}]},
    ]
    r = trace.reduce(planes)
    # gap 1,100,000..1,400,000: midpoint 1,250,000 lies in outer and in inner; inner is innermost
    # gap 1,500,000..2,000,000: midpoint 1,750,000 lies in no span
    assert r["gaps"] == {"inner": pytest.approx(300_000e-9), "unattributed": pytest.approx(500_000e-9)}
    assert r["busy_s"] == pytest.approx(300_000e-9)
    assert r["ops"] == {"a": pytest.approx(1e-4), "b": pytest.approx(1e-4), "c": pytest.approx(1e-4)}


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([{"name": "/host:CPU", "lines": []}])

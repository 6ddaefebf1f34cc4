"""The harness's own arithmetic, without JAX: the arrival plan with its
lead-in, what a window counts, the leaf groups of the training comparison,
and the limits files. Run by hand:

    python3 -m pytest benchmark/tests/test_harness.py -q
"""

import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from lib import arrivals, compare  # noqa: E402

MIX = {"rate_per_s": 2.0, "base_seed": 7, "lead_in_s": 10,
       "prompt_len": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
       "answer_len": {"median": 16, "sigma": 0.5, "min": 4, "max": 32}}


def test_arrivals_start_a_lead_in_before_the_window():
    plan = arrivals.plan(MIX, 1, 20.0, 100)
    assert len(plan) == 60  # rate x (lead-in + window)
    dues = [r["due"] for r in plan]
    assert dues == sorted(dues) and -10.0 < dues[0] < 0.0
    assert dues[-1] == pytest.approx(20.0 - 30.0 * 0.5 / 60)  # half a mean gap before the close
    assert sum(d < 0 for d in dues) > 0 and sum(d >= 0 for d in dues) > 0


def test_every_seed_offers_the_same_work_at_the_same_instants():
    a, b = arrivals.plan(MIX, 1, 20.0, 100), arrivals.plan(MIX, 2 ** 31 + 5, 20.0, 100)
    assert [(r["due"], len(r["prompt"]), r["max_new"]) for r in a] == \
           [(r["due"], len(r["prompt"]), r["max_new"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_a_window_counts_what_happens_inside_it():
    kind = bench_run.load_module("kinds", "open_loop_generate")
    t0 = 100.0

    def result(due, times, done=True):
        return {"due": due, "sent": t0 + due + 0.001, "token_times": times,
                "tokens": [1] * len(times), "done": done, "error": None}

    results = [
        # sent in the lead-in: two tokens before the window, three inside it
        result(-5.0, [96.0, 99.5, 100.5, 101.0, 101.5]),
        # due inside: first token 0.25 s after it was due, one token after the close
        result(2.0, [102.25, 103.0, 110.5]),
        # due inside, never served by the close nor the drain
        result(9.0, [], done=False),
    ]
    out, detail, failed = kind.measure(results, t0, 10.0)
    # every request's tokens: 5; those of the requests due inside the window: 2
    assert detail["tokens_in_window"] == 5 and out["serve_due_tokens_per_s"] == 0.2
    # 5 = the 3 tokens of the requests due inside + 3 owed at the opening - 1 owed at the close
    assert detail["tokens_owed_at_open"] == 3 and detail["tokens_owed_at_close"] == 1
    # gaps closed inside the window: 99.5->100.5, 100.5->101, 101->101.5, 102.25->103
    assert detail["gaps"] == 4 and out["itl_p95_ms"] == pytest.approx(1000.0)
    assert detail["requests_due_in_window"] == 2
    assert detail["ttft_p50_ms"] == pytest.approx(250.0)
    assert detail["ttft_p95_ms"] == pytest.approx(10_000.0)  # the unserved one: the window's length
    assert detail["streaming_at_open"] == 1 and detail["streaming_at_close"] == 1
    assert failed == 1


def test_norm_gaps_by_leaf_group():
    ref = {"losses": [2.0, 4.0, 4.0], "grad_norms": {"a/W": 1.0, "c/W": 2.0, "a/beta": 0.5, "a/gamma": 1.0}}
    ref["update_norms"] = dict(ref["grad_norms"])
    prog = {"losses": [2.2, 4.0, 3.0], "grad_norms": {"a/W": 1.1, "c/W": 2.0, "a/beta": 0.0, "a/gamma": 1.0}}
    prog["update_norms"] = dict(ref["grad_norms"])
    groups = {"weights": ["/W"], "rest": ["/beta", "/gamma"]}
    numbers, where = compare.train_numbers(prog, ref, groups)
    assert numbers["loss_rel_gap.first"] == pytest.approx(0.1)
    assert numbers["loss_rel_gap.later"] == pytest.approx(0.25)  # the worst of the later steps
    # the floor is the group's own median leaf: 1.5 for the weights
    assert numbers["grad_norm_gap.weights"] == pytest.approx(0.1 / 1.5) and where["grad_norm_gap.weights"] == "a/W"
    # a zeroed gradient reads 1.0 against a leaf at or under the median
    assert numbers["grad_norm_gap.rest"] == pytest.approx(0.5 / 0.75)
    assert numbers["update_norm_gap.weights"] == numbers["update_norm_gap.rest"] == 0.0
    ungrouped, _ = compare.train_numbers(prog, ref)
    assert set(ungrouped) == {"loss_rel_gap.first", "loss_rel_gap.later", "grad_norm_gap",
                              "update_norm_gap"}
    with pytest.raises(ValueError):
        compare.train_numbers(prog, ref, {"weights": ["/W"]})


def test_a_number_without_a_limit_fails():
    ok, lines = compare.judge({"a": 0.1, "b": 0.1}, {"a": 0.2})
    assert not ok and [l["within"] for l in lines] == [True, False]


LIMITS = sorted(glob.glob(os.path.join(HERE, "limits", "*.json")))


@pytest.mark.parametrize("path", LIMITS, ids=[os.path.basename(p) for p in LIMITS])
def test_limits_file(path):
    d = json.load(open(path))
    assert os.path.basename(path) == f"{d['config']}.{d['traffic']}.json"
    assert os.path.exists(os.path.join(HERE, "configs", d["config"] + ".json"))
    assert os.path.exists(os.path.join(HERE, "traffic", d["traffic"] + ".json"))
    assert d["origin"]
    for name, limit in d["limits"].items():
        assert name in d["why"] or not d["config"].startswith(("gpt2", "resnet")), name
        if "norm_gap" in name:
            # a zeroed gradient and an unchanged state read exactly 1.0
            assert limit < 1.0, name


def test_every_cell_has_its_limits_file():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        assert bench_run.load_limits(cell)

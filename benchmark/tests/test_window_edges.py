"""The window's edges and the token count (no JAX, no chip): the lock-step
replay of ``tools/replay_schedule.py`` lands on the ledger at today's decode
step, and over faster steps the count over every request FALLS while the
count over the requests due inside the window rises. Run by hand:

    python3 -m pytest benchmark/tests/test_window_edges.py -q
"""

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "tools"))

import replay_schedule  # noqa: E402
from lib import arrivals  # noqa: E402

SECONDS = 40.0
PREFILL_MS = 24.8  # prefill_ms.serve (ledger, PR 24)
# gpt2-large.chat, the change's side (ledger, PR 24): decode_step_ms.serve 122.0
LEDGER = {"every_request_tokens_per_s": 162.78, "itl_p95_ms": 145.24, "slot_occupancy": 85.095}
STEPS_MS = [122, 95, 80, 65, 45, 30]


@pytest.fixture(scope="module")
def chat():
    measure, traffic = replay_schedule.load("chat-steady")
    requests = arrivals.plan(traffic, 0, SECONDS, 2)
    return measure, requests, traffic["engine"]["n_slots"]


def row(chat, step_ms):
    measure, requests, slots = chat
    return replay_schedule.read(measure, requests, slots, SECONDS, step_ms, PREFILL_MS)


@pytest.mark.parametrize("name", sorted(LEDGER))
def test_the_replay_lands_on_the_ledger_at_todays_step(chat, name):
    assert row(chat, 122.0)[name] == pytest.approx(LEDGER[name], rel=0.02)


def test_the_counts_and_their_edges_add_up(chat):
    _, requests, _ = chat
    r = row(chat, 122.0)
    due_tokens = sum(q["max_new"] for q in requests if q["due"] >= 0)
    assert r["tokens_in_window"] == due_tokens + r["tokens_owed_at_open"] - r["tokens_owed_at_close"]
    assert r["tokens_due_in_window"] < r["tokens_in_window"] < due_tokens


def test_a_faster_engine_never_reads_lower_on_the_due_count_and_does_on_the_other(chat):
    rows = [row(chat, s) for s in STEPS_MS]
    due = [r["serve_due_tokens_per_s"] for r in rows]
    every = [r["every_request_tokens_per_s"] for r in rows]
    assert all(b > a for a, b in zip(due, due[1:])), due
    assert all(r["failed"] == 0 and r["streaming_at_close"] < 24 for r in rows)  # no backlog anywhere
    # the defect: with no token lost, the count over every request falls by more than its 1 % bound
    assert min(every) < 0.99 * every[0], every
    # and both tend to the offered load: every token of the 48 requests due in 40 s
    assert row(chat, 0.001)["serve_due_tokens_per_s"] == pytest.approx(6951 / SECONDS, rel=1e-3)


def test_a_backlog_lowers_the_due_count(chat):
    slow, today = row(chat, 160.0), row(chat, 122.0)
    assert slow["streaming_at_close"] == 24 and slow["ttft_p95_ms"] > 1000.0  # a queue has built
    assert slow["serve_due_tokens_per_s"] < 0.99 * today["serve_due_tokens_per_s"]


def test_a_failed_request_lowers_the_due_count(chat):
    measure, requests, slots = chat
    results, _ = replay_schedule.replay(requests, slots, 0.122, PREFILL_MS / 1e3)
    sound, _, failed = measure(results, 0.0, SECONDS)
    assert failed == 0
    # the first request due inside the window loses its connection after five tokens
    broken = copy.deepcopy(results)
    victim = next(r for r in broken if r["due"] >= 0 and len(r["tokens"]) > 50)
    victim.update(token_times=victim["token_times"][:5], tokens=victim["tokens"][:5],
                  done=False, error="ConnectionResetError")
    out, _, failed = measure(broken, 0.0, SECONDS)
    assert failed == 1
    assert out["serve_due_tokens_per_s"] < sound["serve_due_tokens_per_s"] - 1.0

"""``lib/gap_read.py`` and the five readers built on it, on a recorded trace.

``benchmark/testdata/tiny_serve_gaps.json`` was recorded on a v5e (PR 37,
``benchmark/tests/record_gap_testdata.py``): a ``GenerationEngine`` over a
24-layer ``TransformerLM`` of width 1024, 16 slots of 512, under the
profiler. Kept: the first device's ``XLA Modules`` line, the host span
``bench.window`` (49,197,248 to 199,708,509 ns), ``profile_start_time`` and the
program's four-wide ring of the stretch. The window holds two prefills, then
13 executions of ``jit__decode`` of ~4.0 ms (the engine's steps 4 to 16,
dispatched ~8.6 ms apart); between the second and the third a third request
is claimed: six ``jit_convert_element_type`` and one ``jit__threefry_seed`` of
0.6 us each and a ``jit__prefill`` of 1,570,965 ns. The device plane runs
~0.3 ms ahead of the ring (step 4's execution starts at 70,863,840, its
dispatch phase at 71,091,007). Every expected value below is worked out by
hand from the numbers in that file (nanoseconds). Run by hand:

    python3 -m pytest benchmark/tests/test_gap_read.py -q
"""

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from lib import gap_read, phases  # noqa: E402

PROGRAM = "jit__decode"
RUN = {"work": {"decode_program": PROGRAM}}
FIVE = ("launch_gap_ms.serve", "host_turn_ms.serve", "launch_gap_runtime_ms.serve", "turn_ms.serve",
        "claim_gap_ms.serve")


@pytest.fixture
def recorded():
    with open(os.path.join(HERE, "testdata", "tiny_serve_gaps.json")) as f:
        return json.load(f)


def as_this_run(monkeypatch, xplane, ring):
    """The readers read this process's run: hand them the recording."""
    monkeypatch.setattr(phases, "_run", {"xplane": xplane, "ring": None})
    monkeypatch.setattr(gap_read, "_run", {"ring": ring})


def read_five(monkeypatch, xplane, ring):
    as_this_run(monkeypatch, xplane, ring)
    return {name: bench_run.load_module("metrics", name).read(RUN) for name in FIVE}


def modules(xplane):
    return xplane["planes"][0]["lines"][0]["events"]


def test_a_plain_pair_and_a_pair_with_a_prefill_between(recorded):
    runs, pairs = gap_read.device_pairs(recorded, PROGRAM)
    assert len(runs) == 13 and runs[0] == (70_863_840, 74_833_038) and runs[1][0] == 79_389_602
    assert [p["kind"] for p in pairs] == ["plain", "claim"] + ["plain"] * 10
    assert pairs[0] == {"wait_ns": 4_556_564, "between": [], "kind": "plain"}
    # 96,371,490 - (79,389,602 + 3,968,858); the claim's programs lie between
    assert pairs[1]["wait_ns"] == 13_013_030 and pairs[1]["prefill_ns"] == 1_570_965
    assert [n for n, _ in pairs[1]["between"]] == (
        ["jit_convert_element_type", "jit__threefry_seed"] + ["jit_convert_element_type"] * 5 + ["jit__prefill"])
    # programs under 50 us between two steps are listed and do not count
    seeded = copy.deepcopy(recorded)
    modules(seeded)[:] = [e for e in modules(seeded) if not e[0].startswith("jit__prefill")]
    second = gap_read.device_pairs(seeded, PROGRAM)[1][1]
    assert second["kind"] == "plain" and len(second["between"]) == 7
    joined = gap_read.joined_pairs(recorded, recorded["ring"], PROGRAM)
    assert [p["causes"] for p in joined] == [[4 + i, 5 + i] for i in range(12)]
    # step 4's fetch ends at ...413,830,553 + 5,242,300, step 5's dispatch begins at ...421,499,612
    assert joined[0]["host_turn_ns"] == 2_426_759 and joined[0]["admits"] == 0
    assert (joined[0]["emit_ns"], joined[0]["turn_ns"], joined[0]["put_ns"]) == (161_931, 50_370, 2_140_360)
    assert joined[1]["host_turn_ns"] == 10_641_289 and joined[1]["admits"] == 1
    assert joined[1]["turn_ns"] == 8_564_799  # the turn encloses the claim (8,492,459) whole


def test_the_five_numbers(recorded, monkeypatch):
    """Medians over the eleven plain pairs: the sixth of the sorted waits
    (4,545,323), host turns (2,271,390), differences (2,240,291) and turns
    (43,410); the one claim pair's 13,013,030 - 1,570,965."""
    got = read_five(monkeypatch, recorded, recorded["ring"])
    assert got == {"launch_gap_ms.serve": pytest.approx(4.545323), "host_turn_ms.serve": pytest.approx(2.27139),
                   "launch_gap_runtime_ms.serve": pytest.approx(2.240291),
                   "turn_ms.serve": pytest.approx(0.04341), "claim_gap_ms.serve": pytest.approx(11.442065)}
    summary = gap_read.summary(recorded, recorded["ring"], PROGRAM)
    assert summary["pairs"] == {"plain": 11, "claim": 1, "other": 0}
    assert summary["executions"] == 13 and summary["joined_plain_pairs"] == 11


def test_an_edge_pair_cut_by_the_window(recorded):
    """The window closes inside the last execution (182,024,377 to
    186,040,303): it is no whole execution, its pair goes, and the ring's
    one step more is an edge."""
    cut = copy.deepcopy(recorded)
    cut["planes"][1]["lines"][0]["events"][0][2] = 184_000_000 - 49_197_248
    runs, pairs = gap_read.device_pairs(cut, PROGRAM)
    assert len(runs) == 12 and len(pairs) == 11
    assert len(gap_read.window_steps(cut, gap_read.ring_steps(cut["ring"]))) == 13
    joined = gap_read.joined_pairs(cut, cut["ring"], PROGRAM)
    assert len(joined) == 11 and joined[-1]["causes"] == [14, 15]
    # ten plain waits are left: the mean of the fifth and sixth, 4,545,323 and 4,556,564
    assert gap_read.launch_gap_ms(pairs) == pytest.approx(4.5509435)


def test_a_ring_one_step_longer_than_the_plane(recorded, capsys):
    """The plane lacks the last execution (the trace stopped first): the
    join stands. With four steps more than executions it does not."""
    short = copy.deepcopy(recorded)
    del modules(short)[-1]
    joined = gap_read.joined_pairs(short, short["ring"], PROGRAM)
    # ten plain pairs: the mean of 2,271,390 and 2,271,869
    assert len(joined) == 11 and gap_read.host_turn_ms(joined) == pytest.approx(2.2716295)
    del modules(short)[-3:]
    assert len(gap_read.device_pairs(short, PROGRAM)[0]) == 9
    assert gap_read.joined_pairs(short, short["ring"], PROGRAM) is None
    assert "not the same steps" in capsys.readouterr().err
    # the device's own reading needs no ring: seven plain waits, the fourth
    assert gap_read.launch_gap_ms(gap_read.device_pairs(short, PROGRAM)[1]) == pytest.approx(4.545323)


def test_an_execution_without_a_step_of_its_own(recorded, capsys):
    """The ring lost a step in the middle (turned over, or another
    engine's): the executions no longer follow one dispatch phase each."""
    holed = [e for e in recorded["ring"] if e[3] != 9]
    assert gap_read.joined_pairs(recorded, holed, PROGRAM) is None
    assert "dispatch phase of their own" in capsys.readouterr().err


def test_a_program_without_turn_or_causes_reads_as_nothing(recorded, monkeypatch, capsys):
    """The parent of the PR that added them: a three-wide ring, no
    ``gen.turn``. All five are left out and nothing raises."""
    parent_ring = [e[:3] for e in recorded["ring"] if e[0] != "gen.turn"]
    for ring in (parent_ring, [e + [None] for e in parent_ring], [], None):
        assert read_five(monkeypatch, recorded, ring) == dict.fromkeys(FIVE)
        assert gap_read.ring_steps(ring) is None
        assert gap_read.joined_pairs(recorded, ring, PROGRAM) is None
    assert read_five(monkeypatch, None, recorded["ring"]) == dict.fromkeys(FIVE)
    assert capsys.readouterr().err == ""


def test_no_lone_prefill_in_the_span_is_said(recorded, monkeypatch, capsys):
    plain = copy.deepcopy(recorded)
    modules(plain)[:] = [e for e in modules(plain) if e[0].startswith(PROGRAM)]
    got = read_five(monkeypatch, plain, plain["ring"])
    # twelve plain waits now, the claim's 13,013,030 the longest: the mean of the sixth and seventh
    assert got["claim_gap_ms.serve"] is None and got["launch_gap_ms.serve"] == pytest.approx(4.5509435)
    assert "no pair of the span's 12 has one lone prefill between" in capsys.readouterr().err


def test_a_run_the_clock_check_refuses_still_reads(recorded, monkeypatch):
    """The device plane 2 ms late against the ring: no execution starts
    inside a dispatch phase's reach, ``clock_check`` refuses the run and
    the ``idle_*`` readers go silent; the gap readers difference each
    side on its own clock and read as before."""
    ring3 = [e[:3] for e in recorded["ring"]]
    assert phases.clock_check(recorded, ring3)["ok"] is True
    late = copy.deepcopy(recorded)
    for event in modules(late):
        event[1] += 2_000_000
    check = phases.clock_check(late, ring3)
    assert check["ok"] is False and "no launch vouches" in check["why"]
    assert phases.idle_by_phase(late, ring3) is None
    got = read_five(monkeypatch, late, late["ring"])
    assert got["launch_gap_ms.serve"] == pytest.approx(4.545323)
    assert got["host_turn_ms.serve"] == pytest.approx(2.27139)
    assert got["launch_gap_runtime_ms.serve"] == pytest.approx(2.240291)


def test_benchmark_json_declares_the_five():
    per_layer = {m["name"]: m for m in bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")["per_layer"]}
    serving = [w["name"] for w in bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")["workloads"]
               if w["traffic"] not in ("lm-train-1024", "image-train-b128")]
    for name in FIVE:
        entry = per_layer[name]
        assert entry["moves"] == "itl_p95_ms" and entry["better"] == "lower" and entry["unit"] == "ms"
        assert entry["workloads"] == serving and len(serving) == 4
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))

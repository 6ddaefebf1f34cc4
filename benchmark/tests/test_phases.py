"""``lib/phases.py`` and the ten readers built on it, on a recorded trace.

``benchmark/testdata/tiny_lm_phases.json`` is cut from a profiler trace of
three steps of the tiny LM preset on a v5e (PR 24,
``benchmark/tests/record_phases_testdata.py``), with the program's ring of
phases of the same run beside it. Kept of the device plane: the first
seven operations of step 1, its first ``while`` with five operations of
its body, one operation of the loss and the step's last; the first three
operations of step 2; three program executions. Kept of ``/host:CPU``: the
benchmark's spans and the ``TraceAnnotation`` of every phase. Each ``XLA
Ops`` event carries as its fourth element the ``tf_op`` stat of its event
metadata. Every expected value below is worked out by hand from the
numbers in that file (nanoseconds). Run by hand:

    python3 -m pytest benchmark/tests/test_phases.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from lib import phases  # noqa: E402

WINDOW_NS = 8_102_440  # bench.window: start 51,532,820
BUSY_NS = 13_994


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "testdata", "tiny_lm_phases.json")) as f:
        return json.load(f)


@pytest.fixture
def as_this_run(recorded, monkeypatch):
    """The readers read this process's run: hand them the recording."""
    monkeypatch.setattr(phases, "_run", {"xplane": recorded, "ring": recorded["ring"]})


def reader(name):
    return bench_run.load_module("metrics", name).read


def without_names(recorded):
    """The recording as a program without scopes would leave it."""
    return {"start_ns": recorded["start_ns"],
            "planes": [{"name": p["name"],
                        "lines": [{"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
                                  for ln in p["lines"]]} for p in recorded["planes"]]}


def with_names_of_no_scope(recorded):
    """The recording with every operation named, none after a scope."""
    out = without_names(recorded)
    for plane in out["planes"]:
        for ln in plane["lines"]:
            if ln["name"] == "XLA Ops":
                ln["events"] = [e + ["jit(step)/jvp()/while/body/dot_general:"] for e in ln["events"]]
    return out


def test_scope_of_reads_the_innermost_scope():
    assert phases.scope_of("jit(step)/transpose(jvp(head))/jit(_var)/reduce_sum:") == "head"
    assert phases.scope_of("jit(step)/jvp()/while/body/closed_call/attn/dot_general:") == "attn"
    assert phases.scope_of("jit(_decode)/while/body/kv_write/scatter:") == "kv_write"
    # the scan's own slicing, a parameter, nothing at all, a jit of that name
    assert phases.scope_of("jit(step)/jvp()/while/body/dynamic_update_slice:") == "unscoped"
    assert phases.scope_of("params['embed']:") == "unscoped"
    assert phases.scope_of("") == "unscoped"
    assert phases.scope_of("jit(update)/add:") == "unscoped"
    # a fusion named after several operations reads as its first
    assert phases.scope_of("a/mlp/add;a/attn/mul:") == "mlp"


def test_device_scopes_sum_self_times_by_scope(recorded):
    got = phases.device_scopes(recorded)
    # embed: 3 + 97 + 10 + 441 of step 1, 3 of step 2
    assert got["embed"] == pytest.approx(554e-9, rel=1e-9)
    # attn: fusion.435 7 + fusion.437 406 (inside while.9)
    assert got["attn"] == pytest.approx(413e-9, rel=1e-9)
    assert got["mlp"] == pytest.approx(480e-9, rel=1e-9)
    assert got["loss"] == pytest.approx(3042e-9, rel=1e-9)
    # unscoped: copy-start/done 6 + 316 + 6 + 313, copy.140 628 (params['embed']),
    # copy.213 155 and the stacking fusion 161 inside the while, copy-done.105 3,
    # and the while's own 9,126 - (7 + 155 + 406 + 480 + 161) = 7,917
    assert got["unscoped"] == pytest.approx(9505e-9, rel=1e-9)
    assert sum(got.values()) == pytest.approx(BUSY_NS * 1e-9, rel=1e-9)
    assert set(got) == {"embed", "attn", "mlp", "loss", "unscoped"}


def test_device_scopes_of_one_program(recorded):
    # every kept operation ran inside one of the two jit_step executions
    assert phases.device_scopes(recorded, "jit_step") == phases.device_scopes(recorded)
    assert phases.program_executions(recorded, "jit_step") == 2
    assert phases.device_scopes(recorded, "jit_convert_element_type") == {}


def test_a_trace_without_names_reads_as_nothing(recorded):
    bare = without_names(recorded)
    assert phases.device_scopes(bare) is None


def test_clock_offset_is_the_median_over_the_annotations(recorded):
    # ring start - annotation start of the nine phases: 2570 2080 2790 1220
    # 1380 1380 1040 870 1050
    assert phases.annotation_offset_ns(recorded, recorded["ring"]) == 1380
    check = phases.clock_check(recorded, recorded["ring"])
    assert (check["check"], check["offset_ns"], check["ok"]) == ("annotations", 1380, True)


def test_idle_is_divided_among_the_phases_that_cover_it(recorded):
    got = phases.idle_by_phase(recorded, recorded["ring"], device_lead=0)
    # window start -> first operation (628,836): 206,880 before put_batch
    # begins, 421,956 under it. Last operation of step 1 -> first of step 2
    # (2,569,403): put_batch 741,257, dispatch 786,700, fetch_loss 790,070, and
    # 20,080 + 49,040 + 182,256 between and after them. Third operation of step
    # 2 -> window end (4,855,589): put_batch 876,980 + 806,580, dispatch
    # 642,530 + 376,490, fetch_loss 731,740 + 922,110, the rest outside.
    assert got["train.put_batch"] == pytest.approx(2_846_773e-9, rel=1e-9)
    assert got["train.dispatch"] == pytest.approx(1_805_720e-9, rel=1e-9)
    assert got["train.fetch_loss"] == pytest.approx(2_443_920e-9, rel=1e-9)
    assert got[phases.OUTSIDE] == pytest.approx(957_415e-9, rel=1e-9)
    # 2 + 2 + 1 + 2 inside step 1's first operations, 2,648 + 1,890 + 30,070
    # between its kept operations, 1 + 2 in step 2
    assert got[phases.SHORT_GAPS] == pytest.approx(34_618e-9, rel=1e-9)
    assert sum(got.values()) == pytest.approx((WINDOW_NS - BUSY_NS) * 1e-9, rel=1e-9)


def test_the_device_planes_lead_is_taken_out(recorded):
    """In this session the device plane runs about a millisecond early:
    jit_step 1 starts 52,160,812, 810,468 before the dispatch phase that
    launched it begins (52,971,280), and jit_step 2 (54,778,502) 1,014,758
    before dispatch 2 (55,793,260); dispatch 3's execution is not kept. The
    convert_element_type execution (596 ns) is never the longest near a
    dispatch. The lead is the median of the two, 912,613; their quartiles
    (Python's: a quarter of their distance beyond either) lie 306,435
    apart. Laid 912,613 earlier, of the device's idle time put_batch covers
    505,767 of the first gap (it began before the window), 813,459 of the
    second, 63,196 + 806,580 of the third."""
    assert phases.launch_leads(recorded, recorded["ring"]) == [810_468, 1_014_758]
    check = phases.clock_check(recorded, recorded["ring"])
    assert check["device_lead_ns"] == 912_613 and check["dispatches"] == 2 and check["ok"]
    assert check["lead_spread_ns"] == pytest.approx(306_435)
    got = phases.idle_by_phase(recorded, recorded["ring"])
    assert got["train.put_batch"] == pytest.approx(2_189_002e-9, rel=1e-9)
    # dispatch: 102,989 + 635,424 + 642,530 + 376,490
    assert got["train.dispatch"] == pytest.approx(1_757_433e-9, rel=1e-9)
    assert got["train.fetch_loss"] == pytest.approx(2_443_920e-9, rel=1e-9)
    assert got[phases.OUTSIDE] == pytest.approx(1_663_473e-9, rel=1e-9)
    assert sum(got.values()) == pytest.approx((WINDOW_NS - BUSY_NS) * 1e-9, rel=1e-9)


def test_leads_that_spread_are_refused(recorded):
    """One dispatch phase moved 700,000 later: its lead reads 1,510,468,
    the quartiles lie 743,565 apart, over the 500,000 within which a lead
    decides between phases of a millisecond."""
    ring = [list(e) for e in recorded["ring"]]
    first = next(e for e in ring if e[0] == "train.dispatch")
    first[1] += 700_000
    check = phases.clock_check(recorded, ring)
    assert check["lead_spread_ns"] == pytest.approx(743_565) and not check["ok"]
    assert "spreads" in check["why"]
    assert phases.idle_by_phase(recorded, ring) is None


def test_a_ring_on_another_clock_is_refused(recorded):
    shifted = [[n, s + 5_000_000, d] for n, s, d in recorded["ring"]]
    check = phases.clock_check(recorded, shifted)
    assert abs(check["offset_ns"]) > phases.CLOCK_LIMIT_NS and not check["ok"]
    assert phases.idle_by_phase(recorded, shifted) is None
    assert phases.clock_check(recorded, []) is None
    assert phases.idle_by_phase(recorded, []) is None


def test_without_host_spans_the_executions_vouch_for_the_ring(recorded):
    """The mix with ``host_tracer_level`` 0: no annotation to compare with,
    and the window is the extent of the device's operations (52,161,656 to
    54,779,671). The check falls back on the launches: jit_step 1 (clipped
    to the window) leads dispatch 1 (52,971,280) by 809,624; the other
    dispatch phases begin after the window. A ring 5 ms late has no
    dispatch phase in the window at all, so no launch vouches for it."""
    device_only = {"start_ns": recorded["start_ns"],
                   "planes": [p for p in recorded["planes"] if p["name"] != "/host:CPU"]}
    check = phases.clock_check(device_only, recorded["ring"])
    assert (check["check"], check["offset_ns"], check["ok"]) == ("launches", None, True)
    assert phases.launch_leads(device_only, recorded["ring"]) == [809_624]
    shifted = [[n, s + 5_000_000, d] for n, s, d in recorded["ring"]]
    check = phases.clock_check(device_only, shifted)
    assert not check["ok"] and "no launch" in check["why"]
    assert phases.idle_by_phase(device_only, shifted) is None


def test_executions_that_start_late_are_refused_without_host_spans():
    """A dispatch phase of 2 ms (the graph runtime's: rng split, casts, a
    call with hundreds of arguments) whose longest execution starts
    1,500,000 after it began: with nothing else to vouch for the ring, a
    launch over a millisecond late reads as a ring on another clock."""
    op = "%fusion.1 = f32[] fusion()"
    trace_only = {"start_ns": 1_000, "planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [[op, 4_000_000.0, 1_000.0, ""], [op, 8_000_000.0, 1_000.0, ""]]},
        {"name": "XLA Modules", "events": [["jit_step(1)", 6_500_000.0, 1_000.0]]}]}]}
    ring = [["train.dispatch", 1_000 + 5_000_000, 2_000_000]]
    assert phases.launch_leads(trace_only, ring) == [-1_500_000]
    check = phases.clock_check(trace_only, ring)
    assert not check["ok"] and "after the dispatch" in check["why"]
    on_time = [["train.dispatch", 1_000 + 6_400_000, 2_000_000]]
    assert phases.clock_check(trace_only, on_time)["ok"]


def test_phase_stats(recorded):
    got = phases.phase_stats(recorded["ring"])
    assert got["train.put_batch"]["count"] == 3
    # 1,211,500 876,980 806,580
    assert got["train.put_batch"]["median_ms"] == pytest.approx(0.87698)
    assert got["train.put_batch"]["p95_ms"] == pytest.approx(1.2115)
    assert got["train.dispatch"]["median_ms"] == pytest.approx(0.64253)
    assert got["train.fetch_loss"]["total_s"] == pytest.approx(2_443_920e-9)
    lo = recorded["start_ns"] + 54_000_000  # steps 2 and 3 only
    assert phases.phase_stats(recorded["ring"], lo, None)["train.put_batch"]["count"] == 2


def test_nested_phases_keep_what_their_children_leave():
    """gen.admit encloses gen.prefill, which encloses gen.prefill.put; a
    queue wait is no host work of the loop and covers nothing."""
    entries = [("gen.queue_wait", 0, 1000), ("gen.admit", 100, 900), ("gen.prefill", 200, 800),
               ("gen.prefill.put", 250, 300), ("gen.decode.put", 900, 950)]
    assert phases.innermost_segments(entries) == [
        (100, 200, "gen.admit"), (200, 250, "gen.prefill"), (250, 300, "gen.prefill.put"),
        (300, 800, "gen.prefill"), (800, 900, "gen.admit"), (900, 950, "gen.decode.put")]


def test_train_readers(as_this_run):
    assert reader("batch_put_ms.train")({}) == pytest.approx(0.87698)
    # with the device plane's lead of 912,613 taken out
    assert reader("idle_put_share.train")({}) == pytest.approx(100 * 2_189_002 / WINDOW_NS)
    assert reader("attn_device_share.train")({}) == pytest.approx(100 * 413 / BUSY_NS)
    assert reader("head_loss_device_share.train")({}) == pytest.approx(100 * 3042 / BUSY_NS)


def test_serve_readers_on_a_synthetic_ring(recorded, monkeypatch):
    """The recording is a training run: the engine's phases are laid over
    it by hand, and jit_step stands in for the decode program."""
    t0 = recorded["start_ns"]
    ring = [["gen.queue_wait", t0 + 52_000_000, 40_000_000],
            ["gen.queue_wait", t0 + 52_000_000, 90_000_000],
            ["gen.prefill.put", t0 + 53_000_000, 200_000],
            ["gen.prefill", t0 + 52_900_000, 1_000_000],
            ["gen.admit", t0 + 52_800_000, 1_200_000],
            ["gen.decode.put", t0 + 54_000_000, 500_000],
            ["gen.decode.put", t0 + 56_000_000, 700_000],
            ["gen.emit", t0 + 57_000_000, 300_000]]
    monkeypatch.setattr(phases, "_run", {"xplane": recorded, "ring": ring})
    run = {"work": {"decode_program": "jit_step"}}
    assert reader("queue_wait_p95_ms.serve")(run) == pytest.approx(90.0)
    assert reader("decode_put_ms.serve")(run) == pytest.approx(0.6)
    assert reader("emit_ms.serve")(run) == pytest.approx(0.3)
    # all three puts lie in device-idle time, and no host span of the
    # recording bears their names: the executions vouch for the clock
    # (no dispatch phase here, so nothing can, and the reader refuses)
    assert reader("idle_put_share.serve")(run) is None
    ring.append(["gen.decode.dispatch", t0 + 54_778_000, 100_000])  # jit_step 2 starts 502 later
    monkeypatch.setattr(phases, "_run", {"xplane": recorded, "ring": ring})
    assert reader("idle_put_share.serve")(run) == pytest.approx(
        100 * (200_000 + 500_000 + 700_000) / WINDOW_NS)
    # attn 413 ns over two executions; no kv_write in a training step
    assert reader("attn_device_ms.serve")(run) == pytest.approx(1e3 * 413e-9 / 2)
    assert reader("kv_write_device_ms.serve")(run) == 0.0


def test_a_program_without_ring_or_scopes_reads_as_nothing(recorded, monkeypatch):
    """The parent of the PR that added them (no ring, no scope in any
    operation's name): every reader returns None and none raises."""
    bare = without_names(recorded)
    run = {"work": {"decode_program": "jit_step"}}
    new_metrics = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")["per_layer"][11:]
    for state in ({"xplane": bare, "ring": None}, {"xplane": with_names_of_no_scope(recorded), "ring": None},
                  {"xplane": None, "ring": None}):
        monkeypatch.setattr(phases, "_run", state)
        for m in new_metrics:
            assert reader(m["name"])(run) is None, m["name"]


def test_an_executable_older_than_the_scopes_is_left_out(recorded, monkeypatch, capsys):
    """The operations have names, none of them a scope: the executable came
    out of the persistent cache, compiled before the scopes were added (the
    cache's key leaves names out). It would read as a perfect 0 of metrics
    where lower is better, so the scope metrics are left out, and stderr
    says why."""
    stale = with_names_of_no_scope(recorded)
    monkeypatch.setattr(phases, "_run", {"xplane": stale, "ring": None})
    run = {"work": {"decode_program": "jit_step"}}
    assert phases.device_scopes(stale) == {"unscoped": pytest.approx(BUSY_NS * 1e-9)}
    for name in ("attn_device_share.train", "head_loss_device_share.train",
                 "kv_write_device_ms.serve", "attn_device_ms.serve"):
        assert reader(name)(run) is None, name
    assert "compiled before them" in capsys.readouterr().err


def test_op_names_reads_the_files_own_encoding(tmp_path):
    """One plane with one event-metadata entry whose stat refers to an
    interned string, encoded by hand as the profiler writes it."""

    def varint(number, value):
        out = bytearray([number << 3])
        while value >= 0x80:
            out.append(value & 0x7F | 0x80)
            value >>= 7
        return bytes(out + bytes([value]))

    def field(number, payload):
        return varint(number, len(payload))[:0] + bytes([number << 3 | 2]) + varint(0, len(payload))[1:] + payload

    def stat_meta(key, name):
        return field(5, varint(1, key) + field(2, varint(1, key) + field(2, name)))

    stat = field(5, varint(1, 7) + varint(7, 9))  # stat 7 (tf_op) -> interned string 9
    event = field(4, varint(1, 3) + field(2, varint(1, 3) + field(2, b"%fusion.1 = f32[] fusion()") + stat))
    plane = (field(2, b"/device:TPU:0") + field(3, b"\x08\x01") + event
             + stat_meta(7, b"tf_op") + stat_meta(9, b"jit(step)/attn/mul:"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane) + field(1, field(2, b"/host:CPU")))
    assert phases.op_names(str(path)) == {
        "/device:TPU:0": {"%fusion.1 = f32[] fusion()": "jit(step)/attn/mul:"}}

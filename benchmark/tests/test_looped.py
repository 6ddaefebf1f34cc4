"""The ``looped_decoder_lm`` family: its work functions against counts made by
hand at the tiny and the published sizes, its readers on names and counters
made by hand, the scopes tool's division by pass, and whole runs of ``run.py``
at the tiny preset: a sound run is correct and the int8 control is refused.
Run by hand:

    python3 -m pytest benchmark/tests/test_looped.py -q
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
from lib import compare, decoder_read, looped_read, work_looped  # noqa: E402

PUBLISHED = bench_run.load_json(HERE, "configs", "ouro-2.6b.json")
TINY = bench_run.load_json(HERE, "configs", "tiny-ouro.json")
PEAKS = bench_run.load_json(HERE, "peaks.json")["TPU v5 lite"]
CELL = "ouro-2.6b.reason-looped"


def test_weight_and_cache_bytes_by_hand():
    # a layer: Wq, Wk, Wv, Wo 4 x 2048 x 2048; gate, up, down 3 x 2048 x 5632; four gains
    assert work_looped.layer_param_count(PUBLISHED) == (16_777_216 + 34_603_008, 8192)
    assert work_looped.layer_weight_bytes(PUBLISHED, 2) == 48 * (51_380_224 * 2 + 8192 * 4) \
        == 4_934_074_368                                                       # 4.93 GB a pass
    assert work_looped.head_bytes(PUBLISHED, 2) == 2048 * 49152 * 2 + 2048 * 4 == 201_334_784
    assert work_looped.embed_row_bytes(PUBLISHED, 2) == 4096
    # layers + head + embedding + the gate's 2,049 float32: 5.34 GB
    assert work_looped.model_bytes(PUBLISHED, 2) == 4_934_074_368 + 201_334_784 + 201_326_592 + 8196 \
        == 5_336_743_940
    assert work_looped.cache_entries_per_position(PUBLISHED) == 4 * 48 == 192
    # K and V, 16 heads of 128, two bytes, for every (pass, layer)
    assert work_looped.cache_bytes_per_position(PUBLISHED, 2) == 192 * 2 * 16 * 128 * 2 == 1_572_864
    # 5 slots of 896 positions: 7.05 GB
    assert 5 * 896 * 1_572_864 == 7_046_430_720
    assert work_looped.layer_param_count(TINY) == (4 * 64 * 64 + 3 * 64 * 160, 256)
    assert work_looped.layer_weight_bytes(TINY, 2) == 3 * (47_104 * 2 + 256 * 4)
    assert work_looped.cache_bytes_per_position(TINY, 2) == 9 * 2 * 4 * 16 * 2
    # a step over 4 live slots with 1,800 positions behind them: four reads of the layers
    assert work_looped.decode_step_bytes(PUBLISHED, 4, 1800) == (
        4 * 4_934_074_368 + 201_334_784 + 4 * 4096 + 1800 * 1_572_864)
    # a weight byte does ~5 operations a step (5 rows): bound by bytes, 48 x under the ridge
    assert 2 * 5 / 2 < PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"] / 48


def test_scopes_by_operation_name():
    name = "jit(_decode)/while/body/pass_close/mul:"
    assert looped_read.scope_of(name) == "pass_close"
    assert looped_read.scope_of("jit(_decode)/while/body/while/body/closed_call/attn_full/dot_general:") \
        == "attn_full"
    assert looped_read.scope_of("jit(_decode)/while/body/while/body/closed_call/mlp/dot_general:") == "mlp"
    assert looped_read.scope_of("jit(_decode)/kv_write/dynamic_update_slice:") == "kv_write"
    # the accepted readers do not know the new name, and their list is as it was after a call
    assert decoder_read.scope_of(name) == "unscoped"
    assert "pass_close" not in decoder_read.SCOPES


def run_of(work, counters, trace=None):
    return {"counters": counters, "trace": trace, "work": work, "peaks": PEAKS, "chips": 1,
            "setup": {}, "window": {}}


def test_new_readers_on_numbers_made_by_hand(monkeypatch):
    family = bench_run.load_module("families", PUBLISHED["family"])
    work = family.work_model(PUBLISHED, None)
    assert work["looped"] == {"passes": 4, "layer_weight_bytes": 4_934_074_368,
                              "head_bytes": 201_334_784, "embed_row_bytes": 4096,
                              "cache_bytes_per_position": 1_572_864}
    # 3 s traced: 50 decode executions of 56 ms: 22 under attention, 20 the MLPs, 0.2 closing passes
    by_scope = {"attn_full": 1.1, "mlp": 1.0, "pass_close": 0.01, "kv_write": 0.5, "head": 0.02,
                "unscoped": 0.17}
    monkeypatch.setattr(decoder_read, "scope_seconds", lambda program: (dict(by_scope), 50))
    # four snapshots: window open, span open, span close, window close
    monkeypatch.setattr(decoder_read, "_snapshots", [
        {"stack_passes": 0, "decode_steps": 0, "tokens": 0, "prefills": 0},
        {"stack_passes": 1000, "decode_steps": 250, "tokens": 900, "prefills": 4},
        {"stack_passes": 1200, "decode_steps": 300, "tokens": 1081, "prefills": 5},
        {"stack_passes": 2800, "decode_steps": 700, "tokens": 2500, "prefills": 11}])
    trace = {"programs": {"jit__decode": (2.8, 50)}}
    run = run_of(work, {"engine": {"prefills": 11, "decode_steps": 700},
                        "traced": {"decode_steps": 50, "live_kv_positions": 90_000}}, trace)
    read = lambda name: bench_run.load_module("metrics", name).read(run)  # noqa: E731
    assert abs(read("loop_attn_device_ms.serve") - 22.0) < 1e-9
    assert abs(read("loop_pass_device_ms.serve") - (22.0 + 20.0 + 0.2) / 4) < 1e-9
    # 200 passes x 4.93 GB + 50 heads + 180 rows + 90,000 positions x 1.57 MB = 1,138.4 GB:
    # 1.390 s at 819 GB/s of the 2.8 s the decode program ran
    required = 200 * 4_934_074_368 + 50 * 201_334_784 + 180 * 4096 + 90_000 * 1_572_864
    want = 100 * (required / 819e9) / 2.8
    assert abs(read("loop_hbm_share.serve") - want) < 1e-9 and 49.5 < want < 49.8


def test_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    """Another program's run: no trace; a trace none of whose operations lies
    under ``pass_close`` (the parent's program, whose stack runs once); no
    ``stack_passes`` counter. ``None``, no raise."""
    from lib import phases

    names = ("loop_pass_device_ms.serve", "loop_attn_device_ms.serve", "loop_hbm_share.serve")
    monkeypatch.setattr(decoder_read, "_snapshots", [])
    monkeypatch.setattr(phases, "_run", {"xplane": None, "ring": None})
    run = run_of({"decode_program": "jit__decode", "decode_weight_bytes": 1},
                 {"engine": {}, "traced": {"decode_steps": 3, "live_kv_positions": 5}})
    for name in names:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    monkeypatch.setattr(decoder_read, "scope_seconds",
                        lambda program: ({"attn_full": 1.0, "mlp": 0.5}, 10))
    family = bench_run.load_module("families", PUBLISHED["family"])
    run = run_of(family.work_model(PUBLISHED, None),
                 {"engine": {"prefills": 0, "decode_steps": 0},
                  "traced": {"decode_steps": 3, "live_kv_positions": 5}},
                 {"programs": {"jit__decode": (1.0, 10)}})
    for name in names:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    # the scope is there but the parent's counters have no ``stack_passes``
    monkeypatch.setattr(decoder_read, "scope_seconds", lambda program: ({"pass_close": 1.0}, 10))
    monkeypatch.setattr(decoder_read, "_snapshots", [{"tokens": 1, "decode_steps": 1}] * 4)
    for name in ("loop_pass_device_ms.serve", "loop_hbm_share.serve"):
        assert bench_run.load_module("metrics", name).read(run) is None, name


def test_the_scopes_tool_divides_the_step_by_pass():
    from tools import looped_scopes

    by_scope = {"attn_full": 1.1, "mlp": 1.0, "pass_close": 0.01, "kv_write": 0.5, "unscoped": 0.19}
    out = looped_scopes.by_pass(by_scope, 50, 4)
    assert out == {"program": "jit__decode", "passes_per_step": 4, "ms_per_step": 56.0,
                   "ms_per_pass": 10.55,
                   "ms_per_step_by_pass_scope": {"attn_full": 22.0, "mlp": 20.0, "pass_close": 0.2}}


def test_the_cell_and_its_metrics_are_declared():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell, config, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro-2.6b", "reason-looped", 1)
    assert config["family"] == "looped_decoder_lm" and traffic["kind"] == "open_loop_generate"
    assert config["reduced"] == ["max_position_embeddings"] and config["total_ut_steps"] == 4
    assert bench_run.load_limits(cell).keys() == {"served_logit_gap", "served_logit_gap_mean",
                                                  "requests_failed"}
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    for name in ("loop_pass_device_ms.serve", "loop_attn_device_ms.serve", "loop_hbm_share.serve"):
        assert mine[name]["workloads"] == [CELL] and mine[name]["moves"] == "itl_p95_ms"
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))
    assert {"prefill_ms.serve", "decode_step_ms.serve", "gen_late_p95_ms.serve",
            "device_idle_share.serve", "decode_put_ms.serve", "emit_ms.serve",
            "kv_write_device_ms.serve", "sample_device_ms.serve", "prefills_per_100_steps.serve",
            "launch_gap_ms.serve", "turn_ms.serve", "claim_gap_ms.serve"} <= set(mine)
    assert not {"host_turn_ms.serve", "launch_gap_runtime_ms.serve"} & set(mine)
    e2e = {m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"itl_p95_ms", "setup_s"}
    engine = traffic["engine"]
    assert (engine["n_slots"], engine["max_length"], engine["spec_decode_k"],
            engine["prefix_cache_mb"]) == (5, 896, 1, 0)
    assert engine["prefill_buckets"] == [32, 64, 128, 256]
    assert traffic["prompt_len"] == {"median": 96, "sigma": 0.6, "min": 32, "max": 256}
    assert traffic["answer_len"] == {"median": 320, "sigma": 0.5, "min": 128, "max": 640}
    assert (traffic["lead_in_s"], traffic["drain_s"], traffic["check_requests"],
            traffic["trace_seconds"]) == (30, 45, 6, 3)


def last_line(out):
    return json.loads(out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    assert bench_run.main(["--rehearse", "tiny-ouro:tiny-reason-looped", "--seed", "42",
                           "--seconds", "3"]) == 0
    out = capsys.readouterr().out
    assert last_line(out)["correct"] is True and last_line(out)["failed"] == 0
    detail = next(json.loads(l) for l in out.splitlines() if l.startswith('{"detail"'))
    assert detail["counters"]["engine"]["decode_steps"] > 100


def test_int8_control_is_refused_and_bfloat16_is_not():
    family = bench_run.load_module("families", TINY["family"])
    traffic = bench_run.load_json(HERE, "traffic", "tiny-reason-looped.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    limits = bench_run.load_limits({"config": "tiny-ouro", "traffic": "tiny-reason-looped"})
    for seed in (42, 44, 3000000019):
        out = kind.calibrate(family, TINY, traffic, seed, "int8", seconds=3.0)
        assert out["tokens_compared"] >= 300
        assert compare.judge(out["program"], {k: limits[k] for k in out["program"]})[0], out
        assert not compare.judge(out["control"], {k: limits[k] for k in out["control"]})[0], out

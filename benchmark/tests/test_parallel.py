"""The ``parallel_hybrid_decoder_lm`` family: its work functions against
counts made by hand at the tiny and the published sizes, its four readers on
names and counters made by hand, the shape of its limits files, and whole runs
of ``run.py`` at the tiny preset: a sound run is correct, the int8 control is
refused and bfloat16 is not. Run by hand:

    python3 -m pytest benchmark/tests/test_parallel.py -q
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
from lib import compare, decoder_read, parallel_read, work_parallel  # noqa: E402

PUBLISHED = bench_run.load_json(HERE, "configs", "falcon-h1-34b-l6.json")
TINY = bench_run.load_json(HERE, "configs", "tiny-falcon-h1.json")
PEAKS = bench_run.load_json(HERE, "peaks.json")["TPU v5 lite"]
CELL = "falcon-h1-34b-l6.docqa-steady"
NAMES = ("par_mixer_device_ms.serve", "par_attn_device_ms.serve",
         "par_mixer_hbm_share.serve", "par_hbm_share.serve")


def test_bytes_by_hand_at_the_tiny_size():
    """hidden 64; 10 query heads on 2 key heads of 16; 8 state-space heads of
    8 (inner 64, not 2 x 64), state 16, 2 groups, conv 4; MLP 128; vocabulary
    512; 3 layers; bfloat16 weights, a float32 state."""
    attention = 64 * 160 + 2 * 64 * 32 + 160 * 64
    conv = 64 + 2 * 2 * 16                                    # x | B | C
    ssm = 64 * (64 + conv + 8) + 64 * 64 + conv * 4 + conv     # in, out, conv weights and bias
    gains = 64 + 64 + 3 * 8                                   # norm1, the gated norm, dt_bias A_log D
    assert work_parallel.mixer_param_count(TINY) == (attention + ssm, gains)
    assert work_parallel.mixer_weight_bytes(TINY, 2) == 3 * ((attention + ssm) * 2 + gains * 4)
    mlp = 3 * 64 * 128
    assert work_parallel.step_weight_bytes(TINY, 2) == (
        work_parallel.mixer_weight_bytes(TINY, 2) + 3 * (mlp * 2 + 64 * 4) + 64 * 512 * 2 + 64 * 4)
    assert work_parallel.embed_row_bytes(TINY, 2) == 128
    assert work_parallel.state_values(TINY) == 8 * 8 * 16 and work_parallel.tail_values(TINY) == conv * 3
    assert work_parallel.state_bytes_per_live_slot(TINY, 2) == 3 * 2 * (1024 * 4 + 384 * 2)
    assert work_parallel.cache_bytes_per_position(TINY, 2) == 3 * 2 * 2 * 16 * 2
    step = work_parallel.decode_step_bytes(TINY, live_slots=3, live_positions=70)
    assert step == (work_parallel.step_weight_bytes(TINY, 2)
                    + 3 * (128 + 3 * 2 * (4096 + 768)) + 70 * 384)
    assert work_parallel.mixer_step_bytes(TINY, 3, 70) == (
        work_parallel.mixer_weight_bytes(TINY, 2) + 3 * 3 * 2 * (4096 + 768) + 70 * 384)


def test_bytes_by_hand_at_the_published_size():
    matrices, gains = work_parallel.mixer_param_count(PUBLISHED)
    attention = 5120 * (2560 + 512 + 512) + 2560 * 5120        # 31.46 M
    assert matrices == attention + 5120 * 9248 + 4096 * 5120 + 5120 * 5
    assert gains == 5120 + 4096 + 96
    # both mixers of six layers: 0.20 GB a layer, the issue's reckoning
    assert round(work_parallel.mixer_weight_bytes(PUBLISHED, 2) / 6 / 1e9, 2) == 0.20
    # every weight but the embedding: six layers of 0.86 GB and the head's 2.67 GB
    assert round(work_parallel.step_weight_bytes(PUBLISHED, 2) / 1e9, 1) == 7.8
    assert work_parallel.state_values(PUBLISHED) == 32 * 128 * 256 == 1_048_576   # 4.19 MB in float32
    assert work_parallel.state_bytes_per_live_slot(PUBLISHED, 2) == 6 * 2 * (4_194_304 + 5120 * 3 * 2)
    assert work_parallel.cache_bytes_per_position(PUBLISHED, 2) == 6 * 2048 == 12_288
    # the issue's step: 26 live slots at ~2k positions: ~9.8 GB = 12 ms at the peak
    step = work_parallel.decode_step_bytes(PUBLISHED, 26, 26 * 2048)
    assert 11.5 < 1e3 * step / PEAKS["hbm_bytes_per_s"] < 12.5


def test_scopes_by_operation_name():
    base = "jit(_decode)/while/body/closed_call/"
    assert parallel_read.scope_of(base + "mixer_join/mul:") == "mixer_join"
    assert parallel_read.scope_of(base + "attn_full/dot_general:") == "attn_full"
    assert parallel_read.scope_of(base + "ssm_proj/dot_general:") == "ssm_proj"
    assert parallel_read.scope_of(base + "ssm_scan/mul:") == "ssm_scan"
    assert parallel_read.scope_of("jit(_prefill)/state_write/dynamic_update_slice:") == "state_write"
    assert parallel_read.scope_of("jit(_decode)/kv_write/dynamic_update_slice:") == "kv_write"
    # the accepted readers do not know the new name, and their list is as it was after a call
    assert decoder_read.scope_of(base + "mixer_join/mul:") == "unscoped"
    assert "mixer_join" not in decoder_read.SCOPES


def run_of(work, counters, trace=None):
    return {"counters": counters, "trace": trace, "work": work, "peaks": PEAKS, "chips": 1,
            "setup": {}, "window": {}}


def test_the_four_readers_on_numbers_made_by_hand(monkeypatch):
    family = bench_run.load_module("families", PUBLISHED["family"])
    work = family.work_model(PUBLISHED, None)
    w = work["parallel"]
    assert work["decode_program"] == "jit__decode"
    assert w["cache_bytes_per_position"] == 12_288 and w["embed_row_bytes"] == 10_240
    # 3 s traced: 200 decode executions, 14 ms each; by scope, seconds over all of them
    by_scope = {"mixer_join": 0.04, "attn_full": 0.36, "ssm_proj": 0.4, "ssm_conv": 0.06,
                "ssm_scan": 0.34, "mlp": 1.1, "head": 0.45, "unscoped": 0.05}
    monkeypatch.setattr(decoder_read, "scope_seconds", lambda program: (dict(by_scope), 200))
    # four snapshots: window open, span open, span close, window close; 26 live slots a step
    # over the span, each with ~2,000 positions behind it
    snaps = [{"state_slots": 0, "attn_positions_read": 0},
             {"state_slots": 50_000, "attn_positions_read": 100_000_000},
             {"state_slots": 55_200, "attn_positions_read": 110_400_000},
             {"state_slots": 90_000, "attn_positions_read": 180_000_000}]
    monkeypatch.setattr(decoder_read, "_snapshots", snaps)
    trace = {"programs": {"jit__decode": (2.8, 200)}}
    run = run_of(work, {"engine": {"prefills": 6, "decode_steps": 200}}, trace)
    read = lambda name: bench_run.load_module("metrics", name).read(run)  # noqa: E731
    assert abs(read("par_mixer_device_ms.serve") - 6.0) < 1e-9
    assert abs(read("par_attn_device_ms.serve") - 1.8) < 1e-9
    cache = 5200 * w["state_bytes_per_live_slot"] + 10_400_000 * 12_288
    want = 100 * ((200 * w["mixer_weight_bytes"] + cache) / 819e9) / 1.2
    assert abs(read("par_mixer_hbm_share.serve") - want) < 1e-9 and 60 < want < 70
    whole = 100 * ((200 * w["step_weight_bytes"] + 5200 * 10_240 + cache) / 819e9) / 2.8
    assert abs(read("par_hbm_share.serve") - whole) < 1e-9 and 85 < whole < 100


def test_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    """Another program's run: no trace; a trace none of whose operations
    lies under ``mixer_join`` (the parent's program, or one out of the compile
    cache from before the scope); no counter. ``None``, no raise."""
    from lib import phases

    family = bench_run.load_module("families", PUBLISHED["family"])
    monkeypatch.setattr(decoder_read, "_snapshots", [])
    monkeypatch.setattr(phases, "_run", {"xplane": None, "ring": None})
    run = run_of({"decode_program": "jit__decode", "decode_weight_bytes": 1},
                 {"engine": {}, "traced": {"decode_steps": 3}})
    for name in NAMES:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    monkeypatch.setattr(decoder_read, "scope_seconds",
                        lambda program: ({"attn_full": 1.0, "ssm_scan": 0.5}, 10))
    run = run_of(family.work_model(PUBLISHED, None), {"engine": {"prefills": 0, "decode_steps": 0}},
                 {"programs": {"jit__decode": (1.0, 10)}})
    for name in NAMES:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    # the scopes are there but the counters are not
    monkeypatch.setattr(decoder_read, "scope_seconds", lambda program: ({"mixer_join": 1.0}, 10))
    monkeypatch.setattr(decoder_read, "_snapshots", [{"tokens": 1}] * 4)
    for name in NAMES[2:]:
        assert bench_run.load_module("metrics", name).read(run) is None, name


def test_the_cell_and_its_metrics_are_declared():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell, config, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("falcon-h1-34b-l6", "docqa-steady", 1)
    assert config["family"] == "parallel_hybrid_decoder_lm" and traffic["kind"] == "open_loop_generate"
    assert len(cell["why"]) <= 200
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    for name in NAMES:
        assert mine[name]["workloads"] == [CELL] and mine[name]["moves"] == "itl_p95_ms"
        assert mine[name]["layer"] == "Kernels" and mine[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))
    assert {"decode_step_ms.serve", "prefill_ms.serve", "sample_device_ms.serve",
            "kv_write_device_ms.serve", "prefills_per_100_steps.serve", "launch_gap_ms.serve",
            "claim_gap_ms.serve", "turn_ms.serve", "emit_ms.serve", "decode_put_ms.serve",
            "device_idle_share.serve", "gen_late_p95_ms.serve"} | set(NAMES) == set(mine)
    e2e = {m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"itl_p95_ms", "setup_s"}
    engine = traffic["engine"]
    assert (engine["max_length"], engine["spec_decode_k"], engine["prefix_cache_mb"]) == (4096, 1, 0)
    assert engine["n_slots"] % 8 == 0 and engine["n_slots"] <= 48
    assert engine["prefill_buckets"] == [256, 512, 1024, 1536, 2048]
    assert traffic["prompt_len"] == {"median": 1024, "sigma": 0.6, "min": 256, "max": 2048}
    assert traffic["answer_len"]["median"] == 1024
    assert (traffic["answer_len"]["min"], traffic["answer_len"]["max"]) == (256, 2048)


def test_the_limits_files_have_the_shape_of_the_others():
    for pair in (("falcon-h1-34b-l6", "docqa-steady"), ("tiny-falcon-h1", "tiny-docqa")):
        limits = bench_run.load_json(HERE, "limits", "%s.%s.json" % pair)
        assert (limits["config"], limits["traffic"]) == pair
        assert limits["limits"].keys() == {"served_logit_gap", "served_logit_gap_mean",
                                           "requests_failed"}
        assert limits["limits"]["requests_failed"] == 0 and limits["origin"]
        assert isinstance(limits["why"], dict)
    assert bench_run.load_json(HERE, "limits", CELL + ".json")["why"].keys() == {
        "served_logit_gap", "served_logit_gap_mean", "requests_failed"}


def last_line(out):
    return json.loads(out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    assert bench_run.main(["--rehearse", "tiny-falcon-h1:tiny-docqa", "--seed", "42",
                           "--seconds", "3"]) == 0
    out = capsys.readouterr().out
    assert last_line(out)["rehearsal_only"] is True
    assert last_line(out)["correct"] is True and last_line(out)["failed"] == 0
    detail = next(json.loads(l) for l in out.splitlines() if l.startswith('{"detail"'))
    assert detail["counters"]["engine"]["decode_steps"] > 100


def test_int8_control_is_refused_and_bfloat16_is_not():
    family = bench_run.load_module("families", TINY["family"])
    traffic = bench_run.load_json(HERE, "traffic", "tiny-docqa.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    limits = bench_run.load_limits({"config": "tiny-falcon-h1", "traffic": "tiny-docqa"})
    for seed in (42, 44, 3000000019):
        out = kind.calibrate(family, TINY, traffic, seed, "int8", seconds=3.0)
        assert out["tokens_compared"] >= 250
        assert compare.judge(out["program"], {k: limits[k] for k in out["program"]})[0], out
        assert not compare.judge(out["control"], {k: limits[k] for k in out["control"]})[0], out


def test_published_file_keeps_the_catalog_numbers():
    """Every key of the catalog row's ``config`` under the same key with the
    same value, but the two in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    for key, value in row["config"].items():
        if key in PUBLISHED["reduced"]:
            assert PUBLISHED["published"][key] == value and PUBLISHED[key] != value, key
        else:
            assert PUBLISHED[key] == value, key
    assert PUBLISHED["num_hidden_layers"] == 6 and PUBLISHED["deployment"]["chips_sharing_a_layer"] == 1
    assert PUBLISHED["deployment"]["pipeline_stages"] * PUBLISHED["deployment"]["layers_a_stage"] == 72
    assert set(PUBLISHED["init_std"]) == {"embed", "head", "attn.q", "attn.k", "attn.v", "attn.o",
                                          "mamba.in_proj", "mamba.conv_b", "mamba.out_proj",
                                          "mlp.gate", "mlp.up", "mlp.down"}

"""The ``hybrid_decoder_lm`` family: its work functions against counts made by
hand at the tiny and the published sizes, its readers on names and counters
made by hand, and whole runs of ``run.py`` at the tiny preset: a sound run is
correct, the int8 control is refused and bfloat16 is not. Run by hand:

    python3 -m pytest benchmark/tests/test_hybrid.py -q
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
from lib import compare, decoder_read, ssm_read, work_ssm  # noqa: E402

PUBLISHED = bench_run.load_json(HERE, "configs", "granite-4.0-h-small-ep2.json")
TINY = bench_run.load_json(HERE, "configs", "tiny-granite.json")
PEAKS = bench_run.load_json(HERE, "peaks.json")["TPU v5 lite"]
CELL = "granite-4.0-h-small-ep2.longform-sessions"


def test_state_and_expert_bytes_by_hand():
    assert work_ssm.ssm_layers(PUBLISHED) == 9 and work_ssm.ssm_layers(TINY) == 3
    assert work_ssm.state_values(PUBLISHED) == 128 * 64 * 128 == 1_048_576     # 4.19 MB in float32
    assert work_ssm.tail_values(PUBLISHED) == 8448 * 3
    # a live slot: nine layers, the state read and written in float32, the tail in bfloat16
    assert work_ssm.state_bytes_per_live_slot(PUBLISHED, 2) == 9 * 2 * (4_194_304 + 50_688) == 76_409_856
    assert work_ssm.state_bytes_per_live_slot(TINY, 2) == 3 * 2 * (8 * 16 * 16 * 4 + 160 * 3 * 2)
    assert work_ssm.expert_bytes(PUBLISHED, 2) == 3 * 4096 * 768 * 2 == 18_874_368
    assert work_ssm.expert_bytes(TINY, 2) == 3 * 64 * 32 * 2
    # two operations a state value and step against eight bytes: bound by bytes, 60 x under the ridge
    assert 2 * 2 / 8 < PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"] / 60


def test_scopes_by_operation_name():
    name = "jit(_decode)/while/body/closed_call/ssm_scan/mul:"
    assert ssm_read.scope_of(name) == "ssm_scan"
    assert ssm_read.scope_of("jit(_decode)/while/body/closed_call/ssm_proj/dot_general:") == "ssm_proj"
    assert ssm_read.scope_of("jit(_decode)/while/body/closed_call/ssm_conv/reduce_sum:") == "ssm_conv"
    assert ssm_read.scope_of("jit(_prefill)/state_write/dynamic_update_slice:") == "state_write"
    assert ssm_read.scope_of("jit(_decode)/while/body/closed_call/moe_shared/dot_general:") == "moe_shared"
    assert ssm_read.scope_of("ragged-dot-none:") == "moe_experts"
    assert ssm_read.scope_of("jit(_decode)/kv_write/dynamic_update_slice:") == "kv_write"
    # the accepted readers do not know the new names, and their list is as it was after a call
    assert decoder_read.scope_of(name) == "unscoped"
    assert "ssm_scan" not in decoder_read.SCOPES


def run_of(work, counters, trace=None):
    return {"counters": counters, "trace": trace, "work": work, "peaks": PEAKS, "chips": 1,
            "setup": {}, "window": {}}


def test_new_readers_on_numbers_made_by_hand(monkeypatch):
    family = bench_run.load_module("families", PUBLISHED["family"])
    work = family.work_model(PUBLISHED, None)
    assert work["ssm_state"] == {"bytes_per_live_slot": 76_409_856}
    assert work["expert_bytes"] == 18_874_368 and work["decode_program"] == "jit__decode"
    # 3 s traced: 100 decode executions, 1.1 s under the scan, 0.3 s under the projections, 0.05 s the convolution
    by_scope = {"ssm_scan": 1.1, "ssm_proj": 0.3, "ssm_conv": 0.05, "moe_experts": 1.2, "unscoped": 0.1}
    monkeypatch.setattr(decoder_read, "scope_seconds", lambda program: (dict(by_scope), 100))
    # four snapshots: window open, span open, span close, window close; 42 live slots a step over the span
    monkeypatch.setattr(decoder_read, "_snapshots", [{"state_slots": 0}, {"state_slots": 50_000},
                                                     {"state_slots": 54_200}, {"state_slots": 90_000}])
    run = run_of(work, {"engine": {"prefills": 2, "decode_steps": 100}, "traced": {"decode_steps": 100}})
    read = lambda name: bench_run.load_module("metrics", name).read(run)  # noqa: E731
    assert abs(read("ssm_device_ms.serve") - 14.5) < 1e-9
    # 4,200 live slot-steps x 76.4 MB = 320.9 GB: 0.3918 s at 819 GB/s of the 1.1 s under the scan
    want = 100 * (4200 * 76_409_856 / 819e9) / 1.1
    assert abs(read("ssm_state_roofline_share.serve") - want) < 1e-9 and 35.5 < want < 35.7


def test_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    """Another program's run: no trace; a trace none of whose operations
    carries a state-space scope (the parent's program, or one out of the
    compile cache from before the scopes); no counter. ``None``, no raise."""
    from lib import phases

    names = ("ssm_device_ms.serve", "ssm_state_roofline_share.serve")
    monkeypatch.setattr(decoder_read, "_snapshots", [])
    monkeypatch.setattr(phases, "_run", {"xplane": None, "ring": None})
    run = run_of({"decode_program": "jit__decode", "decode_weight_bytes": 1},
                 {"engine": {}, "traced": {"decode_steps": 3}})
    for name in names:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    monkeypatch.setattr(decoder_read, "scope_seconds",
                        lambda program: ({"attn_full": 1.0, "moe_experts": 0.5}, 10))
    family = bench_run.load_module("families", PUBLISHED["family"])
    run = run_of(family.work_model(PUBLISHED, None), {"engine": {"prefills": 0, "decode_steps": 0}})
    for name in names:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    # the scopes are there but the parent's counters have no ``state_slots``
    monkeypatch.setattr(decoder_read, "scope_seconds", lambda program: ({"ssm_scan": 1.0}, 10))
    monkeypatch.setattr(decoder_read, "_snapshots", [{"tokens": 1}] * 4)
    assert bench_run.load_module("metrics", names[1]).read(run) is None


def test_the_cell_and_its_metrics_are_declared():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell, config, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-4.0-h-small-ep2",
                                                                "longform-sessions", 1)
    assert config["family"] == "hybrid_decoder_lm" and traffic["kind"] == "open_loop_generate"
    assert bench_run.load_limits(cell).keys() == {"served_logit_gap", "served_logit_gap_mean",
                                                  "requests_failed"}
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    for name in ("ssm_device_ms.serve", "ssm_state_roofline_share.serve"):
        assert mine[name]["workloads"] == [CELL] and mine[name]["moves"] == "itl_p95_ms"
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))
    assert {"moe_hbm_share.serve", "moe_device_ms.serve", "prefills_per_100_steps.serve",
            "decode_step_ms.serve", "kv_write_device_ms.serve"} <= set(mine)
    e2e = {m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"itl_p95_ms", "setup_s"}
    engine = traffic["engine"]
    assert (engine["n_slots"], engine["max_length"], engine["spec_decode_k"],
            engine["prefix_cache_mb"]) == (64, 4096, 1, 0)
    assert engine["prefill_buckets"] == [128, 256, 512, 1024, 1536]
    assert traffic["prompt_len"] == {"median": 384, "sigma": 0.7, "min": 64, "max": 1536}
    assert traffic["answer_len"] == {"median": 1280, "sigma": 0.5, "min": 384, "max": 2560}


def last_line(out):
    return json.loads(out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    assert bench_run.main(["--rehearse", "tiny-granite:tiny-longform", "--seed", "42", "--seconds", "3"]) == 0
    out = capsys.readouterr().out
    assert last_line(out)["correct"] is True and last_line(out)["failed"] == 0
    detail = next(json.loads(l) for l in out.splitlines() if l.startswith('{"detail"'))
    assert detail["counters"]["engine"]["decode_steps"] > 100


def test_int8_control_is_refused_and_bfloat16_is_not():
    family = bench_run.load_module("families", TINY["family"])
    traffic = bench_run.load_json(HERE, "traffic", "tiny-longform.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    limits = bench_run.load_limits({"config": "tiny-granite", "traffic": "tiny-longform"})
    for seed in (42, 44, 3000000019):
        out = kind.calibrate(family, TINY, traffic, seed, "int8", seconds=3.0)
        assert out["tokens_compared"] >= 300
        assert compare.judge(out["program"], {k: limits[k] for k in out["program"]})[0], out
        assert not compare.judge(out["control"], {k: limits[k] for k in out["control"]})[0], out


def test_published_file_keeps_the_catalog_numbers():
    """Every key of the catalog row's ``config`` under the same key with the
    same value (``layer_types`` whole), but the four in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
    assert PUBLISHED["source"] == row["source_url"]
    assert sorted(PUBLISHED["reduced"]) == sorted(["num_hidden_layers", "num_local_experts",
                                                   "vocab_size", "max_position_embeddings"])
    for key, value in row["config"].items():
        if key in PUBLISHED["reduced"]:
            assert PUBLISHED["published"][key] == value and PUBLISHED[key] != value, key
        else:
            assert PUBLISHED[key] == value, key
    # the layers built are the pattern's first period
    assert PUBLISHED["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert PUBLISHED["num_hidden_layers"] == 10 and PUBLISHED["deployment"]["chips_sharing_a_layer"] == 2

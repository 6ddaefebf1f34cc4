"""The ``sparse_latent_decoder_lm`` family: its work functions against counts
made by hand at the tiny and the published sizes, its four readers on names,
counters and scope times made by hand, and whole runs of ``run.py`` at the
tiny preset: a sound run is correct, the int8 control is refused and
bfloat16 is not, and ``tools/sparse_gap_readings.py`` runs. Run by hand:

    python3 -m pytest benchmark/tests/test_sparse.py -q
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
from lib import compare, decoder_read, sparse_read, work_sparse  # noqa: E402

PUBLISHED = bench_run.load_json(HERE, "configs", "glm-5.2-ep16.json")
TINY = bench_run.load_json(HERE, "configs", "tiny-glm.json")
PEAKS = bench_run.load_json(HERE, "peaks.json")["TPU v5 lite"]
NEW = ("index_device_ms.serve", "sparse_core_device_ms.serve",
       "index_roofline_share.serve", "sparse_core_roofline_share.serve")


def test_expert_bytes_and_indexer_work_by_hand():
    # one routed expert: three matrices of 6144 x 2048 in bfloat16 = 75.5 MB
    assert work_sparse.expert_bytes(PUBLISHED, 2) == 3 * 6144 * 2048 * 2 == 75_497_472
    # layers 0 and 6 of the five kept own an indexer: one key of 128 a position each
    assert work_sparse.index_layers(PUBLISHED) == 2
    assert work_sparse.index_bytes_per_position(PUBLISHED, 2) == 2 * 128 * 2 == 512
    # 32 heads x (a dot product of 128 + its weighted ReLU term), multiply-adds, two layers
    assert work_sparse.index_flops_per_position(PUBLISHED) == 2 * 2 * 32 * 129 == 16_512
    # tiny: layers 0 and 4 of [0, 2, 3, 4] own one; 4 heads of 32
    assert work_sparse.index_layers(TINY) == 2
    assert work_sparse.index_bytes_per_position(TINY, 2) == 2 * 32 * 2
    assert work_sparse.index_flops_per_position(TINY) == 2 * 2 * 4 * 33
    # 512 B and 16,512 operations a position: 32 operations a byte against a ridge of 240.5,
    # so scoring is bound by the key cache's bytes
    work = {"flops_per_position": 16_512, "bytes_per_position": 512}
    assert work_sparse.least_seconds(1e9, work, PEAKS) == 1e9 * 512 / PEAKS["hbm_bytes_per_s"]


def test_selected_entries_work_by_hand():
    assert work_sparse.sparse_values_per_position(PUBLISHED) == 512 + 64
    # every one of the five layers attends to the selection: 5 x 576 x 2 B
    assert work_sparse.sparse_bytes_per_position(PUBLISHED, 2) == 5 * 1152 == 5760
    # a head scores a 576-wide entry and sums a 512-wide latent: 64 x (576 + 512) multiply-adds
    assert work_sparse.sparse_flops_per_position(PUBLISHED) == 5 * 64 * (576 + 512) * 2 == 696_320
    assert work_sparse.sparse_bytes_per_position(TINY, 2) == 4 * 32 * 2
    assert work_sparse.sparse_flops_per_position(TINY) == 4 * 2 * 4 * (32 + 16)
    # 121 operations a byte against a ridge of 240.5: a selected entry is bound by its bytes
    # (half the deepseek cell's 242: half the heads share an entry)
    assert abs(696_320 / 5760 - 120.9) < 0.1
    work = {"flops_per_position": 696_320, "bytes_per_position": 5760}
    assert work_sparse.least_seconds(1e6, work, PEAKS) == 1e6 * 5760 / PEAKS["hbm_bytes_per_s"]
    # were the operations the larger, they would be taken
    assert work_sparse.least_seconds(1.0, {"flops_per_position": 1e6, "bytes_per_position": 1},
                                     PEAKS) == 1e6 / PEAKS["bf16_flops_per_s"]


def test_scopes_by_operation_name():
    under = "jit(_decode)/while/body/closed_call/attn_latent_proj/"
    assert sparse_read.scope_of(under + "attn_index_score/dot_general:") == "attn_index_score"
    assert sparse_read.scope_of(under + "attn_index_select/top_k:") == "attn_index_select"
    assert sparse_read.scope_of(under + "attn_index_proj/dot_general:") == "attn_index_proj"
    assert sparse_read.scope_of(under + "attn_sparse_core/gather:") == "attn_sparse_core"
    assert sparse_read.scope_of(under + "mul:") == "attn_latent_proj"   # the innermost scope
    assert sparse_read.scope_of("jit(_decode)/while/body/closed_call/moe_shared/dot_general:") == "moe_shared"
    assert sparse_read.scope_of("ragged-dot-none:") == "moe_experts"
    assert sparse_read.scope_of("jit(_decode)/kv_write/dynamic_update_slice:") == "kv_write"
    # the accepted readers do not know the new names, and their list is as it was after a call
    assert decoder_read.scope_of(under + "attn_sparse_core/gather:") == "unscoped"
    assert "attn_sparse_core" not in decoder_read.SCOPES


def run_of(work, counters=None):
    return {"counters": counters or {}, "trace": None, "work": work, "peaks": PEAKS, "chips": 1,
            "setup": {}, "window": {}}


def snapshots(monkeypatch, scored, read):
    """Four snapshots as the kind takes them (window open, span open, span
    close, window close); the span's deltas are ``scored`` and ``read``."""
    rows = [{"index_positions_scored": a, "sparse_positions_read": b, "moe_experts_hit": 0}
            for a, b in ((0, 0), (1000, 500), (1000 + scored, 500 + read),
                         (5 * scored, 5 * read))]
    monkeypatch.setattr(decoder_read, "_snapshots", rows)


def test_new_readers_on_numbers_made_by_hand(monkeypatch):
    family = bench_run.load_module("families", PUBLISHED["family"])
    work = family.work_model(PUBLISHED, None)
    assert work["expert_bytes"] == 75_497_472
    assert work["index"] == {"flops_per_position": 16_512, "bytes_per_position": 512}
    assert work["sparse_core"] == {"flops_per_position": 696_320, "bytes_per_position": 5760}
    # 3 s traced, 200 decode executions: the indexer 0.02 + 0.10 + 0.18 s, the core 0.9 s
    by_scope = {"attn_index_proj": 0.02, "attn_index_score": 0.10, "attn_index_select": 0.18,
                "attn_sparse_core": 0.9, "attn_latent_proj": 0.3, "moe_experts": 0.5,
                "unscoped": 0.1}
    monkeypatch.setattr(decoder_read, "scope_seconds", lambda program: (dict(by_scope), 200))
    # 200 steps x 20 slots x ~9,000 positions behind; 2,048 of them read a slot and step
    snapshots(monkeypatch, scored=36_000_000, read=8_192_000)
    run = run_of(work)
    read = lambda name: bench_run.load_module("metrics", name).read(run)  # noqa: E731
    assert abs(read("index_device_ms.serve") - 1.5) < 1e-9
    assert abs(read("sparse_core_device_ms.serve") - 4.5) < 1e-9
    # 36 M positions x 512 B at 819 GB/s = 22.5 ms of the 280 under score + select
    want = 100 * (36e6 * 512 / PEAKS["hbm_bytes_per_s"]) / 0.28
    assert abs(read("index_roofline_share.serve") - want) < 1e-9 and 8.0 < want < 8.1
    # 8.192 M entries x 5,760 B = 57.6 ms of the 900 under the core
    want = 100 * (8.192e6 * 5760 / PEAKS["hbm_bytes_per_s"]) / 0.9
    assert abs(read("sparse_core_roofline_share.serve") - want) < 1e-9 and 6.4 < want < 6.5
    # the accepted expert share reads this family's expert bytes
    monkeypatch.setattr(decoder_read, "_snapshots",
                        [{"moe_experts_hit": v} for v in (0, 100, 100 + 1000, 5000)])
    want = 100 * (1000 * 75_497_472 / PEAKS["hbm_bytes_per_s"]) / 0.5
    assert abs(read("moe_hbm_share.serve") - want) < 1e-9


def test_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    """Another program's run: no trace; a trace none of whose operations
    carries a scope of the selection (the parent's program, or one out of
    the compile cache from before the scopes); no counters (the parent's
    engine has none). ``None``, no raise."""
    from lib import phases

    monkeypatch.setattr(decoder_read, "_snapshots", [])
    monkeypatch.setattr(phases, "_run", {"xplane": None, "ring": None})
    run = run_of({"decode_program": "jit__decode", "decode_weight_bytes": 1})
    for name in NEW:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    monkeypatch.setattr(decoder_read, "scope_seconds",
                        lambda program: ({"attn_latent_core": 1.0, "moe_experts": 0.5}, 10))
    family = bench_run.load_module("families", PUBLISHED["family"])
    run = run_of(family.work_model(PUBLISHED, None))
    for name in NEW:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    # the scopes are there and the counters are not (the parent's snapshots)
    monkeypatch.setattr(decoder_read, "scope_seconds",
                        lambda program: ({"attn_index_score": 1.0, "attn_sparse_core": 0.5}, 10))
    monkeypatch.setattr(decoder_read, "_snapshots", [{"moe_experts_hit": 1}] * 4)
    assert bench_run.load_module("metrics", "index_roofline_share.serve").read(run) is None
    assert bench_run.load_module("metrics", "sparse_core_roofline_share.serve").read(run) is None
    assert bench_run.load_module("metrics", "index_device_ms.serve").read(run) == 100.0


def test_benchmark_entries_name_the_cell_and_the_four_metrics():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell = "glm-5.2-ep16.longctx-agent"
    assert [w for w in bench["workloads"] if w["name"] == cell][0]["chips"] == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [cell] and by_name[name]["moves"] == "itl_p95_ms"
        assert os.path.exists(os.path.join(HERE, "metrics", f"{name}.py"))
    # in no list of a metric that reads null for a _DecoderBackend cell since PR 39
    for name in ("host_turn_ms.serve", "launch_gap_runtime_ms.serve", "hbm_share.serve",
                 "latent_attn_device_ms.serve", "slot_occupancy.serve"):
        assert cell not in by_name[name]["workloads"]
    assert cell in by_name["moe_hbm_share.serve"]["workloads"]


def last_line(out):
    return json.loads(out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    assert bench_run.main(["--rehearse", "tiny-glm:tiny-longctx", "--seed", "42", "--seconds", "3"]) == 0
    out = capsys.readouterr().out
    assert last_line(out)["correct"] is True and last_line(out)["failed"] == 0
    detail = next(json.loads(l) for l in out.splitlines() if l.startswith('{"detail"'))
    assert detail["counters"]["engine"]["decode_steps"] > 100


def test_int8_control_is_refused_and_bfloat16_is_not():
    family = bench_run.load_module("families", TINY["family"])
    traffic = bench_run.load_json(HERE, "traffic", "tiny-longctx.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    limits = bench_run.load_limits({"config": "tiny-glm", "traffic": "tiny-longctx"})
    # seeds that stand clear at this width (limits/tiny-glm.tiny-longctx.json)
    for seed in SEEDS_CLEAR:
        out = kind.calibrate(family, TINY, traffic, seed, "int8", seconds=3.0)
        assert out["tokens_compared"] >= 300
        assert compare.judge(out["program"], {k: limits[k] for k in out["program"]})[0], out
        assert not compare.judge(out["control"], {k: limits[k] for k in out["control"]})[0], out


SEEDS_CLEAR = (42, 44, 3000000019)


def test_gap_readings_tool_runs_at_the_tiny_size():
    """``tools/sparse_gap_readings.py`` end to end on the CPU: its three
    lines, a sound gap equal to the harness's, selections that differ
    from the reference's at few positions by one or two entries of eight,
    and a random wrong token that reads further below the best than the
    runner-up does."""
    import subprocess

    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "sparse_gap_readings.py"),
         "--rehearse", "tiny-glm:tiny-longctx", "--seed", "42"],
        stdout=subprocess.PIPE, text=True, check=True)
    lines = {r["reading"]: r for r in map(json.loads, done.stdout.splitlines())}
    assert set(lines) == {"sound", "selection_flips", "wrong_token"}
    flips = lines["selection_flips"]
    assert flips["positions"] == lines["sound"]["tokens_compared"] and flips["index_topk"] == 8
    assert len(flips["share_by_owning_layer"]) == 2
    assert 0 <= flips["share_of_positions_with_another_selection"] < 0.3
    assert flips["entries_apart_where_apart"]["most"] <= 4
    widest = max(flips["served_tokens"][k].get("widest", 0.0) for k in flips["served_tokens"])
    assert widest == lines["sound"]["served_logit_gap"]
    wrong = lines["wrong_token"]
    assert wrong["positions"] == flips["positions"]
    assert 0 <= wrong["runner_up"]["p50"] <= wrong["random_other_id"]["p50"]
    shares = list(wrong["share_of_random_wrong_tokens_refused_at_limit"].values())
    assert shares == sorted(shares, reverse=True) and 0 <= shares[-1] <= shares[0] <= 1


def test_published_file_keeps_the_catalog_numbers():
    """Every number of the catalog row's ``config`` under the same key,
    but the four in ``reduced``; nested groups (``rope_parameters``, the two
    lists of layer types) whole: the layers kept are named in
    ``deployment.layers``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    assert PUBLISHED["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in PUBLISHED["reduced"]:
            assert PUBLISHED["published"][key] == value and PUBLISHED[key] != value, key
        else:
            assert PUBLISHED[key] == value, key
    assert len(PUBLISHED["deployment"]["layers"]) == PUBLISHED["num_hidden_layers"] == 5

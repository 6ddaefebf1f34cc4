"""The ``latent_decoder_lm`` family: its work functions against counts made
by hand at the tiny and the published sizes, its readers on names and
counters made by hand, and whole runs of ``run.py`` at the tiny preset: a
sound run is correct, the int8 control is refused and bfloat16 is not, and
``tools/latent_gap_readings.py`` runs. Run by hand:

    python3 -m pytest benchmark/tests/test_latent.py -q
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
from lib import compare, decoder_read, latent_read, work_latent  # noqa: E402

PUBLISHED = bench_run.load_json(HERE, "configs", "deepseek-v2-ep8.json")
TINY = bench_run.load_json(HERE, "configs", "tiny-deepseek.json")
PEAKS = bench_run.load_json(HERE, "peaks.json")["TPU v5 lite"]


def test_attention_and_expert_counts_by_hand():
    # q down 5120 x 1536, q up 1536 x 128 x 192, kv down 5120 x 576, kv up 512 x 128 x 256, out 16384 x 5120
    assert work_latent.attention_weight_count(PUBLISHED) == (
        7_864_320 + 37_748_736 + 2_949_120 + 16_777_216 + 83_886_080) == 149_225_472
    assert work_latent.expert_bytes(PUBLISHED, 2) == 3 * 5120 * 1536 * 2 == 47_185_920
    # tiny: 64 x 24, 24 x 4 x 32, 64 x 32, 16 x 4 x 28, 48 x 64
    assert work_latent.attention_weight_count(TINY) == 1536 + 3072 + 2048 + 1792 + 3072
    assert work_latent.expert_bytes(TINY, 2) == 3 * 64 * 32 * 2


def test_fixed_decode_bytes_by_hand():
    attn = 6 * 149_225_472
    dense = 3 * 5120 * 12288                          # layer 0
    shared = 5 * 3 * 5120 * 3072                      # two shared experts of 1536, layers 1-5
    head = 5120 * 12800
    stored = 2 * (attn + dense + shared + head)
    norms = 4 * (6 * (2 * 5120 + 1536 + 512) + 5120)  # two gains a layer, the latents' two, the final one
    router = 4 * 5 * 5120 * 160                       # no bias
    assert work_latent.decode_fixed_weight_bytes(PUBLISHED, 2) == stored + norms + router
    assert 2.7e9 < stored + norms + router < 2.9e9
    tiny = (2 * (3 * 11520 + 3 * 64 * 128 + 2 * 3 * 64 * 64 + 64 * 256)
            + 4 * (3 * (128 + 24 + 16) + 64) + 4 * 2 * 64 * 16)
    assert work_latent.decode_fixed_weight_bytes(TINY, 2) == tiny


def test_latent_cache_and_absorbed_operations_by_hand():
    assert work_latent.latent_values_per_position(PUBLISHED) == 512 + 64
    assert work_latent.latent_bytes_per_position(PUBLISHED, 2) == 6 * 576 * 2 == 6912
    # a head scores a 576-wide entry and sums a 512-wide latent: 128 x (576 + 512) multiply-adds
    assert work_latent.absorbed_flops_per_position(PUBLISHED) == 128 * (576 + 512) * 2 == 278_528
    assert work_latent.latent_bytes_per_position(TINY, 2) == 3 * 32 * 2
    assert work_latent.absorbed_flops_per_position(TINY) == 2 * 4 * (32 + 16)
    # 1,152 B and 278,528 operations a position and layer: 242 operations a byte against a ridge of 240.5
    assert abs(278_528 / 1152 - 241.8) < 0.1 and abs(PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"] - 240.5) < 0.1
    # so live positions are bound by compute, by a hair (the roofline reader takes the larger)
    assert 6 * 278_528 / PEAKS["bf16_flops_per_s"] > 6912 / PEAKS["hbm_bytes_per_s"]


def test_scopes_by_operation_name():
    name = "jit(_decode)/while/body/closed_call/attn_latent_proj/attn_latent_core/dot_general:"
    assert latent_read.scope_of(name) == "attn_latent_core"        # the innermost scope
    assert latent_read.scope_of("jit(_decode)/while/body/closed_call/attn_latent_proj/mul:") == "attn_latent_proj"
    assert latent_read.scope_of("jit(_decode)/while/body/closed_call/moe_shared/dot_general:") == "moe_shared"
    assert latent_read.scope_of("jit(_decode)/while/body/closed_call/moe_route/top_k:") == "moe_route"
    assert latent_read.scope_of("ragged-dot-none:") == "moe_experts"
    assert latent_read.scope_of("jit(_decode)/kv_write/dynamic_update_slice:") == "kv_write"
    # the accepted readers do not know the new names, and their list is as it was after a call
    assert decoder_read.scope_of(name) == "unscoped"
    assert "attn_latent_core" not in decoder_read.SCOPES


def run_of(work, counters, trace=None):
    return {"counters": counters, "trace": trace, "work": work, "peaks": PEAKS, "chips": 1,
            "setup": {}, "window": {}}


def test_new_readers_on_numbers_made_by_hand(monkeypatch):
    family = bench_run.load_module("families", PUBLISHED["family"])
    work = family.work_model(PUBLISHED, None)
    assert work["latent_core"] == {"flops_per_position": 6 * 278_528, "bytes_per_position": 6912}
    # 3 s traced: 100 decode executions, 1.2 s under the core, 0.3 s under the projections
    by_scope = {"attn_latent_core": 1.2, "attn_latent_proj": 0.3, "moe_experts": 0.5, "unscoped": 0.1}
    monkeypatch.setattr(decoder_read, "scope_seconds", lambda program: (dict(by_scope), 100))
    counters = {"engine": {"prefills": 2, "decode_steps": 100},
                "traced": {"live_kv_positions": 14_000_000, "decode_steps": 100}}
    run = run_of(work, counters)
    read = lambda name: bench_run.load_module("metrics", name).read(run)  # noqa: E731
    assert abs(read("latent_attn_device_ms.serve") - 15.0) < 1e-9
    # 14 M positions: 6 x 278,528 x 14e6 / 197e12 = 118.76 ms of the 1,200 under the core
    assert abs(read("latent_core_roofline_share.serve") - 100 * (6 * 278_528 * 14e6 / 197e12) / 1.2) < 1e-9
    assert 9.8 < read("latent_core_roofline_share.serve") < 10.0
    assert read("prefills_per_100_steps.serve") == 2.0


def test_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    """Another program's run: no trace; a trace none of whose operations
    carries a latent scope (the parent's program, or one out of the
    compile cache from before the scopes); no counters. ``None``, no raise."""
    from lib import phases

    names = ("latent_attn_device_ms.serve", "latent_core_roofline_share.serve",
             "prefills_per_100_steps.serve")
    monkeypatch.setattr(decoder_read, "_snapshots", [])
    monkeypatch.setattr(phases, "_run", {"xplane": None, "ring": None})
    run = run_of({"decode_program": "jit__decode", "decode_weight_bytes": 1},
                 {"engine": {}, "traced": {"decode_steps": 3}})
    for name in names:
        assert bench_run.load_module("metrics", name).read(run) is None, name
    monkeypatch.setattr(decoder_read, "scope_seconds",
                        lambda program: ({"attn_full": 1.0, "moe_experts": 0.5}, 10))
    family = bench_run.load_module("families", PUBLISHED["family"])
    run = run_of(family.work_model(PUBLISHED, None),
                 {"engine": {"prefills": 0, "decode_steps": 0}, "traced": {"live_kv_positions": 5}})
    for name in names:
        assert bench_run.load_module("metrics", name).read(run) is None, name


def last_line(out):
    return json.loads(out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    assert bench_run.main(["--rehearse", "tiny-deepseek:tiny-longdoc", "--seed", "42", "--seconds", "3"]) == 0
    out = capsys.readouterr().out
    assert last_line(out)["correct"] is True and last_line(out)["failed"] == 0
    detail = next(json.loads(l) for l in out.splitlines() if l.startswith('{"detail"'))
    assert detail["counters"]["engine"]["decode_steps"] > 100


def test_int8_control_is_refused_and_bfloat16_is_not():
    family = bench_run.load_module("families", TINY["family"])
    traffic = bench_run.load_json(HERE, "traffic", "tiny-longdoc.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    limits = bench_run.load_limits({"config": "tiny-deepseek", "traffic": "tiny-longdoc"})
    # seeds that stand clear at this width (limits/tiny-deepseek.tiny-longdoc.json)
    for seed in (42, 44, 3000000019):
        out = kind.calibrate(family, TINY, traffic, seed, "int8", seconds=3.0)
        assert out["tokens_compared"] >= 300
        assert compare.judge(out["program"], {k: limits[k] for k in out["program"]})[0], out
        assert not compare.judge(out["control"], {k: limits[k] for k in out["control"]})[0], out


def test_gap_readings_tool_runs_at_the_tiny_size():
    """``tools/latent_gap_readings.py`` end to end on the CPU: its four
    lines, a planted wrong token read far above the sound gap, and a sound
    gap of its own pass equal to the harness's."""
    import subprocess

    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "latent_gap_readings.py"),
         "--rehearse", "tiny-deepseek:tiny-longdoc", "--seed", "42"],
        stdout=subprocess.PIPE, text=True, check=True)
    lines = {r["reading"]: r for r in map(json.loads, done.stdout.splitlines())}
    assert set(lines) == {"sound", "planted", "wrong_token", "expert_flips"}
    assert min(lines["planted"]["planted_tokens_read"]) > 10 * lines["sound"]["served_logit_gap"]
    assert lines["wrong_token"]["sound_widest_by_this_pass"] == lines["sound"]["served_logit_gap"]
    assert lines["wrong_token"]["positions"] == lines["sound"]["tokens_compared"]
    assert 0 <= lines["expert_flips"]["share_of_positions_with_a_flip"] < 0.2


def test_published_file_keeps_the_catalog_numbers():
    """Every number of the catalog row's ``config`` under the same key,
    but the four in ``reduced``; nested groups whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2")
    assert PUBLISHED["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in PUBLISHED["reduced"]:
            assert PUBLISHED["published"][key] == value and PUBLISHED[key] != value, key
        else:
            assert PUBLISHED[key] == value, key

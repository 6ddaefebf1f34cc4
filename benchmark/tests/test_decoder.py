"""The ``decoder_lm`` family: its work functions against counts made by
hand, its readers on names and counters made by hand, and whole runs of
``run.py`` at the tiny preset: a sound run is correct, the int8 control is
refused, a served token altered where it is produced is refused. Run by hand:

    python3 -m pytest benchmark/tests/test_decoder.py -q
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
from lib import compare, decoder_read, work_decoder  # noqa: E402

MIMO = bench_run.load_json(HERE, "configs", "mimo-v2.5-ep16.json")
TINY = bench_run.load_json(HERE, "configs", "tiny-mimo.json")


def test_expert_and_attention_counts_by_hand():
    assert work_decoder.expert_bytes(MIMO, 2) == 3 * 4096 * 2048 * 2 == 50_331_648
    # full: q 4096 x 12288, k 4096 x 768, v 4096 x 512, o 8192 x 4096
    assert work_decoder.attention_weight_count(MIMO, 0) == 50_331_648 + 3_145_728 + 2_097_152 + 33_554_432
    # window: 8 key/value heads
    assert work_decoder.attention_weight_count(MIMO, 1) == 50_331_648 + 6_291_456 + 4_194_304 + 33_554_432


def test_fixed_decode_bytes_by_hand():
    attn = 2 * 89_128_960 + 9 * 94_371_840            # layers 0 and 5 full, nine window
    dense = 3 * 4096 * 16384                          # layer 0
    head = 4096 * 19072
    stored = 2 * (attn + dense + head)
    norms = 4 * (11 * 2 * 4096 + 4096)                # two gains a layer, the final norm
    sinks = 4 * 9 * 64
    router = 4 * 10 * (4096 + 1) * 256
    assert work_decoder.decode_fixed_weight_bytes(MIMO, 2) == stored + norms + sinks + router


def test_cache_bytes_by_layer_kind():
    full, window = work_decoder.cache_bytes_per_position(MIMO, 2)
    assert full == 2 * 4 * (192 + 128) * 2 and window == 9 * 8 * (192 + 128) * 2
    # a slot 100 positions in reads 100 of each; one 1,000 in reads 1,000 full and 127 window
    assert work_decoder.cache_bytes_read(MIMO, [100, 1000], 2) == 1100 * full + 227 * window
    assert work_decoder.decode_step_bytes(MIMO, 130, [100, 1000]) == (
        work_decoder.decode_fixed_weight_bytes(MIMO, 2) + 130 * 50_331_648
        + 1100 * full + 227 * window)


def test_scopes_by_operation_name():
    assert decoder_read.scope_of("jit(_decode)/while/body/closed_call/moe_experts/mul:") == "moe_experts"
    assert decoder_read.scope_of("jit(_decode)/while/body/closed_call/attn_window/dot_general:") == "attn_window"
    assert decoder_read.scope_of("jit(_decode)/kv_write/select_n:") == "kv_write"
    assert decoder_read.scope_of("ragged-dot-none:") == "moe_experts"  # XLA's grouped-product kernel
    assert decoder_read.scope_of("jit(_decode)/while/body/closed_call/transpose:") == "unscoped"
    assert decoder_read.scope_of("") == "unscoped"


def test_counter_deltas(monkeypatch):
    monkeypatch.setattr(decoder_read, "_snapshots", [])
    assert decoder_read.counter_delta("moe_experts_hit") is None
    for hit in (10, 40, 100, 180):
        decoder_read.record({"moe_experts_hit": hit, "decode_steps": hit // 10})
    assert decoder_read.counter_delta("moe_experts_hit") == 170
    assert decoder_read.counter_delta("moe_experts_hit", span=True) == 60
    assert decoder_read.counter_delta("moe_pairs_local") is None  # a program without the counter


def last_line(out):
    return json.loads(out.strip().splitlines()[-1])


def numbers(out):
    return {json.loads(l)["compared"]: json.loads(l) for l in out.splitlines()
            if l.startswith('{"compared"')}


def test_sound_run_is_correct(capsys):
    assert bench_run.main(["--rehearse", "tiny-mimo:tiny-reason", "--seed", "42", "--seconds", "3"]) == 0
    out = capsys.readouterr().out
    assert last_line(out)["correct"] is True and last_line(out)["failed"] == 0


def test_token_altered_where_it_is_produced_is_refused(capsys, monkeypatch):
    from deeplearning4j_tpu.serving.generate import GenerationRequest

    sound = GenerationRequest.push_token
    monkeypatch.setattr(GenerationRequest, "push_token",
                        lambda self, tok: sound(self, (int(tok) + 1) % 256))
    assert bench_run.main(["--rehearse", "tiny-mimo:tiny-reason", "--seed", "42", "--seconds", "3"]) == 1
    out = capsys.readouterr().out
    assert numbers(out)["served_logit_gap"]["within"] is False


def test_int8_control_is_refused_and_bfloat16_is_not():
    family = bench_run.load_module("families", TINY["family"])
    traffic = bench_run.load_json(HERE, "traffic", "tiny-reason.json")
    kind = bench_run.load_module("kinds", traffic["kind"])
    limits = bench_run.load_limits({"config": "tiny-mimo", "traffic": "tiny-reason"})
    # seeds that stand clear at this width (limits/tiny-mimo.tiny-reason.json)
    for seed in (42, 44, 3000000019):
        out = kind.calibrate(family, TINY, traffic, seed, "int8", seconds=3.0)
        assert out["tokens_compared"] >= 400
        assert compare.judge(out["program"], {k: limits[k] for k in out["program"]})[0], out
        assert not compare.judge(out["control"], {k: limits[k] for k in out["control"]})[0], out


def test_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    """Another family's run (no expert bytes in its work model, no snapshot
    left, no trace): every new reader returns ``None`` and none raises."""
    from lib import phases

    monkeypatch.setattr(decoder_read, "_snapshots", [])
    monkeypatch.setattr(phases, "_run", {"xplane": None, "ring": None})
    run = {"counters": {"engine": {}, "traced": {"decode_steps": 3}}, "trace": None,
           "work": {"decode_program": "jit__decode", "decode_weight_bytes": 1},
           "peaks": {"hbm_bytes_per_s": 8.19e11}, "chips": 1, "setup": {}, "window": {}}
    for name in ("moe_device_ms.serve", "moe_hbm_share.serve", "window_attn_device_ms.serve",
                 "moe_pairs_per_expert.serve"):
        assert bench_run.load_module("metrics", name).read(run) is None, name

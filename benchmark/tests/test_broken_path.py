"""``correct`` has to come out false when the timed path is broken.

These drive a whole run of ``run.py`` (its CPU rehearsal, which skips only
the look for a chip) with the program broken underneath: a training step
that returns its state unchanged, and a served token altered where it is
produced. Run by hand:

    python3 -m pytest benchmark/tests/test_broken_path.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def numbers(capsys_out):
    return {json.loads(l)["compared"]: json.loads(l) for l in capsys_out.splitlines()
            if l.startswith('{"compared"')}


def test_sound_train_run_is_correct(capsys):
    assert bench_run.main(["--rehearse", "tiny-lm:tiny-lm-train", "--seed", "5", "--seconds", "1"]) == 0
    assert last_line(capsys)["correct"] is True


def test_step_that_returns_its_state_unchanged_is_refused(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import TransformerLM

    sound = TransformerLM.fit_batch

    def unchanged(self, ids, targets, segment_ids=None):
        kept = jax.tree_util.tree_map(jnp.copy, self.params_)
        loss = sound(self, ids, targets, segment_ids)
        self.params_ = kept
        return loss

    monkeypatch.setattr(TransformerLM, "fit_batch", unchanged)
    assert bench_run.main(["--rehearse", "tiny-lm:tiny-lm-train", "--seed", "5", "--seconds", "1"]) == 1
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert numbers(out)["update_norm_gap"]["within"] is False


def test_part_of_the_batch_left_out_is_refused(capsys, monkeypatch):
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM

    sound = TransformerLM.fit_batch

    def half(self, ids, targets, segment_ids=None):
        targets = targets.copy()
        targets[: len(targets) // 2] = -1  # half of the rows no longer count
        return sound(self, ids, targets, segment_ids)

    monkeypatch.setattr(TransformerLM, "fit_batch", half)
    assert bench_run.main(["--rehearse", "tiny-lm:tiny-lm-train", "--seed", "5", "--seconds", "1"]) == 1
    out = capsys.readouterr().out
    assert numbers(out)["loss_rel_gap.first"]["within"] is False


def test_sound_serving_run_is_correct(capsys):
    assert bench_run.main(["--rehearse", "tiny-lm:tiny-chat", "--seed", "5", "--seconds", "2"]) == 0
    assert last_line(capsys)["correct"] is True


def test_token_altered_where_it_is_produced_is_refused(capsys, monkeypatch):
    from deeplearning4j_tpu.serving.generate import GenerationRequest

    sound = GenerationRequest.push_token

    def altered(self, tok):
        sound(self, (int(tok) + 1) % 256)

    monkeypatch.setattr(GenerationRequest, "push_token", altered)
    assert bench_run.main(["--rehearse", "tiny-lm:tiny-chat", "--seed", "5", "--seconds", "2"]) == 1
    out = capsys.readouterr().out
    assert numbers(out)["served_logit_gap"]["within"] is False


def test_graph_step_that_returns_its_state_unchanged_is_refused(capsys, monkeypatch):
    """The same fault under the zoo_graph family: ``fit`` runs, the
    parameters are put back."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph import ComputationGraph

    sound = ComputationGraph.fit

    def unchanged(self, data, epochs=1, batch_size=32):
        kept = jax.tree_util.tree_map(jnp.copy, self.params_)
        sound(self, data, epochs=epochs, batch_size=batch_size)
        self.params_ = kept
        return self

    monkeypatch.setattr(ComputationGraph, "fit", unchanged)
    assert bench_run.main(["--rehearse", "tiny-resnet:tiny-image-train", "--seed", "5", "--seconds", "1"]) == 1
    out = capsys.readouterr().out
    for group in ("weights", "gains", "shifts"):
        assert numbers(out)[f"update_norm_gap.{group}"]["within"] is False

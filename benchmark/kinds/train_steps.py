"""Kind ``train_steps``: training steps through the family's public step,
a fresh host batch of distinct rows every step.

Set-up builds ONE trainer (the compiled step with its state), drives it
from the seed through its first ``check_steps`` steps, keeps what the
comparison needs, and hands the same trainer to the window. After the
window the trainer is freed and the plain reference follows those first
steps: each step's loss, the norm of the first gradient as the optimizer
got it and the norm of the parameters' change, by the worst leaf.

Traffic file: ``batch`` and whatever else the family's ``batch_source`` and
``item_shape`` read, ``check_steps``, ``trace_seconds``. A configuration may
name ``leaf_groups`` (``lib/compare.train_numbers``).
"""

import math
import statistics
import time

import jax
import numpy as np

from lib import compare
from lib.profile import TracedWindow


def first_steps(family, config, traffic, seed, mark=lambda name: None):
    """Build the trainer and drive it from the seed through the checked
    first steps, through the window's own call. Returns the trainer, the
    batches it saw, what the comparison needs of the program, and the
    source of further batches."""
    rng = np.random.default_rng(seed)
    trainer = family.Trainer(config, traffic, seed)
    mark("trainer")
    next_batch = family.batch_source(config, traffic, rng)
    check = [next_batch() for _ in range(traffic["check_steps"])]
    losses, grad_norms = [], None
    for batch in check:
        losses.append(trainer.step(batch))
        if grad_norms is None:
            mark("first_step")
            grad_norms = trainer.first_gradient_norms()
    mark("check_steps")
    program = {"losses": losses, "grad_norms": grad_norms,
               "update_norms": trainer.update_norms()}
    return trainer, check, program, next_batch


def run(ctx):
    family, config, traffic = ctx.family, ctx.config, ctx.traffic
    trainer, check, program, next_batch = first_steps(family, config, traffic, ctx.seed, ctx.mark)

    traced = None
    if ctx.trace:
        # the traced part comes first and is left out of nothing: the
        # window below still counts every step and all its time
        traced = TracedWindow(ctx.trace_dir, traffic.get("host_tracer_level", 2))
    ctx.begin_window()
    t0 = time.monotonic()
    steps, step_s, last_loss = 0, [], math.nan
    if traced is not None:
        with traced:
            while time.monotonic() < t0 + min(traffic["trace_seconds"], ctx.seconds):
                t = time.monotonic()
                with jax.profiler.TraceAnnotation("bench.make_batch"):
                    batch = next_batch()
                with jax.profiler.TraceAnnotation("bench.step"):
                    last_loss = trainer.step(batch)
                step_s.append(time.monotonic() - t)
                steps += 1
        traced_steps = steps
    while time.monotonic() < t0 + ctx.seconds:
        t = time.monotonic()
        batch = next_batch()
        last_loss = trainer.step(batch)  # returns the loss: fetched
        step_s.append(time.monotonic() - t)
        steps += 1
    elapsed = time.monotonic() - t0
    ctx.end_window()

    counters = {"steps": steps, "items_per_step": trainer.items_per_step,
                "step_host_s_median": statistics.median(step_s),
                "retraces": trainer.retraces()}
    done = trainer.steps_taken()
    memory = ctx.memory_peak()
    trainer.close()

    reduction = None
    if traced is not None:
        reduction = ctx.reduce_trace(traced)
        if reduction is not None:
            reduction["steps"] = traced_steps

    t_ref = time.monotonic()
    reference = family.reference_train(config, ctx.seed, check)
    reference_s = time.monotonic() - t_ref
    numbers, where = compare.train_numbers(program, reference, config.get("leaf_groups"))
    numbers["steps_uncounted"] = float(abs(done - (steps + len(check))))
    numbers["last_loss_not_finite"] = 0.0 if math.isfinite(last_loss) else 1.0
    return {"end_to_end": {"train_throughput": steps * trainer.items_per_step / elapsed},
            "attempted": steps, "failed": 0 if math.isfinite(last_loss) else 1,
            "counters": counters, "trace": reduction, "numbers": numbers,
            "where": where, "memory_peak_bytes": memory,
            "detail": {"program_losses": program["losses"],
                       "reference_losses": reference["losses"], "elapsed_s": elapsed,
                       "reference_s": reference_s}}


def calibrate(family, config, traffic, seed, control):
    """The compared numbers of one seed without a measured window: the
    program's against the reference's and, with ``control``, the
    reference's in the control's precision against the reference's."""
    trainer, check, program, _ = first_steps(family, config, traffic, seed)
    trainer.close()
    reference = family.reference_train(config, seed, check)
    groups = config.get("leaf_groups")
    out = {"program": compare.train_numbers(program, reference, groups)[0]}
    if control:
        low = family.reference_train(config, seed, check, mode=control)
        out["control"] = compare.train_numbers(low, reference, groups)[0]
    return out

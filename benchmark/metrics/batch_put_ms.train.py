"""Median host time a batch spends on its way into the step, inside the
program: ``train.put_batch`` (the host arrays going to the device) plus,
where the path iterates a data set, ``train.iterate``. From the program's
ring of phases, over the traced window."""

from lib import phases


def read(run):
    return phases.run_phase_ms(("train.iterate", "train.put_batch"))

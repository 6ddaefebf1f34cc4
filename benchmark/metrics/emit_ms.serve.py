"""Median host time of ``gen.emit``: the per-slot loop after a decode step
that hands each slot its token, over the traced window."""

from lib import phases


def read(run):
    return phases.run_phase_ms(("gen.emit",))

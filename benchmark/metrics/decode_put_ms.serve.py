"""Median host time of a decode step's ``gen.decode.put`` (the step's host
arrays going to the device), over the traced window."""

from lib import phases


def read(run):
    return phases.run_phase_ms(("gen.decode.put",))

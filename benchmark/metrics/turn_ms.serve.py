"""Median ``gen.turn`` with no claim inside, over the traced window: the
loop thread's time from the end of a step's ``gen.emit`` to the start of
the next step's ``gen.decode.put`` (``lib/gap_read.py``, the ring alone)."""

from lib import gap_read


def read(run):
    return gap_read.turn_ms(*gap_read.current())

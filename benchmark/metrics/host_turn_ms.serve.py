"""Median, over the traced window's turns with no claim inside, of the
loop's own time from the end of one step's ``gen.decode.fetch`` to the
start of the next step's ``gen.decode.dispatch``: ``gen.emit`` +
``gen.turn`` + ``gen.decode.put`` (``lib/gap_read.py``, the ring joined by
cause; ``None`` for a program whose ring carries no cause)."""

from lib import gap_read


def read(run):
    return gap_read.host_turn_ms(gap_read.run_joined_pairs(run))

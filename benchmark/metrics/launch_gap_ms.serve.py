"""Median wait of the device between the end of one execution of the
decode program and the start of the next, over the traced window's pairs
with no other program between (``lib/gap_read.py``, the device plane
alone)."""

from lib import gap_read


def read(run):
    return gap_read.launch_gap_ms(gap_read.run_device_pairs(run))

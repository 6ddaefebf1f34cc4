"""``launch_gap_ms.serve`` less ``host_turn_ms.serve``, pair by pair,
median: what of the device's wait the loop's code does not own, from the
device's last operation to ``np.array(state)`` returning and from the
dispatch's entry to the first operation (``lib/gap_read.py``). Each side
is differenced on its own clock. ``None`` where either side is."""

from lib import gap_read


def read(run):
    return gap_read.launch_gap_runtime_ms(gap_read.run_joined_pairs(run))

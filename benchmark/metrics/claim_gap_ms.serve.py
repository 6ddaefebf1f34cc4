"""Over the traced window's pairs of decode executions with exactly one
prefill between: the device's wait less that prefill's own execution,
median: the host time a claim adds to the gap it falls in
(``lib/gap_read.py``, the device plane alone). ``None``, said on stderr,
where the span holds no such pair."""

from lib import gap_read


def read(run):
    pairs = gap_read.run_device_pairs(run)
    value = gap_read.claim_gap_ms(pairs)
    if pairs and value is None:
        gap_read._say(f"no pair of the span's {len(pairs)} has one lone prefill between: claim_gap_ms.serve left out")
    return value

"""95th percentile (nearest rank) of the time a request waited between
``submit`` and its slot claim, over every request this process claimed
(``gen.queue_wait`` in the program's ring: the whole run, lead-in and
drain included, since a 3 s trace holds about three claims)."""

from lib import phases


def read(run):
    return phases.run_phase_ms(("gen.queue_wait",), "p95_ms", whole_run=True)

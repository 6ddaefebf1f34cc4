"""95th percentile of (instant a request left the load generator - instant
it was due): a starved generator must not read as a fast server."""


def read(run):
    return run["counters"].get("gen_late_p95_ms")

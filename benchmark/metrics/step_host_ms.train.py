"""Median host time of one public training step (batch made, step called,
loss fetched), in milliseconds."""


def read(run):
    value = run["counters"].get("step_host_s_median")
    return None if value is None else 1e3 * value

"""Backend compiles plus the engines' own retrace counts inside the
measured window; expected 0."""


def read(run):
    return float(run["window"]["compiles"] + run["counters"].get("retraces", 0))

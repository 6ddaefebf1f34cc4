"""1 - (union of device-operation intervals / traced window), serving cells."""


def read(run):
    return None if not run["trace"] else 100.0 * run["trace"]["idle_share"]

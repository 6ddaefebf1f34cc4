"""Seconds JAX spent in backend compiles (and in loading executables from
the persistent cache) during set-up; moves ``setup_s``."""


def read(run):
    return run["setup"]["compile_s"]

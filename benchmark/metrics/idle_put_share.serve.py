"""Share of the traced window in which the device idled while the engine
was putting a step's or a prefill's host arrays on it (``gen.decode.put`` +
``gen.prefill.put``)."""

from lib import phases


def read(run):
    return phases.run_idle_share(("gen.decode.put", "gen.prefill.put"))

"""Share of the traced window in which the device idled while the program
was bringing a batch to it (``train.iterate`` + ``train.put_batch``): the
idle gaps of ``lib/trace.reduce`` laid under the program's ring of phases."""

from lib import phases


def read(run):
    return phases.run_idle_share(("train.iterate", "train.put_batch"))

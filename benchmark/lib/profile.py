"""Start and stop the JAX profiler around a traced window and reduce what
it wrote. The Python tracer is off (it slows the host and floods the
trace); the runtime's own spans and ``TraceAnnotation`` stay on."""

import os
import shutil

import jax

from lib import trace

WINDOW_SPAN = "bench.window"


class TracedWindow:
    """``with TracedWindow(dir) as tw: ...`` traces the body under the host
    span ``bench.window``; ``tw.reduce(chips)`` gives the numbers."""

    def __init__(self, log_dir, host_tracer_level=2):
        self.log_dir = log_dir
        self.host_tracer_level = host_tracer_level
        self._span = None

    def __enter__(self):
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = self.host_tracer_level
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def reduce(self, chips):
        planes = trace.load_xplane(trace.find_xplane(self.log_dir))
        return trace.reduce(planes, chips=chips, window_span=WINDOW_SPAN), planes

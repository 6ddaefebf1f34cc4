"""Prefills a hundred decode steps, over the window (``GenerationMetrics``:
``prefills`` / ``decode_steps``). A prefill holds every decoding slot for
its length (hundreds of milliseconds at a prompt of thousands), so where
more than five gaps in a hundred follow one, ``itl_p95_ms`` is a prefill
stall and no longer a decode step: the cell's rate keeps this under 3."""


def read(run):
    e = run["counters"].get("engine")
    if not e or not e.get("decode_steps"):
        return None
    return 100.0 * e["prefills"] / e["decode_steps"]

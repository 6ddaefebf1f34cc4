"""Mean host time of one prefill, from ``GenerationMetrics`` (prefill
seconds / prefills, over the window). The engine's timer closes after the
first token is on the host (``_TransformerBackend.prefill`` returns
``int(tok0)``)."""


def read(run):
    e = run["counters"].get("engine")
    if not e or not e["prefills"]:
        return None
    return 1e3 * e["prefill_seconds"] / e["prefills"]

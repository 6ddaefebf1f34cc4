"""Mean host time of one batched decode step, from ``GenerationMetrics``
(decode seconds / decode steps, over the window). The engine's timer
closes after the step's tokens are on the host (``backend.decode`` returns
``np.asarray(nxt)``)."""


def read(run):
    e = run["counters"].get("engine")
    if not e or not e["decode_steps"]:
        return None
    return 1e3 * e["decode_seconds"] / e["decode_steps"]

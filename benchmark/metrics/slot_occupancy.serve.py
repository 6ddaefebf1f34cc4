"""Mean share of the engine's slots that were decoding, over the window's
decode steps: tokens that decode steps produced (all tokens less each
request's first, which prefill produces) / steps / slots."""


def read(run):
    e = run["counters"].get("engine")
    if not e or not e["decode_steps"]:
        return None
    return 100.0 * (e["tokens"] - e["prefills"]) / e["decode_steps"] / run["counters"]["slots"]

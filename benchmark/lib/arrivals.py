"""Seeded arrivals and lengths for open-loop serving traffic.

The arithmetic follows ``deeplearning4j_tpu/loadgen/plan.py`` (seeded
exponential gaps, log-normal lengths, clipped), with one stated reduction
that the benchmark's steadiness needs: the schedule (every gap and every
length, in their order) is drawn from the mix's own ``base_seed``;
``--seed`` draws the token ids (and, in the family, the weights). Every
seed therefore offers the same work at the same instants.

Why not the same set in another order: a window holds some tens of
requests that each keep a slot for many seconds, so the order decides how
many slots are busy during the window and whether they run out for a
moment. Shuffled by the seed, the tokens delivered in a window spread by
10 % and the 95th percentile of time to first token read 140 ms on five
seeds and 400-480 ms on the sixth (my chip runs, PR 23): the seed changing
the work, not the program, and more than any bound may allow.
"""

import numpy as np


def _lognormal_int(rng, n, median, sigma, lo, hi):
    draw = np.exp(np.log(median) + sigma * rng.standard_normal(n))
    return np.clip(np.rint(draw), lo, hi).astype(np.int64)


def plan(mix, seed, seconds, vocab_size):
    """The requests of one run: a list of dicts with ``due`` (seconds from
    the window's start), ``prompt`` (token ids) and ``max_new``.

    ``mix`` is the traffic file: ``rate_per_s``, ``base_seed``,
    ``prompt_len`` and ``answer_len`` (each ``median``, ``sigma``, ``min``,
    ``max``) and ``lead_in_s``: arrivals start that long before the window
    opens (their ``due`` is negative), so that the window opens on slots as
    full as a steady stream keeps them and not on an empty engine. The
    number of requests is ``round(rate_per_s * (lead_in_s + seconds))``.
    """
    lead_in = float(mix["lead_in_s"])
    span = lead_in + seconds
    n = max(1, int(round(mix["rate_per_s"] * span)))
    base = np.random.default_rng(int(mix["base_seed"]))
    gaps = base.exponential(1.0, n)
    p, a = mix["prompt_len"], mix["answer_len"]
    prompt_len = _lognormal_int(base, n, p["median"], p["sigma"], p["min"], p["max"])
    answer_len = _lognormal_int(base, n, a["median"], a["sigma"], a["min"], a["max"])

    rng = np.random.default_rng(int(seed))

    # arrival i is due at the end of gap i; the whole set is scaled so that
    # the last one is due half a mean gap before the window closes
    due = np.cumsum(gaps)
    due = due * (span * (1.0 - 0.5 / n) / due[-1]) - lead_in
    return [{"due": float(due[i]),
             "prompt": rng.integers(0, vocab_size, int(prompt_len[i])).tolist(),
             "max_new": int(answer_len[i])} for i in range(n)]

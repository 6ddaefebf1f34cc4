"""Kind ``open_loop_generate``: open-loop streaming generation over HTTP.

A child process that never imports JAX (``lib/client.py``) sends the
requests of ``lib/arrivals.plan`` on their schedule to the family's server
on loopback and records when each streamed token arrives. Latencies count
from the instant a request was DUE, not from when it was sent; how late
the generator ran is reported beside them.

The arrivals start ``lead_in_s`` before the window opens, so that the
window opens on slots as full as a steady stream keeps them; the lead-in
is set-up and counts in ``setup_s``. Everything measured is measured
inside the window.

End to end: ``itl_p95_ms`` (gaps of every request) and
``serve_due_tokens_per_s`` (tokens of the requests DUE inside the window).
The count over every request stays in the detail line with its two edge
terms: below the knee it is the offered load plus what the lead-in's
requests still owe at the opening less what is still owed at the close,
and both terms shrink as the engine gets faster, the first one faster, so
a faster engine read 2-3 % slower on it (PERF.md section 2). Time to first
token is printed in the detail line only: with some tens of requests in a
window its tail is two or three requests and no bound of at most 10 % holds
it.

After the window has closed and the requests in flight have drained, the
server is freed and the plain reference runs once over a seeded sample of
the finished requests, the longest among them: the widest gap by which a
served (greedy) token's logit lies below the reference's best.

Traffic file: what ``arrivals.plan`` reads (``lead_in_s`` among it),
``engine`` (the family's server settings), ``drain_s``, ``check_requests``,
``trace_seconds``.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from lib import arrivals
from lib.profile import TracedWindow

CLIENT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "lib", "client.py")


def percentile(values, q):
    """The q-th percentile by the nearest-rank rule (no interpolation:
    every reported tail is a latency that some request really had)."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, int(np.ceil(q / 100.0 * len(ordered))) - 1))]


def measure(results, t0, seconds):
    """End-to-end numbers of one window from the client's records: gaps
    closed inside the window, of every request; tokens delivered inside it
    to the requests due inside it, and their time to first token. The
    detail keeps the count over every request and its edges:
    ``tokens_in_window`` = every token of the requests due in the window
    + ``tokens_owed_at_open`` (delivered from the opening on to requests
    due before it) - ``tokens_owed_at_close`` (delivered after the close)."""
    close = t0 + seconds
    ttft, gaps, delivered, delivered_due, late, failed = [], [], 0, 0, [], 0
    owed_at_open = owed_at_close = 0
    for r in results:
        due = t0 + r["due"]
        ok = r["done"] and not r["error"]
        failed += 0 if ok else 1
        if r["due"] >= 0:
            # a request that failed or never got a token counts as the window's length
            ttft.append((r["token_times"][0] - due) if (r["token_times"] and ok) else seconds)
        if r["sent"] is not None:
            late.append(r["sent"] - due)
        times = r["token_times"]
        inside = sum(1 for t in times if t0 <= t <= close)
        after = sum(1 for t in times if t > close)
        delivered += inside
        owed_at_close += after
        if r["due"] >= 0:
            delivered_due += inside
        else:
            owed_at_open += inside + after
        gaps.extend(b - a for a, b in zip(times, times[1:]) if t0 <= b <= close)
    out = {"serve_due_tokens_per_s": delivered_due / seconds}
    if gaps:
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    in_flight = [sum(1 for r in results if r["token_times"] and r["token_times"][0] <= t
                     and (not r["done"] or r["token_times"][-1] > t)) for t in (t0, close)]
    detail = {"ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
              "ttft_p95_ms": 1e3 * percentile(ttft, 95) if ttft else None,
              "requests": len(results), "requests_due_in_window": len(ttft),
              "streaming_at_open": in_flight[0], "streaming_at_close": in_flight[1],
              "tokens_in_window": delivered, "tokens_due_in_window": delivered_due,
              "tokens_owed_at_open": owed_at_open, "tokens_owed_at_close": owed_at_close,
              "gaps": len(gaps),
              "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
              "gen_late_p95_ms": 1e3 * percentile(late, 95) if late else None}
    return out, detail, failed


def sample_finished(results, requests, seed, n):
    """``n`` finished requests drawn from the seed, the longest among them."""
    done = [i for i, r in enumerate(results) if r["done"] and not r["error"] and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda i: len(requests[i]["prompt"]) + len(results[i]["tokens"]))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(seed)
    picked = [longest] + [rest[j] for j in rng.permutation(len(rest))[: n - 1]]
    return [{"prompt": requests[i]["prompt"], "tokens": results[i]["tokens"]} for i in picked]


def drive(server, requests, seconds, drain_s, trace=None, on_open=None, on_close=None):
    """One window: a fresh client child sends ``requests`` to ``server``;
    those with a negative ``due`` go out before the window opens.
    ``trace`` is (TracedWindow, span seconds) or None. Returns the client's
    results, the window's first instant and the engine's counters around
    the window and around the traced span."""
    child = subprocess.Popen([sys.executable, CLIENT], stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE, text=True)
    try:
        warm = [{"due": 0.0, "prompt": requests[i]["prompt"][:32], "max_new": 4}
                for i in range(min(2, len(requests)))]
        child.stdin.write(json.dumps({"port": server.port, "warm": warm, "requests": requests,
                                      "drain_s": drain_s}) + "\n")
        child.stdin.flush()
        ready = json.loads(child.stdout.readline())
        if ready.get("warm_errors"):
            raise RuntimeError(f"warm-up requests failed: {ready['warm_errors']}")
        lead_in = max(0.0, -min(r["due"] for r in requests))
        out = {}
        t0 = out["t0"] = time.monotonic() + 0.05 + lead_in
        child.stdin.write(json.dumps({"t0": t0}) + "\n")
        child.stdin.flush()
        time.sleep(max(t0 - time.monotonic(), 0))
        out["before"] = server.counters()
        if on_open:
            on_open()
        if trace:
            traced, span = trace  # the middle of the window
            time.sleep(max(t0 + (seconds - span) / 2 - time.monotonic(), 0))
            with traced:
                out["span_before"] = server.counters()
                out["span"] = [time.monotonic(), None]
                time.sleep(span)
                out["span"][1] = time.monotonic()
                out["span_after"] = server.counters()
        time.sleep(max(t0 + seconds - time.monotonic(), 0))
        out["after"] = server.counters()
        if on_close:
            on_close()
        out["results"] = json.loads(child.stdout.readline())["results"]
        return out
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()


def run(ctx):
    family, config, traffic = ctx.family, ctx.config, ctx.traffic
    server = family.Server(config, traffic, ctx.seed)
    ctx.mark("server_warm")
    requests = arrivals.plan(traffic, ctx.seed, ctx.seconds, family.vocab_size(config))
    retraced = server.retraces()
    traced = None
    if ctx.trace:
        traced = TracedWindow(ctx.trace_dir, traffic.get("host_tracer_level", 2))
    w = drive(server, requests, ctx.seconds, traffic["drain_s"],
              trace=(traced, min(traffic["trace_seconds"], ctx.seconds / 2)) if traced else None,
              on_open=ctx.begin_window, on_close=ctx.end_window)
    results, t0, before, after = w["results"], w["t0"], w["before"], w["after"]

    retraces = server.retraces() - retraced
    memory = ctx.memory_peak()
    slots = server.slots
    server.close()

    end_to_end, detail, failed = measure(results, t0, ctx.seconds)
    delta = {k: after[k] - before[k] for k in
             ("prefills", "prefill_seconds", "decode_steps", "decode_seconds", "tokens",
              "rejects", "errors", "deadline_exceeded")}
    counters = {"engine": delta, "slots": slots, "retraces": retraces,
                "gen_late_p95_ms": detail["gen_late_p95_ms"]}

    reduction = None
    if traced is not None:
        reduction = ctx.reduce_trace(traced)
        # positions of KV that the decode steps of the traced span had to read:
        # token j >= 1 of a request (token 0 comes from prefill) reads prompt + j
        live = 0
        for req, res in zip(requests, results):
            for j, t in enumerate(res["token_times"]):
                if j >= 1 and w["span"][0] <= t <= w["span"][1]:
                    live += len(req["prompt"]) + j
        counters["traced"] = {
            "live_kv_positions": live,
            "decode_steps": w["span_after"]["decode_steps"] - w["span_before"]["decode_steps"]}

    samples = sample_finished(results, requests, ctx.seed, traffic["check_requests"])
    numbers = {"requests_failed": float(failed)}
    if samples:
        t_ref = time.monotonic()
        ref = family.reference_serve(config, traffic, ctx.seed, samples)
        detail["reference_s"] = time.monotonic() - t_ref
        numbers["served_logit_gap"] = ref["served_logit_gap"]
        numbers["served_logit_gap_mean"] = ref["served_logit_gap_mean"]
        detail["tokens_compared"] = ref["tokens_compared"]
        detail["tokens_below_best"] = ref["tokens_below_best"]
    else:
        numbers["served_logit_gap"] = numbers["served_logit_gap_mean"] = float("inf")
    return {"end_to_end": end_to_end, "attempted": len(requests), "failed": failed,
            "counters": counters, "trace": reduction, "numbers": numbers, "where": {},
            "memory_peak_bytes": memory, "detail": detail}


def calibrate(family, config, traffic, seed, control, seconds=10.0):
    """The compared numbers of one seed from a short window at the cell's
    own load (long enough, with its drain, to finish the mix's longest
    requests and to compare as many tokens as a run does): the served
    tokens' gaps and, with ``control``, those of the tokens the reference
    would serve in the control's precision. The server is built and freed
    as in a run, so the reference has the chip to itself."""
    server = family.Server(config, traffic, seed)
    try:
        requests = arrivals.plan(traffic, seed, seconds, family.vocab_size(config))
        w = drive(server, requests, seconds, traffic["drain_s"])
    finally:
        server.close()
    _, detail, failed = measure(w["results"], w["t0"], seconds)
    samples = sample_finished(w["results"], requests, seed, traffic["check_requests"])
    ref = family.reference_serve(config, traffic, seed, samples, control_mode=control)
    out = {"program": {"served_logit_gap": ref["served_logit_gap"],
                       "served_logit_gap_mean": ref["served_logit_gap_mean"],
                       "requests_failed": float(failed)},
           "tokens_compared": ref["tokens_compared"]}
    if control:
        out["control"] = {"served_logit_gap": ref["control_logit_gap"],
                          "served_logit_gap_mean": ref["control_logit_gap_mean"]}
    return out

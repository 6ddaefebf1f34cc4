"""What a serving cell would read at another engine speed, without a chip.

    python3 benchmark/tools/replay_schedule.py --traffic chat-steady \\
        --step-ms 122 --prefill-ms 24.8 [--step-ms 95 ...]

replays the mix's own schedule (``lib/arrivals.plan``: every due instant and
every length) through a lock-step model of ``GenerationEngine._loop``: while
a slot is free and a request is due, admit it (an admission costs
``--prefill-ms``, holds every slot and emits the request's first token); then
one decode step (``--step-ms``) emits one token to every busy slot. The
model's records go through the same ``measure`` as a run's, so the numbers
are the run's numbers at that speed: both token counts with the window's two
edge terms, ``itl_p50/p95`` and the occupancy of the slots.

No JAX, no clock: it is what a session without a chip sizes a serving claim
with, and what ``tests/test_window_edges.py`` pins the end-to-end count's
behaviour on. It is a model: it knows nothing of the host's share of a step
beyond what ``--step-ms`` includes, and takes every step for equally long.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def replay(requests, slots, step_s, prefill_s):
    """Client-shaped records (``due``, ``sent``, ``token_times``, ...) of
    ``requests`` served by ``slots`` slots in lock step, on the plan's own
    clock (the window opens at 0), and one ``(end, busy slots)`` a decode
    step."""
    results = [{"due": r["due"], "sent": r["due"], "token_times": [], "tokens": [],
                "done": False, "error": None} for r in requests]
    order = sorted(range(len(requests)), key=lambda i: requests[i]["due"])
    active, steps, nxt = [], [], 0
    now = requests[order[0]]["due"]

    def emit(i):
        results[i]["token_times"].append(now)
        results[i]["tokens"].append(0)
        results[i]["done"] = len(results[i]["tokens"]) >= requests[i]["max_new"]

    while nxt < len(order) or active:
        while nxt < len(order) and len(active) < slots and requests[order[nxt]]["due"] <= now:
            now += prefill_s
            emit(order[nxt])
            if not results[order[nxt]]["done"]:
                active.append(order[nxt])
            nxt += 1
        if not active:
            if nxt < len(order):
                now = max(now, requests[order[nxt]]["due"])
            continue
        now += step_s
        steps.append((now, len(active)))
        for i in active:
            emit(i)
        active = [i for i in active if not results[i]["done"]]
    return results, steps


def read(measure, requests, slots, seconds, step_ms, prefill_ms):
    """One row: ``measure`` over the replay, with the window's occupancy."""
    results, steps = replay(requests, slots, step_ms / 1e3, prefill_ms / 1e3)
    out, detail, failed = measure(results, 0.0, seconds)
    inside = [busy for end, busy in steps if 0.0 <= end <= seconds]
    return {"step_ms": step_ms, "prefill_ms": prefill_ms,
            "serve_due_tokens_per_s": out["serve_due_tokens_per_s"],
            "every_request_tokens_per_s": detail["tokens_in_window"] / seconds,
            **{k: detail[k] for k in ("tokens_in_window", "tokens_due_in_window", "tokens_owed_at_open",
                                      "tokens_owed_at_close", "itl_p50_ms", "ttft_p95_ms",
                                      "streaming_at_open", "streaming_at_close")},
            "itl_p95_ms": out.get("itl_p95_ms"),
            "slot_occupancy": 100.0 * sum(inside) / (len(inside) * slots) if inside else None,
            "failed": failed}


def load(traffic_name):
    """(``measure`` of the mix's kind, the mix) by the mix's name."""
    import run as bench_run

    traffic = bench_run.load_json(HERE, "traffic", traffic_name + ".json")
    return bench_run.load_module("kinds", traffic["kind"]).measure, traffic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--step-ms", type=float, action="append", required=True,
                    help="a decode step, host share included; may be given several times")
    ap.add_argument("--prefill-ms", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window; BENCHMARK.json's run_seconds unless given")
    args = ap.parse_args()

    import run as bench_run
    from lib import arrivals

    measure, traffic = load(args.traffic)
    seconds = args.seconds or float(bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")["run_seconds"])
    requests = arrivals.plan(traffic, 0, seconds, 2)  # the seed draws token ids only
    for step_ms in args.step_ms:
        print(json.dumps(read(measure, requests, traffic["engine"]["n_slots"], seconds,
                              step_ms, args.prefill_ms)))


if __name__ == "__main__":
    main()

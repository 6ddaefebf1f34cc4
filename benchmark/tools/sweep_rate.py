"""Find the highest rate a serving cell sustains, once, when the cell is
defined: one process, the engine built and warmed once, one window per
rate. The cell's traffic file then gets about four fifths of the knee as a
number; no run of the benchmark searches.

    python3 benchmark/tools/sweep_rate.py --workload gpt2-large.chat \\
        --seed 1 --seconds 20 --rates 6,8,10,12,14

A rate is sustained when the backlog does not grow: the time to first
token of the window's second half stays near that of its first half, and
no more requests are streaming when the window closes than when it opened
(the mix's ``lead_in_s`` fills the slots before each window, so a window
shows a steady stream and not a ramp from an empty engine).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    import run as bench_run
    from lib import arrivals

    cell, config, traffic = bench_run.load_cell(
        bench_run.load_json(bench_run.ROOT, "BENCHMARK.json"), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep_rate: needs the TPU")
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    family = bench_run.load_module("families", config["family"])
    kind = bench_run.load_module("kinds", traffic["kind"])
    server = family.Server(config, traffic, args.seed)
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(traffic, rate_per_s=rate)
            requests = arrivals.plan(mix, args.seed + i, args.seconds, family.vocab_size(config))
            w = kind.drive(server, requests, args.seconds, traffic["drain_s"])
            e2e, detail, failed = kind.measure(w["results"], w["t0"], args.seconds)
            close = w["t0"] + args.seconds
            half = w["t0"] + args.seconds / 2

            def ttft(rs):
                return sorted(1e3 * (r["token_times"][0] - (w["t0"] + r["due"]))
                              for r in rs if r["token_times"])

            first = ttft([r for r in w["results"] if 0 <= r["due"] and w["t0"] + r["due"] < half])
            second = ttft([r for r in w["results"] if w["t0"] + r["due"] >= half])
            unfinished = sum(1 for r in w["results"]
                             if not r["token_times"] or r["token_times"][-1] > close)
            steps = w["after"]["decode_steps"] - w["before"]["decode_steps"]
            print(json.dumps({
                "rate_per_s": rate, "requests": len(requests), "failed": failed,
                **e2e, **detail,
                "ttft_p50_first_half_ms": first[len(first) // 2] if first else None,
                "ttft_p50_second_half_ms": second[len(second) // 2] if second else None,
                "unfinished_at_close": unfinished,
                "decode_step_ms": 1e3 * (w["after"]["decode_seconds"] - w["before"]["decode_seconds"]) / max(steps, 1),
                "occupancy": (w["after"]["tokens"] - w["before"]["tokens"]
                              - (w["after"]["prefills"] - w["before"]["prefills"])) / max(steps, 1) / server.slots,
            }), flush=True)
    finally:
        server.close()


if __name__ == "__main__":
    main()

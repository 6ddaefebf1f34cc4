"""Open-loop HTTP load generator for ``POST /generate`` with streaming.

Runs as a child process that never imports JAX (one process owns the
chip): ``python benchmark/lib/client.py``. Protocol on stdin/stdout, one
JSON object per line:

    parent -> {"port": p, "warm": [request, ...], "requests": [request, ...],
               "drain_s": s}
    child  -> {"ready": true}                 (after the warm requests ended)
    parent -> {"t0": monotonic seconds}       (the window's first instant)
    child  -> {"results": [...]}              (after the last request ended,
                                               or drain_s after the last due)

A request is {"due": seconds after t0, "prompt": [ids], "max_new": n}. A
result has ``due``, ``sent`` (when the request left, on the same clock as
t0: CLOCK_MONOTONIC is one clock for every process of the machine),
``token_times``, ``tokens``, ``done`` and ``error``.
"""

import asyncio
import json
import sys
import time


async def _one(port, req, t0, result):
    delay = t0 + req["due"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    body = json.dumps({"prompt": req["prompt"], "max_new": req["max_new"],
                       "temperature": 0.0, "stream": True}).encode()
    result["sent"] = time.monotonic()
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"POST /generate HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\nConnection: close\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        status = (await reader.readline()).split()
        code = int(status[1]) if len(status) > 1 else 0
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if code != 200:
            result["error"] = f"HTTP {code}: {(await reader.read(300)).decode(errors='replace')}"
            return
        while True:
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            data = await reader.readexactly(size + 2)
            now = time.monotonic()
            for line in data.splitlines():
                if not line:
                    continue
                obj = json.loads(line)
                if "token" in obj:
                    result["token_times"].append(now)
                    result["tokens"].append(obj["token"])
                elif obj.get("done"):
                    result["done"] = True
                elif "error" in obj:
                    result["error"] = f"{obj['error']}: {obj.get('message', '')}"
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        result["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


def _blank(req):
    return {"due": req["due"], "sent": None, "token_times": [], "tokens": [],
            "done": False, "error": None}


async def _run(port, requests, t0, drain_s):
    results = [_blank(r) for r in requests]
    tasks = [asyncio.ensure_future(_one(port, r, t0, res))
             for r, res in zip(requests, results)]
    if tasks:
        last_due = t0 + max(r["due"] for r in requests)
        _, pending = await asyncio.wait(
            tasks, timeout=max(last_due + drain_s - time.monotonic(), 0.0))
        for task in pending:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return results


def main():
    job = json.loads(sys.stdin.readline())
    warm = asyncio.run(_run(job["port"], job["warm"], time.monotonic(), 600.0))
    print(json.dumps({"ready": True, "warm_errors": [r["error"] for r in warm if r["error"]]}),
          flush=True)
    go = json.loads(sys.stdin.readline())
    results = asyncio.run(_run(job["port"], job["requests"], go["t0"], job["drain_s"]))
    print(json.dumps({"results": results}), flush=True)


if __name__ == "__main__":
    main()

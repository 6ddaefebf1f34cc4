"""Package-boundary drive for speculative decoding + shared-prefix KV
reuse (ISSUE 16). User-style: everything through subprocesses and HTTP,
the way an operator would touch it — a live server runs a shared-prefix
storm with speculation on, outputs stay bit-identical across the storm,
/healthz surfaces the new knobs plus draft-acceptance and prefix-hit
telemetry, and `cli serve` accepts the new flags end-to-end."""
import json
import subprocess
import sys
import textwrap
import time
import os
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

checks = []


def check(name, ok, detail=""):
    checks.append((name, bool(ok)))
    print(f"[{'OK' if ok else 'FAIL'}] {name} {detail}", flush=True)


ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)


def post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.status, json.loads(r.read())


def get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, json.loads(r.read())


# --------------------------------------------------------------------------
# 1-5: shared-prefix storm over HTTP with speculation on (transformer)
# --------------------------------------------------------------------------
SERVER = textwrap.dedent("""\
    import sys
    import numpy as np
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.serving import (
        BucketPolicy, InferenceEngine, InferenceServer)
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    m = TransformerLM(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                      max_length=64, seed=7).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, (4, 32)).astype(np.int32)
    tgt = np.roll(ids, -1, 1).astype(np.int32); tgt[:, -1] = -1
    for _ in range(3):
        m.fit_batch(ids, tgt)
    gen = GenerationEngine(m, n_slots=2, max_length=64, spec_decode_k=4,
                           prefix_cache_mb=4.0)
    gen.warmup()
    eng = InferenceEngine(m, buckets=BucketPolicy(batch_buckets=[1]))
    srv = InferenceServer(eng, port=0, generation=gen).start()
    print(srv.port, flush=True)
    sys.stdin.readline()   # parent closes stdin to stop us
    srv.generation = None
    srv.shutdown()
""")

proc = subprocess.Popen([sys.executable, "-c", SERVER],
                        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                        text=True, env=ENV, cwd=REPO)
try:
    port = int(proc.stdout.readline())
    base = f"http://127.0.0.1:{port}"
    prompt = list(range(1, 25))  # the shared "system prompt"

    _s, first = post(base + "/generate",
                     {"prompt": prompt, "max_new": 16, "stream": False})
    seqs = []
    t0 = time.perf_counter()
    for _ in range(9):
        _s, body = post(base + "/generate",
                        {"prompt": prompt, "max_new": 16, "stream": False})
        seqs.append(body["sequence"])
    storm_s = time.perf_counter() - t0
    check("shared-prefix storm outputs bit-identical across requests",
          all(s == first["sequence"] for s in seqs),
          f"10 requests, {storm_s:.2f}s")

    _s, h = get(base + "/healthz")
    gen_info = h.get("generation", {})
    check("/healthz describes the speculation + prefix-cache knobs",
          gen_info.get("spec_decode_k") == 4
          and gen_info.get("draft_mode") == "ngram"
          and gen_info.get("prefix_cache", {}).get("limit_bytes")
          == 4 * (1 << 20),
          f"spec_decode_k={gen_info.get('spec_decode_k')} "
          f"draft_mode={gen_info.get('draft_mode')}")
    pc = gen_info.get("prefix_cache", {})
    check("prefix cache HIT on every repeat of the shared prompt",
          pc.get("lookups", 0) >= 10 and pc.get("hits", 0) >= 9,
          f"{pc.get('hits')}/{pc.get('lookups')} hits")

    _s, mx = get(base + "/metrics")
    gm = mx.get("generation", {})
    check("draft acceptance recorded and > 50% on repeated content",
          gm.get("draft_proposed", 0) > 0
          and gm.get("draft_acceptance", 0.0) > 0.5,
          f"acceptance={gm.get('draft_acceptance')}")
    check("prefill FLOPs avoided counted for the skipped prefills",
          gm.get("prefill_flops_avoided", 0) > 0,
          f"{gm.get('prefill_flops_avoided', 0):,} FLOPs")
finally:
    try:
        proc.stdin.close()
    except OSError:
        pass
    proc.wait(timeout=30)

# --------------------------------------------------------------------------
# 6: the new knobs ride `cli serve` end-to-end (recurrent zoo model —
# speculation needs a transformer and coerces off, prefix cache works)
# --------------------------------------------------------------------------
p = subprocess.Popen(
    [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
     "--model", "textgenlstm", "--num-classes", "16", "--port", "0",
     "--gen-slots", "2", "--gen-max-length", "32",
     "--spec-decode-k", "4", "--prefix-cache-mb", "2"],
    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    env=ENV, cwd=REPO)
try:
    port = None
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline:
        line = p.stdout.readline()
        if not line:
            break
        if line.startswith("listening on"):
            port = int(line.split(":")[2].split()[0].rstrip("/"))
            break
    ok_boot = port is not None
    hits = 0
    if ok_boot:
        prompt = [1, 2, 3, 4, 5]
        _s, a = post(f"http://127.0.0.1:{port}/generate",
                     {"prompt": prompt, "max_new": 6, "stream": False})
        _s, b = post(f"http://127.0.0.1:{port}/generate",
                     {"prompt": prompt, "max_new": 6, "stream": False})
        _s, h = get(f"http://127.0.0.1:{port}/healthz")
        pc = h.get("generation", {}).get("prefix_cache", {})
        hits = pc.get("hits", 0)
        ok_boot = a["sequence"] == b["sequence"] and hits >= 1
    check("cli serve accepts --spec-decode-k/--prefix-cache-mb and the "
          "prefix cache hits over HTTP", ok_boot,
          f"port={port} hits={hits}")
finally:
    p.terminate()
    try:
        p.wait(timeout=15)
    except subprocess.TimeoutExpired:
        p.kill()

# --------------------------------------------------------------------------
n_bad = sum(1 for _n, ok in checks if not ok)
print(f"\ndrive_generate: {len(checks) - n_bad}/{len(checks)} checks green")
sys.exit(1 if n_bad else 0)

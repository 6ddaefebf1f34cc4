"""Package-boundary drive for mesh-sharded serving (ISSUE 20).
User-style: a live server runs with tensor-parallel engines on a 2x4
(batch, model) mesh — /predict answers match a replicated engine of the
same seed, /generate streams the same greedy tokens solo decode would,
/healthz surfaces the mesh/policy/shard-report telemetry, and
`cli serve --mesh` boots a sharded zoo model end-to-end with a 0-byte
reshard ledger."""
import json
import os
import subprocess
import sys
import textwrap
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

checks = []


def check(name, ok, detail=""):
    checks.append((name, bool(ok)))
    print(f"[{'OK' if ok else 'FAIL'}] {name} {detail}", flush=True)


ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
           XLA_FLAGS="--xla_force_host_platform_device_count=8")


def post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=240) as r:
        return r.status, json.loads(r.read())


def get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, json.loads(r.read())


# --------------------------------------------------------------------------
# 1-5: sharded server over HTTP — predict parity, greedy generation
# parity, /healthz shard telemetry. The solo references are computed in
# a SEPARATE process (same seeds) so nothing is shared but determinism.
# --------------------------------------------------------------------------
SERVER = textwrap.dedent("""\
    import sys
    import numpy as np
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.serving_mesh import ServingMesh
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.serving.sharded import (
        ShardedInferenceEngine, sharded_generation_engine)

    conf = (NeuralNetConfiguration.builder().seed(21).list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=8, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    mesh = ServingMesh(batch=2, model=4)
    eng = ShardedInferenceEngine(MultiLayerNetwork(conf).init(), mesh=mesh)
    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                       max_length=64, seed=9).init()
    gen = sharded_generation_engine(lm, mesh, n_slots=4, max_length=64)
    srv = InferenceServer(eng, port=0, generation=gen).start()
    print(srv.port, flush=True)
    sys.stdin.readline()   # parent closes stdin to stop us
    srv.generation = None
    srv.shutdown()
""")

SOLO = textwrap.dedent("""\
    import json
    import numpy as np
    from deeplearning4j_tpu.models.transformer_lm import TransformerLM
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import InferenceEngine
    from deeplearning4j_tpu.serving.generate import GenerationEngine

    conf = (NeuralNetConfiguration.builder().seed(21).list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=8, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    eng = InferenceEngine(MultiLayerNetwork(conf).init())
    x = np.linspace(-1.0, 1.0, 4 * 16, dtype=np.float32).reshape(4, 16)
    y = eng.infer(x)
    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                       max_length=64, seed=9).init()
    gen = GenerationEngine(lm, n_slots=4, max_length=64)
    try:
        r = gen.submit(np.asarray([5, 9, 11, 2]), max_new=12,
                       temperature=0.0)
        toks = [int(t) for t in r.result(timeout=120)]
    finally:
        gen.shutdown()
    print(json.dumps({"y": y.tolist(), "tokens": toks}))
""")

solo_out = subprocess.run([sys.executable, "-c", SOLO], check=True,
                          capture_output=True, text=True, env=ENV,
                          cwd=REPO)
solo = json.loads(solo_out.stdout.splitlines()[-1])

proc = subprocess.Popen([sys.executable, "-c", SERVER],
                        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                        text=True, env=ENV, cwd=REPO)
try:
    port = int(proc.stdout.readline())
    base = f"http://127.0.0.1:{port}"

    x = [[float(v) for v in row]
         for row in __import__("numpy").linspace(
             -1.0, 1.0, 4 * 16).reshape(4, 16)]
    _s, body = post(base + "/predict", {"inputs": x})
    import numpy as np

    y_sh = np.asarray(body["outputs"], dtype=np.float32)
    y_solo = np.asarray(solo["y"], dtype=np.float32)
    check("sharded /predict matches a replicated engine (rtol 1e-5)",
          np.allclose(y_solo, y_sh, rtol=1e-5, atol=1e-6),
          f"max abs diff {np.max(np.abs(y_solo - y_sh)):.2e}")

    _s, g1 = post(base + "/generate",
                  {"prompt": [5, 9, 11, 2], "max_new": 12, "stream": False})
    _s, g2 = post(base + "/generate",
                  {"prompt": [5, 9, 11, 2], "max_new": 12, "stream": False})
    check("sharded greedy /generate matches solo decode token-for-token",
          g1["sequence"] == solo["tokens"],
          f"{len(g1['sequence'])} tokens")
    check("repeat sharded /generate is bit-identical",
          g1["sequence"] == g2["sequence"])

    _s, h = get(base + "/healthz")
    rep = h.get("shard_report") or {}
    check("/healthz surfaces mesh + policy + shard report",
          h.get("mesh") == {"batch": 2, "model": 4}
          and rep.get("policy") == "auto"
          and 0 < rep.get("per_device_bytes", 0) < rep.get("total_bytes", 0)
          and h.get("fallback_active") is False,
          f"per-device {rep.get('per_device_bytes'):,}/"
          f"{rep.get('total_bytes'):,} bytes")
finally:
    try:
        proc.stdin.close()
    except OSError:
        pass
    proc.wait(timeout=30)

# --------------------------------------------------------------------------
# 6: `cli serve --mesh` boots a sharded zoo model end-to-end
# --------------------------------------------------------------------------
t0 = time.perf_counter()
r = subprocess.run(
    [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
     "--model", "lenet", "--num-classes", "8", "--mesh", "2x4",
     "--cpu-mesh", "8", "--port", "0", "--smoke"],
    capture_output=True, text=True, env=dict(os.environ), cwd=REPO,
    timeout=600)
out = r.stdout
check("cli serve --mesh 2x4 boots, shards, and answers the smoke request",
      r.returncode == 0 and "sharded: policy auto" in out
      and "reshard host bytes 0" in out and "smoke: HTTP 200 ok" in out,
      f"{time.perf_counter() - t0:.1f}s")

# --------------------------------------------------------------------------
n_bad = sum(1 for _n, ok in checks if not ok)
print(f"\ndrive_sharded: {len(checks) - n_bad}/{len(checks)} checks green")
sys.exit(1 if n_bad else 0)

"""Package-boundary drive for load generation + adaptive capacity
(ISSUE 18). User-style: everything through subprocesses and HTTP, the
way an operator (or CI) would touch it — `cli loadgen` compiles
declarative plans deterministically (same seed → byte-identical
fingerprint, different seed → different stream), a ChaosPlan-idiom
JSON plan file round-trips through the CLI, a malformed plan fails
fast with a typed message, a compiled stream replays over the wire
against a live server, and `cli serve --smoke --controllers` closes
the observe→act loop end to end: SLO breach → verdict → deadline
retune, every action a verdict-carrying flight event."""
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

checks = []


def check(name, ok, detail=""):
    checks.append((name, bool(ok)))
    print(f"[{'OK' if ok else 'FAIL'}] {name} {detail}", flush=True)


ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)


def cli(*args, timeout=300):
    p = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.cli", *args],
        capture_output=True, text=True, cwd=REPO, env=ENV,
        timeout=timeout)
    return p.returncode, p.stdout, p.stderr


# --------------------------------------------------------------------------
# 1-3: CLI plan compilation is deterministic and seed-sensitive
# --------------------------------------------------------------------------
rc, out, _ = cli("loadgen", "--list")
check("loadgen --list names both builtin plans",
      rc == 0 and "diurnal_flash" in out and "cluster" in out)


def compile_fp(*extra):
    rc, out, err = cli("loadgen", "--builtin", "diurnal_flash",
                       "--compile-only", "--json", "--duration-s", "15",
                       *extra)
    assert rc == 0, err
    return json.loads(out)["fingerprint"]


fp_a = compile_fp("--seed", "9")
fp_b = compile_fp("--seed", "9")
check("same seed compiles an identical stream (fingerprint)",
      fp_a == fp_b, fp_a[:16])
fp_c = compile_fp("--seed", "10")
check("different seed compiles a different stream", fp_c != fp_a)

# --------------------------------------------------------------------------
# 4-5: ChaosPlan-idiom JSON plan files — good one compiles, bad one
# fails fast with a typed message
# --------------------------------------------------------------------------
with tempfile.TemporaryDirectory() as td:
    good = os.path.join(td, "plan.json")
    with open(good, "w") as f:
        json.dump({
            "name": "drive-custom",
            "seed": 3,
            "duration_s": 10.0,
            "arrivals": [{"process": "poisson", "rps": 12.0}],
            "tenants": [
                {"name": "steady", "kind": "predict",
                 "rows": {"dist": "lognormal", "median": 2,
                          "sigma": 0.5, "max": 8}},
                {"name": "spam", "weight": 1,
                 "adversarial": "one_token_spam"},
            ],
        }, f)
    rc, out, _ = cli("loadgen", "--plan", good, "--compile-only",
                     "--json")
    body = json.loads(out) if rc == 0 else {}
    check("custom JSON plan file compiles through the CLI",
          rc == 0 and body.get("plan") == "drive-custom"
          and body.get("n_requests", 0) > 0,
          f"n={body.get('n_requests')}")

    bad = os.path.join(td, "bad.json")
    with open(bad, "w") as f:
        json.dump({"arrivals": [{"process": "warp_drive"}],
                   "tenants": [{"name": "t"}]}, f)
    rc, out, err = cli("loadgen", "--plan", bad, "--compile-only")
    check("unknown arrival process fails fast",
          rc != 0 and "warp_drive" in (out + err),
          (out + err).strip().splitlines()[0] if (out + err).strip()
          else "")

# --------------------------------------------------------------------------
# 6: replay a compiled stream over the wire against a live server
# --------------------------------------------------------------------------
os.environ["JAX_PLATFORMS"] = "cpu"
from deeplearning4j_tpu.nn.conf import (  # noqa: E402
    InputType,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf.layers import (  # noqa: E402
    DenseLayer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.serving import (  # noqa: E402
    BucketPolicy,
    InferenceEngine,
    InferenceServer,
)

conf = (NeuralNetConfiguration.builder().seed(1).list()
        .layer(DenseLayer(n_out=8, activation="tanh"))
        .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(4)).build())
engine = InferenceEngine(MultiLayerNetwork(conf).init(),
                         buckets=BucketPolicy(batch_buckets=[8],
                                              max_batch=8))
engine.warmup()
server = InferenceServer(engine, port=0)
server.start()
time.sleep(0.2)
try:
    rc, out, _ = cli("loadgen", "--builtin", "cluster",
                     "--duration-s", "6", "--seed", "2",
                     "--compression", "6", "--shape", "4",
                     "--replay", f"127.0.0.1:{server.port}", "--json")
    body = json.loads(out) if rc == 0 else {}
    rep = body.get("report", {})
    check("CLI replay over HTTP lands ok responses on a live server",
          rc == 0 and rep.get("outcomes", {}).get("ok", 0) > 0,
          str(rep.get("outcomes")))
finally:
    server.shutdown()

# --------------------------------------------------------------------------
# 7: the closed loop end to end — serve --smoke --controllers replays
# a compressed diurnal+flash day against its own HTTP front under a
# deliberately tight SLO and must observe verdict-carrying retunes
# --------------------------------------------------------------------------
rc, out, err = cli("serve", "--model", "lenet", "--port", "0",
                   "--smoke", "--controllers", timeout=600)
check("serve --smoke --controllers: breach → verdict → deadline retune",
      rc == 0 and "controller_retune" in out
      and "serving_latency_slo_breach" in out,
      (out.strip().splitlines()[-1] if out.strip() else err[-200:]))

# --------------------------------------------------------------------------
n_bad = sum(1 for _n, ok in checks if not ok)
print(f"\ndrive_loadgen: {len(checks) - n_bad}/{len(checks)} checks green")
sys.exit(1 if n_bad else 0)

"""Package-boundary drive for the fused-kernel layer (ISSUE 12).
User-style: import the package, serve int8 over real HTTP, run the
generation engine on the cell decode path, read the kernel registry's
observability surface. Runs on the CPU (kernels interpret or fall back)."""
import json
import os
import sys
import urllib.request

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

checks = []


def check(name, ok, detail=""):
    checks.append((name, bool(ok)))
    print(f"[{'OK' if ok else 'FAIL'}] {name} {detail}", flush=True)


# 1-3: int8 serving over real HTTP ---------------------------------------
from deeplearning4j_tpu.models.lenet import LeNet
from deeplearning4j_tpu.serving.engine import InferenceEngine
from deeplearning4j_tpu.serving.server import InferenceServer
from deeplearning4j_tpu.obs.metrics import default_registry

net = LeNet(num_classes=10).init()
rng = np.random.default_rng(0)
X = rng.standard_normal((60, 28, 28, 1)).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 60)]
for _ in range(10):  # train to sharp logits: top-1 agreement is only a
    net.fit(X, y)    # meaningful oracle when top-2 gaps exceed the
    # per-channel quantization error (~3e-4 on these heads)

eng = InferenceEngine(net, int8_serving=True)
rep = eng.warmup()
check("int8 engine warms every bucket", rep["compiles"] > 0, str(rep))
check("int8 report", eng.int8_report and
      eng.int8_report["layers_quantized"] >= 1, str(eng.int8_report))
ref = InferenceEngine(net).infer(X[:16])
got = eng.infer(X[:16])
check("int8 top-1 == f32 top-1",
      np.array_equal(np.argmax(ref, 1), np.argmax(got, 1)))

srv = InferenceServer(eng, port=0).start()
port = srv.port
try:
    body = json.dumps({"inputs": X[:2].tolist()}).encode()
    r = urllib.request.urlopen(
        urllib.request.Request(f"http://127.0.0.1:{port}/predict",
                               data=body), timeout=30)
    out = json.loads(r.read())
    check("HTTP /predict 200 on int8 engine",
          r.status == 200 and len(out["outputs"]) == 2)
    h = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=10).read())
    check("healthz describes int8", h.get("int8_serving") is True, str(
        {k: h.get(k) for k in ("int8_serving",)}))
finally:
    srv.shutdown()

# 4-6: generation engine on the cell decode path -------------------------
from deeplearning4j_tpu.models.textgen_lstm import TextGenerationLSTM
from deeplearning4j_tpu.serving.generate import GenerationEngine

tg = TextGenerationLSTM(num_classes=77, units=64, max_length=32).init()
gen = GenerationEngine(tg, n_slots=4, max_length=64)
check("decode cell path auto-selected", gen.backend.cell_path)
gen.warmup()
before = dict(gen.trace_counts)
outs = [gen.generate(rng.integers(0, 77, (10,)).astype(np.int32),
                     max_new=12) for _ in range(6)]
retr = sum(gen.trace_counts.get(k, 0) - before.get(k, 0)
           for k in gen.trace_counts)
check("6 generations, 0 steady-state retraces",
      retr == 0 and all(o.shape[0] == 22 for o in outs))
legacy = GenerationEngine(tg, n_slots=4, max_length=64,
                          decode_cell_path=False)
legacy.warmup()
outs2 = [legacy.generate(o[:10], max_new=12) for o in outs]
check("cell path bit-identical to legacy decode",
      all(np.array_equal(a, b) for a, b in zip(outs, outs2)))
legacy.shutdown()
gen.shutdown()

# 7-9: registry observability --------------------------------------------
from deeplearning4j_tpu.nn.ops.registry import default_kernel_registry
from deeplearning4j_tpu.obs import flight

snap = default_kernel_registry().snapshot()
check("registry resolved kernels this process", len(snap) >= 1,
      str({k: len(v) for k, v in snap.items()}))
evts = [e for e in flight.default_flight_recorder().events()
        if e["kind"] == "kernel_fallback"]
check("kernel_fallback flight events on CPU",
      len(evts) >= 1, evts[0].get("reason", "") if evts else "")
prom = default_registry().prometheus_text()
check("kernel_enabled gauge scrapeable", "kernel_enabled{" in prom)

# 10: fused kernels through the interpreter (real kernel math on CPU) ----
os.environ["DL4J_TPU_FUSED_LSTM"] = "interpret"
default_kernel_registry().reset("fused_lstm")
gen_k = GenerationEngine(tg, n_slots=4, max_length=64)
gen_k.warmup()
outs3 = [gen_k.generate(o[:10], max_new=12) for o in outs]
gen_k.shutdown()
check("interpret-mode fused cell decode bit-identical",
      all(np.array_equal(a, b) for a, b in zip(outs, outs3)))
snap = default_kernel_registry().snapshot().get("fused_lstm", {})
check("fused_lstm probe green under interpreter",
      any(v["enabled"] for v in snap.values()), str(snap))

fails = [n for n, ok in checks if not ok]
print(f"\n{len(checks) - len(fails)}/{len(checks)} checks passed")
sys.exit(1 if fails else 0)

"""Package-boundary drive for the invariant analyzer + lock witness
(ISSUE 14). User-style: invoke `cli lint` the way CI would — clean
tree exits 0 against the reviewed baseline, each seeded defect class
flips it non-zero with an accurate file:line, the baseline suppresses
and expires, --json parses — then arm the lock witness and catch a
synthetic ABBA typed."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

checks = []


def check(name, ok, detail=""):
    checks.append((name, bool(ok)))
    print(f"[{'OK' if ok else 'FAIL'}] {name} {detail}", flush=True)


def cli_lint(*args, cwd=None):
    """Run `python -m deeplearning4j_tpu.cli lint ...` as an operator
    would (package boundary: separate process, no test harness)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.cli", "lint", *args],
        capture_output=True, text=True, cwd=cwd or REPO, env=env)
    return p.returncode, p.stdout, p.stderr


# 1-2: clean shipped tree gates green against the reviewed baseline ------
rc, out, err = cli_lint()
check("clean tree exits 0", rc == 0, out.strip().splitlines()[-1]
      if out.strip() else err[-200:])
rc, out, _ = cli_lint("--json")
body = json.loads(out)
check("--json parses; ok=true, 0 active, 0 stale",
      body["ok"] and body["counts"]["active"] == 0
      and body["counts"]["stale"] == 0, str(body["counts"]))

# 3-6: each defect class seeded into a scratch tree flips non-zero with
# file:line --------------------------------------------------------------
SEEDS = {
    "durability-unsynced-replace": ("pkg/train/ckpt.py", 4, """\
        import os

        def publish(t, d):
            os.replace(t, d)
        """),
    "typed-errors-bare-raise": ("pkg/serving/router.py", 3, """\
        def pick(d, k):
            if k not in d:
                raise KeyError(k)
            return d[k]
        """),
    "trace-host-sync": ("pkg/train/steps.py", 5, """\
        import jax

        def make():
            def step(p, b):
                return p * float(b.sum())
            return jax.jit(step)
        """),
    "event-schema": ("pkg/obs_bits.py", 4, """\
        from deeplearning4j_tpu.obs import flight as _flight

        def w():
            _flight.record("never_declared_event_drive")
        """),
}
for rule, (rel, line, src) in SEEDS.items():
    with tempfile.TemporaryDirectory(prefix="drive_lint_") as tmp:
        path = os.path.join(tmp, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(src))
        rc, out, _ = cli_lint("--root", tmp, "--no-baseline",
                              os.path.join(tmp, "pkg"))
        loc = f"{rel}:{line}"
        check(f"seeded {rule} -> non-zero with {loc}",
              rc != 0 and loc in out and rule in out,
              out.strip().splitlines()[0] if out.strip() else "")

# 7-9: baseline suppresses, then expires loudly --------------------------
with tempfile.TemporaryDirectory(prefix="drive_lint_bl_") as tmp:
    rel, line, src = SEEDS["durability-unsynced-replace"]
    path = os.path.join(tmp, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(src))
    bl = os.path.join(tmp, "BASELINE.json")
    rc, out, _ = cli_lint("--root", tmp, "--no-baseline",
                          "--write-baseline", bl,
                          os.path.join(tmp, "pkg"))
    check("--write-baseline triages the finding",
          rc == 0 and os.path.exists(bl), out.strip())
    rc, out, _ = cli_lint("--root", tmp, "--baseline", bl,
                          os.path.join(tmp, "pkg"))
    check("baseline suppresses -> exit 0",
          rc == 0 and "suppressed" in out, out.strip().splitlines()[-1])
    with open(path, "w") as f:  # fix the violation: entry goes stale
        f.write("import os\n\ndef publish(t, d):\n"
                "    os.fsync(0)\n    os.replace(t, d)\n")
    rc, out, _ = cli_lint("--root", tmp, "--baseline", bl,
                          os.path.join(tmp, "pkg"))
    check("fixed finding -> stale baseline entry fails loudly",
          rc != 0 and "stale" in out, out.strip().splitlines()[-1])

# 10: the events table renders and matches ARCHITECTURE ------------------
rc, out, _ = cli_lint("--events-table")
arch = open(os.path.join(REPO, "ARCHITECTURE.md")).read()
check("--events-table renders and ARCHITECTURE embeds it",
      rc == 0 and out.strip() in arch,
      f"{len(out.splitlines())} lines")

# 11-12: lock witness catches a synthetic ABBA typed + flight event ------
import threading
import time

from deeplearning4j_tpu.obs import flight, lockwitness as lw
from deeplearning4j_tpu.obs.lockwitness import LockOrderViolationError

lw.reset()
A = lw.witnessed_rlock("drive.A")
B = lw.witnessed_rlock("drive.B")
errors = []
seq0 = flight.default_flight_recorder().recorded_total
with lw.armed(strict=True):
    barrier = threading.Barrier(2)

    def fwd():
        with A:
            barrier.wait()
            time.sleep(0.05)
            try:
                with B:
                    pass
            except LockOrderViolationError as e:
                errors.append(e)

    def bwd():
        barrier.wait()
        with B:
            time.sleep(0.05)
            try:
                with A:
                    pass
            except LockOrderViolationError as e:
                errors.append(e)

    ts = [threading.Thread(target=fwd), threading.Thread(target=bwd)]
    [t.start() for t in ts]
    [t.join(timeout=30) for t in ts]
check("ABBA -> typed LockOrderViolationError",
      len(errors) == 1 and isinstance(errors[0],
                                      LockOrderViolationError),
      str(errors[:1]))
evs = [e for e in flight.default_flight_recorder().events()
       if e["seq"] >= seq0 and e["kind"] == "lock_cycle"]
check("lock_cycle flight event recorded", len(evs) == 1,
      evs[0].get("cycle") if evs else "none")

# 13: a chaos drill runs green under the witness with 0 cycles -----------
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import jax

jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.chaos import drills

card = drills.run_matrix(names=["checkpoint_enospc"])
check("drill green under witness, scorecard lock_cycles == 0",
      card["ok"] and card["lock_cycles"] == 0,
      f"lock_cycles={card['lock_cycles']}")

n_bad = sum(1 for _, ok in checks if not ok)
print(f"\n{len(checks) - n_bad}/{len(checks)} checks green")
sys.exit(1 if n_bad else 0)

"""Package-boundary drive for the sharded input pipeline (ISSUE 19).
User-style: everything through the CLI the way an operator (or CI)
would touch it — `cli data pack` drains a dataset into record shards,
`cli data verify` CRC-checks them (and fails non-zero once a byte is
flipped), a fit trained from `--data-dir` prints its deterministic
stream fingerprint, a SIGKILL mid-run leaves a valid checkpoint whose
meta carries the data position, and `--resume` replays the EXACT
remaining batch stream: the resumed run's final fingerprint is
bit-identical to the uninterrupted oracle's. The resumed run's flight
dump shows the `data_resume` forensic."""
import json
import os
import re
import signal
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


FP_RE = re.compile(r"data stream fingerprint ([0-9a-f]{64}) "
                   r"\(batches=(\d+)\)")

td = tempfile.mkdtemp(prefix="drive_data_")
shards = os.path.join(td, "shards")
EPOCHS = 3

# --------------------------------------------------------------------------
# 1-2: pack a real dataset into record shards; verify is green
# --------------------------------------------------------------------------
rc, out, err = cli("data", "pack", "--dataset", "mnist",
                   "--batch-size", "16", "--num-examples", "96",
                   "--out", shards, "--shard-size", "2")
check("data pack drains mnist into record shards",
      rc == 0 and "packed" in out, out.strip()[:80] or err[-120:])
rc, out, _ = cli("data", "verify", shards)
check("data verify is green on a fresh pack", rc == 0 and "0 bad" in out)

# --------------------------------------------------------------------------
# 3: flip one payload byte — verify must fail typed and non-zero
# --------------------------------------------------------------------------
victim = os.path.join(shards, sorted(
    f for f in os.listdir(shards) if f.endswith(".dl4jshard"))[0])
orig = open(victim, "rb").read()
raw = bytearray(orig)
raw[len(raw) // 2] ^= 0xFF
open(victim, "wb").write(bytes(raw))
rc, out, _ = cli("data", "verify", shards, "--json")
rep = json.loads(out) if out.strip().startswith("{") else {}
check("data verify fails non-zero on a flipped byte",
      rc == 1 and rep.get("bad") == 1,
      str([s["error"] for s in rep.get("shards", []) if not s["ok"]])[:90])
open(victim, "wb").write(orig)  # heal for the training legs

# --------------------------------------------------------------------------
# 4: uninterrupted oracle fit — the reference stream fingerprint
# --------------------------------------------------------------------------
ck_oracle = os.path.join(td, "ck_oracle")
rc, out, err = cli("--model", "lenet", "--dataset", "mnist",
                   "--data-dir", shards, "--epochs", str(EPOCHS),
                   "--checkpoint-dir", ck_oracle, timeout=600)
m = FP_RE.search(out)
check("oracle fit from --data-dir prints its stream fingerprint",
      rc == 0 and m is not None,
      m.group(1)[:16] if m else (err[-150:] or out[-150:]))
oracle_fp, oracle_batches = (m.group(1), int(m.group(2))) if m else ("", 0)

# --------------------------------------------------------------------------
# 5: SIGKILL mid-run — poll for the first checkpoint, then kill -9
# --------------------------------------------------------------------------
ck_kill = os.path.join(td, "ck_kill")
proc = subprocess.Popen(
    [sys.executable, "-m", "deeplearning4j_tpu.cli", "--model", "lenet",
     "--dataset", "mnist", "--data-dir", shards, "--epochs", str(EPOCHS),
     "--checkpoint-dir", ck_kill],
    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    cwd=REPO, env=ENV)
deadline = time.time() + 240
ckpt = None
while time.time() < deadline and proc.poll() is None:
    # .zip only: atomic-rename staging files are checkpoint_*.zip.tmp-*
    done = [f for f in (os.listdir(ck_kill) if os.path.isdir(ck_kill)
                        else []) if f.startswith("checkpoint_")
            and f.endswith(".zip")]
    if done:
        ckpt = sorted(done)[-1]
        break
    time.sleep(0.1)
if proc.poll() is None:
    proc.send_signal(signal.SIGKILL)
    proc.wait()
check("SIGKILL landed after the first mid-run checkpoint",
      ckpt is not None and proc.returncode == -signal.SIGKILL,
      str(ckpt))

# --------------------------------------------------------------------------
# 6-7: resume replays the EXACT remaining stream through the CLI
# --------------------------------------------------------------------------
epoch_done = int(re.search(r"epoch_(\d+)", ckpt).group(1)) if ckpt else 0
remaining = EPOCHS - epoch_done
rc, out, err = cli("--model", "lenet", "--dataset", "mnist",
                   "--data-dir", shards, "--epochs", str(remaining),
                   "--checkpoint-dir", ck_kill, "--resume", timeout=600)
check("resume restores the checkpointed data position",
      rc == 0 and "data resume:" in out,
      next((line for line in out.splitlines()
            if line.startswith("data resume:")), err[-120:]))
m = FP_RE.search(out)
check("resumed stream fingerprint is bit-identical to the oracle's",
      m is not None and m.group(1) == oracle_fp
      and int(m.group(2)) == oracle_batches,
      f"{(m.group(1)[:16] if m else '?')} vs {oracle_fp[:16]} "
      f"(batches {m.group(2) if m else '?'}/{oracle_batches})")

# --------------------------------------------------------------------------
# 8: the black box of the resumed run shows the data_resume forensic
# --------------------------------------------------------------------------
rc, out, _ = cli("flight-dump", ck_kill)
check("flight-dump shows the data_resume forensic",
      rc == 0 and "data_resume" in out)

# --------------------------------------------------------------------------
n_bad = sum(1 for _n, ok in checks if not ok)
print(f"\ndrive_data: {len(checks) - n_bad}/{len(checks)} checks green")
sys.exit(1 if n_bad else 0)

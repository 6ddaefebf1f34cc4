"""Package-boundary drive for the chaos-engineering subsystem
(ISSUE 13). User-style: import the package, arm declarative fault
plans around real workloads (fit + checkpoints, registry publish,
generation), run the drill matrix, and read the forensic surfaces the
invariant checker reads. CPU container (8-device virtual mesh)."""
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

checks = []


def check(name, ok, detail=""):
    checks.append((name, bool(ok)))
    print(f"[{'OK' if ok else 'FAIL'}] {name} {detail}", flush=True)


import tempfile

from deeplearning4j_tpu.chaos import (
    ChaosPlan,
    StorageError,
    hooks,
    list_seams,
    load_plan,
)
from deeplearning4j_tpu.chaos import drills
from deeplearning4j_tpu.obs import flight

# 1-2: the seam registry is the documented, discoverable surface ---------
seams = list_seams()
check("seam registry >= 12 seams", len(seams) >= 12,
      f"{len(seams)} seams")
check("every subsystem has a seam",
      {"storage", "serving", "generation", "training", "deployment",
       "kernels"} <= {s["subsystem"] for s in seams})

# 3-5: a declarative JSON plan (operator-style: text, not code) arms a
# disk-full fault under a real checkpointing fit ---------------------------
plan = load_plan(json.dumps({
    "name": "drive-enospc", "seed": 3,
    "faults": [{"seam": "fs.replace", "mode": "enospc", "at_call": 2,
                "match": {"surface": "checkpoint"}}]}))
tmp = tempfile.mkdtemp(prefix="drive_chaos_")
from deeplearning4j_tpu.chaos.drills import _batches, _net, _policy
from deeplearning4j_tpu.data import ExistingDataSetIterator
from deeplearning4j_tpu.train import faults
from deeplearning4j_tpu.train.listeners import CheckpointListener

model = _net(policy=_policy())
ck = os.path.join(tmp, "ckpts")
model.add_listeners(CheckpointListener(ck, save_every_n_epochs=1,
                                       keep_mode="last", keep_last=3))
err = None
seq0 = flight.default_flight_recorder().recorded_total
with plan.armed():
    try:
        model.fit(ExistingDataSetIterator(_batches(3)), epochs=3)
    except StorageError as e:
        err = e
check("second checkpoint publish fails typed StorageError",
      err is not None and err.surface == "checkpoint", repr(err))
check("previous checkpoint survives and loads",
      faults.load_latest_valid(ck)[1].endswith(".zip"))
check("no staging litter after the failed write",
      not [n for n in os.listdir(ck) if ".tmp-" in n])
check("nothing stays armed after the plan exits",
      hooks.armed_points() == [])
evs = [e["kind"] for e in flight.default_flight_recorder().events()
       if e["seq"] >= seq0]
check("forensics: chaos_inject + storage_error in the black box",
      "chaos_inject" in evs and "storage_error" in evs)

# 8: orphaned staging debris from a PRIOR crash is swept on dir open -----
import time as _time

stale = os.path.join(ck, "old.zip.tmp-1-dead")
open(stale, "w").write("junk")
os.utime(stale, (0, 0))
CheckpointListener(ck, save_every_n_epochs=1)
check("stale .tmp swept on checkpoint-dir open",
      not os.path.exists(stale))

# 9-11: the drill matrix through the CLI entry point ----------------------
from deeplearning4j_tpu.cli import chaos_main

out_path = os.path.join(tmp, "scorecard.json")
rc = chaos_main(["--fast", "--out", out_path])
with open(out_path) as f:
    scorecard = json.load(f)
check("cli chaos --fast exits 0 (all single-fault drills green)",
      rc == 0, f"rc={rc}")
check("fast matrix covers >= 12 drills",
      scorecard["n_drills"] >= 12, f"{scorecard['n_drills']} drills")
check("zero silent-corruption findings",
      not scorecard["silent_corruption_findings"])

# 12-13: one paired-fault storm end to end -------------------------------
t0 = _time.monotonic()
r = drills.run_drill("paired_ckpt_corrupt_during_recovery")
check("paired drill (ckpt corruption DURING dropout recovery) green",
      r.ok, json.dumps([c for c in r.checks if not c["ok"]]))
check("paired drill within deadline",
      _time.monotonic() - t0 < 240.0)

# 14: the generation->canary-gate residue drill --------------------------
r = drills.run_drill("generation_canary_gate")
check("generation-only regression trips auto-rollback", r.ok,
      json.dumps([c for c in r.checks if not c["ok"]]))

import shutil

shutil.rmtree(tmp, ignore_errors=True)
failed = [n for n, ok in checks if not ok]
print(f"\n{len(checks) - len(failed)}/{len(checks)} checks passed",
      flush=True)
if failed:
    print("FAILED:", failed, flush=True)
    sys.exit(1)
print("drive_chaos: ALL GREEN", flush=True)

"""Package-boundary drive for the SLO alert engine (ISSUE 15).
User-style: everything through subprocesses and HTTP, the way an
operator (or CI) would touch it — a live metrics endpoint serves
/alerts (JSON + Prometheus) and a verdict-enriched /healthz, a real
injected fault flips the verdict, `cli alerts` renders it with the
rollout exit code, the flight ring scrapes incrementally via
?since_seq, `cli flight-dump` merges two processes' rings into one
timeline, the chaos matrix verifies detection on a drill, lint gates
the alert-name schema, and the doc tables are byte-identical."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time
import urllib.request

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


def get(url, accept=None):
    req = urllib.request.Request(
        url, headers={} if accept is None else {"Accept": accept})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, r.headers.get("Content-Type"), r.read()


# --------------------------------------------------------------------------
# 1-6: a live metrics endpoint, watched and faulted over HTTP
# --------------------------------------------------------------------------
SERVER = textwrap.dedent("""\
    import sys, time
    from deeplearning4j_tpu.obs.exporter import MetricsServer
    from deeplearning4j_tpu.obs import flight

    srv = MetricsServer(port=0).start()
    print(srv.port, flush=True)
    for line in sys.stdin:   # parent drives: each line records an event
        kind = line.strip()
        if not kind:
            break
        flight.record(kind, injected_by="drive_alerts")
        print("recorded", flush=True)
""")

proc = subprocess.Popen([sys.executable, "-c", SERVER],
                        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                        text=True, env=ENV, cwd=REPO)
try:
    port = int(proc.stdout.readline())
    base = f"http://127.0.0.1:{port}"

    _s, _c, body = get(base + "/alerts")
    body = json.loads(body)
    check("live /alerts answers JSON with a healthy verdict",
          body["verdict"]["status"] in ("healthy", "unknown")
          and len(body["alerts"]) >= 15,
          f"{body['verdict']['status']}, {len(body['alerts'])} rules")

    proc.stdin.write("storage_error\n")
    proc.stdin.flush()
    proc.stdout.readline()
    time.sleep(1.1)  # clear the scrape-tick throttle
    _s, _c, body = get(base + "/alerts")
    firing = [a["name"] for a in json.loads(body)["alerts"]
              if a["state"] == "firing"]
    check("injected storage_error flips storage_errors to firing",
          "storage_errors" in firing, str(firing))

    _s, ctype, text = get(base + "/alerts", accept="text/plain")
    check("/alerts content-negotiates a Prometheus ALERTS list",
          ctype.startswith("text/plain")
          and b'alertname="storage_errors"' in text, ctype)

    _s, _c, h = get(base + "/healthz")
    check("/healthz carries the critical verdict",
          json.loads(h)["verdict"]["status"] == "critical",
          json.loads(h)["verdict"]["status"])

    _s, _c, f1 = get(base + "/debug/flight")
    cur = json.loads(f1)["next_since_seq"]
    proc.stdin.write("checkpoint_write\n")
    proc.stdin.flush()
    proc.stdout.readline()
    _s, _c, f2 = get(base + f"/debug/flight?since_seq={cur}")
    evs = json.loads(f2)["events"]
    check("incremental /debug/flight?since_seq returns only new events",
          any(e["kind"] == "checkpoint_write" for e in evs)
          and all(e["seq"] > cur for e in evs),
          f"{len(evs)} new events past seq {cur}")

    rc, out, err = cli("alerts", base)
    check("cli alerts one-shot exits 2 on a critical verdict "
          "(rollout-gate contract)",
          rc == 2 and "CRITICAL" in out and "storage_errors" in out,
          f"rc={rc}")
finally:
    try:
        proc.stdin.close()
    except OSError:
        pass
    proc.wait(timeout=10)

# --------------------------------------------------------------------------
# 7: two rings, one merged postmortem through the CLI
# --------------------------------------------------------------------------
with tempfile.TemporaryDirectory() as td:
    mk = textwrap.dedent(f"""\
        import sys
        from deeplearning4j_tpu.obs.flight import FlightRecorder
        r = FlightRecorder()
        for k in sys.argv[2:]:
            r.record(k, src=sys.argv[1])
        r.dump(path="{td}/flight_recorder_" + sys.argv[1] + ".json")
    """)
    subprocess.run([sys.executable, "-c", mk, "1111", "step", "fit_end"],
                   env=ENV, cwd=REPO, check=True)
    subprocess.run([sys.executable, "-c", mk, "2222", "publish",
                    "canary_start"], env=ENV, cwd=REPO,
                   check=True)
    rc, out, _ = cli("flight-dump", td)
    check("cli flight-dump merges a directory of rings into one "
          "timeline",
          rc == 0 and "merged timeline" in out and "publish" in out
          and "fit_end" in out, f"rc={rc}")

# --------------------------------------------------------------------------
# 8: chaos drill verifies DETECTION (expected_alerts + scorecard)
# --------------------------------------------------------------------------
with tempfile.TemporaryDirectory() as td:
    out_json = os.path.join(td, "score.json")
    rc, out, err = cli("chaos", "--drill", "checkpoint_fsync_fail",
                       "--out", out_json)
    score = json.load(open(out_json))
    d = score["drills"][0]
    check("chaos drill green with its expected alert fired",
          rc == 0 and d["ok"]
          and "storage_errors" in d["alerts_fired"]
          and d["expected_alerts"] == ["storage_errors"]
          and score["alerts_verified"] == 1,
          f"rc={rc} fired={d.get('alerts_fired')}")

# --------------------------------------------------------------------------
# 9-11: lint — clean tree at ZERO baseline, alert-name schema enforced,
# doc tables byte-identical
# --------------------------------------------------------------------------
rc, out, _ = cli("lint", "--json")
body = json.loads(out)
check("cli lint clean at ZERO baseline entries",
      rc == 0 and body["ok"] and body["counts"]["suppressed"] == 0,
      str(body["counts"]))

with tempfile.TemporaryDirectory() as td:
    seed = os.path.join(td, "pkg", "watch.py")
    os.makedirs(os.path.dirname(seed))
    with open(seed, "w") as f:
        f.write("from deeplearning4j_tpu.obs.alerts import AlertRule\n"
                "R = AlertRule('bogus_alert_name', 'threshold', "
                "metric='g')\n")
    rc, out, _ = cli("lint", "--no-baseline", "--root", td, td)
    check("undeclared AlertRule name fails lint with file:line",
          rc != 0 and "alert-schema" in out and "watch.py:2" in out,
          out.strip().splitlines()[0] if out.strip() else "")

rc, out, _ = cli("lint", "--alerts-table")
arch = open(os.path.join(REPO, "ARCHITECTURE.md")).read()
check("--alerts-table output is byte-identical to the ARCHITECTURE "
      "embed", rc == 0 and out.strip() in arch, f"{len(out)} bytes")

# --------------------------------------------------------------------------
n_bad = sum(1 for _n, ok in checks if not ok)
print(f"\ndrive_alerts: {len(checks) - n_bad}/{len(checks)} checks green")
sys.exit(1 if n_bad else 0)

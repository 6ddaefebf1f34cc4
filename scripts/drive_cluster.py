"""Package-boundary drive for the multi-replica serving tier (ISSUE 17).
User-style: three real `cli serve --cluster` processes share one
registry directory behind a toy session-sticky round-robin front, all
driven over HTTP the way an operator's load balancer would. The
choreography is the tentpole's acceptance story: the canary-controller
lease lands on exactly one replica, that replica is SIGKILLed
mid-canary-window, a survivor steals the lease after the TTL, a peer's
journaled dispatch failures trip the rollback, and the rollback lands
on EVERY surviving replica — then one survivor drains cleanly and the
front reroutes its sessions without dropping a request."""
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import textwrap
import time
import urllib.error
import urllib.request
import zlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

checks = []


def check(name, ok, detail=""):
    checks.append((name, bool(ok)))
    print(f"[{'OK' if ok else 'FAIL'}] {name} {detail}", flush=True)


ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)

# replicas are subprocesses and one chip has one owner: the cluster
# tier is driven on the CPU, pinned in each replica before the CLI
LAUNCH = textwrap.dedent("""\
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deeplearning4j_tpu.cli import main
    sys.exit(main(["serve", *sys.argv[1:]]))
""")


def http(method, url, body=None, tenant=None, timeout=15):
    """One HTTP exchange -> (status, parsed-JSON body). 4xx/5xx are
    returned, not raised; connection-level failures raise OSError."""
    headers = {"Content-Type": "application/json"}
    if tenant is not None:
        headers["X-Tenant"] = tenant
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, headers=headers,
                                 method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def start_replica(rid, regdir, logdir):
    log = open(os.path.join(logdir, f"{rid}.err"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-c", LAUNCH,
         "--registry-dir", regdir, "--cluster", "--replica-id", rid,
         "--heartbeat-s", "0.2", "--lease-ttl-s", "1.0",
         "--global-tenant-quota", "9",
         "--canary-fraction", "0.5", "--canary-window", "120",
         "--port", "0", "--max-wait-ms", "1"],
        stdout=subprocess.PIPE, stderr=log, text=True, env=ENV,
        cwd=REPO)
    banner = None
    port = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"{rid} exited during startup "
                               f"(see {log.name})")
        if line.startswith("cluster: replica "):
            banner = line.strip()
        if line.startswith("listening on http://"):
            port = int(line.split(":")[2].split()[0].rstrip("/").split("(")[0])
            break
    if port is None:
        raise RuntimeError(f"{rid} never printed its listen line")
    return {"id": rid, "proc": proc, "port": port, "banner": banner,
            "base": f"http://127.0.0.1:{port}"}


class Front:
    """Toy session-sticky round-robin front: a session hashes to a home
    replica and stays there; dead (connection refused) and draining
    (503 ServerDrainingError) replicas are skipped, and the session
    re-homes to the next alive one — the reroute the drain contract
    promises."""

    def __init__(self, replicas):
        self.replicas = list(replicas)
        self.down = set()
        self.drained = set()

    def alive(self):
        return [r for r in self.replicas
                if r["id"] not in self.down and r["id"] not in self.drained]

    def home(self, session):
        cand = self.alive()
        if not cand:
            raise RuntimeError("front: no replicas left")
        start = zlib.crc32(session.encode()) % len(self.replicas)
        for i in range(len(self.replicas)):
            r = self.replicas[(start + i) % len(self.replicas)]
            if r in cand:
                return r
        raise RuntimeError("unreachable")

    def predict(self, session, x):
        for _ in range(len(self.replicas) + 1):
            r = self.home(session)
            try:
                st, body, _ = http("POST",
                                   r["base"] + "/models/m/predict",
                                   {"inputs": x}, tenant=session)
            except OSError:
                self.down.add(r["id"])
                continue
            if st == 503 and body.get("error") == "ServerDrainingError":
                self.drained.add(r["id"])
                continue
            return r, st, body
        raise RuntimeError("front: every replica refused")


# --------------------------------------------------------------------------
# registry seed: the trainer's role, in-process (v1 published before the
# tier comes up; v2 published mid-flight)
# --------------------------------------------------------------------------
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from deeplearning4j_tpu.nn.conf import (  # noqa: E402
    InputType,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf.layers import (  # noqa: E402
    DenseLayer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.serving.cluster import ClusterCoordinator  # noqa: E402
from deeplearning4j_tpu.serving.registry import ModelRegistry  # noqa: E402
from deeplearning4j_tpu.train.faults import save_checkpoint  # noqa: E402
from deeplearning4j_tpu.updaters import Adam  # noqa: E402


def net(seed):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
            .list()
            .layer(DenseLayer(n_out=6, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    m = MultiLayerNetwork(conf)
    m.init()
    return m


class PeerStats:
    """The journaled-gate stats shape: what a fourth serving replica
    would fold out after watching its canary slice fail."""
    requests = 9
    errors = 5
    latency_sum = 0.09
    gen_requests = 0
    gen_errors = 0
    gen_latency_sum = 0.0
    score = None
    _n_scores = 0


work = tempfile.mkdtemp(prefix="drive_cluster_")
regdir = os.path.join(work, "registry")
reg = ModelRegistry(regdir)
reg.publish("m", save_checkpoint(net(1), os.path.join(work, "ck1")),
            score=0.5)

replicas = []
observer = None
X = [[0.0, 0.0, 0.0, 0.0]]
SESSIONS = [f"s{i}" for i in range(6)]

try:
    # ----------------------------------------------------------------------
    # 1-3: the tier comes up — 3 replicas, one journal, one membership view
    # ----------------------------------------------------------------------
    for rid in ("r1", "r2", "r3"):
        replicas.append(start_replica(rid, regdir, work))
    check("three --cluster replicas came up with cluster banners",
          all(r["banner"] and f"replica {r['id']}" in r["banner"]
              for r in replicas),
          replicas[0]["banner"] or "")

    alive = []
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        _s, h, _ = http("GET", replicas[0]["base"] + "/healthz")
        alive = h.get("cluster", {}).get("alive", [])
        if {"r1", "r2", "r3"} <= set(alive):
            break
        time.sleep(0.3)
    check("heartbeats converge: every replica sees all three alive",
          {"r1", "r2", "r3"} <= set(alive), str(alive))
    check("cluster-wide tenant quota is journal-visible on /healthz",
          h.get("cluster", {}).get("global_tenant_quota") == 9,
          str(h.get("cluster", {}).get("global_tenant_quota")))

    front = Front(replicas)
    homes = {}
    ok_all = True
    for _ in range(3):
        for s in SESSIONS:
            r, st, body = front.predict(s, X)
            ok_all &= st == 200 and body.get("model_version") == 1
            homes.setdefault(s, set()).add(r["id"])
    check("session-sticky front serves v1 from every home replica",
          ok_all and all(len(v) == 1 for v in homes.values())
          and len(set().union(*homes.values())) == 3,
          str({s: sorted(v) for s, v in homes.items()}) if not ok_all
          else f"{len(set().union(*homes.values()))} distinct homes")

    # ----------------------------------------------------------------------
    # 4-5: publish v2 -> a canary window opens and EXACTLY ONE replica
    # holds the controller lease
    # ----------------------------------------------------------------------
    reg.publish("m", save_checkpoint(net(2), os.path.join(work, "ck2")),
                score=0.45)
    holder = None
    epoch0 = None
    canary_open = False
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        for s in SESSIONS:
            front.predict(s, X)
        _s, h, _ = http("GET", replicas[0]["base"] + "/healthz")
        lease = h.get("cluster", {}).get("leases", {}).get("m")
        _s, mh, _ = http("GET",
                         replicas[0]["base"] + "/models/m/healthz")
        canary_open = mh.get("canary") is not None
        if canary_open and lease and lease.get("replica"):
            holder, epoch0 = lease["replica"], int(lease["epoch"])
            break
        time.sleep(0.2)
    check("publish opened a canary window across the tier",
          canary_open, str(mh.get("canary")))
    check("exactly one replica holds the canary-controller lease",
          holder in {"r1", "r2", "r3"}, f"holder={holder} epoch={epoch0}")

    # ----------------------------------------------------------------------
    # 6-8: SIGKILL the lease holder mid-window -> front fails over, a
    # survivor steals the lease at a higher epoch
    # ----------------------------------------------------------------------
    victim = next(r for r in replicas if r["id"] == holder)
    victim["proc"].send_signal(signal.SIGKILL)
    victim["proc"].wait(timeout=10)
    survivors = [r for r in replicas if r["id"] != holder]

    ok_all = True
    for s in SESSIONS:
        _r, st, body = front.predict(s, X)
        ok_all &= st == 200
    check("front fails over past the SIGKILLed holder (no 5xx)",
          ok_all and victim["id"] in front.down, str(sorted(front.down)))

    new_holder = None
    epoch1 = None
    lost = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        for s in SESSIONS:
            front.predict(s, X)
        _s, h, _ = http("GET", survivors[0]["base"] + "/healthz")
        lease = h.get("cluster", {}).get("leases", {}).get("m") or {}
        lost = h.get("cluster", {}).get("lost", [])
        if (lease.get("replica") in {r["id"] for r in survivors}
                and int(lease.get("epoch", 0)) > epoch0):
            new_holder, epoch1 = lease["replica"], int(lease["epoch"])
            break
        time.sleep(0.2)
    check("a survivor steals the lease at a HIGHER epoch (takeover)",
          new_holder is not None and epoch1 > epoch0,
          f"{holder}@{epoch0} -> {new_holder}@{epoch1}")
    check("the killed replica is judged lost by heartbeat staleness",
          holder in lost, str(lost))

    # ----------------------------------------------------------------------
    # 9-10: a peer's journaled dispatch failures are ground truth — the
    # new controller trips, and the rollback lands on EVERY survivor
    # ----------------------------------------------------------------------
    observer = ClusterCoordinator(regdir, "robs", heartbeat_s=0.2,
                                  lease_ttl_s=1.0)
    observer.heartbeat()
    observer.journal_gate("m", 2, "canary", PeerStats(), urgent=True)
    t0 = time.monotonic()
    rolled = False
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        for s in SESSIONS:
            front.predict(s, X)
        reg.refresh(force=True)
        if (reg.get("m")["versions"].get("2", {}).get("status")
                == "rolled_back"):
            rolled = True
            break
        time.sleep(0.1)
    latency = time.monotonic() - t0
    check("peer-journaled failures trip the cluster rollback",
          rolled, f"{latency:.2f}s after the gate record")

    converged = False
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        views = []
        for r in survivors:
            _s, mh, _ = http("GET", r["base"] + "/models/m/healthz")
            views.append(mh.get("canary") is None
                         and mh.get("active_version") == 1)
        if all(views):
            converged = True
            break
        for s in SESSIONS:
            front.predict(s, X)
        time.sleep(0.1)
    check("rollback converges on every surviving replica (v1 active, "
          "no canary)", converged, f"{len(survivors)} survivors")

    holder_r = next(r for r in survivors if r["id"] == new_holder)
    other_r = next(r for r in survivors if r["id"] != new_holder)
    _s, fl, _ = http("GET", holder_r["base"] + "/debug/flight")
    kinds = [e["kind"] for e in fl.get("events", [])]
    want = ["replica_lost", "lease_steal", "regression_trip", "rollback"]
    it = iter(kinds)
    ordered = all(k in it for k in want)
    check("new holder's flight ring orders replica_lost -> lease_steal "
          "-> regression_trip -> rollback", ordered,
          str([k for k in kinds if k in set(want)]))
    _s, fl2, _ = http("GET", other_r["base"] + "/debug/flight")
    check("the NON-holder survivor applied the rollback from the WAL",
          any(e["kind"] == "cluster_rollback_applied"
              for e in fl2.get("events", [])),
          other_r["id"])

    # ----------------------------------------------------------------------
    # 11-13: clean drain — the drained survivor 503s new work typed, the
    # front re-homes its sessions, service never blips
    # ----------------------------------------------------------------------
    st, body, _ = http("POST", other_r["base"] + "/drain")
    check("POST /drain flips the replica to draining",
          st == 200 and body.get("draining") is True, str(body))
    st, body, hdrs = http("POST", other_r["base"] + "/models/m/predict",
                          {"inputs": X}, tenant="s0")
    check("a drained replica 503s new requests typed with Retry-After",
          st == 503 and body.get("error") == "ServerDrainingError"
          and "Retry-After" in hdrs, f"{st} {body.get('error')}")

    ok_all = True
    served_by = set()
    for s in SESSIONS:
        r, st, body = front.predict(s, X)
        ok_all &= st == 200 and body.get("model_version") == 1
        served_by.add(r["id"])
    check("front re-homes drained sessions; the last replica serves v1 "
          "for everyone",
          ok_all and served_by == {new_holder}
          and other_r["id"] in front.drained,
          f"served_by={sorted(served_by)}")
finally:
    if observer is not None:
        observer.shutdown(release_leases=False)
    for r in replicas:
        if r["proc"].poll() is None:
            r["proc"].terminate()
            try:
                r["proc"].wait(timeout=10)
            except subprocess.TimeoutExpired:
                r["proc"].kill()
    shutil.rmtree(work, ignore_errors=True)

n_bad = sum(1 for _n, ok in checks if not ok)
print(f"\ndrive_cluster: {len(checks) - n_bad}/{len(checks)} checks green")
sys.exit(1 if n_bad else 0)

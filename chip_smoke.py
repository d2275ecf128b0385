"""chip_smoke.py — does the served scheduling path still start, and answer
correctly, on the chip?

Drives the deployment shape, not a solve call.  This process never imports
JAX (a parent that has touched JAX holds the chip, and a child that needs
it then fails or hangs): it starts ONE child, the normal entry point
``python -m kube_batch_tpu.cmd.main`` with the shipped five-action conf and
every default left on (pipelined loop, guard, query plane, replication
publisher), and talks to it through the HTTP API a client would use:

1. learn the device from the serving process (``GET /version``) — anything
   but the expected platform is a failure.  The expected platform is ``tpu``
   unless ``--platform`` states another one (``--platform cpu`` with small
   ``--nodes/--pods`` rehearses the same command in a sandbox and in
   tier-1); a CPU run nobody asked for fails;
2. load the cluster at the real size — BASELINE.json's 50k×5k
   configuration: 3 weighted queues, 5,000 nodes (32 cores, 128 GiB, 110
   pods), 12,500 PodGroups with minMember=4, 50,000 pending pods with the
   request mix of ``testing.synthetic.synthetic_cluster``, from ``--seed`` —
   as batched list-body POSTs, then ``POST /v1/sync``;
3. cold drain: wait for every pod to bind, and check the answer here, in
   numpy, from what was sent and what came back;
4. steady rounds: each deletes 2% of the bound gangs and posts as many new
   ones, waits for the binds and re-checks the invariants — the phase that
   runs what only a chip runs (delta open, donated scatter, top-K bucket,
   warm carry, lease retire/wait) — until the guard's shadow oracle has
   compared the fast path with its oracle at least ``--min-audits`` times;
5. reads: what-if probes and a capacity sweep against the leader's query
   plane, with verdicts this process can check from its own ledger;
6. nothing hidden: the guard reports no trip, no failed-closed solve, no
   audit mismatch, every path healthy; the trace plane shows the full
   program on the cold drain and topk + warm engaged in the steady rounds
   (sharded + shard_map when the server has more than one device); the
   child's log holds no failed cycle, no swallowed failure, no traceback;
   the child is alive at the end, and is then terminated and reaped.

Exit 0 and two lines of stdout only when every phase passed: the run's
summary ``{"smoke": {...}}``, then, as the last line, the verdict — one JSON
object with exactly these keys, the device as the serving process reported
it: ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Both are also left in ``<out>/result.json``.  Any failure exits non-zero and
prints no result (the reason goes to stderr and to ``<out>/failure.txt``).
The timings in ``smoke`` are smoke output — they include compilation and
are not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
# looked at BEFORE the package import below can build it: a library that was
# not here when this run began and that the server then loads was built from
# resource_ops.c on this machine, by this run
NATIVE_LIB_HERE_AT_START = os.path.exists(os.path.join(
    REPO, "kube_batch_tpu", "native", "libresource_ops.so"))

from kube_batch_tpu.api import serialize  # noqa: E402
from kube_batch_tpu.api.pod import (  # noqa: E402
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    Queue,
)
from kube_batch_tpu.api.types import PodPhase  # noqa: E402
from kube_batch_tpu.envutil import compile_cache_dir  # noqa: E402
from kube_batch_tpu.testing.synthetic import (  # noqa: E402
    CPU_CHOICES,
    MEM_CHOICES,
    NODE_CPU,
    NODE_MEM,
    NODE_PODS,
)

NAMESPACE = "smoke"
GANG = 4
QUEUE_WEIGHTS = (1, 2, 3)
CHURN = 0.02  # share of the bound gangs each steady round replaces

#: lines in the child's log that mean a failure was caught and carried on
#: from (scheduler.py, serve/plane.py, actions/allocate.py, guard/plane.py)
LOG_FAILURE_MARKERS = (
    b"scheduling cycle failed",
    b"pre-warm failed",
    b"lease publication failed",
    b"probe dispatch failed",
    b"Traceback (most recent call last)",
)
#: a task counts as bound once the bind RPC is out (api/types.TaskStatus)
BOUND_STATUSES = ("BINDING", "BOUND", "RUNNING")


class SmokeFailure(Exception):
    """One phase did not hold; the message says which and why."""


# --------------------------------------------------------------------------
# the cluster this process loads, and its own ledger of it
# --------------------------------------------------------------------------


class Ledger:
    """What was sent: live pods with their requests and gangs, and the
    nodes.  Everything the checks compare the server's answers against."""

    def __init__(self, n_nodes: int, seed: int):
        self.rng = np.random.default_rng(seed)
        self.node_names = [f"n{i}" for i in range(n_nodes)]
        self.node_index = {n: i for i, n in enumerate(self.node_names)}
        self.pods: dict = {}    # "ns/name" -> (cpu, mem, gang name)
        self.gangs: dict = {}   # gang name -> [pod dict, ...]
        self.pg_dicts: dict = {}  # gang name -> podgroup dict
        self._next_gang = 0
        self._next_pod = 0

    def node_dicts(self) -> list:
        return [
            serialize.node_to_dict(Node(
                name=n,
                allocatable={"cpu": NODE_CPU, "memory": NODE_MEM,
                             "pods": NODE_PODS},
            ))
            for n in self.node_names
        ]

    def new_gangs(self, n_gangs: int):
        """(podgroup dicts, pod dicts) for ``n_gangs`` fresh gangs."""
        cpus = self.rng.choice(CPU_CHOICES, n_gangs * GANG)
        mems = self.rng.choice(MEM_CHOICES, n_gangs * GANG)
        pgs, pods = [], []
        for g in range(n_gangs):
            j = self._next_gang
            self._next_gang += 1
            gang = f"pg{j}"
            pg = serialize.pod_group_to_dict(PodGroup(
                name=gang, namespace=NAMESPACE, min_member=GANG,
                queue=f"q{j % len(QUEUE_WEIGHTS)}", creation_index=j,
            ))
            self.pg_dicts[gang] = pg
            pgs.append(pg)
            members = []
            for m in range(GANG):
                i = self._next_pod
                self._next_pod += 1
                cpu, mem = float(cpus[g * GANG + m]), float(mems[g * GANG + m])
                pod = serialize.pod_to_dict(Pod(
                    name=f"t{i}", namespace=NAMESPACE,
                    requests={"cpu": cpu, "memory": mem},
                    annotations={GROUP_NAME_ANNOTATION: gang},
                    phase=PodPhase.PENDING, creation_index=i,
                ))
                self.pods[f"{NAMESPACE}/t{i}"] = (cpu, mem, gang)
                members.append(pod)
            self.gangs[gang] = members
            pods.extend(members)
        return pgs, pods

    def retire_gangs(self, n_gangs: int):
        """Drop the ``n_gangs`` oldest gangs from the ledger; returns their
        (podgroup dicts, pod dicts) for the DELETE bodies."""
        names = list(self.gangs)[:n_gangs]
        pgs, pods = [], []
        for gang in names:
            pgs.append(self.pg_dicts.pop(gang))
            for pod in self.gangs.pop(gang):
                del self.pods[f"{pod['namespace']}/{pod['name']}"]
                pods.append(pod)
        return pgs, pods

    def node_usage(self, binds: list):
        """[N, 3] (cpu, mem, pods) summed over ``binds`` — raises on a bind
        that names an unknown pod or node, or a pod bound twice."""
        keys = [b["pod"] for b in binds]
        if len(set(keys)) != len(keys):
            raise SmokeFailure("a pod is bound twice")
        unknown = [k for k in keys if k not in self.pods]
        if unknown:
            raise SmokeFailure(
                f"{len(unknown)} binds name pods never sent (or deleted), "
                f"e.g. {unknown[:3]}")
        bad_nodes = [b["node"] for b in binds
                     if b["node"] not in self.node_index]
        if bad_nodes:
            raise SmokeFailure(f"binds name unknown nodes, e.g. {bad_nodes[:3]}")
        idx = np.fromiter((self.node_index[b["node"]] for b in binds),
                          np.int64, len(binds))
        req = np.array([self.pods[k][:2] + (1.0,) for k in keys],
                       np.float64).reshape(len(keys), 3)
        used = np.zeros((len(self.node_names), 3))
        np.add.at(used, idx, req)
        return used

    def check_binds(self, binds: list) -> np.ndarray:
        """The invariants of a correct answer; returns per-node usage."""
        used = self.node_usage(binds)
        cap = np.array([NODE_CPU, NODE_MEM, NODE_PODS])
        over = np.flatnonzero((used > cap + 1e-6).any(axis=1))
        if over.size:
            n = int(over[0])
            raise SmokeFailure(
                f"{over.size} nodes over allocatable, e.g. "
                f"{self.node_names[n]} uses {used[n].tolist()} of "
                f"{cap.tolist()}")
        per_gang: dict = {}
        for b in binds:
            gang = self.pods[b["pod"]][2]
            per_gang[gang] = per_gang.get(gang, 0) + 1
        split = {g: c for g, c in per_gang.items() if 0 < c < GANG}
        if split:
            raise SmokeFailure(
                f"{len(split)} gangs bound below minMember={GANG}, e.g. "
                f"{list(split.items())[:3]}")
        return used


# --------------------------------------------------------------------------
# the child and the HTTP client
# --------------------------------------------------------------------------


class Server:
    """The one child: ``python -m kube_batch_tpu.cmd.main``."""

    def __init__(self, out_dir: str, conf: str, sync_timeout: float):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(out_dir, "server.log")
        self._log_pos = 0
        # the child's environment is this process's, plus only the two
        # directories that bring a trip's bundle back with the run (their
        # defaults are relative to the child's cwd): no JAX_PLATFORMS, no
        # KB_* oracle switch; JAX_COMPILATION_CACHE_DIR passes through
        env = dict(os.environ)
        env["KB_GUARD_DIR"] = os.path.join(out_dir, "guard")
        env["KB_TRACE_DIR"] = os.path.join(out_dir, "flight")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "kube_batch_tpu.cmd.main",
             "--listen-address", f"127.0.0.1:{self.port}",
             "--scheduler-conf", conf,
             "--cache-sync-timeout", str(sync_timeout),
             # the egress throttle is a deployment setting: the reference's
             # 50 QPS default would spend 1,000 s writing 50,000 binds
             "--kube-api-qps", "1000000", "--kube-api-burst", "1000000"],
            cwd=REPO, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def check(self) -> None:
        """Fail fast: the child is alive and its log holds no failure that
        the server caught and carried on from."""
        if self.proc.poll() is not None:
            raise SmokeFailure(
                f"the server exited with code {self.proc.returncode}; "
                f"see {self.log_path}")
        with open(self.log_path, "rb") as f:
            f.seek(self._log_pos)
            new = f.read()
        for marker in LOG_FAILURE_MARKERS:
            if marker in new:
                raise SmokeFailure(
                    f"the server's log holds {marker.decode()!r}; "
                    f"see {self.log_path}")
        # re-read the last few bytes next time: a marker may straddle reads
        self._log_pos += max(0, len(new) - 64)

    def _open(self, method: str, path: str, body=None, timeout=120.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.url + path, data=data, method=method,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw = r.read()
        return json.loads(raw) if raw else None

    def request(self, method: str, path: str, body=None, timeout=120.0):
        try:
            return self._open(method, path, body, timeout)
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{method} {path} answered {e.code}: {e.read()[:500]!r}")
        except OSError as e:  # URLError, timeouts, a connection the child dropped
            self.check()
            raise SmokeFailure(f"{method} {path} failed: {e}")

    def get(self, path: str, timeout=120.0):
        return self.request("GET", path, timeout=timeout)

    def send(self, method: str, kind: str, items: list, batch: int) -> None:
        """Batched list-body ingest; every element must apply."""
        for i in range(0, len(items), batch):
            chunk = items[i:i + batch]
            resp = self.request(method, f"/v1/{kind}", chunk)
            if not resp.get("ok") or resp.get("applied") != len(chunk):
                raise SmokeFailure(
                    f"{method} /v1/{kind} applied {resp} of {len(chunk)}")

    def wait_up(self, deadline: float) -> dict:
        while True:
            self.check()
            try:
                return self._open("GET", "/version", timeout=5.0)
            except OSError:
                pass  # not listening yet
            if time.monotonic() > deadline:
                raise SmokeFailure("the server never answered /version")
            time.sleep(0.5)

    def stop(self) -> None:
        """Terminate and reap the child (SIGKILL if it will not go)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()


def wait_bound(server: Server, want: set, deadline: float, poll: float,
               what: str):
    """Poll /v1/bindings until every pod in ``want`` is bound; returns the
    bound rows of the whole cluster and when the first of ``want`` was seen
    bound."""
    t_first = None
    while True:
        server.check()
        binds = [b for b in server.get("/v1/bindings")
                 if b["status"] in BOUND_STATUSES]
        bound = sum(1 for b in binds if b["pod"] in want)
        if bound and t_first is None:
            t_first = time.monotonic()
        if bound == len(want):
            return binds, t_first
        if time.monotonic() > deadline:
            raise SmokeFailure(
                f"{what}: {bound} of {len(want)} pods bound at the deadline")
        time.sleep(poll)


# --------------------------------------------------------------------------
# the phases
# --------------------------------------------------------------------------


def compile_cache_entries() -> int:
    """How many compiles the child's persistent cache holds now."""
    try:
        return sum(1 for e in os.scandir(compile_cache_dir()) if e.is_file())
    except OSError:
        return 0


def wait_dispatch(server: Server, key: str, phase: str) -> dict:
    """/v1/trace once its ``solve_dispatches`` tally holds ``key``.  Binds
    show in /v1/bindings from the replay on, while a cycle's spans reach the
    trace ring only when the cycle ends — so the cycle that bound the last
    pod may still be running when the binds are all there."""
    until = time.monotonic() + 60.0
    while True:
        server.check()
        trace = server.get("/v1/trace")
        if trace["solve_dispatches"].get(key):
            return trace
        if time.monotonic() > until:
            raise SmokeFailure(
                f"{phase}: no {key!r} dispatch in the trace plane: "
                f"{trace['solve_dispatches']}")
        time.sleep(0.5)


def expected_dispatch_keys(device_count: int):
    """(full-program key, steady-round key) that /v1/trace's
    ``solve_dispatches`` must show: the full [T, N] program on the cold
    drain, the compacted warm-carried one in the steady rounds — over the
    mesh whenever the server has more than one device."""
    prefix = "sharded+shard_map" if device_count > 1 else "single"
    return prefix, f"{prefix}+topk+warm"


def check_reads(server: Server, ledger: Ledger, used: np.ndarray) -> dict:
    """What-if verdicts that this process can check from its own ledger:
    one gang that fits, one that cannot, one capacity sweep."""
    cap = np.array([NODE_CPU, NODE_MEM, NODE_PODS])
    small = {"cpu": float(CPU_CHOICES[0]), "memory": float(MEM_CHOICES[0])}
    small_vec = np.array([small["cpu"], small["memory"], 1.0])
    room = np.floor(((cap - used) / small_vec).min(axis=1)).clip(min=0)
    if room.sum() < 64:
        raise SmokeFailure(
            "the loaded cluster has no room left for the what-if checks; "
            "choose --nodes/--pods with spare capacity")
    queue = f"q{len(QUEUE_WEIGHTS) - 1}"

    fits = server.request("POST", "/v1/whatif", {
        "queue": queue, "count": GANG, "requests": small})
    if not fits.get("feasible") or "snapshot_version" not in fits:
        raise SmokeFailure(f"a {GANG}×{small} gang must fit: {fits}")
    placed = [n for n in fits.get("nodes", []) if n]
    if len(placed) != GANG:
        raise SmokeFailure(f"what-if placed {placed}, not {GANG} members")
    need = {}
    for n in placed:
        if n not in ledger.node_index:
            raise SmokeFailure(f"what-if names unknown node {n!r}")
        need[n] = need.get(n, 0) + 1
    for n, k in need.items():
        if room[ledger.node_index[n]] < k:
            raise SmokeFailure(
                f"what-if places {k} members on {n}, which has room for "
                f"{int(room[ledger.node_index[n]])}")

    # one member larger than any node: no state of this cluster fits it
    huge = {"cpu": 2 * NODE_CPU, "memory": float(MEM_CHOICES[0])}
    cannot = server.request("POST", "/v1/whatif", {
        "queue": queue, "count": GANG, "requests": huge})
    if cannot.get("feasible") or "snapshot_version" not in cannot:
        raise SmokeFailure(f"a {huge} member fits no node: {cannot}")

    sweep = server.request("POST", "/v1/whatif/sweep", {
        "queue": queue, "requests": small, "max_count": 64})
    if sweep.get("max_fit") != 64 or "snapshot_version" not in sweep:
        raise SmokeFailure(
            f"room for {int(room.sum())} small members, sweep says {sweep}")
    return {
        "fits": {k: fits[k] for k in ("feasible", "snapshot_version")},
        "cannot": {k: cannot[k] for k in ("feasible", "snapshot_version")},
        "sweep": {k: sweep[k] for k in ("max_fit", "snapshot_version")},
    }


def check_guard(guard: dict, min_audits: int) -> None:
    bad = {k: guard[k] for k in
           ("trips_total", "failed_closed", "audits_mismatched") if guard[k]}
    unhealthy = {n: p["state"] for n, p in guard["paths"].items()
                 if p["state"] != "healthy"}
    if not guard["enabled"] or bad or unhealthy:
        raise SmokeFailure(f"guard not clean: {bad} {unhealthy} "
                           f"(enabled={guard['enabled']})")
    if guard["audits_run"] < min_audits:
        raise SmokeFailure(
            f"the shadow oracle ran {guard['audits_run']} audits, wanted "
            f"{min_audits}: not enough steady rounds engaged a fast path")


def run(args, out_dir: str) -> dict:
    t_start = time.monotonic()
    deadline = t_start + args.deadline
    cache_entries0 = compile_cache_entries()
    n_gangs = args.pods // GANG
    ledger = Ledger(args.nodes, args.seed)
    server = Server(out_dir, args.scheduler_conf, sync_timeout=args.deadline)
    try:
        # 1. the device, from the serving process
        runtime = server.wait_up(min(deadline, t_start + 300))
        if runtime["platform"] != args.platform:
            raise SmokeFailure(
                f"the server runs on {runtime['platform']!r} "
                f"({runtime['device_kind']} × {runtime['device_count']}); "
                f"expected {args.platform!r}")

        # 2. load at the real size
        t0 = time.monotonic()
        server.send("POST", "queues", [
            serialize.queue_to_dict(Queue(name=f"q{i}", weight=w))
            for i, w in enumerate(QUEUE_WEIGHTS)], batch=16)
        server.send("POST", "nodes", ledger.node_dicts(), batch=1000)
        pgs, pods = ledger.new_gangs(n_gangs)
        server.send("POST", "podgroups", pgs, batch=2500)
        server.send("POST", "pods", pods, batch=5000)
        server.request("POST", "/v1/sync", {})
        t_synced = time.monotonic()

        # 3. cold drain
        binds, t_first = wait_bound(server, set(ledger.pods), deadline,
                                    poll=1.0, what="cold drain")
        t_drained = time.monotonic()
        used = ledger.check_binds(binds)
        full_key, steady_key = expected_dispatch_keys(
            runtime["device_count"])
        trace = wait_dispatch(server, full_key, "cold drain (full program)")
        retraces0 = trace["retraces_attributed"]

        # 4. steady rounds
        churn_gangs = max(1, int(n_gangs * CHURN))
        rounds = 0
        guard = server.get("/v1/guard")
        while rounds < args.rounds or guard["audits_run"] < args.min_audits:
            if rounds >= args.max_rounds:
                break
            old_pgs, old_pods = ledger.retire_gangs(churn_gangs)
            server.send("DELETE", "pods", old_pods, batch=5000)
            server.send("DELETE", "podgroups", old_pgs, batch=2500)
            new_pgs, new_pods = ledger.new_gangs(churn_gangs)
            server.send("POST", "podgroups", new_pgs, batch=2500)
            server.send("POST", "pods", new_pods, batch=5000)
            want = {f"{p['namespace']}/{p['name']}" for p in new_pods}
            binds, _ = wait_bound(server, want, deadline, poll=0.25,
                                  what=f"steady round {rounds}")
            if len(binds) != len(ledger.pods):
                raise SmokeFailure(
                    f"steady round {rounds}: {len(binds)} binds for "
                    f"{len(ledger.pods)} live pods")
            used = ledger.check_binds(binds)
            rounds += 1
            guard = server.get("/v1/guard")
        trace = wait_dispatch(server, steady_key, "steady rounds")

        # 5. reads.  A lease may lag the commit by one cycle (the published
        # snapshot is the one the last solve consumed, and the idle tick
        # that follows re-publishes), so a verdict that disagrees with the
        # ledger is asked again for a few schedule periods before it counts
        reads_until = time.monotonic() + 15.0
        while True:
            try:
                whatif = check_reads(server, ledger, used)
                break
            except SmokeFailure:
                if time.monotonic() > reads_until:
                    raise
                time.sleep(1.0)

        # 6. nothing hidden
        guard = server.get("/v1/guard")
        check_guard(guard, args.min_audits)
        server.check()
        statuses: dict = {}
        for b in binds:
            statuses[b["status"]] = statuses.get(b["status"], 0) + 1
    finally:
        server.stop()
    return {
        "platform": runtime["platform"],
        "device_kind": runtime["device_kind"],
        "device_count": runtime["device_count"],
        "jax": runtime["jax"],
        "native": {"server": runtime["native"],
                   "library_here_before_this_run": NATIVE_LIB_HERE_AT_START},
        "compile_cache": {
            "dir": runtime["compile_cache_dir"],
            "entries_at_start": cache_entries0,
            "entries_at_end": compile_cache_entries(),
        },
        "sizes": {"nodes": args.nodes, "pods": args.pods,
                  "podgroups": n_gangs, "queues": len(QUEUE_WEIGHTS)},
        "seed": args.seed,
        "pods_bound": len(binds),
        "bind_statuses": statuses,
        "load_s": round(t_synced - t0, 1),
        "first_bind_s_including_compile": round(t_first - t_synced, 1),
        "cold_drain_s_including_compile": round(t_drained - t_synced, 1),
        "rounds": rounds,
        "pods_per_round": churn_gangs * GANG,
        "solve_dispatches": trace["solve_dispatches"],
        "retraces_attributed_in_steady_rounds":
            trace["retraces_attributed"] - retraces0,
        "guard": {k: guard[k] for k in (
            "trips_total", "failed_closed", "audits_run",
            "audits_mismatched")} | {
            "paths": {n: p["state"] for n, p in guard["paths"].items()}},
        "whatif": whatif,
        "wall_s": round(time.monotonic() - t_start, 1),
        "timings_are": "smoke output including compilation, not benchmark "
                       "results",
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu",
                    help="the platform the server must report (default tpu; "
                         "a stated expectation, never a fallback)")
    ap.add_argument("--nodes", type=int, default=5000,
                    help="with more than one device, fewer than 129 nodes "
                         "never shard and fail the sharded check")
    ap.add_argument("--pods", type=int, default=50000,
                    help="fewer than 1,024 pods never engage compaction "
                         "and fail the steady-round check")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=8,
                    help="steady rounds to run at least")
    ap.add_argument("--min-audits", type=int, default=1,
                    help="keep running rounds until /v1/guard reports this "
                         "many shadow-oracle audits (one per 64 allocate "
                         "dispatches with a fast path engaged)")
    ap.add_argument("--max-rounds", type=int, default=200)
    ap.add_argument("--deadline", type=float, default=1100.0,
                    help="seconds this run may take before it fails")
    ap.add_argument("--scheduler-conf",
                    default=os.path.join(REPO, "config",
                                         "kube-batch-tpu-conf.yaml"))
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "chip_smoke"))
    args = ap.parse_args(argv)
    if args.pods % GANG:
        ap.error(f"--pods must be a multiple of the gang size {GANG}")
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    failure = None
    try:
        smoke = run(args, out_dir)
    except SmokeFailure as e:
        failure = str(e)
    if "jax" in sys.modules:
        failure = "this process imported jax; the parent must stay off it"
    if failure is not None:
        msg = f"chip_smoke FAILED: {failure}"
        with open(os.path.join(out_dir, "failure.txt"), "w") as f:
            f.write(msg + "\n")
        try:
            with open(os.path.join(out_dir, "server.log"), "rb") as f:
                f.seek(max(0, os.path.getsize(f.name) - 8000))
                tail = f.read().decode(errors="replace")
            print(f"--- end of server.log ---\n{tail}", file=sys.stderr)
        except OSError:
            pass
        print(msg, file=sys.stderr)
        return 1
    # the last line is the verdict and holds nothing else: whoever runs the
    # chip check reads exactly {"ok", "device": {"platform", "kind", "count"}}
    verdict = {
        "ok": True,
        "device": {"platform": str(smoke["platform"]),
                   "kind": str(smoke["device_kind"]),
                   "count": int(smoke["device_count"])},
    }
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        f.write(json.dumps(verdict | {"smoke": smoke}) + "\n")
    print(json.dumps({"smoke": smoke}))
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())

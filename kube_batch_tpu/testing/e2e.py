"""Live-apiserver e2e driver — the rebuild's test/e2e (job.go, queue.go).

One command runs the reference's core behavioral scenarios against a REAL
Kubernetes API server (kind or any URL) with the scheduler in --master
mode, end to end through the chart's CRDs, the list+watch shim, the
binder/evictor, and the status writeback:

    python -m kube_batch_tpu.testing.e2e --master https://127.0.0.1:6443
    python -m kube_batch_tpu.testing.e2e --stub        # CI: no cluster

Scenarios (test/e2e/job.go:82,118,189; queue.go:26; job.go:458;
predicates.go:35,84,161):
  gang              — minMember gang schedules atomically
  gang_full         — a gang that cannot fully fit binds NOTHING
  preemption        — a high-priority job evicts same-queue victims, then
                      places once the kubelet terminates them
  reclaim           — a starved weighted queue reclaims cross-queue
  proportion        — two weighted queues split capacity by weight
  node_selector     — selector pods land only on matching nodes
  taints            — only tolerating pods land on a tainted node
  hostport          — same hostPort forces distinct nodes
  volume            — a local-PV claim pins its pod; the PV pre-binds
  job_priority      — a PriorityClass-backed job wins contended capacity

With --stub, an in-process fake apiserver (real HTTP, real watch streams)
plays the cluster, including the kubelet's part: a Binding POST transitions
the pod to Running on the node, a DELETE terminates it — the state machine
the scenarios need. The same scenario code runs unmodified against a real
cluster; there the kubelet/PV controller do that work.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import queue as _queue
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger("kube_batch_tpu")

SCHED = "volcano"  # default scheduler-name the shim filters on

# collection resource segment → canonical list path (mirrors k8s/watch.py)
_COLLECTIONS = {
    "namespaces": "/api/v1/namespaces",
    "pods": "/api/v1/pods",
    "nodes": "/api/v1/nodes",
    "persistentvolumes": "/api/v1/persistentvolumes",
    "persistentvolumeclaims": "/api/v1/persistentvolumeclaims",
    "podgroups": "/apis/scheduling.incubator.k8s.io/v1alpha1/podgroups",
    "queues": "/apis/scheduling.incubator.k8s.io/v1alpha1/queues",
    "poddisruptionbudgets": "/apis/policy/v1/poddisruptionbudgets",
    "priorityclasses": "/apis/scheduling.k8s.io/v1/priorityclasses",
    "storageclasses": "/apis/storage.k8s.io/v1/storageclasses",
    "customresourcedefinitions":
        "/apis/apiextensions.k8s.io/v1/customresourcedefinitions",
    "leases": "/apis/coordination.k8s.io/v1/leases",
}


def _merge(dst: dict, patch: dict) -> dict:
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        elif v is None:
            dst.pop(k, None)
        else:
            dst[k] = v
    return dst


class StubApiServer:
    """A watchable fake apiserver with a built-in kubelet simulation."""

    def __init__(self):
        self._store: Dict[str, Dict[str, dict]] = {k: {} for k in _COLLECTIONS}
        self._watchers: Dict[str, List[_queue.Queue]] = {k: [] for k in _COLLECTIONS}
        self._rv = 0
        self._lock = threading.RLock()
        self.httpd: Optional[ThreadingHTTPServer] = None

    # ---- store ---------------------------------------------------------
    @staticmethod
    def _key(obj: dict) -> str:
        meta = obj.get("metadata") or {}
        ns = meta.get("namespace")
        return f"{ns}/{meta['name']}" if ns else meta["name"]

    def _emit(self, kind: str, etype: str, obj: dict) -> None:
        self._rv += 1
        obj.setdefault("metadata", {})["resourceVersion"] = str(self._rv)
        event = {"type": etype, "object": json.loads(json.dumps(obj))}
        for q in list(self._watchers[kind]):
            q.put(event)

    def upsert(self, kind: str, obj: dict) -> None:
        with self._lock:
            key = self._key(obj)
            etype = "MODIFIED" if key in self._store[kind] else "ADDED"
            self._store[kind][key] = obj
            self._emit(kind, etype, obj)

    def delete(self, kind: str, key: str) -> bool:
        with self._lock:
            obj = self._store[kind].pop(key, None)
            if obj is None:
                return False
            self._emit(kind, "DELETED", obj)
            return True

    def patch(self, kind: str, key: str, patch: dict) -> bool:
        with self._lock:
            obj = self._store[kind].get(key)
            if obj is None:
                return False
            _merge(obj, patch)
            self._emit(kind, "MODIFIED", obj)
            return True

    # ---- kubelet simulation -------------------------------------------
    def bind_pod(self, ns: str, name: str, node: str) -> bool:
        """Binding subresource → the kubelet runs the pod."""
        with self._lock:
            pod = self._store["pods"].get(f"{ns}/{name}")
            if pod is None:
                return False
            pod.setdefault("spec", {})["nodeName"] = node
            pod.setdefault("status", {})["phase"] = "Running"
            self._emit("pods", "MODIFIED", pod)
            return True

    # ---- HTTP ----------------------------------------------------------
    def start(self, host: str = "127.0.0.1", port: int = 0) -> str:
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.0"  # close-delimited watch streams

            def log_message(self, *a):
                pass

            def _send(self, code: int, obj) -> None:
                data = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _route(self) -> Tuple[Optional[str], List[str], str]:
                """path → (collection kind, trailing segments, query). The
                LAST matching segment is the resource — namespaced paths
                (/api/v1/namespaces/<ns>/pods/...) contain 'namespaces'
                first but address the inner collection."""
                path, _, query = self.path.partition("?")
                parts = [p for p in path.split("/") if p]
                for i in range(len(parts) - 1, -1, -1):
                    if parts[i] in _COLLECTIONS:
                        return parts[i], parts[i + 1:], query
                return None, [], query

            def _obj_key(self, kind: str, rest: List[str]) -> str:
                # .../namespaces/<ns>/<kind>/<name> carries the namespace
                # two segments before the kind; cluster-scoped is just name
                path = self.path.split("?")[0]
                if "/namespaces/" in path:
                    ns = path.split("/namespaces/")[1].split("/")[0]
                    return f"{ns}/{rest[0]}"
                if kind == "pods" and rest:
                    return rest[0] if "/" in rest[0] else f"default/{rest[0]}"
                return rest[0]

            def do_GET(self):
                kind, rest, query = self._route()
                if kind is None:
                    self._send(404, {"error": "not found"})
                    return
                if "watch=true" in query:
                    q: _queue.Queue = _queue.Queue()
                    with stub._lock:
                        # close the LIST→watch gap: whatever the store holds
                        # NOW replays as MODIFIED (the shim's handlers are
                        # upserts, so re-delivery is harmless) — an event
                        # emitted between the client's list and this
                        # registration cannot be lost
                        for obj in stub._store[kind].values():
                            q.put({"type": "MODIFIED",
                                   "object": json.loads(json.dumps(obj))})
                        stub._watchers[kind].append(q)
                    try:
                        self.send_response(200)
                        self.send_header("Content-Type", "application/json")
                        self.end_headers()
                        while True:
                            try:
                                event = q.get(timeout=1.0)
                            except _queue.Empty:
                                continue
                            self.wfile.write(
                                (json.dumps(event) + "\n").encode()
                            )
                            self.wfile.flush()
                    except (BrokenPipeError, ConnectionResetError, OSError):
                        return
                    finally:
                        try:
                            stub._watchers[kind].remove(q)
                        except ValueError:
                            pass
                    return
                with stub._lock:
                    if rest:  # single object GET (lease elector)
                        obj = stub._store[kind].get(self._obj_key(kind, rest))
                        if obj is None:
                            self._send(404, {"error": "not found"})
                        else:
                            self._send(200, obj)
                        return
                    items = [json.loads(json.dumps(o))
                             for o in stub._store[kind].values()]
                self._send(200, {
                    "items": items,
                    "metadata": {"resourceVersion": str(stub._rv)},
                })

            def _body(self) -> dict:
                n = int(self.headers.get("Content-Length", 0))
                return json.loads(self.rfile.read(n) or b"{}")

            def do_POST(self):
                kind, rest, _ = self._route()
                if kind is None:
                    self._send(404, {"error": "not found"})
                    return
                body = self._body()
                if kind == "pods" and rest and rest[-1] == "binding":
                    path = self.path.split("?")[0]
                    ns = (path.split("/namespaces/")[1].split("/")[0]
                          if "/namespaces/" in path else "default")
                    ok = stub.bind_pod(ns, rest[-2], (body.get("target") or {}).get("name", ""))
                    self._send(201 if ok else 404, {})
                    return
                # creation: stamp the namespace from the URL when present
                path = self.path.split("?")[0]
                if "/namespaces/" in path:
                    ns = path.split("/namespaces/")[1].split("/")[0]
                    body.setdefault("metadata", {}).setdefault("namespace", ns)
                stub.upsert(kind, body)
                self._send(201, body)

            def do_PUT(self):
                kind, rest, _ = self._route()
                if kind is None or not rest:
                    self._send(404, {"error": "not found"})
                    return
                body = self._body()
                stub.upsert(kind, body)
                self._send(200, body)

            def do_PATCH(self):
                kind, rest, _ = self._route()
                if kind is None or not rest:
                    self._send(404, {"error": "not found"})
                    return
                key = self._obj_key(kind, rest)
                ok = stub.patch(kind, key, self._body())
                self._send(200 if ok else 404, {})

            def do_DELETE(self):
                kind, rest, _ = self._route()
                if kind is None or not rest:
                    self._send(404, {"error": "not found"})
                    return
                ok = stub.delete(kind, self._obj_key(kind, rest))
                self._send(200 if ok else 404, {})

        self.httpd = ThreadingHTTPServer((host, port), Handler)
        threading.Thread(target=self.httpd.serve_forever, daemon=True,
                         name="stub-apiserver").start()
        return f"http://{host}:{self.httpd.server_address[1]}"

    def stop(self) -> None:
        if self.httpd is not None:
            self.httpd.shutdown()
            self.httpd.server_close()


# ---------------------------------------------------------------------------
# client helpers (work against the stub AND a real apiserver)
# ---------------------------------------------------------------------------


class Cluster:
    """Minimal apiserver client for the scenarios. Creates are tracked so
    teardown() can delete them in reverse order — scenario isolation on a
    real cluster, where objects would otherwise leak across runs."""

    def __init__(self, master: str, **auth):
        from kube_batch_tpu.k8s.transport import ApiTransport

        self.t = ApiTransport(master, **auth)
        self._created: List[str] = []  # object paths, creation order

    def _obj_path(self, collection_path: str, obj: dict) -> str:
        meta = obj.get("metadata") or {}
        ns, name = meta.get("namespace"), meta.get("name", "")
        if ns and not collection_path.rstrip("/").endswith(f"namespaces/{ns}"):
            prefix, _, resource = collection_path.rpartition("/")
            return f"{prefix}/namespaces/{ns}/{resource}/{name}"
        return f"{collection_path}/{name}"

    def create(self, collection_path: str, obj: dict, tolerate_conflict=False) -> None:
        import urllib.error

        try:
            self.t.request("POST", collection_path, obj)
        except urllib.error.HTTPError as e:
            if not (tolerate_conflict and e.code == 409):
                raise
            return
        self._created.append(self._obj_path(collection_path, obj))

    def ensure_namespace(self, ns: str) -> None:
        self.create("/api/v1/namespaces",
                    {"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": ns}},
                    tolerate_conflict=True)

    def teardown(self) -> None:
        """Best-effort reverse-order cleanup of everything this client made."""
        import urllib.error

        for path in reversed(self._created):
            try:
                self.t.request("DELETE", path)
            except (urllib.error.HTTPError, OSError):
                pass
        self._created.clear()

    def pods(self, ns: str) -> Dict[str, dict]:
        # namespaced list (the stub lists everything regardless; a real
        # cluster must not pay a cluster-wide pod list per wait poll)
        listing = self.t.get_json(f"/api/v1/namespaces/{ns}/pods")
        return {
            StubApiServer._key(p): p for p in listing.get("items", [])
            if (p.get("metadata") or {}).get("namespace") == ns
        }

    def apply_crds(self) -> None:
        """Apply deployment/crds/*.yaml — the chart's CRD registration."""
        import glob
        import os
        import urllib.error

        import yaml

        crd_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "deployment", "crds")
        for path in sorted(glob.glob(os.path.join(crd_dir, "*.yaml"))):
            with open(path) as f:
                crd = yaml.safe_load(f)
            try:
                self.create(_COLLECTIONS["customresourcedefinitions"], crd)
            except urllib.error.HTTPError as e:
                if e.code != 409:  # already exists
                    raise

    # -- object builders (test/e2e/util.go analogs) ----------------------
    def queue(self, name: str, weight: int) -> None:
        self.create(_COLLECTIONS["queues"], {
            "apiVersion": "scheduling.incubator.k8s.io/v1alpha1",
            "kind": "Queue", "metadata": {"name": name},
            "spec": {"weight": weight},
        })

    def node_obj(self, name: str, cpu_m: int = 4000, mem_gi: int = 16) -> dict:
        return {
            "apiVersion": "v1", "kind": "Node",
            "metadata": {"name": name,
                         "labels": {"kubernetes.io/hostname": name}},
            "spec": {},
            "status": {
                "allocatable": {"cpu": f"{cpu_m}m", "memory": f"{mem_gi}Gi",
                                "pods": "110"},
                "capacity": {"cpu": f"{cpu_m}m", "memory": f"{mem_gi}Gi",
                             "pods": "110"},
                "conditions": [{"type": "Ready", "status": "True"}],
            },
        }

    def podgroup(self, ns: str, name: str, min_member: int, queue: str) -> None:
        self.create(_COLLECTIONS["podgroups"], {
            "apiVersion": "scheduling.incubator.k8s.io/v1alpha1",
            "kind": "PodGroup",
            "metadata": {"name": name, "namespace": ns},
            "spec": {"minMember": min_member, "queue": queue},
        })

    def pod(self, ns: str, name: str, group: str, cpu_m: int = 1000,
            priority: int = 0, node: Optional[str] = None,
            node_selector: Optional[dict] = None,
            tolerations: Optional[list] = None,
            host_port: Optional[int] = None) -> None:
        container = {
            "name": "c", "image": "busybox",
            "resources": {"requests": {"cpu": f"{cpu_m}m", "memory": "1Gi"}},
        }
        if host_port is not None:
            container["ports"] = [{"containerPort": host_port,
                                   "hostPort": host_port}]
        obj = {
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {
                "name": name, "namespace": ns,
                "uid": f"{ns}-{name}-uid",
                "annotations": {"scheduling.k8s.io/group-name": group},
            },
            "spec": {
                "schedulerName": SCHED,
                "priority": priority,
                "containers": [container],
            },
            "status": {"phase": "Pending"},
        }
        if node_selector:
            obj["spec"]["nodeSelector"] = node_selector
        if tolerations:
            obj["spec"]["tolerations"] = tolerations
        if node is not None:
            obj["spec"]["nodeName"] = node
            obj["status"]["phase"] = "Running"
        self.create(f"/api/v1/namespaces/{ns}/pods", obj)

    def wait(self, predicate, timeout: float = 60.0, what: str = "",
             interval: float = 0.25) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if predicate():
                return
            time.sleep(interval)
        raise TimeoutError(f"e2e wait timed out: {what}")

    def n_on_nodes(self, ns: str, prefix: str = "") -> int:
        return sum(
            1 for k, p in self.pods(ns).items()
            if k.split("/", 1)[1].startswith(prefix)
            and (p.get("spec") or {}).get("nodeName")
        )


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------


def scenario_gang(c: Cluster, ns: str) -> None:
    """Gang scheduling (job.go:82): all minMember tasks bind together."""
    c.queue(f"{ns}-q", 1)
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n1"))
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n2"))
    c.podgroup(ns, "gang", 6, f"{ns}-q")
    for i in range(6):
        c.pod(ns, f"g{i}", "gang")
    c.wait(lambda: c.n_on_nodes(ns, "g") == 6, what="gang fully scheduled")


def scenario_gang_full(c: Cluster, ns: str) -> None:
    """Gang: Full Occupied (job.go:118): an unsatisfiable gang binds NOTHING
    (no partial placement) while a fitting gang proceeds."""
    c.queue(f"{ns}-q", 1)
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n1", cpu_m=4000))
    c.podgroup(ns, "big", 8, f"{ns}-q")   # 8 x 1000m > 4000m — can't fit
    for i in range(8):
        c.pod(ns, f"big{i}", "big")
    c.podgroup(ns, "ok", 3, f"{ns}-q")
    for i in range(3):
        c.pod(ns, f"ok{i}", "ok")
    c.wait(lambda: c.n_on_nodes(ns, "ok") == 3, what="fitting gang scheduled")
    time.sleep(2.0)  # give the scheduler cycles to (wrongly) place the big gang
    assert c.n_on_nodes(ns, "big") == 0, "partial gang placement happened"


def scenario_preemption(c: Cluster, ns: str) -> None:
    """Preemption (job.go:189): a high-priority same-queue job evicts
    running victims and places once they terminate."""
    c.queue(f"{ns}-q", 1)
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n1", cpu_m=4000))
    # minMember 2 with 4 running replicas: gang slack 2 — the victims the
    # gang plugin permits (evicting from a min==replicas gang would break
    # it, and the reference's Evictable refuses that too, gang.go:71-94)
    c.podgroup(ns, "low", 2, f"{ns}-q")
    for i in range(4):  # fills the node
        c.pod(ns, f"low{i}", "low", node=f"{ns}-n1")
    c.podgroup(ns, "high", 2, f"{ns}-q")
    for i in range(2):
        c.pod(ns, f"high{i}", "high", priority=1000)
    c.wait(lambda: c.n_on_nodes(ns, "high") == 2, timeout=90,
           what="high-priority job placed after preemption")


def scenario_reclaim(c: Cluster, ns: str) -> None:
    """Reclaim across queues (queue.go:26): a starved weighted queue evicts
    another queue's overuse."""
    c.queue(f"{ns}-q1", 1)
    c.queue(f"{ns}-q2", 1)
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n1", cpu_m=4000))
    # gang slack 2 (see scenario_preemption): reclaimable without breaking
    # the hog's own gang
    c.podgroup(ns, "hog", 2, f"{ns}-q1")
    for i in range(4):
        c.pod(ns, f"hog{i}", "hog", node=f"{ns}-n1")
    c.podgroup(ns, "starved", 2, f"{ns}-q2")
    for i in range(2):
        c.pod(ns, f"starved{i}", "starved")
    c.wait(lambda: c.n_on_nodes(ns, "starved") == 2, timeout=90,
           what="starved queue reclaimed")


def scenario_proportion(c: Cluster, ns: str) -> None:
    """Proportion (job.go:458): weighted queues split contended capacity
    ~by weight; nothing is overcommitted."""
    c.queue(f"{ns}-gold", 2)
    c.queue(f"{ns}-bronze", 1)
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n1", cpu_m=6000))
    c.podgroup(ns, "gj", 1, f"{ns}-gold")
    c.podgroup(ns, "bj", 1, f"{ns}-bronze")
    for i in range(6):
        c.pod(ns, f"gp{i}", "gj")
        c.pod(ns, f"bp{i}", "bj")
    c.wait(lambda: c.n_on_nodes(ns) >= 6, what="capacity filled")
    time.sleep(2.0)
    gold, bronze = c.n_on_nodes(ns, "gp"), c.n_on_nodes(ns, "bp")
    assert gold + bronze <= 6, f"overcommit: {gold}+{bronze}"
    assert gold >= bronze, f"weights inverted: gold={gold} bronze={bronze}"
    assert gold >= 3, f"gold under-served: {gold}"


def scenario_node_selector(c: Cluster, ns: str) -> None:
    """NodeAffinity/selector (predicates.go:35): a selector pod lands only
    on the matching node."""
    c.queue(f"{ns}-q", 1)
    red, blue = c.node_obj(f"{ns}-red"), c.node_obj(f"{ns}-blue")
    red["metadata"]["labels"]["color"] = "red"
    blue["metadata"]["labels"]["color"] = "blue"
    c.create(_COLLECTIONS["nodes"], red)
    c.create(_COLLECTIONS["nodes"], blue)
    c.podgroup(ns, "sel", 2, f"{ns}-q")
    for i in range(2):
        c.pod(ns, f"sel{i}", "sel", node_selector={"color": "blue"})
    c.wait(lambda: c.n_on_nodes(ns, "sel") == 2, what="selector pods placed")
    for k, p in c.pods(ns).items():
        assert p["spec"].get("nodeName") in (None, f"{ns}-blue"), (k, p["spec"])


def scenario_taints(c: Cluster, ns: str) -> None:
    """Taints/Tolerations (predicates.go:161): only tolerating pods land on
    the tainted node; the others go to the clean node."""
    c.queue(f"{ns}-q", 1)
    tainted = c.node_obj(f"{ns}-tainted", cpu_m=4000)
    tainted["spec"]["taints"] = [
        {"key": "dedicated", "value": "ml", "effect": "NoSchedule"}]
    c.create(_COLLECTIONS["nodes"], tainted)
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-clean", cpu_m=2000))
    c.podgroup(ns, "tol", 3, f"{ns}-q")
    tol = [{"key": "dedicated", "operator": "Equal", "value": "ml",
            "effect": "NoSchedule"}]
    for i in range(3):
        # selector pins tol pods to the tainted node: they can land there
        # ONLY via the toleration (the predicate under test), and the clean
        # node's exact capacity stays reserved for the plain gang
        c.pod(ns, f"tol{i}", "tol", tolerations=tol,
              node_selector={"kubernetes.io/hostname": f"{ns}-tainted"})
    c.podgroup(ns, "plain", 2, f"{ns}-q")
    for i in range(2):
        c.pod(ns, f"plain{i}", "plain")
    c.wait(lambda: c.n_on_nodes(ns) == 5, what="all pods placed")
    pods = c.pods(ns)
    for k, p in pods.items():
        name = k.split("/", 1)[1]
        on = p["spec"].get("nodeName")
        if name.startswith("plain"):
            assert on == f"{ns}-clean", (k, on)


def scenario_hostport(c: Cluster, ns: str) -> None:
    """Hostport (predicates.go:84): two pods claiming the same hostPort
    land on different nodes."""
    c.queue(f"{ns}-q", 1)
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n1"))
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n2"))
    c.podgroup(ns, "hp", 2, f"{ns}-q")
    for i in range(2):
        c.pod(ns, f"hp{i}", "hp", host_port=8080)
    c.wait(lambda: c.n_on_nodes(ns, "hp") == 2, what="hostport pods placed")
    nodes = {p["spec"]["nodeName"] for p in c.pods(ns).values()}
    assert len(nodes) == 2, f"hostPort conflict ignored: {nodes}"


def scenario_volume(c: Cluster, ns: str) -> None:
    """Local-PV reachability (the volumebinder feed, cache.go:189-209): a
    pod claiming an unbound no-provisioner PVC lands ONLY on the node its
    static PV is reachable from, and the scheduler pre-binds the PV
    (claimRef) cluster-side."""
    c.queue(f"{ns}-q", 1)
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-a"))
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-b"))
    c.create(_COLLECTIONS["storageclasses"], {
        "apiVersion": "storage.k8s.io/v1", "kind": "StorageClass",
        "metadata": {"name": f"{ns}-local"},
        "provisioner": "kubernetes.io/no-provisioner",
        "volumeBindingMode": "WaitForFirstConsumer",
    })
    c.create(_COLLECTIONS["persistentvolumes"], {
        "apiVersion": "v1", "kind": "PersistentVolume",
        "metadata": {"name": f"{ns}-pv"},
        "spec": {
            "capacity": {"storage": "10Gi"},
            "accessModes": ["ReadWriteOnce"],
            "storageClassName": f"{ns}-local",
            "local": {"path": "/mnt/ssd0"},
            "nodeAffinity": {"required": {"nodeSelectorTerms": [
                {"matchExpressions": [{"key": "kubernetes.io/hostname",
                                       "operator": "In",
                                       "values": [f"{ns}-b"]}]}
            ]}},
        },
        "status": {"phase": "Available"},
    })
    c.create(f"/api/v1/namespaces/{ns}/persistentvolumeclaims", {
        "apiVersion": "v1", "kind": "PersistentVolumeClaim",
        "metadata": {"name": "data", "namespace": ns},
        "spec": {"accessModes": ["ReadWriteOnce"],
                 "resources": {"requests": {"storage": "5Gi"}},
                 "storageClassName": f"{ns}-local"},
        "status": {"phase": "Pending"},
    })
    c.podgroup(ns, "stateful", 1, f"{ns}-q")
    pod = {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": "stateful-0", "namespace": ns,
                     "uid": f"{ns}-stateful-0-uid",
                     "annotations": {"scheduling.k8s.io/group-name": "stateful"}},
        "spec": {
            "schedulerName": SCHED,
            "containers": [{"name": "c", "image": "busybox",
                            "resources": {"requests": {"cpu": "500m",
                                                       "memory": "1Gi"}}}],
            "volumes": [{"name": "v",
                         "persistentVolumeClaim": {"claimName": "data"}}],
        },
        "status": {"phase": "Pending"},
    }
    c.create(f"/api/v1/namespaces/{ns}/pods", pod)
    c.wait(lambda: (c.pods(ns).get(f"{ns}/stateful-0") or {}).get(
        "spec", {}).get("nodeName") == f"{ns}-b",
        what="stateful pod on the PV's node")

    def claim_ref_landed():
        pv = c.t.get_json(f"/api/v1/persistentvolumes/{ns}-pv")
        ref = (pv.get("spec") or {}).get("claimRef") or {}
        return ref.get("name") == "data"
    c.wait(claim_ref_landed, timeout=30, what="PV claimRef pre-bound")


def scenario_job_priority(c: Cluster, ns: str) -> None:
    """Job priority (job.go:410): when both jobs are pending and capacity
    fits only one, the PriorityClass-backed job wins it atomically."""
    c.queue(f"{ns}-q", 1)
    c.create(_COLLECTIONS["priorityclasses"], {
        "apiVersion": "scheduling.k8s.io/v1", "kind": "PriorityClass",
        "metadata": {"name": f"{ns}-high"}, "value": 1000,
    })
    c.create(_COLLECTIONS["nodes"], c.node_obj(f"{ns}-n1", cpu_m=4000))
    # low submitted FIRST (earlier creation would win a priority tie)
    c.podgroup(ns, "low", 4, f"{ns}-q")
    for i in range(4):
        c.pod(ns, f"low{i}", "low")
    c.podgroup(ns, "high", 4, f"{ns}-q")
    for i in range(4):
        c.pod(ns, f"high{i}", "high", priority=1000)
    c.wait(lambda: c.n_on_nodes(ns, "high") == 4, timeout=60,
           what="high-priority job placed first")
    assert c.n_on_nodes(ns, "low") == 0, "low job took the contended capacity"


SCENARIOS = {
    "gang": scenario_gang,
    "gang_full": scenario_gang_full,
    "preemption": scenario_preemption,
    "reclaim": scenario_reclaim,
    "proportion": scenario_proportion,
    "node_selector": scenario_node_selector,
    "taints": scenario_taints,
    "hostport": scenario_hostport,
    "volume": scenario_volume,
    "job_priority": scenario_job_priority,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def scheduler_process(master: str, extra_args=(), **auth):
    """The REAL CLI scheduler (`python -m kube_batch_tpu.cmd.main --master
    ...`, shipped 5-action conf) as a subprocess — exactly the deployment
    shape. Yields the Popen; logs drain to a temp file (an undrained PIPE
    would block the scheduler mid-run), surfaced on error."""
    import os
    import subprocess
    import tempfile

    from kube_batch_tpu.envutil import cpu_env

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from kube_batch_tpu.framework.conf import shipped_conf_path

    conf = shipped_conf_path()
    env = cpu_env()  # a test harness: its scheduler child never needs a chip
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    # hand the scheduler subprocess the same credentials the scenario
    # client carries (in_cluster_auth reads these overrides)
    token_tmp = None
    if auth.get("token"):
        token_tmp = tempfile.NamedTemporaryFile("w", delete=False, suffix=".token")
        token_tmp.write(auth["token"])
        token_tmp.close()
        env["KB_KUBE_TOKEN_FILE"] = token_tmp.name
    if auth.get("insecure"):
        env["KB_KUBE_INSECURE"] = "1"
    cmd = [
        sys.executable, "-m", "kube_batch_tpu.cmd.main",
        "--master", master,
        "--listen-address", "127.0.0.1:0",
        "--schedule-period", "0.25",
        "--scheduler-conf", conf,
        *extra_args,
    ]
    logf = tempfile.NamedTemporaryFile("w+", delete=False, suffix=".sched.log")
    proc = subprocess.Popen(cmd, env=env, stdout=logf, stderr=subprocess.STDOUT,
                            text=True)
    try:
        yield proc
    except Exception:
        logf.flush()
        try:
            with open(logf.name) as f:
                logger.error("scheduler process output:\n%s", f.read()[-4000:])
        except OSError:
            pass
        raise
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
        logf.close()
        os.unlink(logf.name)
        if token_tmp is not None:
            os.unlink(token_tmp.name)


def run_scenario(name: str, master: str, **auth) -> None:
    """One scenario: scheduler up, scenario body, scheduler DOWN, then
    teardown — deleting the scenario's objects under a live scheduler would
    bury failure-time log diagnostics in teardown-reaction noise."""
    c = Cluster(master, **auth)
    try:
        with scheduler_process(master, **auth) as proc:
            c.ensure_namespace(f"e2e-{name.replace('_', '-')}")
            SCENARIOS[name](c, ns=f"e2e-{name.replace('_', '-')}")
            if proc.poll() is not None:
                raise RuntimeError(
                    f"scheduler exited early rc={proc.returncode}")
    finally:
        c.teardown()


def run_density(master: str, n_pods: int = 3000, n_nodes: int = 100,
                gang: int = 100, **auth) -> dict:
    """The kubemark density benchmark at the LIVE protocol level
    (test/kubemark + test/e2e/benchmark.go:53-285): N hollow nodes, a
    minMember=`gang` gang, then `n_pods` 1m-cpu latency pods — all through
    the real apiserver protocol (watch in, Binding POSTs out), measuring
    per-pod create→bind PodStartupLatency percentiles.  benchmark/run.py
    covers the served path at scale; this covers the wire."""
    ns = "e2e-density"
    c = Cluster(master, **auth)
    c.apply_crds()
    c.ensure_namespace(ns)
    # density is a THROUGHPUT measurement: lift the client egress throttle
    # (kube-api-qps 50 would serialize the per-cycle status writeback into
    # the latency signal; the reference's kubemark rig tunes QPS up too)
    # teardown runs AFTER the scheduler process exits (see run_scenario)
    with contextlib.ExitStack() as stack:
        stack.callback(c.teardown)
        stack.enter_context(scheduler_process(master, extra_args=(
            "--kube-api-qps", "5000", "--kube-api-burst", "10000"), **auth))
        c.queue(f"{ns}-q", 1)
        for i in range(n_nodes):
            c.create(_COLLECTIONS["nodes"],
                     c.node_obj(f"{ns}-n{i}", cpu_m=32000, mem_gi=64))
        # phase 1: the density gang (benchmark.go:50,61-71)
        c.podgroup(ns, "gang", gang, f"{ns}-q")
        for i in range(gang):
            c.pod(ns, f"gang-{i}", "gang", cpu_m=10)
        c.wait(lambda: c.n_on_nodes(ns, "gang-") == gang, timeout=120,
               what="density gang scheduled")
        # phase 2: latency pods in node-count batches (benchmark.go:74-110)
        created_at: Dict[str, float] = {}
        for i in range(n_pods):
            name = f"lat-{i}"
            c.podgroup(ns, name, 1, f"{ns}-q")
            created_at[name] = time.perf_counter()
            c.pod(ns, name, name, cpu_m=1)
        bound_at: Dict[str, float] = {}

        def all_bound():
            now = time.perf_counter()
            for key, p in c.pods(ns).items():
                name = key.split("/", 1)[1]
                if (name.startswith("lat-") and name not in bound_at
                        and (p.get("spec") or {}).get("nodeName")):
                    bound_at[name] = now
            return len(bound_at) >= n_pods
        # 1s poll: each poll LISTs every pod; tighter polling would load
        # the single-core stub more than it refines the percentiles
        c.wait(all_bound, timeout=600, what="latency pods scheduled",
               interval=1.0)
        lat = sorted(
            (bound_at[k] - created_at[k]) * 1e3 for k in bound_at
        )
        if not lat:
            return {"pods": 0, "nodes": n_nodes, "gang": gang}

        def pct(p):
            from kube_batch_tpu.sim.metrics import nearest_rank

            return round(nearest_rank(lat, p), 1)
        return {
            "pods": n_pods, "nodes": n_nodes, "gang": gang,
            "startup_p50_ms": pct(0.50), "startup_p90_ms": pct(0.90),
            "startup_p99_ms": pct(0.99),
            "note": "create->bind wall clock through the live watch/bind "
                    "protocol; resolution = the poll interval. Against the "
                    "--stub apiserver the protocol endpoint (pure-Python "
                    "HTTP on this host) bounds throughput, not the "
                    "scheduler — use a real/kind cluster for absolute "
                    "numbers; benchmark/run.py measures the served "
                    "path at scale.",
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--master", help="apiserver URL (kind / real cluster)")
    ap.add_argument("--stub", action="store_true",
                    help="run against the in-process stub apiserver")
    ap.add_argument("--token", default=None)
    ap.add_argument("--insecure", action="store_true")
    ap.add_argument("--scenarios", default=",".join(SCENARIOS),
                    help="comma-separated subset")
    ap.add_argument("--density", action="store_true",
                    help="run the kubemark density benchmark instead of the "
                         "behavioral scenarios")
    ap.add_argument("--density-pods", type=int, default=3000)
    ap.add_argument("--density-nodes", type=int, default=100)
    args = ap.parse_args(argv)
    if not args.stub and not args.master:
        ap.error("need --master URL or --stub")
    auth = {"token": args.token, "insecure": args.insecure}

    if args.density:
        stub = None
        try:
            if args.stub:
                stub = StubApiServer()
                master = stub.start()
            else:
                master = args.master
            result = run_density(
                master, n_pods=args.density_pods, n_nodes=args.density_nodes,
                gang=min(100, args.density_pods),
                **{k: v for k, v in auth.items() if v},
            )
            print(json.dumps(result), flush=True)
            return 0
        finally:
            if stub is not None:
                stub.stop()

    names = [s for s in args.scenarios.split(",") if s]
    failures = []
    for name in names:
        stub = None
        try:
            if args.stub:
                stub = StubApiServer()
                master = stub.start()
            else:
                master = args.master
            c = Cluster(master, **{k: v for k, v in auth.items() if v})
            c.apply_crds()
            t0 = time.time()
            run_scenario(name, master,
                         **{k: v for k, v in auth.items() if v})
            print(f"PASS {name} ({time.time() - t0:.1f}s)", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
        finally:
            if stub is not None:
                stub.stop()
    print(f"{len(names) - len(failures)}/{len(names)} scenarios passed",
          flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

"""Kubernetes front-end shim: recorded k8s JSON fixtures → framework
objects → a real scheduling cycle (VERDICT r2 missing #2 — the documented,
tested path from real API objects to the cache)."""

import json
import os
import time

import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.resources import GPU, ResourceSpec
from kube_batch_tpu.api.types import PodGroupPhase, PodPhase
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.k8s import (
    RESOURCES,
    WatchAdapter,
    node_from_k8s,
    parse_quantity,
    pdb_from_k8s,
    pod_from_k8s,
    pod_group_from_k8s,
    priority_class_from_k8s,
    queue_from_k8s,
)
from kube_batch_tpu.scheduler import Scheduler

GiB = 1024**3

FIXTURES = json.load(
    open(os.path.join(os.path.dirname(__file__), "fixtures_k8s", "objects.json"))
)


class TestQuantityParsing:
    def test_forms(self):
        assert parse_quantity("100m") == 0.1
        assert parse_quantity("500u") == 5e-4
        assert parse_quantity("50n") == 5e-8
        assert parse_quantity("2") == 2.0
        assert parse_quantity("1Gi") == 2**30
        assert parse_quantity("500Mi") == 500 * 2**20
        assert parse_quantity("2G") == 2e9
        assert parse_quantity("1e3") == 1000.0
        assert parse_quantity(4) == 4.0


class TestTranslation:
    def test_pod_full(self):
        pod = pod_from_k8s(FIXTURES["pod_full"])
        assert pod.key() == "ml/trainer-0"
        assert pod.uid == "8f14e45f-ceea-467f-a0e6-9d8a76b3c001"
        # requests: sum over app containers, k8s units → framework units
        assert pod.requests["cpu"] == 750.0            # 500m + 250m → milli
        assert pod.requests["memory"] == GiB + 512 * 2**20
        assert pod.requests[GPU] == 2000.0             # 2 GPUs → milli
        # init containers: per-dim max
        assert pod.init_requests["cpu"] == 2000.0
        assert pod.init_requests["memory"] == 4 * GiB
        assert pod.group_name == "train-job"
        assert pod.priority == 1000
        assert pod.priority_class == "high-priority"
        assert pod.node_selector == {"accelerator": "tpu"}
        assert pod.host_ports == (18080,)
        assert pod.volume_claims == ("train-data",)
        assert pod.owner == "job-uid-123"
        assert pod.scheduler_name == "volcano"
        assert len(pod.tolerations) == 1 and pod.tolerations[0].key == "dedicated"
        aff = pod.affinity
        assert aff is not None
        assert aff.node_terms == [[("zone", "In", ("us-central1-a",))]]
        assert len(aff.pod_anti_affinity) == 1
        assert aff.pod_anti_affinity[0].match_labels == {"app": "trainer"}
        assert pod.creation_index > 0

    def test_pod_bound(self):
        pod = pod_from_k8s(FIXTURES["pod_bound"])
        assert pod.node_name == "node-a"
        assert pod.phase == PodPhase.RUNNING
        assert pod.affinity is None

    def test_node(self):
        node = node_from_k8s(FIXTURES["node"])
        assert node.name == "node-a"
        assert node.allocatable["cpu"] == 31900.0      # milli
        assert node.allocatable["memory"] == 120 * GiB
        assert node.allocatable["pods"] == 110.0
        assert node.allocatable[GPU] == 8000.0
        assert node.capacity["cpu"] == 32000.0
        assert node.ready and not node.unschedulable
        assert node.conditions == {"MemoryPressure": False, "DiskPressure": False}
        assert len(node.taints) == 1 and node.taints[0].effect == "NoSchedule"

    def test_podgroup(self):
        pg = pod_group_from_k8s(FIXTURES["podgroup"])
        assert pg.key() == "ml/train-job"
        assert pg.min_member == 4
        assert pg.queue == "ml-queue"
        assert pg.phase == PodGroupPhase.PENDING
        assert pg.min_resources == {"cpu": 3000.0, "memory": 6 * GiB}

    def test_queue(self):
        q = queue_from_k8s(FIXTURES["queue"])
        assert q.name == "ml-queue" and q.weight == 4
        assert q.capability["cpu"] == 100_000.0

    def test_priorityclass(self):
        pc = priority_class_from_k8s(FIXTURES["priorityclass"])
        assert pc.name == "high-priority" and pc.value == 1000
        assert not pc.global_default

    def test_pdb(self):
        pdb = pdb_from_k8s(FIXTURES["pdb"])
        assert pdb.min_available == 2 and pdb.owner == "rs-uid-9"

    def test_pdb_percentage_skipped(self):
        obj = {"metadata": {"name": "pct"}, "spec": {"minAvailable": "50%"}}
        assert pdb_from_k8s(obj) is None


def _gang_pod(i: int) -> dict:
    """A member of the train-job gang, derived from the recorded pod."""
    pod = json.loads(json.dumps(FIXTURES["pod_full"]))
    pod["metadata"]["name"] = f"trainer-{i}"
    pod["metadata"]["uid"] = f"trainer-uid-{i}"
    # drop anti-affinity/ports/volumes so 4 members fit one test node
    del pod["spec"]["affinity"]["podAntiAffinity"]
    pod["spec"]["containers"][0]["ports"] = []
    pod["spec"]["volumes"] = []
    return pod


def _make_cache() -> SchedulerCache:
    return SchedulerCache(spec=ResourceSpec(scalar_names=(GPU,)))


class TestEndToEnd:
    def test_watch_replay_to_scheduled_gang(self):
        """Recorded LIST+WATCH events → cache → a real cycle binds the
        gang. The full documented path from k8s API objects to placements."""
        cache = _make_cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        adapter.replay(
            [("priorityclasses", "ADDED", FIXTURES["priorityclass"]),
             ("queues", "ADDED", FIXTURES["queue"]),
             ("podgroups", "ADDED", FIXTURES["podgroup"]),
             ("nodes", "ADDED", FIXTURES["node"])]
            + [("pods", "ADDED", _gang_pod(i)) for i in range(4)]
        )
        cache.mark_synced()
        assert set(cache.queues) == {"ml-queue"}
        assert "ml/train-job" in cache.jobs
        job = cache.jobs["ml/train-job"]
        assert len(job.tasks) == 4
        assert job.priority == 0  # resolved at session open, not ingest
        # PodGroup arrived Pending-phase → needs enqueue, like the shipped
        # conf (config/kube-batch-tpu-conf.yaml)
        from kube_batch_tpu.framework.conf import parse_scheduler_conf

        conf = parse_scheduler_conf("""
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
""")
        sched = Scheduler(cache, conf=conf)
        sched.run_once()
        cache.flush_binds()
        assert len(cache.binder.binds) == 4
        assert all(n == "node-a" for n in cache.binder.binds.values())
        # the gang rode the toleration through node-a's taint; priority
        # resolved from the PriorityClass during the session
        assert job.priority == 1000
        errs = cache.columns.check_consistency(cache)
        assert not errs, errs[:3]

    def test_watch_stream_factory_start(self):
        """start() with an injected stream seeds every resource and marks
        the cache synced — the informer WaitForCacheSync analog."""
        cache = _make_cache()

        def stream(kind):
            if kind == "nodes":
                return [("ADDED", FIXTURES["node"])]
            if kind == "queues":
                return [("ADDED", FIXTURES["queue"])]
            return []

        adapter = WatchAdapter(
            cache, api_server="http://unused",
            resources=("nodes", "queues"), stream_factory=stream,
        )
        adapter.start()
        assert cache.wait_for_cache_sync()
        assert "node-a" in cache.nodes and "ml-queue" in cache.queues
        adapter.stop()

    def test_bind_evict_writeback(self):
        """K8sBackend POSTs the Binding subresource and DELETEs on evict —
        the egress half of the front end, against a recording fake apiserver."""
        import threading
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from kube_batch_tpu.api.pod import Pod
        from kube_batch_tpu.k8s.bind import K8sBackend

        calls = []

        class API(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                calls.append(("POST", self.path, json.loads(body)))
                self.send_response(201)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

            def do_DELETE(self):
                calls.append(("DELETE", self.path, None))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

        srv = ThreadingHTTPServer(("127.0.0.1", 0), API)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            backend = K8sBackend(f"http://127.0.0.1:{srv.server_address[1]}")
            pod = Pod(name="w", namespace="ns", uid="u1")
            backend.bind(pod, "node-a")
            backend.evict(pod)
        finally:
            srv.shutdown()
        method, path, body = calls[0]
        assert (method, path) == ("POST", "/api/v1/namespaces/ns/pods/w/binding")
        assert body["target"] == {"apiVersion": "v1", "kind": "Node",
                                  "name": "node-a"}
        assert calls[1][:2] == ("DELETE", "/api/v1/namespaces/ns/pods/w")

    def test_seed_reconciles_after_relist(self):
        """A re-list (410 recovery) against a populated cache upserts
        instead of duplicating and deletes objects that vanished during the
        disconnect."""
        cache = _make_cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        pod_a = FIXTURES["pod_bound"]
        pod_b = json.loads(json.dumps(pod_a))
        pod_b["metadata"]["name"] = "web-2"
        pod_b["metadata"]["uid"] = "web-2-uid"
        adapter.replay([
            ("queues", "ADDED", FIXTURES["queue"]),
            ("nodes", "ADDED", FIXTURES["node"]),
            ("pods", "ADDED", pod_a),
            ("pods", "ADDED", pod_b),
        ])
        assert cache.nodes["node-a"].used.milli_cpu == 200.0
        # re-list: web-2 vanished while disconnected; web-1 still there
        listing = {"items": [pod_a], "metadata": {"resourceVersion": "9"}}
        adapter._get_json = lambda path: listing  # transport stub
        rv = adapter._seed("pods")
        assert rv == "9"
        assert "default/web-1" in cache.pods
        assert "default/web-2" not in cache.pods
        assert cache.nodes["node-a"].used.milli_cpu == 100.0
        errs = cache.columns.check_consistency(cache)
        assert not errs, errs[:3]

    def test_seed_reconcile_deletes_gang_pod(self):
        """Reconcile-deletion of a pod carrying a group annotation must
        resolve the REAL job key (via the stored pod object), releasing its
        gang's task and the node accounting."""
        cache = _make_cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        member = _gang_pod(0)
        member["spec"]["nodeName"] = "node-a"
        member["status"]["phase"] = "Running"
        adapter.replay([
            ("queues", "ADDED", FIXTURES["queue"]),
            ("podgroups", "ADDED", FIXTURES["podgroup"]),
            ("nodes", "ADDED", FIXTURES["node"]),
            ("pods", "ADDED", member),
        ])
        job = cache.jobs["ml/train-job"]
        assert "ml/trainer-0" in job.tasks
        used_before = cache.nodes["node-a"].used.milli_cpu
        assert used_before > 0
        # pod vanished during a watch gap → re-list without it
        adapter._get_json = lambda path: {
            "items": [], "metadata": {"resourceVersion": "5"}
        }
        adapter._seed("pods")
        assert "ml/trainer-0" not in job.tasks
        assert cache.nodes["node-a"].used.milli_cpu == 0.0
        errs = cache.columns.check_consistency(cache)
        assert not errs, errs[:3]

    def test_seed_isolates_bad_objects(self):
        """One unparseable object must not poison the seed."""
        cache = _make_cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        bad = {"metadata": {"name": "bad"}, "spec": {"containers": [
            {"resources": {"requests": {"cpu": "not-a-quantity"}}}
        ]}}
        adapter._get_json = lambda path: {
            "items": [bad, FIXTURES["pod_bound"]],
            "metadata": {"resourceVersion": "3"},
        }
        adapter._seed("pods")
        assert "default/web-1" in cache.pods

    def test_modify_and_delete_events(self):
        cache = _make_cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        adapter.replay([
            ("queues", "ADDED", FIXTURES["queue"]),
            ("nodes", "ADDED", FIXTURES["node"]),
            ("pods", "ADDED", FIXTURES["pod_bound"]),
        ])
        assert "default/web-1" in cache.jobs["default/web-1"].tasks
        node = cache.nodes["node-a"]
        assert node.used.milli_cpu == 100.0
        # MODIFIED: pod finishes → accounting released
        done = json.loads(json.dumps(FIXTURES["pod_bound"]))
        done["status"]["phase"] = "Succeeded"
        adapter.replay([("pods", "MODIFIED", done)])
        assert node.used.milli_cpu == 0.0
        # DELETED: pod gone entirely
        adapter.replay([("pods", "DELETED", done)])
        assert "default/web-1" not in cache.pods
        # node cordon + delete
        cordoned = json.loads(json.dumps(FIXTURES["node"]))
        cordoned["spec"]["unschedulable"] = True
        adapter.replay([("nodes", "MODIFIED", cordoned)])
        assert cache.nodes["node-a"].node.unschedulable
        adapter.replay([("nodes", "DELETED", cordoned)])
        assert "node-a" not in cache.nodes
        errs = cache.columns.check_consistency(cache)
        assert not errs, errs[:3]


def _pvc_pod(name: str, claim: str) -> dict:
    """A pod in ml/ carrying one PVC, derived from the recorded pod —
    affinity/ports dropped so volume reachability alone decides the node."""
    pod = json.loads(json.dumps(FIXTURES["pod_full"]))
    pod["metadata"]["name"] = name
    pod["metadata"]["uid"] = f"{name}-uid"
    pod["metadata"]["annotations"].pop(
        "scheduling.k8s.io/group-name", None)
    del pod["spec"]["affinity"]
    pod["spec"]["containers"][0]["ports"] = []
    pod["spec"]["containers"][0]["resources"]["requests"].pop("nvidia.com/gpu")
    pod["spec"]["volumes"] = [
        {"name": "v", "persistentVolumeClaim": {"claimName": claim}}
    ]
    return pod


class TestVolumeK8sMode:
    """VERDICT r4 missing #1: pv/pvc/storageclass flow through the k8s-mode
    watch into a real volume ledger, and volume reachability constrains
    placement (cache.go:189-209,258-269,311-320)."""

    def _node(self, name: str) -> dict:
        node = json.loads(json.dumps(FIXTURES["node"]))
        node["metadata"]["name"] = name
        node["metadata"]["labels"]["kubernetes.io/hostname"] = name
        node["spec"]["taints"] = []
        return node

    # plain pods shadow into the default queue (cache/util.go:42-60),
    # which must exist in the cluster or the job is skipped at session open
    DEFAULT_QUEUE = {"apiVersion": "scheduling.incubator.k8s.io/v1alpha1",
                     "kind": "Queue", "metadata": {"name": "default"},
                     "spec": {"weight": 1}}

    def _cache(self):
        from kube_batch_tpu.cache.volume import K8sPVLedger

        return SchedulerCache(
            spec=ResourceSpec(scalar_names=(GPU,)),
            volume_binder=K8sPVLedger(),
        )

    def test_local_pv_constrains_placement(self):
        """An unbound no-provisioner claim must land on the one node its
        static local PV is reachable from — node-b, never node-a."""
        cache = self._cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        adapter.replay([
            ("queues", "ADDED", self.DEFAULT_QUEUE),
            ("storageclasses", "ADDED", FIXTURES["storageclass_local"]),
            ("persistentvolumes", "ADDED", FIXTURES["pv_local"]),
            ("persistentvolumeclaims", "ADDED", FIXTURES["pvc_unbound"]),
            ("nodes", "ADDED", self._node("node-a")),
            ("nodes", "ADDED", self._node("node-b")),
            ("pods", "ADDED", _pvc_pod("stateful-1", "train-data")),
        ])
        cache.mark_synced()
        binder = cache.volume_binder
        assert binder.pvs["pv-ssd-b"].node == "node-b"
        assert binder.pvs["pv-ssd-b"].storage_class == "local-ssd"
        assert "ml/train-data" in binder.claims
        sched = Scheduler(cache)
        sched.run_once()
        cache.flush_binds()
        assert cache.binder.binds == {"ml/stateful-1": "node-b"}
        # the ledger binding became durable at dispatch
        assert binder.bound["ml/train-data"] == "pv-ssd-b"
        errs = cache.columns.check_consistency(cache)
        assert not errs, errs[:3]

    def test_dynamic_claim_places_anywhere(self):
        """A claim of a provisioner-backed class is feasible on every node
        (the volume is created after scheduling)."""
        cache = self._cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        adapter.replay([
            ("queues", "ADDED", self.DEFAULT_QUEUE),
            ("storageclasses", "ADDED", FIXTURES["storageclass_dynamic"]),
            ("persistentvolumeclaims", "ADDED", FIXTURES["pvc_dynamic"]),
            ("nodes", "ADDED", self._node("node-a")),
            ("pods", "ADDED", _pvc_pod("worker-1", "scratch")),
        ])
        cache.mark_synced()
        sched = Scheduler(cache)
        sched.run_once()
        cache.flush_binds()
        assert cache.binder.binds == {"ml/worker-1": "node-a"}

    def test_unknown_claim_fails_placement(self):
        """A pod referencing a PVC the cluster doesn't carry stays Pending
        (FindPodVolumes errors in the reference)."""
        cache = self._cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        adapter.replay([
            ("queues", "ADDED", self.DEFAULT_QUEUE),
            ("nodes", "ADDED", self._node("node-a")),
            ("pods", "ADDED", _pvc_pod("orphan-1", "no-such-claim")),
        ])
        cache.mark_synced()
        sched = Scheduler(cache)
        sched.run_once()
        cache.flush_binds()
        assert cache.binder.binds == {}

    def test_bound_pvc_pins_node(self):
        """A PVC already bound (spec.volumeName) to a local PV pins its pod
        to that PV's node."""
        cache = self._cache()
        pvc = json.loads(json.dumps(FIXTURES["pvc_unbound"]))
        pvc["spec"]["volumeName"] = "pv-ssd-b"
        pvc["status"]["phase"] = "Bound"
        adapter = WatchAdapter(cache, api_server="http://unused")
        adapter.replay([
            ("queues", "ADDED", self.DEFAULT_QUEUE),
            ("persistentvolumes", "ADDED", FIXTURES["pv_local"]),
            ("persistentvolumeclaims", "ADDED", pvc),
            ("nodes", "ADDED", self._node("node-a")),
            ("nodes", "ADDED", self._node("node-b")),
            ("pods", "ADDED", _pvc_pod("stateful-2", "train-data")),
        ])
        cache.mark_synced()
        sched = Scheduler(cache)
        sched.run_once()
        cache.flush_binds()
        assert cache.binder.binds == {"ml/stateful-2": "node-b"}

    def test_pvc_deletion_reconciles(self):
        """DELETED events and re-list reconciliation drop ledger entries."""
        cache = self._cache()
        adapter = WatchAdapter(cache, api_server="http://unused")
        adapter.replay([
            ("storageclasses", "ADDED", FIXTURES["storageclass_local"]),
            ("persistentvolumes", "ADDED", FIXTURES["pv_local"]),
            ("persistentvolumeclaims", "ADDED", FIXTURES["pvc_unbound"]),
        ])
        binder = cache.volume_binder
        assert binder.pvs and binder.claims and binder.storage_classes
        adapter.replay([
            ("persistentvolumeclaims", "DELETED", FIXTURES["pvc_unbound"]),
            ("persistentvolumes", "DELETED", FIXTURES["pv_local"]),
            ("storageclasses", "DELETED", FIXTURES["storageclass_local"]),
        ])
        assert not binder.pvs and not binder.claims
        assert not binder.storage_classes
        # re-list reconciliation: a vanished PV/PVC disappears from the ledger
        adapter.replay([
            ("persistentvolumes", "ADDED", FIXTURES["pv_local"]),
            ("persistentvolumeclaims", "ADDED", FIXTURES["pvc_unbound"]),
        ])
        adapter._reconcile_deletions("persistentvolumes", [])
        adapter._reconcile_deletions("persistentvolumeclaims", [])
        assert not binder.pvs and not binder.claims

    def test_bind_writes_cluster_side(self):
        """bind_volumes PATCHes the PV claimRef (static) / the PVC
        selected-node annotation (dynamic) through the throttled transport;
        a failed write queues and retries on the next bind."""
        from kube_batch_tpu.cache.volume import (
            K8sPVLedger, SELECTED_NODE_ANNOTATION)

        class StubTransport:
            def __init__(self):
                self.requests = []
                self.fail_next = 0

            def request(self, method, path, body=None, **kw):
                if self.fail_next:
                    self.fail_next -= 1
                    raise OSError("apiserver away")
                self.requests.append((method, path, body))

        class T:  # minimal task
            def __init__(self, name, ns, claims):
                self.uid = f"{ns}/{name}"
                self.pod = type("P", (), {
                    "namespace": ns, "volume_claims": claims})()

        tr = StubTransport()
        led = K8sPVLedger(transport=tr)
        from kube_batch_tpu.k8s.translate import (
            pv_from_k8s, pvc_from_k8s)

        led.add_storage_class("local-ssd", "kubernetes.io/no-provisioner")
        led.add_storage_class("standard", "pd.csi.storage.gke.io")
        led.add_pv(pv_from_k8s(FIXTURES["pv_local"]))
        led.add_pvc(pvc_from_k8s(FIXTURES["pvc_unbound"]))
        led.add_pvc(pvc_from_k8s(FIXTURES["pvc_dynamic"]))

        static = T("s", "ml", ("train-data",))
        led.allocate_volumes(static, "node-b")
        led.bind_volumes(static)
        led.drain_writes()  # cluster writes run off-cycle on a worker
        assert tr.requests[-1][1] == "/api/v1/persistentvolumes/pv-ssd-b"
        assert tr.requests[-1][2]["spec"]["claimRef"]["name"] == "train-data"
        # an unbound PVC MODIFIED event must NOT clear the in-flight binding
        led.add_pvc(pvc_from_k8s(FIXTURES["pvc_unbound"]))
        assert led.bound["ml/train-data"] == "pv-ssd-b"

        dyn = T("d", "ml", ("scratch",))
        led.allocate_volumes(dyn, "node-a")
        tr.fail_next = 1
        led.bind_volumes(dyn)  # PATCH fails -> queued
        led.drain_writes()
        assert led._pending_writes
        # next bind flushes the queue (retry runs before new writes)
        led.bound.pop("ml/train-data")
        led.add_pvc(pvc_from_k8s(FIXTURES["pvc_unbound"]))
        led.allocate_volumes(static, "node-b")
        led.bind_volumes(static)
        led.drain_writes()
        assert not led._pending_writes
        ann = [r for r in tr.requests
               if "persistentvolumeclaims/scratch" in r[1]]
        assert ann and ann[0][2]["metadata"]["annotations"][
            SELECTED_NODE_ANNOTATION] == "node-a"
        led.close()  # bounded pv-writes join (tier-D shutdown discipline)


class TestVolumeIngestSeam:
    """The _volume_ingest dispatcher (KBT008 dogfood, PR 4): a binder
    lacking an ingest method drops the event LOUDLY — one warning per
    (binder type, method), never a silent getattr miss — and a complete
    binder receives every call."""

    def test_missing_method_warns_once_and_does_not_raise(self, caplog):
        import logging

        from kube_batch_tpu.cache.volume import StandalonePVBinder
        from kube_batch_tpu.k8s.translate import (
            _MISSING_INGEST_WARNED,
            apply_event,
        )

        # the standalone ledger has no PVC objects — --master PVC events
        # reaching it are real drops and must be observable
        cache = SchedulerCache(volume_binder=StandalonePVBinder())
        assert not hasattr(cache.volume_binder, "add_pvc")
        _MISSING_INGEST_WARNED.clear()
        with caplog.at_level(logging.WARNING, logger="kube_batch_tpu"):
            apply_event(cache, "persistentvolumeclaims", "ADDED",
                        FIXTURES["pvc_unbound"])
            apply_event(cache, "persistentvolumeclaims", "ADDED",
                        FIXTURES["pvc_dynamic"])
        drops = [r for r in caplog.records if "has no add_pvc" in r.message]
        assert len(drops) == 1  # warn-once per (type, method), not per event
        assert "dropping" in drops[0].message

    def test_complete_binder_receives_the_dispatch(self, caplog):
        import logging

        from kube_batch_tpu.cache.volume import K8sPVLedger
        from kube_batch_tpu.k8s.translate import apply_event

        cache = SchedulerCache(volume_binder=K8sPVLedger())
        with caplog.at_level(logging.WARNING, logger="kube_batch_tpu"):
            apply_event(cache, "persistentvolumeclaims", "ADDED",
                        FIXTURES["pvc_unbound"])
            apply_event(cache, "storageclasses", "ADDED",
                        FIXTURES["storageclass_local"])
            apply_event(cache, "persistentvolumes", "ADDED",
                        FIXTURES["pv_local"])
        assert cache.volume_binder.claims
        assert cache.volume_binder.storage_classes
        assert cache.volume_binder.pvs
        assert not [r for r in caplog.records if "has no " in r.message]

    def test_fake_binder_is_a_complete_silent_seam(self, caplog):
        import logging

        from kube_batch_tpu.k8s.translate import apply_event

        # the default fake implements the full ingest surface as explicit
        # no-ops (cache/interface.py) — no warnings, nothing stored
        cache = SchedulerCache()
        with caplog.at_level(logging.WARNING, logger="kube_batch_tpu"):
            apply_event(cache, "persistentvolumes", "ADDED",
                        FIXTURES["pv_local"])
            apply_event(cache, "persistentvolumeclaims", "DELETED",
                        FIXTURES["pvc_unbound"])
            apply_event(cache, "storageclasses", "DELETED",
                        FIXTURES["storageclass_local"])
        assert not [r for r in caplog.records if "has no " in r.message]
        assert cache.volume_binder.pvs == {}


class TestEventFuzz:
    def test_shuffled_duplicate_events_keep_cache_consistent(self):
        """Watch streams can deliver duplicates and orderings the happy path
        never sees (reconnect races, re-list overlap): random multisets of
        ADDED/MODIFIED/DELETED per object, shuffled, must leave a consistent
        cache that still schedules — duplicate ADDED upserts (informer
        add-or-update semantics), DELETED of unknowns no-ops."""
        import numpy as np

        from kube_batch_tpu.cache.volume import K8sPVLedger
        from kube_batch_tpu.scheduler import Scheduler

        def node(name):
            n = json.loads(json.dumps(FIXTURES["node"]))
            n["metadata"]["name"] = name
            n["spec"]["taints"] = []
            return n

        for seed in range(4):
            rng = np.random.default_rng(seed)
            cache = SchedulerCache(spec=ResourceSpec(scalar_names=(GPU,)),
                                   volume_binder=K8sPVLedger())
            adapter = WatchAdapter(cache, api_server="http://unused")
            objects = (
                [("queues", FIXTURES["queue"]),
                 ("queues", {"metadata": {"name": "default"},
                             "spec": {"weight": 1}}),
                 ("priorityclasses", FIXTURES["priorityclass"]),
                 ("podgroups", FIXTURES["podgroup"]),
                 ("storageclasses", FIXTURES["storageclass_local"]),
                 ("persistentvolumes", FIXTURES["pv_local"]),
                 ("persistentvolumeclaims", FIXTURES["pvc_unbound"]),
                 ("poddisruptionbudgets", FIXTURES["pdb"])]
                + [("nodes", node(f"n{i}")) for i in range(3)]
                + [("pods", _gang_pod(i)) for i in range(4)]
            )
            events = []
            for kind, obj in objects:
                for _ in range(int(rng.integers(1, 4))):
                    events.append((kind, str(rng.choice(
                        ["ADDED", "MODIFIED", "DELETED"])), obj))
            order = rng.permutation(len(events))
            adapter.replay([events[i] for i in order])
            cache.mark_synced()
            sched = Scheduler(cache)
            sched.run_once()
            cache.flush_binds()
            errs = cache.columns.check_consistency(cache)
            assert not errs, (seed, errs[:5])
            # a full re-list (everything as MODIFIED upserts) converges
            adapter.replay([(k, "MODIFIED", o) for k, o in objects])
            sched.run_once()
            cache.flush_binds()
            errs = cache.columns.check_consistency(cache)
            assert not errs, (seed, "after relist", errs[:5])


class TestPvNodeAffinityFailClosed:
    """round-5 ADVICE #1 regression: a PV whose REQUIRED nodeAffinity terms are
    unrecognized must translate as restrictive (reachable from no node),
    never as node=None (reachable from every node); metadata.name In
    expressions are a recognized single-node pin."""

    @staticmethod
    def _pv(node_affinity):
        spec = {"storageClassName": "local-ssd"}
        if node_affinity is not None:
            spec["nodeAffinity"] = node_affinity
        return {"apiVersion": "v1", "kind": "PersistentVolume",
                "metadata": {"name": "pv-x"}, "spec": spec}

    def test_no_required_affinity_is_reachable_everywhere(self):
        from kube_batch_tpu.k8s.translate import pv_from_k8s

        assert pv_from_k8s(self._pv(None)).node is None

    def test_hostname_in_term_pins_the_node(self):
        from kube_batch_tpu.k8s.translate import pv_from_k8s

        aff = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
            {"key": "kubernetes.io/hostname", "operator": "In",
             "values": ["node-b"]}]}]}}
        assert pv_from_k8s(self._pv(aff)).node == "node-b"

    def test_metadata_name_expression_pins_the_node(self):
        from kube_batch_tpu.k8s.translate import pv_from_k8s

        aff = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
            {"key": "metadata.name", "operator": "In",
             "values": ["node-c"]}]}]}}
        assert pv_from_k8s(self._pv(aff)).node == "node-c"

    def test_metadata_name_match_fields_pin_the_node(self):
        from kube_batch_tpu.k8s.translate import pv_from_k8s

        aff = {"required": {"nodeSelectorTerms": [{"matchFields": [
            {"key": "metadata.name", "operator": "In",
             "values": ["node-d"]}]}]}}
        assert pv_from_k8s(self._pv(aff)).node == "node-d"

    def test_unrecognized_required_terms_fail_closed(self):
        from kube_batch_tpu.cache.volume import K8sPVLedger
        from kube_batch_tpu.k8s.translate import (
            PV_NODE_RESTRICTED_UNKNOWN, pv_from_k8s, pvc_from_k8s)

        aff = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
            {"key": "topology.kubernetes.io/zone", "operator": "In",
             "values": ["us-central1-a"]}]}]}}
        pv = pv_from_k8s(self._pv(aff))
        assert pv.node == PV_NODE_RESTRICTED_UNKNOWN
        # and the ledger treats it as unreachable from every node, so the
        # placement fails instead of landing where the volume can't attach
        led = K8sPVLedger()
        led.add_storage_class("local-ssd", "kubernetes.io/no-provisioner")
        led.add_pv(pv)
        led.add_pvc(pvc_from_k8s({
            "metadata": {"name": "zonal-data", "namespace": "ml"},
            "spec": {"storageClassName": "local-ssd"},
            "status": {"phase": "Pending"},
        }))

        class T:
            uid = "ml/consumer"
            pod = type("P", (), {"namespace": "ml",
                                 "volume_claims": ("zonal-data",)})()

        assert not led.volume_feasible(T(), "node-a")
        assert not led.volume_feasible(T(), "us-central1-a")


class TestPvLedgerRetryQueue:
    """round-5 ADVICE #2 regression: retry-queue overflow must release the
    dropped claimRef's ledger binding (so it re-derives), and queued
    retries must drain on a timer even when the scheduler goes idle."""

    class _Transport:
        def __init__(self, fail_next=0):
            self.requests = []
            self.fail_next = fail_next

        def request(self, method, path, body=None, **kw):
            if self.fail_next:
                self.fail_next -= 1
                raise OSError("apiserver away")
            self.requests.append((method, path, body))

    @staticmethod
    def _task(name, claims):
        class T:
            uid = f"ml/{name}"
            pod = type("P", (), {"namespace": "ml",
                                 "volume_claims": tuple(claims)})()

        return T()

    def _led(self, transport):
        from kube_batch_tpu.api.pod import (
            PersistentVolume, PersistentVolumeClaim)
        from kube_batch_tpu.cache.volume import K8sPVLedger

        led = K8sPVLedger(transport=transport)
        led.add_storage_class("local-ssd", "kubernetes.io/no-provisioner")
        for pv in ("pv-1", "pv-2"):
            led.add_pv(PersistentVolume(name=pv, node="node-a",
                                        storage_class="local-ssd"))
        for claim in ("c1", "c2"):
            led.add_pvc(PersistentVolumeClaim(name=claim, namespace="ml",
                                              storage_class="local-ssd"))
        return led

    def test_overflow_releases_dropped_bindings(self):
        tr = self._Transport(fail_next=100)  # apiserver down throughout
        led = self._led(tr)
        led.MAX_PENDING_WRITES = 1
        led.RETRY_FLUSH_INTERVAL = 3600.0  # keep the timer out of this test
        t1 = self._task("a", ["c1"])
        led.allocate_volumes(t1, "node-a")
        led.bind_volumes(t1)
        led.drain_writes()
        assert led._pending_writes and "ml/c1" in led.bound
        dropped_pv = led.bound["ml/c1"]
        t2 = self._task("b", ["c2"])
        led.allocate_volumes(t2, "node-a")
        led.bind_volumes(t2)  # retry of c1 fails again, c2 fails → overflow
        led.drain_writes()
        assert len(led._pending_writes) == 1
        # the dropped claimRef's binding is released for re-derivation —
        # before the fix it stayed in `bound` with no queued write forever
        assert "ml/c1" not in led.bound
        assert "ml/c2" in led.bound
        # and the freed PV is claimable again
        t3 = self._task("c", ["c1"])
        led.allocate_volumes(t3, "node-a")
        assert led.reservations[t3.uid]["ml/c1"] == dropped_pv
        led.close()

    def test_idle_timer_flushes_queued_retries(self):
        tr = self._Transport(fail_next=1)
        led = self._led(tr)
        led.RETRY_FLUSH_INTERVAL = 0.05
        t1 = self._task("a", ["c1"])
        led.allocate_volumes(t1, "node-a")
        led.bind_volumes(t1)  # first PATCH fails → queued, timer armed
        led.drain_writes()
        assert led._pending_writes
        # NO further bind_volumes call: the timer alone must drain it
        deadline = time.time() + 5.0
        while led._pending_writes and time.time() < deadline:
            time.sleep(0.02)
        led.drain_writes()
        assert not led._pending_writes
        assert any("persistentvolumes/" in r[1] for r in tr.requests)
        led.close()


class TestPvTopologyAffinity:
    """ROADMAP follow-on to the fail-closed floor: a PV restricted by
    zonal/regional required terms is reachable from every node whose labels
    satisfy the full nodeSelectorTerms (the reference volumebinder's
    behavior) — the PV_NODE_RESTRICTED_UNKNOWN sentinel now only bites when
    the candidate's labels are unknown to the ledger."""

    ZONAL_AFF = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
        {"key": "topology.kubernetes.io/zone", "operator": "In",
         "values": ["us-central1-a"]}]}]}}

    @staticmethod
    def _pv(node_affinity, name="pv-z"):
        spec = {"storageClassName": "local-ssd"}
        if node_affinity is not None:
            spec["nodeAffinity"] = node_affinity
        return {"apiVersion": "v1", "kind": "PersistentVolume",
                "metadata": {"name": name}, "spec": spec}

    @staticmethod
    def _task(uid, claims):
        class T:
            pass

        t = T()
        t.uid = uid
        t.pod = type("P", (), {"namespace": "ml", "volume_claims": tuple(claims)})()
        return t

    def _zonal_ledger(self):
        from kube_batch_tpu.cache.volume import K8sPVLedger
        from kube_batch_tpu.k8s.translate import pv_from_k8s, pvc_from_k8s

        led = K8sPVLedger()
        led.add_storage_class("local-ssd", "kubernetes.io/no-provisioner")
        led.add_pv(pv_from_k8s(self._pv(self.ZONAL_AFF)))
        led.add_pvc(pvc_from_k8s({
            "metadata": {"name": "zonal-data", "namespace": "ml"},
            "spec": {"storageClassName": "local-ssd"},
            "status": {"phase": "Pending"},
        }))
        return led

    def test_translate_carries_full_terms(self):
        from kube_batch_tpu.k8s.translate import (
            PV_NODE_RESTRICTED_UNKNOWN, pv_from_k8s)

        pv = pv_from_k8s(self._pv(self.ZONAL_AFF))
        assert pv.node == PV_NODE_RESTRICTED_UNKNOWN
        assert pv.node_terms == (
            (("topology.kubernetes.io/zone", "In", ("us-central1-a",)),),
        )

    def test_single_node_pin_also_carries_terms(self):
        from kube_batch_tpu.k8s.translate import pv_from_k8s

        aff = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
            {"key": "kubernetes.io/hostname", "operator": "In",
             "values": ["node-b"]}]}]}}
        pv = pv_from_k8s(self._pv(aff))
        assert pv.node == "node-b"
        assert pv.node_terms

    def test_zonal_pv_feasible_on_labeled_in_zone_node_only(self):
        led = self._zonal_ledger()
        led.set_node_labels("node-a", {"topology.kubernetes.io/zone":
                                       "us-central1-a"})
        led.set_node_labels("node-b", {"topology.kubernetes.io/zone":
                                       "us-central1-b"})
        t = self._task("ml/consumer", ["zonal-data"])
        assert led.volume_feasible(t, "node-a")
        assert not led.volume_feasible(t, "node-b")
        # a node the ledger has no labels for stays fail-closed
        assert not led.volume_feasible(t, "node-unknown")

    def test_allocate_and_bind_on_zone_match(self):
        led = self._zonal_ledger()
        led.set_node_labels("node-a", {"topology.kubernetes.io/zone":
                                       "us-central1-a"})
        t = self._task("ml/consumer", ["zonal-data"])
        led.allocate_volumes(t, "node-a")
        led.bind_volumes(t)
        assert led.bound["ml/zonal-data"] == "pv-z"

    def test_deleting_node_labels_fails_closed_again(self):
        led = self._zonal_ledger()
        led.set_node_labels("node-a", {"topology.kubernetes.io/zone":
                                       "us-central1-a"})
        t = self._task("ml/consumer", ["zonal-data"])
        assert led.volume_feasible(t, "node-a")
        led.forget_node_labels("node-a")
        assert not led.volume_feasible(t, "node-a")

    def test_cache_node_ingest_feeds_ledger_labels(self):
        from kube_batch_tpu.api.pod import Node
        from kube_batch_tpu.cache.cache import SchedulerCache

        led = self._zonal_ledger()
        cache = SchedulerCache(volume_binder=led)
        cache.add_node(Node(
            name="node-a",
            allocatable={"cpu": 4000.0},
            labels={"topology.kubernetes.io/zone": "us-central1-a"},
        ))
        t = self._task("ml/consumer", ["zonal-data"])
        assert led.volume_feasible(t, "node-a")
        cache.delete_node("node-a")
        assert not led.volume_feasible(t, "node-a")

    def test_hostname_terms_work_without_label_ingest(self):
        # the kubelet-set hostname label is synthesized, so a multi-host
        # hostname In [...] term works even on ledgers that never saw labels
        led = self._zonal_ledger()
        from kube_batch_tpu.k8s.translate import pv_from_k8s, pvc_from_k8s

        aff = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
            {"key": "kubernetes.io/hostname", "operator": "In",
             "values": ["node-a", "node-b"]}]}]}}
        led.add_pv(pv_from_k8s(self._pv(aff, name="pv-two-hosts")))
        led.add_pvc(pvc_from_k8s({
            "metadata": {"name": "dual", "namespace": "ml"},
            "spec": {"storageClassName": "local-ssd"},
            "status": {"phase": "Pending"},
        }))
        t = self._task("ml/dual-consumer", ["dual"])
        # pin fast path covers node-a (first value); terms cover node-b too
        assert led.volume_feasible(t, "node-a")
        assert led.volume_feasible(t, "node-b")
        assert not led.volume_feasible(t, "node-c")


class TestNodeSelectorTermsMatch:
    """Shared evaluator semantics (api/pod.py): OR across terms, AND within,
    Gt/Lt numeric, unknown operators fail closed."""

    def test_or_across_terms_and_within(self):
        from kube_batch_tpu.api.pod import node_selector_terms_match

        terms = (
            (("zone", "In", ("a",)), ("disk", "In", ("ssd",))),
            (("region", "In", ("r1",)),),
        )
        assert node_selector_terms_match(terms, {"zone": "a", "disk": "ssd"})
        assert node_selector_terms_match(terms, {"region": "r1"})
        assert not node_selector_terms_match(terms, {"zone": "a", "disk": "hdd"})

    def test_exists_notin_gt_lt(self):
        from kube_batch_tpu.api.pod import node_selector_terms_match

        assert node_selector_terms_match(
            ((("gpu", "Exists", ()),),), {"gpu": "1"})
        assert not node_selector_terms_match(
            ((("gpu", "DoesNotExist", ()),),), {"gpu": "1"})
        assert node_selector_terms_match(
            ((("slots", "Gt", ("4",)),),), {"slots": "8"})
        assert not node_selector_terms_match(
            ((("slots", "Lt", ("4",)),),), {"slots": "8"})

    def test_unknown_operator_fails_closed(self):
        from kube_batch_tpu.api.pod import node_selector_terms_match

        assert not node_selector_terms_match(
            ((("zone", "Near", ("a",)),),), {"zone": "a"})


class TestPvAffinityReviewRegressions:
    """Two fail-open holes caught in review of the topology-affinity change:
    a hostname pin AND'd with further requirements must not bypass term
    evaluation, and unlabeled nodes must not satisfy negative operators."""

    def test_pin_with_anded_zone_requirement_does_not_fail_open(self):
        from kube_batch_tpu.cache.volume import K8sPVLedger
        from kube_batch_tpu.k8s.translate import (
            PV_NODE_RESTRICTED_UNKNOWN, pv_from_k8s, pvc_from_k8s)

        # ONE term: hostname In [n1] AND zone In [z1] — conditional pin
        aff = {"required": {"nodeSelectorTerms": [{"matchExpressions": [
            {"key": "kubernetes.io/hostname", "operator": "In",
             "values": ["n1"]},
            {"key": "topology.kubernetes.io/zone", "operator": "In",
             "values": ["z1"]}]}]}}
        pv = pv_from_k8s({"apiVersion": "v1", "kind": "PersistentVolume",
                          "metadata": {"name": "pv-cond"},
                          "spec": {"storageClassName": "local-ssd",
                                   "nodeAffinity": aff}})
        # the pin fast path must NOT claim n1 unconditionally
        assert pv.node == PV_NODE_RESTRICTED_UNKNOWN
        led = K8sPVLedger()
        led.add_storage_class("local-ssd", "kubernetes.io/no-provisioner")
        led.add_pv(pv)
        led.add_pvc(pvc_from_k8s({
            "metadata": {"name": "c", "namespace": "ml"},
            "spec": {"storageClassName": "local-ssd"},
            "status": {"phase": "Pending"},
        }))
        t = TestPvTopologyAffinity._task("ml/x", ["c"])
        # n1 in the WRONG zone: both requirements are AND'd, so infeasible
        led.set_node_labels("n1", {"topology.kubernetes.io/zone": "z2"})
        assert not led.volume_feasible(t, "n1")
        # n1 in the right zone: feasible
        led.set_node_labels("n1", {"topology.kubernetes.io/zone": "z1"})
        assert led.volume_feasible(t, "n1")

    def test_negative_operator_on_unlabeled_node_fails_closed(self):
        from kube_batch_tpu.api.pod import PersistentVolume
        from kube_batch_tpu.cache.volume import K8sPVLedger
        from kube_batch_tpu.k8s.translate import (
            PV_NODE_RESTRICTED_UNKNOWN, pvc_from_k8s)

        led = K8sPVLedger()
        led.add_storage_class("local-ssd", "kubernetes.io/no-provisioner")
        led.add_pv(PersistentVolume(
            name="pv-neg", storage_class="local-ssd",
            node=PV_NODE_RESTRICTED_UNKNOWN,
            node_terms=((("topology.kubernetes.io/zone", "NotIn", ("z1",)),),),
        ))
        led.add_pvc(pvc_from_k8s({
            "metadata": {"name": "c", "namespace": "ml"},
            "spec": {"storageClassName": "local-ssd"},
            "status": {"phase": "Pending"},
        }))
        t = TestPvTopologyAffinity._task("ml/x", ["c"])
        # ledger never saw labels for this node: NotIn must NOT match the
        # absent key (the node may well be IN z1) — fail closed
        assert not led.volume_feasible(t, "mystery-node")
        # with labels ingested the genuine semantics apply
        led.set_node_labels("n-out", {"topology.kubernetes.io/zone": "z2"})
        assert led.volume_feasible(t, "n-out")
        led.set_node_labels("n-in", {"topology.kubernetes.io/zone": "z1"})
        assert not led.volume_feasible(t, "n-in")

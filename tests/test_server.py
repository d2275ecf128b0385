"""L0/L10 layer tests: options defaults (options_test.go:51), the HTTP
admin/ingest API (the informer + CLI seam), leader election, serialization
round-trips, and the queue CLI against a live server."""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import pytest

from kube_batch_tpu.api import serialize
from kube_batch_tpu.api.pod import (
    GROUP_NAME_ANNOTATION,
    Affinity,
    Node,
    Pod,
    PodGroup,
    Queue,
    Taint,
    Toleration,
)
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.cli import queue as queue_cli
from kube_batch_tpu.cmd import options
from kube_batch_tpu.cmd.leader_election import LeaderElector
from kube_batch_tpu.cmd.server import AdminServer
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.scheduler import Scheduler
from tests.fixtures import build_node, build_pod


def _get(port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        body = r.read()
        ctype = r.headers.get("Content-Type", "")
        return json.loads(body) if "json" in ctype else body.decode()


def _post_method(port: int, path: str, obj, method: str):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(obj).encode(),
        method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=5) as r:
        return json.loads(r.read())


def _post(port: int, path: str, obj):
    return _post_method(port, path, obj, "POST")


class TestOptions:
    def test_defaults(self):
        opt = options.parse([])
        assert opt.scheduler_name == "volcano"
        assert opt.schedule_period == 1.0
        assert opt.default_queue == "default"
        assert opt.enable_leader_election is False
        assert opt.listen_address == ":8080"
        assert opt.enable_priority_class is True
        assert opt.kube_api_qps == 50.0
        assert opt.kube_api_burst == 100

    def test_leader_election_requires_namespace(self):
        opt = options.parse(["--leader-elect"])
        with pytest.raises(ValueError):
            opt.check_option_or_die()

    def test_flag_parse(self):
        opt = options.parse(
            ["--scheduler-name", "kb", "--schedule-period", "0.5",
             "--listen-address", "127.0.0.1:9999"]
        )
        assert opt.scheduler_name == "kb"
        assert opt.schedule_period == 0.5
        assert opt.listen_host_port == ("127.0.0.1", 9999)

    def test_malformed_listen_address_rejected(self):
        opt = options.parse(["--listen-address", "localhost"])
        with pytest.raises(ValueError):
            opt.check_option_or_die()
        opt = options.parse(["--listen-address", "[::]:8080"])
        assert opt.listen_host_port == ("::", 8080)

    def test_priority_class_toggle(self):
        from kube_batch_tpu.api.pod import PriorityClass
        cache = SchedulerCache(resolve_priority=False)
        cache.add_priority_class(PriorityClass(name="high", value=100))
        assert cache.priority_classes == {}
        pod = build_pod("default", "p", None, PodPhase.PENDING,
                        {"cpu": 100.0}, priority_class="high")
        cache.add_pod(pod)
        assert pod.priority == 0


class TestSerialize:
    def test_pod_round_trip(self):
        pod = Pod(
            name="p1", requests={"cpu": 1000, "memory": 1 << 30},
            annotations={GROUP_NAME_ANNOTATION: "pg1"},
            tolerations=[Toleration(key="k", operator="Exists")],
            affinity=Affinity(node_terms=[[("zone", "In", ("a", "b"))]]),
            host_ports=(8080,),
        )
        back = serialize.pod_from_dict(serialize.pod_to_dict(pod))
        assert back.key() == pod.key()
        assert back.requests == pod.requests
        assert back.group_name == "pg1"
        assert back.tolerations[0].operator == "Exists"
        assert back.affinity.node_terms == [[("zone", "In", ("a", "b"))]]
        assert back.host_ports == (8080,)

    def test_pod_affinity_round_trip(self):
        from kube_batch_tpu.api.pod import PodAffinityTerm
        pod = Pod(
            name="p2",
            affinity=Affinity(
                pod_affinity=[PodAffinityTerm(match_labels={"app": "db"})],
                pod_anti_affinity=[
                    PodAffinityTerm(match_labels={"app": "w"}, topology_key="zone")
                ],
            ),
        )
        back = serialize.pod_from_dict(serialize.pod_to_dict(pod))
        assert back.affinity.pod_affinity[0].match_labels == {"app": "db"}
        assert back.affinity.pod_anti_affinity[0].topology_key == "zone"

    def test_node_round_trip(self):
        node = Node(name="n1", allocatable={"cpu": 4000},
                    taints=[Taint(key="t", effect="NoSchedule")],
                    labels={"zone": "a"})
        back = serialize.node_from_dict(serialize.node_to_dict(node))
        assert back.name == "n1" and back.taints[0].key == "t"
        assert back.labels == {"zone": "a"}

    def test_pod_group_round_trip(self):
        pg = PodGroup(name="pg1", min_member=3, queue="q1")
        back = serialize.pod_group_from_dict(serialize.pod_group_to_dict(pg))
        assert back.min_member == 3 and back.queue == "q1"
        assert back.phase is None


class TestAdminServer:
    @pytest.fixture()
    def server(self):
        cache = SchedulerCache()
        srv = AdminServer(cache, port=0)
        srv.start()
        yield cache, srv
        srv.stop()

    def test_health_version_metrics(self, server):
        _, srv = server
        assert _get(srv.port, "/healthz") == "ok"
        # /version names the device the serving process runs on, as JAX
        # reports it there (chip_smoke.py reads it instead of guessing)
        import jax

        ver = _get(srv.port, "/version")
        assert "kube-batch-tpu" in ver["version"]
        assert ver["jax"] == jax.__version__
        assert (ver["platform"], ver["device_kind"], ver["device_count"]) == (
            jax.devices()[0].platform, jax.devices()[0].device_kind,
            len(jax.devices()))
        assert ver["native"].startswith(("built", "loaded", "numpy"))
        assert "compile_cache_dir" in ver
        assert "volcano_e2e_scheduling_latency_milliseconds" in _get(srv.port, "/metrics")

    def test_ingest_schedule_and_read_back(self, server):
        cache, srv = server
        _post(srv.port, "/v1/queues", {"name": "default", "weight": 1})
        _post(srv.port, "/v1/nodes", serialize.node_to_dict(build_node("n1")))
        _post(srv.port, "/v1/podgroups",
              serialize.pod_group_to_dict(PodGroup(name="pg1", min_member=1)))
        _post(srv.port, "/v1/pods", serialize.pod_to_dict(
            build_pod("default", "p1", None, PodPhase.PENDING,
                      {"cpu": 1000.0}, group_name="pg1")))
        # one scheduling cycle over the ingested state
        Scheduler(cache, conf=load_scheduler_conf(None)).run_once()
        bindings = _get(srv.port, "/v1/bindings")
        assert bindings == [{"pod": "default/p1", "node": "n1", "status": "BINDING"}]
        jobs = _get(srv.port, "/v1/jobs")
        assert jobs[0]["phase"] == "Running"
        queues = _get(srv.port, "/v1/queues")
        assert queues[0]["name"] == "default" and queues[0]["running"] == 1

    def test_pod_repost_is_upsert(self, server):
        cache, srv = server
        _post(srv.port, "/v1/queues", {"name": "default", "weight": 1})
        pod = serialize.pod_to_dict(
            build_pod("default", "p1", None, PodPhase.PENDING, {"cpu": 500.0}))
        _post(srv.port, "/v1/pods", pod)
        pod["requests"] = {"cpu": 700.0}
        _post(srv.port, "/v1/pods", pod)  # re-POST: update, not duplicate
        job = next(iter(cache.jobs.values()))
        assert len(job.tasks) == 1
        assert job.total_request.milli_cpu == 700.0

    def test_batched_ingest_list_body(self, server):
        """A list body applies the whole batch under one lock acquisition
        and ONE dirty-version advance — the high-QPS ingest path."""
        cache, srv = server
        _post(srv.port, "/v1/queues", {"name": "default", "weight": 1})
        v0 = cache.dirty.version
        pods = [
            serialize.pod_to_dict(build_pod(
                "default", f"bp{i}", None, PodPhase.PENDING, {"cpu": 100.0}))
            for i in range(6)
        ]
        resp = _post(srv.port, "/v1/pods", pods)
        assert resp == {"ok": True, "applied": 6}
        assert all(f"default/bp{i}" in cache.pods for i in range(6))
        assert cache.dirty.version == v0 + 1
        # batched DELETE takes the same path
        resp = _post_method(srv.port, "/v1/pods", pods[:2], "DELETE")
        assert resp == {"ok": True, "applied": 2}
        assert "default/bp0" not in cache.pods
        assert "default/bp2" in cache.pods

    def test_batched_ingest_rejects_malformed_batch_wholesale(self, server):
        cache, srv = server
        good = serialize.pod_to_dict(build_pod(
            "default", "gx", None, PodPhase.PENDING, {"cpu": 100.0}))
        with pytest.raises(urllib.error.HTTPError):
            _post(srv.port, "/v1/pods", [good, {"bogus_field": 1}])
        # the whole batch parses before any element applies
        assert "default/gx" not in cache.pods

    def test_delete_and_errors(self, server):
        cache, srv = server
        _post(srv.port, "/v1/queues", {"name": "q2", "weight": 3})
        assert "q2" in cache.queues
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/queues",
            data=json.dumps({"name": "q2"}).encode(), method="DELETE",
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=5)
        assert "q2" not in cache.queues
        with pytest.raises(urllib.error.HTTPError):
            _post(srv.port, "/v1/widgets", {})
        with pytest.raises(urllib.error.HTTPError):
            _post(srv.port, "/v1/pods", {"bogus_field": 1})


class TestQueueCLI:
    def test_create_and_list(self, capsys):
        cache = SchedulerCache()
        srv = AdminServer(cache, port=0)
        srv.start()
        try:
            server = f"http://127.0.0.1:{srv.port}"
            assert queue_cli.main(["--server", server, "create",
                                   "--name", "gold", "--weight", "5"]) == 0
            assert cache.queues["gold"].weight == 5
            assert queue_cli.main(["--server", server, "list"]) == 0
            out = capsys.readouterr().out
            assert "gold" in out and "Weight" in out
        finally:
            srv.stop()

    def test_master_mode_round_trip(self, capsys):
        """--master: create writes the Queue CRD to the cluster (the
        authoritative store, create.go:47-68), list reads CRDs back
        (list.go:51-87), and the scheduler ingests the created object
        through its normal translate path."""
        import json as _json
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        store = {}

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                obj = _json.loads(self.rfile.read(n))
                store[obj["metadata"]["name"]] = obj
                body = _json.dumps(obj).encode()
                self.send_response(201)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                body = _json.dumps({"items": list(store.values())}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            master = f"http://127.0.0.1:{srv.server_address[1]}"
            # connection flags AFTER the subcommand (the documented form the
            # shared parent parser exists to support)
            assert queue_cli.main(["create", "--master", master,
                                   "--name", "gold", "--weight", "5"]) == 0
            stored = store["gold"]
            assert stored["apiVersion"] == "scheduling.incubator.k8s.io/v1alpha1"
            assert stored["kind"] == "Queue"
            assert stored["spec"]["weight"] == 5
            capsys.readouterr()  # drop create's output — list must stand alone
            assert queue_cli.main(["--master", master, "list"]) == 0
            out = capsys.readouterr().out
            row = [ln for ln in out.splitlines() if ln.startswith("gold")]
            assert row and "5" in row[0].split(), out
            # the object the CLI wrote is exactly what the scheduler's watch
            # ingests: apply it through the translate path
            from kube_batch_tpu.k8s.translate import apply_event

            cache = SchedulerCache()
            apply_event(cache, "queues", "ADDED", stored)
            assert cache.queues["gold"].weight == 5
        finally:
            srv.shutdown()


class TestRateLimiter:
    def test_bind_throttled_to_qps(self):
        from kube_batch_tpu.cache.fake import FakeBinder
        from kube_batch_tpu.cmd.server import RateLimitedBackend

        rl = RateLimitedBackend(FakeBinder(), qps=100.0, burst=5)
        pods = [build_pod("default", f"p{i}", None, PodPhase.PENDING, {})
                for i in range(15)]
        t0 = time.perf_counter()
        for p in pods:
            rl.bind(p, "n1")
        elapsed = time.perf_counter() - t0
        # 15 binds, burst 5 → ≥10 token waits at 100/s ≈ ≥0.1s
        assert elapsed >= 0.08
        assert len(rl._backend.binds) == 15

    def test_single_bucket_shared_across_seams(self):
        """Binder + evictor + status updater drain ONE token budget: the
        reference's writes all ride a single throttled rest.Config
        (server.go:69-70), so combined egress must not reach 3x qps."""
        from kube_batch_tpu.cache.fake import FakeBinder, FakeEvictor
        from kube_batch_tpu.cmd.server import (
            RateLimitedBackend, TokenBucket)

        bucket = TokenBucket(qps=100.0, burst=5)
        binder = RateLimitedBackend(FakeBinder(), bucket=bucket)
        evictor = RateLimitedBackend(FakeEvictor(), bucket=bucket)
        pods = [build_pod("default", f"p{i}", None, PodPhase.PENDING, {})
                for i in range(16)]
        t0 = time.perf_counter()
        for i, p in enumerate(pods):
            (binder.bind(p, "n1") if i % 2 == 0 else evictor.evict(p))
        elapsed = time.perf_counter() - t0
        # 16 writes against a SHARED burst of 5 → ≥11 waits at 100/s;
        # independent buckets would sail through both bursts in ~0.03s
        assert elapsed >= 0.08
        assert len(binder._backend.binds) == 8
        assert len(evictor._backend.evicts) == 8


class TestLeaderElection:
    def test_single_leader_and_failover(self, tmp_path):
        a = LeaderElector(str(tmp_path), identity="a",
                          lease_duration=0.4, renew_deadline=0.3, retry_period=0.05)
        b = LeaderElector(str(tmp_path), identity="b",
                          lease_duration=0.4, renew_deadline=0.3, retry_period=0.05)
        order = []

        def lead(elector, name, hold):
            def body():
                order.append(name)
                time.sleep(hold)
            elector.run(body)

        ta = threading.Thread(target=lead, args=(a, "a", 0.3), daemon=True)
        ta.start()
        time.sleep(0.1)
        assert a.is_leader() and not b.is_leader()
        tb = threading.Thread(target=lead, args=(b, "b", 0.1), daemon=True)
        tb.start()
        time.sleep(0.1)
        assert order == ["a"]  # b blocked while a's lease is valid
        ta.join(2)
        tb.join(2)
        assert order == ["a", "b"]  # release → standby takes over


class _LeaseStub:
    """In-memory coordination.k8s.io/v1 Lease apiserver with resourceVersion
    compare-and-swap — the contract K8sLeaseElector relies on (a stale PUT
    must 409, exactly like the real apiserver)."""

    def __init__(self):
        import json as _json
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        store = self.store = {}
        lock = threading.Lock()
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _send(self, code, obj=None):
                body = _json.dumps(obj or {}).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _name(self):
                return self.path.rstrip("/").split("/")[-1]

            def _body(self):
                n = int(self.headers.get("Content-Length", 0))
                return _json.loads(self.rfile.read(n))

            def do_GET(self):
                with lock:
                    obj = store.get(self._name())
                self._send(200, obj) if obj else self._send(404)

            def do_POST(self):
                obj = self._body()
                name = (obj.get("metadata") or {}).get("name", "")
                with lock:
                    if name in store:
                        return self._send(409)
                    obj.setdefault("metadata", {})["resourceVersion"] = "1"
                    store[name] = obj
                    stub.writes += 1
                self._send(201, obj)

            def do_PUT(self):
                obj = self._body()
                name = self._name()
                with lock:
                    cur = store.get(name)
                    if cur is None:
                        return self._send(404)
                    if (obj.get("metadata") or {}).get("resourceVersion") != (
                        cur["metadata"]["resourceVersion"]
                    ):
                        return self._send(409)
                    obj["metadata"]["resourceVersion"] = str(
                        int(cur["metadata"]["resourceVersion"]) + 1
                    )
                    store[name] = obj
                    stub.writes += 1
                self._send(200, obj)

        self.writes = 0
        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()
        self.url = f"http://127.0.0.1:{self.srv.server_address[1]}"

    def shutdown(self):
        self.srv.shutdown()


class TestK8sLeaseElection:
    def _elector(self, url, ident, **kw):
        from kube_batch_tpu.cmd.leader_election import K8sLeaseElector
        from kube_batch_tpu.k8s.transport import ApiTransport

        # whole seconds: the Lease wire format is leaseDurationSeconds
        kw.setdefault("lease_duration", 1.0)
        kw.setdefault("renew_deadline", 0.75)
        kw.setdefault("retry_period", 0.1)
        return K8sLeaseElector(
            ApiTransport(url), namespace="kube-system", identity=ident, **kw
        )

    def test_single_leader_and_failover(self):
        """Two electors on different 'hosts' (no shared filesystem — only
        the apiserver): one leads, the standby blocks while the lease is
        valid, release hands over (server.go:106-151 semantics)."""
        stub = _LeaseStub()
        try:
            a = self._elector(stub.url, "host-a")
            b = self._elector(stub.url, "host-b")
            order = []

            def lead(elector, name, hold):
                def body():
                    order.append(name)
                    time.sleep(hold)
                elector.run(body)

            ta = threading.Thread(target=lead, args=(a, "host-a", 0.6), daemon=True)
            ta.start()
            time.sleep(0.25)
            assert a.is_leader() and not b.is_leader()
            tb = threading.Thread(target=lead, args=(b, "host-b", 0.2), daemon=True)
            tb.start()
            time.sleep(0.2)
            assert order == ["host-a"]  # b blocked while a's lease is valid
            ta.join(4)
            tb.join(4)
            assert order == ["host-a", "host-b"]  # release → takeover
            # the release vacated the lease; b then took it and released
            spec = stub.store["kube-batch-tpu"]["spec"]
            assert spec["holderIdentity"] == ""
            assert spec["leaseTransitions"] >= 1
        finally:
            stub.shutdown()

    def test_sub_second_duration_rejected(self):
        import pytest as _pytest

        with _pytest.raises(ValueError):
            self._elector("http://x", "a", lease_duration=0.4)

    def test_expired_lease_takeover_and_cas(self):
        """A dead leader's expired lease is taken over; a stale
        resourceVersion write loses the CAS and reports failure, not a
        split brain."""
        stub = _LeaseStub()
        try:
            a = self._elector(stub.url, "host-a")
            b = self._elector(stub.url, "host-b")
            assert a._try_acquire_or_renew()          # a creates the lease
            assert not b._try_acquire_or_renew()      # valid → b fails
            time.sleep(1.1)                           # a dies; lease expires
            assert b._try_acquire_or_renew()          # b takes over
            assert stub.store["kube-batch-tpu"]["spec"]["holderIdentity"] == "host-b"
            assert stub.store["kube-batch-tpu"]["spec"]["leaseTransitions"] == 1
            # CAS: a PUT carrying a stale resourceVersion must 409 → False
            import urllib.request
            stale = dict(stub.store["kube-batch-tpu"])
            stale["metadata"] = dict(stale["metadata"], resourceVersion="0")
            req = urllib.request.Request(
                stub.url + "/apis/coordination.k8s.io/v1/namespaces/"
                "kube-system/leases/kube-batch-tpu",
                data=__import__("json").dumps(stale).encode(),
                headers={"Content-Type": "application/json"}, method="PUT",
            )
            try:
                urllib.request.urlopen(req)
                raise AssertionError("stale PUT must 409")
            except urllib.error.HTTPError as e:
                assert e.code == 409
        finally:
            stub.shutdown()

    def test_unreachable_apiserver_reports_failure(self):
        """Transport errors run the renew deadline down instead of raising
        out of the loop (the standby keeps retrying)."""
        e = self._elector("http://127.0.0.1:1", "host-x")  # nothing listens
        assert e._try_acquire_or_renew() is False
        assert e.is_leader() is False


class TestPersistence:
    def test_save_load_round_trip(self, tmp_path):
        """SURVEY.md §5.4: restart = reload durable state; the Inqueue phase
        survives (enqueue.go:115)."""
        from kube_batch_tpu.api.types import PodGroupPhase
        from kube_batch_tpu.cache.persistence import load_state, save_state

        cache = SchedulerCache()
        cache.add_queue(serialize.queue_from_dict({"name": "gold", "weight": 3}))
        cache.add_node(build_node("n1"))
        pg = PodGroup(name="pg1", min_member=2, queue="gold",
                      phase=PodGroupPhase.INQUEUE)
        cache.add_pod_group(pg)
        cache.add_pod(build_pod("default", "p1", "n1", PodPhase.RUNNING,
                                {"cpu": 500.0}, group_name="pg1"))
        path = str(tmp_path / "state.json")
        save_state(cache, path)

        fresh = SchedulerCache()
        assert load_state(fresh, path)
        assert fresh.queues["gold"].weight == 3
        assert fresh.jobs["default/pg1"].pod_group.phase == PodGroupPhase.INQUEUE
        # bound pod replays node accounting
        node = fresh.nodes["n1"]
        assert node.used.milli_cpu == 500.0
        assert not load_state(SchedulerCache(), str(tmp_path / "missing.json"))

    def test_shadow_pod_groups_not_persisted(self, tmp_path):
        from kube_batch_tpu.cache.persistence import load_state, save_state
        cache = SchedulerCache()
        cache.add_queue(serialize.queue_from_dict({"name": "default"}))
        cache.add_pod(build_pod("default", "solo", None, PodPhase.PENDING,
                                {"cpu": 100.0}))  # plain pod → shadow PG
        path = str(tmp_path / "state.json")
        save_state(cache, path)
        fresh = SchedulerCache()
        load_state(fresh, path)
        job = next(iter(fresh.jobs.values()))
        assert job.pod_group is not None and job.pod_group.shadow


class TestDebugEndpoints:
    def test_stacks(self):
        cache = SchedulerCache()
        srv = AdminServer(cache, port=0)
        srv.start()
        try:
            body = _get(srv.port, "/debug/stacks")
            assert "thread" in body
        finally:
            srv.stop()

    def test_pprof_samples_other_threads(self):
        """/debug/pprof is a SAMPLING profiler over every thread — it must
        attribute samples to a busy worker thread, not just itself."""
        import threading as _threading

        cache = SchedulerCache()
        srv = AdminServer(cache, port=0)
        srv.start()
        stop = _threading.Event()

        def busy():
            x = 0
            while not stop.is_set():
                x += 1

        t = _threading.Thread(target=busy, daemon=True)
        t.start()
        try:
            body = _get(srv.port, "/debug/pprof?seconds=0.5")
            assert "samples:" in body
            assert "busy" in body, body[:500]
        finally:
            stop.set()
            srv.stop()


class TestCacheSyncBarrier:
    def test_wait_for_cache_sync(self):
        """WaitForCacheSync analog (cache.go:363-384): the barrier blocks
        until signaled, and the bounded wait falls through on timeout."""
        cache = SchedulerCache()
        assert not cache.wait_for_cache_sync()        # not signaled yet
        assert not cache.wait_for_cache_sync(0.01)    # bounded wait times out
        cache.mark_synced()
        assert cache.wait_for_cache_sync()
        assert cache.wait_for_cache_sync(0.01)

    def test_sync_endpoint_signals_barrier(self):
        cache = SchedulerCache()
        srv = AdminServer(cache, port=0)
        srv.start()
        try:
            assert not cache.wait_for_cache_sync()
            _post(srv.port, "/v1/sync", {})
            assert cache.wait_for_cache_sync()
        finally:
            srv.stop()


class TestRestartWithBindings:
    def test_bound_pods_survive_restart_and_are_not_rescheduled(self, tmp_path):
        """Crash-restart story: binder acks persist pod.node_name, so a
        state-file round trip restores placements as Bound (Pending+nodeName
        → Bound, helpers.go:35-61) with correct node accounting, and the
        next cycle on the fresh process re-schedules nothing."""
        from kube_batch_tpu.api.types import TaskStatus
        from kube_batch_tpu.cache.persistence import load_state, save_state
        from kube_batch_tpu.framework.conf import load_scheduler_conf
        from kube_batch_tpu.scheduler import Scheduler

        cache = SchedulerCache()
        cache.add_queue(Queue(name="default", weight=1))
        cache.add_node(Node(name="n1", allocatable={
            "cpu": 8000.0, "memory": float(16 << 30), "pods": 110.0}))
        for i in range(3):
            cache.add_pod(Pod(name=f"p{i}", namespace="c1",
                              requests={"cpu": 1000.0,
                                        "memory": float(1 << 30)},
                              phase=PodPhase.PENDING))
        Scheduler(cache, conf=load_scheduler_conf(None)).run_once()
        assert len(cache.binder.binds) == 3
        path = str(tmp_path / "state.json")
        save_state(cache, path)

        fresh = SchedulerCache()
        assert load_state(fresh, path)
        # placements restored: tasks Bound on n1, idle reflects them
        for i in range(3):
            task = fresh.jobs[f"c1/p{i}"].tasks[f"c1/p{i}"]
            assert task.status == TaskStatus.BOUND
            assert task.node_name == "n1"
        assert fresh.nodes["n1"].used.milli_cpu == 3000
        # the restarted process schedules nothing new
        Scheduler(fresh, conf=load_scheduler_conf(None)).run_once()
        assert fresh.binder.binds == {}


class TestTokenBucketConcurrency:
    def test_take_sleeps_outside_the_lock(self):
        """round-5 ADVICE #3 regression: a waiter must reserve under the lock and
        sleep OUTSIDE it — a sleeper holding self._lock serializes the
        16-worker status pool and head-of-line blocks the bind loop."""
        from kube_batch_tpu.cmd.server import TokenBucket

        bucket = TokenBucket(qps=4.0, burst=1)
        bucket.take()  # consume the burst token; next take waits ~0.25s
        waiter = threading.Thread(target=bucket.take)
        waiter.start()
        try:
            time.sleep(0.05)  # let the waiter reserve and start sleeping
            acquired = bucket._lock.acquire(timeout=0.05)
            if acquired:
                bucket._lock.release()
            assert acquired, "take() held the lock through its sleep"
        finally:
            waiter.join()

    def test_parallel_waiters_keep_aggregate_rate(self):
        """Reservations are debt positions: N concurrent waiters sleep in
        parallel yet tokens still mint at qps overall."""
        from kube_batch_tpu.cmd.server import TokenBucket

        bucket = TokenBucket(qps=100.0, burst=1)
        threads = [threading.Thread(target=bucket.take) for _ in range(9)]
        t0 = time.perf_counter()
        bucket.take()  # burst token
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        # 10 takes, burst 1 → 9 minted tokens at 100/s ≈ ≥0.09s aggregate,
        # and nowhere near 9 serialized full waits either
        assert elapsed >= 0.07
        assert elapsed < 1.0

"""Query plane (serve/ + ops/probe.py): oracle exactness vs the committed
solves, lease consistency under a concurrently mutating cycle, micro-batcher
deadline/overflow behavior under a stubbed clock, sharded-probe bit
equivalence, and the /v1/whatif HTTP surface.

The oracle tests are the subsystem's contract: a gang the probe reports
feasible at nodes X on a frozen snapshot must bind to EXACTLY X when
actually submitted (same rows, same tie-breaks, same machinery), and an
infeasible verdict must carry the same fit-error histogram the committed
cycle would record."""

from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
import urllib.request
from concurrent.futures import TimeoutError as FutureTimeout

import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION, Pod, PodGroup, Queue
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.framework.interface import get_action
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.serve.batcher import MicroBatcher, QueueFull
from kube_batch_tpu.serve.lease import LeaseBroker, SnapshotLease
from kube_batch_tpu.serve.plane import QueryPlane, WhatifError

from fixtures import GiB, build_cache, build_node, build_pod

CONF = load_scheduler_conf(None)


def _run(cache, names=("allocate",)):
    ssn = open_session(cache, CONF.tiers)
    try:
        for name in names:
            get_action(name).execute(ssn)
    finally:
        close_session(ssn)
    cache.flush_binds()


def _probe(qp: QueryPlane, body: dict) -> dict:
    """Submit one request and drive the flush synchronously (the test
    planes run with start_thread=False)."""
    fut = qp.submit(body)
    qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
    return fut.result(timeout=60)


@pytest.fixture
def plane_factory():
    planes = []

    def make(cache, **kw):
        kw.setdefault("start_thread", False)
        qp = QueryPlane(cache, **kw)
        planes.append(qp)
        return qp

    yield make
    for qp in planes:
        qp.close()


# ==========================================================================
# oracle exactness: frozen snapshot — probe answers vs the committed solve
# ==========================================================================


class TestWhatifOracle:
    def _heterogeneous_cache(self):
        """Nodes of varied size with varied running load — scores differ
        per node, so placement is a real decision, not a degenerate tie."""
        nodes = [
            build_node("n0", cpu=8000, mem=16 * GiB),
            build_node("n1", cpu=4000, mem=8 * GiB),
            build_node("n2", cpu=16000, mem=32 * GiB),
            build_node("n3", cpu=8000, mem=16 * GiB),
            build_node("n4", cpu=2000, mem=4 * GiB),
        ]
        pods = [
            build_pod("c1", "r0", "n0", PodPhase.RUNNING,
                      {"cpu": 6000, "memory": 4 * GiB}, group_name="run0"),
            build_pod("c1", "r1", "n2", PodPhase.RUNNING,
                      {"cpu": 2000, "memory": 2 * GiB}, group_name="run0"),
            build_pod("c1", "r2", "n3", PodPhase.RUNNING,
                      {"cpu": 7000, "memory": GiB}, group_name="run1"),
        ]
        return build_cache(
            queues=[Queue(name="default", weight=1)],
            pod_groups=[
                PodGroup(name="run0", namespace="c1", min_member=1,
                         queue="default"),
                PodGroup(name="run1", namespace="c1", min_member=1,
                         queue="default"),
            ],
            nodes=nodes,
            pods=pods,
        )

    def _submit_gang(self, cache, count, requests, *, priority=0,
                     selector=None, min_member=None):
        cache.add_pod_group(PodGroup(
            name="probe-pg", namespace="c1",
            min_member=min_member if min_member is not None else count,
            queue="default",
        ))
        for i in range(count):
            cache.add_pod(build_pod(
                "c1", f"probe-{i}", None, PodPhase.PENDING, dict(requests),
                group_name="probe-pg", priority=priority,
                node_selector=selector or {},
            ))

    def test_feasible_gang_binds_exactly_at_probed_nodes(self, plane_factory):
        cache = self._heterogeneous_cache()
        qp = plane_factory(cache)
        _run(cache)  # publishes the lease for the frozen state
        resp = _probe(qp, {
            "queue": "default", "count": 3,
            "requests": {"cpu": 1500, "memory": 2 * GiB},
        })
        assert resp["feasible"] and resp["committed"]
        assert all(n is not None for n in resp["nodes"])

        # now ACTUALLY submit the same gang and run the real allocate path
        self._submit_gang(cache, 3, {"cpu": 1500, "memory": 2 * GiB})
        _run(cache)
        binds = dict(cache.binder.binds)
        got = [binds[f"c1/probe-{i}"] for i in range(3)]
        assert got == resp["nodes"], (
            "probe promised member->node placement must bind verbatim"
        )

    def test_gang_needing_more_nodes_than_a_pass_has_rounds(
            self, plane_factory):
        """Nine members that take a node each, over nodes whose scores
        differ: every bidder wants the same best node, a node admits one,
        so the gang needs nine rounds where a pass has six.  The pass that
        runs out of rounds carries the six it placed (before: it reverted
        them and failed the gang, in the probe and in the cycle alike), and
        the probe's nodes are the nodes the committed solve then binds."""
        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node(f"h{i}", cpu=6000 + 300 * i, mem=16 * GiB)
                   for i in range(12)],
        )
        qp = plane_factory(cache)
        _run(cache)
        request = {"cpu": 5000, "memory": GiB}
        resp = _probe(qp, {"queue": "default", "count": 9,
                           "requests": request})
        assert resp["feasible"] and resp["committed"]
        assert len(set(resp["nodes"])) == 9
        self._submit_gang(cache, 9, request)
        _run(cache)
        rounds = get_action("allocate").last_solve_rounds
        assert 6 < rounds <= 18, rounds
        binds = dict(cache.binder.binds)
        assert [binds[f"c1/probe-{i}"] for i in range(9)] == resp["nodes"]

    def test_min_available_above_count_cannot_commit(self, plane_factory):
        """min_available > count is a gang that can never reach readiness:
        the commit gate must see the REAL value (no clamp to count), so
        committed is false — matching the real gang discard, which reverts
        exactly such placements and binds nothing."""
        cache = self._heterogeneous_cache()
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {
            "queue": "default", "count": 2, "min_available": 5,
            "requests": {"cpu": 500, "memory": GiB},
        })
        assert not resp["committed"], (
            "a 2-member gang with minAvailable=5 must never probe committed"
        )
        # oracle: the real submission's gang discard binds nothing
        self._submit_gang(cache, 2, {"cpu": 500, "memory": GiB},
                          min_member=5)
        _run(cache)
        assert not any(k.startswith("c1/probe-")
                       for k in dict(cache.binder.binds)), (
            "committed gang discard must revert the under-min placement"
        )

    def test_pure_tie_break_case_matches(self, plane_factory):
        """Identical nodes: placement is decided ENTIRELY by the per-(row,
        node) tie hash — the peek_task_rows row oracle is what makes the
        probe land on the committed solve's nodes."""
        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node(f"t{i}", cpu=8000, mem=16 * GiB)
                   for i in range(6)],
        )
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {
            "queue": "default", "count": 4,
            "requests": {"cpu": 1000, "memory": GiB},
        })
        assert resp["feasible"]
        self._submit_gang(cache, 4, {"cpu": 1000, "memory": GiB})
        _run(cache)
        binds = dict(cache.binder.binds)
        assert [binds[f"c1/probe-{i}"] for i in range(4)] == resp["nodes"]

    def test_infeasible_reason_matches_committed_fit_errors(
            self, plane_factory):
        cache = self._heterogeneous_cache()
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {
            "queue": "default", "count": 1,
            "requests": {"cpu": 1000, "memory": GiB},
            "node_selector": {"zone": "nowhere"},
        })
        assert not resp["feasible"]
        assert resp["unplaced"] == 1

        self._submit_gang(cache, 1, {"cpu": 1000, "memory": GiB},
                          selector={"zone": "nowhere"})
        _run(cache)
        assert "c1/probe-0" not in dict(cache.binder.binds)
        job = next(j for j in cache.jobs.values() if j.name == "probe-pg")
        (fe,) = job.nodes_fit_errors.values()
        committed = dict(fe._hist)
        assert resp["fit_errors"] == committed

    def test_resource_infeasible_reason_matches(self, plane_factory):
        cache = self._heterogeneous_cache()
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {
            "queue": "default", "count": 1,
            "requests": {"cpu": 64000, "memory": GiB},
        })
        assert not resp["feasible"]
        self._submit_gang(cache, 1, {"cpu": 64000, "memory": GiB})
        _run(cache)
        job = next(j for j in cache.jobs.values() if j.name == "probe-pg")
        (fe,) = job.nodes_fit_errors.values()
        committed = dict(fe._hist)
        assert resp["fit_errors"] == committed

    def test_eviction_probe_matches_committed_preempt(self, plane_factory):
        """The high-priority starved-gang scenario (TestPreemptAction):
        the probe's hypothetical eviction set must equal what the real
        preempt action then evicts, and the claim node must match."""
        cache = build_cache(
            queues=["default"],
            pod_groups=[
                PodGroup(name="low", namespace="c1", min_member=1,
                         queue="default"),
            ],
            nodes=[build_node("n1", cpu=2000, mem=4 * GiB, pods=10)],
            pods=[
                build_pod("c1", "low-1", "n1", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, group_name="low"),
                build_pod("c1", "low-2", "n1", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, group_name="low"),
            ],
        )
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {
            "queue": "default", "count": 1, "priority": 100,
            "requests": {"cpu": 1000, "memory": GiB},
            "evictions": True,
        })
        assert not resp["feasible"]  # node is full — no idle placement
        ev = resp["evictions"]
        assert ev["covered"]
        assert ev["claim_nodes"] == ["n1"]
        assert len(ev["victims"]) == 1 and ev["victims"][0].startswith("c1/low-")

        self._submit_gang(cache, 1, {"cpu": 1000, "memory": GiB},
                          priority=100)
        _run(cache, names=("allocate", "preempt"))
        assert sorted(cache.evictor.evicts) == ev["victims"]

    def test_no_eviction_when_gang_would_break(self, plane_factory):
        """gang slack: victims below their job's minAvailable are off
        limits — probe and committed preempt agree on the refusal."""
        cache = build_cache(
            queues=["default"],
            pod_groups=[
                PodGroup(name="low", namespace="c1", min_member=2,
                         queue="default"),
            ],
            nodes=[build_node("n1", cpu=2000, mem=4 * GiB, pods=10)],
            pods=[
                build_pod("c1", "low-1", "n1", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, group_name="low"),
                build_pod("c1", "low-2", "n1", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, group_name="low"),
            ],
        )
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {
            "queue": "default", "count": 1, "priority": 100,
            "requests": {"cpu": 1000, "memory": GiB},
            "evictions": True,
        })
        assert resp["evictions"]["victims"] == []
        assert not resp["evictions"]["covered"]

        self._submit_gang(cache, 1, {"cpu": 1000, "memory": GiB},
                          priority=100)
        _run(cache, names=("allocate", "preempt"))
        assert cache.evictor.evicts == []

    def test_admission_verdict_mirrors_enqueue_capability(
            self, plane_factory):
        cache = self._heterogeneous_cache()
        qp = plane_factory(cache)
        _run(cache)
        ok = _probe(qp, {
            "queue": "default", "count": 1,
            "requests": {"cpu": 100, "memory": GiB},
            "min_resources": {"cpu": 2000, "memory": 2 * GiB},
        })
        assert ok["enqueue_admitted"]
        # cluster total cpu = 38000, ×1.2 = 45600; used = 15000 → idle 30600
        too_big = _probe(qp, {
            "queue": "default", "count": 1,
            "requests": {"cpu": 100, "memory": GiB},
            "min_resources": {"cpu": 99000},
        })
        assert not too_big["enqueue_admitted"]

    def test_idle_and_empty_cluster_still_serve(self, plane_factory):
        """Serving deployments publish a lease even when the cycle has
        nothing to solve — an idle cluster is exactly when capacity
        planning what-ifs arrive."""
        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node("i0", cpu=4000, mem=8 * GiB)],
        )
        qp = plane_factory(cache)
        _run(cache)  # no jobs at all
        resp = _probe(qp, {"queue": "default", "count": 1,
                           "requests": {"cpu": 1000, "memory": GiB}})
        assert resp["feasible"] and resp["nodes"] == ["i0"]
        # a steadily idle cluster republishes only when ingest moves the
        # version — the snapshot rebuild is paid once, not every period
        published = qp.broker.published
        _run(cache)
        assert qp.broker.published == published
        again = _probe(qp, {"queue": "default", "count": 1,
                            "requests": {"cpu": 1000, "memory": GiB}})
        assert again["nodes"] == ["i0"]
        assert again["snapshot_version"] == resp["snapshot_version"]

    def test_request_validation(self, plane_factory):
        cache = self._heterogeneous_cache()
        qp = plane_factory(cache)
        with pytest.raises(WhatifError):
            qp.submit({"count": 0})
        with pytest.raises(WhatifError):
            qp.submit({"count": 10_000})
        with pytest.raises(WhatifError):
            qp.submit({"count": 1, "requests": "not-a-map"})
        with pytest.raises(WhatifError):
            qp.submit({"count": 1, "requests": {"cpu": "abc"}})
        # malformed per-request fields must 400 at submit — never inside
        # the batch flush where they would fail the whole window
        with pytest.raises(WhatifError):
            qp.submit({"count": 1, "priority": "high"})
        with pytest.raises(WhatifError):
            qp.submit({"count": 1, "tolerations": "not-a-list"})
        with pytest.raises(WhatifError):
            qp.submit({"count": 1, "tolerations": [{"bogus": 1}]})
        with pytest.raises(WhatifError):
            qp.submit({"count": 1, "min_resources": {"cpu": "abc"}})
        # i32-overflowing integers must 400 here too — inside the flush
        # they would OverflowError the batch encode and 500 the window
        with pytest.raises(WhatifError):
            qp.submit({"count": 1, "min_available": 2**40})
        with pytest.raises(WhatifError):
            qp.submit({"count": 1, "priority": 2**40})


class TestQueueAdmissionVeto:
    """The queue-state half of the admission verdict: JobEnqueueable
    (plugins/proportion.py) vetoes a gang whose min_resources plus the
    queue's current allocation exceed its Capability — the probe must
    apply the same veto, with the same quanta tolerance, as the
    committed enqueue action."""

    def _capped_cache(self):
        # queue "capped" holds 6000 cpu / 4 GiB of running load against a
        # 10000-cpu capability; the CLUSTER has far more idle than that,
        # so only the queue veto separates the verdicts below
        return build_cache(
            queues=[Queue(name="capped", weight=1,
                          capability={"cpu": 10000.0, "memory": 64 * GiB,
                                      "pods": 16.0})],
            pod_groups=[PodGroup(name="run0", namespace="c1", min_member=1,
                                 queue="capped")],
            nodes=[build_node(f"n{i}", cpu=16000, mem=64 * GiB, pods=64)
                   for i in range(2)],
            pods=[build_pod("c1", "r0", "n0", PodPhase.RUNNING,
                            {"cpu": 6000, "memory": 4 * GiB},
                            group_name="run0")],
        )

    def test_queue_capability_vetoes_over_cap_min_resources(
            self, plane_factory):
        cache = self._capped_cache()
        qp = plane_factory(cache)
        _run(cache)
        base = {"queue": "capped", "count": 1,
                "requests": {"cpu": 100, "memory": GiB}}
        # 6000 allocated + 3000 = 9000 ≤ 10000 → admitted
        under = _probe(qp, dict(base, min_resources={"cpu": 3000}))
        assert under["enqueue_admitted"]
        # 6000 + 8000 = 14000 > 10000 → queue veto, even though the
        # cluster-wide capability gate alone (idle ≈ 32400) would admit
        over = _probe(qp, dict(base, min_resources={"cpu": 8000}))
        assert not over["enqueue_admitted"]
        assert over["feasible"], "the veto is advisory, not a placement gate"

    def test_veto_honors_quanta_tolerance(self, plane_factory):
        """Resource.less_equal admits need−cap below the per-dim quantum
        (MIN_MILLI_CPU = 10); the columnar verdict must agree at the
        boundary."""
        cache = self._capped_cache()
        qp = plane_factory(cache)
        _run(cache)
        base = {"queue": "capped", "count": 1,
                "requests": {"cpu": 100, "memory": GiB}}
        within = _probe(qp, dict(base, min_resources={"cpu": 4005}))
        assert within["enqueue_admitted"]      # need 10005, over by 5 < 10
        beyond = _probe(qp, dict(base, min_resources={"cpu": 4020}))
        assert not beyond["enqueue_admitted"]  # need 10020, over by 20

    def test_unknown_queue_skips_the_veto(self, plane_factory):
        """A queue the snapshot does not know (proportion's attrs map has
        no entry) cannot veto — only the cluster capability gate applies,
        exactly like jobEnqueueableFns finding no attr."""
        cache = self._capped_cache()
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {"queue": "ghost", "count": 1,
                           "requests": {"cpu": 100, "memory": GiB},
                           "min_resources": {"cpu": 8000}})
        assert resp["enqueue_admitted"]

    def test_verdict_mirrors_committed_enqueue_action(self, plane_factory):
        """Probe verdicts vs the real enqueue action on the same state:
        the over-cap gang stays Pending, the under-cap gang goes InQueue —
        matching enqueue_admitted per gang."""
        from kube_batch_tpu.api.types import PodGroupPhase

        cache = self._capped_cache()
        qp = plane_factory(cache)
        _run(cache)
        verdicts = {}
        for name, cpu in (("over", 8000.0), ("under", 3000.0)):
            verdicts[name] = _probe(qp, {
                "queue": "capped", "count": 1,
                "requests": {"cpu": 100, "memory": GiB},
                "min_resources": {"cpu": cpu},
            })["enqueue_admitted"]
            cache.add_pod_group(PodGroup(
                name=name, namespace="c1", min_member=1, queue="capped",
                min_resources={"cpu": cpu}, phase=PodGroupPhase.PENDING,
            ))
            cache.add_pod(build_pod(
                "c1", f"{name}-0", None, PodPhase.PENDING,
                {"cpu": 100, "memory": GiB}, group_name=name))
        assert verdicts == {"over": False, "under": True}
        _run(cache, names=("enqueue",))
        phases = {name: cache.jobs[f"c1/{name}"].pod_group.phase
                  for name in ("over", "under")}
        assert phases["over"] == PodGroupPhase.PENDING
        assert phases["under"] == PodGroupPhase.INQUEUE


class TestPeekTaskRows:
    def test_peek_matches_alloc_order_across_free_and_growth(self):
        """peek(k) must predict alloc() exactly — free-list LIFO first,
        then ascending grown rows — or the probe's tie-hash oracle drifts
        from the rows a submitted gang actually lands on."""
        from kube_batch_tpu.api.columns import _Axis

        ax = _Axis(floor=4)
        for _ in range(2):
            ax.alloc()
        ax.free(0)  # freed row returns LIFO
        want = ax.peek(8)  # crosses the growth boundary (cap=4)
        got = []
        for _ in range(8):
            row = ax.alloc()
            if row is None:  # the ColumnStore growth path
                ax.on_grown(ax.grown_cap())
                row = ax.alloc()
            got.append(row)
        assert want == got


# ==========================================================================
# lease consistency — concurrent with a mutating cycle
# ==========================================================================


def _mk_lease(version, snap="snap"):
    return SnapshotLease(
        snap=snap, meta=None, version=version, config=None,
        evict_config=None, mesh=None, probe_rows=(), queue_rows={},
    )


class TestLeaseBroker:
    def test_stale_publish_ignored(self):
        broker = LeaseBroker()
        broker.publish(_mk_lease(5))
        broker.publish(_mk_lease(3))  # stale publisher — dropped
        assert broker.current().version == 5
        broker.publish(_mk_lease(6))
        assert broker.current().version == 6

    def test_current_times_out_without_publisher(self):
        broker = LeaseBroker()
        t0 = time.monotonic()
        assert broker.current(timeout=0.05) is None
        assert time.monotonic() - t0 < 5

    def test_swap_guard_excludes_dispatch(self):
        """A probe dispatch must never overlap the resident swap — the
        no-torn-read guarantee on donating backends."""
        broker = LeaseBroker()
        broker.publish(_mk_lease(1))
        order = []
        in_swap = threading.Event()
        release = threading.Event()

        def swapper():
            with broker.swap_guard():
                order.append("swap_start")
                in_swap.set()
                release.wait(timeout=5)
                order.append("swap_end")

        t = threading.Thread(target=swapper)
        t.start()
        assert in_swap.wait(timeout=5)
        threading.Timer(0.05, release.set).start()
        with broker.dispatch(timeout=5):
            order.append("dispatch")
        t.join(timeout=5)
        assert order == ["swap_start", "swap_end", "dispatch"]

    def test_swap_guard_retires_lease_on_donating_backends(self, monkeypatch):
        from kube_batch_tpu.serve import lease as lease_mod

        monkeypatch.setattr(lease_mod, "_donation_active", lambda: True)
        broker = LeaseBroker()
        broker.publish(_mk_lease(1))
        with broker.swap_guard():
            assert broker.current() is None  # buffers about to be donated
        assert broker.retired == 1
        broker.publish(_mk_lease(2))
        assert broker.current().version == 2

    def test_swap_guard_keeps_lease_on_cpu(self, monkeypatch):
        from kube_batch_tpu.serve import lease as lease_mod

        monkeypatch.setattr(lease_mod, "_donation_active", lambda: False)
        broker = LeaseBroker()
        broker.publish(_mk_lease(1))
        with broker.swap_guard():
            pass
        assert broker.current().version == 1
        assert broker.retired == 0

    def test_donating_swap_waits_for_inflight_dispatch(self, monkeypatch):
        """A dispatch's device round-trip counts as an in-flight READER:
        a donating swap must wait it out before invalidating the buffers
        (the lock itself is no longer held across the round-trip)."""
        from kube_batch_tpu.serve import lease as lease_mod

        monkeypatch.setattr(lease_mod, "_donation_active", lambda: True)
        broker = LeaseBroker()
        broker.publish(_mk_lease(1))
        order = []
        reading = threading.Event()
        release = threading.Event()

        def reader():
            with broker.dispatch(timeout=5) as lease:
                assert lease is not None
                order.append("read_start")
                reading.set()
                release.wait(timeout=5)
                order.append("read_end")

        t = threading.Thread(target=reader)
        t.start()
        assert reading.wait(timeout=5)
        threading.Timer(0.05, release.set).start()
        with broker.swap_guard():
            order.append("swap")
        t.join(timeout=5)
        assert order == ["read_start", "read_end", "swap"]

    def test_publish_never_blocks_behind_dispatch(self):
        """The broker lock is bookkeeping-only: a publish lands while a
        dispatch's (slow) device round-trip is still in flight."""
        broker = LeaseBroker()
        broker.publish(_mk_lease(1))
        in_read = threading.Event()
        release = threading.Event()

        def reader():
            with broker.dispatch(timeout=5):
                in_read.set()
                release.wait(timeout=5)

        t = threading.Thread(target=reader)
        t.start()
        assert in_read.wait(timeout=5)
        broker.publish(_mk_lease(2))  # must not deadlock behind the reader
        assert broker.current().version == 2
        release.set()
        t.join(timeout=5)

    def test_swap_guard_parks_a_new_flush_and_waits_out_two_readers(
            self, monkeypatch):
        """Two flushes in flight are two readers: a donating swap waits
        for BOTH, and a flush that comes while the swap waits parks behind
        it (the writer has priority: two readers cannot starve the cycle)."""
        from kube_batch_tpu.serve import lease as lease_mod

        monkeypatch.setattr(lease_mod, "_donation_active", lambda: True)
        broker = LeaseBroker()
        broker.publish(_mk_lease(1))
        order = []
        reading = threading.Semaphore(0)
        release = {"r1": threading.Event(), "r2": threading.Event()}

        def reader(name):
            with broker.dispatch(timeout=5) as lease:
                assert lease.version == 1
                reading.release()
                assert release[name].wait(timeout=10)
                order.append(name)

        def swapper():
            with broker.swap_guard():
                order.append("swap")
            broker.publish(_mk_lease(2))

        def late_flush():
            with broker.dispatch(timeout=10) as lease:
                order.append(("late", lease.version))

        readers = [threading.Thread(target=reader, args=(n,))
                   for n in release]
        for t in readers:
            t.start()
        assert reading.acquire(timeout=5) and reading.acquire(timeout=5)
        swap = threading.Thread(target=swapper)
        swap.start()
        deadline = time.monotonic() + 5
        while not broker._swapping and time.monotonic() < deadline:
            time.sleep(0.001)
        assert broker._swapping
        late = threading.Thread(target=late_flush)
        late.start()
        release["r1"].set()
        readers[0].join(timeout=5)
        time.sleep(0.05)
        assert order == ["r1"]  # one reader left: the swap still waits
        release["r2"].set()
        for t in readers + [swap, late]:
            t.join(timeout=10)
            assert not t.is_alive()
        # the parked flush reads what the cycle published after its swap
        assert order == ["r1", "r2", "swap", ("late", 2)]

    def test_a_newer_version_answers_after_every_older_one(self):
        """The order of answers with two flushes in flight: a flush that
        took version 2 waits for every flush that took version 1 and has
        not answered; flushes of one version do not wait for each other."""
        broker = LeaseBroker()
        broker.publish(_mk_lease(1))
        with contextlib.ExitStack() as old, contextlib.ExitStack() as same:
            t_old = old.enter_context(broker.delivery())
            t_same = same.enter_context(broker.delivery())
            for turn in (t_old, t_same):
                with broker.dispatch(timeout=5, turn=turn) as lease:
                    assert lease.version == turn.version == 1
            broker.publish(_mk_lease(2))
            answered = threading.Event()

            def newer():
                with broker.delivery() as turn:
                    with broker.dispatch(timeout=5, turn=turn) as lease:
                        assert lease.version == turn.version == 2
                    turn.wait()
                    answered.set()

            t = threading.Thread(target=newer)
            t.start()
            t_same.wait()  # the same version: returns at once
            t_old.wait()
            assert not answered.wait(timeout=0.1)
            old.close()    # one older flush answered, one still to
            assert not answered.wait(timeout=0.1)
            same.close()
            assert answered.wait(timeout=5)
            t.join(timeout=5)
        assert broker._undelivered == []

    def test_a_flush_that_fails_gives_up_its_turn(self):
        broker = LeaseBroker()
        broker.publish(_mk_lease(1))
        with pytest.raises(RuntimeError):
            with broker.delivery() as turn:
                with broker.dispatch(timeout=5, turn=turn):
                    raise RuntimeError("flush fell over")
        assert broker._undelivered == [] and broker._readers == 0
        broker.publish(_mk_lease(2))
        with broker.delivery() as turn:
            with broker.dispatch(timeout=5, turn=turn):
                pass
            turn.wait()  # nothing older is left to wait for

    def test_a_flush_without_a_lease_has_no_turn_to_wait_for(self):
        broker = LeaseBroker()
        with broker.delivery() as turn:
            with broker.dispatch(timeout=0.01, turn=turn) as lease:
                assert lease is None
            assert turn.version is None and broker._undelivered == []
            turn.wait()


class TestLeaseUnderChurn:
    def test_versions_monotonic_and_answers_valid_under_live_cycles(self):
        """Whatifs served WHILE cycles mutate the cache: every answer
        carries a valid version token, tokens never regress, and every
        response decodes cleanly (no torn snapshot)."""
        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node(f"c{i}", cpu=8000, mem=16 * GiB)
                   for i in range(8)],
        )
        qp = QueryPlane(cache, max_batch=4, window_s=0.001,
                        start_thread=True)
        try:
            _run(cache)
            stop = threading.Event()
            seen: dict = {c: [] for c in range(3)}
            errors: list = []
            delivered: list = []  # a flush's version as it starts to answer
            deliver_all = qp._deliver_all

            def spy(batch, answers):
                delivered.append(answers[0]["snapshot_version"])
                deliver_all(batch, answers)

            qp._deliver_all = spy

            def client(c):
                while not stop.is_set():
                    try:
                        fut = qp.submit({
                            "queue": "default", "count": 2,
                            "requests": {"cpu": 500, "memory": GiB},
                        })
                        resp = fut.result(timeout=30)
                        assert isinstance(resp["feasible"], bool)
                        assert len(resp["nodes"]) == 2
                        seen[c].append(resp["snapshot_version"])
                    except Exception as e:  # noqa: BLE001
                        errors.append(repr(e))
                        return

            # the probe program compiles with the first answer: had the
            # clients started cold, every cycle below would be over before
            # the first flush took its lease
            qp.submit({"queue": "default", "count": 2,
                       "requests": {"cpu": 500, "memory": GiB}}
                      ).result(timeout=120)
            threads = [threading.Thread(target=client, args=(c,))
                       for c in seen]
            for t in threads:
                t.start()
            serial = itertools.count()
            for _ in range(6):  # churning cycles concurrent with serving
                # ...and serving between the cycles: a few flushes answer
                # from each version before the next is published
                n, deadline = len(delivered), time.monotonic() + 10
                while len(delivered) < n + 4 and time.monotonic() < deadline:
                    time.sleep(0.001)
                j = next(serial)
                cache.add_pod_group(PodGroup(
                    name=f"churn{j}", namespace="w", min_member=1,
                    queue="default"))
                cache.add_pod(Pod(
                    name=f"churn{j}-0", namespace="w",
                    requests={"cpu": 250.0, "memory": float(GiB)},
                    annotations={GROUP_NAME_ANNOTATION: f"churn{j}"},
                    phase=PodPhase.PENDING, creation_index=50_000 + j,
                ))
                _run(cache)
            stop.set()
            for t in threads:
                t.join(timeout=60)
            assert not errors, errors
            assert all(seen.values()), "a client never got an answer"
            published = qp.broker.current().version
            # each client's tokens never regress (it appends its own
            # answers in the order it got them), and neither do the
            # flushes' in the order they answered, whichever of the two
            # workers ran them
            for c, versions in seen.items():
                assert versions == sorted(versions), (c, versions)
                assert 0 <= versions[0] and versions[-1] <= published
            assert delivered == sorted(delivered)
            assert len(set(delivered)) >= 6, "cycles did not interleave"
        finally:
            qp.close()

    def test_publish_failure_degrades_serving_not_cycle(self, monkeypatch):
        """A broken query plane must never take the scheduling cycle down
        (the write path outranks serving)."""
        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node("d0", cpu=4000, mem=8 * GiB)],
        )
        qp = QueryPlane(cache, start_thread=False)
        try:
            monkeypatch.setattr(
                qp, "publish_session",
                lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
            )
            cache.add_pod_group(PodGroup(
                name="pg", namespace="c1", min_member=1, queue="default"))
            cache.add_pod(build_pod(
                "c1", "p0", None, PodPhase.PENDING,
                {"cpu": 1000, "memory": GiB}, group_name="pg"))
            _run(cache)  # must not raise
            assert dict(cache.binder.binds)["c1/p0"] == "d0"
        finally:
            qp.close()

    def test_swapping_actions_republish_retired_lease(
            self, plane_factory, monkeypatch):
        """On donating backends EVERY resident swap retires the lease —
        and reclaim/backfill/preempt all swap AFTER allocate publishes.
        Each swapping action must republish right after its dispatch, so a
        full pipeline cycle ends with a LIVE lease instead of leaving
        serving dark until the next cycle's allocate."""
        from kube_batch_tpu.serve import lease as lease_mod

        monkeypatch.setattr(lease_mod, "_donation_active", lambda: True)
        # full node of low-priority RUNNING work + a starved high-priority
        # gang: allocate can't place it, so preempt dispatches its solve
        # (a second resident swap after allocate's publish)
        cache = build_cache(
            queues=["default"],
            pod_groups=[
                PodGroup(name="low", namespace="c1", min_member=1,
                         queue="default"),
                PodGroup(name="hi", namespace="c1", min_member=1,
                         queue="default"),
            ],
            nodes=[build_node("n1", cpu=2000, mem=4 * GiB, pods=10)],
            pods=[
                build_pod("c1", "low-1", "n1", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, group_name="low"),
                build_pod("c1", "low-2", "n1", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, group_name="low"),
                build_pod("c1", "hi-0", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="hi",
                          priority=100),
            ],
        )
        qp = plane_factory(cache)
        _run(cache, names=("enqueue", "reclaim", "allocate", "preempt"))
        # preempt's swap retired allocate's publish... and republished
        assert qp.broker.retired >= 1, "scenario never exercised retirement"
        lease = qp.broker.current()
        assert lease is not None, (
            "query plane left leaseless after the cycle's last swap"
        )
        # ...and the republished lease actually serves (CPU buffers are
        # still valid — only the broker's donation gate was patched)
        resp = _probe(qp, {"queue": "default", "count": 1,
                           "requests": {"cpu": 1000, "memory": GiB}})
        assert resp["snapshot_version"] == lease.version


# ==========================================================================
# micro-batcher — stubbed clock
# ==========================================================================


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def monotonic(self):
        return self.t


class TestMicroBatcher:
    def _mk(self, flushed, **kw):
        clock = FakeClock()
        kw.setdefault("max_batch", 4)
        kw.setdefault("window_s", 0.010)
        kw.setdefault("max_queue", 8)
        b = MicroBatcher(lambda batch: flushed.append(batch), clock=clock,
                        start_thread=False, **kw)
        return b, clock

    def test_deadline_flush(self):
        flushed = []
        b, clock = self._mk(flushed)
        b.submit("r1")
        assert b.tick() == 0          # window not elapsed
        clock.t = 0.009
        assert b.tick() == 0
        clock.t = 0.010               # deadline from FIRST enqueue
        assert b.tick() == 1
        assert [r for r, _f in flushed[0]] == ["r1"]

    def test_bucket_fill_flushes_immediately(self):
        flushed = []
        b, clock = self._mk(flushed)
        for i in range(4):
            b.submit(f"r{i}")
        assert b.tick() == 4          # bucket full — no window wait
        assert b.depth() == 0

    def test_oversize_burst_drains_in_buckets(self):
        flushed = []
        b, clock = self._mk(flushed)
        for i in range(7):
            b.submit(f"r{i}")
        assert b.tick() == 4
        clock.t = 1.0
        assert b.tick() == 3
        assert [len(x) for x in flushed] == [4, 3]

    def test_overflow_rejects_immediately(self):
        flushed = []
        b, clock = self._mk(flushed, max_queue=2)
        f1, f2 = b.submit("a"), b.submit("b")
        f3 = b.submit("c")            # over capacity — shed, don't buffer
        assert isinstance(f3.exception(timeout=1), QueueFull)
        assert b.rejected == 1
        assert not f1.done() and not f2.done()  # accepted, still pending
        clock.t = 1.0
        assert b.tick() == 2

    def test_flush_failure_fails_that_batch_only(self):
        calls = []

        def flaky(batch):
            calls.append(batch)
            if len(calls) == 1:
                raise RuntimeError("dispatch exploded")

        clock = FakeClock()
        b = MicroBatcher(flaky, max_batch=2, window_s=0.01, max_queue=8,
                        clock=clock, start_thread=False)
        f1 = b.submit("a")
        clock.t = 1.0
        b.tick()
        assert isinstance(f1.exception(timeout=1), RuntimeError)
        f2 = b.submit("b")
        clock.t = 2.0
        b.tick()
        assert len(calls) == 2  # the batcher kept serving

    def test_stop_drains_pending_futures(self):
        flushed = []
        clock = FakeClock()
        b = MicroBatcher(lambda batch: flushed.append(batch), max_batch=4,
                        window_s=10.0, max_queue=8, clock=clock,
                        start_thread=True)
        fut = b.submit("late")
        b.stop()
        assert isinstance(fut.exception(timeout=5), QueueFull)
        assert b.submit("after-stop").exception(timeout=1) is not None


class TestTwoWorkers:
    """Two flushes in flight: the batcher's workers on real threads, with a
    flush that blocks until the test lets it go."""

    @staticmethod
    def _blocking(fail=()):
        started, entered = [], threading.Semaphore(0)
        gates: dict = {}

        def flush(batch):
            name = batch[0][0]
            gates.setdefault(name, threading.Event())
            started.append(name)
            entered.release()
            assert gates[name].wait(timeout=30)
            if name in fail:
                raise RuntimeError(f"flush {name} exploded")
            for req, fut in batch:
                fut.set_result(req)

        def release(name):
            gates.setdefault(name, threading.Event()).set()

        return flush, started, entered, release

    def test_a_second_batch_starts_beside_the_first_and_a_third_waits(self):
        flush, started, entered, release = self._blocking()
        b = MicroBatcher(flush, max_batch=1, window_s=0.0, max_queue=8)
        try:
            futs = {n: b.submit(n) for n in "abc"}
            assert entered.acquire(timeout=5) and entered.acquire(timeout=5)
            assert sorted(started) == ["a", "b"]
            # both workers are taken: the third batch is due and waits
            assert not entered.acquire(timeout=0.2)
            assert b.depth() == 1 and not futs["c"].done()
            release("b")
            assert futs["b"].result(timeout=5) == "b"
            assert entered.acquire(timeout=5)
            assert started[2] == "c" and not futs["a"].done()
            release("a")
            release("c")
            assert futs["a"].result(timeout=5) == "a"
            assert futs["c"].result(timeout=5) == "c"
        finally:
            for n in "abc":
                release(n)
            b.stop()

    def test_stop_joins_both_workers_and_fails_what_is_queued(self):
        flush, started, entered, release = self._blocking()
        b = MicroBatcher(flush, max_batch=1, window_s=0.0, max_queue=8)
        futs = {n: b.submit(n) for n in "abcd"}
        assert entered.acquire(timeout=5) and entered.acquire(timeout=5)
        assert len(b._threads) == 2
        stopper = threading.Thread(target=b.stop)
        stopper.start()
        deadline = time.monotonic() + 5
        while not b._stopped and time.monotonic() < deadline:
            time.sleep(0.001)
        release("a")
        release("b")
        stopper.join(timeout=15)
        assert not stopper.is_alive()
        assert not any(t.is_alive() for t in b._threads)
        # in flight at the stop: answered; queued: failed, as with one worker
        assert futs["a"].result(timeout=1) == "a"
        assert futs["b"].result(timeout=1) == "b"
        for n in "cd":
            assert isinstance(futs[n].exception(timeout=1), QueueFull)
        assert sorted(started) == ["a", "b"]  # "c" and "d" never began
        assert b.submit("after-stop").exception(timeout=1) is not None

    def test_a_failing_flush_fails_its_batch_only_beside_another(self):
        flush, started, entered, release = self._blocking(fail=("a",))
        b = MicroBatcher(flush, max_batch=1, window_s=0.0, max_queue=8)
        try:
            futs = {n: b.submit(n) for n in "ab"}
            assert entered.acquire(timeout=5) and entered.acquire(timeout=5)
            release("a")
            assert isinstance(futs["a"].exception(timeout=5), RuntimeError)
            assert not futs["b"].done()  # in flight beside it, untouched
            # the worker whose flush failed keeps serving
            futs["c"] = b.submit("c")
            assert entered.acquire(timeout=5)
            release("c")
            assert futs["c"].result(timeout=5) == "c"
            release("b")
            assert futs["b"].result(timeout=5) == "b"
        finally:
            for n in "abc":
                release(n)
            b.stop()

    def test_tick_stays_synchronous_on_the_callers_thread(self):
        ran = []

        def flush(batch):
            ran.append(threading.get_ident())
            for req, fut in batch:
                fut.set_result(req)

        clock = FakeClock()
        b = MicroBatcher(flush, max_batch=2, window_s=0.01, max_queue=8,
                        clock=clock, start_thread=False)
        assert b._threads == []
        futs = [b.submit(n) for n in "abc"]
        assert b.tick() == 2
        # the flush ran, whole, before tick returned, and on this thread
        assert ran == [threading.get_ident()]
        assert [f.done() for f in futs] == [True, True, False]
        clock.t = 1.0
        assert b.tick() == 1 and futs[2].result(timeout=1) == "c"
        b.stop()


# ==========================================================================
# sharded probe — bit-exact vs single device, both impls
# ==========================================================================


class TestShardedProbe:
    @pytest.fixture(scope="class")
    def frozen(self):
        """A nearly-full cluster with RUNNING load: one allocate cycle
        binds the synthetic gangs, the binds are promoted to RUNNING, and
        the running podgroups relax to min_member=1 so victims carry gang
        slack — without it every gang sits exactly at minAvailable and the
        eviction probe (correctly) refuses every victim."""
        import dataclasses

        from kube_batch_tpu.actions.allocate import (
            build_session_snapshot,
            session_allocate_config,
        )
        from kube_batch_tpu.testing.synthetic import synthetic_cluster

        cache = synthetic_cluster(n_tasks=400, n_nodes=16, gang_size=4,
                                  n_queues=2, seed=11)
        _run(cache)
        for key, node in sorted(cache.binder.binds.items()):
            cache.update_pod(dataclasses.replace(
                cache.pods[key], phase=PodPhase.RUNNING, node_name=node))
        for _uid, job in sorted(cache.jobs.items()):
            if job.pod_group is not None:
                cache.update_pod_group(
                    dataclasses.replace(job.pod_group, min_member=1))
        ssn = open_session(cache, CONF.tiers)
        try:
            snap, meta = build_session_snapshot(ssn)
            config = session_allocate_config(ssn)
        finally:
            close_session(ssn)
        return snap, config

    def _batch(self, snap, seed=0):
        from kube_batch_tpu.ops.probe import ProbeBatch

        rng = np.random.default_rng(seed)
        T, R = snap.task_req.shape
        W = snap.task_sel_bits.shape[1]
        Wt = snap.task_tol_bits.shape[1]
        B, G = 6, 8
        req = np.zeros((B, G, R), np.float32)
        valid = np.zeros((B, G), bool)
        for b in range(B):
            n = int(rng.integers(1, G + 1))
            valid[b, :n] = True
            # mix: small (feasible), large (infeasible), and node-filling
            # (feasible only via eviction) asks
            req[b, :n, 0] = float(rng.choice([250.0, 3000.0, 7500.0]))
            req[b, :n, 1] = float(2 ** 30)
        batch = ProbeBatch(
            req=req, valid=valid,
            min_avail=np.maximum(valid.sum(1), 1).astype(np.int32),
            queue=(np.arange(B) % 2).astype(np.int32),
            prio=np.full(B, 50, np.int32),
            sel_bits=np.zeros((B, W), np.uint32),
            sel_impossible=np.zeros(B, bool),
            tol_bits=np.zeros((B, Wt), np.uint32),
            min_res=np.zeros((B, R), np.float32),
            has_min_res=np.zeros(B, bool),
        )
        rows = np.arange(T, T + G, dtype=np.int32)
        return batch, rows

    @pytest.mark.slow
    def test_sharded_probe_bit_exact_both_impls(self, frozen):
        import jax

        from kube_batch_tpu.ops.eviction import EvictConfig
        from kube_batch_tpu.ops.probe import probe_solve
        from kube_batch_tpu.parallel.mesh import (
            make_mesh,
            program,
            snapshot_shardings,
        )

        snap, config = frozen
        batch, rows = self._batch(snap)
        evc = EvictConfig(mode="preempt", victim_gang=True,
                          victim_conformance=True)
        single = probe_solve(snap, batch, rows, config, evc, True)
        assert bool(np.asarray(single.victims).any()), (
            "fixture must exercise the eviction probe"
        )
        mesh = make_mesh(len(jax.devices()))
        dev = jax.device_put(snap, snapshot_shardings(mesh))
        for impl in ("shard_map", "pjit"):
            fn = program("probe", mesh, impl, config, evict_config=evc,
                         with_evictions=True)
            with mesh:
                res = fn(dev, batch, rows)
            for f in single._fields:
                assert np.array_equal(
                    np.asarray(getattr(single, f)),
                    np.asarray(getattr(res, f)),
                ), (impl, f)

    @pytest.mark.slow
    def test_no_retrace_across_batch_fill(self, frozen):
        from kube_batch_tpu.ops.eviction import EvictConfig
        from kube_batch_tpu.ops.probe import probe_solve
        from kube_batch_tpu.utils import jitstats

        snap, config = frozen
        evc = EvictConfig(mode="preempt")
        b1, rows = self._batch(snap, seed=1)
        probe_solve(snap, b1, rows, config, evc, False)  # warmup
        before = jitstats.compile_counts().get("probe_solve", 0)
        for seed in (2, 3, 4):  # varying fill, same (B, G) buckets
            bn, rows = self._batch(snap, seed=seed)
            probe_solve(snap, bn, rows, config, evc, False)
        after = jitstats.compile_counts().get("probe_solve", 0)
        assert after == before, "probe retraced across batch fill"


# ==========================================================================
# flush partitioning + pre-warm (serving-latency hygiene)
# ==========================================================================


class TestFlushPartitionAndPrewarm:
    def _cache(self):
        return build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node(f"p{i}", cpu=8000, mem=16 * GiB)
                   for i in range(4)],
        )

    def test_mixed_window_splits_by_evictions_flag(self, plane_factory):
        """One --evictions request in a window must not run the eviction
        program for the co-batched plain probes: the flush partitions the
        window into (plain, evictions) sub-dispatches against the SAME
        lease."""
        cache = self._cache()
        qp = plane_factory(cache, max_batch=8)
        _run(cache)
        plain = qp.submit({"queue": "default", "count": 1,
                           "requests": {"cpu": 500, "memory": GiB}})
        evict = qp.submit({"queue": "default", "count": 1,
                           "requests": {"cpu": 500, "memory": GiB},
                           "evictions": True})
        d0 = qp.dispatches
        qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
        r_plain = plain.result(timeout=120)
        r_evict = evict.result(timeout=120)
        assert qp.dispatches == d0 + 2, (
            "mixed window must split into exactly two dispatches"
        )
        assert "evictions" not in r_plain
        assert "evictions" in r_evict
        # both halves answered against the same lease
        assert r_plain["snapshot_version"] == r_evict["snapshot_version"]

    def test_uniform_window_stays_one_dispatch(self, plane_factory):
        cache = self._cache()
        qp = plane_factory(cache, max_batch=8)
        _run(cache)
        futs = [qp.submit({"queue": "default", "count": 1,
                           "requests": {"cpu": 250, "memory": GiB}})
                for _ in range(4)]
        d0 = qp.dispatches
        qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
        for f in futs:
            assert f.result(timeout=120)["feasible"]
        assert qp.dispatches == d0 + 1

    def test_cancelled_futures_skipped_at_flush(self, plane_factory):
        """A handler that times out cancels its future (cmd/server.py):
        the flush must not spend a dispatch on a fully-abandoned window,
        and a partially-abandoned one must not count the abandoned request
        in the verdict counters (it would mask an outage as successes)."""
        cache = self._cache()
        qp = plane_factory(cache, max_batch=8)
        _run(cache)
        # fully abandoned window: no dispatch at all
        f0 = qp.submit({"queue": "default", "count": 1,
                        "requests": {"cpu": 500, "memory": GiB}})
        assert f0.cancel()
        d0 = qp.dispatches
        qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
        assert qp.dispatches == d0, "abandoned window must not dispatch"
        # partially abandoned: live request served, abandoned one uncounted
        gone = qp.submit({"queue": "default", "count": 1,
                          "requests": {"cpu": 500, "memory": GiB}})
        live = qp.submit({"queue": "default", "count": 1,
                          "requests": {"cpu": 500, "memory": GiB}})
        assert gone.cancel()
        served0 = qp.requests_served
        qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
        assert live.result(timeout=120)["feasible"]
        assert qp.requests_served == served0 + 1

    def test_prewarm_compiles_floor_bucket_off_request_path(
            self, plane_factory):
        from kube_batch_tpu.utils import jitstats

        cache = self._cache()
        qp = plane_factory(cache, prewarm=True)
        _run(cache)  # publish kicks the warm thread
        assert qp._warm_threads, "publish must kick a pre-warm thread"
        for t in qp._warm_threads:
            t.join(timeout=300)
        # the warm dispatch compiled the serving floor bucket but stayed
        # out of the serving counters
        assert qp.dispatches == 0
        compiles0 = jitstats.compile_counts().get("probe_solve", 0)
        assert compiles0 >= 1
        # first REAL request rides the warm cache: no retrace
        resp = _probe(qp, {"queue": "default", "count": 2,
                           "requests": {"cpu": 500, "memory": GiB}})
        assert resp["feasible"]
        assert jitstats.compile_counts().get("probe_solve", 0) == compiles0
        # a republish of the same lease shape must not warm again
        lease = qp.broker.current()
        qp._maybe_prewarm(lease)
        assert len(qp._warm_threads) == 1


# ==========================================================================
# the flush plans its points: sweeps' counts ride the plain probes' dispatch
# ==========================================================================


def _flush_window(qp: QueryPlane) -> int:
    """Flush what is queued; the dispatches that took."""
    d0 = qp.dispatches
    qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
    return qp.dispatches - d0


def _gang_buckets(qp: QueryPlane) -> list:
    """The gang bucket G of each dispatch of the last flush."""
    flush = qp.tracer.state()["last_detached"]["whatif:flush"]
    return [c["attrs"]["gang"] for c in flush["children"]
            if c["name"] == "whatif:probe"]


def _reference_sweep(qp: QueryPlane, body: dict):
    """The plain reference: the walk every sweep made for itself before a
    flush planned its points together — grid, bracket, binary search —
    each count asked as a request of its own, alone in its window, at its
    own gang bucket.  Returns (max_fit, probes)."""
    max_count = body["max_count"]
    feasible = {}

    def probe(counts):
        for c in counts:
            feasible[c] = _probe(
                qp, dict(body, count=c, min_available=c))["feasible"]

    grid = sorted({c for c in (1, 2, 4, 8, 16, 32, 64) if c < max_count}
                  | {max_count})
    probe(grid)
    if not feasible[grid[0]]:
        lo = 0
    elif feasible[max_count]:
        lo = max_count
    else:
        lo = max(c for c in grid if feasible[c])
        hi = min(c for c in grid if not feasible[c])
        while hi - lo > 1:
            mid = (lo + hi) // 2
            probe([mid])
            if feasible[mid]:
                lo = mid
            else:
                hi = mid
    return lo, len(feasible)


class TestFlushPlan:
    #: on five 8,000 m nodes a member of 8,000 m fits 5 times (the grid
    #: brackets 4..8: two binary steps), one of 2,000 m 20 times (16..32:
    #: four steps), one of 1,000 m 40 times (32..64: five steps, asked to
    #: 64); 100 m fits to any count asked and 64,000 m never (no step)
    FIT_5 = {"cpu": 8000, "memory": GiB}
    FIT_20 = {"cpu": 2000, "memory": GiB}
    FIT_ALL = {"cpu": 100, "memory": 64 * 2**20}
    FIT_NONE = {"cpu": 64000, "memory": GiB}

    def _plane(self, plane_factory, **kw):
        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node(f"f{i}", cpu=8000, mem=64 * GiB)
                   for i in range(5)],
            pods=[build_pod("c1", "low", "f0", PodPhase.RUNNING,
                            {"cpu": 10, "memory": 2**20}, group_name="run")],
            pod_groups=[PodGroup(name="run", namespace="c1", min_member=1,
                                 queue="default")],
        )
        qp = plane_factory(cache, **kw)
        _run(cache)
        return qp

    @staticmethod
    def _plain(requests, count=1, **kw):
        return dict({"queue": "default", "count": count,
                     "requests": dict(requests)}, **kw)

    @staticmethod
    def _sweep(requests, max_count=64):
        return {"queue": "default", "max_count": max_count,
                "requests": dict(requests)}

    def test_an_answer_does_not_depend_on_lane_chunk_or_gang_bucket(
            self, plane_factory):
        """The same seeded requests answered alone, each at its own gang
        bucket, and as lanes of mixed dispatches at G = 64 beside sweeps'
        counts, in two lane orders: byte-identical responses; and every
        sweep's max_fit and probes are what the per-sweep walk gives."""
        cache = TestWhatifOracle()._heterogeneous_cache()
        qp = plane_factory(cache)
        _run(cache)
        rng = np.random.default_rng(45)
        bodies = [
            self._plain(
                {"cpu": int(rng.choice([250, 500, 1000, 2000, 3000, 9000])),
                 "memory": int(rng.choice([1, 2, 4])) * GiB},
                count=int(rng.integers(1, 9)),
                priority=int(rng.integers(0, 3)))
            for _ in range(8)
        ]
        sweeps = [self._sweep({"cpu": 1000, "memory": GiB}),
                  self._sweep({"cpu": 3000, "memory": 2 * GiB}, 48)]
        alone = []
        for b in bodies:
            alone.append(json.dumps(_probe(qp, b), sort_keys=True))
            assert _gang_buckets(qp) == [8]
        walked = [_reference_sweep(qp, b) for b in sweeps]
        assert {fit for fit, _ in walked} - {0, 48, 64}, (
            "the fixture must put a boundary inside a grid gap")
        for order in (bodies, bodies[::-1]):
            futs = [qp.submit(b) for b in order[:4]]
            swept = [qp.submit_sweep(b) for b in sweeps]
            futs += [qp.submit(b) for b in order[4:]]
            # 8 + 7 + 7 points: two chunks, a sweep's 64 or 48 in each, then
            # the steps of the sweeps whose boundary lies in a grid gap
            assert _flush_window(qp) >= 2
            assert _gang_buckets(qp)[:2] == [64, 64]
            mixed = [json.dumps(f.result(timeout=120), sort_keys=True)
                     for f in futs]
            expect = alone if order is bodies else alone[::-1]
            assert mixed == expect
            for fut, (fit, probes) in zip(swept, walked):
                resp = fut.result(timeout=120)
                assert (resp["max_fit"], resp["probes"]) == (fit, probes)
                assert resp["feasible"] == (fit >= 1)

    def test_grid_points_ride_the_plain_dispatch(self, plane_factory):
        qp = self._plane(plane_factory)
        p0 = qp.points
        futs = [qp.submit(self._plain(self.FIT_20, count=c))
                for c in (1, 3, 8)]
        sweep = qp.submit_sweep(self._sweep(self.FIT_ALL))
        assert _flush_window(qp) == 1
        assert qp.points - p0 == 3 + 7
        assert all(f.result(timeout=120)["feasible"] for f in futs)
        assert sweep.result(timeout=120)["max_fit"] == 64
        assert sweep.result()["probes"] == 7

    def test_sweeps_refine_together_one_dispatch_a_step(self, plane_factory):
        qp = self._plane(plane_factory)
        p0 = qp.points
        short = qp.submit_sweep(self._sweep(self.FIT_5))
        long_ = qp.submit_sweep(self._sweep(self.FIT_20))
        # the grid, then four steps: the short sweep rides the first two
        assert _flush_window(qp) == 1 + 4
        assert qp.points - p0 == 2 * 7 + 2 + 4
        assert short.result(timeout=120)["max_fit"] == 5
        assert short.result()["probes"] == 7 + 2
        assert long_.result(timeout=120)["max_fit"] == 20
        assert long_.result()["probes"] == 7 + 4
        for fut, body in ((short, self.FIT_5), (long_, self.FIT_20)):
            assert _reference_sweep(qp, self._sweep(body)) == (
                fut.result()["max_fit"], fut.result()["probes"])

    def test_evictions_keep_a_dispatch_of_their_own(self, plane_factory):
        qp = self._plane(plane_factory)
        plain = qp.submit(self._plain(self.FIT_20, count=2))
        sweep = qp.submit_sweep(self._sweep(self.FIT_NONE))
        evict = qp.submit(self._plain(self.FIT_20, evictions=True))
        assert _flush_window(qp) == 2
        assert "evictions" not in plain.result(timeout=120)
        assert "evictions" in evict.result(timeout=120)
        assert sweep.result(timeout=120)["max_fit"] == 0

    def test_points_past_the_bucket_spill_into_a_second_chunk(
            self, plane_factory):
        qp = self._plane(plane_factory)
        p0 = qp.points
        futs = [qp.submit_sweep(self._sweep(self.FIT_ALL)),
                qp.submit(self._plain(self.FIT_5)),
                qp.submit_sweep(self._sweep(self.FIT_NONE)),
                qp.submit_sweep(self._sweep(self.FIT_ALL, 16))]
        assert 7 + 1 + 7 + 5 > qp.batcher.max_batch
        assert _flush_window(qp) == 2
        assert qp.points - p0 == 20
        fits = [f.result(timeout=120) for f in futs]
        assert [r.get("max_fit") for r in fits] == [64, None, 0, 16]
        assert fits[1]["feasible"]

    def test_a_mixed_flush_answers_against_one_lease(self, plane_factory):
        """Plain, sweep (with refinement steps) and evictions of one window:
        one entry into the broker, one snapshot_version on every response,
        whatever the cache does between the dispatches."""
        qp = self._plane(plane_factory)
        entered = []
        dispatch = qp.broker.dispatch

        def counted(**kw):
            entered.append(kw)
            return dispatch(**kw)

        qp.broker.dispatch = counted
        probe = qp._probe

        def probe_then_ingest(lease, reqs, **kw):
            # the cluster moves on under the flush: a lease published
            # mid-window must not answer the window's later dispatches
            qp.cache.add_node(build_node(f"late{qp.dispatches}", cpu=8000))
            return probe(lease, reqs, **kw)

        qp._probe = probe_then_ingest
        version = qp.broker.current().version
        futs = [qp.submit(self._plain(self.FIT_20, count=4)),
                qp.submit_sweep(self._sweep(self.FIT_5)),
                qp.submit(self._plain(self.FIT_20, evictions=True)),
                qp.submit(self._plain(self.FIT_NONE))]
        assert _flush_window(qp) == 2 + 2
        assert len(entered) == 1
        resps = [f.result(timeout=120) for f in futs]
        assert {r["snapshot_version"] for r in resps} == {version}
        assert {r["staleness"]["version"] for r in resps} == {version}
        assert resps[1]["max_fit"] == 5

    @pytest.mark.parametrize("failing, failed", [
        # lanes 0-15: plain 0, sweeps 1 and 2, the first count of sweep 3;
        # the second chunk: the rest of sweep 3's grid and plain 4
        (1, {0, 1, 2, 3}),
        (2, {3, 4}),
        # the evictions request's own dispatch
        (3, {5}),
        # the refinement step: sweep 2 alone has a boundary to find
        (4, {2}),
    ])
    def test_a_failing_chunk_fails_only_its_own_requests(
            self, plane_factory, failing, failed):
        qp = self._plane(plane_factory)
        probe = qp._probe
        calls = []

        def flaky(lease, reqs, **kw):
            calls.append(len(reqs))
            if len(calls) == failing:
                raise RuntimeError("device fell over")
            return probe(lease, reqs, **kw)

        qp._probe = flaky
        futs = [qp.submit(self._plain(self.FIT_20)),
                qp.submit_sweep(self._sweep(self.FIT_ALL)),
                qp.submit_sweep(self._sweep(self.FIT_5)),
                qp.submit_sweep(self._sweep(self.FIT_NONE)),
                qp.submit(self._plain(self.FIT_20, count=2)),
                qp.submit(self._plain(self.FIT_20, evictions=True))]
        served0 = qp.requests_served
        _flush_window(qp)
        # sweep 2's steps stop with the chunk that failed it
        assert calls[:3] == [16, 7, 1]
        assert len(calls) == {1: 3, 4: 4}.get(failing, 5)
        for i, fut in enumerate(futs):
            if i in failed:
                with pytest.raises(WhatifError) as err:
                    fut.result(timeout=120)
                assert err.value.status == 500
                assert "device fell over" in str(err.value)
            else:
                resp = fut.result(timeout=120)
                assert resp["snapshot_version"] >= 0
                if i == 2:
                    assert (resp["max_fit"], resp["probes"]) == (5, 9)
        assert qp.requests_served - served0 == len(futs) - len(failed)


# ==========================================================================
# two flushes in flight: the plane under the batcher's two workers
# ==========================================================================


class TestTwoFlushesInFlight:
    BODY = {"queue": "default", "count": 1,
            "requests": {"cpu": 500, "memory": GiB}}
    #: the request whose probe the test holds on the "device": by its count
    HELD = dict(BODY, count=3)

    def _plane(self, plane_factory, **kw):
        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node(f"t{i}", cpu=8000, mem=16 * GiB)
                   for i in range(4)],
        )
        kw.setdefault("max_batch", 1)
        kw.setdefault("window_s", 0.0)
        qp = plane_factory(cache, start_thread=True, **kw)
        _run(cache)
        # compile the probe before any thread waits on one
        qp.submit(self.BODY).result(timeout=120)
        qp.submit(self.HELD).result(timeout=120)
        return qp

    @staticmethod
    def _hold_probes(qp, held_counts=(3,)):
        """Probes of a request with a count in ``held_counts`` wait, after
        their program ran, until the test lets them go: a flush held on the
        device while the other worker goes on."""
        probe, entered = qp._probe, threading.Semaphore(0)
        go = threading.Event()

        def held(lease, reqs, **kw):
            host = probe(lease, reqs, **kw)
            if reqs[0]["count"] in held_counts:
                entered.release()
                assert go.wait(timeout=30)
            return host

        qp._probe = held
        return entered, go

    @staticmethod
    def _flush_attrs(qp):
        """The attributes every flush span from now on begins with."""
        seen, traced = [], qp._flush_traced

        def spy(batch, sp):
            seen.append(dict(sp.attrs))
            traced(batch, sp)

        qp._flush_traced = spy
        return seen

    def test_one_client_never_overlaps_and_two_held_flushes_do(
            self, plane_factory):
        from kube_batch_tpu.metrics import metrics as prom

        qp = self._plane(plane_factory)
        attrs = self._flush_attrs(qp)
        overlapped0 = prom.WHATIF_FLUSHES_OVERLAPPED._values.get((), 0.0)
        flushes0 = prom.WHATIF_BATCH_SIZE._count[()]

        def grown():
            return (prom.WHATIF_FLUSHES_OVERLAPPED._values.get((), 0.0)
                    - overlapped0,
                    prom.WHATIF_BATCH_SIZE._count[()] - flushes0)

        # one client, one request at a time: a flush begins alone
        for _ in range(5):
            assert qp.submit(self.BODY).result(timeout=30)["feasible"]
        assert grown() == (0, 5)
        assert [a["in_flight"] for a in attrs] == [1] * 5
        # two flushes held on the device at once: the second began beside
        # the first, and a third waits for a worker
        entered, go = self._hold_probes(qp, held_counts=(3, 4))
        futs = [qp.submit(self.HELD), qp.submit(dict(self.HELD, count=4))]
        assert entered.acquire(timeout=30) and entered.acquire(timeout=30)
        third = qp.submit(self.BODY)
        time.sleep(0.1)
        assert grown() == (1, 7) and qp.batcher.depth() == 1
        assert sorted(a["in_flight"] for a in attrs[5:]) == [1, 2]
        assert qp._in_flight == 2
        go.set()
        for f in futs + [third]:
            assert f.result(timeout=30)["feasible"]
        deadline = time.monotonic() + 5
        while qp._in_flight and time.monotonic() < deadline:
            time.sleep(0.001)
        assert qp._in_flight == 0 and grown()[1] == 8
        # the flush's span says so on /v1/trace
        flush = qp.tracer.state()["last_detached"]["whatif:flush"]
        assert flush["attrs"]["in_flight"] in (1, 2)
        assert "volcano_whatif_flushes_overlapped_total{} " in (
            prom.render_prometheus())

    @pytest.mark.parametrize("newer", [True, False])
    def test_an_older_lease_answers_first_and_an_equal_one_does_not_wait(
            self, plane_factory, newer):
        """Flush 1 takes version v and is held on the device.  Flush 2
        takes a NEWER version (a cycle published meanwhile: on CPU the old
        lease keeps serving, nothing waits for its reader) and is ready
        first: it answers only after flush 1.  With the SAME version it
        answers at once, while flush 1 is still held."""
        qp = self._plane(plane_factory)
        entered, go = self._hold_probes(qp)
        order = []
        first = qp.submit(self.HELD)
        first.add_done_callback(lambda f: order.append("first"))
        assert entered.acquire(timeout=30)
        v = qp.broker.current().version
        if newer:
            qp.cache.add_node(build_node("late", cpu=8000, mem=16 * GiB))
            _run(qp.cache)
            assert qp.broker.current().version > v
        second = qp.submit(self.BODY)
        second.add_done_callback(lambda f: order.append("second"))
        if newer:
            with pytest.raises(FutureTimeout):
                second.result(timeout=0.3)
            assert order == [] and not second.done()
        else:
            assert second.result(timeout=30)["snapshot_version"] == v
            assert order == ["second"] and not first.done()
        go.set()
        r1, r2 = first.result(timeout=30), second.result(timeout=30)
        assert r1["snapshot_version"] == v
        if newer:
            assert order == ["first", "second"]
            # each answered from the lease ITS flush held
            assert r2["snapshot_version"] == qp.broker.current().version > v
            assert r2["staleness"]["version"] == r2["snapshot_version"]

    def test_the_planes_totals_are_exact_after_concurrent_flushes(
            self, plane_factory):
        """Eight clients against two workers, the interpreter switching
        threads every 10 us: every flush, dispatch, point and answer is
        counted once (a lost update would leave a total short)."""
        import sys

        from kube_batch_tpu.metrics import metrics as prom

        qp = self._plane(plane_factory, max_batch=4, window_s=0.0005)
        attrs = self._flush_attrs(qp)
        before = (qp.flushes, qp.dispatches, qp.points, qp.requests_served,
                  prom.WHATIF_BATCH_SIZE._count[()],
                  prom.WHATIF_DISPATCHES._values.get((), 0.0),
                  prom.WHATIF_DISPATCH_POINTS._values.get((), 0.0))
        clients, each = 8, 25
        errors: list = []

        def client():
            try:
                for _ in range(each):
                    assert qp.submit(self.BODY).result(timeout=60)["feasible"]
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=client)
                       for _ in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(interval)
        assert not errors, errors
        deadline = time.monotonic() + 5
        while qp._in_flight and time.monotonic() < deadline:
            time.sleep(0.001)
        total = clients * each
        flushes = len(attrs)
        assert sum(a["batch"] for a in attrs) == total
        assert len({a["seq"] for a in attrs}) == flushes
        assert any(a["in_flight"] == 2 for a in attrs)
        assert all(a["in_flight"] in (1, 2) for a in attrs)
        after = (qp.flushes, qp.dispatches, qp.points, qp.requests_served,
                 prom.WHATIF_BATCH_SIZE._count[()],
                 prom.WHATIF_DISPATCHES._values.get((), 0.0),
                 prom.WHATIF_DISPATCH_POINTS._values.get((), 0.0))
        # a plain request is one point and a flush of up to four one dispatch
        assert [a - b for a, b in zip(after, before)] == [
            flushes, flushes, total, total, flushes, flushes, total]

    def test_a_failing_flush_fails_its_own_batch_beside_a_held_one(
            self, plane_factory):
        qp = self._plane(plane_factory)
        entered, go = self._hold_probes(qp)
        held = qp.submit(self.HELD)
        assert entered.acquire(timeout=30)
        staleness = qp._staleness
        qp._staleness = lambda lease: (_ for _ in ()).throw(
            RuntimeError("flush fell over"))
        failed = qp.submit(self.BODY)
        assert isinstance(failed.exception(timeout=30), RuntimeError)
        qp._staleness = staleness
        # it gave up its reader and its turn: the held flush and the next
        # one answer, and nothing is left registered
        assert not held.done()
        assert qp.submit(self.BODY).result(timeout=30)["feasible"]
        go.set()
        assert held.result(timeout=30)["feasible"]
        deadline = time.monotonic() + 5
        while qp._in_flight and time.monotonic() < deadline:
            time.sleep(0.001)
        assert qp._in_flight == 0
        assert qp.broker._undelivered == [] and qp.broker._readers == 0


# ==========================================================================
# HTTP surface — POST /v1/whatif + metrics counters
# ==========================================================================


class TestWhatifHTTP:
    def _post(self, port, body, path="/v1/whatif"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(body).encode(), method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    def test_end_to_end_with_metrics(self):
        from urllib.error import HTTPError

        from kube_batch_tpu.cmd.server import AdminServer
        from kube_batch_tpu.metrics import metrics as M

        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node(f"h{i}", cpu=8000, mem=16 * GiB)
                   for i in range(4)],
        )
        # generous dispatch timeout: the handler's future wait is keyed to
        # it, and the FIRST probe at this (B, G) bucket pays a cold compile
        qp = QueryPlane(cache, max_batch=8, window_s=0.002,
                        dispatch_timeout=90, start_thread=True)
        srv = AdminServer(cache, port=0, query_plane=qp)
        srv.start()
        try:
            _run(cache)
            req0 = sum(M.WHATIF_REQUESTS._values.values())
            disp0 = sum(M.WHATIF_DISPATCHES._values.values())
            ok = self._post(srv.port, {
                "queue": "default", "count": 2,
                "requests": {"cpu": 1000, "memory": GiB},
            })
            assert ok["feasible"] and len(ok["nodes"]) == 2
            bad = self._post(srv.port, {
                "queue": "default", "count": 2,
                "requests": {"cpu": 990000, "memory": GiB},
            })
            assert not bad["feasible"] and bad["fit_errors"]
            assert ok["snapshot_version"] == bad["snapshot_version"]

            with pytest.raises(HTTPError) as err:
                self._post(srv.port, {"count": -2})
            assert err.value.code == 400

            assert sum(M.WHATIF_REQUESTS._values.values()) == req0 + 2
            assert sum(M.WHATIF_DISPATCHES._values.values()) > disp0
            rendered = M.render_prometheus()
            assert "volcano_whatif_requests_total" in rendered
            assert "volcano_whatif_batch_size" in rendered
        finally:
            srv.stop()
            qp.close()

    def test_503_when_plane_missing_or_cold(self):
        from urllib.error import HTTPError

        from kube_batch_tpu.cmd.server import AdminServer

        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node("x0", cpu=4000, mem=8 * GiB)],
        )
        srv = AdminServer(cache, port=0)  # no query plane wired
        srv.start()
        try:
            with pytest.raises(HTTPError) as err:
                self._post(srv.port, {"count": 1, "requests": {"cpu": 1}})
            assert err.value.code == 503
        finally:
            srv.stop()

        qp = QueryPlane(cache, start_thread=True, dispatch_timeout=0.05)
        srv = AdminServer(cache, port=0, query_plane=qp)
        srv.start()
        try:
            # no cycle has run — no lease published yet
            with pytest.raises(HTTPError) as err:
                self._post(srv.port, {"count": 1, "requests": {"cpu": 1}})
            assert err.value.code == 503
        finally:
            srv.stop()
            qp.close()


# ==========================================================================
# the re-arm at the commit: a cycle leaves the lease on the state it left
# ==========================================================================


class TestLeaseRearm:
    """A pipelined cycle's last act under its session is to publish the
    lease again on the state its binds left, stamped with the version the
    cache has as the session hands it back: what-ifs see a commit at once,
    not an idle tick later."""

    BIG = {"cpu": 6000, "memory": 2 * GiB}

    def _cluster(self, n_nodes):
        """One node that holds a ``BIG`` pod, and n - 1 that never could."""
        nodes = [build_node("big", cpu=8000, mem=16 * GiB)] + [
            build_node(f"s{i}", cpu=1000, mem=2 * GiB)
            for i in range(n_nodes - 1)]
        return build_cache(queues=[Queue(name="default", weight=1)],
                           nodes=nodes)

    def _big_pod(self, cache, name):
        cache.add_pod_group(PodGroup(
            name=name, namespace="c1", min_member=1, queue="default"))
        cache.add_pod(build_pod("c1", name, None, PodPhase.PENDING,
                                dict(self.BIG), group_name=name))

    @staticmethod
    def _rearms():
        from kube_batch_tpu.metrics import metrics as m

        return {k[0]: v for k, v in m.LEASE_REARMS._values.items()}

    @pytest.mark.parametrize("n_nodes", [
        pytest.param(3, id="one-device"),
        # 160 nodes pad to the 256 at which the 8-device test mesh shards
        pytest.param(160, id="mesh"),
    ])
    def test_a_probe_sees_the_commit_at_once(self, plane_factory, n_nodes):
        from kube_batch_tpu.scheduler import Scheduler

        cache = self._cluster(n_nodes)
        qp = plane_factory(cache)
        sched = Scheduler(cache, conf=CONF)
        try:
            sched.run_once_pipelined()  # an empty cluster's lease
            sched.drain_pipeline()
            ask = {"queue": "default", "count": 1, "requests": self.BIG}
            before = _probe(qp, ask)
            assert before["feasible"] and before["nodes"] == ["big"]
            self._big_pod(cache, "p0")
            rearms0 = self._rearms()
            sched.run_once_pipelined()  # binds p0 where the probe said
            sched.drain_pipeline()
            assert dict(cache.binder.binds) == {"c1/p0": "big"}
            # no tick and no second cycle: the capacity is gone already
            after = _probe(qp, ask)
            assert after["feasible"] is False, (
                "the lease still shows the node as it was before the bind")
            lease = qp.broker.current()
            assert after["snapshot_version"] == lease.version
            assert lease.version == cache.last_close_version \
                == cache.dirty.version > cache.last_open_version
            assert (lease.mesh is not None) == (n_nodes >= 129)
            assert self._rearms()["published"] == rearms0["published"] + 1
            # and the tick after it owes nothing
            assert sched.run_once_pipelined("floor") is False
        finally:
            sched.close()

    def test_a_bind_that_moves_no_status_waits_for_the_next_ingest(
            self, plane_factory):
        """What the re-arm does NOT cover, pinned so that nobody reads more
        into it: bind decisions stamp nothing by themselves, so a pod bound
        into a gang that is already Running (min_member long met) leaves
        the tracker where the open found it, the re-arm reads ``not_owed``
        and the lease shows the node as it was, as before the re-arm
        existed, until the next ingest (in a cluster: the kubelet's Running
        update) starts a cycle that publishes from its open."""
        from kube_batch_tpu.scheduler import Scheduler
        from kube_batch_tpu.sim import kubelet as kl

        cache = self._cluster(3)
        qp = plane_factory(cache)
        sched = Scheduler(cache, conf=CONF)
        try:
            cache.add_pod_group(PodGroup(
                name="pg", namespace="c1", min_member=1, queue="default"))
            cache.add_pod(build_pod(
                "c1", "small", None, PodPhase.PENDING,
                {"cpu": 500, "memory": GiB}, group_name="pg"))
            sched.run_once_pipelined()
            sched.drain_pipeline()
            assert dict(cache.binder.binds) == {"c1/small": "big"}
            cache.add_pod(build_pod("c1", "p1", None, PodPhase.PENDING,
                                    dict(self.BIG), group_name="pg"))
            rearms0 = self._rearms()
            sched.run_once_pipelined()
            sched.drain_pipeline()
            assert dict(cache.binder.binds)["c1/p1"] == "big"
            assert cache.last_close_version == cache.last_open_version
            assert self._rearms()["not_owed"] == rearms0["not_owed"] + 1
            ask = {"queue": "default", "count": 1, "requests": self.BIG}
            assert _probe(qp, ask)["feasible"] is True  # the gap
            kl.set_running(cache, "c1/p1", "big")
            sched.run_once_pipelined()
            sched.drain_pipeline()
            assert _probe(qp, ask)["feasible"] is False
        finally:
            sched.close()

    def test_a_pending_ingest_signal_skips_the_rearm(self, plane_factory):
        """The cycle that signal starts at once publishes from its own
        open, as every cycle did before: nothing is lost but one publish."""
        from kube_batch_tpu.scheduler import Scheduler

        cache = self._cluster(3)
        qp = plane_factory(cache)
        sched = Scheduler(cache, conf=CONF)
        cache.set_ingest_signal(sched.trigger.notify)
        try:
            self._big_pod(cache, "p0")  # signals; nothing consumes it
            assert sched.trigger.ingest_pending()
            rearms0 = self._rearms()
            sched.run_once_pipelined()
            sched.drain_pipeline()
            assert dict(cache.binder.binds) == {"c1/p0": "big"}
            assert self._rearms()["ingest_pending"] == \
                rearms0["ingest_pending"] + 1
            stale = qp.broker.current()
            assert stale.version == cache.last_open_version \
                < cache.last_close_version
            assert sched.trigger.poll()  # the loop's wake takes the signal
            sched.run_once_pipelined()   # the cycle it starts
            sched.drain_pipeline()
            lease = qp.broker.current()
            assert lease.version == cache.last_close_version \
                == cache.dirty.version
            after = _probe(qp, {"queue": "default", "count": 1,
                                "requests": self.BIG})
            assert after["feasible"] is False
            grown = {k: v - rearms0[k] for k, v in self._rearms().items()}
            assert grown == {"published": 0.0, "ingest_pending": 1.0,
                             "not_owed": 1.0}
        finally:
            cache.set_ingest_signal(None)
            sched.close()


# ==========================================================================
# verdict honesty: per-response `unmodeled: [...]` (guard-plane PR satellite)
# ==========================================================================


class TestUnmodeledHonesty:
    """Probe verdicts whose conf carries preempt gates the eviction probe
    does not model (drf/proportion victim gates), or whose gang only the
    backfill path could bind (all-BestEffort), must say so PER RESPONSE —
    a one-shot process log is invisible to the client that needs it."""

    DRF_TIER1_CONF = """
    actions: "enqueue, reclaim, allocate, backfill, preempt"
    tiers:
    - plugins:
      - name: priority
      - name: gang
      - name: conformance
      - name: drf
    - plugins:
      - name: predicates
      - name: proportion
      - name: nodeorder
    """

    def _cache(self):
        return build_cache(
            queues=[Queue(name="default", weight=1)],
            pod_groups=[],
            nodes=[build_node("n0", cpu=8000, mem=16 * GiB)],
            pods=[],
        )

    def _run_conf(self, cache, conf_text):
        import textwrap

        from kube_batch_tpu.framework.conf import parse_scheduler_conf

        conf = parse_scheduler_conf(textwrap.dedent(conf_text))
        ssn = open_session(cache, conf.tiers)
        try:
            get_action("allocate").execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()

    def test_shipped_conf_plain_probe_has_empty_unmodeled(self, plane_factory):
        cache = self._cache()
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {"queue": "default", "count": 1,
                           "requests": {"cpu": 1000, "memory": GiB}})
        assert resp["unmodeled"] == []

    def test_shipped_conf_eviction_probe_has_empty_unmodeled(
        self, plane_factory
    ):
        # the shipped conf's first voting preempt tier is gang+conformance
        # — fully modeled, so the field stays empty even with evictions on
        cache = self._cache()
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {"queue": "default", "count": 1,
                           "requests": {"cpu": 1000, "memory": GiB},
                           "evictions": True})
        assert resp["unmodeled"] == []

    def test_drf_victim_gate_reported_on_eviction_probes_only(
        self, plane_factory
    ):
        cache = self._cache()
        qp = plane_factory(cache)
        self._run_conf(cache, self.DRF_TIER1_CONF)
        lease = qp.broker.current()
        assert lease.unmodeled_gates == ("drf",)
        with_ev = _probe(qp, {"queue": "default", "count": 1,
                              "requests": {"cpu": 1000, "memory": GiB},
                              "evictions": True})
        assert any("drf" in gap for gap in with_ev["unmodeled"])
        plain = _probe(qp, {"queue": "default", "count": 1,
                            "requests": {"cpu": 1000, "memory": GiB}})
        # the gate only affects eviction answers — plain probes stay clean
        assert plain["unmodeled"] == []

    def test_all_best_effort_gang_reports_backfill_gap(self, plane_factory):
        cache = self._cache()
        qp = plane_factory(cache)
        _run(cache)
        resp = _probe(qp, {"queue": "default", "count": 2, "requests": {}})
        assert resp["feasible"] is False  # documented probe scope
        assert any("backfill" in gap.lower() for gap in resp["unmodeled"])

    def test_cli_render_surfaces_unmodeled(self):
        from kube_batch_tpu.cli.whatif import _render

        out = _render({
            "feasible": False, "snapshot_version": 7, "nodes": [None],
            "unmodeled": ["preempt victim gate 'drf' (conf tier) is not "
                          "modeled by the eviction probe"],
        })
        assert "! unmodeled:" in out and "drf" in out

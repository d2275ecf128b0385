"""ColumnStore consistency: the persistent columnar host model must agree
with the object model after ingest, scheduling cycles, evictions, churn,
and axis growth (api/columns.py check_consistency)."""

import numpy as np

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION, Node, Pod, PodGroup, PriorityClass
from kube_batch_tpu.api.types import PodPhase, TaskStatus
from kube_batch_tpu.framework.conf import parse_scheduler_conf
from kube_batch_tpu.scheduler import Scheduler

from tests.fixtures import GiB, build_cache, build_node, build_pod

FULL_CONF = """
actions: "enqueue, reclaim, allocate, backfill, preempt"
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
"""


def _soak_add_gang(cache, rng, next_id, queues=("default",),
                   cpu_choices=(250, 500, 1000), prio_choices=(0,)):
    """Shared gang generator for the churn soaks: a random-size PodGroup in
    a random queue with random per-task cpu and priority."""
    g = next_id[0]
    next_id[0] += 1
    size = int(rng.integers(1, 4))
    queue = queues[int(rng.integers(len(queues)))]
    cache.add_pod_group(PodGroup(
        name=f"g{g}", namespace="c", min_member=size, queue=queue,
        creation_index=g,
    ))
    prio = int(rng.choice(prio_choices))
    for i in range(size):
        cache.add_pod(Pod(
            name=f"g{g}-{i}", namespace="c",
            requests={"cpu": float(rng.choice(cpu_choices)),
                      "memory": float(GiB)},
            annotations={GROUP_NAME_ANNOTATION: f"g{g}"},
            priority=prio,
            creation_index=g * 10 + i,
        ))


def assert_consistent(cache):
    errs = cache.columns.check_consistency(cache)
    assert not errs, "\n".join(errs)


class TestColumnConsistency:
    def test_ingest_and_cycle(self):
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg1", namespace="c1", min_member=3, queue="default")],
            nodes=[build_node("n1", cpu=4000, mem=8 * GiB),
                   build_node("n2", cpu=4000, mem=8 * GiB)],
            pods=[
                build_pod("c1", f"p{i}", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pg1")
                for i in range(3)
            ] + [build_pod("c1", "solo", None, PodPhase.PENDING,
                           {"cpu": 500, "memory": GiB})],
        )
        assert_consistent(cache)
        sched = Scheduler(cache)
        sched.run_once()
        assert_consistent(cache)
        assert len(cache.binder.binds) == 4

    def test_churn_and_growth(self):
        """Enough pods to force several task-axis growths + delete/re-add
        churn so rows are freed and reused."""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node(f"n{i}", cpu=64000, mem=64 * GiB, pods=200)
                   for i in range(4)],
            pods=[],
        )
        for i in range(40):
            cache.add_pod(build_pod("c1", f"p{i}", None, PodPhase.PENDING,
                                    {"cpu": 100, "memory": GiB // 8}))
        assert_consistent(cache)
        # delete half (frees rows), re-add with different requests
        for i in range(0, 40, 2):
            cache.delete_pod(cache.pods[f"c1/p{i}"])
        assert_consistent(cache)
        for i in range(40, 120):
            cache.add_pod(build_pod("c1", f"p{i}", None, PodPhase.PENDING,
                                    {"cpu": 200, "memory": GiB // 4}))
        assert_consistent(cache)
        sched = Scheduler(cache)
        sched.run_once()
        assert_consistent(cache)
        # every pending pod fit
        assert len(cache.binder.binds) == 100

    def test_full_pipeline_with_eviction(self):
        """Eviction flows (preempt) + kubelet sim keep columns in sync."""
        cache = build_cache(
            queues=["default"],
            pod_groups=[
                PodGroup(name="low", namespace="c1", min_member=1, queue="default"),
                PodGroup(name="high", namespace="c1", min_member=1, queue="default",
                         priority_class="high-prio"),
            ],
            nodes=[build_node("n1", cpu=2000, mem=4 * GiB, pods=10)],
            pods=[
                build_pod("c1", "low-1", "n1", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, group_name="low"),
                build_pod("c1", "low-2", "n1", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, group_name="low"),
                build_pod("c1", "high-1", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="high",
                          priority=100),
            ],
        )
        cache.add_priority_class(PriorityClass(name="high-prio", value=100))
        conf = parse_scheduler_conf(FULL_CONF)
        sched = Scheduler(cache, conf=conf)
        sched.run_once()
        assert_consistent(cache)
        assert len(cache.evictor.evicts) == 1
        cache.delete_pod(cache.pods[cache.evictor.evicts[0]])
        assert_consistent(cache)
        sched.run_once()
        cache.flush_binds()
        assert cache.binder.binds.get("c1/high-1") == "n1"
        assert_consistent(cache)

    def test_node_update_and_labels(self):
        """set_node on a bound node rewrites ledger views in place and
        re-interns labels; late-arriving labels un-impossible selectors."""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1", cpu=4000, mem=8 * GiB)],
            pods=[build_pod("c1", "sel", None, PodPhase.PENDING,
                            {"cpu": 500, "memory": GiB},
                            node_selector={"zone": "a"})],
        )
        sched = Scheduler(cache)
        sched.run_once()
        assert cache.binder.binds == {}  # no node carries zone=a yet
        assert_consistent(cache)
        # node gains the label → selector becomes satisfiable
        cache.add_node(Node(name="n1", allocatable={"cpu": 4000,
                                                    "memory": 8 * GiB,
                                                    "pods": 110},
                            labels={"zone": "a"}))
        sched.run_once()
        cache.flush_binds()
        assert cache.binder.binds == {"c1/sel": "n1"}
        assert_consistent(cache)

    def test_node_delete_with_residents_demotes_then_retires(self):
        """Deleting a node with resident bound pods keeps them registered on
        a nodeless placeholder (zero capacity, excluded from snapshots) — a
        re-added node replays their accounting via set_node, and a kubelet
        update can't re-account a task into fresh capacity (the underflow
        the 150-cycle soak caught). The placeholder retires with its last
        resident, freeing the row with no task aliasing it."""
        cols_pods = [build_pod("c1", "resident", "n1", PodPhase.RUNNING,
                               {"cpu": 500, "memory": GiB})]
        cache = build_cache(
            queues=["default"], nodes=[build_node("n1")], pods=cols_pods,
        )
        cols = cache.columns
        cache.delete_node("n1")
        # demoted, not freed: resident stays attached, node leaves snapshots
        node = cache.nodes["n1"]
        assert node.node is None and "c1/resident" in node.tasks
        assert not cols.n_valid[node._row]
        assert (node.allocatable.vec == 0).all()
        assert_consistent(cache)
        # re-add: accounting replays (underflow-free), pod still resident
        cache.add_node(build_node("n1", cpu=4000, mem=8 * GiB))
        node = cache.nodes["n1"]
        assert node.node is not None
        assert node.idle.milli_cpu == 3500.0
        assert_consistent(cache)
        # delete again, then the resident dies → placeholder retires
        cache.delete_node("n1")
        row = cache.nodes["n1"]._row
        cache.delete_pod(cache.pods["c1/resident"])
        assert "n1" not in cache.nodes
        assert not (cols.t_node == row).any()
        cache.add_node(build_node("n2"))  # may reuse the freed row
        row2 = cols.node_rows["n2"]
        assert not (cols.t_node == row2).any()
        assert_consistent(cache)

    def test_node_delete_without_residents_frees_row(self):
        cache = build_cache(queues=["default"], nodes=[build_node("n1")],
                            pods=[])
        cols = cache.columns
        row = cols.node_rows["n1"]
        live_before = cols.nodes.n_live
        cache.delete_node("n1")
        assert "n1" not in cache.nodes
        assert "n1" not in cols.node_rows  # the COLUMN row was freed too
        assert cols.nodes.n_live == live_before - 1
        assert not cols.n_valid[row]
        assert_consistent(cache)

    def test_allocate_action_picks_sharded_path(self):
        """VERDICT r2 #3: on a multi-device part with a big-enough node
        axis, the production AllocateAction must dispatch the mesh-sharded
        solve — and produce correct bindings through it."""
        import jax

        from kube_batch_tpu.framework.interface import get_action
        from kube_batch_tpu.parallel.mesh import SHARD_MIN_NODES

        if len(jax.devices()) < 2:
            import pytest

            pytest.skip("needs the multi-device virtual mesh")
        n_nodes = 200  # node axis pads to 256 == SHARD_MIN_NODES
        cache = build_cache(
            queues=["default"],
            nodes=[build_node(f"n{i}") for i in range(n_nodes)],
            pods=[build_pod("c1", f"p{i}", None, PodPhase.PENDING,
                            {"cpu": 500, "memory": GiB}) for i in range(4)],
        )
        sched = Scheduler(cache)
        sched.run_once()
        cache.flush_binds()
        action = get_action("allocate")
        assert action.last_solve_mode == "sharded", action.last_solve_mode
        assert len(cache.binder.binds) == 4
        assert_consistent(cache)

    def test_node_delete_readd_keeps_resident_accounted(self):
        """A re-added node replays its surviving residents' accounting
        immediately (the delete demoted, not orphaned, them) — there is no
        window where bound capacity reads as free (the 150-cycle soak's
        underflow: the scheduler filled the 'free' capacity, then the pod's
        next event re-accounted it). A later pod update must not
        double-account either."""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1")],
            pods=[build_pod("c1", "res", "n1", PodPhase.RUNNING,
                            {"cpu": 500, "memory": GiB})],
        )
        task = cache.jobs["c1/res"].tasks["c1/res"]
        row = task._row
        cache.delete_node("n1")
        cache.add_node(build_node("n1"))
        node = cache.nodes["n1"]
        assert int(cache.columns.t_node[row]) == node._row
        assert "c1/res" in node.tasks
        idle_cpu = node.idle.milli_cpu
        assert idle_cpu == node.allocatable.milli_cpu - 500
        assert_consistent(cache)
        # the pod's next event (informer resync analog) is idempotent
        cache.update_pod(cache.pods["c1/res"])
        node = cache.nodes["n1"]
        assert node.idle.milli_cpu == idle_cpu
        assert "c1/res" in node.tasks
        assert_consistent(cache)

    def test_randomized_churn_soak(self):
        """Seeded soak: many cycles of random adds / deletes / updates /
        node churn / kubelet transitions, asserting full column/object
        consistency after every cycle.  The strongest drift guard the
        columnar model has — any missed choke point shows up here."""
        rng = np.random.default_rng(7)
        cache = build_cache(
            queues=["default"],
            nodes=[build_node(f"n{i}", cpu=8000, mem=16 * GiB, pods=30)
                   for i in range(6)],
            pods=[],
        )
        sched = Scheduler(cache)
        next_id = [0]

        def add_gang():
            _soak_add_gang(cache, rng, next_id)

        for cycle in range(25):
            op = rng.random()
            if op < 0.5:
                add_gang()
            elif op < 0.7 and cache.pods:
                # kubelet: a bound pod starts running, or a pod dies
                key = list(cache.pods)[int(rng.integers(len(cache.pods)))]
                pod = cache.pods[key]
                if pod.node_name and rng.random() < 0.6:
                    upd = Pod(
                        name=pod.name, namespace=pod.namespace, uid=pod.uid,
                        requests=dict(pod.requests), node_name=pod.node_name,
                        phase=PodPhase.RUNNING,
                        annotations=dict(pod.annotations),
                        creation_index=pod.creation_index,
                    )
                    cache.update_pod(upd)
                else:
                    cache.delete_pod(pod)
            elif op < 0.8:
                # node churn: delete or (re-)add
                name = f"n{int(rng.integers(6))}"
                if name in cache.nodes and rng.random() < 0.5:
                    cache.delete_node(name)
                else:
                    cache.add_node(build_node(name, cpu=8000, mem=16 * GiB,
                                              pods=30))
            # else: idle cycle
            sched.run_once()
            cache.flush_binds()
            errs = cache.columns.check_consistency(cache)
            assert not errs, (cycle, errs[:5])
        # the soak actually scheduled things
        assert len(cache.binder.binds) > 10

    def test_persistence_roundtrip_columns(self):
        """--state-file save/restore rebuilds a consistent column store and
        the restored cache schedules."""
        import os
        import tempfile

        from kube_batch_tpu.cache.cache import SchedulerCache
        from kube_batch_tpu.cache.persistence import load_state, save_state

        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=2,
                                 queue="default")],
            nodes=[build_node("n1"), build_node("n2")],
            pods=[
                build_pod("c1", "bound", "n1", PodPhase.RUNNING,
                          {"cpu": 500, "memory": GiB}),
                build_pod("c1", "g-0", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB}, group_name="pg"),
                build_pod("c1", "g-1", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB}, group_name="pg"),
            ],
        )
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "state.json")
            save_state(cache, path)
            restored = SchedulerCache()
            load_state(restored, path)
        assert_consistent(restored)
        assert restored.nodes["n1"].used.milli_cpu == 500.0
        Scheduler(restored).run_once()
        restored.flush_binds()
        assert set(restored.binder.binds) == {"c1/g-0", "c1/g-1"}
        assert_consistent(restored)

    def test_rebuild_from_pod_store(self):
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1")],
            pods=[build_pod("c1", "a", "n1", PodPhase.RUNNING,
                            {"cpu": 500, "memory": GiB}),
                  build_pod("c1", "b", None, PodPhase.PENDING,
                            {"cpu": 500, "memory": GiB})],
        )
        cache.rebuild_from_pod_store()
        assert_consistent(cache)
        idle = cache.nodes["n1"].idle
        assert idle.milli_cpu == cache.nodes["n1"].allocatable.milli_cpu - 500


class TestFullPipelineChurnSoak:
    def test_five_action_churn_soak(self):
        """Seeded soak over the SHIPPED 5-action pipeline (enqueue, reclaim,
        allocate, backfill, preempt) with two weighted queues, random
        priorities, kubelet transitions (run / die / honor evictions), and
        node churn — after every cycle: full column/object consistency and
        the node resource algebra invariants (never overcommit, reclaim's
        and preempt's evictions included)."""
        conf = parse_scheduler_conf(FULL_CONF)
        rng = np.random.default_rng(11)
        from kube_batch_tpu.api.pod import Queue

        cache = build_cache(
            queues=[Queue(name="qa", weight=3), Queue(name="qb", weight=1)],
            nodes=[build_node(f"n{i}", cpu=6000, mem=16 * GiB, pods=30)
                   for i in range(4)],
            pods=[],
        )
        sched = Scheduler(cache, conf=conf)
        next_id = [0]

        def add_gang():
            _soak_add_gang(cache, rng, next_id, queues=("qa", "qb"),
                           cpu_choices=(500, 1000, 2000),
                           prio_choices=(0, 0, 0, 100))

        quanta = cache.spec.quanta
        for cycle in range(30):
            op = rng.random()
            if op < 0.45:
                add_gang()
            elif op < 0.65 and cache.pods:
                key = list(cache.pods)[int(rng.integers(len(cache.pods)))]
                pod = cache.pods[key]
                if pod.node_name and rng.random() < 0.7:
                    cache.update_pod(Pod(
                        name=pod.name, namespace=pod.namespace, uid=pod.uid,
                        requests=dict(pod.requests), node_name=pod.node_name,
                        phase=PodPhase.RUNNING,
                        annotations=dict(pod.annotations),
                        priority=pod.priority,
                        creation_index=pod.creation_index,
                    ))
                else:
                    cache.delete_pod(pod)
            elif op < 0.75:
                name = f"n{int(rng.integers(4))}"
                if name in cache.nodes and rng.random() < 0.5:
                    cache.delete_node(name)
                else:
                    cache.add_node(build_node(name, cpu=6000, mem=16 * GiB,
                                              pods=30))
            # honor pending evictions like a kubelet: terminate the pods the
            # evictor asked for, so Releasing capacity actually frees
            for key in list(cache.evictor.evicts):
                pod = cache.pods.get(key)
                if pod is not None:
                    cache.delete_pod(pod)
            cache.evictor.evicts.clear()

            sched.run_once()
            cache.flush_binds()
            errs = cache.columns.check_consistency(cache)
            assert not errs, (cycle, errs[:5])
            for node in cache.nodes.values():
                assert (node.idle.vec >= -quanta).all(), (cycle, node.name)
                assert (node.used.vec
                        <= node.allocatable.vec + quanta).all(), (
                    cycle, node.name)
        assert len(cache.binder.binds) > 10


class TestResidentFeatureCache:
    def test_reuse_and_invalidation(self):
        """The resident snapshot hands out the SAME device arrays while
        nothing moved, refreshes after ingest (a new task: its feature rows
        ride the resident swap's delta; a node meta change: the node
        feature version re-uploads), and the refreshed arrays carry the new
        values — the staleness hazard the version counters exist for."""
        import numpy as np

        from kube_batch_tpu.api.columns import resident_snap
        from kube_batch_tpu.framework.conf import load_scheduler_conf
        from kube_batch_tpu.framework.session import close_session, open_session

        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1", cpu=4000, mem=8 * GiB)],
            pods=[build_pod("c", "p0", None, PodPhase.PENDING,
                            {"cpu": 1000, "memory": GiB}, group_name="g0")],
            pod_groups=[PodGroup(name="g0", namespace="c", min_member=1,
                                 queue="default")],
        )
        cols = cache.columns
        conf = load_scheduler_conf(None)
        ssn = open_session(cache, conf.tiers)
        try:
            snap, _meta = cols.device_snapshot(ssn)
            r1 = resident_snap(cols, snap)
            r2 = resident_snap(cols, snap)
            assert r1.task_req is r2.task_req  # cached, no re-upload
            assert r1.node_alloc is r2.node_alloc
            assert cols.resident_features(snap).node_alloc is r1.node_alloc
            np.testing.assert_array_equal(
                np.asarray(r1.task_req), cols.t_init32)
        finally:
            close_session(ssn)
        # ingest invalidates: a new task must appear in the next upload
        v0 = cols.task_feature_version
        cache.add_pod_group(PodGroup(name="g1", namespace="c", min_member=1,
                                     queue="default"))
        cache.add_pod(build_pod("c", "p1", None, PodPhase.PENDING,
                                {"cpu": 2000, "memory": GiB},
                                group_name="g1"))
        assert cols.task_feature_version > v0
        ssn = open_session(cache, conf.tiers)
        try:
            snap2, meta2 = cols.device_snapshot(ssn)
            r3 = resident_snap(cols, snap2)
            assert r3.task_req is not r1.task_req
            np.testing.assert_array_equal(
                np.asarray(r3.task_req), cols.t_init32)
            assert r3.node_alloc is r1.node_alloc  # no node moved
            # node meta change (labels) invalidates node bits
            prev_bits = r3.node_label_bits
            node = cache.nodes["n1"]
            obj = build_node("n1", cpu=4000, mem=8 * GiB,
                             labels={"zone": "z1"})
            node.set_node(obj)
            snap3, _ = cols.device_snapshot(ssn)
            r4 = resident_snap(cols, snap3)
            assert r4.node_label_bits is not prev_bits
            np.testing.assert_array_equal(
                np.asarray(r4.node_label_bits), cols.n_label_bits)
        finally:
            close_session(ssn)

    def test_kill_switch(self, monkeypatch):
        monkeypatch.setenv("KB_DEVICE_CACHE", "0")
        from kube_batch_tpu.framework.conf import load_scheduler_conf
        from kube_batch_tpu.framework.session import close_session, open_session

        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1", cpu=4000, mem=8 * GiB)],
            pods=[],
        )
        cols = cache.columns
        conf = load_scheduler_conf(None)
        ssn = open_session(cache, conf.tiers)
        try:
            snap, _ = cols.device_snapshot(ssn)
            assert cols.resident_features(snap) is snap
            assert cols.per_cycle_resident(snap) is snap
        finally:
            close_session(ssn)

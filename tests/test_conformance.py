"""Conformance scenarios — the rebuild's analog of the reference e2e suite
(test/e2e/job.go, predicates.go, nodeorder.go, queue.go; SURVEY.md §4.2).

Each test is a behavioral spec of the whole scheduler run against the fake
backend: synthetic objects through the real cache handlers, real session +
actions, assertions on captured binds/evicts. Invariant-style where the
reference's own placement is randomized (scheduler_helper.go:147-158)."""

import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.pod import (
    Affinity,
    PodGroup,
    PriorityClass,
    Queue,
    Taint,
    Toleration,
)
from kube_batch_tpu.api.types import PodGroupPhase, PodPhase

from tests.fixtures import GiB, build_cache, build_node, build_pod
from tests.test_actions import run_actions


def _cache_with_pv_binder(**kw):
    """build_cache with the real PV ledger behind the volume seams."""
    from kube_batch_tpu.cache.volume import StandalonePVBinder

    cache = build_cache(**kw)
    cache.volume_binder = StandalonePVBinder()
    return cache


def gang(cache_kw_pods, name, n, cpu=1000, queue="default", priority=0, **pod_kw):
    """Append n pending gang pods for PodGroup `name` to a pod list."""
    for i in range(n):
        cache_kw_pods.append(
            build_pod("c1", f"{name}-{i}", None, PodPhase.PENDING,
                      {"cpu": cpu, "memory": GiB}, group_name=name,
                      priority=priority, **pod_kw)
        )


class TestJobScenarios:
    def test_schedule_multiple_jobs(self):
        """job.go:48 Schedule Multiple Jobs: several gangs co-scheduled."""
        pods = []
        for j in range(3):
            gang(pods, f"job{j}", 2)
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name=f"job{j}", namespace="c1", min_member=2,
                                 queue="default") for j in range(3)],
            nodes=[build_node("n1", cpu=4000, mem=16 * GiB),
                   build_node("n2", cpu=4000, mem=16 * GiB)],
            pods=pods,
        )
        run_actions(cache)
        assert len(cache.binder.binds) == 6

    def test_gang_full_occupied_cluster_binds_nothing(self):
        """job.go:118 Gang: Full Occupied: no partial gang on a full cluster."""
        pods = [
            build_pod("c1", f"run-{i}", "n1", PodPhase.RUNNING,
                      {"cpu": 1000, "memory": GiB})
            for i in range(4)
        ]
        gang(pods, "starved", 2)
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="starved", namespace="c1", min_member=2,
                                 queue="default")],
            nodes=[build_node("n1", cpu=4000, mem=16 * GiB)],
            pods=pods,
        )
        run_actions(cache)
        assert cache.binder.binds == {}
        job = cache.jobs["c1/starved"]
        assert any(c.type == "Unschedulable" for c in job.pod_group.conditions)

    def test_gang_unsatisfied_releases_resources_to_other_job(self):
        """job.go:149 Gang: an unsatisfiable gang must not hold resources a
        satisfiable gang needs (the Statement discard, statement.go:309)."""
        pods = []
        gang(pods, "big", 3)    # needs 3×1000m — cluster only has 2000m
        gang(pods, "small", 2)  # needs 2×1000m — fits iff big released
        cache = build_cache(
            queues=["default"],
            pod_groups=[
                PodGroup(name="big", namespace="c1", min_member=3, queue="default"),
                PodGroup(name="small", namespace="c1", min_member=2, queue="default"),
            ],
            nodes=[build_node("n1", cpu=2000, mem=16 * GiB)],
            pods=pods,
        )
        run_actions(cache)
        assert set(cache.binder.binds) == {"c1/small-0", "c1/small-1"}

    def test_fit_unassigned_task_counts_toward_gang(self):
        """job.go:369: a task already bound counts toward minMember; only the
        remainder schedules."""
        pods = [
            build_pod("c1", "pre-0", "n1", PodPhase.RUNNING,
                      {"cpu": 1000, "memory": GiB}, group_name="pg"),
        ]
        gang(pods, "rest", 1)
        pods[-1].annotations = dict(pods[-1].annotations)
        # put the pending pod in the same podgroup
        from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION
        pods[-1].annotations[GROUP_NAME_ANNOTATION] = "pg"
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=2,
                                 queue="default")],
            nodes=[build_node("n1", cpu=4000, mem=16 * GiB)],
            pods=pods,
        )
        run_actions(cache)
        assert set(cache.binder.binds) == {"c1/rest-0"}

    def test_task_priority_placed_first_under_scarcity(self):
        """job.go:329 TaskPriority: within a job, high-priority tasks win the
        scarce capacity (priority plugin TaskOrderFn, priority.go:40-60)."""
        pods = []
        gang(pods, "lo", 2, priority=1)
        gang(pods, "hi", 2, priority=100)
        # one job containing both priority bands
        from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION
        for p in pods:
            p.annotations[GROUP_NAME_ANNOTATION] = "mixed"
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="mixed", namespace="c1", min_member=2,
                                 queue="default")],
            nodes=[build_node("n1", cpu=2000, mem=16 * GiB)],
            pods=pods,
        )
        run_actions(cache)
        assert set(cache.binder.binds) == {"c1/hi-0", "c1/hi-1"}

    def test_job_priority_wins_scarce_capacity(self):
        """job.go:410 Job Priority: the high-PriorityClass job gets the
        cluster; the low one starves."""
        pods = []
        gang(pods, "low", 2)
        gang(pods, "high", 2)
        cache = build_cache(
            queues=["default"],
            pod_groups=[
                PodGroup(name="low", namespace="c1", min_member=2, queue="default"),
                PodGroup(name="high", namespace="c1", min_member=2, queue="default",
                         priority_class="prio-100"),
            ],
            nodes=[build_node("n1", cpu=2000, mem=16 * GiB)],
            pods=pods,
        )
        cache.add_priority_class(PriorityClass(name="prio-100", value=100))
        run_actions(cache)
        assert set(cache.binder.binds) == {"c1/high-0", "c1/high-1"}

    def test_multiple_preemption(self):
        """job.go:221 Multiple Preemption: two starved preemptors evict two
        running victims."""
        pods = [
            build_pod("c1", f"victim-{i}", "n1", PodPhase.RUNNING,
                      {"cpu": 1000, "memory": GiB}, group_name="lowjob")
            for i in range(3)
        ]
        gang(pods, "high", 2, priority=100)
        cache = build_cache(
            queues=["default"],
            pod_groups=[
                PodGroup(name="lowjob", namespace="c1", min_member=1, queue="default"),
                PodGroup(name="high", namespace="c1", min_member=2, queue="default",
                         priority_class="prio-100"),
            ],
            nodes=[build_node("n1", cpu=3000, mem=16 * GiB)],
            pods=pods,
        )
        cache.add_priority_class(PriorityClass(name="prio-100", value=100))
        run_actions(cache, action_names=["preempt"])
        assert len(cache.evictor.evicts) == 2
        assert all(k.startswith("c1/victim-") for k in cache.evictor.evicts)

    def test_proportion_weighted_split(self):
        """job.go:458 Proportion: 3:1 weighted queues split a 4000m cluster
        3000/1000 (proportion.go:101-154)."""
        pods = []
        for i in range(8):
            pods.append(build_pod("c1", f"a-{i}", None, PodPhase.PENDING,
                                  {"cpu": 500, "memory": GiB // 2}, group_name=f"ja{i}"))
        for i in range(8):
            pods.append(build_pod("c1", f"b-{i}", None, PodPhase.PENDING,
                                  {"cpu": 500, "memory": GiB // 2}, group_name=f"jb{i}"))
        cache = build_cache(
            queues=[Queue(name="qa", weight=3), Queue(name="qb", weight=1)],
            pod_groups=(
                [PodGroup(name=f"ja{i}", namespace="c1", min_member=1, queue="qa")
                 for i in range(8)]
                + [PodGroup(name=f"jb{i}", namespace="c1", min_member=1, queue="qb")
                   for i in range(8)]
            ),
            nodes=[build_node("n1", cpu=4000, mem=16 * GiB)],
            pods=pods,
        )
        run_actions(cache, action_names=["allocate"])
        a_binds = sum(1 for k in cache.binder.binds if k.startswith("c1/a-"))
        b_binds = sum(1 for k in cache.binder.binds if k.startswith("c1/b-"))
        assert a_binds == 6, cache.binder.binds
        assert b_binds == 2, cache.binder.binds


class TestPredicateScenarios:
    def test_node_affinity_required_term(self):
        """predicates.go e2e:35 NodeAffinity: required In-term steers the pod."""
        cache = build_cache(
            queues=["default"],
            nodes=[
                build_node("east", labels={"zone": "us-east"}),
                build_node("west", labels={"zone": "us-west"}),
            ],
            pods=[
                build_pod("c1", "pinned", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB},
                          affinity=Affinity(node_terms=[[("zone", "In", ("us-east",))]])),
            ],
        )
        run_actions(cache)
        assert cache.binder.binds == {"c1/pinned": "east"}

    def test_node_affinity_multi_term_or(self):
        """Multi-term affinity (OR) is host-validated: the device proposal is
        re-checked through the predicates plugin in the allocate replay."""
        cache = build_cache(
            queues=["default"],
            nodes=[
                build_node("a", labels={"zone": "z1"}),
                build_node("b", labels={"zone": "z2"}),
                build_node("c", labels={"zone": "z3"}),
            ],
            pods=[
                build_pod("c1", "either", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB},
                          affinity=Affinity(node_terms=[
                              [("zone", "In", ("z1",))],
                              [("zone", "In", ("z2",))],
                          ])),
            ],
        )
        # device can't encode the OR; run enough cycles for the host net to
        # land it (each cycle re-proposes; the accept set shrinks to legal)
        for _ in range(4):
            run_actions(cache)
            if cache.binder.binds:
                break
        assert list(cache.binder.binds.values())[0] in ("a", "b")

    def test_hostport_conflict(self):
        """predicates.go e2e:84 Hostport: two pods wanting the same host port
        land on different nodes."""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1"), build_node("n2")],
            pods=[
                build_pod("c1", "web-0", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB}, host_ports=(8080,)),
                build_pod("c1", "web-1", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB}, host_ports=(8080,)),
            ],
        )
        for _ in range(4):
            run_actions(cache)
            if len(cache.binder.binds) == 2:
                break
        assert len(cache.binder.binds) == 2
        assert cache.binder.binds["c1/web-0"] != cache.binder.binds["c1/web-1"]

    def test_hostport_blocked_by_resident(self):
        """A resident pod's host port blocks the only node — the pending
        claimant must stay unbound (exercises the port index the vectorized
        fallback placement consults)."""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1")],
            pods=[
                build_pod("c1", "resident", "n1", PodPhase.RUNNING,
                          {"cpu": 500, "memory": GiB}, host_ports=(9090,)),
                build_pod("c1", "wants", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB}, host_ports=(9090,)),
            ],
        )
        run_actions(cache)
        assert "c1/wants" not in cache.binder.binds

    def test_hostport_gangs_promoted_to_bulk(self):
        """Conflict-free ported gangs take the bulk path (ports promotion);
        placements stay correct and port-exclusive per node."""
        cache = build_cache(
            queues=["default"],
            pod_groups=[
                PodGroup(name=f"g{j}", namespace="c1", min_member=2,
                         queue="default") for j in range(4)
            ],
            nodes=[build_node(f"n{i}", pods=4) for i in range(8)],
            pods=[
                build_pod("c1", f"g{j}-{i}", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB}, group_name=f"g{j}",
                          host_ports=(7000 + j,))
                for j in range(4) for i in range(2)
            ],
        )
        run_actions(cache)
        assert len(cache.binder.binds) == 8
        # no two pods sharing a port landed on the same node
        seen = {}
        for j in range(4):
            for i in range(2):
                node = cache.binder.binds[f"c1/g{j}-{i}"]
                assert (node, 7000 + j) not in seen
                seen[(node, 7000 + j)] = True
        from kube_batch_tpu.framework.interface import get_action

        fb = get_action("allocate").last_fallback
        assert fb["promoted_ports_jobs"] >= 1, fb

    def test_memory_pressure_gate_excludes_node(self):
        """predicates.go:233-276 pressure gates, enabled via plugin args:
        a MemoryPressure node is excluded and the placement still rides the
        fast (device) path — no job is demoted to the host replay for it."""
        from kube_batch_tpu.framework.conf import parse_scheduler_conf
        from kube_batch_tpu.framework.interface import get_action

        conf = parse_scheduler_conf("""
actions: "allocate, backfill"
tiers:
- plugins:
  - name: gang
  - name: predicates
    arguments:
      predicate.MemoryPressureEnable: "true"
""")
        cache = build_cache(
            queues=["default"],
            nodes=[
                build_node("pressured", conditions={"MemoryPressure": True}),
                build_node("healthy"),
            ],
            pods=[build_pod("c1", "p0", None, PodPhase.PENDING,
                            {"cpu": 500, "memory": GiB})],
        )
        from kube_batch_tpu.scheduler import Scheduler

        Scheduler(cache, conf=conf).run_once()
        cache.flush_binds()
        assert cache.binder.binds == {"c1/p0": "healthy"}
        fb = get_action("allocate").last_fallback
        assert fb["slow_jobs"] == 0, fb  # pressure no longer demotes jobs
        assert not cache.columns.check_consistency(cache)

    def test_taints_block_untolerated(self):
        """predicates.go e2e:161 Taints/Tolerations."""
        cache = build_cache(
            queues=["default"],
            nodes=[
                build_node("tainted", taints=[Taint(key="dedicated", value="ml",
                                                    effect="NoSchedule")]),
                build_node("open"),
            ],
            pods=[
                build_pod("c1", "plain", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB}),
                build_pod("c1", "tolerant", None, PodPhase.PENDING,
                          {"cpu": 500, "memory": GiB},
                          tolerations=[Toleration(key="dedicated", value="ml",
                                                  effect="NoSchedule")]),
            ],
        )
        run_actions(cache)
        assert cache.binder.binds["c1/plain"] == "open"
        assert "c1/tolerant" in cache.binder.binds  # either node is legal

    def test_max_pods_respected(self):
        """predicates.go e2e:209 MaxPods: the pods capacity dimension caps
        placements per node."""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1", cpu=64000, mem=64 * GiB, pods=2)],
            pods=[
                build_pod("c1", f"p{i}", None, PodPhase.PENDING,
                          {"cpu": 100, "memory": GiB // 4})
                for i in range(5)
            ],
        )
        run_actions(cache)
        assert len(cache.binder.binds) == 2

    def test_unschedulable_node_excluded(self):
        """CheckNodeUnschedulable (predicates.go:181-192)."""
        cache = build_cache(
            queues=["default"],
            nodes=[
                build_node("cordoned", unschedulable=True),
                build_node("open"),
            ],
            pods=[build_pod("c1", "p0", None, PodPhase.PENDING,
                            {"cpu": 500, "memory": GiB})],
        )
        run_actions(cache)
        assert cache.binder.binds == {"c1/p0": "open"}

    def test_not_ready_node_excluded_from_snapshot(self):
        """cache.go:595-597: NotReady nodes never enter the snapshot."""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("down", ready=False)],
            pods=[build_pod("c1", "p0", None, PodPhase.PENDING,
                            {"cpu": 500, "memory": GiB})],
        )
        run_actions(cache)
        assert cache.binder.binds == {}


class TestNodeOrderScenarios:
    def test_least_requested_spreads(self):
        """nodeorder.go e2e:138 Least Requested: a new pod prefers the idler
        node."""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("busy", cpu=8000, mem=16 * GiB),
                   build_node("idle", cpu=8000, mem=16 * GiB)],
            pods=[
                build_pod("c1", "resident", "busy", PodPhase.RUNNING,
                          {"cpu": 6000, "memory": 8 * GiB}),
                build_pod("c1", "new", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}),
            ],
        )
        run_actions(cache)
        assert cache.binder.binds == {"c1/new": "idle"}

    def test_binpack_packs_when_weighted(self):
        """The binpack row (BASELINE north star): with binpack outweighing
        leastrequested, the new pod packs onto the busier node."""
        conf = """
actions: "allocate"
tiers:
- plugins:
  - name: gang
  - name: binpack
    arguments:
      binpack.weight: 10
"""
        cache = build_cache(
            queues=["default"],
            nodes=[build_node("busy", cpu=8000, mem=16 * GiB),
                   build_node("idle", cpu=8000, mem=16 * GiB)],
            pods=[
                build_pod("c1", "resident", "busy", PodPhase.RUNNING,
                          {"cpu": 6000, "memory": 8 * GiB}),
                build_pod("c1", "new", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}),
            ],
        )
        run_actions(cache, conf_text=conf)
        assert cache.binder.binds == {"c1/new": "busy"}


class TestStatementScenario:
    def test_statement_discard_restores_state(self):
        """job.go:292 Statement: allocate then discard leaves no trace."""
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node("n1", cpu=4000, mem=8 * GiB)],
            pods=[build_pod("c1", "p0", None, PodPhase.PENDING,
                            {"cpu": 1000, "memory": GiB}, group_name="pg")],
        )
        from kube_batch_tpu.framework.conf import parse_scheduler_conf
        from kube_batch_tpu.framework.session import close_session, open_session
        from kube_batch_tpu.api.types import TaskStatus

        conf = parse_scheduler_conf(
            'actions: "allocate"\ntiers:\n- plugins:\n  - name: gang\n')
        ssn = open_session(cache, conf.tiers)
        job = next(iter(ssn.jobs.values()))
        task = next(iter(job.tasks.values()))
        node = ssn.nodes["n1"]
        idle_before = node.idle.vec.copy()

        stmt = ssn.statement()
        stmt.allocate(task, "n1")
        assert task.status == TaskStatus.ALLOCATED
        assert node.idle.vec[0] == idle_before[0] - 1000
        stmt.discard()
        assert task.status == TaskStatus.PENDING
        assert node.idle.vec[0] == idle_before[0]
        assert cache.binder.binds == {}
        close_session(ssn)

    def test_statement_commit_binds(self):
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node("n1", cpu=4000, mem=8 * GiB)],
            pods=[build_pod("c1", "p0", None, PodPhase.PENDING,
                            {"cpu": 1000, "memory": GiB}, group_name="pg")],
        )
        from kube_batch_tpu.framework.conf import parse_scheduler_conf
        from kube_batch_tpu.framework.session import close_session, open_session

        conf = parse_scheduler_conf(
            'actions: "allocate"\ntiers:\n- plugins:\n  - name: gang\n')
        ssn = open_session(cache, conf.tiers)
        job = next(iter(ssn.jobs.values()))
        task = next(iter(job.tasks.values()))
        stmt = ssn.statement()
        stmt.allocate(task, "n1")
        stmt.commit()
        assert cache.binder.binds == {"c1/p0": "n1"}
        close_session(ssn)


class TestReclaimScenario:
    def test_reclaim_respects_deserved(self):
        """queue.go e2e:26: reclaim only down to the victim queue's deserved
        share (proportion.go:171-196)."""
        pods = [
            build_pod("c1", f"a-{i}", "n1", PodPhase.RUNNING,
                      {"cpu": 1000, "memory": GiB}, group_name="ja")
            for i in range(4)
        ]
        pods.append(build_pod("c1", "b-0", None, PodPhase.PENDING,
                              {"cpu": 1000, "memory": GiB}, group_name="jb"))
        cache = build_cache(
            queues=[Queue(name="qa", weight=1), Queue(name="qb", weight=1)],
            pod_groups=[
                PodGroup(name="ja", namespace="c1", min_member=1, queue="qa"),
                PodGroup(name="jb", namespace="c1", min_member=1, queue="qb"),
            ],
            nodes=[build_node("n1", cpu=4000, mem=16 * GiB)],
            pods=pods,
        )
        run_actions(cache, action_names=["reclaim"])
        # qb deserves 1000m (its request caps it); exactly one eviction
        assert len(cache.evictor.evicts) == 1


class TestInterPodAffinity:
    def test_pod_affinity_co_locates(self):
        """e2e predicates.go:112 "Pod Affinity": a pod with required pod
        affinity lands in the same topology domain as the matching pod;
        the group's first pod passes via the affinity-only fast path."""
        from kube_batch_tpu.api.pod import Affinity, PodAffinityTerm
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pga", namespace="c1", min_member=1,
                                 queue="default"),
                        PodGroup(name="pgb", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node(f"n{i}", cpu=8000, mem=16 * GiB) for i in range(4)],
            pods=[
                build_pod("c1", "anchor", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pga",
                          labels={"app": "db"}),
                build_pod("c1", "follower", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pgb",
                          affinity=Affinity(pod_affinity=[
                              PodAffinityTerm(match_labels={"app": "db"})])),
            ],
        )
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert binds["c1/anchor"] == binds["c1/follower"]

    def test_pod_anti_affinity_spreads(self):
        """e2e-style anti-affinity: two pods with the same label and
        hostname-scope anti-affinity must land on different nodes."""
        from kube_batch_tpu.api.pod import Affinity, PodAffinityTerm
        anti = Affinity(pod_anti_affinity=[PodAffinityTerm(match_labels={"app": "w"})])
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=2,
                                 queue="default")],
            nodes=[build_node(f"n{i}", cpu=8000, mem=16 * GiB) for i in range(3)],
            pods=[
                build_pod("c1", "w-0", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pg",
                          labels={"app": "w"}, affinity=anti),
                build_pod("c1", "w-1", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pg",
                          labels={"app": "w"}, affinity=anti),
            ],
        )
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert len(binds) == 2
        assert binds["c1/w-0"] != binds["c1/w-1"]

    def test_anti_affinity_against_running_pod(self):
        """Anti-affinity vs an already-running pod in the same domain."""
        from kube_batch_tpu.api.pod import Affinity, PodAffinityTerm
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node("n0", cpu=8000, mem=16 * GiB),
                   build_node("n1", cpu=8000, mem=16 * GiB)],
            pods=[
                build_pod("c1", "existing", "n0", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, labels={"app": "x"}),
                build_pod("c1", "new", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pg",
                          affinity=Affinity(pod_anti_affinity=[
                              PodAffinityTerm(match_labels={"app": "x"})])),
            ],
        )
        run_actions(cache, action_names=["allocate"])
        assert cache.binder.binds["c1/new"] == "n1"

    def test_zone_topology_affinity(self):
        """Non-hostname topology key: domain = nodes sharing the zone label."""
        from kube_batch_tpu.api.pod import Affinity, PodAffinityTerm
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node("n0", cpu=8000, mem=16 * GiB, labels={"zone": "a"}),
                   build_node("n1", cpu=8000, mem=16 * GiB, labels={"zone": "a"}),
                   build_node("n2", cpu=8000, mem=16 * GiB, labels={"zone": "b"})],
            pods=[
                build_pod("c1", "anchor", "n0", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, labels={"app": "db"}),
                build_pod("c1", "near", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pg",
                          affinity=Affinity(pod_affinity=[
                              PodAffinityTerm(match_labels={"app": "db"},
                                              topology_key="zone")])),
            ],
        )
        run_actions(cache, action_names=["allocate"])
        assert cache.binder.binds["c1/near"] in ("n0", "n1")  # zone a only


def _affinity_scenarios():
    """The reference e2e scenarios that ``affinity-10k-5k`` is the at-size
    twin of (SURVEY section 4.2; hostname anti-affinity is
    ``test_pod_anti_affinity_spreads`` above): nodeorder.go's three, and a
    zone-keyed required affinity over more than one zone.  Each is (nodes,
    pods, check(binds))."""
    from kube_batch_tpu.api.pod import Affinity, PodAffinityTerm

    def pending(name, **kw):
        return build_pod("c1", name, None, PodPhase.PENDING,
                         {"cpu": 1000, "memory": GiB}, **kw)

    def running(name, node, **kw):
        return build_pod("c1", name, node, PodPhase.RUNNING,
                         {"cpu": 1000, "memory": GiB}, **kw)

    four = [build_node(f"n{i}", cpu=8000, mem=16 * GiB) for i in range(4)]
    zoned = [build_node(f"n{i}", cpu=8000, mem=16 * GiB,
                        labels={"zone": "abc"[i // 2]}) for i in range(6)]
    db = PodAffinityTerm(match_labels={"app": "db"})
    return {
        # nodeorder.go:29 "Node Affinity": a preferred term steers
        "node_affinity": (
            [build_node("plain", cpu=8000, mem=16 * GiB),
             build_node("ssd", cpu=8000, mem=16 * GiB, labels={"disk": "ssd"})],
            [pending("p", affinity=Affinity(preferred_node_terms=[
                (50.0, [("disk", "In", ("ssd",))])]))],
            lambda b: b["c1/p"] == "ssd"),
        # nodeorder.go:74 "Pod Affinity": soft co-location
        "pod_affinity": (
            four,
            [running("anchor", "n2", labels={"app": "db"}),
             pending("near", affinity=Affinity(
                 preferred_pod_affinity=[(50.0, db)]))],
            lambda b: b["c1/near"] == "n2"),
        # nodeorder.go:138 "Least Requested": the idler node
        "least_requested": (
            [build_node("busy", cpu=8000, mem=16 * GiB),
             build_node("idle", cpu=8000, mem=16 * GiB)],
            [build_pod("c1", "resident", "busy", PodPhase.RUNNING,
                       {"cpu": 6000, "memory": 8 * GiB}),
             pending("new")],
            lambda b: b["c1/new"] == "idle"),
        # a required affinity under a zone key, three zones: the follower
        # lands in the anchor's zone, on either of its nodes
        "zone_affinity": (
            zoned,
            [running("anchor", "n3", labels={"app": "db"}),
             pending("near", affinity=Affinity(pod_affinity=[
                 PodAffinityTerm(match_labels={"app": "db"},
                                 topology_key="zone")]))],
            lambda b: b["c1/near"] in ("n2", "n3")),
        # and its anti twin: one pod a zone, the third zone is the free one
        "zone_anti_affinity": (
            zoned,
            [running("a", "n0", labels={"app": "db"}),
             running("b", "n5", labels={"app": "db"}),
             pending("c", labels={"app": "db"}, affinity=Affinity(
                 pod_anti_affinity=[PodAffinityTerm(
                     match_labels={"app": "db"}, topology_key="zone")]))],
            lambda b: b["c1/c"] in ("n2", "n3")),
    }


@pytest.mark.parametrize("path", ["planes", "object_scan"])
@pytest.mark.parametrize("scenario", sorted(_affinity_scenarios()))
def test_affinity_scenarios_on_the_planes_and_on_the_object_scan(
        scenario, path):
    """Each scenario through the columnar store's match-count planes (the
    exclusive session every deployment runs) and through the object-scan
    oracle (an isolated session: ``build_snapshot`` calls
    ``pod_affinity_ok`` / ``preferred_pod_affinity_score`` per node, and the
    host predicate re-validates at replay)."""
    from kube_batch_tpu.framework.conf import parse_scheduler_conf
    from kube_batch_tpu.framework.interface import get_action
    from kube_batch_tpu.framework.session import close_session, open_session
    from tests.test_actions import TWO_TIER_CONF

    nodes, pods, check = _affinity_scenarios()[scenario]
    cache = build_cache(queues=["default"], nodes=nodes, pods=pods)
    ssn = open_session(cache, parse_scheduler_conf(TWO_TIER_CONF).tiers,
                       isolated=(path == "object_scan"))
    assert (ssn.columns is None) == (path == "object_scan")
    get_action("allocate").execute(ssn)
    close_session(ssn)
    cache.flush_binds()
    assert check(cache.binder.binds), cache.binder.binds


class TestPreferredAffinity:
    def test_preferred_node_affinity_steers(self):
        """e2e nodeorder.go "Node Affinity" (:29): a preferred term steers
        placement toward the matching node without excluding others."""
        from kube_batch_tpu.api.pod import Affinity
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node("plain", cpu=8000, mem=16 * GiB),
                   build_node("ssd", cpu=8000, mem=16 * GiB,
                              labels={"disk": "ssd"})],
            pods=[build_pod("c1", "p", None, PodPhase.PENDING,
                            {"cpu": 1000, "memory": GiB}, group_name="pg",
                            affinity=Affinity(preferred_node_terms=[
                                (50.0, [("disk", "In", ("ssd",))])]))],
        )
        run_actions(cache, action_names=["allocate"])
        assert cache.binder.binds["c1/p"] == "ssd"

    def test_preferred_pod_affinity_co_locates(self):
        """e2e nodeorder.go "Pod Affinity" (:74): soft co-location."""
        from kube_batch_tpu.api.pod import Affinity, PodAffinityTerm
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node(f"n{i}", cpu=8000, mem=16 * GiB) for i in range(4)],
            pods=[
                build_pod("c1", "anchor", "n2", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, labels={"app": "db"}),
                build_pod("c1", "near", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pg",
                          affinity=Affinity(preferred_pod_affinity=[
                              (50.0, PodAffinityTerm(match_labels={"app": "db"}))])),
            ],
        )
        run_actions(cache, action_names=["allocate"])
        assert cache.binder.binds["c1/near"] == "n2"

    def test_preferred_pod_anti_affinity_avoids(self):
        from kube_batch_tpu.api.pod import Affinity, PodAffinityTerm
        cache = build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="pg", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node("n0", cpu=8000, mem=16 * GiB),
                   build_node("n1", cpu=8000, mem=16 * GiB)],
            pods=[
                build_pod("c1", "noisy", "n0", PodPhase.RUNNING,
                          {"cpu": 1000, "memory": GiB}, labels={"app": "noisy"}),
                build_pod("c1", "quiet", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="pg",
                          affinity=Affinity(preferred_pod_anti_affinity=[
                              (50.0, PodAffinityTerm(match_labels={"app": "noisy"}))])),
            ],
        )
        run_actions(cache, action_names=["allocate"])
        assert cache.binder.binds["c1/quiet"] == "n1"


class TestVolumeScenarios:
    """Standalone PV ledger behind the VolumeBinder seam (cache/volume.py;
    cache.go:189-209, 258-269 — AllocateVolumes can fail a node,
    BindVolumes consumes)."""

    def _cache_with_pv_binder(self, **kw):
        return _cache_with_pv_binder(**kw)

    def test_node_without_required_volume_is_skipped(self):
        """A pod claiming a node-local PV must land on the PV's node even
        when another node scores equally on resources."""
        from kube_batch_tpu.api.pod import PersistentVolume

        cache = self._cache_with_pv_binder(
            queues=["default"],
            nodes=[build_node("n1", cpu=8000, mem=16 * GiB),
                   build_node("n2", cpu=8000, mem=16 * GiB)],
            pods=[build_pod("c1", "dbpod", None, PodPhase.PENDING,
                            {"cpu": 1000, "memory": GiB},
                            volume_claims=("data-claim",))],
        )
        cache.volume_binder.add_pv(
            PersistentVolume(name="pv-local", node="n2", claim="data-claim"))
        run_actions(cache, action_names=["allocate"])
        assert cache.binder.binds["c1/dbpod"] == "n2"
        # the binding became durable at dispatch (BindVolumes)
        assert cache.volume_binder.bound == {"data-claim": "pv-local"}
        assert cache.volume_binder.reservations == {}

    def test_unsatisfiable_claim_fails_placement(self):
        from kube_batch_tpu.api.pod import PersistentVolume

        cache = self._cache_with_pv_binder(
            queues=["default"],
            nodes=[build_node("n1", cpu=8000, mem=16 * GiB)],
            pods=[build_pod("c1", "dbpod", None, PodPhase.PENDING,
                            {"cpu": 1000, "memory": GiB},
                            volume_claims=("ghost-claim",))],
        )
        cache.volume_binder.add_pv(
            PersistentVolume(name="pv-other", node="n1", claim="someone-else"))
        run_actions(cache, action_names=["allocate"])
        assert "c1/dbpod" not in cache.binder.binds

    def test_two_claimants_one_pv(self):
        """Two pods wanting the same pre-bound claim volume: exactly one may
        hold it (second claimant of the same PVC is a config error upstream;
        the ledger must still never double-book a PV)."""
        from kube_batch_tpu.api.pod import PersistentVolume

        cache = self._cache_with_pv_binder(
            queues=["default"],
            nodes=[build_node("n1", cpu=8000, mem=16 * GiB)],
            pods=[
                build_pod("c1", "a", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB},
                          volume_claims=("claim-a",)),
                build_pod("c1", "b", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB},
                          volume_claims=("claim-b",)),
            ],
        )
        # single wildcard PV: only one claim can take it
        cache.volume_binder.add_pv(PersistentVolume(name="pv1"))
        run_actions(cache, action_names=["allocate"])
        placed = [k for k in ("c1/a", "c1/b") if k in cache.binder.binds]
        assert len(placed) == 1
        assert len(cache.volume_binder.bound) == 1

    def test_allocate_volumes_idempotent_per_task(self):
        """The bulk-path volume pre-check followed by a demoted job's
        sequential replay re-allocates the same task: must not double-book."""
        from kube_batch_tpu.api.pod import PersistentVolume, Pod
        from kube_batch_tpu.cache.volume import StandalonePVBinder
        from kube_batch_tpu.api.task_info import TaskInfo
        from kube_batch_tpu.api.resources import DEFAULT_SPEC

        binder = StandalonePVBinder()
        binder.add_pv(PersistentVolume(name="pv1"))
        binder.add_pv(PersistentVolume(name="pv2"))
        pod = Pod(name="p", namespace="c1", requests={"cpu": 100},
                  volume_claims=("c",))
        task = TaskInfo(pod, DEFAULT_SPEC)
        binder.allocate_volumes(task, "n1")
        binder.allocate_volumes(task, "n1")  # replay — same reservation
        assert len(binder.reservations) == 1
        assert len(binder.reservations[task.uid]) == 1
        binder.allocate_volumes(task, "n2")  # moved host — superseded
        assert len(binder.reservations[task.uid]) == 1
        binder.bind_volumes(task)
        assert len(binder.bound) == 1 and binder.reservations == {}


class TestPDBGang:
    """PodDisruptionBudget as the legacy gang source (event_handlers.go:
    484-594): pods sharing a controller + a PDB on that controller form a
    gang with the PDB's min-available, in the default queue, with
    events-only status (job_updater.go:108-111)."""

    def test_gang_defined_only_by_pdb_schedules(self):
        from kube_batch_tpu.api.pod import PodDisruptionBudget

        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1", cpu=4000, mem=8 * GiB)],
        )
        cache.add_pdb(PodDisruptionBudget(
            name="pdb1", namespace="c1", min_available=3, owner="rs-1"))
        for i in range(3):
            cache.add_pod(build_pod("c1", f"w{i}", None, PodPhase.PENDING,
                                    {"cpu": 1000, "memory": GiB}, owner="rs-1"))
        job = cache.jobs["c1/rs-1"]
        assert job.pdb is not None and job.pod_group is None
        assert job.min_available == 3 and job.queue == "default"
        run_actions(cache, action_names=["allocate"])
        assert len(cache.binder.binds) == 3

    def test_pdb_gang_blocks_partial_placement(self):
        from kube_batch_tpu.api.pod import PodDisruptionBudget

        cache = build_cache(
            queues=["default"],
            nodes=[build_node("n1", cpu=2000, mem=8 * GiB)],  # fits only 2
        )
        cache.add_pdb(PodDisruptionBudget(
            name="pdb1", namespace="c1", min_available=3, owner="rs-1"))
        for i in range(3):
            cache.add_pod(build_pod("c1", f"w{i}", None, PodPhase.PENDING,
                                    {"cpu": 1000, "memory": GiB}, owner="rs-1"))
        run_actions(cache, action_names=["allocate"])
        assert len(cache.binder.binds) == 0  # all-or-nothing gang
        # events-only status: an Unschedulable event was recorded, and no
        # PodGroup status write happened for the PDB job
        assert any(kind == "Unschedulable" and key == "c1/rs-1"
                   for kind, key, _ in cache.events)

    def test_delete_pdb_releases_gang(self):
        from kube_batch_tpu.api.pod import PodDisruptionBudget

        cache = build_cache(queues=["default"],
                            nodes=[build_node("n1", cpu=2000, mem=8 * GiB)])
        pdb = PodDisruptionBudget(
            name="pdb1", namespace="c1", min_available=3, owner="rs-1")
        cache.add_pdb(pdb)
        for i in range(3):
            cache.add_pod(build_pod("c1", f"w{i}", None, PodPhase.PENDING,
                                    {"cpu": 1000, "memory": GiB}, owner="rs-1"))
        cache.delete_pdb(pdb)
        job = cache.jobs["c1/rs-1"]
        assert job.pdb is None
        # the gang constraint is gone: the pods re-shadow as singletons and
        # now schedule individually (2 of 3 fit the 2000m node)
        assert job.pod_group is not None and job.pod_group.shadow
        assert job.min_available == 1
        run_actions(cache, action_names=["allocate"])
        assert len(cache.binder.binds) == 2

    def test_pods_before_pdb_ordering(self):
        """Owner pods ingested BEFORE their PDB: the synthesized shadow
        PodGroup must yield to the PDB as the gang source."""
        from kube_batch_tpu.api.pod import PodDisruptionBudget

        cache = build_cache(queues=["default"],
                            nodes=[build_node("n1", cpu=2000, mem=8 * GiB)])
        for i in range(3):
            cache.add_pod(build_pod("c1", f"w{i}", None, PodPhase.PENDING,
                                    {"cpu": 1000, "memory": GiB}, owner="rs-1"))
        job = cache.jobs["c1/rs-1"]
        assert job.pod_group is not None and job.pod_group.shadow
        cache.add_pdb(PodDisruptionBudget(
            name="pdb1", namespace="c1", min_available=3, owner="rs-1"))
        assert job.pod_group is None and job.pdb is not None
        assert job.min_available == 3
        run_actions(cache, action_names=["allocate"])
        assert len(cache.binder.binds) == 0  # gang of 3 can't fit 2 slots

    def test_discarded_gang_releases_pv_reservations(self):
        """A gang that can't fully place must not hold PV reservations
        across cycles (Statement discard releases assumed volumes), so other
        claimants of the same wildcard PV still schedule."""
        from kube_batch_tpu.api.pod import PersistentVolume

        cache = _cache_with_pv_binder(
            queues=["default"],
            pod_groups=[PodGroup(name="gang2", namespace="c1", min_member=2,
                                 queue="default")],
            nodes=[build_node("n1", cpu=8000, mem=16 * GiB)],
            pods=[
                # task A: satisfiable claim; task B: unsatisfiable → the
                # gang discards every cycle
                build_pod("c1", "a", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="gang2",
                          volume_claims=("claim-a",)),
                build_pod("c1", "b", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB}, group_name="gang2",
                          volume_claims=("ghost",)),
                # independent singleton wanting the same wildcard PV
                build_pod("c1", "solo", None, PodPhase.PENDING,
                          {"cpu": 1000, "memory": GiB},
                          volume_claims=("claim-solo",)),
            ],
        )
        cache.volume_binder.add_pv(PersistentVolume(name="pv1"))
        run_actions(cache, action_names=["allocate"])
        assert "c1/a" not in cache.binder.binds  # gang blocked
        assert "c1/b" not in cache.binder.binds
        assert cache.binder.binds.get("c1/solo") == "n1"
        # no reservation lingers for the discarded gang
        assert cache.volume_binder.reservations == {}


class TestPreemptPhase2Divergence:
    """Pins the DECLARED divergence from the reference's preempt phase 2
    (PARITY.md "known divergences" / actions/preempt.py:104-131): the
    reference runs intra-job rebalancing unconditionally (preempt.go:145-174)
    and would evict an equal-rank running sibling to pipeline a pending one —
    zero-gain churn; this rebuild gates phase 2 on a task-order plugin
    verdict (or, with no voter, on the raw priority extremes) and SKIPS the
    equal-rank case. These tests pin both sides of the gate so a refactor
    cannot silently change the behavior."""

    def _cache(self, pending_priority):
        pods = [
            build_pod("c1", f"run-{i}", "n1", PodPhase.RUNNING,
                      {"cpu": 1000, "memory": GiB}, group_name="job",
                      priority=0)
            for i in range(2)
        ] + [
            build_pod("c1", "pend-0", None, PodPhase.PENDING,
                      {"cpu": 1000, "memory": GiB}, group_name="job",
                      priority=pending_priority)
        ]
        return build_cache(
            queues=["default"],
            pod_groups=[PodGroup(name="job", namespace="c1", min_member=1,
                                 queue="default")],
            nodes=[build_node("n1", cpu=2000, mem=16 * GiB)],  # full
            pods=pods,
        )

    def test_equal_rank_sibling_not_evicted(self):
        """The divergent case: the reference would evict a running sibling
        for the equal-priority pending task; the gate skips phase 2 and
        nothing happens."""
        cache = self._cache(pending_priority=0)
        run_actions(cache, action_names=["preempt"])
        assert len(cache.evictor.evicts) == 0
        assert len(cache.binder.binds) == 0

    def test_outranking_pending_task_preempts_sibling(self):
        """The gate's positive side (matching the reference): a pending task
        that outranks a running sibling via the priority plugin's task order
        evicts exactly one sibling and pipelines onto the freed capacity."""
        cache = self._cache(pending_priority=100)
        run_actions(cache, action_names=["preempt"])
        assert len(cache.evictor.evicts) == 1
        assert next(iter(cache.evictor.evicts)).startswith("c1/run-")
        # the preemptor pipelines (placed on Releasing capacity) — it binds
        # only after the eviction completes, so no bind yet this cycle
        assert len(cache.binder.binds) == 0

    def test_no_task_order_voter_falls_back_to_raw_priority(self):
        """With the priority plugin disabled (no task-order voter), the gate
        falls back to comparing raw priority extremes — still skipping the
        equal-rank case."""
        conf_no_priority = """
actions: "preempt"
tiers:
- plugins:
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: proportion
  - name: nodeorder
  - name: predicates
"""
        cache = self._cache(pending_priority=0)
        run_actions(cache, conf_text=conf_no_priority,
                    action_names=["preempt"])
        assert len(cache.evictor.evicts) == 0

    def test_reference_exact_restores_ungated_phase2(self):
        """`preempt.referenceExact: "true"` on any conf tier restores
        preempt.go:145-174's unconditional phase 2: the equal-rank pending
        sibling DOES evict a running one (the churn the gate avoids)."""
        conf_exact = """
actions: "preempt"
tiers:
- plugins:
  - name: priority
    arguments:
      preempt.referenceExact: "true"
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: proportion
  - name: nodeorder
  - name: predicates
"""
        cache = self._cache(pending_priority=0)
        run_actions(cache, conf_text=conf_exact, action_names=["preempt"])
        assert len(cache.evictor.evicts) == 1
        assert next(iter(cache.evictor.evicts)).startswith("c1/run-")


class TestReclaimReferenceExact:
    """`reclaim.referenceExact: "true"` disables the idle-fit claimant gate
    (the PARITY.md reclaim divergence): like reclaim.go:107-199, a
    cross-queue victim is evicted even when free capacity could satisfy the
    claimant."""

    def _cache(self):
        from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION, Node, Pod

        cache = build_cache(queues=[])
        from kube_batch_tpu.api.pod import Queue

        cache.add_queue(Queue(name="q0", weight=1))
        cache.add_queue(Queue(name="q1", weight=3))
        # free cpu for the claimant AND a cross-queue victim on the node
        cache.add_node(Node(name="n1", allocatable={
            "cpu": 4000.0, "memory": float(64 * GiB), "pods": 110.0}))
        cache.add_pod_group(PodGroup(name="r", namespace="b", min_member=1,
                                     queue="q0", creation_index=0))
        cache.add_pod(Pod(name="r", namespace="b",
                          requests={"cpu": 1000.0, "memory": float(GiB)},
                          annotations={GROUP_NAME_ANNOTATION: "r"},
                          phase=PodPhase.RUNNING, node_name="n1",
                          creation_index=0))
        cache.add_pod_group(PodGroup(name="p", namespace="b", min_member=1,
                                     queue="q1", creation_index=1))
        cache.add_pod(Pod(name="p", namespace="b",
                          requests={"cpu": 1000.0, "memory": float(GiB)},
                          annotations={GROUP_NAME_ANNOTATION: "p"},
                          phase=PodPhase.PENDING, creation_index=1))
        return cache

    CONF = """
actions: "reclaim, allocate, backfill"
tiers:
- plugins:
  - name: priority
{ARG}
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: proportion
  - name: nodeorder
  - name: predicates
"""

    def _run(self, cache, exact: bool):
        from kube_batch_tpu.framework.conf import parse_scheduler_conf
        from kube_batch_tpu.scheduler import Scheduler

        arg = ('    arguments:\n'
               '      reclaim.referenceExact: "true"') if exact else ""
        conf = parse_scheduler_conf(self.CONF.replace("{ARG}", arg))
        sched = Scheduler(cache, conf=conf)
        sched.run_once()
        cache.flush_binds()

    def test_gate_on_no_eviction(self):
        """Default: the claimant fits idle, so allocate places it and the
        victim survives (the declared improvement)."""
        cache = self._cache()
        self._run(cache, exact=False)
        assert not cache.evictor.evicts
        assert "b/p" in cache.binder.binds

    def test_reference_exact_evicts_like_the_reference(self):
        """With the escape hatch, reclaim evicts the cross-queue victim for
        the claimant even though free capacity could satisfy it —
        reclaim.go's exact behavior."""
        cache = self._cache()
        self._run(cache, exact=True)
        assert "b/r" in cache.evictor.evicts, cache.evictor.evicts


class TestRealRequestBackfill:
    """BEYOND-REFERENCE (backfill.go:87's own TODO): real-request tasks fill
    capacity stranded by host-side gang discards.  The batched solve gave
    the capacity to gang G; G's volume claims failed host-side and its
    Statement discarded, leaving the freed capacity stranded for the rest
    of the cycle.  The reference's backfill (BestEffort-only) could never
    perform this fill; ours re-solves over gang-safe claimants."""

    def _cache(self):
        pods = []
        # gang G: 4 x 1000m with unsatisfiable volume claims — the device
        # places it, the host volume pre-check demotes, the slow replay
        # discards (no PV exists anywhere)
        for i in range(4):
            pods.append(build_pod(
                "c1", f"g-{i}", None, PodPhase.PENDING,
                {"cpu": 1000, "memory": GiB}, group_name="g",
                volume_claims=("no-such-pv",),
            ))
        # singleton S, created later (worse rank): crowded out by G in the
        # main solve
        pods.append(build_pod("c1", "s-0", None, PodPhase.PENDING,
                              {"cpu": 1000, "memory": GiB}, group_name="s"))
        return _cache_with_pv_binder(
            queues=["default"],
            pod_groups=[
                PodGroup(name="g", namespace="c1", min_member=4,
                         queue="default", creation_index=1),
                PodGroup(name="s", namespace="c1", min_member=1,
                         queue="default", creation_index=2),
            ],
            nodes=[build_node("n1", cpu=4000, mem=16 * GiB)],
            pods=pods,
        )

    def test_stranded_capacity_backfilled(self):
        cache = self._cache()
        ssn = run_actions(cache, action_names=["allocate", "backfill"])
        from kube_batch_tpu.framework.interface import get_action

        assert get_action("allocate").last_host_discards == 1
        # the control signal backfill consumed rides the SESSION, not the
        # process-global action registry (round-5 ADVICE #5) — ≥1 because the
        # backfill helper replay's own discards accumulate on it too
        assert ssn.host_discards >= 1
        # G discarded entirely; S backfilled into the freed capacity
        assert set(cache.binder.binds) == {"c1/s-0"}
        assert not cache.evictor.evicts
        errs = cache.columns.check_consistency(cache)
        assert not errs, errs[:3]

    def test_flag_off_leaves_capacity_stranded(self):
        """`backfill.realRequests: "false"` restores the reference-shaped
        behavior: the stranded task waits for the next cycle."""
        conf_off = """
actions: "allocate, backfill"
tiers:
- plugins:
  - name: priority
    arguments:
      backfill.realRequests: "false"
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: proportion
  - name: nodeorder
  - name: predicates
"""
        cache = self._cache()
        run_actions(cache, conf_text=conf_off,
                    action_names=["allocate", "backfill"])
        assert not cache.binder.binds  # s-0 stranded until next cycle


class TestAllocateRunsOn:
    """A solve that spent its whole rounds x outer budget while still
    placing runs on once in the same cycle.  A packed cluster: every node
    has room for ONE of the pending pods and no two nodes score the same,
    so equal pods all bid for the one best-scored node and a round places
    one pod; 18 rounds cannot place more than 18 pods of the 32, with room
    for all of them free, and the reference's sequential loop, which has no
    round budget, would have reached that room in this cycle."""

    N = 32

    def _cache(self):
        nodes, pods, groups = [], [], []
        for i in range(self.N):
            nodes.append(build_node(f"n{i}", cpu=8000, mem=64 * GiB))
            # distinct scores: a filler of 100 m more on each node
            pods.append(build_pod(
                "c1", f"fill-{i}", f"n{i}", PodPhase.RUNNING,
                {"cpu": 1100 + 100 * i, "memory": GiB}, group_name="fill"))
        groups.append(PodGroup(name="fill", namespace="c1", min_member=1,
                               queue="default", creation_index=0))
        for g in range(8):
            groups.append(PodGroup(name=f"g{g}", namespace="c1",
                                   min_member=4, queue="default",
                                   creation_index=1 + g))
            for i in range(4):
                pods.append(build_pod(
                    "c1", f"g{g}-{i}", None, PodPhase.PENDING,
                    {"cpu": 3500, "memory": GiB}, group_name=f"g{g}"))
        return build_cache(queues=["default"], pod_groups=groups,
                           nodes=nodes, pods=pods)

    @staticmethod
    def _allocate_dispatches() -> float:
        from kube_batch_tpu.metrics import metrics as m

        return sum(v for k, v in m.SOLVE_DISPATCHES._values.items()
                   if k[0] == "allocate")

    def test_a_solve_out_of_rounds_runs_on_once(self):
        cache = self._cache()
        before = self._allocate_dispatches()
        run_actions(cache, action_names=["allocate"])
        assert self._allocate_dispatches() - before == 2
        from kube_batch_tpu.metrics import metrics as m

        assert m.ALLOCATE_RUNS_ON._values[()] >= 1
        # whole gangs only, one pod a node, and more than 18 rounds place
        binds = cache.binder.binds
        assert 18 < len(binds) <= 32 and len(binds) % 4 == 0
        assert len(set(binds.values())) == len(binds)
        errs = cache.columns.check_consistency(cache)
        assert not errs, errs[:3]

    def test_a_solve_inside_its_budget_is_not_repeated(self):
        cache = TestRealRequestBackfill()._cache()
        before = self._allocate_dispatches()
        run_actions(cache, action_names=["allocate"])
        assert self._allocate_dispatches() - before == 1

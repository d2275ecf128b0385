"""Kernel-level tests of the gang-constrained allocate solve.

These test *invariants*, not exact placements (SURVEY.md §7.3: the reference
randomizes tie-breaks itself, scheduler_helper.go:147-158): no node
overcommit, no committed partial gang, priority wins contention, overused
queues gain nothing.
"""

import numpy as np
import pytest

from kube_batch_tpu.api.cluster_info import ClusterInfo
from kube_batch_tpu.api.job_info import JobInfo
from kube_batch_tpu.api.node_info import NodeInfo
from kube_batch_tpu.api.pod import Node, Pod, PodGroup, Queue
from kube_batch_tpu.api.queue_info import QueueInfo
from kube_batch_tpu.api.resources import DEFAULT_SPEC
from kube_batch_tpu.api.snapshot import build_snapshot
from kube_batch_tpu.api.task_info import TaskInfo
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.ops.assignment import AllocateConfig, allocate_solve

GiB = 2**30


def build_cluster(nodes, jobs, queues=("default",)):
    """nodes: [(name, cpu_milli, mem)], jobs: [(name, queue, min_member,
    [(task, cpu, mem, prio)])]."""
    ci = ClusterInfo(DEFAULT_SPEC)
    for q in queues:
        name, weight = q if isinstance(q, tuple) else (q, 1)
        ci.queues[name] = QueueInfo(Queue(name=name, weight=weight))
    for name, cpu, mem in nodes:
        ni = NodeInfo(
            Node(name=name, allocatable={"cpu": cpu, "memory": mem, "pods": 110}),
            DEFAULT_SPEC,
        )
        ci.nodes[name] = ni
    for jname, queue, min_member, tasks in jobs:
        pg = PodGroup(name=jname, min_member=min_member, queue=queue)
        job = JobInfo(f"default/{jname}", DEFAULT_SPEC, pg)
        for tname, cpu, mem, prio in tasks:
            pod = Pod(name=f"{jname}-{tname}", requests={"cpu": cpu, "memory": mem},
                      priority=prio, phase=PodPhase.PENDING)
            job.add_task(TaskInfo(pod, DEFAULT_SPEC))
        ci.jobs[job.uid] = job
    return ci


def solve(ci, **kw):
    snap, meta = build_snapshot(ci)
    res = allocate_solve(snap, AllocateConfig(**kw))
    return snap, meta, res


def assert_no_overcommit(snap, res):
    assert np.all(np.asarray(res.node_idle) >= -np.asarray(snap.quanta)[None, :])
    assert np.all(np.asarray(res.node_releasing) >= -np.asarray(snap.quanta)[None, :])


class TestBasicAllocate:
    def test_single_job_fits(self):
        ci = build_cluster(
            nodes=[("n1", 4000, 8 * GiB)],
            jobs=[("j1", "default", 2, [(f"t{i}", 1000, 1 * GiB, 0) for i in range(2)])],
        )
        snap, meta, res = solve(ci)
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        assert np.all(assigned >= 0)
        assert not np.any(np.asarray(res.pipelined)[: meta.n_tasks])
        assert_no_overcommit(snap, res)

    def test_spreads_across_nodes_when_needed(self):
        # 4 tasks × 3000m on 2 × 8000m nodes → 2+2 split required
        ci = build_cluster(
            nodes=[("n1", 8000, 16 * GiB), ("n2", 8000, 16 * GiB)],
            jobs=[("j1", "default", 4, [(f"t{i}", 3000, 1 * GiB, 0) for i in range(4)])],
        )
        snap, meta, res = solve(ci)
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        assert np.all(assigned >= 0)
        counts = np.bincount(assigned, minlength=2)
        assert counts.max() == 2  # 3 × 3000m would overcommit
        assert_no_overcommit(snap, res)

    def test_padding_rows_never_assigned(self):
        ci = build_cluster(
            nodes=[("n1", 4000, 8 * GiB)],
            jobs=[("j1", "default", 1, [("t0", 1000, GiB, 0)])],
        )
        snap, meta, res = solve(ci)
        assert np.all(np.asarray(res.assigned)[meta.n_tasks:] == -1)


class TestGang:
    def test_partial_gang_discarded(self):
        # minMember=3 but capacity for 2 → nothing committed (Statement.Discard)
        ci = build_cluster(
            nodes=[("n1", 2000, 8 * GiB)],
            jobs=[("j1", "default", 3, [(f"t{i}", 1000, GiB, 0) for i in range(3)])],
        )
        snap, meta, res = solve(ci)
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        assert np.all(assigned == -1)
        assert not np.asarray(res.committed)[: meta.n_jobs].any()
        # idle fully restored
        np.testing.assert_allclose(
            np.asarray(res.node_idle), np.asarray(snap.node_idle)
        )

    def test_gang_off_commits_partial(self):
        ci = build_cluster(
            nodes=[("n1", 2000, 8 * GiB)],
            jobs=[("j1", "default", 3, [(f"t{i}", 1000, GiB, 0) for i in range(3)])],
        )
        snap, meta, res = solve(ci, gang=False)
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        assert (assigned >= 0).sum() == 2

    def test_discarded_gang_frees_resources_for_smaller_job(self):
        # big gang (min 4, only 3 fit) must not starve the small job (min 1)
        ci = build_cluster(
            nodes=[("n1", 3000, 8 * GiB)],
            jobs=[
                ("big", "default", 4, [(f"t{i}", 1000, GiB, 10) for i in range(4)]),
                ("small", "default", 1, [("t0", 1000, GiB, 0)]),
            ],
        )
        snap, meta, res = solve(ci)
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        job_of = np.asarray(snap.task_job)[: meta.n_tasks]
        big_idx = meta.job_uids.index("default/big")
        small_idx = meta.job_uids.index("default/small")
        assert np.all(assigned[job_of == big_idx] == -1)
        assert np.all(assigned[job_of == small_idx] >= 0)

    def test_two_gangs_contending(self):
        # two min=2 gangs, capacity 3 → exactly one gang commits fully
        ci = build_cluster(
            nodes=[("n1", 3000, 8 * GiB)],
            jobs=[
                ("a", "default", 2, [(f"t{i}", 1000, GiB, 0) for i in range(2)]),
                ("b", "default", 2, [(f"t{i}", 1000, GiB, 0) for i in range(2)]),
            ],
        )
        snap, meta, res = solve(ci)
        committed = np.asarray(res.committed)[: meta.n_jobs]
        assert committed.sum() == 1
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        job_of = np.asarray(snap.task_job)[: meta.n_tasks]
        winner = np.flatnonzero(committed)[0]
        assert (assigned[job_of == winner] >= 0).sum() == 2
        assert np.all(assigned[job_of != winner] == -1)


class TestPriorityAndFairness:
    def test_high_priority_job_wins_contention(self):
        ci = build_cluster(
            nodes=[("n1", 2000, 8 * GiB)],
            jobs=[
                ("lo", "default", 2, [(f"t{i}", 1000, GiB, 0) for i in range(2)]),
                ("hi", "default", 2, [(f"t{i}", 1000, GiB, 0) for i in range(2)]),
            ],
        )
        for uid, prio in [("default/lo", 1), ("default/hi", 100)]:
            ci.jobs[uid].priority = prio
        snap, meta, res = solve(ci)
        job_of = np.asarray(snap.task_job)[: meta.n_tasks]
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        hi = meta.job_uids.index("default/hi")
        assert np.all(assigned[job_of == hi] >= 0)
        assert np.all(assigned[job_of != hi] == -1)

    def test_proportion_shares_capacity_between_queues(self):
        # 2 queues, weight 1:1, cluster 4000m; each queue requests 4000m →
        # each deserves ~2000m → 2 tasks each
        ci = build_cluster(
            nodes=[("n1", 4000, 32 * GiB)],
            queues=[("qa", 1), ("qb", 1)],
            jobs=[
                ("ja", "qa", 1, [(f"t{i}", 1000, GiB, 0) for i in range(4)]),
                ("jb", "qb", 1, [(f"t{i}", 1000, GiB, 0) for i in range(4)]),
            ],
        )
        snap, meta, res = solve(ci)
        job_of = np.asarray(snap.task_job)[: meta.n_tasks]
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        ja = meta.job_uids.index("default/ja")
        a_placed = (assigned[job_of == ja] >= 0).sum()
        b_placed = (assigned[job_of != ja] >= 0).sum()
        assert a_placed == 2 and b_placed == 2

    def test_weighted_queues(self):
        # weight 3:1 over 4000m → 3000/1000 split
        ci = build_cluster(
            nodes=[("n1", 4000, 32 * GiB)],
            queues=[("qa", 3), ("qb", 1)],
            jobs=[
                ("ja", "qa", 1, [(f"t{i}", 1000, GiB, 0) for i in range(4)]),
                ("jb", "qb", 1, [(f"t{i}", 1000, GiB, 0) for i in range(4)]),
            ],
        )
        snap, meta, res = solve(ci)
        job_of = np.asarray(snap.task_job)[: meta.n_tasks]
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        ja = meta.job_uids.index("default/ja")
        assert (assigned[job_of == ja] >= 0).sum() == 3
        assert (assigned[job_of != ja] >= 0).sum() == 1


class TestDistributed:
    def test_initialize_noop_single_process(self):
        import jax

        from kube_batch_tpu.parallel.distributed import global_mesh, initialize
        initialize()  # single-process: must not raise
        assert jax.process_count() == 1
        mesh = global_mesh()
        # the global mesh spans EVERY visible device (the follower-host
        # contribution path)
        assert mesh.devices.size == len(jax.devices())
        assert mesh.axis_names == ("nodes",)


class TestShardedSolveAgreement:
    @pytest.mark.slow
    def test_sharded_solve_matches_single_device(self):
        """The mesh-sharded solve (node axis over 8 virtual devices,
        parallel/mesh.py) must produce EXACTLY the single-device assignment —
        GSPMD partitioning is an execution detail, not a semantic one."""
        import jax

        from kube_batch_tpu.parallel.mesh import call, make_mesh, program
        from kube_batch_tpu.testing.synthetic import synthetic_device_snapshot

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual CPU mesh")
        snap, meta = synthetic_device_snapshot(n_tasks=2000, n_nodes=512,
                                               gang_size=5, n_queues=3)
        cfg = AllocateConfig()
        single = allocate_solve(snap, cfg)
        mesh = make_mesh(8)
        sharded = call(program("full", mesh, None, cfg), mesh, snap)
        s_a = np.asarray(single.assigned)[: meta.n_tasks]
        m_a = np.asarray(sharded.assigned)[: meta.n_tasks]
        np.testing.assert_array_equal(s_a, m_a)
        np.testing.assert_array_equal(
            np.asarray(single.pipelined)[: meta.n_tasks],
            np.asarray(sharded.pipelined)[: meta.n_tasks],
        )
        np.testing.assert_allclose(
            np.asarray(single.node_idle), np.asarray(sharded.node_idle),
            rtol=1e-5, atol=1e-3,
        )
        assert (s_a >= 0).sum() > 0  # non-vacuous
        # the lazy fit-error histogram's sharded twin (failure cycles in
        # sharded mode dispatch it) must match the single-device one
        from kube_batch_tpu.ops.assignment import failure_histogram_solve

        np.testing.assert_array_equal(
            np.asarray(failure_histogram_solve(snap)),
            np.asarray(call(
                program("fail_hist", mesh, None, None), mesh, snap)),
        )


class TestOuterLoopContinuation:
    def test_capped_rounds_continue_across_outer_passes(self):
        """The outer while_loop must keep going when the bidding rounds hit
        their cap while still placing (regression: an early exit gated only
        on gang reverts dropped placeable tasks with rounds=1)."""
        n = 6
        ci = build_cluster(
            nodes=[(f"n{i}", 1000, 2 * GiB) for i in range(n)],
            jobs=[(f"j{i}", "default", 1, [("t", 1000, GiB, 0)])
                  for i in range(n)],
        )
        # rounds=1: every outer pass places at most one bidding round's worth;
        # with identical scores the argmax herds and conflicts leave tasks
        # unplaced each round — only outer continuation finishes the set
        snap, meta, res = solve(ci, rounds=1, outer=8)
        assigned = np.asarray(res.assigned)[: meta.n_tasks]
        assert (assigned >= 0).all(), assigned
        assert_no_overcommit(snap, res)


def _herding_gangs(n_nodes, n_gangs, size=4):
    """``n_gangs`` gangs of ``size`` one-core tasks over one-core nodes:
    identical scores, so bidders herd, a node admits one of them a round,
    and placing everyone takes several rounds however much room there is."""
    return build_cluster(
        nodes=[(f"n{i:03d}", 1000, 64 * GiB) for i in range(n_nodes)],
        jobs=[(f"g{i:03d}", "default", size,
               [(f"t{k}", 1000, GiB, 0) for k in range(size)])
              for i in range(n_gangs)],
    )


def _members_placed(snap, meta, res):
    """[n_jobs] placed members of each job."""
    assigned = np.asarray(res.assigned)[: meta.n_tasks]
    job_of = np.asarray(snap.task_job)[: meta.n_tasks]
    return np.bincount(job_of[assigned >= 0], minlength=meta.n_jobs)


class TestCappedPassCarriesItsPlacements:
    """A pass whose bidding rounds ended only because the round counter ran
    out, while the last round still placed, discards nothing: gangs that
    are half way are carried into the next pass.  Only when bidding has
    stopped (no progress, budget spent, nothing left to bid) is a gang
    below MinAvailable reverted and failed for the cycle."""

    def test_half_placed_gangs_finish_in_the_next_pass(self):
        # room for all 8 gangs five times over; 2 rounds a pass place about
        # three quarters of the 32 members, so the first pass ends capped
        # with gangs half way (before: those were reverted AND failed, and
        # the solve ended after one pass with 5 of 8 gangs placed)
        snap, meta, res = solve(_herding_gangs(40, 8), rounds=2, outer=3)
        assert (_members_placed(snap, meta, res) == 4).all()
        assert np.asarray(res.committed)[: meta.n_jobs].all()
        assert 2 < int(res.rounds_run) <= 6
        assert_no_overcommit(snap, res)

    def test_spent_budget_leaves_every_gang_whole_or_absent(self):
        # the same herd with 2 rounds in all: the last pass is capped too,
        # and it must still discard — no partial gang survives the budget
        snap, meta, res = solve(_herding_gangs(40, 8), rounds=1, outer=2)
        placed = _members_placed(snap, meta, res)
        assert set(placed.tolist()) <= {0, 4}, placed
        assert 0 < (placed == 4).sum() < 8, placed  # the budget did bite
        np.testing.assert_array_equal(
            np.asarray(res.committed)[: meta.n_jobs], placed == 4)
        assert int(res.rounds_run) == 2
        # what the reverted members had taken is free again
        idle = np.asarray(res.node_idle)[:40, 0]
        assert (idle == 0).sum() == placed.sum(), (idle, placed)
        assert_no_overcommit(snap, res)

    def test_unreachable_min_member_is_discarded_after_a_capped_pass(self):
        # minMember 3 with 2 members: one round places both (so the pass
        # ends capped, with progress) and leaves nothing pending.  The loop
        # may not end on a pass that skipped the discard.
        ci = build_cluster(
            nodes=[(f"n{i}", 4000, 8 * GiB) for i in range(2)],
            jobs=[("short", "default", 3,
                   [(f"t{i}", 1000, GiB, 0) for i in range(2)])],
        )
        snap, meta, res = solve(ci, rounds=1, outer=3)
        assert (np.asarray(res.assigned)[: meta.n_tasks] == -1).all()
        assert not np.asarray(res.committed)[: meta.n_jobs].any()
        np.testing.assert_allclose(
            np.asarray(res.node_idle), np.asarray(snap.node_idle))

    @pytest.mark.parametrize("rounds,outer", [
        (1, 1), (1, 3), (2, 2), (2, 3), (3, 3), (6, 3)])
    def test_rounds_run_stays_inside_the_budget(self, rounds, outer):
        # more gangs than nodes can hold (12 x 4 members, 40 slots): some
        # budget runs dry half way whatever it is
        snap, meta, res = solve(_herding_gangs(40, 12), rounds=rounds,
                                outer=outer)
        assert 0 < int(res.rounds_run) <= rounds * outer
        placed = _members_placed(snap, meta, res)
        assert set(placed.tolist()) <= {0, 4}, placed
        assert_no_overcommit(snap, res)


def _prefer_last_node_row(snap):
    """Module-level custom score row (jit-cache friendly): strongly prefer
    the highest live node index."""
    import jax.numpy as jnp

    N = snap.node_alloc.shape[0]
    col = jnp.where(snap.node_valid, jnp.arange(N, dtype=jnp.float32), 0.0)
    return jnp.broadcast_to(col[None, :], (snap.task_req.shape[0], N)) * 100.0


class TestScoreRowExtensionSeam:
    def test_custom_row_changes_placement(self):
        """The session_plugins.go:392-492 extension surface at the tensor
        level: a registered device score row must actually steer the solve."""
        from kube_batch_tpu.ops.scoring import ScoreWeights

        ci = build_cluster(
            nodes=[(f"n{i}", 64000, 64 * GiB) for i in range(4)],
            jobs=[(f"j{i}", "default", 1, [("t", 1000, GiB, 0)])
                  for i in range(8)],
        )
        # baseline: least-requested spreads the 8 tasks across empty nodes
        snap, meta, base = solve(ci)
        base_nodes = set(np.asarray(base.assigned)[: meta.n_tasks].tolist())
        assert len(base_nodes) > 1

        ci2 = build_cluster(
            nodes=[(f"n{i}", 64000, 64 * GiB) for i in range(4)],
            jobs=[(f"j{i}", "default", 1, [("t", 1000, GiB, 0)])
                  for i in range(8)],
        )
        snap2, meta2, custom = solve(
            ci2,
            weights=ScoreWeights(
                extra_rows=(("prefer-last", _prefer_last_node_row, 1.0),)
            ),
        )
        assigned = np.asarray(custom.assigned)[: meta2.n_tasks]
        # the custom row dominates the bounded 0..10 built-ins: every task
        # lands on the last live node (it has capacity for all 8)
        last = max(
            int(i) for i, name in enumerate(meta2.node_names)
            if name
        )
        assert np.all(assigned == last), assigned
        assert_no_overcommit(snap2, custom)

    def test_session_level_registration(self):
        """A plugin registering through Session.add_score_row changes real
        action placement end-to-end."""
        from kube_batch_tpu import actions as _a  # noqa: F401
        from kube_batch_tpu import plugins as _p  # noqa: F401
        from kube_batch_tpu.framework.conf import load_scheduler_conf
        from kube_batch_tpu.framework.interface import get_action
        from kube_batch_tpu.framework.session import close_session, open_session
        from kube_batch_tpu.testing.synthetic import synthetic_cluster

        cache = synthetic_cluster(n_tasks=16, n_nodes=4, gang_size=1, n_queues=1)
        conf = load_scheduler_conf(None)
        ssn = open_session(cache, conf.tiers)
        ssn.add_score_row("prefer-last", _prefer_last_node_row, weight=1.0)
        get_action("allocate").execute(ssn)
        close_session(ssn)
        cache.flush_binds()
        hosts = set(cache.binder.binds.values())
        # every task funneled onto one node (nodes are big enough)
        assert hosts == {"n3"}, hosts

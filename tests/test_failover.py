"""Warm-standby leader failover: the surviving per-cycle device-resident
cache is revalidated (version token + check_consistency) against the
pod-store rebuild and KEPT — post-failover cycles are bit-exact with the
host columns and pay no cold re-upload; only a failed revalidation
cold-starts. Plus the cmd/server warm-standby re-contend loop."""

from __future__ import annotations

import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.pod import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    Queue,
)
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.framework.interface import get_action
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.scheduler import Scheduler
from kube_batch_tpu.sim import kubelet as kl
from kube_batch_tpu.testing.synthetic import GiB


def _mk_cache(n_nodes=6):
    cache = SchedulerCache()
    # realistic axis capacities so the scatter-delta path engages (micro
    # columns rightly prefer whole-column uploads) — same sizing rationale
    # as test_snapshot_delta's round-trip test
    cache.columns.reserve(n_tasks=2048, n_nodes=128, n_jobs=512)
    for q in range(2):
        cache.add_queue(Queue(name=f"q{q}", uid=f"uq{q}", weight=q + 1))
    for i in range(n_nodes):
        cache.add_node(Node(
            name=f"n{i}",
            allocatable={"cpu": 16000.0, "memory": 64 * GiB, "pods": 110.0},
        ))
    return cache


def _add_gang(cache, serial, size=2):
    g = f"g{serial}"
    cache.add_pod_group(PodGroup(
        name=g, namespace="fo", uid=f"pg-{g}", min_member=size,
        queue=f"q{serial % 2}", creation_index=serial,
    ))
    for k in range(size):
        cache.add_pod(Pod(
            name=f"{g}-{k}", namespace="fo", uid=f"pod-{g}-{k}",
            requests={"cpu": 500.0, "memory": 1 * GiB},
            annotations={GROUP_NAME_ANNOTATION: g},
            phase=PodPhase.PENDING,
            creation_index=serial * 100 + k,
        ))


def _add_gang_cpu(cache, serial, size=2, cpu=500.0):
    """_add_gang with a per-gang cpu request (heterogeneous occupancy for
    the crash-recovery bit-exactness test)."""
    g = f"g{serial}"
    cache.add_pod_group(PodGroup(
        name=g, namespace="fo", uid=f"pg-{g}", min_member=size,
        queue=f"q{serial % 2}", creation_index=serial,
    ))
    for k in range(size):
        cache.add_pod(Pod(
            name=f"{g}-{k}", namespace="fo", uid=f"pod-{g}-{k}",
            requests={"cpu": cpu, "memory": 1 * GiB},
            annotations={GROUP_NAME_ANNOTATION: g},
            phase=PodPhase.PENDING,
            creation_index=serial * 100 + k,
        ))


def _cycle(cache, conf, check_resident=False):
    """One real scheduling cycle; optionally assert the device-resident
    per-cycle columns are bit-exact with the freshly built host columns."""
    from kube_batch_tpu.api.resident import SWAP_FIELDS

    ssn = open_session(cache, conf.tiers)
    try:
        if check_resident:
            cols = cache.columns
            snap, _meta = cols.device_snapshot(ssn)
            swapped = cols.per_cycle_resident(snap)
            for field in SWAP_FIELDS:
                host = np.asarray(getattr(snap, field))
                dev = np.asarray(getattr(swapped, field))
                assert np.array_equal(host, dev), (
                    f"device-resident {field} diverged post-failover"
                )
        for name in conf.actions:
            get_action(name).execute(ssn)
    finally:
        close_session(ssn)
    cache.flush_binds()


def _warm_resident(cache, conf, cycles=6):
    """Run enough churny cycles that the per-cycle device cache exists and
    the scatter path has engaged."""
    for i in range(cycles):
        _add_gang(cache, serial=i + 1)
        _cycle(cache, conf)
        # progress some pods so statuses churn
        for key in sorted(cache.pods)[: 2]:
            pod = cache.pods[key]
            if pod.node_name and pod.phase == PodPhase.PENDING:
                kl.set_running(cache, key, pod.node_name)
    rc = cache.columns._per_cycle_dev.get(None)
    assert rc is not None and rc.version > 0
    return rc


class TestWarmStandbyRevalidation:
    def test_warm_failover_keeps_resident_cache_bit_exact(self):
        """The acceptance path: after failover_recover the SAME resident
        cache object serves (compiled executables + buffers kept), the next
        cycle is bit-exact vs the host columns, and its upload counters
        move like any steady-state cycle — NOT like a cold start."""
        conf = load_scheduler_conf(None)
        cache = _mk_cache()
        rc = _warm_resident(cache, conf)

        # baseline: what a normal steady-state cycle adds in full uploads
        # (tiny columns legitimately prefer whole-column re-uploads)
        pre = rc.counters()
        _add_gang(cache, serial=100)
        _cycle(cache, conf)
        steady_delta = rc.counters()["full_uploads"] - pre["full_uploads"]

        report = cache.failover_recover()
        assert report["mode"] == "warm", report
        assert report["resident_tokens"]["single"] > 0
        # identity: the cache OBJECT survived — nothing was recompiled
        assert cache.columns._per_cycle_dev.get(None) is rc

        before = rc.counters()
        _cycle(cache, conf, check_resident=True)
        after = rc.counters()
        post_failover_delta = after["full_uploads"] - before["full_uploads"]
        # the first post-failover cycle costs no more than an ordinary
        # steady-state cycle — and far less than a cold start (which pays
        # one full upload per per-cycle field)
        from kube_batch_tpu.api.resident import SWAP_FIELDS

        assert post_failover_delta <= steady_delta, (
            f"warm failover re-uploaded: {post_failover_delta} vs "
            f"steady {steady_delta}"
        )
        assert post_failover_delta < len(SWAP_FIELDS)
        assert cache.columns.check_consistency(cache) == []

    def test_cold_start_for_comparison_re_uploads_everything(self):
        """The cold path the warm standby avoids: dropping residency makes
        the next cycle full-upload every per-cycle field."""
        from kube_batch_tpu.api.resident import SWAP_FIELDS

        conf = load_scheduler_conf(None)
        cache = _mk_cache()
        _warm_resident(cache, conf)
        cache.columns.drop_resident()
        assert cache.columns._per_cycle_dev == {}
        _cycle(cache, conf, check_resident=True)
        rc = cache.columns._per_cycle_dev.get(None)
        assert rc is not None
        assert rc.counters()["full_uploads"] >= len(SWAP_FIELDS)

    def test_failed_revalidation_cold_starts(self, monkeypatch):
        conf = load_scheduler_conf(None)
        cache = _mk_cache()
        _warm_resident(cache, conf)
        monkeypatch.setattr(
            cache.columns.__class__, "check_consistency",
            lambda self, c: ["planted inconsistency"],
        )
        report = cache.failover_recover()
        assert report["mode"] == "cold"
        assert report["errors"] == ["planted inconsistency"]
        assert cache.columns._per_cycle_dev == {}

    def test_unsynced_resident_cache_never_survives(self):
        """A resident cache that never synced a snapshot (version token 0)
        has mirrors of unknown provenance — revalidation must drop it."""
        from kube_batch_tpu.api.resident import PerCycleDeviceCache

        cache = _mk_cache()
        cache.columns._per_cycle_dev[None] = PerCycleDeviceCache()
        report = cache.columns.revalidate_resident(cache)
        assert report["mode"] == "cold"
        assert cache.columns._per_cycle_dev == {}

    def test_failover_flushes_quarantine(self):
        """The new leader's rebuilt state supersedes the old reign's
        failure history — shelved tasks get a fresh start."""
        conf = load_scheduler_conf(None)
        cache = _mk_cache()
        _warm_resident(cache, conf)
        cache.resync.poison_after = 1

        class Exploding:
            def bind(self, pod, hostname):
                raise RuntimeError("down")

        cache.binder = Exploding()
        _add_gang(cache, serial=50, size=1)
        _cycle(cache, conf)
        cache.process_resync_tasks()
        cache.process_resync_tasks()
        assert cache.resync.quarantined
        cache.failover_recover()
        assert cache.resync.quarantined == {}


class TestWarmStandbyLoop:
    def test_lost_lease_recovers_and_recontends(self, monkeypatch):
        """run_warm_standby: reign 1 loses the lease (LostLeadership), the
        loop resets the elector, reign 2 recovers through failover_recover
        and schedules again — same process, no crash."""
        from kube_batch_tpu.cmd.leader_election import LostLeadership
        from kube_batch_tpu.cmd.server import run_warm_standby

        cache = _mk_cache()
        recoveries = []
        monkeypatch.setattr(
            cache, "failover_recover",
            lambda: recoveries.append(1) or {"mode": "warm",
                                             "resident_tokens": {},
                                             "errors": []},
        )
        sched = Scheduler(cache, conf=load_scheduler_conf(None),
                          schedule_period=0.0)
        sched.on_cycle_end = sched.stop  # each reign runs exactly one cycle

        class StubElector:
            def __init__(self):
                self.runs = 0
                self.resets = 0

            def run(self, lead, on_stopped_leading=None):
                self.runs += 1
                if self.runs == 1:
                    raise LostLeadership("reign 1 lost the lease")
                lead()

            def reset(self):
                self.resets += 1

        elector = StubElector()
        run_warm_standby(elector, sched, cache, max_takeovers=3)
        assert elector.runs == 2 and elector.resets == 1
        assert recoveries == [1]  # reign 2 recovered before its first cycle

    def test_elector_reset_rearms_for_the_same_process(self, tmp_path):
        from kube_batch_tpu.cmd.leader_election import LeaderElector

        e = LeaderElector(str(tmp_path), identity="a")
        e.release()
        assert e._stop.is_set()
        e.reset()
        assert not e._stop.is_set() and e._renew_thread is None

    def test_scheduler_rearms_after_stop(self):
        """run_forever must be re-enterable after stop() — the standby's
        second reign reuses the same Scheduler object."""
        cache = _mk_cache()
        sched = Scheduler(cache, conf=load_scheduler_conf(None),
                          schedule_period=0.0)
        sched.on_cycle_end = sched.stop
        sched.run_forever()   # reign 1: one cycle then stop
        sched.run_forever()   # reign 2 must actually run, not exit at once
        assert sched._stop    # stopped again via on_cycle_end


@pytest.mark.parametrize("seed", [0])
def test_failover_mid_churn_open_state_matches_full_view(seed):
    """After a failover rebuild, the next session open hands out exactly
    what a from-scratch session_view derives (the delta machinery was
    invalidated by the rebuild, not corrupted by it)."""
    conf = load_scheduler_conf(None)
    cache = _mk_cache()
    _warm_resident(cache, conf)
    cache.failover_recover()
    ssn = open_session(cache, conf.tiers)
    try:
        expected = cache.session_view()
        assert set(ssn.jobs) | {j.uid for j in ssn.gate_dropped_jobs} \
            == set(expected.jobs)
    finally:
        close_session(ssn)


# ==========================================================================
# crash recovery: save → process "restart" → load → warm revalidate
# (guard-plane PR satellite)
# ==========================================================================


class TestCrashRecovery:
    """cache/persistence.py save → a fresh process's load →
    ``failover_recover`` warm revalidation, under randomized churn with
    in-flight binds: the next cycle must be BIT-EXACT against the
    uninterrupted run, and no pod may regress to Pending after an acked
    bind."""

    CONF = None  # shipped 5-action conf (enqueue re-promotes parked jobs)

    @classmethod
    def _conf(cls):
        if cls.CONF is None:
            from kube_batch_tpu.framework.conf import shipped_conf_path

            cls.CONF = load_scheduler_conf(shipped_conf_path())
        return cls.CONF

    def _full_cycle(self, cache):
        conf = self._conf()
        ssn = open_session(cache, conf.tiers)
        ssn.action_names = list(conf.actions)
        try:
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        # binds stay IN FLIGHT here (async binder pool) — the save must
        # drain them itself so the state file can't miss a just-acked bind

    def _churn(self, cache, rng, serial):
        """One churn step: new gangs with HETEROGENEOUS requests (node
        occupancies then differ everywhere, so scores are strictly
        ordered and no decision ever falls to the row-keyed tie-break —
        the restart's row permutation must not be able to change a
        decision), plus random progressions of bound pods to RUNNING."""
        for g in range(int(rng.integers(1, 3))):
            size = int(rng.integers(1, 4))
            cpu = 300.0 + 97.0 * serial + 31.0 * g
            _add_gang_cpu(cache, serial=serial * 10 + g, size=size, cpu=cpu)
        for key in sorted(cache.pods):
            pod = cache.pods[key]
            if pod.node_name and pod.phase == PodPhase.PENDING and rng.random() < 0.4:
                kl.set_running(cache, key, pod.node_name)

    def test_restart_recovers_bit_exact_with_no_bind_regression(
        self, tmp_path
    ):
        from kube_batch_tpu.cache.persistence import load_state, save_state

        path = str(tmp_path / "state.json")
        rng = np.random.default_rng(7)
        cache_a = _mk_cache()
        for serial in range(1, 6):
            self._churn(cache_a, rng, serial)
            self._full_cycle(cache_a)
        # save mid-stream: binds dispatched by the last cycle are still in
        # flight on the async binder — save_state drains them first
        save_state(cache_a, path)
        acked = {k: p.node_name for k, p in cache_a.pods.items()
                 if p.node_name}
        assert acked, "churn must have produced acked binds"

        # "restart": a brand-new process's cache, re-listed from the state
        # file, then warm-revalidated exactly like the standby takeover
        cache_b = SchedulerCache()
        cache_b.columns.reserve(n_tasks=2048, n_nodes=128, n_jobs=512)
        assert load_state(cache_b, path)
        report = cache_b.failover_recover()
        assert report.get("errors", []) == []

        # no pod regresses to Pending after an acked bind: every acked
        # placement survives the restart with its node intact
        for key, node in acked.items():
            restored = cache_b.pods[key]
            assert restored.node_name == node, (
                f"{key} lost its acked bind across the restart"
            )
        from kube_batch_tpu.api.types import TaskStatus as TS

        for job in cache_b.jobs.values():
            for t in job.tasks.values():
                if t.uid in {cache_a.pods[k].uid for k in acked}:
                    assert t.status != TS.PENDING

        # identical next-cycle input on both sides
        for c in (cache_a, cache_b):
            _add_gang_cpu(c, serial=999, size=2, cpu=777.0)

        # the next cycle's SOLVE INPUT is bit-exact UP TO the row
        # permutation the pod-store rebuild introduces (the row allocator
        # re-deals rows; every per-task column gathered through the
        # uid→row maps must agree exactly)
        conf = self._conf()
        ssn_a = open_session(cache_a, conf.tiers)
        ssn_b = open_session(cache_b, conf.tiers)
        try:
            snap_a, meta_a = cache_a.columns.device_snapshot(ssn_a)
            snap_b, meta_b = cache_b.columns.device_snapshot(ssn_b)
            assert meta_a.n_tasks == meta_b.n_tasks
            row_a = {
                t.pod.uid: r
                for r, t in enumerate(cache_a.columns.task_by_row)
                if t is not None
            }
            row_b = {
                t.pod.uid: r
                for r, t in enumerate(cache_b.columns.task_by_row)
                if t is not None
            }
            assert sorted(row_a) == sorted(row_b)
            uids = sorted(row_a)
            pa = np.asarray([row_a[u] for u in uids])
            pb = np.asarray([row_b[u] for u in uids])
            from kube_batch_tpu.api.types import TaskStatus as TS

            def canon_status(arr):
                # a restored acked bind is BOUND where the uninterrupted
                # process still shows BINDING (its ack just landed) — the
                # documented restart collapse; both are ready/allocated
                # states and decision-equivalent.  PENDING is what must
                # never appear for an acked bind (asserted above).
                out = np.array(arr)
                out[out == int(TS.BINDING)] = int(TS.BOUND)
                return out

            for field in ("task_req", "task_resreq", "task_prio",
                          "task_status", "task_valid", "task_pending",
                          "task_best_effort", "task_creation"):
                a = np.asarray(getattr(snap_a, field))[pa]
                b = np.asarray(getattr(snap_b, field))[pb]
                if field == "task_status":
                    a, b = canon_status(a), canon_status(b)
                assert np.array_equal(a, b), (
                    f"snapshot column {field} diverged across the restart"
                )
            # node columns are permutation-free (insertion order replays)
            for field in ("node_idle", "node_releasing", "node_used",
                          "node_alloc", "node_valid", "node_sched"):
                a = np.asarray(getattr(snap_a, field))
                b = np.asarray(getattr(snap_b, field))
                assert np.array_equal(a, b), (
                    f"snapshot column {field} diverged across the restart"
                )
        finally:
            close_session(ssn_a)
            close_session(ssn_b)

        # and the next cycle's DECISIONS are identical: same binds for the
        # new gang, same post-cycle statuses for every task
        before_a = dict(cache_a.binder.binds)
        self._full_cycle(cache_a)
        cache_a.flush_binds()
        self._full_cycle(cache_b)
        cache_b.flush_binds()
        new_a = {k: v for k, v in cache_a.binder.binds.items()
                 if k not in before_a}
        new_b = dict(cache_b.binder.binds)  # fresh binder: all new
        assert new_a and new_a == new_b
        from kube_batch_tpu.api.types import TaskStatus as TS2

        def canon(st):
            return TS2.BOUND if st == TS2.BINDING else st

        status_a = {
            t.uid: canon(t.status)
            for j in cache_a.jobs.values() for t in j.tasks.values()
        }
        status_b = {
            t.uid: canon(t.status)
            for j in cache_b.jobs.values() for t in j.tasks.values()
        }
        assert status_a == status_b
        assert cache_a.columns.check_consistency(cache_a) == []
        assert cache_b.columns.check_consistency(cache_b) == []

"""Result-integrity guard plane: the fused invariant sentinel condemns
corrupted solves (fail closed — zero binds), the per-fast-path breaker
demotes/probes/re-promotes without wedging or flapping, trips survive the
races (in-flight audit, mid-cycle conf reload), and the diagnostics bundle
replays deterministically."""

from __future__ import annotations

import threading

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
from kube_batch_tpu.api.types import PodPhase, TaskStatus
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.framework.conf import load_scheduler_conf, shipped_conf_path
from kube_batch_tpu.framework.interface import get_action
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.guard.plane import DEMOTED, HEALTHY, PROBING, GuardPlane
from kube_batch_tpu.sim import kubelet as kl
from kube_batch_tpu.testing.synthetic import GiB

# the SHIPPED 5-action conf: a fail-closed cycle writes the unplaced job
# back PodGroupPending, and only the enqueue action re-promotes it next
# cycle — the production pipeline is the recovery path under test
CONF = load_scheduler_conf(shipped_conf_path())


def _mk_cache(reserve_topk=False):
    cache = SchedulerCache()
    if reserve_topk:
        # capT ≥ 1024 gives the KB_TOPK plan a 256-row pending bucket and
        # capN 64 > K, so the compacted fast path ENGAGES at test scale
        cache.columns.reserve(n_tasks=1024, n_nodes=64)
    cache.add_queue(Queue(name="q0", uid="uq0", weight=1))
    for i in range(4):
        cache.add_node(Node(
            name=f"n{i}",
            allocatable={"cpu": 8000.0, "memory": 64 * GiB, "pods": 110.0},
        ))
    return cache


def _add_gang(cache, serial, size=2, cpu=500.0):
    g = f"g{serial}"
    cache.add_pod_group(PodGroup(
        name=g, namespace="t", uid=f"pg-{g}", min_member=size, queue="q0",
        creation_index=serial,
    ))
    for k in range(size):
        cache.add_pod(Pod(
            name=f"{g}-{k}", namespace="t", uid=f"pod-{g}-{k}",
            requests={"cpu": cpu, "memory": 1 * GiB},
            annotations={GROUP_NAME_ANNOTATION: g},
            phase=PodPhase.PENDING, creation_index=serial * 100 + k,
        ))


def _cycle(cache):
    ssn = open_session(cache, CONF.tiers)
    ssn.action_names = list(CONF.actions)
    try:
        for name in CONF.actions:
            get_action(name).execute(ssn)
    finally:
        close_session(ssn)
    cache.flush_binds()
    gp = getattr(cache, "guard_plane", None)
    if gp is not None:
        gp.end_cycle()  # what Scheduler._cycle does each tick


def _corrupt_ledger(cache):
    """Zero a live node's capacity word in the STATIC device feature cache
    — the sim corruption preset's 'ledger' class, inlined."""
    import jax

    cols = cache.columns
    feat = cols._dev_cache[None]
    ver, dev = feat["node_alloc"]
    host = np.array(jax.device_get(dev))
    live = np.flatnonzero(np.asarray(cols.n_valid))
    host[int(live[0])] = 0.0
    feat["node_alloc"] = (ver, jax.device_put(host))


def _corrupt_pending(cache):
    """Flip a RUNNING row's device pending bit, mirror pinned to host truth
    (the HBM-flip model) — detected by the host eligibility checksum."""
    import jax

    cols = cache.columns
    rc = cols._per_cycle_dev[None]
    rows = np.flatnonzero(
        np.asarray(cols.t_status) == int(TaskStatus.RUNNING)
    )
    r = int(rows[0])
    host = np.array(jax.device_get(rc._dev["task_pending"]))
    host[r] = True
    rc._dev["task_pending"] = jax.device_put(host)
    rc._mirror["task_pending"][r] = False
    return r


# ==========================================================================
# tier 1: the fused sentinel + fail-closed dispatch
# ==========================================================================


class TestSentinelFailClosed:
    def test_clean_cycles_never_trip(self):
        cache = _mk_cache(reserve_topk=True)
        for s in range(3):
            _add_gang(cache, s)
            _cycle(cache)
        gp = cache.guard_plane
        assert gp.enabled and gp.trips_total == 0
        assert len(cache.binder.binds) == 6

    def test_corrupted_capacity_word_fails_closed_then_heals(self, tmp_path):
        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)
        gp = cache.guard_plane
        gp.bundle_dir = str(tmp_path)
        binds_before = len(cache.binder.binds)
        _corrupt_ledger(cache)
        _add_gang(cache, 1)
        _cycle(cache)
        # condemned solve: the sentinel's capacity cross-check fired and
        # NOTHING was dispatched from it
        assert gp.trips_total >= 1
        assert gp.failed_closed >= 1
        assert len(cache.binder.binds) == binds_before
        assert any("node_overcommit" in t["detail"] for t in gp.trip_log)
        # the trip healed the resident caches (drop + full re-upload), so
        # the NEXT cycle is clean and the gang binds
        _cycle(cache)
        assert len(cache.binder.binds) == binds_before + 2
        assert gp.trips_total == 1  # no re-trip after the heal

    def test_phantom_pending_bit_caught_by_host_checksum(self, tmp_path):
        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)
        # progress gang 0 to RUNNING so a flippable row exists
        for key in sorted(cache.pods):
            pod = cache.pods[key]
            if pod.node_name:
                kl.set_running(cache, key, pod.node_name)
        _cycle(cache)
        gp = cache.guard_plane
        gp.bundle_dir = str(tmp_path)
        _corrupt_pending(cache)
        binds_before = len(cache.binder.binds)
        running = {k for k, p in cache.pods.items() if p.node_name}
        _add_gang(cache, 1)
        _cycle(cache)
        # the FIRST dispatch that consumed the corrupt column (reclaim runs
        # before allocate in the shipped conf) tripped on the checksum and
        # failed closed; its heal re-uploaded clean columns, so the SAME
        # cycle's later actions lawfully placed the new gang — the phantom
        # row itself was never re-dispatched
        assert gp.trips_total == 1
        assert any("eligibility" in t["detail"] for t in gp.trip_log)
        assert len(cache.binder.binds) == binds_before + 2
        for key in running:  # no RUNNING pod was re-bound anywhere
            assert cache.binder.binds[key] == cache.pods[key].node_name
        _cycle(cache)  # clean after the heal — no re-trip
        assert gp.trips_total == 1

    def test_kb_guard_escape_hatch_disables_everything(self, monkeypatch):
        monkeypatch.setenv("KB_GUARD", "0")
        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)
        gp = cache.guard_plane
        assert not gp.enabled
        _corrupt_ledger(cache)
        _add_gang(cache, 1)
        _cycle(cache)  # no sentinel, no trip — the pre-guard behavior
        assert gp.trips_total == 0

    def test_sentinel_rides_the_existing_readback(self):
        """The guard adds ZERO extra device transfers on the allocate path:
        exactly one device_get per execute (the pre-guard count)."""
        import jax

        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)  # warm
        _add_gang(cache, 1)
        calls = []
        real = jax.device_get

        def counting(x):
            calls.append(1)
            return real(x)

        ssn = open_session(cache, CONF.tiers)
        try:
            import unittest.mock as mock

            with mock.patch.object(
                type(get_action("allocate")), "execute",
                wraps=get_action("allocate").execute,
            ):
                with mock.patch("jax.device_get", side_effect=counting):
                    get_action("allocate").execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()
        # one choke-point readback (+ one fit-histogram readback only on
        # failure cycles — this cycle places everything)
        assert len(calls) == 1


# ==========================================================================
# tier 3: the per-fast-path breaker (demote → cooldown → probe → promote)
# ==========================================================================


class TestGuardPlaneBreaker:
    def _plane(self, cooldown=3):
        return GuardPlane(enabled=True, audit_every=0, cooldown=cooldown)

    def test_demote_probe_repromote_arc(self):
        gp = self._plane(cooldown=3)
        assert gp.allow("topk")
        gp.consume_verdict("allocate", ["topk"], 7)  # trip
        assert gp.paths["topk"].state == DEMOTED
        assert not gp.allow("topk")
        gp.end_cycle()  # the TRIP cycle itself — not a clean cycle
        for _ in range(3):  # clean oracle cycles
            gp.end_cycle()
        assert gp.paths["topk"].state == PROBING
        assert gp.allow("topk")  # half-open: the fast path runs again
        gp.consume_verdict("allocate", ["topk"], 0)  # clean engaged probe
        gp.end_cycle()
        assert gp.paths["topk"].state == HEALTHY
        assert gp.paths["topk"].promotions == 1

    def test_failed_probe_re_demotes_and_never_flaps_per_cycle(self):
        gp = self._plane(cooldown=2)
        gp.consume_verdict("allocate", ["topk"], 1)
        for _ in range(3):  # trip cycle + 2 clean
            gp.end_cycle()
        assert gp.paths["topk"].state == PROBING
        gp.consume_verdict("allocate", ["topk"], 1)  # probe fails
        assert gp.paths["topk"].state == DEMOTED
        gp.end_cycle()  # the failed-probe cycle
        gp.end_cycle()
        # the next probe window is a FULL cooldown away — no per-cycle flap
        assert gp.paths["topk"].state == DEMOTED
        gp.end_cycle()
        assert gp.paths["topk"].state == PROBING

    def test_unengaged_probe_waits_without_wedging(self):
        """A probing path that gets no engagement (no pending work for the
        compacted plan) must stay PROBING — allow() keeps answering True,
        so the next engageable cycle promotes; never permanently demoted."""
        gp = self._plane(cooldown=1)
        gp.consume_verdict("allocate", ["topk"], 1)
        gp.end_cycle()  # trip cycle
        gp.end_cycle()  # one clean cycle → half-open
        assert gp.paths["topk"].state == PROBING
        for _ in range(5):  # idle cycles: no engagement either way
            gp.end_cycle()
        assert gp.paths["topk"].state == PROBING
        assert gp.allow("topk")
        gp.consume_verdict("allocate", ["topk"], 0)
        gp.end_cycle()
        assert gp.paths["topk"].state == HEALTHY

    def test_unattributable_trip_demotes_engaged_history(self):
        gp = self._plane()
        gp.consume_verdict("allocate", ["topk"], 0)  # topk has engaged
        gp.consume_verdict("reclaim", [], 3)         # full-matrix trip
        assert gp.paths["topk"].state == DEMOTED
        assert gp.paths["shard_map"].state == HEALTHY  # never engaged

    def test_audit_mismatch_trips_and_demotes(self):
        gp = self._plane()
        gp.note_audit("allocate", ["shard_map"], matched=False,
                      detail="fast-vs-oracle mismatch")
        assert gp.paths["shard_map"].state == DEMOTED
        assert gp.audits_mismatched == 1
        assert any(t["reason"] == "audit" for t in gp.trip_log)

    def test_audit_cadence_counts_dispatches(self):
        gp = GuardPlane(enabled=True, audit_every=4, cooldown=2)
        due = [gp.audit_due("allocate") for _ in range(8)]
        assert due == [False, False, False, True, False, False, False, True]
        # per-action counters are independent
        assert gp.audit_due("reclaim") is False

    def test_trip_concurrent_with_inflight_audit_does_not_wedge(self):
        """The re-promotion race the ISSUE names: a sentinel trip lands
        while an audit of the same cycle is still comparing.  Whatever the
        interleaving, the path must end DEMOTED with a working cooldown —
        never wedged in a state allow()/end_cycle() cannot move."""
        for _ in range(20):
            gp = self._plane(cooldown=2)
            barrier = threading.Barrier(2)

            def sentinel_trip():
                barrier.wait()
                gp.consume_verdict("allocate", ["topk"], 5)

            def audit_mismatch():
                barrier.wait()
                gp.note_audit("allocate", ["topk"], matched=False)

            ts = [threading.Thread(target=sentinel_trip),
                  threading.Thread(target=audit_mismatch)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert gp.paths["topk"].state == DEMOTED
            gp.end_cycle()  # trip cycle
            for _ in range(2):
                gp.end_cycle()
            assert gp.paths["topk"].state == PROBING  # cooldown still works
            gp.consume_verdict("allocate", ["topk"], 0)
            gp.end_cycle()
            assert gp.paths["topk"].state == HEALTHY

    def test_mid_cycle_conf_reload_preserves_guard_state(self, tmp_path):
        """scheduler.py's hot reload keeps the RUNNING conf on a broken
        edit and swaps actions at the cycle boundary — either way the
        guard plane rides the CACHE, not the conf, so demotion state
        survives a reload mid-cooldown."""
        from kube_batch_tpu.scheduler import Scheduler

        cache = _mk_cache()
        conf_path = tmp_path / "conf.yaml"
        conf_path.write_text(
            'actions: "enqueue, allocate, backfill"\n'
            "tiers:\n- plugins:\n  - name: gang\n  - name: predicates\n"
            "  - name: proportion\n  - name: nodeorder\n"
        )
        sched = Scheduler(cache, conf_path=str(conf_path),
                          schedule_period=0.01)
        sched.pipelined = False
        _add_gang(cache, 0)
        sched.run_once()
        gp = cache.guard_plane
        gp.consume_verdict("allocate", ["topk"], 9)  # demote mid-run
        assert gp.paths["topk"].state == DEMOTED
        # conf edit lands mid-cooldown; next cycle hot-reloads it
        conf_path.write_text(
            'actions: "enqueue, allocate"\n'
            "tiers:\n- plugins:\n  - name: gang\n  - name: predicates\n"
            "  - name: proportion\n  - name: nodeorder\n"
        )
        import os

        os.utime(conf_path, (1e9, 2e9))  # force a visible mtime move
        sched.run_once()
        assert [a.name for a in sched.actions] == ["enqueue", "allocate"]
        assert cache.guard_plane is gp  # same breaker, same state machine
        assert gp.paths["topk"].state in (DEMOTED, PROBING)
        for _ in range(gp.cooldown + 1):
            sched.run_once()
        assert gp.paths["topk"].state == PROBING  # cooldown ran to half-open


# ==========================================================================
# demotion-aware dispatch: a demoted path really runs its oracle
# ==========================================================================


class TestDemotionAwareDispatch:
    def test_demoted_topk_runs_full_matrix_until_repromoted(self):
        from kube_batch_tpu.actions.allocate import (
            dispatch_allocate_solve,
            session_allocate_config,
        )
        from kube_batch_tpu.actions.allocate import build_session_snapshot

        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)
        gp = cache.guard_plane
        alloc = get_action("allocate")
        assert alloc.last_topk is not None  # compaction engaged when healthy
        gp.paths["topk"].state = DEMOTED
        _add_gang(cache, 1)
        _cycle(cache)
        assert alloc.last_topk is None      # oracle (full-matrix) ran
        gp.paths["topk"].state = HEALTHY
        _add_gang(cache, 2)
        _cycle(cache)
        assert alloc.last_topk is not None  # fast path back
        # every cycle placed its gang regardless of path — demotion is a
        # performance decision, never a correctness one
        assert len(cache.binder.binds) == 6


# ==========================================================================
# diagnostics bundles: dump, atomicity, deterministic replay
# ==========================================================================


class TestBundles:
    def test_trip_bundle_replays_deterministically(self, tmp_path):
        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)
        gp = cache.guard_plane
        gp.bundle_dir = str(tmp_path)
        _corrupt_ledger(cache)
        _add_gang(cache, 1)
        _cycle(cache)
        assert len(gp.bundles) >= 1
        from kube_batch_tpu.guard.bundle import load_bundle, replay_bundle

        path = gp.bundles[0]
        snap, meta, pend_rows = load_bundle(path)
        assert meta["action"] in ("allocate", "reclaim", "preempt",
                                  "backfill")
        assert meta["report"]["verdict"] > 0
        # the replay re-derives the SAME integrity failure from the
        # captured (corrupt) snapshot — twice, bit-stable
        rep1 = replay_bundle(path)
        rep2 = replay_bundle(path)
        assert rep1["reproduced"] and rep2["reproduced"]
        assert rep1["fast_verdict"] == rep2["fast_verdict"]
        assert rep1.get("fast_violations") == rep2.get("fast_violations")

    def test_checksum_trip_bundle_reproduces_via_host_checksum(
        self, tmp_path
    ):
        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)
        for key in sorted(cache.pods):
            pod = cache.pods[key]
            if pod.node_name:
                kl.set_running(cache, key, pod.node_name)
        _cycle(cache)
        gp = cache.guard_plane
        gp.bundle_dir = str(tmp_path)
        _corrupt_pending(cache)
        _add_gang(cache, 1)
        _cycle(cache)
        assert gp.bundles
        from kube_batch_tpu.guard.bundle import replay_bundle

        rep = replay_bundle(gp.bundles[-1])
        assert rep["reproduced"]
        assert rep["host_checksum_mismatch"] is True

    def test_no_half_bundles_on_disk(self, tmp_path):
        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)
        gp = cache.guard_plane
        gp.bundle_dir = str(tmp_path)
        _corrupt_ledger(cache)
        _add_gang(cache, 1)
        _cycle(cache)
        entries = sorted(p.name for p in tmp_path.iterdir())
        assert entries and all(e.startswith("trip-") for e in entries), (
            "atomic publish must leave only complete trip-* bundles"
        )

    @pytest.mark.parametrize("flag", [False, True])
    def test_bundle_from_before_the_pallas_removal(self, tmp_path, flag):
        """A bundle directory is input from outside the program: one
        written before PR 29 carries ``use_pallas`` in its config.  Off, it
        replays as any other; on, it names a kernel that is gone, and the
        replay says so."""
        import json

        from kube_batch_tpu.guard.bundle import replay_bundle

        cache = _mk_cache(reserve_topk=True)
        _add_gang(cache, 0)
        _cycle(cache)
        gp = cache.guard_plane
        gp.bundle_dir = str(tmp_path)
        _corrupt_ledger(cache)
        _add_gang(cache, 1)
        _cycle(cache)
        path = gp.bundles[0]
        want = replay_bundle(path)
        meta_path = tmp_path / path.rsplit("/", 1)[-1] / "meta.json"
        meta = json.loads(meta_path.read_text())
        assert "use_pallas" not in meta["config"]
        assert not any("PALLAS" in k for k in meta["knobs"])
        meta["config"]["use_pallas"] = flag
        meta_path.write_text(json.dumps(meta))
        if flag:
            with pytest.raises(ValueError, match="removed in PR 29"):
                replay_bundle(path)
        else:
            assert replay_bundle(path) == want


# ==========================================================================
# sentinel invariant math (device-level units)
# ==========================================================================


class TestInvariantMath:
    @pytest.fixture(scope="class")
    def snap(self):
        import jax.numpy as jnp

        from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot
        from kube_batch_tpu.api.snapshot import DeviceSnapshot

        ab = abstract_snapshot()
        z = DeviceSnapshot(*[jnp.zeros(s.shape, s.dtype) for s in ab
                             if s is not None])  # aff_terms: no leaf
        T, R, N, J = 16, 3, 8, 4
        return z._replace(
            task_req=jnp.ones((T, R), jnp.float32),
            task_resreq=jnp.ones((T, R), jnp.float32),
            task_job=jnp.arange(T, dtype=jnp.int32) % J,
            task_valid=jnp.ones(T, bool),
            task_pending=jnp.ones(T, bool),
            task_node=jnp.full(T, -1, jnp.int32),
            task_aff_idx=jnp.full(1, -1, jnp.int32),
            task_pref_idx=jnp.full(1, -1, jnp.int32),
            node_idle=jnp.full((N, R), 8.0, jnp.float32),
            node_alloc=jnp.full((N, R), 8.0, jnp.float32),
            node_valid=jnp.ones(N, bool),
            node_sched=jnp.ones(N, bool),
            job_min_avail=jnp.ones(J, jnp.int32),
            job_valid=jnp.ones(J, bool),
            job_schedulable=jnp.ones(J, bool),
            queue_weight=jnp.ones(2, jnp.float32),
            queue_valid=jnp.ones(2, bool),
            total=jnp.full(R, 64.0, jnp.float32),
            quanta=jnp.full(R, 0.01, jnp.float32),
        )

    def test_lawful_result_verdict_zero(self, snap):
        from kube_batch_tpu.ops.assignment import AllocateConfig
        from kube_batch_tpu.ops.invariants import allocate_sentinel_solve

        _res, v, h, _e = allocate_sentinel_solve(snap, AllocateConfig())
        assert int(v) == 0 and not np.asarray(h).any()

    def test_nan_ledger_hits_nonfinite_slot(self, snap):
        import jax.numpy as jnp

        from kube_batch_tpu.ops.assignment import AllocateConfig
        from kube_batch_tpu.ops.invariants import (
            INVARIANT_NAMES,
            allocate_sentinel_solve,
        )

        bad = snap._replace(node_used=snap.node_used.at[0, 0].set(jnp.nan))
        _res, v, h = allocate_sentinel_solve(bad, AllocateConfig())[:3]
        assert int(v) > 0
        assert np.asarray(h)[INVARIANT_NAMES.index("nonfinite")] > 0

    def test_inconsistent_ledger_hits_overcommit_slot(self, snap):
        from kube_batch_tpu.ops.assignment import AllocateConfig
        from kube_batch_tpu.ops.invariants import (
            INVARIANT_NAMES,
            allocate_sentinel_solve,
        )

        bad = snap._replace(node_idle=snap.node_idle.at[0, 0].set(1e6))
        _res, v, h = allocate_sentinel_solve(bad, AllocateConfig())[:3]
        assert int(v) > 0
        assert np.asarray(h)[INVARIANT_NAMES.index("node_overcommit")] > 0

    def test_pipelined_occupancy_is_lawful(self, snap):
        """A node carrying a PIPELINED task lawfully shows used >
        allocatable by that task's resreq (it borrows the dying victim's
        share) — the capacity cross-check must NOT false-positive there."""
        import jax.numpy as jnp

        from kube_batch_tpu.api.types import TaskStatus
        from kube_batch_tpu.ops.assignment import AllocateConfig
        from kube_batch_tpu.ops.invariants import allocate_sentinel_solve

        s = snap._replace(
            task_status=snap.task_status.at[0].set(
                int(TaskStatus.PIPELINED)),
            task_node=snap.task_node.at[0].set(0),
            task_pending=snap.task_pending.at[0].set(False),
            # node 0: fully used + the pipelined borrow on top
            node_idle=snap.node_idle.at[0].set(0.0),
            node_used=snap.node_used.at[0].set(9.0),  # alloc 8 + borrow 1
        )
        _res, v, _h, _e = allocate_sentinel_solve(s, AllocateConfig())
        assert int(v) == 0

    def test_evict_sentinel_clean_and_checksum_stable(self, snap):
        from kube_batch_tpu.ops.eviction import EvictConfig
        from kube_batch_tpu.ops.invariants import (
            evict_sentinel_solve,
            host_eligibility_checksum,
        )

        _res, v, _h, e = evict_sentinel_solve(
            snap, EvictConfig(mode="reclaim"))
        assert int(v) == 0
        # the device checksum equals the host twin on an uncorrupted snap
        host_snap = snap  # jnp arrays read host-side via np.asarray
        assert (int(e) & 0xFFFFFFFF) == host_eligibility_checksum(host_snap)

"""Delta-vs-full-rebuild equivalence over randomized churn.

The cross-cycle machinery (cache/dirty.py, columns.sync_session_rows, the
per-cycle device-resident cache) promises BIT-EXACT equivalence with the
from-scratch path.  These tests churn a real SchedulerCache through the
ordinary ingest surface — gang arrivals, completions, status flips, node
crashes/rejoins, queue and priority-class changes — run real scheduling
cycles, and after every cycle compare the delta-built device snapshot (and
the session-open state) against a forced full rebuild.
"""

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
    PriorityClass,
    Queue,
)
from kube_batch_tpu.api.snapshot import DeviceSnapshot
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.framework.interface import get_action
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.sim import kubelet as kl
from kube_batch_tpu.testing.synthetic import GiB


def _mk_cache(n_nodes=6, n_queues=2):
    cache = SchedulerCache()
    for q in range(n_queues):
        cache.add_queue(Queue(name=f"q{q}", uid=f"uq{q}", weight=q + 1))
    for i in range(n_nodes):
        cache.add_node(Node(
            name=f"n{i}",
            allocatable={"cpu": 16000.0, "memory": 64 * GiB, "pods": 110.0},
        ))
    return cache


class _Churner:
    """Randomized but seed-deterministic cluster churn through the real
    ingest handlers."""

    def __init__(self, cache, rng, n_queues=2):
        self.cache = cache
        self.rng = rng
        self.n_queues = n_queues
        self.serial = 0
        self.gangs = []  # job names with live pods

    def add_gang(self, size=None):
        self.serial += 1
        g = f"g{self.serial}"
        size = size or int(self.rng.integers(1, 4))
        self.cache.add_pod_group(PodGroup(
            name=g, namespace="churn", uid=f"pg-{g}", min_member=size,
            queue=f"q{int(self.rng.integers(self.n_queues))}",
            creation_index=self.serial,
        ))
        for k in range(size):
            self.cache.add_pod(Pod(
                name=f"{g}-{k}", namespace="churn", uid=f"pod-{g}-{k}",
                requests={"cpu": float(self.rng.choice([250.0, 500.0, 1000.0])),
                          "memory": 1 * GiB},
                annotations={GROUP_NAME_ANNOTATION: g},
                phase=PodPhase.PENDING,
                creation_index=self.serial * 100 + k,
            ))
        self.gangs.append(g)

    def complete_gang(self):
        if not self.gangs:
            return
        g = self.gangs.pop(int(self.rng.integers(len(self.gangs))))
        job_uid = f"churn/{g}"
        job = self.cache.jobs.get(job_uid)
        keys = sorted(job.tasks.keys()) if job is not None else []
        for key in keys:
            kl.delete_pod(self.cache, key)
        self.cache.delete_pod_group(job_uid)

    def flip_statuses(self):
        # bound pods progress to Running/Succeeded like a kubelet would
        pods = [p for p in self.cache.pods.values() if p.node_name]
        if not pods:
            return
        pods.sort(key=lambda p: p.key())
        for p in pods[: int(self.rng.integers(1, 3))]:
            if p.phase == PodPhase.PENDING:
                kl.set_running(self.cache, p.key(), p.node_name)
            elif p.phase == PodPhase.RUNNING and self.rng.random() < 0.5:
                kl.set_succeeded(self.cache, p.key())

    def node_churn(self):
        r = self.rng.random()
        if r < 0.5:
            self.cache.delete_node(f"n{int(self.rng.integers(3))}")
        else:
            i = int(self.rng.integers(3))
            self.cache.add_node(Node(
                name=f"n{i}",
                allocatable={"cpu": 16000.0, "memory": 64 * GiB,
                             "pods": 110.0},
            ))

    def step(self):
        r = self.rng.random()
        if r < 0.45:
            self.add_gang()
        elif r < 0.70:
            self.complete_gang()
        elif r < 0.90:
            self.flip_statuses()
        else:
            self.node_churn()


def _snapshot_arrays(snap: DeviceSnapshot) -> dict:
    return {f: np.array(getattr(snap, f)) for f in snap._fields}


def _assert_snaps_equal(delta: dict, full: dict, context: str):
    for field, want in full.items():
        got = delta[field]
        assert got.shape == want.shape, f"{context}: {field} shape"
        assert np.array_equal(got, want), (
            f"{context}: field {field} diverged between delta and full "
            f"rebuild (rows {np.flatnonzero(np.any(np.atleast_2d(got != want), axis=-1))[:8]})"
        )


@pytest.mark.parametrize("seed", [0, 7, 23])
def test_delta_device_snapshot_bit_exact_under_churn(seed):
    """Over randomized churn sequences, the delta-built device snapshot is
    bit-exact against a from-scratch row rescan every cycle (acceptance
    criterion of the cross-cycle resident-snapshot PR)."""
    rng = np.random.default_rng(seed)
    cache = _mk_cache()
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, rng)
    for _ in range(4):
        churn.add_gang()
    delta_cycles = 0
    for cycle in range(14):
        churn.step()
        ssn = open_session(cache, conf.tiers)
        cols = cache.columns
        try:
            snap, _meta = cols.device_snapshot(ssn)
            got = _snapshot_arrays(snap)
            path = cols.last_snapshot_path
            delta_cycles += path == "delta"
            # force the full rescan over the same session state and compare
            cols.sync_session_rows(ssn)
            snap_full, _ = cols.device_snapshot(ssn)
            _assert_snaps_equal(
                got, _snapshot_arrays(snap_full),
                f"seed={seed} cycle={cycle} path={path}",
            )
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()
    assert cache.columns.check_consistency(cache) == []
    # the delta path must actually engage (else this test proves nothing)
    assert delta_cycles >= 5, f"delta path engaged only {delta_cycles}x"


@pytest.mark.parametrize("seed", [3, 11])
def test_delta_open_state_matches_full_view(seed):
    """The delta session open hands out exactly the membership, priorities,
    and at-open PodGroup statuses a full session_view would derive."""
    rng = np.random.default_rng(seed)
    cache = _mk_cache()
    cache.add_priority_class(PriorityClass(name="high", value=50))
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, rng)
    for _ in range(3):
        churn.add_gang()
    for cycle in range(10):
        churn.step()
        ssn = open_session(cache, conf.tiers)
        try:
            # expected: re-derive the full view against the SAME live state
            # (session_view only reads; the exclusive gate is already held)
            expected = cache.session_view()
            assert set(ssn.jobs) | {j.uid for j in ssn.gate_dropped_jobs} \
                == set(expected.jobs), f"cycle {cycle} membership"
            for uid, job in expected.jobs.items():
                assert job.priority == expected.jobs[uid].priority
            expected_status = {
                uid: (j.pod_group.phase, j.pod_group.running,
                      j.pod_group.failed, j.pod_group.succeeded)
                for uid, j in expected.jobs.items() if j.pod_group is not None
            }
            assert ssn.pod_group_status_at_open == expected_status, (
                f"cycle {cycle} at-open status"
            )
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()


def test_per_cycle_device_cache_round_trips_bit_exact():
    """The scatter-refreshed device-resident per-cycle columns fetch back
    bit-identical to the host columns after every churn cycle."""
    from kube_batch_tpu.api.resident import SWAP_FIELDS

    rng = np.random.default_rng(5)
    cache = _mk_cache()
    # realistic axis capacities: with micro columns the cache rightly
    # prefers whole-column re-uploads (cheaper than the smallest fixed
    # scatter payload) and the delta path under test would never engage.
    # Node axis stays below SHARD_MIN_NODES so the actions keep the
    # single-device dispatch this test exercises
    cache.columns.reserve(n_tasks=2048, n_nodes=128, n_jobs=512)
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, rng)
    for _ in range(3):
        churn.add_gang()
    cols = cache.columns
    for cycle in range(8):
        churn.step()
        ssn = open_session(cache, conf.tiers)
        try:
            snap, _meta = cols.device_snapshot(ssn)
            swapped = cols.per_cycle_resident(snap)
            for field in SWAP_FIELDS:
                host = np.asarray(getattr(snap, field))
                dev = np.asarray(getattr(swapped, field))
                assert np.array_equal(host, dev), (
                    f"cycle {cycle}: device-resident {field} diverged"
                )
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()
    pcd = cols._per_cycle_dev.get(None)
    assert pcd is not None and pcd.scatter_updates > 0, (
        "scatter-delta path never engaged"
    )


def _bit_equal_to_whole_upload(snap, swapped, context):
    """Every field a swap refreshes fetches back byte-for-byte what a
    whole upload of the host column would hold."""
    from kube_batch_tpu.api.resident import SWAP_FIELDS

    for field in SWAP_FIELDS:
        host = np.asarray(getattr(snap, field))
        dev = np.asarray(getattr(swapped, field))
        assert dev.dtype == host.dtype and dev.shape == host.shape, (
            f"{context}: {field} {dev.dtype}{dev.shape} vs "
            f"{host.dtype}{host.shape}")
        assert dev.tobytes() == host.tobytes(), (
            f"{context}: device-resident {field} is not the host column")


def _selector_gang(cache, name, label):
    """A one-pod gang whose node selector names `label` — a sparse task
    bitset row that refresh_task_bits recomputes when the label universe
    moves."""
    cache.add_pod_group(PodGroup(
        name=name, namespace="churn", uid=f"pg-{name}", min_member=1,
        queue="q0", creation_index=9000,
    ))
    cache.add_pod(Pod(
        name=f"{name}-0", namespace="churn", uid=f"pod-{name}-0",
        requests={"cpu": 250.0, "memory": 1 * GiB},
        annotations={GROUP_NAME_ANNOTATION: name},
        node_selector=dict([label]),
        phase=PodPhase.PENDING, creation_index=900000,
    ))


def _swap_cycle(cache, conf, context, run_actions=True):
    """One session: the device snapshot through resident_snap, checked
    bit-equal to a whole upload; returns the resident counters' movement
    over the swap and the jit specializations it added."""
    from kube_batch_tpu.api.columns import resident_snap
    from kube_batch_tpu.utils import jitstats

    cols = cache.columns
    ssn = open_session(cache, conf.tiers)
    try:
        snap, _meta = cols.device_snapshot(ssn)
        before = cols.resident_counters().get("single", {})
        compiles = jitstats.total_compiles()
        swapped = resident_snap(cols, snap)
        after = cols.resident_counters()["single"]
        compiles = jitstats.total_compiles() - compiles
        _bit_equal_to_whole_upload(snap, swapped, context)
        if run_actions:
            for name in conf.actions:
                get_action(name).execute(ssn)
    finally:
        close_session(ssn)
    cache.flush_binds()
    moved = {k: after[k] - before.get(k, 0) for k in after}
    return moved, compiles


@pytest.mark.parametrize("case", [
    "churn", "freed_and_reallocated", "refresh_task_bits", "axis_growth",
    "delta_past_scatter_slots",
])
def test_fused_swap_bit_equal_to_whole_upload(case, monkeypatch):
    """The one-program swap leaves every resident column — per-cycle AND
    task feature — byte-for-byte what a whole upload would hold, through
    the events that move task feature rows: randomized churn, rows freed
    and re-allocated inside one cycle, a refresh_task_bits cycle, an axis
    growth, and a delta wider than the widest slot bucket."""
    from kube_batch_tpu.api import resident as res

    rng = np.random.default_rng(17)
    cache = _mk_cache()
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, rng)
    cols = cache.columns
    if case == "delta_past_scatter_slots":
        # narrow buckets: a 24-pod arrival overflows the widest one and
        # must re-upload its columns whole (values exact either way)
        monkeypatch.setattr(res, "SCATTER_SLOT_BUCKETS", (4, 8, 16))
        monkeypatch.setattr(res, "SCATTER_SLOTS", 16)
    if case != "axis_growth":
        cols.reserve(n_tasks=2048, n_nodes=128, n_jobs=512)
    for _ in range(3):
        churn.add_gang()
    _swap_cycle(cache, conf, f"{case}: cold")
    cache_obj = cols._per_cycle_dev[None]
    for cycle in range(6):
        context = f"{case}: cycle {cycle}"
        if case == "churn":
            for _ in range(3):
                churn.step()
        elif case == "freed_and_reallocated":
            # the allocator is LIFO: the rows a completed gang frees are
            # the rows the next arrivals take, all inside one cycle
            churn.complete_gang()
            churn.add_gang(size=3)
            churn.add_gang(size=2)
        elif case == "refresh_task_bits":
            if cycle == 0:
                _selector_gang(cache, "sel", ("zone", "z1"))
            elif cycle == 2:
                # a new label pair joins the universe: the selector row's
                # bitset is recomputed at the next snapshot build
                cache.add_node(Node(
                    name="nz", labels={"zone": "z1"},
                    allocatable={"cpu": 16000.0, "memory": 64 * GiB,
                                 "pods": 110.0},
                ))
            else:
                churn.add_gang()
        elif case == "axis_growth":
            for _ in range(4):
                churn.add_gang(size=3)   # walks the task axis past a bucket
        else:
            churn.add_gang(size=24 if cycle == 2 else 2)
        moved, _ = _swap_cycle(cache, conf, context)
        if case == "delta_past_scatter_slots" and cycle == 2:
            assert moved["feature_uploads"] > 0, (
                "a delta past the widest bucket must re-upload whole")
    if case == "axis_growth":
        assert cols.tasks.cap > 8, "the task axis never grew"
    if case == "refresh_task_bits":
        assert int(cols.t_sel_bits.any(axis=1).sum()) > 0
    assert cols._per_cycle_dev[None] is cache_obj
    assert cols.check_consistency(cache) == []


def test_steady_swap_is_one_program_and_no_feature_upload():
    """A steady churn swap (pods come and go, no node moves) makes at most
    three device calls — the packed program plus the two tiny queue
    columns' whole puts — uploads no task feature column whole, and adds
    no jit specialization once the cold upload has prewarmed."""
    rng = np.random.default_rng(29)
    cache = _mk_cache()
    cache.columns.reserve(n_tasks=2048, n_nodes=128, n_jobs=512)
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, rng)
    for _ in range(3):
        churn.add_gang()
    cold, _ = _swap_cycle(cache, conf, "cold")
    from kube_batch_tpu.api.resident import SWAP_FIELDS, TASK_FEATURE_FIELDS

    assert cold["full_uploads"] == len(SWAP_FIELDS)
    assert cold["feature_uploads"] == len(TASK_FEATURE_FIELDS)
    # the cold swap: one put a field, then 3 buckets x 2 prewarm passes
    assert cold["dispatches"] == len(SWAP_FIELDS) + 6
    scattered = 0
    for cycle in range(8):
        churn.complete_gang()
        churn.add_gang()
        churn.flip_statuses()
        moved, compiles = _swap_cycle(cache, conf, f"cycle {cycle}")
        assert moved["version"] == 1
        assert 1 <= moved["dispatches"] <= 3, moved
        assert moved["feature_uploads"] == 0, moved
        assert compiles == 0, f"cycle {cycle} compiled {compiles}"
        scattered += moved["scatter_updates"]
    assert scattered > 0, "the packed scatter path never engaged"


def test_repeat_resident_snap_is_free_and_reads_nothing_back(monkeypatch):
    """A second resident_snap on the identical host snapshot returns the
    identical device snapshot — no diff, no version bump, no dispatch (the
    same cycle's oracle, histogram and lease publish lean on it) — and no
    swap is ever handed a device array to convert back to numpy."""
    from kube_batch_tpu.api import resident as res
    from kube_batch_tpu.api.columns import resident_snap

    seen = []
    real_swap = res.PerCycleDeviceCache.swap

    def spy(self, snap, feature_token=None):
        seen.extend(
            (f, type(getattr(snap, f))) for f in res.SWAP_FIELDS
            if not isinstance(getattr(snap, f), np.ndarray)
        )
        return real_swap(self, snap, feature_token)

    monkeypatch.setattr(res.PerCycleDeviceCache, "swap", spy)
    rng = np.random.default_rng(31)
    cache = _mk_cache()
    cache.columns.reserve(n_tasks=2048, n_nodes=128, n_jobs=512)
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, rng)
    for _ in range(3):
        churn.add_gang()
    cols = cache.columns
    for cycle in range(4):
        churn.step()
        ssn = open_session(cache, conf.tiers)
        try:
            snap, _meta = cols.device_snapshot(ssn)
            first = resident_snap(cols, snap)
            held = cols.resident_counters()["single"]
            again = resident_snap(cols, snap)
            assert again is first, f"cycle {cycle}: repeat built a new snap"
            assert cols.resident_counters()["single"] == held, (
                f"cycle {cycle}: a repeat moved the counters")
            # the actions' own dispatch of this very snapshot is a repeat
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()
    assert seen == [], f"a swap was handed non-host columns: {seen[:4]}"
    assert "task_best_effort" not in res.PER_CYCLE_FIELDS
    assert "task_best_effort" in res.TASK_FEATURE_FIELDS


def test_full_fallback_on_row_space_changes():
    """Queue and priority-class changes invalidate the delta path for one
    open (row spaces / priority resolution are global inputs)."""
    cache = _mk_cache()
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, np.random.default_rng(1))
    churn.add_gang()

    def one_open():
        ssn = open_session(cache, conf.tiers)
        close_session(ssn)
        return cache.last_open_path

    assert one_open() == "full"      # cold cache
    churn.add_gang()
    assert one_open() == "delta"     # low churn
    cache.add_queue(Queue(name="q9", uid="uq9", weight=1))
    assert one_open() == "full"      # queue row space moved
    assert one_open() == "delta"
    cache.add_priority_class(PriorityClass(name="p", value=9))
    assert one_open() == "full"      # priority universe moved
    assert one_open() == "delta"


def test_delta_disabled_forces_full_path():
    cache = _mk_cache()
    cache.delta_enabled = False
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, np.random.default_rng(2))
    churn.add_gang()
    for _ in range(3):
        ssn = open_session(cache, conf.tiers)
        close_session(ssn)
        assert cache.last_open_path == "full"
        assert cache.columns.last_snapshot_path == "full"


def test_close_session_delta_matches_full_rebuild(monkeypatch):
    """The delta close-status pass (visit only touched/need-record rows,
    qcounts off the j_phase column) must leave byte-identical end state to
    the forced full visit (KB_DELTA_CLOSE=0): PodGroup phases/counts,
    recorded events, and QueueStatus writes, over randomized churn."""

    def run(delta_close: bool, seed=13, cycles=10):
        if delta_close:
            monkeypatch.delenv("KB_DELTA_CLOSE", raising=False)
        else:
            monkeypatch.setenv("KB_DELTA_CLOSE", "0")
        rng = np.random.default_rng(seed)
        cache = _mk_cache()
        conf = load_scheduler_conf(None)
        churn = _Churner(cache, rng)
        for _ in range(4):
            churn.add_gang()
        states = []
        for _ in range(cycles):
            churn.step()
            ssn = open_session(cache, conf.tiers)
            try:
                for name in conf.actions:
                    get_action(name).execute(ssn)
            finally:
                close_session(ssn)
            cache.flush_binds()
            states.append({
                uid: (j.pod_group.phase, j.pod_group.running,
                      j.pod_group.failed, j.pod_group.succeeded)
                for uid, j in sorted(cache.jobs.items())
                if j.pod_group is not None
            })
            states.append(
                {q: dict(c) for q, c in
                 sorted(cache._queue_status_written.items())}
            )
        events = list(cache.events)
        assert cache.columns.check_consistency(cache) == []
        cache.stop()
        return states, events

    delta_states, delta_events = run(True)
    full_states, full_events = run(False)
    assert delta_states == full_states
    assert delta_events == full_events


def test_stale_fit_state_cleared_across_delta_opens():
    """A job that recorded fit errors in one cycle starts the next session
    clean even when the open takes the delta path (note_fit_state feeds the
    targeted clearing set)."""
    cache = _mk_cache()
    conf = load_scheduler_conf(None)
    churn = _Churner(cache, np.random.default_rng(4))
    churn.add_gang(size=2)
    ssn = open_session(cache, conf.tiers)
    job = next(iter(ssn.jobs.values()))
    job.job_fit_errors = "synthetic"
    from kube_batch_tpu.api.job_info import FitErrors

    fe = FitErrors()
    fe.set_histogram({"synthetic reason": 1}, 1)
    job.nodes_fit_errors["t"] = fe
    ssn.note_fit_state(job)
    close_session(ssn)
    ssn = open_session(cache, conf.tiers)
    try:
        assert cache.last_open_path == "delta"
        refreshed = ssn.jobs.get(job.uid)
        assert refreshed is None or refreshed.job_fit_errors == ""
        assert refreshed is None or refreshed.nodes_fit_errors == {}
    finally:
        close_session(ssn)

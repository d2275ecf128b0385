"""Sharded-resident solve path: bit-exact equivalence over randomized churn.

The conftest forces an 8-device virtual CPU mesh, so the mesh-sharded solve
(and its per-shard scatter-delta residency, api/resident.py) runs in-process
here: a ≥200-node cluster pads past SHARD_MIN_NODES and the allocate action
dispatches sharded.  These tests churn a real cache through real cycles and
assert the acceptance criteria of the sharded-residency PR:

- the sharded-delta device columns fetch back bit-identical to the host
  columns every cycle (the scatter writes exactly the changed rows);
- sharded-delta vs sharded-full-upload (KB_DEVICE_CACHE=0) vs single-device
  (KB_SHARD=0) cycles produce identical binds and end state;
- a mesh change / device-count change falls back to a full re-upload.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.framework.interface import get_action
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.testing.synthetic import synthetic_cluster

N_NODES = 200   # pads to 256 == SHARD_MIN_NODES → the sharded path engages
N_TASKS = 240


def _mk_cache(seed=0):
    return synthetic_cluster(
        n_tasks=N_TASKS, n_nodes=N_NODES, gang_size=4, n_queues=2, seed=seed
    )


def _churn(cache, rng, serial):
    """Seed-deterministic churn: complete one bound gang, add one gang."""
    from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION, Pod, PodGroup
    from kube_batch_tpu.api.types import PodPhase

    for uid, job in sorted(cache.jobs.items()):
        pods = [cache.pods.get(key) for key in sorted(job.tasks)]
        if pods and all(p is not None and p.node_name for p in pods):
            for p in pods:
                cache.delete_pod(p)
            cache.delete_pod_group(uid)
            break
    j = next(serial)
    cache.add_pod_group(PodGroup(
        name=f"sh{j}", namespace="shard", min_member=2,
        queue=f"q{j % 2}", creation_index=10_000 + j,
    ))
    for t in range(2):
        cache.add_pod(Pod(
            name=f"sh{j}-{t}", namespace="shard",
            requests={"cpu": float(rng.choice([250.0, 500.0])),
                      "memory": float(2 ** 30)},
            annotations={GROUP_NAME_ANNOTATION: f"sh{j}"},
            phase=PodPhase.PENDING,
            creation_index=(10_000 + j) * 10 + t,
        ))


def _run_cycles(cache, conf, cycles=5, seed=7):
    """Run `cycles` churned scheduling cycles; returns the per-cycle bind
    sequences and the final task-status column."""
    import itertools

    rng = np.random.default_rng(seed)
    serial = itertools.count(1)
    binds = []
    for _ in range(cycles):
        _churn(cache, rng, serial)
        ssn = open_session(cache, conf.tiers)
        try:
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()
        binds.append(sorted(cache.binder.binds.items()))
    cols = cache.columns
    status = [
        (cols.task_by_row[r]._key, int(cols.t_status[r]))
        for r in np.flatnonzero(cols.t_valid).tolist()
    ]
    return binds, sorted(status)


@pytest.fixture
def _env_guard():
    saved = {k: os.environ.get(k) for k in ("KB_DEVICE_CACHE", "KB_SHARD")}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_allocate_dispatches_sharded_with_resident_cache():
    """The sharded dispatch must ride the per-shard scatter cache: after a
    few churn cycles the sharded cache exists, scatter-delta updates
    engaged, and every cached field round-trips bit-exact."""
    from kube_batch_tpu.api.columns import resident_snap
    from kube_batch_tpu.api.resident import SWAP_FIELDS
    from kube_batch_tpu.parallel.mesh import default_mesh

    import itertools

    cache = _mk_cache()
    conf = load_scheduler_conf(None)
    rng = np.random.default_rng(3)
    serial = itertools.count(1)
    cols = cache.columns
    mesh = default_mesh()
    assert mesh is not None, "conftest must provide the 8-device mesh"
    for cycle in range(5):
        _churn(cache, rng, serial)
        ssn = open_session(cache, conf.tiers)
        try:
            snap, _meta = cols.device_snapshot(ssn)
            swapped = resident_snap(cols, snap, mesh)
            for field in SWAP_FIELDS:
                host = np.asarray(getattr(snap, field))
                dev = np.asarray(getattr(swapped, field))
                assert np.array_equal(host, dev), (
                    f"cycle {cycle}: sharded-resident {field} diverged"
                )
            for name in conf.actions:
                get_action(name).execute(ssn)
            assert get_action("allocate").last_solve_mode == "sharded"
        finally:
            close_session(ssn)
        cache.flush_binds()
    sharded = cols._per_cycle_dev.get(mesh)
    assert sharded is not None
    assert sharded.scatter_updates > 0, "per-shard delta path never engaged"
    assert sharded.clean_hits > 0
    assert cols.check_consistency(cache) == []


def test_sharded_swap_is_two_programs_and_no_feature_upload():
    """The mesh path's fused swap: the replicated columns ride one packed
    program and the node-axis columns its per-shard twin, so a steady churn
    swap makes at most two program calls (plus the tiny columns' whole
    puts), uploads no task feature column whole, adds no jit
    specialization after the cold upload, and every column it refreshes
    fetches back byte-for-byte the host column.  A repeat resident_snap on
    the identical host snapshot is free."""
    from kube_batch_tpu.api.columns import resident_snap
    from kube_batch_tpu.api.resident import (
        NODE_AXIS_FIELDS,
        SWAP_FIELDS,
        TASK_FEATURE_FIELDS,
    )
    from kube_batch_tpu.parallel.mesh import default_mesh
    from kube_batch_tpu.utils import jitstats

    import itertools

    cache = _mk_cache(seed=5)
    # axes wide enough that the job/task columns take the scatter path
    cache.columns.reserve(n_tasks=2048, n_jobs=512)
    conf = load_scheduler_conf(None)
    rng = np.random.default_rng(11)
    serial = itertools.count(1)
    cols = cache.columns
    mesh = default_mesh()
    tiny = None
    for cycle in range(6):
        _churn(cache, rng, serial)
        ssn = open_session(cache, conf.tiers)
        try:
            snap, _meta = cols.device_snapshot(ssn)
            before = cols.resident_counters().get("sharded", {})
            compiles = jitstats.total_compiles()
            swapped = resident_snap(cols, snap, mesh)
            after = cols.resident_counters()["sharded"]
            compiles = jitstats.total_compiles() - compiles
            for field in SWAP_FIELDS:
                host = np.asarray(getattr(snap, field))
                dev = np.asarray(getattr(swapped, field))
                assert dev.tobytes() == host.tobytes(), (
                    f"cycle {cycle}: sharded-resident {field} diverged")
            assert resident_snap(cols, snap, mesh) is swapped
            assert cols.resident_counters()["sharded"] == after
            moved = {k: after[k] - before.get(k, 0)
                     for k in ("version", "dispatches", "full_uploads",
                               "feature_uploads", "scatter_updates")}
            sharded = cols._per_cycle_dev[mesh]
            if cycle == 0:
                assert moved["feature_uploads"] == len(TASK_FEATURE_FIELDS)
                assert sharded._shard_layout.fields, (
                    "no node-axis column took the per-shard layout")
                packed = {f for f, *_ in sharded._layout.fields}
                assert packed and not packed & NODE_AXIS_FIELDS
                tiny = len(SWAP_FIELDS) - len(packed) - len(
                    sharded._shard_layout.fields)
            else:
                assert moved["version"] == 1
                assert moved["feature_uploads"] == 0, moved
                assert compiles == 0, f"cycle {cycle} compiled {compiles}"
                # two programs at most; every other call is a whole put of
                # a column too small for its own payload
                assert moved["dispatches"] - moved["full_uploads"] <= 2, moved
                assert moved["full_uploads"] <= tiny, moved
                assert moved["scatter_updates"] > 0, moved
            for name in conf.actions:
                get_action(name).execute(ssn)
            assert get_action("allocate").last_solve_mode == "sharded"
        finally:
            close_session(ssn)
        cache.flush_binds()
    assert cols.check_consistency(cache) == []


def test_sharded_delta_vs_full_vs_single_bit_exact(_env_guard):
    """Identical churn on three caches — sharded+delta, sharded with the
    resident cache disabled (full uploads), and the single-device solve —
    must produce identical bind sequences and end state."""
    conf = load_scheduler_conf(None)

    os.environ.pop("KB_DEVICE_CACHE", None)
    os.environ.pop("KB_SHARD", None)
    binds_delta, status_delta = _run_cycles(_mk_cache(), conf)

    os.environ["KB_DEVICE_CACHE"] = "0"
    binds_full, status_full = _run_cycles(_mk_cache(), conf)
    os.environ.pop("KB_DEVICE_CACHE", None)

    os.environ["KB_SHARD"] = "0"
    binds_single, status_single = _run_cycles(_mk_cache(), conf)
    os.environ.pop("KB_SHARD", None)

    assert binds_delta == binds_full, "sharded delta vs full binds diverged"
    assert status_delta == status_full
    assert binds_delta == binds_single, "sharded vs single binds diverged"
    assert status_delta == status_single


def test_mesh_change_falls_back_to_full_upload():
    """A mesh change (reshard / device-set change) must drop the old
    sharded cache wholesale and full-upload once on the new mesh."""
    from kube_batch_tpu.api.columns import resident_snap
    from kube_batch_tpu.parallel.mesh import make_mesh

    cache = _mk_cache()
    conf = load_scheduler_conf(None)
    cols = cache.columns
    ssn = open_session(cache, conf.tiers)
    try:
        snap, _meta = cols.device_snapshot(ssn)
        mesh8 = make_mesh(8)
        resident_snap(cols, snap, mesh8)
        c8 = cols._per_cycle_dev.get(mesh8)
        assert c8 is not None and c8.full_uploads > 0
        # reshard to a 4-device mesh: the 8-device cache must be dropped
        mesh4 = make_mesh(4)
        swapped = resident_snap(cols, snap, mesh4)
        assert cols._per_cycle_dev.get(mesh8) is None
        c4 = cols._per_cycle_dev.get(mesh4)
        assert c4 is not None and c4.full_uploads > 0
        host = np.asarray(snap.node_idle)
        assert np.array_equal(host, np.asarray(swapped.node_idle))
    finally:
        close_session(ssn)


def test_high_churn_delta_falls_back_to_full_upload(monkeypatch):
    """A per-shard delta wider than the slot budget re-uploads the whole
    (sharded) column — values stay exact either way."""
    from kube_batch_tpu.api import resident as res
    from kube_batch_tpu.parallel.mesh import make_mesh

    # shrink the per-shard budget so a 16-row single-shard delta overflows
    monkeypatch.setattr(res, "SHARD_SCATTER_SLOTS", 8)
    cache = _mk_cache()
    conf = load_scheduler_conf(None)
    cols = cache.columns
    ssn = open_session(cache, conf.tiers)
    try:
        snap, _meta = cols.device_snapshot(ssn)
        c = res.ShardedPerCycleDeviceCache(make_mesh(8))
        c.swap(snap)
        uploads0, scatters0 = c.full_uploads, c.scatter_updates
        # 16 changed rows land in shard 0 (shard size 32) — over budget
        host = np.asarray(snap.node_idle)
        wide = host.copy()
        wide[:16] += 1.0
        snap2 = snap._replace(node_idle=wide)
        swapped = c.swap(snap2)
        assert np.array_equal(wide, np.asarray(swapped.node_idle))
        assert c.full_uploads > uploads0, "wide delta must full-upload"
        # a later small delta rides the scatter again
        wide2 = wide.copy()
        wide2[3] += 1.0
        swapped = c.swap(snap2._replace(node_idle=wide2))
        assert np.array_equal(wide2, np.asarray(swapped.node_idle))
        assert c.scatter_updates > scatters0
    finally:
        close_session(ssn)

"""shard_map scale-out: bit-exactness vs the pjit oracle and the
single-device solve, zero steady-state retraces on both impls, the
authored-collective byte accounting, and the task-axis (2-D mesh) cycle.

The conftest forces an 8-device virtual CPU mesh; clusters here pad past
SHARD_MIN_NODES so the allocate action dispatches sharded.  KB_SHARD_MAP
toggles shard_map (default) vs the pjit oracle; KB_TASK_SHARDS=2 selects
the 2-D (tasks × nodes) mesh; KB_SHARD=0 forces the single-device path.
"""

from __future__ import annotations

import itertools
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

_ENV_KEYS = ("KB_SHARD", "KB_SHARD_MAP", "KB_TASK_SHARDS", "KB_DEVICE_CACHE")


@pytest.fixture
def _env_guard():
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _mk_cache(seed=0, n_tasks=N_TASKS, n_nodes=N_NODES):
    return synthetic_cluster(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=2, seed=seed
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
        name=f"sm{j}", namespace="shardmap", min_member=2,
        queue=f"q{j % 2}", creation_index=20_000 + j,
    ))
    for t in range(2):
        cache.add_pod(Pod(
            name=f"sm{j}-{t}", namespace="shardmap",
            requests={"cpu": float(rng.choice([250.0, 500.0])),
                      "memory": float(2 ** 30)},
            annotations={GROUP_NAME_ANNOTATION: f"sm{j}"},
            phase=PodPhase.PENDING,
            creation_index=(20_000 + j) * 10 + t,
        ))


def _run_cycles(cache, conf, cycles=4, seed=7):
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


def _session_snapshot(seed=3, **size):
    cache = _mk_cache(seed, **size)
    conf = load_scheduler_conf(None)
    ssn = open_session(cache, conf.tiers)
    try:
        from kube_batch_tpu.actions.allocate import (
            build_session_snapshot,
            session_allocate_config,
        )

        snap, _meta = build_session_snapshot(ssn)
        config = session_allocate_config(ssn)
    finally:
        close_session(ssn)
    return snap, config


# --------------------------------------------------------------------------
# cycle-level equivalence over randomized churn
# --------------------------------------------------------------------------


def test_cycles_shard_map_vs_pjit_vs_single(_env_guard):
    """Identical churn on three caches — shard_map (default), the pjit
    oracle (KB_SHARD_MAP=0), and the single-device solve (KB_SHARD=0) —
    must produce identical bind sequences and end state."""
    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)

    binds_sm, status_sm = _run_cycles(_mk_cache(), conf)
    assert get_action("allocate").last_solve_mode == "sharded"

    os.environ["KB_SHARD_MAP"] = "0"
    binds_pj, status_pj = _run_cycles(_mk_cache(), conf)
    os.environ.pop("KB_SHARD_MAP")

    os.environ["KB_SHARD"] = "0"
    binds_1, status_1 = _run_cycles(_mk_cache(), conf)
    os.environ.pop("KB_SHARD")

    assert binds_sm == binds_pj, "shard_map vs pjit binds diverged"
    assert status_sm == status_pj
    assert binds_sm == binds_1, "shard_map vs single-device binds diverged"
    assert status_sm == status_1


def test_cycles_task_axis_sharded(_env_guard):
    """A 2-D (tasks=2 × nodes=4) mesh cycle (KB_TASK_SHARDS=2) must match
    the single-device cycle bit-for-bit — the task-axis-sharded
    equivalence case."""
    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)

    os.environ["KB_TASK_SHARDS"] = "2"
    binds_2d, status_2d = _run_cycles(_mk_cache(), conf)
    assert get_action("allocate").last_solve_mode == "sharded"
    os.environ.pop("KB_TASK_SHARDS")

    os.environ["KB_SHARD"] = "0"
    binds_1, status_1 = _run_cycles(_mk_cache(), conf)

    assert binds_2d == binds_1, "task-axis-sharded binds diverged"
    assert status_2d == status_1


# --------------------------------------------------------------------------
# solve-level equivalence on a forced-4-device mesh (not the conftest 8)
# --------------------------------------------------------------------------


def test_forced_4_device_solves_bit_exact(_env_guard):
    import jax

    from kube_batch_tpu.ops.assignment import (
        allocate_solve,
        failure_histogram_solve,
    )
    from kube_batch_tpu.ops.eviction import EvictConfig, evict_solve
    from kube_batch_tpu.parallel.mesh import make_mesh, program

    snap, config = _session_snapshot()
    mesh = make_mesh(4)
    local = jax.device_get(allocate_solve(snap, config))
    with mesh:
        sm = jax.device_get(
            program("full", mesh, "shard_map", config)(snap))
        pj = jax.device_get(
            program("full", mesh, "pjit", config)(snap))
    for name in local._fields:
        assert np.array_equal(getattr(local, name), getattr(sm, name)), (
            f"shard_map {name} diverged on the 4-device mesh")
        assert np.array_equal(getattr(local, name), getattr(pj, name)), (
            f"pjit {name} diverged on the 4-device mesh")

    hist = jax.device_get(failure_histogram_solve(snap))
    with mesh:
        hist_sm = jax.device_get(
            program("fail_hist", mesh, "shard_map", None)(snap))
    assert np.array_equal(hist, hist_sm)

    for mode in ("reclaim", "preempt"):
        ec = EvictConfig(mode=mode, idle_gate=(mode == "reclaim"))
        ev = jax.device_get(evict_solve(snap, ec))
        with mesh:
            ev_sm = jax.device_get(
                program("evict", mesh, "shard_map", ec)(snap))
        for name in ev._fields:
            assert np.array_equal(getattr(ev, name), getattr(ev_sm, name)), (
                f"shard_map evict[{mode}] {name} diverged")


@pytest.mark.parametrize("kind", ["full", "topk"])
def test_capped_pass_solves_bit_exact_on_the_test_mesh(kind):
    """An input that meets the round cap while still placing (600 pods in
    gangs of 4 over 48 nodes, 2 rounds a pass: the first pass ends with
    gangs half way and carries them on): shard_map = pjit = single device
    on the conftest's 8 devices, full matrix and compacted."""
    import jax

    from kube_batch_tpu.ops.assignment import allocate_solve
    from kube_batch_tpu.parallel.mesh import make_mesh, program

    snap, config = _session_snapshot(n_tasks=600, n_nodes=48)
    config = config._replace(rounds=2)
    local = jax.device_get(allocate_solve(snap, config))
    assert 2 < int(local.rounds_run) <= 2 * config.outer
    assert (local.assigned >= 0).sum() == 600
    if kind == "topk":
        config = config._replace(topk=4)
        rows = np.full(1024, -1, np.int32)
        pend = np.flatnonzero(np.asarray(snap.task_pending))
        rows[: pend.size] = pend
        args = (snap, rows)
    else:
        args = (snap,)
    mesh = make_mesh()
    assert mesh.devices.size == 8
    with mesh:
        got = {impl: jax.device_get(program(kind, mesh, impl, config)(*args))
               for impl in ("shard_map", "pjit")}
    for impl, res in got.items():
        for name in local._fields:
            if name.startswith("topk_"):
                continue
            assert np.array_equal(getattr(local, name), getattr(res, name)), (
                f"{impl} {kind} {name} diverged on a capped input")


def test_enqueue_gate_mesh_matches_single():
    import jax

    from kube_batch_tpu.ops.admission import enqueue_gate_solve
    from kube_batch_tpu.parallel.mesh import make_mesh, program

    rng = np.random.default_rng(11)
    minr = rng.uniform(0, 4, (64, 3)).astype(np.float32)
    cand = rng.random(64) < 0.6
    idle0 = np.asarray([40.0, 30.0, 20.0], np.float32)
    quanta = np.full(3, 1e-3, np.float32)
    single = np.asarray(
        jax.device_get(enqueue_gate_solve(minr, cand, idle0, quanta)))
    mesh = make_mesh(8)
    with mesh:
        sharded = np.asarray(jax.device_get(
            program("gate", mesh, None, None)(minr, cand, idle0, quanta)))
    assert np.array_equal(single, sharded)


# --------------------------------------------------------------------------
# zero steady-state retraces on both impls
# --------------------------------------------------------------------------


@pytest.mark.parametrize("impl_env", [{}, {"KB_SHARD_MAP": "0"}])
def test_zero_steady_state_retraces(_env_guard, impl_env):
    from kube_batch_tpu.utils import jitstats

    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    os.environ.update(impl_env)
    cache = _mk_cache(seed=5)
    rng = np.random.default_rng(9)
    serial = itertools.count(1)

    def cycle():
        _churn(cache, rng, serial)
        ssn = open_session(cache, conf.tiers)
        try:
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()

    for _ in range(3):   # warmup: compiles + scatter prewarm
        cycle()
    before = jitstats.total_compiles()
    for _ in range(3):   # steady state
        cycle()
    assert jitstats.total_compiles() == before, (
        f"steady-state retrace on impl={impl_env or 'shard_map'}")


# --------------------------------------------------------------------------
# authored-collective byte accounting
# --------------------------------------------------------------------------


def test_collective_bytes_scale_with_tasks_not_nodes():
    """The traced per-round collective bytes must be invariant to the node
    count and linear in the task count — the O(tasks) comms claim, checked
    against the compiled program's jaxpr."""
    from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot
    from kube_batch_tpu.parallel.mesh import collective_stats, make_mesh

    mesh = make_mesh(8)
    base = collective_stats(mesh, snap=abstract_snapshot(T=256, N=512))
    nodes2 = collective_stats(mesh, snap=abstract_snapshot(T=256, N=1024))
    tasks2 = collective_stats(mesh, snap=abstract_snapshot(T=512, N=512))
    assert base["per_round_bytes"] > 0
    assert nodes2["per_round_bytes"] == base["per_round_bytes"], (
        "per-round collective bytes moved with the node count")
    assert tasks2["per_round_bytes"] == 2 * base["per_round_bytes"], (
        "per-round collective bytes are not linear in the task count")
    # the four dependent reductions (2×pmax, pmin, psum) are fused into
    # ONE stacked-payload all_gather per round — a single DCN latency hop
    round_ops = base["ops"]["per_round"]
    assert set(round_ops) == {"all_gather"}, round_ops
    assert round_ops["all_gather"]["count"] == 1, round_ops
    # the one-per-solve node-ledger gather grows with N, and only it
    assert nodes2["per_solve_bytes"] > base["per_solve_bytes"]
    # nested-loop accounting (KBT204's byte-formula inputs): the bidding
    # rounds are a dynamically-capped while INSIDE the outer gang-pass
    # while, so no static inner trip count exists — the expanded total
    # counts the site ×1 and the unbounded flag marks it as a floor
    assert base["per_round_bytes_expanded"] == base["per_round_bytes"]
    assert base["per_round_has_unbounded_inner_loop"] is True


def test_collective_bytes_task_axis_gathers():
    """On the 2-D mesh the per-round inventory gains the task-axis
    reassembly all_gathers; bytes stay O(tasks)."""
    from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot
    from kube_batch_tpu.parallel.mesh import collective_stats, make_mesh

    mesh2 = make_mesh(8, task_shards=2)
    st = collective_stats(mesh2, snap=abstract_snapshot(T=256, N=512))
    assert "all_gather" in st["ops"]["per_round"]
    nodes2 = collective_stats(mesh2, snap=abstract_snapshot(T=256, N=1024))
    assert nodes2["per_round_bytes"] == st["per_round_bytes"]


# --------------------------------------------------------------------------
# adaptive per-shard scatter slot budgets
# --------------------------------------------------------------------------


def test_adaptive_ladder_shapes():
    from kube_batch_tpu.api.resident import (
        SHARD_SCATTER_SLOT_BUCKETS,
        adaptive_ladder,
    )

    # zero churn reproduces the static default exactly
    assert adaptive_ladder(0.0, 1024) == SHARD_SCATTER_SLOT_BUCKETS
    assert adaptive_ladder(5.0, 1024) == (16, 128, 1024)
    # sustained churn drops the too-small buckets
    assert adaptive_ladder(100.0, 1024) == (256, 1024)
    assert adaptive_ladder(600.0, 1024) == (1024,)
    # the hard cap clamps everything
    assert adaptive_ladder(0.0, 8) == (8,)


def test_ladder_retargets_without_steady_retrace(_env_guard):
    """A sustained churn burst retargets the ladder (prewarming the new
    buckets at the retarget), after which deltas of the new width scatter
    with ZERO fresh compiles — and values stay exact throughout."""
    from kube_batch_tpu.api import resident as res
    from kube_batch_tpu.parallel.mesh import make_mesh
    from kube_batch_tpu.utils import jitstats

    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    snap, _config = _session_snapshot(seed=8)
    c = res.ShardedPerCycleDeviceCache(make_mesh(8))
    c.swap(snap)
    assert c._ladder == res.SHARD_SCATTER_SLOT_BUCKETS
    host = np.asarray(snap.node_idle).copy()
    cur = snap
    # sustained 60-rows-in-one-shard churn: EWMA must climb past the
    # 16-bucket regime and retarget the base bucket upward
    for i in range(1, 14):
        host = host.copy()
        host[:60] += float(i)
        cur = cur._replace(node_idle=host)
        out = c.swap(cur)
        assert np.array_equal(host, np.asarray(out.node_idle))
    assert c.ladder_retargets > 0
    assert c._ladder[0] > 16
    assert c.counters()["slot_ladder"] == list(c._ladder)
    # post-retarget steady state: same-width deltas are jit cache hits
    before = jitstats.total_compiles()
    for i in range(3):
        host = host.copy()
        host[:60] -= 1.0
        cur = cur._replace(node_idle=host)
        out = c.swap(cur)
        assert np.array_equal(host, np.asarray(out.node_idle))
    assert jitstats.total_compiles() == before, (
        "retargeted ladder bucket was not pre-warmed")

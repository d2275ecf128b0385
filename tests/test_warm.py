"""Warm-started incremental allocate (KB_WARM, ISSUE 14): the carried
cross-cycle candidate table + in-program repair must be bit-identical to
the KB_WARM=0 cold per-solve build (and therefore to the KB_TOPK=0 full
program) over randomized multi-cycle churn on all three impls
(single-device, shard_map, pjit); the merge/θ-cut/erosion fixtures pin the
table-refresh algebra at the solve level; the guard plane demotes the warm
path like any other fast path and half-open probes re-promote it; and the
carried table is dropped wholesale on axis growth, mesh changes, and
resident-cache drops (the plan_topk_bucket lifetime satellite).

The conftest forces an 8-device virtual CPU mesh (like test_shard_map);
sharded cases pad past SHARD_MIN_NODES so allocate dispatches sharded.
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

_ENV_KEYS = ("KB_TOPK", "KB_WARM", "KB_SHARD", "KB_SHARD_MAP",
             "KB_TASK_SHARDS")


@pytest.fixture
def _env_guard():
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _churn(cache, rng, serial, namespace="warm"):
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
        name=f"wm{j}", namespace=namespace, min_member=2,
        queue=f"q{j % 2}", creation_index=30_000 + j,
    ))
    for t in range(2):
        cache.add_pod(Pod(
            name=f"wm{j}-{t}", namespace=namespace,
            requests={"cpu": float(rng.choice([250.0, 500.0, 1000.0])),
                      "memory": float(2 ** 30)},
            annotations={GROUP_NAME_ANNOTATION: f"wm{j}"},
            phase=PodPhase.PENDING,
            creation_index=(30_000 + j) * 10 + t,
        ))


def _run_cycles(cache, conf, cycles=6, seed=11):
    rng = np.random.default_rng(seed)
    serial = itertools.count(1)
    binds = []
    warm_cycles = 0
    merge_cycles = 0
    partial_rerank = 0
    for _ in range(cycles):
        _churn(cache, rng, serial)
        ssn = open_session(cache, conf.tiers)
        try:
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()
        lw = get_action("allocate").last_warm
        if lw is not None:
            warm_cycles += 1
            if not lw["cold"]:
                merge_cycles += 1
                if lw["reranked"] < lw["bucket_live"]:
                    partial_rerank += 1
        binds.append(sorted(cache.binder.binds.items()))
    cols = cache.columns
    status = sorted(
        (cols.task_by_row[r]._key, int(cols.t_status[r]))
        for r in np.flatnonzero(cols.t_valid).tolist()
    )
    return binds, status, warm_cycles, merge_cycles, partial_rerank


def _mk_cache(n_tasks=600, n_nodes=48, seed=0):
    return synthetic_cluster(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=2, seed=seed
    )


# --------------------------------------------------------------------------
# cycle-level warm-vs-cold equivalence over randomized churn (3 impls)
# --------------------------------------------------------------------------


def test_cycles_warm_vs_cold_single_device(_env_guard):
    """Identical churn, KB_WARM default (carried table) vs KB_WARM=0 (cold
    per-solve build): binds and end state must be identical; the carry
    must actually engage, take the merge path, and genuinely re-rank less
    than the live bucket (otherwise "warm" is just a renamed cold build)."""
    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    os.environ["KB_SHARD"] = "0"

    # a CONTENDED cluster (standing ~60-row backlog): carried rows exist
    # across cycles, so the merge path can genuinely skip re-ranking them
    binds_w, status_w, wc, mc, partial = _run_cycles(
        _mk_cache(n_tasks=760, n_nodes=36), conf)
    assert wc > 0, "warm carry never engaged"
    assert mc > 0, "warm carry never took the merge path"
    assert partial > 0, "merge cycles always re-ranked the whole bucket"

    os.environ["KB_WARM"] = "0"
    binds_c, status_c, wc_c, _, _ = _run_cycles(
        _mk_cache(n_tasks=760, n_nodes=36), conf)
    assert wc_c == 0

    assert binds_w == binds_c, "warm vs cold binds diverged"
    assert status_w == status_c


@pytest.mark.parametrize("impl_env", [{}, {"KB_SHARD_MAP": "0"}])
def test_cycles_warm_sharded_vs_cold(_env_guard, impl_env):
    """The sharded carried table (shard_map default, pjit via
    KB_SHARD_MAP=0) against the cold sharded build under the same churn —
    bit-identical binds and end state."""
    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    os.environ.update(impl_env)

    binds_w, status_w, wc, mc, _ = _run_cycles(
        _mk_cache(n_tasks=600, n_nodes=200), conf)
    assert get_action("allocate").last_solve_mode == "sharded"
    assert wc > 0 and mc > 0, "sharded warm carry never engaged/merged"

    os.environ["KB_WARM"] = "0"
    binds_c, status_c, wc_c, _, _ = _run_cycles(
        _mk_cache(n_tasks=600, n_nodes=200), conf)
    assert wc_c == 0

    assert binds_w == binds_c, (
        f"sharded warm vs cold binds diverged ({impl_env or 'shard_map'})")
    assert status_w == status_c


# --------------------------------------------------------------------------
# solve-level: the table-refresh algebra (merge, θ-cut, erosion, re-rank)
# --------------------------------------------------------------------------


def _session_snapshot(n_tasks, n_nodes, seed=3):
    cache = synthetic_cluster(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=2, n_queues=2, seed=seed
    )
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


def _pend_rows(snap, bucket):
    rows = np.flatnonzero(np.asarray(snap.task_pending))
    assert 0 < rows.size <= bucket
    out = np.full(bucket, -1, np.int32)
    out[: rows.size] = rows.astype(np.int32)
    return out


def _zero_table(P, W):
    import jax.numpy as jnp

    return (jnp.zeros((P, W), jnp.int32),
            jnp.full((P, W), -(2 ** 31), jnp.int32),
            jnp.full((P, W), -1, jnp.int32),
            jnp.zeros(P, bool))


def _plan(P, row_map=None, changed=(), rerank_rows=None, rerank_slots=None,
          c_slots=8, r_slots=8):
    rm = np.full(P, -1, np.int32) if row_map is None else row_map
    ch = np.full(c_slots, -1, np.int32)
    ch[: len(changed)] = np.asarray(list(changed), np.int32)
    rr = np.full(r_slots, -1, np.int32)
    rs = np.full(r_slots, -1, np.int32)
    if rerank_rows is not None:
        rr[: len(rerank_rows)] = np.asarray(rerank_rows, np.int32)
        rs[: len(rerank_slots)] = np.asarray(rerank_slots, np.int32)
    return (rm, ch, rr, rs)


def _cmp(full, got, tag):
    for name in full._fields:
        if name.startswith("topk_"):
            continue
        assert np.array_equal(getattr(full, name), getattr(got, name)), (
            f"{tag}: diverged on {name}")


@pytest.mark.parametrize("rounds", [6, 2])
def test_warm_solve_carry_merge_and_cut_bit_exact(rounds):
    """The full solve-level life of a carried table: cold build → identity
    carry → displacement merge (a node's key improves and must enter) →
    hard erosion (a table node's budget zeroed: its entries are removed
    and the θ-cut must not resurrect anything) — each step bit-identical
    to the full-matrix AND the cold compacted solve on that snapshot.
    ``rounds=2`` meets the round cap while still placing, so every step
    also runs passes that carry half-placed gangs on."""
    import jax
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import (
        allocate_solve,
        allocate_topk_solve,
        warm_allocate_solve,
    )

    snap, config = _session_snapshot(400, 16, seed=7)
    config = config._replace(rounds=rounds)
    P, K, W = 512, 4, 8
    rows = _pend_rows(snap, P)
    cfg_w = config._replace(topk=W)
    full = jax.device_get(allocate_solve(snap, config))
    assert rounds < int(full.rounds_run) <= rounds * config.outer
    cold = jax.device_get(
        allocate_topk_solve(snap, rows, config._replace(topk=K)))
    _cmp(full, cold, "cold-topk")

    # cold build through the warm program (everything re-ranked)
    live = int((rows >= 0).sum())
    plan0 = _plan(P, rerank_rows=rows[:live],
                  rerank_slots=np.arange(live), r_slots=P)
    res, table, _ = warm_allocate_solve(
        snap, jnp.asarray(rows), _zero_table(P, W), plan0, cfg_w, K)
    _cmp(full, jax.device_get(res), "warm-cold-build")

    # identity carry: nothing changed → no re-rank, no changed nodes
    ident = _plan(P, row_map=np.arange(P, dtype=np.int32))
    res, table, _ = warm_allocate_solve(
        snap, jnp.asarray(rows), table, ident, cfg_w, K)
    _cmp(full, jax.device_get(res), "warm-identity-carry")

    # displacement: free half of node 3's used capacity — its score rises
    # and the merge must insert it exactly where the full argmax would
    ni = np.asarray(snap.node_idle).copy()
    nu = np.asarray(snap.node_used).copy()
    freed = nu[3] * 0.5
    ni[3] += freed
    nu[3] -= freed
    snap2 = snap._replace(node_idle=jnp.asarray(ni),
                          node_used=jnp.asarray(nu))
    full2 = jax.device_get(allocate_solve(snap2, config))
    res, table, _ = warm_allocate_solve(
        snap2, jnp.asarray(rows), table,
        _plan(P, row_map=np.arange(P, dtype=np.int32), changed=[3]),
        cfg_w, K)
    _cmp(full2, jax.device_get(res), "warm-displacement-merge")

    # erosion: zero node 3's idle — carried entries for it are removed,
    # the θ-cut keeps the remainder an exact prefix
    ni3 = np.asarray(snap2.node_idle).copy()
    nu3 = np.asarray(snap2.node_used).copy()
    nu3[3] += ni3[3]
    ni3[3] = 0.0
    snap3 = snap2._replace(node_idle=jnp.asarray(ni3),
                           node_used=jnp.asarray(nu3))
    full3 = jax.device_get(allocate_solve(snap3, config))
    res, table, _ = warm_allocate_solve(
        snap3, jnp.asarray(rows), table,
        _plan(P, row_map=np.arange(P, dtype=np.int32), changed=[3]),
        cfg_w, K)
    _cmp(full3, jax.device_get(res), "warm-erosion-cut")


def test_warm_erosion_flags_rows_for_rerank():
    """A W=2 table whose best node dies must flag the affected rows as
    eroded (truncated AND valid prefix below k_min) — the signal the host
    planner re-ranks on next cycle — while staying bit-exact."""
    import jax
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import (
        allocate_solve,
        warm_allocate_solve,
    )

    # 16 nodes against W=2 tables: rows are TRUNCATED at build (feasible
    # nodes beyond the stored width exist), so losing a table head is a
    # genuine coverage loss the erosion flag must report
    snap, config = _session_snapshot(400, 16, seed=5)
    P, K, W = 512, 2, 2
    rows = _pend_rows(snap, P)
    cfg_w = config._replace(topk=W)
    live = int((rows >= 0).sum())
    plan0 = _plan(P, rerank_rows=rows[:live],
                  rerank_slots=np.arange(live), r_slots=P)
    _res, table, eroded0 = warm_allocate_solve(
        snap, jnp.asarray(rows), _zero_table(P, W), plan0, cfg_w, K)
    # live rows healthy after the build (padding slots flag eroded by
    # design — they carry empty always-truncated tables the planner
    # never maps to a task)
    assert not bool(np.any(np.asarray(eroded0)[:live]))

    # kill the most popular table node (mode of slot-0 indices)
    t_idx = np.asarray(table[0])
    top = np.bincount(t_idx[:live, 0]).argmax()
    ni = np.asarray(snap.node_idle).copy()
    nu = np.asarray(snap.node_used).copy()
    nv = np.asarray(snap.node_sched).copy()
    nv[top] = False  # unschedulable → statically infeasible for everyone
    snap2 = snap._replace(node_sched=jnp.asarray(nv),
                          node_idle=jnp.asarray(ni),
                          node_used=jnp.asarray(nu))
    full2 = jax.device_get(allocate_solve(snap2, config))
    res, _table, eroded = warm_allocate_solve(
        snap2, jnp.asarray(rows), table,
        _plan(P, row_map=np.arange(P, dtype=np.int32), changed=[int(top)]),
        cfg_w, K)
    _cmp(full2, jax.device_get(res), "erosion-fixture")
    live_rows = rows[:live]
    assert bool(np.any(np.asarray(eroded)[:live][live_rows >= 0])), (
        "no row flagged eroded after its table head died")


def test_warm_task_invalidation_rerank_bit_exact():
    """A row whose OWN features change (its request grows) is re-ranked by
    the planner; the warm program with that row in the rerank sub-bucket
    must match the full solve on the mutated snapshot."""
    import jax
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import (
        allocate_solve,
        warm_allocate_solve,
    )

    snap, config = _session_snapshot(400, 16, seed=9)
    P, K, W = 512, 4, 8
    rows = _pend_rows(snap, P)
    cfg_w = config._replace(topk=W)
    live = int((rows >= 0).sum())
    plan0 = _plan(P, rerank_rows=rows[:live],
                  rerank_slots=np.arange(live), r_slots=P)
    _res, table, _ = warm_allocate_solve(
        snap, jnp.asarray(rows), _zero_table(P, W), plan0, cfg_w, K)

    victim_slot = live // 2
    victim_row = int(rows[victim_slot])
    req = np.asarray(snap.task_req).copy()
    req[victim_row] *= 2.0
    snap2 = snap._replace(task_req=jnp.asarray(req))
    full2 = jax.device_get(allocate_solve(snap2, config))
    res, _t, _ = warm_allocate_solve(
        snap2, jnp.asarray(rows), table,
        _plan(P, row_map=np.arange(P, dtype=np.int32),
              rerank_rows=[victim_row], rerank_slots=[victim_slot]),
        cfg_w, K)
    _cmp(full2, jax.device_get(res), "task-invalidation-rerank")


# --------------------------------------------------------------------------
# guard plane: warm demotes like any fast path, half-open re-promotes
# --------------------------------------------------------------------------


def test_guard_demotes_warm_and_repromotes(_env_guard):
    """A trip attributed to the warm path pins the dispatch to the cold
    build (last_warm None, compaction still engaged); after the cooldown's
    clean cycles the half-open probe runs warm again and one clean engaged
    cycle re-promotes."""
    from kube_batch_tpu.guard import guard_of

    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    os.environ["KB_SHARD"] = "0"
    cache = _mk_cache()
    rng = np.random.default_rng(23)
    serial = itertools.count(1)
    gp = guard_of(cache)
    gp.cooldown = 2

    def cycle():
        _churn(cache, rng, serial)
        ssn = open_session(cache, conf.tiers)
        try:
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()
        gp.end_cycle()

    for _ in range(3):
        cycle()
    assert get_action("allocate").last_warm is not None

    gp.trip("allocate", ["warm"], reason="test", detail="forced")
    assert gp.paths["warm"].state == "demoted"
    cycle()
    assert get_action("allocate").last_warm is None, (
        "demoted warm path still dispatched the carry")
    assert get_action("allocate").last_topk is not None, (
        "warm demotion must not take compaction down with it")
    while gp.paths["warm"].state == "demoted":
        cycle()
    assert gp.paths["warm"].state == "probing"
    cycle()  # the half-open probe runs warm and promotes on the clean cycle
    assert get_action("allocate").last_warm is not None
    assert gp.paths["warm"].state == "healthy"
    assert gp.paths["warm"].promotions >= 1


# --------------------------------------------------------------------------
# table lifetime: axis growth / resident drops / mesh changes drop wholesale
# --------------------------------------------------------------------------


def test_warm_table_dropped_on_axis_growth_and_resident_drop(_env_guard):
    """The plan_topk_bucket lifetime satellite: a cache axis re-grow
    (ColumnStore.reserve) and a resident drop (guard heal) must invalidate
    the carried table WHOLESALE, never index-shift it."""
    cache = _mk_cache()
    cols = cache.columns
    st = cols.warm_table_state(mesh=None, impl=None)
    assert cols.warm_table_state(mesh=None, impl=None) is st
    cols.reserve(n_tasks=cols.tasks.cap + 1)       # task-axis growth
    assert not cols._warm_tables, "task growth kept the carried table"

    st = cols.warm_table_state(mesh=None, impl=None)
    cols.reserve(n_nodes=cols.nodes.cap + 1)       # node-axis growth
    assert not cols._warm_tables, "node growth kept the carried table"

    st = cols.warm_table_state(mesh=None, impl=None)
    cols.drop_resident()                           # guard heal path
    assert not cols._warm_tables, "drop_resident kept the carried table"
    assert st is not cols.warm_table_state(mesh=None, impl=None)


def test_warm_table_dropped_on_mesh_change(_env_guard):
    """A mesh change drops the old mesh's resident cache AND its carried
    tables — stale node placements must never feed a warm merge."""
    import jax

    from kube_batch_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device (forced-host) backend")
    conf = load_scheduler_conf(None)
    cache = _mk_cache(n_tasks=200, n_nodes=16)
    ssn = open_session(cache, conf.tiers)
    try:
        from kube_batch_tpu.actions.allocate import build_session_snapshot

        snap, _ = build_session_snapshot(ssn)
        cols = cache.columns
        mesh = make_mesh(2)
        cols.per_cycle_resident(snap, mesh=mesh)
        st = cols.warm_table_state(mesh=mesh, impl="shard_map")
        assert (mesh, "shard_map") in cols._warm_tables
        # path flip: the single-device dispatch creates its cache and the
        # abandoned mesh's residency + carried tables go with it
        cols.per_cycle_resident(snap, mesh=None)
        assert (mesh, "shard_map") not in cols._warm_tables
        del st
    finally:
        close_session(ssn)


def test_warm_declines_without_absorbed_delta(_env_guard):
    """A state that has not absorbed the current resident swap (broken
    delta chain — e.g. KB_DEVICE_CACHE=0) must refuse to plan; the
    dispatch then falls back to the cold build."""
    cache = _mk_cache(n_tasks=200, n_nodes=16)
    cols = cache.columns
    st = cols.warm_table_state(mesh=None, impl=None)
    rows = np.full(64, -1, np.int32)
    rows[:4] = [0, 1, 2, 3]
    from kube_batch_tpu.ops.assignment import AllocateConfig

    assert st.plan(cols, rows, 4, AllocateConfig()) is None


# --------------------------------------------------------------------------
# satellite: the bucketed failure histogram
# --------------------------------------------------------------------------


def test_failure_histogram_bucket_matches_full():
    """failure_histogram_bucket_solve == failure_histogram_solve at every
    bucket row (the only rows any consumer reads), single-device and over
    a forced mesh (shard_map + pjit)."""
    import jax
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import (
        failure_histogram_bucket_solve,
        failure_histogram_solve,
    )

    snap, _config = _session_snapshot(240, 8, seed=13)
    rows = _pend_rows(snap, 256)
    live = rows[rows >= 0]
    hf = np.asarray(failure_histogram_solve(snap))
    hb = np.asarray(failure_histogram_bucket_solve(snap, jnp.asarray(rows)))
    assert np.array_equal(hf[live], hb[live])
    assert not hb[[r for r in range(hb.shape[0])
                   if r not in set(live.tolist())]].any()

    if len(jax.devices()) >= 4:
        from kube_batch_tpu.parallel.mesh import make_mesh, program

        mesh = make_mesh(4)
        with mesh:
            hs = np.asarray(
                program("fail_hist_bucket", mesh, "shard_map", None)(
                    snap, jnp.asarray(rows)))
            hp = np.asarray(
                program("fail_hist_bucket", mesh, "pjit", None)(
                    snap, jnp.asarray(rows)))
        assert np.array_equal(hf[live], hs[live])
        assert np.array_equal(hf[live], hp[live])


# --------------------------------------------------------------------------
# knob parsing
# --------------------------------------------------------------------------


def test_resolve_warm_knob(_env_guard):
    from kube_batch_tpu.actions.allocate import resolve_warm

    os.environ.pop("KB_WARM", None)
    assert resolve_warm() is True
    os.environ["KB_WARM"] = "0"
    assert resolve_warm() is False
    os.environ["KB_WARM"] = "1"
    assert resolve_warm() is True
    # garbage DISABLES — a typo'd disable attempt must not silently
    # re-enable the fast path under an oracle comparison (KB_TOPK rule)
    os.environ["KB_WARM"] = "offf"
    assert resolve_warm() is False

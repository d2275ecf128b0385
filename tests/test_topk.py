"""Top-K candidate compaction (KB_TOPK, ISSUE 10): compacted-vs-full
bit-exactness over randomized churn on the single-device, shard_map, and
pjit paths; the forced-exhaustion fixture proving the full-matrix re-entry
fires and still matches; the exact-lex-top-K extraction against a numpy
reference; zero steady-state retraces on the compacted path; and the
zero-per-round-collective contract of the compacted shard_map program.

The conftest forces an 8-device virtual CPU mesh (like test_shard_map);
clusters in the sharded cases pad past SHARD_MIN_NODES so the allocate
action dispatches sharded.
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

_ENV_KEYS = ("KB_TOPK", "KB_SHARD", "KB_SHARD_MAP", "KB_TASK_SHARDS")


@pytest.fixture
def _env_guard():
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _churn(cache, rng, serial, namespace="topk"):
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
        name=f"tk{j}", namespace=namespace, min_member=2,
        queue=f"q{j % 2}", creation_index=30_000 + j,
    ))
    for t in range(2):
        cache.add_pod(Pod(
            name=f"tk{j}-{t}", namespace=namespace,
            requests={"cpu": float(rng.choice([250.0, 500.0, 1000.0])),
                      "memory": float(2 ** 30)},
            annotations={GROUP_NAME_ANNOTATION: f"tk{j}"},
            phase=PodPhase.PENDING,
            creation_index=(30_000 + j) * 10 + t,
        ))


def _run_cycles(cache, conf, cycles=5, seed=11):
    rng = np.random.default_rng(seed)
    serial = itertools.count(1)
    binds = []
    compacted = 0
    for _ in range(cycles):
        _churn(cache, rng, serial)
        ssn = open_session(cache, conf.tiers)
        try:
            for name in conf.actions:
                get_action(name).execute(ssn)
        finally:
            close_session(ssn)
        cache.flush_binds()
        if get_action("allocate").last_topk is not None:
            compacted += 1
        binds.append(sorted(cache.binder.binds.items()))
    cols = cache.columns
    status = sorted(
        (cols.task_by_row[r]._key, int(cols.t_status[r]))
        for r in np.flatnonzero(cols.t_valid).tolist()
    )
    return binds, status, compacted


def _mk_cache(n_tasks=600, n_nodes=48, seed=0):
    # n_tasks pads past the smallest pending bucket (256) so steady churn
    # cycles take the compacted dispatch; the first (cold) cycle's full
    # pending set exceeds the bucket gate and runs the full program
    return synthetic_cluster(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=2, seed=seed
    )


# --------------------------------------------------------------------------
# cycle-level compacted-vs-full equivalence over randomized churn
# --------------------------------------------------------------------------


def test_cycles_topk_vs_full_single_device(_env_guard):
    """Identical churn, KB_TOPK default (compacted) vs KB_TOPK=0 (the
    full-matrix oracle), single-device: binds and end state must be
    identical, and the compacted dispatch must actually engage."""
    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    os.environ["KB_SHARD"] = "0"

    binds_t, status_t, compacted = _run_cycles(_mk_cache(), conf)
    assert compacted > 0, "compacted dispatch never engaged"

    os.environ["KB_TOPK"] = "0"
    binds_f, status_f, compacted_f = _run_cycles(_mk_cache(), conf)
    assert compacted_f == 0

    assert binds_t == binds_f, "compacted vs full binds diverged"
    assert status_t == status_f


@pytest.mark.parametrize("impl_env", [{}, {"KB_SHARD_MAP": "0"}])
def test_cycles_topk_sharded_vs_full(_env_guard, impl_env):
    """The sharded compacted path (shard_map default, pjit oracle via
    KB_SHARD_MAP=0) against the full-matrix sharded program under the same
    churn — bit-identical binds and end state."""
    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    os.environ.update(impl_env)

    binds_t, status_t, compacted = _run_cycles(
        _mk_cache(n_tasks=600, n_nodes=200), conf)
    assert get_action("allocate").last_solve_mode == "sharded"
    assert compacted > 0, "sharded compacted dispatch never engaged"

    os.environ["KB_TOPK"] = "0"
    binds_f, status_f, _ = _run_cycles(
        _mk_cache(n_tasks=600, n_nodes=200), conf)

    assert binds_t == binds_f, (
        f"sharded compacted vs full binds diverged ({impl_env or 'shard_map'})")
    assert status_t == status_f


# --------------------------------------------------------------------------
# solve-level: forced exhaustion + direct equivalence
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


def test_forced_exhaustion_fallback_bit_exact():
    """The adversarial fixture: a tiny K against hot node contention (240
    pending tasks bidding for 8 nodes) forces candidate lists to exhaust
    mid-solve.  The full-matrix re-entry must fire (counters > 0) and the
    result must still be bit-identical to the full program."""
    import jax

    from kube_batch_tpu.ops.assignment import allocate_solve, allocate_topk_solve

    snap, config = _session_snapshot(240, 8)
    full = jax.device_get(allocate_solve(snap, config))
    rows = _pend_rows(snap, 256)
    topk = jax.device_get(
        allocate_topk_solve(snap, rows, config._replace(topk=2))
    )
    for name in full._fields:
        if name.startswith("topk_"):
            continue
        assert np.array_equal(getattr(full, name), getattr(topk, name)), (
            f"exhaustion fixture diverged on {name}")
    assert int(topk.topk_exhausted) > 0, "fixture never exhausted"
    assert int(topk.topk_reentries) > 0, "full-head re-entry never fired"


def test_forced_exhaustion_sharded_bit_exact():
    """The same exhaustion fixture through the shard_map and pjit compacted
    programs on a forced 4-device mesh."""
    import jax

    from kube_batch_tpu.ops.assignment import allocate_solve
    from kube_batch_tpu.parallel.mesh import make_mesh, program

    snap, config = _session_snapshot(240, 8)
    full = jax.device_get(allocate_solve(snap, config))
    rows = _pend_rows(snap, 256)
    cfg = config._replace(topk=2)
    mesh = make_mesh(4)
    with mesh:
        sm = jax.device_get(
            program("topk", mesh, "shard_map", cfg)(snap, rows))
        pj = jax.device_get(
            program("topk", mesh, "pjit", cfg)(snap, rows))
    for name in full._fields:
        if name.startswith("topk_"):
            continue
        assert np.array_equal(getattr(full, name), getattr(sm, name)), (
            f"shard_map exhaustion fixture diverged on {name}")
        assert np.array_equal(getattr(full, name), getattr(pj, name)), (
            f"pjit exhaustion fixture diverged on {name}")
    assert int(sm.topk_exhausted) > 0
    assert int(sm.topk_exhausted) == int(pj.topk_exhausted)


@pytest.mark.parametrize("rounds", [6, 2])
def test_solve_level_topk_matches_full_randomized(rounds):
    """Direct solve-level equivalence across K widths on a contended
    snapshot (no cycle machinery in the loop).  ``rounds=2`` meets the
    round cap while still placing, so passes carry half-placed gangs on."""
    import jax

    from kube_batch_tpu.ops.assignment import allocate_solve, allocate_topk_solve

    snap, config = _session_snapshot(400, 16, seed=7)
    config = config._replace(rounds=rounds)
    full = jax.device_get(allocate_solve(snap, config))
    assert rounds < int(full.rounds_run) <= rounds * config.outer
    rows = _pend_rows(snap, 512)
    for k in (2, 4, 8):
        topk = jax.device_get(
            allocate_topk_solve(snap, rows, config._replace(topk=k))
        )
        for name in full._fields:
            if name.startswith("topk_"):
                continue
            assert np.array_equal(getattr(full, name), getattr(topk, name)), (
                f"K={k} diverged on {name}")


# --------------------------------------------------------------------------
# the exact-lex-top-K extraction itself
# --------------------------------------------------------------------------


def test_lex_topk_matches_reference():
    """lex_topk against a brute-force lexicographic sort, under heavy
    score AND hash ties (the adversarial regime the two-key order exists
    for), including the order of the emitted list."""
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import NEG, f32_sort_key, lex_topk

    rng = np.random.default_rng(5)
    P, M, K = 40, 150, 12
    score = np.round(rng.uniform(0, 3, (P, M)) * 4).astype(np.float32) / 4
    score[rng.random((P, M)) < 0.35] = NEG
    hashes = rng.integers(0, 5, (P, M)).astype(np.int32)
    skey = np.asarray(f32_sort_key(jnp.asarray(score)))
    idx0 = np.broadcast_to(np.arange(M, dtype=np.int32), (P, M)).copy()
    oi, os_, oh = lex_topk(
        jnp.asarray(skey), jnp.asarray(hashes), jnp.asarray(idx0), K, 32
    )
    oi = np.asarray(oi)
    for p in range(P):
        ref = sorted(
            range(M), key=lambda n: (-skey[p, n], -hashes[p, n], n)
        )[:K]
        assert ref == oi[p].tolist(), f"row {p} extraction order diverged"


def test_f32_sort_key_is_monotone():
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import f32_sort_key

    vals = np.asarray(
        [-3.0e38, -1.0e10, -1.5, -1.0, -1e-30, 0.0, 1e-30, 1.0, 2.5, 3.0e38],
        np.float32,
    )
    keys = np.asarray(f32_sort_key(jnp.asarray(vals)))
    assert (np.diff(keys) > 0).all()
    # the two zeros compare EQUAL as floats and must key equal too — a
    # custom extra_rows score emitting -0.0 must not order differently
    # from the float-comparing full-matrix oracle
    zeros = np.asarray(f32_sort_key(jnp.asarray([-0.0, 0.0], jnp.float32)))
    assert zeros[0] == zeros[1]


def test_resolve_topk_garbage_disables(_env_guard):
    from kube_batch_tpu.actions.allocate import TOPK_DEFAULT, resolve_topk

    os.environ.pop("KB_TOPK", None)
    assert resolve_topk() == TOPK_DEFAULT
    os.environ["KB_TOPK"] = "16"
    assert resolve_topk() == 16
    # a typo'd attempt to DISABLE must not silently re-enable compaction
    os.environ["KB_TOPK"] = "off"
    assert resolve_topk() == 0
    os.environ["KB_TOPK"] = "0"
    assert resolve_topk() == 0


# --------------------------------------------------------------------------
# dispatch planning: bucket ladder + ratchet
# --------------------------------------------------------------------------


def test_plan_topk_bucket_is_shape_derived(_env_guard):
    """The pending bucket is a pure function of the task-capacity shape —
    the zero-steady-retrace guarantee: no pending-count wobble can move
    the compacted program's shapes while the cache's own buckets hold."""
    from kube_batch_tpu.actions.allocate import (
        plan_topk_bucket,
        topk_bucket_for,
    )

    snap, _config = _session_snapshot(600, 48)
    capT = snap.task_req.shape[0]
    bucket = topk_bucket_for(capT)
    assert bucket is not None and bucket <= capT // 4
    # steady-state shape: a handful of pending rows in a big task bucket
    pend = np.zeros(capT, bool)
    pend[5:17] = True
    snap = snap._replace(task_pending=pend)
    rows, k = plan_topk_bucket(snap, None, 32)
    assert rows is not None and k == 32
    assert rows.shape[0] == bucket
    assert rows[11] == 16 and rows[12] == -1
    # a different pending count maps to the SAME bucket
    pend2 = np.zeros(capT, bool)
    pend2[: bucket] = True
    rows2, _ = plan_topk_bucket(snap._replace(task_pending=pend2), None, 32)
    assert rows2.shape[0] == bucket
    # pending past the bucket declines (cold start → full program)
    pend3 = np.zeros(capT, bool)
    pend3[: bucket + 1] = True
    assert plan_topk_bucket(
        snap._replace(task_pending=pend3), None, 32) == (None, 0)
    # K >= node bucket declines compaction; K=0 declines
    assert plan_topk_bucket(snap, None, 10 ** 6) == (None, 0)
    assert plan_topk_bucket(snap, None, 0) == (None, 0)
    # tiny task buckets have no compaction rung
    assert topk_bucket_for(512) is None


# --------------------------------------------------------------------------
# zero steady-state retraces + zero per-round collectives
# --------------------------------------------------------------------------


def test_zero_steady_state_retraces_compacted(_env_guard):
    """Churn cycles with the compacted dispatch on: after warmup, no jit
    entry point may retrace (the bucket ratchet makes boundary flapping
    structurally impossible)."""
    from kube_batch_tpu.utils import jitstats

    conf = load_scheduler_conf(None)
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    cache = _mk_cache(n_tasks=600, n_nodes=200, seed=9)
    rng = np.random.default_rng(13)
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

    for _ in range(3):
        cycle()
    assert get_action("allocate").last_topk is not None
    before = jitstats.total_compiles()
    for _ in range(3):
        cycle()
    assert jitstats.total_compiles() == before, (
        "steady-state retrace on the compacted path")


def test_compacted_shard_map_zero_round_collectives():
    """The compacted shard_map program's traced collective inventory:
    everything (candidate merge, ledger + node-column gathers) is
    per-solve; the round loop crosses ZERO bytes."""
    from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot
    from kube_batch_tpu.ops.assignment import AllocateConfig
    from kube_batch_tpu.parallel.mesh import collective_stats, make_mesh

    mesh = make_mesh(8)
    st = collective_stats(
        mesh, config=AllocateConfig(topk=4),
        snap=abstract_snapshot(T=256, N=512), pend_bucket=64,
    )
    assert st["per_round_bytes"] == 0, st["ops"]["per_round"]
    assert st["ops"]["per_round"] == {}
    assert st["per_solve_bytes"] > 0

"""Multi-chip dryrun body — run as `python -m kube_batch_tpu.testing.dryrun N`.

This module holds the actual mesh work for `__graft_entry__.dryrun_multichip`.
It is executed in a *fresh child process* whose environment was set before
any jax import (JAX_PLATFORMS=cpu, XLA_FLAGS
--xla_force_host_platform_device_count=N): the virtual device count is fixed
when the backend initialises, so it cannot be changed in a process that has
already touched jax.

Mirrors the reference's multi-core fan-out obligation (SURVEY.md §2.8, §5.7):
the node axis is sharded over the device mesh the way scheduler_helper.go:34
fans predicates over 16 workers.
"""

from __future__ import annotations

import sys

import numpy as np


def run(n_devices: int) -> None:
    import jax

    from kube_batch_tpu.ops.assignment import AllocateConfig, allocate_solve
    from kube_batch_tpu.ops.eviction import EvictConfig, evict_solve
    from kube_batch_tpu.parallel.mesh import call, make_mesh, program
    from kube_batch_tpu.testing.synthetic import synthetic_device_snapshot

    assert len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, have {len(jax.devices())}"
    )
    mesh = make_mesh(n_devices)

    # 1. quick smoke at a small shape
    snap, meta = synthetic_device_snapshot(
        n_tasks=256, n_nodes=max(64, n_devices * 8), gang_size=4, n_queues=3,
        gpu_task_frac=0.2,
    )
    result = call(program("full", mesh, None, AllocateConfig()), mesh, snap)
    assigned = np.asarray(result.assigned)[: meta.n_tasks]
    placed = int((assigned >= 0).sum())
    assert placed > 0, "multichip dryrun placed nothing"
    # invariant: no node overcommitted
    assert np.all(np.asarray(result.node_idle) >= -np.asarray(snap.quanta)[None, :])
    print(
        f"dryrun_multichip({n_devices}): placed {placed}/{meta.n_tasks} tasks "
        f"across {meta.n_nodes} sharded nodes — OK"
    )

    # 2. a shape that crosses the 4096 padding bucket (task axis pads to
    # 5120, the multiple-of-1024 regime) with sharded-vs-single equivalence:
    # GSPMD partitioning must be an execution detail, not a semantic one
    snap_big, meta_big = synthetic_device_snapshot(
        n_tasks=5000, n_nodes=1024, gang_size=4, n_queues=3,
    )
    cfg = AllocateConfig()
    sharded = call(program("full", mesh, None, cfg), mesh, snap_big)
    single = allocate_solve(snap_big, cfg)
    s_a = np.asarray(single.assigned)[: meta_big.n_tasks]
    m_a = np.asarray(sharded.assigned)[: meta_big.n_tasks]
    assert (s_a == m_a).all(), "sharded assignment diverged past the 4096 bucket"
    placed_big = int((m_a >= 0).sum())
    assert placed_big > 0
    print(
        f"dryrun_multichip({n_devices}): 5000x1024 (padded 5120, past the "
        f"4096 bucket) placed {placed_big}, sharded == single — OK"
    )

    # 3. the eviction solve sharded over the same mesh (preempt/reclaim's
    # production path on multi-chip parts): most jobs RUNNING on a tight
    # cluster so the pending remainder has genuine claims and victim pools
    snap_ev, meta_ev = synthetic_device_snapshot(
        n_tasks=512, n_nodes=max(16, n_devices * 2), gang_size=4, n_queues=3,
    )
    snap_ev = _with_running(snap_ev, meta_ev, frac=0.7)
    ev_cfg = EvictConfig(mode="reclaim")
    ev_sharded = call(program("evict", mesh, None, ev_cfg), mesh, snap_ev)
    ev_single = evict_solve(snap_ev, ev_cfg)
    assert (
        np.asarray(ev_sharded.claim_node) == np.asarray(ev_single.claim_node)
    ).all(), "sharded eviction solve diverged"
    assert (
        np.asarray(ev_sharded.evicted) == np.asarray(ev_single.evicted)
    ).all()
    n_claims = int((np.asarray(ev_sharded.claim_node)[: meta_ev.n_tasks] >= 0).sum())
    print(
        f"dryrun_multichip({n_devices}): eviction solve sharded == single "
        f"({n_claims} claims) — OK"
    )


def _with_running(snap, meta, frac: float):
    """Mark the first `frac` of jobs RUNNING with round-robin node placement
    and consistent accounting — turns the pending-only synthetic snapshot
    into an eviction scenario (claimants + cross-queue victim pools)."""
    from kube_batch_tpu.api.types import TaskStatus

    task_job = np.asarray(snap.task_job)
    nj, nn = meta.n_jobs, meta.n_nodes
    run_jobs = np.zeros(snap.job_min_avail.shape[0], bool)
    run_jobs[: int(nj * frac)] = True
    run_task = run_jobs[task_job] & np.asarray(snap.task_valid)
    idxs = np.flatnonzero(run_task)
    nodes = (np.arange(idxs.size) % nn).astype(np.int32)
    task_node = np.asarray(snap.task_node).copy()
    task_node[idxs] = nodes
    status = np.asarray(snap.task_status).copy()
    status[idxs] = int(TaskStatus.RUNNING)
    pending = np.asarray(snap.task_pending) & ~run_task
    req = np.asarray(snap.task_resreq)
    used = np.zeros_like(np.asarray(snap.node_used))
    np.add.at(used, nodes, req[idxs])
    idle = np.maximum(np.asarray(snap.node_alloc) - used, 0.0)
    J = snap.job_min_avail.shape[0]
    job_ready = np.bincount(task_job[idxs], minlength=J).astype(np.int32)
    job_allocated = np.zeros_like(np.asarray(snap.job_allocated))
    np.add.at(job_allocated, task_job[idxs], req[idxs])
    Q = snap.queue_weight.shape[0]
    queue_alloc = np.zeros_like(np.asarray(snap.queue_alloc))
    np.add.at(queue_alloc, np.asarray(snap.job_queue)[task_job[idxs]], req[idxs])
    # running jobs become min_available=1 singletons-with-slack: a gang
    # sitting exactly at its minMember can never lose a member
    # (gang.go:71-94), which would leave the eviction scenario victimless
    job_min = np.asarray(snap.job_min_avail).copy()
    job_min[run_jobs] = 1
    return snap._replace(
        task_node=task_node,
        task_status=status,
        task_pending=pending,
        node_idle=idle,
        node_used=used,
        job_ready=job_ready,
        job_allocated=job_allocated,
        queue_alloc=queue_alloc,
        job_min_avail=job_min,
    )


if __name__ == "__main__":
    run(int(sys.argv[1]) if len(sys.argv) > 1 else 8)

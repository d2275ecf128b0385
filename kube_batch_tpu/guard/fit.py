"""Does a program the guard falls back to hold the cluster?

A demotion lands on an oracle: the pjit program for a tripped shard_map,
the full [T, N] matrix for a tripped compaction.  Where the cluster is
sized for the fast path alone, the full matrix may not fit the device; XLA
would then refuse it at compile time or die in an allocation, in the
middle of a cycle and every cycle again.  So a demoted dispatch asks first, with the
repository's own liveness audit (analysis/hbm_audit.py, tier C) at the
shapes of the snapshot in hand and the node shards of the mesh it would
run on, against the device's own memory limit: the answer follows from the
mesh and from what fits, as ``parallel.mesh.should_shard`` does, not from
a size written down anywhere.  The audit overestimates (it charges every
equation's output, XLA fuses them), so a "fits" is safe and a "does not
fit" may be early (at 150,528 x 5,120 it counts 28 GiB for the
single-device full matrix where the TPU's compiler allocates 11.5: on ONE
chip that demotion would fail closed though the program would run; the
audit's oracle, which is the cold start's own program, is therefore not
asked).  A demotion that cannot be shown to fit FAILS CLOSED
(:meth:`GuardPlane.fail_closed`): no solve, no binds, an error in the log,
until the breaker's half-open probe lets the fast path try again.
"""

from __future__ import annotations

import os
from typing import Dict

import jax

#: (program, node shards, shapes) -> bytes the program needs on one device
_needs: Dict[tuple, int] = {}


class OracleUnfit(RuntimeError):
    """The program a demotion selected does not fit the device."""


def device_budget_bytes() -> int:
    """One device's memory: ``KB_HBM_BUDGET`` where it is set (the tier-C
    audit's override), else what the backend reports for this device, else
    the audit's default profile (a backend that reports nothing: the
    CPU's)."""
    from kube_batch_tpu.analysis.hbm_audit import budget_bytes

    if not os.environ.get("KB_HBM_BUDGET", "").strip():
        stats = jax.local_devices()[0].memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    return budget_bytes()[0]


def _point_of(snap):
    """The tier-C shape point of a snapshot: its own axis extents."""
    from kube_batch_tpu.analysis.jaxpr_audit import ShapePoint

    T, R = snap.task_req.shape
    N = snap.node_alloc.shape[0]
    return ShapePoint(
        name="live", tasks=T, nodes=N, T=T, N=N,
        J=snap.job_valid.shape[0], Q=snap.queue_valid.shape[0], R=R,
        W=snap.task_sel_bits.shape[1], K_aff=snap.task_aff_idx.shape[0],
        P=T, topk=0, warm_w=0, warm_c=0, warm_pi=0, probe_b=0, probe_g=0,
    )


def require_fit(what: str, fn, snap, *rest, mesh=None,
                spmd_shards: int = 1) -> None:
    """Raise :class:`OracleUnfit` unless the jitted ``fn(snap, *rest)``
    fits one device.  ``spmd_shards``: the node shards of a program that
    is jitted with shardings and carries no specs inside
    (hbm_audit._Liveness).  Traced once per (program, shapes)."""
    from kube_batch_tpu.analysis.hbm_audit import peak_live_bytes

    # static arguments (a config) pass through as they are
    args = jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                   if hasattr(a, "shape") else a), (snap, *rest))
    key = (fn, spmd_shards, tuple(
        getattr(a, "shape", a) for a in jax.tree.leaves(args)))
    need = _needs.get(key)
    if need is None:
        if mesh is not None:
            with mesh:
                closed = fn.trace(*args).jaxpr
        else:
            closed = fn.trace(*args).jaxpr
        need = _needs[key] = peak_live_bytes(
            closed, sp=_point_of(snap), spmd_shards=spmd_shards)
    budget = device_budget_bytes()
    if need > budget:
        raise OracleUnfit(
            f"{what} needs {need / 2**30:.2f} GiB on one device "
            f"(liveness audit) and the device holds {budget / 2**30:.2f}")

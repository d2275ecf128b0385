"""Cycle invariant sentinel — the device-side result-integrity check fused
into every dispatching solve program (guard plane tier 1).

Five stacked fast paths (delta snapshot open, resident device columns,
shard_map collectives, KB_TOPK compaction, pipelined writeback) each ship
with a bit-exact oracle knob, yet nothing in production ever exercised
those oracles: a silent divergence — an XLA/driver regression, an HBM
bit-flip in a resident column, a future PR's bug in the delta scatters —
would dispatch wrong binds and evictions to a real cluster with zero
detection.  This module closes the gap at the solve layer: each committed
solve program gains a FUSED tail that re-derives the lawfulness of its own
result from the same snapshot it consumed —

- per-node committed allocation fits the cycle-start budget AND the node's
  capacity (the capacity cross-check is what catches a corrupted resident
  idle column: the solve's own fit math trusts the corrupt budget, but
  idle+used ≤ allocatable is redundant state the corruption breaks);
- no task is assigned that was not an eligible pending row (a task already
  RUNNING being re-assigned = "assigned twice");
- every committed assignment was cycle-start feasible (static predicates
  re-checked row-wise at the assigned node — O(T·W), not [T, N]);
- committed gangs meet min_available (the vectorized JobReady gate,
  re-derived);
- victims are valid RUNNING residents, stay within gang slack, and cover
  their claimant (eviction solves);
- an all-finite sweep over the result ledgers and every f32 snapshot
  input (ledgers, budgets, fairness state).

The check returns ONE verdict word (i32, 0 = lawful) plus a violation
histogram ([N_INVARIANTS] i32) that ride the action's existing single
annotated ``device_get`` — the AllocateResult-counters idiom — so the
steady-state cost is a handful of O(T)/O(N) reductions fused into a
program already streaming [T, N] intermediates (bench ``guard_overhead``
holds the delta under 5% of steady-cycle p50).  On a nonzero verdict the
action discards the result and FAILS CLOSED: no binds or evictions are
dispatched from a condemned solve (kube_batch_tpu/guard owns the demotion
/ audit / bundle response).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from kube_batch_tpu.api.snapshot import DeviceSnapshot
from kube_batch_tpu.ops.assignment import (
    AllocateConfig,
    AllocateResult,
    allocate_solve,
    allocate_topk_solve,
)
from kube_batch_tpu.ops.eviction import (
    EvictConfig,
    EvictResult,
    evict_solve,
    gang_slack0,
    victim_running,
)
from kube_batch_tpu.utils import jitstats

#: violation classes — one histogram slot each, shared by every solve's
#: sentinel so the guard plane and the diagnostics bundle speak one schema
INVARIANT_NAMES = (
    "assign_ineligible",   # placement/claim on a non-eligible-pending row
    "assign_infeasible",   # static predicates fail at the assigned node,
    #                        or an index out of range
    "node_overcommit",     # committed allocation exceeds budget/capacity,
    #                        or the cycle-start ledgers are inconsistent
    "gang_violation",      # committed gang below min_available / slack
    "victim_ineligible",   # evicted row is not a valid RUNNING resident
    "claim_uncovered",     # a claim's victims do not cover the claimant
    "nonfinite",           # non-finite ledger/score/budget value
    "admit_ineligible",    # enqueue-gate admission of a non-candidate
)
N_INVARIANTS = len(INVARIANT_NAMES)

_I = {name: i for i, name in enumerate(INVARIANT_NAMES)}


def _i32sum(x) -> jnp.ndarray:
    # dtype pinned: the counts ride the action readback and must stay i32
    # under the jaxpr audit's x64 probe
    return jnp.sum(x, dtype=jnp.int32)


def _nonfinite_count(*arrays) -> jnp.ndarray:
    total = jnp.int32(0)
    for a in arrays:
        # kbt: allow[KBT005] trace-time unroll over a fixed small tuple of
        # snapshot fields inside the fused sentinel program — reductions
        # fuse into one graph, zero per-iteration host dispatch
        total = total + _i32sum(~jnp.isfinite(a))
    return total


def _snapshot_nonfinite(snap: DeviceSnapshot) -> jnp.ndarray:
    """All-finite sweep over every f32 input the solves consume: ledgers,
    requests, fairness state, budgets, quanta."""
    return _nonfinite_count(
        snap.task_req, snap.task_resreq,
        snap.node_idle, snap.node_releasing, snap.node_used, snap.node_alloc,
        snap.job_allocated,
        snap.queue_weight, snap.queue_capability, snap.queue_alloc,
        snap.queue_request,
        snap.total, snap.quanta,
    )


def _eligible_pending(snap: DeviceSnapshot) -> jnp.ndarray:
    """[T] bool — exactly the solves' claimant/bidder eligibility."""
    tj = snap.task_job
    return (
        snap.task_pending
        & snap.task_valid
        & snap.job_valid[tj]
        & snap.job_schedulable[tj]
    )


#: second multiplier of the victim-checksum mix (wrapped i32 two's
#: complement — the tie-hash constants' idiom)
_CK = 0x9E3779B1 - (1 << 32)


def eligibility_checksum(snap: DeviceSnapshot) -> jnp.ndarray:
    """i32 checksum of the device's bidder-eligibility + victim-pool
    vectors — the sentinel's device-vs-host divergence probe.  A flipped
    resident status/pending/node word changes WHICH rows are eligible,
    which the purely device-side invariants cannot see (they re-derive
    from the same corrupted columns); the host recomputes this checksum
    from its own columns (:func:`host_eligibility_checksum` — the same
    formula over the same-shaped arrays) and a mismatch condemns the
    solve even when the phantom row never wins a bid (the proportion gate
    often blocks it — defense that HIDES the corruption)."""
    T = snap.task_req.shape[0]
    idx = jnp.arange(T, dtype=jnp.int32) + 1
    elig = jnp.sum(
        jnp.where(_eligible_pending(snap), idx, 0), dtype=jnp.int32
    )
    run = jnp.sum(
        jnp.where(victim_running(snap), idx * jnp.int32(_CK), 0),
        dtype=jnp.int32,
    )
    return elig ^ run


def host_eligibility_checksum(snap) -> int:
    """The host twin of :func:`eligibility_checksum`, over the HOST-backed
    snapshot columns — wrapped mod-2^32 arithmetic matches the device's
    i32 two's complement exactly."""
    import numpy as np

    from kube_batch_tpu.api.types import TaskStatus

    # kbt: allow[KBT005] the host twin reads the HOST-backed snapshot the
    # actions keep for numpy access — these asarray calls copy nothing and
    # never touch the device (the device side is eligibility_checksum,
    # fused into the solve program)
    tj, valid, pending, status, node, jvalid, jsched = [
        np.asarray(a) for a in (  # kbt: allow[KBT005] host-backed reads ^
            snap.task_job, snap.task_valid, snap.task_pending,
            snap.task_status, snap.task_node, snap.job_valid,
            snap.job_schedulable,
        )
    ]
    elig_mask = pending & valid & jvalid[tj] & jsched[tj]
    run_mask = (
        valid & (status == int(TaskStatus.RUNNING)) & (node >= 0)
        & jvalid[tj]
    )
    idx = np.arange(elig_mask.shape[0], dtype=np.int64) + 1
    elig = int(np.sum(np.where(elig_mask, idx, 0), dtype=np.int64)) & 0xFFFFFFFF
    ck = _CK & 0xFFFFFFFF
    run = int(np.sum(np.where(run_mask, (idx * ck) & 0xFFFFFFFF, 0),
                     dtype=np.int64)) & 0xFFFFFFFF
    return (elig ^ run) & 0xFFFFFFFF


def _static_feasible_at(snap: DeviceSnapshot, node_idx: jnp.ndarray,
                        active: jnp.ndarray) -> jnp.ndarray:
    """[T] bool — row-wise static-predicate re-check at ``node_idx`` (the
    assigned/claimed node per task): node health, selector bits, taint
    toleration, and the sparse inter-pod-affinity correction rows.  A
    row-wise gather, O(T·W) — never a [T, N] recompute."""
    T = snap.task_req.shape[0]
    N = snap.node_label_bits.shape[0]
    safe = jnp.clip(node_idx, 0, N - 1)
    labels = snap.node_label_bits[safe]                       # [T, W]
    taints = snap.node_taint_bits[safe]
    sel_ok = jnp.all(
        (snap.task_sel_bits & labels) == snap.task_sel_bits, axis=-1
    ) & ~snap.task_sel_impossible
    tol_ok = jnp.all((taints & ~snap.task_tol_bits) == 0, axis=-1)
    node_ok = snap.node_valid[safe] & snap.node_sched[safe]
    ok = node_ok & sel_ok & tol_ok
    # sparse affinity rows: the mask at the row's chosen node must hold
    rows = jnp.clip(snap.task_aff_idx, 0, T - 1)
    chosen = jnp.clip(node_idx[rows], 0, N - 1)
    aff_at = jnp.take_along_axis(
        snap.task_aff_mask, chosen[:, None], axis=1
    )[:, 0]
    # padding rows (-1) and rows whose node is inactive contribute True
    upd = jnp.where(
        (snap.task_aff_idx >= 0) & active[rows], aff_at, True
    )
    ok = ok.at[rows].min(upd)
    return ok | ~active


def _same_solve_exclusions(snap: DeviceSnapshot, assigned: jnp.ndarray,
                           N: int) -> jnp.ndarray:
    """[] i32 — placements of this solve that a required anti-affinity
    term forbids beside ANOTHER placement of this solve: for each placed
    sparse row and each pair (signature, topology key) its terms exclude,
    the placed rows the signature selects in its domain, itself apart.  The
    rule ``assignment.make_term_round`` keeps, order-free; what the solve's
    placements share with pods already bound is ``task_aff_mask``'s, which
    ``_static_feasible_at`` re-checks."""
    x = snap.aff_terms
    T = assigned.shape[0]
    Pp = x.anti.shape[1]
    idx = snap.task_aff_idx
    a_k = assigned[jnp.clip(idx, 0, T - 1)]
    placed_k = (idx >= 0) & (a_k >= 0)
    dom_a = x.dom[:, jnp.clip(a_k, 0, N - 1)]                  # [Pp, K]
    pair = jnp.arange(Pp, dtype=jnp.int32)[:, None]
    sel = (x.selp & placed_k[:, None]).T.astype(jnp.int32)     # [Pp, K]
    count = jnp.zeros((Pp, N), jnp.int32).at[pair, dom_a].add(sel)
    others = jnp.take_along_axis(count, dom_a, axis=1) - sel
    return _i32sum((x.anti & placed_k[:, None]).T & (others > 0))


def allocate_invariants(snap: DeviceSnapshot, res: AllocateResult,
                        config: AllocateConfig):
    """(verdict i32, hist [N_INVARIANTS] i32) for one allocate-shaped
    result.  Verdict 0 ⇔ every invariant holds."""
    T, R = snap.task_req.shape
    N = snap.node_idle.shape[0]
    J = snap.job_min_avail.shape[0]
    tj = snap.task_job
    assigned, pipelined = res.assigned, res.pipelined
    placed = assigned >= 0

    # (1) only eligible pending rows may place — a RUNNING row re-assigned
    # is the "assigned twice" class
    n_inel = _i32sum(placed & ~_eligible_pending(snap))

    # (2) bounds + cycle-start static feasibility at the assigned node
    in_range = (assigned >= -1) & (assigned < N)
    feas = _static_feasible_at(snap, assigned, placed)
    n_infeas = _i32sum(~in_range) + _i32sum(placed & ~feas)
    if snap.aff_terms is not None:
        n_infeas = n_infeas + _same_solve_exclusions(snap, assigned, N)

    # (3) per-node budget + capacity: the committed deltas must fit the
    # cycle-start budgets (what the solve promised), AND post-solve used
    # must stay under allocatable, AND the cycle-start ledgers themselves
    # must be self-consistent (idle+used ≤ allocatable; idle ≥ 0) — the
    # redundant cross-checks that catch a corrupted resident ledger word
    # the solve's own budget math would trust.  PIPELINED occupancy is the
    # sanctioned exception: a pipelined task borrows a dying victim's share
    # (node.AddTask(Pipelined): Releasing -= r, Used += r), so `used` may
    # lawfully exceed `allocatable` by exactly the pipelined resreq resident
    # on the node — both at cycle start (reclaim ran earlier this cycle) and
    # in the post-solve ledgers (this solve's own pipelined placements).
    from kube_batch_tpu.api.types import TaskStatus

    seg = jnp.where(placed, jnp.clip(assigned, 0, N - 1), N)
    alloc_delta = jax.ops.segment_sum(
        jnp.where((placed & ~pipelined)[:, None], snap.task_resreq, 0.0),
        seg, num_segments=N + 1,
    )[:N]
    pipe_delta = jax.ops.segment_sum(
        jnp.where((placed & pipelined)[:, None], snap.task_resreq, 0.0),
        seg, num_segments=N + 1,
    )[:N]
    pipe_here = (
        snap.task_valid
        & (snap.task_status == jnp.int32(int(TaskStatus.PIPELINED)))
        & (snap.task_node >= 0)
    )
    pipe_resident = jax.ops.segment_sum(
        jnp.where(pipe_here[:, None], snap.task_resreq, 0.0),
        jnp.where(pipe_here, snap.task_node, N), num_segments=N + 1,
    )[:N]
    q = snap.quanta
    cap = snap.node_alloc + pipe_resident
    over = (
        jnp.any(alloc_delta > snap.node_idle + q, axis=-1)
        | jnp.any(pipe_delta > snap.node_releasing + q, axis=-1)
        | (snap.node_valid & jnp.any(
            res.node_used > cap + pipe_delta + q, axis=-1))
        | (snap.node_valid & jnp.any(
            snap.node_idle + snap.node_used > cap + q, axis=-1))
        | (snap.node_valid & jnp.any(snap.node_idle < -q, axis=-1))
    )
    n_over = _i32sum(over)

    # (4) committed gangs meet min_available — the vectorized JobReady
    # commit gate, re-derived from the surviving placements
    if config.gang:
        new_alloc = jax.ops.segment_sum(
            (placed & ~pipelined).astype(jnp.int32), tj, num_segments=J
        )
        new_any = jax.ops.segment_sum(
            placed.astype(jnp.int32), tj, num_segments=J
        )
        n_gang = _i32sum(
            (new_any > 0)
            & ((snap.job_ready + new_alloc) < snap.job_min_avail)
        )
    else:
        n_gang = jnp.int32(0)

    # (5) all-finite sweep: result ledgers + every f32 snapshot input
    n_fin = _snapshot_nonfinite(snap) + _nonfinite_count(
        res.node_idle, res.node_releasing, res.node_used, res.deserved
    )

    zero = jnp.int32(0)
    hist = jnp.stack([
        n_inel, n_infeas, n_over, n_gang, zero, zero, n_fin, zero,
    ]).astype(jnp.int32)
    return jnp.sum(hist, dtype=jnp.int32), hist


def evict_invariants(snap: DeviceSnapshot, res: EvictResult,
                     config: EvictConfig):
    """(verdict i32, hist) for one eviction-shaped result (reclaim or
    preempt)."""
    T, R = snap.task_req.shape
    N = snap.node_alloc.shape[0]
    J = snap.job_min_avail.shape[0]
    claim_node, evicted, victim_claimant = (
        res.claim_node, res.evicted, res.victim_claimant,
    )
    claimed = claim_node >= 0

    # claimants must be eligible pending rows, statically feasible at the
    # claimed node, and in range
    n_inel = _i32sum(claimed & ~_eligible_pending(snap))
    in_range = (
        (claim_node >= -1) & (claim_node < N)
        & (victim_claimant >= -1) & (victim_claimant < T)
    )
    feas = _static_feasible_at(snap, claim_node, claimed)
    n_infeas = _i32sum(~in_range) + _i32sum(claimed & ~feas)

    # victims: valid RUNNING residents, victim↔claimant consistency
    running = victim_running(snap)
    n_victim = (
        _i32sum(evicted & ~running)
        + _i32sum(evicted != (victim_claimant >= 0))
    )

    # gang slack: a job never drops below MinAvailable (victim gate).
    # Only jobs that actually LOST victims are judged — an unready gang
    # (ready < min_available) has negative slack but zero evictions, which
    # is lawful
    if config.victim_gang:
        evict_cnt = jax.ops.segment_sum(
            evicted.astype(jnp.int32), snap.task_job, num_segments=J
        )
        n_gang = _i32sum(
            (evict_cnt > 0) & (evict_cnt > gang_slack0(snap, config))
        )
    else:
        n_gang = jnp.int32(0)

    # coverage: every claim's victims cover the claimant's request in
    # every dimension — evictions never happen without a covered placement
    vseg = jnp.where(
        evicted & (victim_claimant >= 0),
        jnp.clip(victim_claimant, 0, T - 1), T,
    )
    cover = jax.ops.segment_sum(
        jnp.where(evicted[:, None], snap.task_resreq, 0.0),
        vseg, num_segments=T + 1,
    )[:T]
    n_cover = _i32sum(
        claimed & jnp.any(snap.task_req > cover + snap.quanta, axis=-1)
    )

    n_fin = _snapshot_nonfinite(snap)
    zero = jnp.int32(0)
    hist = jnp.stack([
        n_inel, n_infeas, zero, n_gang, n_victim, n_cover, n_fin, zero,
    ]).astype(jnp.int32)
    return jnp.sum(hist, dtype=jnp.int32), hist


# --------------------------------------------------------------------------
# sentinel-fused solve programs — the dispatch-facing entry points.  Each is
# the committed solve body plus its invariant tail in ONE compiled program
# (jit-of-jit inlines the inner solve), so the sentinel shares the solve's
# dispatch and its verdict rides the action's existing single device_get.
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("config",))
def allocate_sentinel_solve(snap: DeviceSnapshot, config: AllocateConfig):
    """allocate_solve with the fused invariant tail → (result, verdict,
    hist, eligibility checksum)."""
    res = allocate_solve.__wrapped__(snap, config)
    verdict, hist = allocate_invariants(snap, res, config)
    return res, verdict, hist, eligibility_checksum(snap)


@partial(jax.jit, static_argnames=("config",))
def allocate_topk_sentinel_solve(snap: DeviceSnapshot, pend_rows,
                                 config: AllocateConfig):
    """The compacted allocate solve with the fused invariant tail.  The
    invariants run on the scattered-back [T] result, so a compaction bug
    that mis-scatters the bucket is in scope, not just the rounds."""
    res = allocate_topk_solve.__wrapped__(snap, pend_rows, config)
    verdict, hist = allocate_invariants(snap, res, config)
    return res, verdict, hist, eligibility_checksum(snap)


def _warm_sentinel_body(snap, pend_rows, t_idx, t_skey, t_hash, t_trunc,
                        row_map, changed_nodes, rerank_rows, rerank_slots,
                        config: AllocateConfig, k_min: int):
    """The warm-started compacted solve (ops.assignment._warm_allocate_solve)
    plus the fused invariant tail: the invariants run on the scattered-back
    [T] result, so a table-carry bug that merges a stale key into a wrong
    placement is in scope exactly like a compaction mis-scatter."""
    from kube_batch_tpu.ops.assignment import _warm_allocate_solve

    res, table, eroded = _warm_allocate_solve(
        snap, pend_rows, t_idx, t_skey, t_hash, t_trunc,
        row_map, changed_nodes, rerank_rows, rerank_slots, config, k_min,
    )
    verdict, hist = allocate_invariants(snap, res, config)
    return res, verdict, hist, eligibility_checksum(snap), table, eroded


_WARM_SENTINEL = None


def warm_sentinel_solve_fn():
    """Jitted sentinel-fused warm solve — module-level memo with the same
    backend-dependent table donation as ops.assignment.warm_solve_fn."""
    global _WARM_SENTINEL
    if _WARM_SENTINEL is None:
        from kube_batch_tpu.ops.assignment import WARM_TABLE_ARGNUMS

        donate = (
            () if jax.default_backend() == "cpu" else WARM_TABLE_ARGNUMS
        )
        _WARM_SENTINEL = jitstats.register(
            "warm_allocate_sentinel_solve",
            jax.jit(_warm_sentinel_body,
                    static_argnames=("config", "k_min"),
                    donate_argnums=donate),
        )
    return _WARM_SENTINEL


@partial(jax.jit, static_argnames=("config",))
def evict_sentinel_solve(snap: DeviceSnapshot, config: EvictConfig,
                         pend_rows=None):
    """evict_solve (reclaim/preempt; on the pending bucket where
    ``pend_rows`` is given) with the fused invariant tail: the invariants
    run on the [T] result the bucket's claims were scattered to, so a
    mis-scatter is in scope, not just the rounds."""
    res = evict_solve.__wrapped__(snap, config, pend_rows)
    verdict, hist = evict_invariants(snap, res, config)
    return res, verdict, hist, eligibility_checksum(snap)


def enqueue_gate_invariants(admitted, cand, min_res, idle0, quanta):
    """(verdict, hist) for the enqueue admission scan: an admitted row must
    have been a candidate, and the budget inputs must be finite."""
    n_admit = _i32sum(admitted & ~cand)
    n_fin = _nonfinite_count(min_res, idle0, quanta)
    zero = jnp.int32(0)
    hist = jnp.stack([
        zero, zero, zero, zero, zero, zero, n_fin, n_admit,
    ]).astype(jnp.int32)
    return jnp.sum(hist, dtype=jnp.int32), hist


_GATE_SENTINEL = None


def enqueue_gate_sentinel_fn():
    """Jitted admission scan + fused invariant tail (module-level memo,
    mirroring ops.admission.enqueue_gate_fn)."""
    global _GATE_SENTINEL
    if _GATE_SENTINEL is None:
        from kube_batch_tpu.ops.admission import gate_scan

        def fused(min_res, cand, idle0, quanta):
            admitted = gate_scan(min_res, cand, idle0, quanta)
            verdict, hist = enqueue_gate_invariants(
                admitted, cand, min_res, idle0, quanta
            )
            return admitted, verdict, hist

        _GATE_SENTINEL = jitstats.register(
            "enqueue_gate_sentinel", jax.jit(fused)
        )
    return _GATE_SENTINEL


def enqueue_gate_sentinel_solve(min_res, cand, idle0, quanta):
    return enqueue_gate_sentinel_fn()(min_res, cand, idle0, quanta)


# retrace accounting: steady-state cycles must hit the jit cache (the bench
# asserts the counters stay flat with the guard on)
jitstats.register("allocate_sentinel_solve", allocate_sentinel_solve)
jitstats.register("allocate_topk_sentinel_solve", allocate_topk_sentinel_solve)
jitstats.register("evict_sentinel_solve", evict_sentinel_solve)

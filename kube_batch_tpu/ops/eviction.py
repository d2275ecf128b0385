"""Device-resident eviction solve — reclaim + preempt as compiled auctions.

The reference's reclaim (actions/reclaim/reclaim.go:107-199) and preempt
phase 1 (actions/preempt/preempt.go:110-137,180-260) are host loops:
per pending "claimant" task, scan every node, collect Running victims passing
the tier-intersected Evictable verdicts (conformance ∩ gang ∩ drf/proportion,
session_plugins.go:100-182), evict until the claimant's request is covered,
then pipeline the claimant onto the freed (Releasing) resources.

Here both run as bidding rounds on device, sharing one kernel:

  round:  eligible claimants bid for their best feasible node, where
          "feasible" means the node carries enough evictable victim resource
          for the claimant's queue (cross-queue victims for reclaim,
          same-queue/other-job for preempt). One claimant — the lowest
          virtual-rank bidder — wins each node per round (evictions are far
          sparser than allocations, so per-round node exclusivity costs
          little wall-clock and keeps victim accounting exact).
  pick:   per node, victims are taken in reverse task order (the reference's
          victimsQueue pops !TaskOrderFn, preempt.go:219-224) until the
          winner's InitResreq is covered — a segmented prefix scan.
  caps:   global constraints are then enforced exactly: gang slack (a job
          never drops below MinAvailable, gang.go:71-94), proportion queue
          budget (a victim queue never drops below deserved,
          proportion.go:171-196), and DRF share dominance for preempt
          (drf.go:85-110). Victims dropped by a cap can break a claim's
          coverage; such claims cancel entirely — evictions never happen
          without a covered placement (reclaim.go:150-163 validates victim
          sufficiency before evicting).

The host action replays the result through session verbs, re-validating each
claim with the real plugin callbacks on the (small) selected sets — the
device narrows O(tasks × nodes × victims) to O(claims), the host stays
authoritative for semantics.

Memory footprint: every [claimant, node] plane (static predicates, score,
tie hash, the gates' probe, the per-round capacity matmuls and the masked
argmax) is built over the claimant axis the caller hands in.  The action
(actions/reclaim.py) hands in the pending bucket ``pend_rows`` [P] whenever
the pending set fits the one bucket the task axis' shape gives
(actions/allocate.py ``topk_bucket_for``): [P, N] planes, 168 MB each at
P=8,192 x N=5,120 against 1.03 GB at T=50,176.  Victims only ever need
task-axis vectors.  With ``pend_rows=None`` (a pending set past the bucket,
an axis too small to have one, the sharded bodies) the claimant axis is the
whole task axis and the planes are [T, N], as before.  The tier-C HBM audit
(analysis/hbm_audit.py) traces both: a [P, N] plane is still a task-axis x
node-axis temporary (KBT202, as allocate's table build is, ROADMAP 1.(2)),
and both blow the v5e budget at the 1M x 100k north star (KBT201); the
waivers in ``HBM_ALLOWLIST`` say which program each covers, and the sparse
rebuild (candidate table over per-(queue, node) capacity keys, with
re-rank-on-growth since evictions grow capacity within a pass) deletes them.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kube_batch_tpu.api.snapshot import DeviceSnapshot
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.ops import fairness, ordering
from kube_batch_tpu.ops.assignment import (
    _best_node,
    _tie_break_hash,
    pend_view,
    tie_break_hash_rows,
)
from kube_batch_tpu.ops.feasibility import static_predicates
from kube_batch_tpu.ops.ordering import segmented_prefix
from kube_batch_tpu.ops.scoring import ScoreWeights, score_matrix

NEG = jnp.float32(-3.0e38)
BIG = jnp.int32(1 << 30)
SHARE_DELTA = 1e-6  # drf.go:23 shareDelta
# the gates count at most this many tasks a node (every task takes a pod
# slot, so a node's pod capacity binds long before; keeps the sum in i32)
NODE_SLOTS = 1 << 10


class EvictConfig(NamedTuple):
    """Static eviction-solve configuration (jit cache key).

    Victim gates mirror the reference's TIERED Evictable dispatch
    (session_plugins.go:100-182): only plugins in the first tier containing
    any voting plugin constrain victims — under the default two-tier conf
    (gang+conformance in tier 1, drf/proportion in tier 2) the drf/proportion
    victim vetoes never bind. Ordering flags are independent: they shape the
    claimant rank / overused gate / commit gate like the allocate solve."""

    mode: str = "reclaim"     # "reclaim" (cross-queue) | "preempt" (same-queue)
    rounds: int = 8
    # reclaim-only: skip as many claimants as fit free Idle (allocate places
    # them later this cycle) — set by the action layer ONLY when allocate is
    # actually configured after reclaim and host predicates are exact
    idle_gate: bool = False
    # both modes: skip as many claimants as fit the nodes' Idle plus the
    # capacity their RELEASING victims have promised (an eviction in flight
    # is not ordered again) — set by the action layer only when allocate is
    # in the pipeline to place them and host predicates are exact
    releasing_gate: bool = False
    # ordering / gating (claimant side)
    gang: bool = True
    drf: bool = True
    proportion: bool = True
    # victim gates (first voting tier only)
    victim_gang: bool = True
    victim_conformance: bool = True
    victim_proportion: bool = False
    victim_drf: bool = False
    weights: ScoreWeights = ScoreWeights()


class EvictResult(NamedTuple):
    claim_node: jnp.ndarray       # [T] i32 — node the claimant pipelines onto, -1
    evicted: jnp.ndarray          # [T] bool — task chosen as victim
    victim_claimant: jnp.ndarray  # [T] i32 — claimant task index a victim serves, -1
    rounds_run: jnp.ndarray       # [] i32 — bidding rounds the solve ran
    # [] i32 — claimants the gates left to allocate beyond what Idle alone
    # holds: the releasing gate's (volcano_evict_claims_total)
    gated_releasing: jnp.ndarray


# ---- the victim machinery shared by every eviction path ------------------
# (the committed solves below AND the query plane's hypothetical probe,
# ops/probe.py _evict_probe — one set of lines, so the probe cannot drift
# from the solve; tests/test_whatif.py's fixture equivalence is the
# behavioral check, this sharing is the structural one)


def victim_running(snap: DeviceSnapshot) -> jnp.ndarray:
    """[T] bool — base victim eligibility: valid RUNNING tasks on a node
    whose job is in-session.  job_valid gates victims too: the columnar
    snapshot's row space carries tasks of jobs OUTSIDE the session (dropped
    at open / unknown queue), which the per-session object snapshot never
    contained — their rows' job metadata is stale scratch and the host
    decode would drop them anyway, wasting the whole claim."""
    return (
        snap.task_valid
        & (snap.task_status == int(TaskStatus.RUNNING))
        & (snap.task_node >= 0)
        & snap.job_valid[snap.task_job]
    )


def gang_slack0(snap: DeviceSnapshot, config: EvictConfig) -> jnp.ndarray:
    """[J] i32 — evictions a job can absorb while staying ≥ MinAvailable;
    MinAvailable ≤ 1 jobs are not gangs — always evictable (gang.go:71-94).
    BIG everywhere when the gang victim gate is off."""
    J = snap.job_min_avail.shape[0]
    if not config.victim_gang:
        return jnp.full(J, BIG)
    return jnp.where(
        snap.job_min_avail > 1, snap.job_ready - snap.job_min_avail, BIG
    )


def claim_winners(has, best, rank, n_nodes: int):
    """One winner per node — the lowest-rank bidder.  The claimant axis C
    is whatever the caller bids with: the full task axis (the solves) or a
    speculative gang's members (the probe).  Returns
    (is_winner [C] bool, winner_idx [N] i32 — claimant index or -1,
    node_has_claim [N] bool)."""
    N = n_nodes
    idx = jnp.arange(has.shape[0], dtype=jnp.int32)
    bid_node = jnp.where(has, best, N)
    win_rank = (
        jnp.full(N + 1, BIG, jnp.int32)
        .at[bid_node].min(jnp.where(has, rank, BIG))
    )[:N]
    is_winner = has & (rank == win_rank[jnp.clip(best, 0, N - 1)])
    winner_idx = (
        jnp.full(N, -1, jnp.int32)
        .at[jnp.where(is_winner, best, 0)]
        .max(jnp.where(is_winner, idx, -1))
    )
    return is_winner, winner_idx, winner_idx >= 0


def pick_victims(snap: DeviceSnapshot, vmask, node_req, node_has_claim,
                 victim_rank, slack_rem, config: EvictConfig, n_nodes: int,
                 *, qbudget_rem=None, task_queue=None):
    """Victim selection for one round's claimed nodes: victims pop in
    reverse task order until the winner's request is covered (the
    reference's victimsQueue pops !TaskOrderFn, preempt.go:219-224 — a
    segmented prefix scan), then the exact global caps — gang slack (a job
    never drops below MinAvailable, gang.go:71-94) and, when
    ``qbudget_rem``/``task_queue`` are given, the proportion queue budget
    (proportion.go:171-196) — then the coverage recheck: victims dropped by
    a cap can break a claim's coverage, and such claims cancel entirely
    (evictions never happen without a covered placement,
    reclaim.go:150-163).  Returns (final_take [T] bool, covered [N] bool)."""
    T = snap.task_req.shape[0]
    N = n_nodes
    J = snap.job_min_avail.shape[0]
    Q = snap.queue_weight.shape[0]
    vn = jnp.clip(snap.task_node, 0, N - 1)

    # named for trace_reduce.py's programs: the selection scan, then the caps
    with jax.named_scope("evict_pick"):
        seg = jnp.where(vmask, snap.task_node, N)
        order = ordering.sort_by_segment_then_rank(seg, victim_rank, N + 1)
        seg_s = seg[order]
        req_s = jnp.where(vmask[order, None], snap.task_resreq[order], 0.0)
        is_start = jnp.concatenate(
            [jnp.array([True]), seg_s[1:] != seg_s[:-1]])
        prefix = segmented_prefix(req_s, is_start)               # exclusive
        need_s = node_req[jnp.clip(seg_s, 0, N - 1)]
        covered_before = jnp.all(prefix >= need_s - snap.quanta, axis=-1)
        take_s = vmask[order] & (seg_s < N) & ~covered_before
        take = jnp.zeros(T, bool).at[order].set(take_s)

    with jax.named_scope("evict_cap"):
        if config.victim_gang:
            # position among taken victims of the same job < remaining slack
            jorder = ordering.sort_by_segment_then_rank(
                jnp.where(take, snap.task_job, J), victim_rank, J + 1
            )
            js = jnp.where(take, snap.task_job, J)[jorder]
            j_start = jnp.concatenate([jnp.array([True]), js[1:] != js[:-1]])
            pos = segmented_prefix(
                take[jorder].astype(jnp.float32)[:, None], j_start
            )[:, 0].astype(jnp.int32)
            keep_j = take[jorder] & (pos < slack_rem[jnp.clip(js, 0, J - 1)])
            take = jnp.zeros(T, bool).at[jorder].set(keep_j)
        if qbudget_rem is not None:
            # cumulative eviction per victim queue ≤ remaining budget
            qorder = ordering.sort_by_segment_then_rank(
                jnp.where(take, task_queue, Q), victim_rank, Q + 1
            )
            qs = jnp.where(take, task_queue, Q)[qorder]
            q_start = jnp.concatenate([jnp.array([True]), qs[1:] != qs[:-1]])
            qreq_s = jnp.where(take[qorder, None], snap.task_resreq[qorder], 0.0)
            qprefix = segmented_prefix(qreq_s, q_start)
            fits_budget = jnp.all(
                qprefix + qreq_s
                <= qbudget_rem[jnp.clip(qs, 0, Q - 1)] + snap.quanta,
                axis=-1,
            )
            take = jnp.zeros(T, bool).at[qorder].set(take[qorder] & fits_budget)

        # coverage recheck after caps; cancel uncovered claims
        got = jax.ops.segment_sum(
            jnp.where(take[:, None], snap.task_resreq, 0.0),
            jnp.where(take, snap.task_node, N),
            num_segments=N + 1,
        )[:N]
        covered = node_has_claim & jnp.all(got >= node_req - snap.quanta, axis=-1)
        return take & covered[vn], covered


def local_evict_bids(snap: DeviceSnapshot, config: EvictConfig,
                     pend_rows=None, view=None):
    """Build the single-program bids head: ``bids(victim_ok, claimant_ok)
    -> (best, has)`` — the per-round [C, N]-scale victim-capacity /
    feasibility / masked-argmax block, computed from the full matrices in
    one logical program.  The claimant axis C is the task axis, or the
    pending bucket where ``pend_rows`` ([P] i32 global task rows, -1
    padding; ``view`` its :func:`~ops.assignment.pend_view`) is given:
    ``victim_ok`` stays [T] either way, ``claimant_ok`` / ``best`` /
    ``has`` are [C], and a bucket row's tie hash is that of its GLOBAL
    task index, so every tie falls as on the full axis.  The shard_map path
    substitutes the explicit-collective block head
    (parallel/shard_solve.py); the rest of the solve is the SHARED
    :func:`evict_rounds` machinery."""
    R = snap.task_req.shape[1]
    N = snap.node_alloc.shape[0]
    Q = snap.queue_weight.shape[0]
    preempt = config.mode == "preempt"
    task_queue = snap.job_queue[snap.task_job]                      # [T]
    if pend_rows is None:
        view, claimant_queue = snap, task_queue
    else:
        claimant_queue = view.job_queue[view.task_job]              # [P]
    static_ok = static_predicates(view)
    score = score_matrix(view, config.weights)
    if pend_rows is None:
        tie_hash = _tie_break_hash(snap.task_req.shape[0], N)
    else:
        tie_hash = tie_break_hash_rows(
            jnp.maximum(pend_rows, 0), jnp.arange(N, dtype=jnp.int32))

    def bids(victim_ok, claimant_ok):
        # ---- per-(queue, node) evictable capacity --------------------
        vreq = jnp.where(victim_ok[:, None], snap.task_resreq, 0.0)
        vnode = jnp.where(victim_ok, snap.task_node, N)
        tot_v = jax.ops.segment_sum(vreq, vnode, num_segments=N + 1)[:N]  # [N, R]
        per_qn = jnp.zeros((Q, N, R), jnp.float32).at[
            task_queue, jnp.clip(snap.task_node, 0, N - 1)
        ].add(vreq)
        if preempt:
            cap = per_qn                      # same-queue victims (own job
            #                                   over-counted; corrected in
            #                                   the shared victim selection)
        else:
            cap = tot_v[None] - per_qn        # cross-queue victims

        # ---- bids ----------------------------------------------------
        # feasible[t, n] iff claimant t's InitResreq fits cap[queue_t, n].
        # Each claimant's queue-specific capacity row is gathered with a
        # one-hot matmul over the queue axis ([C,Q]@[Q,N] on the MXU, one
        # per resource dim): compile cost and kernel count stay flat as the
        # queue bucket grows, unlike the unrolled per-queue fits pass this
        # replaces (Q=128 would mean 128 full [C,N] passes). The one-hot
        # contraction selects exactly one row, so it is exact, not a sum.
        onehot_q = (claimant_queue[:, None] == jnp.arange(Q)[None, :]).astype(
            jnp.float32
        )                                                            # [C, Q]
        # a queue index outside [0, Q) gathers an all-zero capacity row from
        # the one-hot contraction; a near-zero request could still pass the
        # epsilon compare against it — make such tasks categorically
        # infeasible rather than relying on claimant_ok to exclude them
        feas = static_ok & claimant_ok[:, None]
        feas &= ((claimant_queue >= 0) & (claimant_queue < Q))[:, None]
        for r in range(R):  # R is the small static resource dim
            # HIGHEST precision: TPU default matmul truncates the f32
            # capacity operand to bf16 (~2^-8 relative), which at byte-unit
            # memory magnitudes (~1e11) dwarfs the 10 MiB quantum the
            # epsilon compare below relies on — exact f32 keeps the one-hot
            # contraction a true row selection
            # kbt: allow[KBT005] trace-time unroll over the small static
            # resource dim R inside jit — R fused matmuls in the compiled
            # graph, zero per-iteration host dispatch
            cap_tr = jnp.matmul(
                onehot_q, cap[:, :, r], precision=jax.lax.Precision.HIGHEST
            )                                                        # [C, N]
            feas &= view.task_req[:, r, None] <= cap_tr + snap.quanta[r]
        masked = jnp.where(feas, score, NEG)
        # tie-hash spread: without it every equal-score claimant bids the
        # same argmax node and only one claim lands per round
        return _best_node(masked, tie_hash)

    return bids


def gate_room_local(task_req, static_ok, snap: DeviceSnapshot,
                    config: EvictConfig):
    """[T', 2] i32 — how many tasks like this one ``snap``'s node block holds
    without a further eviction (column 1), and how many of those its Idle
    alone holds where the idle gate is on (column 0; what the releasing
    gate adds is the difference): the claimant gates' [T, N] probe, shared
    by the single program and the shard_map body (which psums it over the
    node shards).  A node counts by its cycle-start Idle (the idle gate,
    reclaim only), and a node with RELEASING capacity by its Idle plus that
    capacity (the releasing gate, both modes): the reference's
    ``FutureIdle`` (node_info.go: idle + releasing - pipelined; the
    snapshot's ``node_releasing`` is already net of what is pipelined
    there, api/node_info.py).  With the releasing gate alone only the
    nodes that have something releasing count.  One pass whichever gates
    are on."""
    idle_gate = config.idle_gate and config.mode != "preempt"
    promised = jnp.any(snap.node_releasing > 0.0, axis=-1)           # [N]

    def held(budget, nodes=None):
        # [T] how many such tasks the nodes' budgets hold (a node that
        # holds one passes ``fits``; ``nodes`` [N] bool: those that count);
        # dimensions the task does not ask for do not bind
        need = task_req[:, None, :]                                  # [T, 1, R]
        per_dim = jnp.where(
            need > 0.0,
            jnp.floor((budget[None] + snap.quanta) / jnp.maximum(need, 1e-9)),
            jnp.float32(NODE_SLOTS),
        )
        slots = jnp.clip(
            jnp.min(per_dim, axis=-1), 0, NODE_SLOTS).astype(jnp.int32)
        ok = static_ok if nodes is None else static_ok & nodes[None, :]
        return jnp.sum(jnp.where(ok, slots, 0), axis=1)

    def count():
        by_idle = (held(snap.node_idle) if idle_gate
                   else jnp.zeros(task_req.shape[0], jnp.int32))
        if not config.releasing_gate:
            return jnp.stack([by_idle, by_idle], axis=1)
        budget = snap.node_idle + jnp.where(
            promised[:, None], snap.node_releasing, 0.0)
        # with the releasing gate alone only the promised nodes count
        return jnp.stack(
            [by_idle, held(budget, None if idle_gate else promised)], axis=1)

    if idle_gate:
        return count()
    # the releasing gate alone (preempt): nothing releasing, nothing to count
    return jax.lax.cond(
        jnp.any(promised), count,
        lambda: jnp.zeros((task_req.shape[0], 2), jnp.int32))


def gates_on(config: EvictConfig) -> bool:
    return (config.idle_gate and config.mode != "preempt") \
        or config.releasing_gate


def _claimant_axis(pend_rows, T: int):
    """(to_claimants, to_tasks): between the task axis and the claimant
    axis, which is the task axis itself (``pend_rows`` None) or the pending
    bucket."""
    if pend_rows is None:
        def to_claimants(x):
            return x

        def to_tasks(x, fill):
            del fill
            return x
    else:
        live = pend_rows >= 0
        safe = jnp.clip(pend_rows, 0, T - 1)
        # padding slots land in the dropped T slot of a [T+1] buffer
        # (negative indices must never reach a scatter)
        scat = jnp.where(live, pend_rows, T)

        def to_claimants(x):
            """[T] -> [P]: the bucket's rows of a task-axis vector."""
            g = x[safe]
            return g & live if g.dtype == jnp.bool_ else g

        def to_tasks(x, fill):
            """[P, ...] -> [T, ...]: ``fill`` where the bucket has no row."""
            buf = jnp.full((T + 1,) + x.shape[1:], fill, x.dtype)
            return buf.at[scat].set(x)[:T]

    return to_claimants, to_tasks


def any_claimant(claimant_base) -> jnp.ndarray:
    """[] bool — somebody is left to bid once the gates have counted: the
    predicate of :func:`evict_rounds`' one branch."""
    return jnp.any(claimant_base)


def evict_rounds(
    snap: DeviceSnapshot,
    config: EvictConfig,
    make_bids,
    gate_room=None,
    n_nodes=None,
    claimant_mask=None,
    pend_rows=None,
) -> EvictResult:
    """The eviction machinery shared by every solve path: victim/claimant
    eligibility, ranks, winner-per-node selection, victim picking, global
    caps, coverage, and the commit gate — everything that reads only the
    task/job/queue-axis vectors (replicated under shard_map).  The [C, N]-
    scale bids come from the ``bids(victim_ok, claimant_ok)`` that
    ``make_bids()`` builds; ``gate_room`` ([C, 2]) is the
    claimant gates' probe (:func:`gate_room_local` summed over the node
    shards; required iff :func:`gates_on`).

    A solve whose gates leave no claimant ENDS AT THE GATES: everything
    below them (the bids head, the victims' and the claimants' ranks, the
    fairness setup, the rounds, the commit gate) is one branch of a
    ``lax.cond`` on :func:`any_claimant`, and the other returns the empty
    result the rounds would have returned, with ``rounds_run`` 0: no round
    ran.  The predicate is the program's own gate, computed on the device
    from its input, so the result is the same by construction; the builder
    is called inside the branch so that its planes are not built above it.

    The claimant axis C is the
    task axis, or the pending bucket where ``pend_rows`` ([P] i32 global
    task rows in ascending order, -1 padding, covering EVERY pending row)
    is given: eligibility is computed on [T] and gathered to the bucket,
    the claimants' virtual rank, the bid and the winner selection run on
    the bucket, a winner's bucket slot is mapped back to its task row
    before it indexes anything, and the probe and the claims are scattered
    to [T] — so the result, and all the victim machinery, is on the task
    axis whichever axis the bids ran on, and is the same result (a row
    that is not pending can never bid).
    ``n_nodes`` overrides the GLOBAL node count when ``snap``'s node arrays
    are shard-local blocks (the shard_map body).  ``claimant_mask`` ([T]
    bool) restricts claimants beyond the standard eligibility — callers
    probing a SUBSET of the pending work (a single job's what-if, a drained
    queue) share this machinery instead of forking it."""
    T = snap.task_req.shape[0]
    N = n_nodes if n_nodes is not None else snap.node_alloc.shape[0]
    _, to_tasks = _claimant_axis(pend_rows, T)
    subrank = None  # the gates' ranking reads it before anything bids
    claimant_base = (
        snap.task_pending
        & snap.task_valid
        & snap.job_valid[snap.task_job]
        & snap.job_schedulable[snap.task_job]
    )
    if claimant_mask is not None:
        claimant_base &= claimant_mask
    if gates_on(config):
        # IMPROVEMENT over reclaim.go (which never looks at Idle and will
        # evict cross-queue victims for a task free capacity could satisfy):
        # a claimant that fits some schedulable node's cycle-start Idle is
        # left to the allocate action — eviction is for capacity that must
        # be TAKEN, not capacity that's already free (the idle gate; the
        # action layer enables it only when allocate really runs after
        # reclaim.  Preempt runs after allocate, so its claimants already
        # failed idle placement this cycle).  The releasing gate, in both
        # modes: an eviction in flight is not ordered twice (PARITY "known
        # divergences").  A node's RELEASING victims have promised their
        # capacity; a claimant that fits that node's Idle plus the promise
        # already has its room on the way — the allocate that follows
        # pipelines it, or binds it once the victims' DELETE has drained.
        # reclaim.go / preempt.go look at neither and evict again every
        # cycle until the kubelet is done.
        #
        # Both gates COUNT: the room that needs no further eviction holds
        # ``gate_room[t]`` tasks like t, so of the claimants with room
        # anywhere only the first ``gate_room[t]``, the least roomy first,
        # are left to allocate.  An existential gate ("fits somewhere")
        # lets two free slots keep a hundred claimants away from the
        # victims they need, for ever where they are gangs that allocate
        # cannot complete on two slots.  Claimants with host-only
        # constraints are exempt (their device fit is approximate —
        # allocate's host re-check might reject the node and strand them).
        # the bucket's probe on the task axis: a row outside the bucket is
        # not pending, so it was no candidate with any room either
        gate_room = to_tasks(gate_room, 0)
        room = gate_room[:, 1]
        cand = claimant_base & (room > 0) & ~snap.task_needs_host
        subrank = ordering.task_subranks(snap.task_prio, snap.task_creation)
        # most constrained first: a node with room for a large claimant
        # has room for a smaller one, so the candidates with the least room
        # are counted against it before those that could go elsewhere
        ahead = ordering.multisort_ranks(
            [jnp.where(cand, room, BIG), subrank])
        gated = cand & (ahead < room)
        claimant_base &= ~gated
        gated_releasing = jnp.sum(
            gated & (ahead >= gate_room[:, 0])).astype(jnp.int32)
    else:
        gated_releasing = jnp.int32(0)

    # the gates are the program's own: where they leave no claimant (and
    # where nothing was pending to begin with) the result is known here,
    # and it is the rounds' initial state, less the round that finds that
    # out: no round ran
    claim_node, evicted, victim_claimant, rounds_run = jax.lax.cond(
        any_claimant(claimant_base),
        lambda: _bid_rounds(snap, config, make_bids, claimant_base, subrank,
                            N, pend_rows),
        lambda: (jnp.full(T, -1, jnp.int32), jnp.zeros(T, bool),
                 jnp.full(T, -1, jnp.int32), jnp.int32(0)),
    )
    return EvictResult(
        claim_node=claim_node, evicted=evicted,
        victim_claimant=victim_claimant, rounds_run=rounds_run,
        gated_releasing=gated_releasing,
    )


def _bid_rounds(snap: DeviceSnapshot, config: EvictConfig, make_bids,
                claimant_base, subrank, N: int, pend_rows):
    """The taken branch of :func:`evict_rounds`: the bidding rounds and the
    commit gate, with everything only they read (the bids head, the
    victims' and the claimants' ranks, the fairness setup) built HERE, so
    that none of it is computed for a solve that ends at its gates.
    ``subrank`` is the task axis' subrank where the gates ranked with it,
    else None.  Returns (claim_node, evicted, victim_claimant, rounds_run)."""
    T, R = snap.task_req.shape
    J = snap.job_min_avail.shape[0]
    Q = snap.queue_weight.shape[0]
    preempt = config.mode == "preempt"
    to_claimants, to_tasks = _claimant_axis(pend_rows, T)
    bids_fn = make_bids()
    task_queue = snap.job_queue[snap.task_job]                      # [T]
    running = victim_running(snap)
    # victims pop in reverse task order (!TaskOrderFn, preempt.go:219-224)
    victim_rank = ordering.multisort_ranks([snap.task_prio, -snap.task_creation])

    deserved = fairness.proportion_deserved(
        snap.total, snap.queue_weight, snap.queue_request, snap.queue_valid
    )
    slack0 = gang_slack0(snap, config)
    # proportion budget: resource a queue can lose while staying ≥ deserved
    qbudget0 = jnp.maximum(snap.queue_alloc - deserved, 0.0)        # [Q, R]

    # the claimants' side of the rank, on the claimant axis: on the bucket
    # the virtual order is computed among the bucket's rows alone (a third
    # of a round's device time on [T]; its three sorts, prefix scans and
    # gathers shrink with the axis).  Only the ORDER among eligible
    # claimants is read (claim_winners), and it is the task axis' order:
    # a row outside the bucket is no claimant, so it adds 0.0 to every
    # prefix and never stands between two claimants' keys, and the bucket
    # ascends, so every stable-sort tie falls as on [T] (the exactness
    # story of ops/assignment.py scatter_bucket_result).  The subrank is
    # taken among the bucket's rows: sort_by_segment_then_rank packs it
    # under the axis' own length.
    c_resreq = to_claimants(snap.task_resreq)
    c_job = to_claimants(snap.task_job)
    c_queue = to_claimants(task_queue)
    # (the task axis' own subrank is the gates' where they ranked)
    c_subrank = subrank if (
        pend_rows is None and subrank is not None
    ) else ordering.task_subranks(
        to_claimants(snap.task_prio), to_claimants(snap.task_creation))

    def round_body(state):
        claim_node, evicted, victim_claimant, i, _ = state
        placed = claim_node >= 0

        # ---- live fairness state -------------------------------------
        placed_req = jnp.where(placed[:, None], snap.task_resreq, 0.0)
        evicted_req = jnp.where(evicted[:, None], snap.task_resreq, 0.0)
        job_delta = jax.ops.segment_sum(
            placed_req - evicted_req, snap.task_job, num_segments=J
        )
        job_alloc_now = snap.job_allocated + job_delta
        queue_alloc_now = snap.queue_alloc + jax.ops.segment_sum(
            job_delta, snap.job_queue, num_segments=Q
        )
        evict_cnt = jax.ops.segment_sum(
            evicted.astype(jnp.int32), snap.task_job, num_segments=J
        )
        slack_rem = slack0 - evict_cnt                               # [J]
        q_evicted = jax.ops.segment_sum(
            evicted_req, task_queue, num_segments=Q
        )
        qbudget_rem = qbudget0 - q_evicted                           # [Q, R]
        pipe_cnt = jax.ops.segment_sum(
            placed.astype(jnp.int32), snap.task_job, num_segments=J
        )
        job_pipelined_now = (snap.job_ready + pipe_cnt) >= snap.job_min_avail
        job_need = jnp.maximum(
            snap.job_min_avail - (snap.job_ready + pipe_cnt), 0
        )

        # ---- victim eligibility --------------------------------------
        victim_ok = running & ~evicted
        if config.victim_conformance:
            victim_ok &= ~snap.task_critical
        if config.victim_gang:
            victim_ok &= slack_rem[snap.task_job] > 0
        if config.victim_proportion and not preempt:
            # victim's resreq must fit its queue's remaining budget over the
            # semantic dims (proportion.go:171-196 LessEqual has no pods)
            sem = fairness.semantic_mask(R)
            victim_ok &= jnp.all(
                (snap.task_resreq <= qbudget_rem[task_queue] + snap.quanta)[..., sem],
                axis=-1,
            )
        if preempt and config.victim_drf:
            # victim-job share after eviction must stay ≥ some preemptor's
            # share; the exact pairwise test happens at selection time —
            # here only the per-victim post-eviction share is prepared
            victim_post_share = fairness.dominant_share(
                job_alloc_now[snap.task_job] - snap.task_resreq, snap.total
            )
        else:
            victim_post_share = jnp.zeros(T, jnp.float32)

        # ---- claimant eligibility + rank -----------------------------
        claimant_ok = claimant_base & ~placed
        if config.proportion and not preempt:
            # reclaim skips overused claimant queues (reclaim.go:112-116)
            q_overused = fairness.overused(deserved, queue_alloc_now, snap.quanta)
            claimant_ok &= ~q_overused[task_queue]
        bidder_ok = to_claimants(claimant_ok)                        # [C]
        rank = ordering.virtual_task_ranks(
            bidder_ok,
            c_resreq,
            c_job,
            c_queue,
            c_subrank,
            snap.job_prio,
            job_pipelined_now,
            snap.job_creation,
            job_alloc_now,
            queue_alloc_now,
            deserved,
            snap.total,
            job_need,
            gang_enabled=config.gang,
            drf_enabled=config.drf,
            proportion_enabled=config.proportion,
        )                                                            # [C]

        # ---- victim-capacity bids ([C, N]-scale, path-specific head) -
        with jax.named_scope("evict_bid"):
            best, has = bids_fn(victim_ok, bidder_ok)
        has &= bidder_ok

        # ---- one winner per node: lowest claimant rank ---------------
        is_winner, winner_task, node_has_claim = claim_winners(
            has, best, rank, N
        )
        if pend_rows is not None:
            # bucket slot -> task row (the bucket ascends, so the largest
            # slot among equal ranks is the largest row, as on [T])
            winner_task = jnp.where(
                node_has_claim, pend_rows[jnp.maximum(winner_task, 0)], -1)
        node_req = jnp.where(
            node_has_claim[:, None], snap.task_req[jnp.maximum(winner_task, 0)], jnp.inf
        )                                                            # [N, R]
        winner_job = jnp.where(
            node_has_claim, snap.task_job[jnp.maximum(winner_task, 0)], -1
        )                                                            # [N]
        winner_queue = jnp.where(
            node_has_claim, task_queue[jnp.maximum(winner_task, 0)], -1
        )
        if preempt and config.victim_drf:
            winner_post_share = fairness.dominant_share(
                job_alloc_now[jnp.maximum(winner_job, 0)]
                + snap.task_resreq[jnp.maximum(winner_task, 0)],
                snap.total,
            )                                                        # [N]

        # ---- victims (shared machinery: selection + caps + coverage) -
        vn = jnp.clip(snap.task_node, 0, N - 1)
        vmask = victim_ok & node_has_claim[vn]
        if preempt:
            # same queue, different job (preempt.go:113-121)
            vmask &= (task_queue == winner_queue[vn]) & (snap.task_job != winner_job[vn])
            if config.victim_drf:
                # preemptor's post-allocation share must stay ≤ victim's
                # post-eviction share (drf.go:85-110)
                vmask &= winner_post_share[vn] <= victim_post_share + SHARE_DELTA
        else:
            vmask &= task_queue != winner_queue[vn]                  # cross-queue
        proportion_cap = config.victim_proportion and not preempt
        final_take, covered = pick_victims(
            snap, vmask, node_req, node_has_claim, victim_rank, slack_rem,
            config, N,
            qbudget_rem=qbudget_rem if proportion_cap else None,
            task_queue=task_queue if proportion_cap else None,
        )

        # ---- apply ---------------------------------------------------
        new_claim = is_winner & covered[jnp.clip(best, 0, N - 1)]   # [C]
        claim_node = jnp.where(
            to_tasks(new_claim, False), to_tasks(best, -1), claim_node)
        evicted = evicted | final_take
        victim_claimant = jnp.where(
            final_take, winner_task[vn], victim_claimant
        )
        return (claim_node, evicted, victim_claimant, i + 1, jnp.any(new_claim))

    def round_cond(state):
        *_, i, progress = state
        return (i < config.rounds) & progress

    claim_node, evicted, victim_claimant, rounds_run, _ = jax.lax.while_loop(
        round_cond,
        round_body,
        (
            jnp.full(T, -1, jnp.int32),
            jnp.zeros(T, bool),
            jnp.full(T, -1, jnp.int32),
            jnp.int32(0),
            jnp.bool_(True),
        ),
    )

    if preempt and config.gang:
        # commit gate: the preemptor job must reach Pipelined
        # (ready + pipelined ≥ MinAvailable, preempt.go:127-137); claims of
        # failing jobs revert, and their victims un-evict (Statement.Discard)
        pipe_cnt = jax.ops.segment_sum(
            (claim_node >= 0).astype(jnp.int32), snap.task_job, num_segments=J
        )
        job_ok = (snap.job_ready + pipe_cnt) >= snap.job_min_avail
        revert = (claim_node >= 0) & ~job_ok[snap.task_job]
        claim_node = jnp.where(revert, -1, claim_node)
        victim_revert = (victim_claimant >= 0) & revert[
            jnp.clip(victim_claimant, 0, T - 1)
        ]
        evicted &= ~victim_revert
        victim_claimant = jnp.where(victim_revert, -1, victim_claimant)

    return claim_node, evicted, victim_claimant, rounds_run


@partial(jax.jit, static_argnames=("config",))
def evict_solve(snap: DeviceSnapshot, config: EvictConfig,
                pend_rows=None) -> EvictResult:
    """The eviction solve on one device.  ``pend_rows`` ([P] i32, the
    pending bucket as actions/allocate.py ``plan_pend_bucket`` plans it)
    runs every [claimant, node] plane on [P, N]; None runs them on [T, N].
    One function, both shapes: the result is bit-identical."""
    view = snap if pend_rows is None else pend_view(snap, pend_rows)
    room = None
    if gates_on(config):
        room = gate_room_local(
            view.task_req, static_predicates(view), snap, config)
    return evict_rounds(
        snap, config, partial(local_evict_bids, snap, config, pend_rows, view),
        room, pend_rows=pend_rows)

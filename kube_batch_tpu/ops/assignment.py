"""The gang-constrained allocate solve — allocate.go + statement.go as one
compiled tensor program.

The reference's allocate is an ordered greedy loop: pop queue (skip overused),
pop job, pop task, predicate all nodes (16 workers), score, pick best, place
on Idle or pipeline on Releasing, commit the job's Statement iff JobReady else
roll back (allocate.go:95-200, statement.go:309-337). That sequencing is
O(tasks × nodes) of host work per cycle.

Here the same semantics run as batched auction rounds on device:

  round:  every unplaced task bids for its best feasible node (argmax over a
          masked score row); conflicts on a node are resolved by admitting
          bidders in task-order-rank sequence until the node's budget is
          exhausted (a segmented prefix-sum over the rank-sorted bidders —
          the moral equivalent of "the PQ order reaches the node first");
          losers re-bid next round against updated budgets.
  gang:   once bidding has stopped (a round placed nothing, or the
          rounds x outer budget is spent), jobs whose allocated count
          (existing ready + new) misses MinAvailable get every new placement
          reverted — the vectorized Statement.Discard (statement.go:309-322);
          an outer iteration then lets surviving tasks re-bid for the freed
          resources.  A pass that only ran out of its `rounds` while still
          placing discards nothing: it carries its placements, half-placed
          gangs included, into the next pass, which re-ranks and bids on.

Divergences from the sequential loop are the sanctioned ones (SURVEY.md
§7.3): placement ties may resolve differently (the reference's
SelectBestNode is itself randomized among max-score nodes,
scheduler_helper.go:147-158), but the invariants hold — no node overcommit,
no committed partial gang, overused queues don't gain tasks.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kube_batch_tpu.api.snapshot import DeviceSnapshot
from kube_batch_tpu.utils import jitstats
from kube_batch_tpu.ops import fairness, ordering
from kube_batch_tpu.ops.ordering import segmented_prefix as _segmented_prefix
from kube_batch_tpu.ops.feasibility import fits, static_predicates
from kube_batch_tpu.ops.scoring import MAX_PRIORITY, ScoreWeights, score_matrix

NEG = jnp.float32(-3.0e38)

# the multiplicative hash constants as wrapped int32 (two's complement):
# int32 wrapping arithmetic is bit-identical to uint32 mod-2^32, and staying
# in int32 avoids uint32<->float casts on the device
_H1 = 0x9E3779B1 - (1 << 32)
_H2 = 0x85EBCA77 - (1 << 32)
_H3 = 0xCA87C3EB - (1 << 32)


def tie_break_hash_rows(ti: jnp.ndarray, ni: jnp.ndarray) -> jnp.ndarray:
    """[len(ti), len(ni)] deterministic per-(task, node) hash in [0, 65535]
    (i32) from explicit GLOBAL task/node indices.  The what-if probe
    (ops/probe.py) hashes a speculative gang at the rows it WOULD occupy on
    submission — sharing this one formula is what makes the probe's
    tie-breaks bit-identical to the committed solve's."""
    h = ti[:, None] * jnp.int32(_H1) + ni[None, :] * jnp.int32(_H2)
    h = (h ^ jax.lax.shift_right_logical(h, jnp.int32(15))) * jnp.int32(_H3)
    return jax.lax.shift_right_logical(h, jnp.int32(16))


def _tie_break_hash(T: int, N: int, t0=0, n0=0) -> jnp.ndarray:
    """[T, N] deterministic per-(task, node) hash in [0, 65535] (i32).
    Ordering is identical to the previous float form (a monotone rescale of
    the same 16 hash bits).  `t0`/`n0` (static or traced i32) offset the
    indices to GLOBAL coordinates when (T, N) is a block of a larger matrix
    — the shard_map round head (parallel/shard_solve.py) computes the hash
    of its local block and must agree bit-for-bit with the full matrix."""
    return tie_break_hash_rows(
        jnp.arange(T, dtype=jnp.int32) + t0,
        jnp.arange(N, dtype=jnp.int32) + n0,
    )


def _best_node(masked: jnp.ndarray, tie_hash: jnp.ndarray):
    """Lexicographic argmax: among the nodes carrying the exact maximum
    score, pick by per-(task, node) hash — the reference's SelectBestNode
    picks uniformly among max-score nodes (scheduler_helper.go:147-158), and
    without a spread every equal-score task herds onto the same argmax node,
    filling one node per bidding round. Exact two-key semantics: a hash can
    never override a genuine score difference (unlike additive jitter).

    Returns (best [T] i32, has [T] bool)."""
    best_val = jnp.max(masked, axis=1)
    tie = masked >= best_val[:, None]
    best = jnp.argmax(jnp.where(tie, tie_hash, -1), axis=1).astype(jnp.int32)
    return best, best_val > NEG


class AllocateConfig(NamedTuple):
    """Static solve configuration (plugin enables + round counts). Part of
    the jit cache key."""

    rounds: int = 6          # bidding rounds per outer iteration
    outer: int = 3           # gang discard-retry iterations
    gang: bool = True        # gang plugin (JobReady commit gate)
    drf: bool = True         # drf job ordering
    proportion: bool = True  # queue overused gating + queue order
    topk: int = 0            # top-K candidate compaction width (the
    #                          allocate_topk_solve path only; 0 in every
    #                          full-matrix program — see KB_TOPK in
    #                          actions/allocate.py's dispatch)
    weights: ScoreWeights = ScoreWeights()


def without_removed_fields(fields: dict) -> dict:
    """The fields of an AllocateConfig serialized by an older version (a
    guard bundle's meta.json, a replication frame from a leader not yet
    upgraded) without what this version no longer has: ``use_pallas``,
    whose kernel PR 29 removed.  Off, it is dropped; on, the program it
    names cannot be run here, and that is an error."""
    fields = dict(fields)
    if fields.pop("use_pallas", False):
        raise ValueError(
            "config has use_pallas=true: the Pallas round-head kernel was "
            "removed in PR 29, so its program cannot be run")
    return fields


class AllocateResult(NamedTuple):
    assigned: jnp.ndarray       # [T] i32 node index, -1 = unplaced
    pipelined: jnp.ndarray      # [T] bool — placed on Releasing (future) budget
    committed: jnp.ndarray      # [J] bool — job's new placements were kept
    node_idle: jnp.ndarray      # [N, R] post-solve
    node_releasing: jnp.ndarray  # [N, R] post-solve
    node_used: jnp.ndarray      # [N, R] post-solve
    deserved: jnp.ndarray       # [Q, R] proportion deserved (diagnostics)
    rounds_run: jnp.ndarray     # [] i32 — total bidding rounds executed
    #                             (convergence diagnostic for round tuning)
    topk_exhausted: jnp.ndarray  # [] i32 — task-rounds whose candidate list
    #                              was exhausted (0 on the full-matrix path)
    topk_reentries: jnp.ndarray  # [] i32 — rounds that re-entered the
    #                              full-matrix head for exhausted rows
    term_exclusions: object = None  # [] i32 — bidders a same-solve placement
    #                                 turned away from their first choice
    #                                 (None without DeviceSnapshot.aff_terms)


@jax.jit
def failure_histogram_solve(snap: DeviceSnapshot) -> jnp.ndarray:
    """[T, N_REASONS] cycle-start fit-error histogram as its OWN dispatch.

    The histogram re-walks the [T, N]-scale predicate bitsets, so folding it
    into allocate_solve taxed every cycle — including the steady-state ones
    where every pending task places and the histogram is never read
    (allocate.go:151-155 only builds FitErrors for tasks that failed). The
    action calls this lazily, after the solve's assignment shows unplaced
    pending tasks."""
    from kube_batch_tpu.ops.feasibility import FeasibilityMasks, failure_histogram

    static_ok = static_predicates(snap)
    fit0_idle = fits(snap.task_req, snap.node_idle, snap.quanta)
    fit0_rel = fits(snap.task_req, snap.node_releasing, snap.quanta)
    return failure_histogram(
        snap,
        FeasibilityMasks(
            static_ok, fit0_idle, fit0_rel, static_ok & (fit0_idle | fit0_rel)
        ),
    )


def _queue_gate(
    cand: jnp.ndarray,        # [T] bool — bid this round
    order: jnp.ndarray,       # [T] i32 — queue-major rank-minor sort, hoisted
    #                           out of the round loop (the (queue, rank) key
    #                           is static per outer pass)
    task_job: jnp.ndarray,    # [T] i32
    task_queue: jnp.ndarray,  # [T] i32
    resreq: jnp.ndarray,      # [T, R]
    qalloc: jnp.ndarray,      # [Q, R] — queue allocation incl. this cycle
    deserved: jnp.ndarray,    # [Q, R]
    quanta: jnp.ndarray,      # [R]
    job_need: jnp.ndarray,    # [J] i32 — minAvailable − currently-ready
    n_jobs: int,
) -> jnp.ndarray:
    """Proportion admission (the Overused pop-gate, allocate.go:101-104 +
    proportion.go:198-209, at the granularity the sequential loop actually
    enforces it): walk each queue's bidders in rank order; a bidder passes
    while its queue is not yet overused at its prefix position. An unready
    job's first `need` bidders form the gang chunk and pass iff the queue
    wasn't overused when the chunk head arrived — the whole Statement commits
    even if it overshoots deserved, exactly like a popped gang job."""
    T, R = resreq.shape
    # a job's bidders are contiguous inside its queue segment because the
    # hoisted order sorts by (queue, rank) and rank orders by (job, subrank)
    cs = cand[order]
    qs = task_queue[order]
    js = task_job[order]
    rq = jnp.where(cs[:, None], resreq[order], 0.0)
    q_start = jnp.concatenate([jnp.array([True]), qs[1:] != qs[:-1]])
    prefix = _segmented_prefix(rq, q_start)  # [T, R] exclusive, per queue
    # overused over semantic dims only — pods is capacity, not fairness
    sem = fairness.semantic_mask(R)
    pos_overused = jnp.all(
        (deserved[qs] <= qalloc[qs] + prefix + quanta)[..., sem], axis=-1
    )
    # candidate position within the job (segmented candidate count)
    j_start = jnp.concatenate([jnp.array([True]), js[1:] != js[:-1]])
    ci = cs.astype(jnp.float32)[:, None]
    pos_in_job = _segmented_prefix(ci, j_start)[:, 0].astype(jnp.int32)
    in_chunk = cs & (pos_in_job < job_need[js])
    # chunk head verdict, broadcast job-wide
    head_ok = jnp.zeros(n_jobs, bool).at[js].max(cs & (pos_in_job == 0) & ~pos_overused)
    ok = cs & (~pos_overused | (in_chunk & head_ok[js]))
    return jnp.zeros(T, bool).at[order].set(ok)


def _resolve_conflicts(
    cand: jnp.ndarray,      # [T] bool — bidding this round on this budget
    choice: jnp.ndarray,    # [T] i32 — chosen node per task
    rank: jnp.ndarray,      # [T] i32 — task order (lower wins)
    fit_req: jnp.ndarray,   # [T, R] — InitResreq (fit check, allocate.go:161)
    acct_req: jnp.ndarray,  # [T, R] — Resreq (budget consumption,
    #                                  statement.go allocate→node.AddTask)
    budget: jnp.ndarray,    # [N, R]
    quanta: jnp.ndarray,    # [R]
):
    """Admit bidders per node in rank order while the prefix fits the budget.

    Returns (accept [T] bool, delta [N, R] consumed). The prefix test charges
    each bidder its predecessors' Resreq plus its own InitResreq, which is
    exactly the sequential loop's state when it reaches that task.
    """
    T, R = fit_req.shape
    N = budget.shape[0]
    seg = jnp.where(cand, choice, N)  # non-bidders park in segment N
    # rank-major within node
    order = ordering.sort_by_segment_then_rank(seg, rank, N + 1)
    seg_s = seg[order]
    acct_s = jnp.where(cand[order, None], acct_req[order], 0.0)
    fit_s = fit_req[order]
    is_start = jnp.concatenate([jnp.array([True]), seg_s[1:] != seg_s[:-1]])
    within_excl = _segmented_prefix(acct_s, is_start)
    budget_here = budget[jnp.clip(seg_s, 0, N - 1)]
    ok = jnp.all(fit_s + within_excl <= budget_here + quanta, axis=-1)
    accept_s = ok & cand[order] & (seg_s < N)
    accept = jnp.zeros(T, bool).at[order].set(accept_s)
    delta = jax.ops.segment_sum(
        jnp.where(accept_s[:, None], acct_s, 0.0), seg_s, num_segments=N + 1
    )[:N]
    return accept, delta


def round_head_parts(snap: DeviceSnapshot, config: AllocateConfig,
                     tie_hash: jnp.ndarray = None):
    """:func:`local_round_head` plus its intermediates: ``(head,
    static_ok, score)``.  The what-if probe (ops/probe.py) calls this with
    an explicit ``tie_hash`` — the hash at the GLOBAL rows a speculative
    gang would occupy — and reuses static_ok/score for its eviction bids
    and fit-error histogram; sharing ONE head body is what keeps probe
    answers structurally bit-identical to the committed solve."""
    static_ok = static_predicates(snap)           # [T, N]
    score = score_matrix(snap, config.weights)
    # static predicates folded into the score once — every round reuses it
    score_static = jnp.where(static_ok, score, NEG)
    T, N = score.shape
    if tie_hash is None:
        tie_hash = _tie_break_hash(T, N)

    def head(idle, releasing, pending):
        fit_idle = fits(snap.task_req, idle, snap.quanta)
        # zero-releasing clusters (every allocate-only cycle) skip
        # the second [T, N] fit entirely: with an all-zero budget the
        # only "fits" are tasks below quanta in every dim — BestEffort
        # tasks, which are never solver-pending (task_pending
        # excludes them), so all-False is exact for solver outputs
        fit_rel = jax.lax.cond(
            jnp.any(releasing > 0.0),
            lambda rel: fits(snap.task_req, rel, snap.quanta),
            lambda rel: jnp.zeros_like(fit_idle),
            releasing,
        )
        # score_static pre-folds the loop-invariant static predicate
        # mask into the score (hoisted out of the rounds)
        masked = jnp.where(
            (fit_idle | fit_rel) & pending[:, None], score_static, NEG
        )
        best, has = _best_node(masked, tie_hash)
        # allocate if the chosen node fits Idle, else pipeline onto
        # Releasing (allocate.go:161-184: the idle-vs-releasing decision
        # happens on the already-selected best-score node)
        chose_idle = jnp.take_along_axis(fit_idle, best[:, None], axis=1)[:, 0]
        return best, has, chose_idle

    return head, static_ok, score


def local_round_head(snap: DeviceSnapshot, config: AllocateConfig):
    """Build the single-program round head: ``head(idle, releasing,
    pending) -> (best, has, chose_idle)`` computed from the full [T, N]
    matrices in one logical program (on the pjit path GSPMD partitions it
    implicitly).  The shard_map path substitutes the explicit-collective
    block head (parallel/shard_solve.py); everything else in the solve is
    the SHARED :func:`allocate_rounds` machinery, so the two paths can only
    diverge in the head — which both compute bit-identically."""
    return round_head_parts(snap, config)[0]


def make_term_round(snap: DeviceSnapshot, config: AllocateConfig):
    """The in-solve half of inter-pod (anti-)affinity, required and
    preferred: pods placed earlier in the same solve count.  ``snap`` is the
    solve's task view with ``aff_terms`` (api/affinity_planes.AffinityTerms)
    beside the sparse rows of ``task_aff_idx``; returns ``term_round(idle,
    releasing, assigned, rank, best, has, chose_idle) -> (best, has,
    chose_idle, turned_away)``, which every bidding round calls after its
    head and its queue gate.

    The head's choice for a sparse row rests on ``task_aff_mask`` and
    ``task_pref_pod``, the planes as they stood when the snapshot was taken.
    Here the round's bidding sparse rows are walked once more IN RANK ORDER,
    each choosing its best node (the round head's own two-key argmax over
    its full [N] score row) among those the placements so far leave open:
    those the solve has accepted in earlier rounds, and the choices of the
    rows walked before it in this round (their room is taken too).  So two
    pods that a required anti-affinity term makes exclusive never win one
    domain in one round (the first bidder wins, the capacity conflict's own
    shape), a later round sees the earlier rounds' placements, and a
    group's first pod pins the domain its followers join (``need``: the
    first-pod fast path of predicates.pod_affinity_ok).  Inside one solve the anti-affinity rule is
    symmetric (a selected pod does not join the domain of a pod whose term
    excludes it either): order-free, which is what lets the sentinel check
    it.  A row's preferred score is nodeorder.preferred_pod_affinity_score
    over the same placements (``here`` joined with them), min-max reduced
    as the host reduces it: twenty replicas that prefer to sit apart do not
    all read the one score row the cycle's start gave them.  Whether a
    choice is then admitted is still the capacity conflict's to say; a
    bidder that loses there bids again next round."""
    x = snap.aff_terms
    T = snap.task_req.shape[0]
    N = snap.node_alloc.shape[0]
    K, Pp = x.anti.shape
    idx = snap.task_aff_idx
    live = idx >= 0
    rows = jnp.clip(idx, 0, T - 1)
    slot = jnp.arange(K, dtype=jnp.int32)
    view_k = pend_view(snap._replace(aff_terms=None), idx)._replace(
        task_aff_idx=jnp.where(live, slot, -1))
    w_pod = config.weights.pod_affinity
    with jax.named_scope("term_rows"):
        # everything of the score but the preferred pod terms, which move
        # with the walk
        score_static = jnp.where(
            static_predicates(view_k),
            score_matrix(view_k, config.weights._replace(pod_affinity=0.0)),
            NEG)                                               # [K, N]
        tie = tie_break_hash_rows(
            jnp.maximum(x.row, 0), jnp.arange(N, dtype=jnp.int32))
    req_k = view_k.task_req
    pair = jnp.arange(Pp, dtype=jnp.int32)
    i32max = jnp.iinfo(jnp.int32).max

    def term_round(idle, releasing, assigned, rank, best, has, chose_idle):
        a_k = assigned[rows]
        placed_k = live & (a_k >= 0)
        dom_a = x.dom[:, jnp.clip(a_k, 0, N - 1)]              # [Pp, K]

        def plane(flag):
            """[Pp, N] bool: a placed row with ``flag`` on pair p sits in
            the node's domain under p."""
            upd = (flag & placed_k[:, None]).T.astype(jnp.int32)
            by_domain = jnp.zeros((Pp, N), jnp.int32).at[
                pair[:, None], dom_a].add(upd) > 0
            return jnp.take_along_axis(by_domain, x.dom, axis=1)

        sel0, anti0 = plane(x.selp), plane(x.anti)
        any0 = jnp.any(x.selp & placed_k[:, None], axis=0)     # [Pp]
        bid_k = live & has[rows]
        first = best[rows]          # the head's choice, static mask only
        order = jnp.argsort(jnp.where(bid_k, rank[rows], i32max))
        rel_any = jnp.any(releasing > 0.0)

        def step(i, st):
            (sel_at, anti_at, anyp, idle, releasing, best_k, has_k, chose_k,
             turned) = st
            k = order[i]
            shut = jnp.any(
                (x.anti[k][:, None] & sel_at) | (x.selp[k][:, None] & anti_at)
                | ((x.need[k] & anyp)[:, None] & ~sel_at), axis=0)
            fit_i = jnp.all(req_k[k] <= idle + snap.quanta, axis=-1)
            fit_r = rel_any & jnp.all(
                req_k[k] <= releasing + snap.quanta, axis=-1)
            score = score_static[k]
            if w_pod:
                raw = x.pw[k] @ (x.here | sel_at).astype(jnp.float32)  # [N]
                lo = jnp.min(jnp.where(x.live, raw, jnp.inf))
                span = jnp.max(jnp.where(x.live, raw, -jnp.inf)) - lo
                score = score + w_pod * jnp.where(
                    x.live & (span > 0.0),
                    MAX_PRIORITY * (raw - lo)
                    / jnp.where(span > 0.0, span, 1.0), 0.0)
            masked = jnp.where(~shut & (fit_i | fit_r), score, NEG)
            top = jnp.max(masked)
            b = jnp.argmax(
                jnp.where(masked >= top, tie[k], -1)).astype(jnp.int32)
            h = top > NEG
            same = x.dom == x.dom[:, b][:, None]    # [Pp, N]: b's domains
            sel_at = sel_at | (same & (h & x.selp[k])[:, None])
            anti_at = anti_at | (same & (h & x.anti[k])[:, None])
            # the room the rows walked before it took is gone for this one
            # (replicas that prefer company would else all choose the one
            # node and the capacity conflict would admit a node's worth a
            # round); what it is finally given is still the conflict's to say
            took = jnp.where(h, req_k[k], 0.0)
            idle = idle.at[b].add(jnp.where(fit_i[b], -took, 0.0))
            releasing = releasing.at[b].add(jnp.where(fit_i[b], 0.0, -took))
            return (sel_at, anti_at, anyp | (h & x.selp[k]), idle, releasing,
                    best_k.at[k].set(b), has_k.at[k].set(h),
                    chose_k.at[k].set(fit_i[b]),
                    turned + shut[first[k]].astype(jnp.int32))

        with jax.named_scope("term_round"):
            *_, best_k, has_k, chose_k, turned = jax.lax.fori_loop(
                0, jnp.sum(bid_k, dtype=jnp.int32), step,
                (sel0, anti0, any0, idle, releasing, first,
                 jnp.zeros(K, bool), jnp.zeros(K, bool), jnp.int32(0)))
        scat = jnp.where(bid_k, idx, T)   # everything else drops
        return (best.at[scat].set(best_k, mode="drop"),
                has.at[scat].set(has_k, mode="drop"),
                chose_idle.at[scat].set(chose_k, mode="drop"), turned)

    return term_round


def allocate_rounds(
    snap: DeviceSnapshot,
    config: AllocateConfig,
    head_fn,
    idle0: jnp.ndarray,
    releasing0: jnp.ndarray,
    used0: jnp.ndarray,
    compact_head=None,
) -> AllocateResult:
    """The solve machinery shared by every allocate path: bidding rounds
    with ``head_fn`` supplying (best, has, chose_idle) per round, conflict
    resolution, the proportion gate, and the gang commit/discard outer
    loop.  ``idle0``/``releasing0``/``used0`` are the GLOBAL [N, R] cycle-
    start ledgers (the shard_map body passes the explicitly all-gathered
    replicated copies; per-round cross-shard traffic then lives entirely
    inside ``head_fn``).

    ``compact_head`` (the top-K compaction path) replaces ``head_fn`` with
    a head returning ``(best, has, chose_idle, exhausted_count)`` — the
    candidate-table scan plus its full-matrix exhaustion re-entry (see
    :func:`allocate_topk_solve`); the extra count feeds the
    ``topk_exhausted``/``topk_reentries`` diagnostics."""
    T, R = snap.task_req.shape
    N = idle0.shape[0]
    J = snap.job_min_avail.shape[0]
    Q = snap.queue_weight.shape[0]

    subrank = ordering.task_subranks(snap.task_prio, snap.task_creation)
    # required inter-pod terms: same-solve placements count (no leaf and no
    # equation where the snapshot carries none)
    term_round = (make_term_round(snap, config)
                  if snap.aff_terms is not None else None)

    # proportion deserved is computed once per cycle from the session-open
    # state (proportion.go:101-154 runs in OnSessionOpen)
    deserved = fairness.proportion_deserved(
        snap.total, snap.queue_weight, snap.queue_request, snap.queue_valid
    )

    eligible = (
        snap.task_pending
        & snap.task_valid
        & snap.job_valid[snap.task_job]
        & snap.job_schedulable[snap.task_job]
    )

    def outer_body(state):
        (idle, releasing, used, assigned, pipelined, job_failed, o,
         rounds_total, exh_total, reent_total, _more, turned_total) = state

        # ---- fairness state + virtual-time rank, once per outer pass -----
        # (the rank is a static plan for the whole round set: virtual time
        # already charges each bidder its prefix position, so per-round
        # recomputation only corrects second-order drift — not worth the
        # dozen extra 50k-element sorts per round)
        placed0 = assigned >= 0
        placed_req0 = jnp.where(placed0[:, None], snap.task_resreq, 0.0)
        job_new0 = jax.ops.segment_sum(placed_req0, snap.task_job, num_segments=J)
        new_alloc_cnt0 = jax.ops.segment_sum(
            (placed0 & ~pipelined).astype(jnp.int32), snap.task_job, num_segments=J
        )
        job_ready_now = (snap.job_ready + new_alloc_cnt0) >= snap.job_min_avail
        job_need0 = jnp.maximum(
            snap.job_min_avail - (snap.job_ready + new_alloc_cnt0), 0
        )
        pending0 = eligible & ~placed0 & ~job_failed[snap.task_job]
        rank = ordering.virtual_task_ranks(
            pending0,
            snap.task_resreq,
            snap.task_job,
            snap.job_queue[snap.task_job],
            subrank,
            snap.job_prio,
            job_ready_now,
            snap.job_creation,
            snap.job_allocated + job_new0,
            snap.queue_alloc
            + jax.ops.segment_sum(job_new0, snap.job_queue, num_segments=Q),
            deserved,
            snap.total,
            job_need0,
            gang_enabled=config.gang,
            drf_enabled=config.drf,
            proportion_enabled=config.proportion,
        )
        task_queue = snap.job_queue[snap.task_job]
        # queue-major rank-minor sort for the proportion gate — static per
        # outer pass, hoisted out of the rounds (one 50k-sort per round saved)
        qgate_order = ordering.sort_by_segment_then_rank(task_queue, rank, Q)

        def round_cond(state):
            *_, i, progress, _turned = state
            return (i < config.rounds) & progress

        def round_body(state):
            (idle, releasing, used, assigned, pipelined, exh_n, reent_n,
             i, _, turned_n) = state
            placed = assigned >= 0
            placed_req = jnp.where(placed[:, None], snap.task_resreq, 0.0)
            job_new = jax.ops.segment_sum(placed_req, snap.task_job, num_segments=J)
            queue_alloc = snap.queue_alloc + jax.ops.segment_sum(
                job_new, snap.job_queue, num_segments=Q
            )
            pending = eligible & ~placed & ~job_failed[snap.task_job]

            if compact_head is not None:
                best, has, chose_idle, exh_round = compact_head(
                    idle, releasing, pending
                )
                exh_n = exh_n + exh_round
                reent_n = reent_n + (exh_round > 0).astype(jnp.int32)
            else:
                best, has, chose_idle = head_fn(idle, releasing, pending)
            if config.proportion:
                new_alloc_cnt = jax.ops.segment_sum(
                    (placed & ~pipelined).astype(jnp.int32),
                    snap.task_job,
                    num_segments=J,
                )
                job_need = jnp.maximum(
                    snap.job_min_avail - (snap.job_ready + new_alloc_cnt), 0
                )
                has &= _queue_gate(
                    has,
                    qgate_order,
                    snap.task_job,
                    task_queue,
                    snap.task_resreq,
                    queue_alloc,
                    deserved,
                    snap.quanta,
                    job_need,
                    J,
                )
            if term_round is not None:
                best, has, chose_idle, turned = term_round(
                    idle, releasing, assigned, rank, best, has, chose_idle)
                turned_n = turned_n + turned
            alloc_cand = has & chose_idle
            pipe_cand = has & ~chose_idle

            acc_a, delta_a = _resolve_conflicts(
                alloc_cand, best, rank, snap.task_req, snap.task_resreq, idle, snap.quanta
            )
            # pipeline-on-releasing bidders exist only when eviction freed
            # capacity this cycle; the steady-state allocate-only round has
            # none — skip the second sort + segmented scan entirely
            acc_p, delta_p = jax.lax.cond(
                jnp.any(pipe_cand),
                lambda: _resolve_conflicts(
                    pipe_cand, best, rank, snap.task_req, snap.task_resreq,
                    releasing, snap.quanta,
                ),
                lambda: (jnp.zeros(T, bool), jnp.zeros_like(releasing)),
            )
            # statement.Allocate → node.AddTask(Allocated): Idle -= r, Used += r
            # statement.Pipeline → node.AddTask(Pipelined): Releasing -= r, Used += r
            idle = idle - delta_a
            releasing = releasing - delta_p
            used = used + delta_a + delta_p
            newly = acc_a | acc_p
            assigned = jnp.where(newly, best, assigned)
            pipelined = pipelined | acc_p
            return (idle, releasing, used, assigned, pipelined, exh_n,
                    reent_n, i + 1, jnp.any(newly), turned_n)

        (idle, releasing, used, assigned, pipelined, exh_total, reent_total,
         rounds_i, rounds_progress, turned_total) = (
            jax.lax.while_loop(
                round_cond,
                round_body,
                (idle, releasing, used, assigned, pipelined, exh_total,
                 reent_total, jnp.int32(0), jnp.bool_(True), turned_total),
            )
        )
        # inner loop capped while still placing? another outer pass continues
        rounds_capped = rounds_progress & (rounds_i >= config.rounds)
        # bidding has STOPPED when the rounds ended for want of progress, the
        # rounds x outer budget is spent, or nothing is left to bid; only
        # then is a gang below MinAvailable a gang that cannot get there (the
        # reference discards a Statement on what is free, never on a loop
        # counter: allocate.go:192-196).  A pass that merely ran out of rounds
        # carries its placements, whole and partial, into the next pass
        settled = (
            ~rounds_capped
            | (o + 1 >= config.outer)
            | ~jnp.any(eligible & (assigned < 0) & ~job_failed[snap.task_job])
        )
        # ---- gang commit/discard (vectorized Statement) -----------------
        new_alloc_cnt = jax.ops.segment_sum(
            ((assigned >= 0) & ~pipelined).astype(jnp.int32),
            snap.task_job,
            num_segments=J,
        )
        if config.gang:
            job_ok = (snap.job_ready + new_alloc_cnt) >= snap.job_min_avail
        else:
            job_ok = jnp.ones(J, bool)
        # a job whose placements get reverted is done for this cycle — the
        # reference pops each job once and a discarded Statement isn't
        # retried (allocate.go:192-196); without this, a big starved gang
        # would re-grab the freed capacity every iteration and smaller jobs
        # behind it would never see it
        new_any = jax.ops.segment_sum(
            (assigned >= 0).astype(jnp.int32), snap.task_job, num_segments=J
        )
        job_discard = settled & ~job_ok & (new_any > 0)
        job_failed = job_failed | job_discard
        revert = (assigned >= 0) & job_discard[snap.task_job]
        seg = jnp.where(revert, assigned, N)
        rev_req = jnp.where(revert[:, None], snap.task_resreq, 0.0)
        rev_alloc = jax.ops.segment_sum(
            jnp.where(~pipelined[:, None], rev_req, 0.0), seg, num_segments=N + 1
        )[:N]
        rev_pipe = jax.ops.segment_sum(
            jnp.where(pipelined[:, None], rev_req, 0.0), seg, num_segments=N + 1
        )[:N]
        idle = idle + rev_alloc
        releasing = releasing + rev_pipe
        used = used - rev_alloc - rev_pipe
        reverted_any = jnp.any(revert)
        assigned = jnp.where(revert, -1, assigned)
        pipelined = pipelined & ~revert
        # still work to do? when this iteration reverted a gang (freed
        # capacity another job can grab) OR the bidding rounds hit their cap
        # while still placing — AND schedulable pending tasks remain
        more = (reverted_any | rounds_capped) & jnp.any(
            eligible & (assigned < 0) & ~job_failed[snap.task_job]
        )
        return (idle, releasing, used, assigned, pipelined, job_failed, o + 1,
                rounds_total + rounds_i, exh_total, reent_total, more,
                turned_total)

    def outer_cond(state):
        *_, o, _rounds, _exh, _reent, more, _turned = state
        return (o < config.outer) & more

    init = (
        idle0,
        releasing0,
        used0,
        jnp.full(T, -1, jnp.int32),
        jnp.zeros(T, bool),
        jnp.zeros(J, bool),
        jnp.int32(0),
        jnp.int32(0),
        jnp.int32(0),
        jnp.int32(0),
        jnp.bool_(True),
        # the bidders turned away by a same-solve placement: a carry of no
        # leaf where the snapshot has no term
        jnp.int32(0) if term_round is not None else (),
    )
    # while_loop with early exit — a scan would pay every outer iteration
    # (~12% of solve time each) even after everything is placed
    (idle, releasing, used, assigned, pipelined, _, _, rounds_run,
     exhausted, reentries, _, turned_away) = (
        jax.lax.while_loop(outer_cond, outer_body, init)
    )

    # after the final outer revert, every surviving placement belongs to a
    # job that passed the commit gate; committed = "has surviving placements"
    new_any_cnt = jax.ops.segment_sum(
        (assigned >= 0).astype(jnp.int32), snap.task_job, num_segments=J
    )
    committed = new_any_cnt > 0
    return AllocateResult(
        assigned=assigned,
        pipelined=pipelined,
        committed=committed,
        node_idle=idle,
        node_releasing=releasing,
        node_used=used,
        deserved=deserved,
        rounds_run=rounds_run,
        topk_exhausted=exhausted,
        topk_reentries=reentries,
        term_exclusions=turned_away if term_round is not None else None,
    )


@partial(jax.jit, static_argnames=("config",))
def allocate_solve(snap: DeviceSnapshot, config: AllocateConfig) -> AllocateResult:
    """One allocate action pass over the snapshot."""
    return allocate_rounds(
        snap, config, local_round_head(snap, config),
        snap.node_idle, snap.node_releasing, snap.node_used,
    )


# ==========================================================================
# Top-K candidate compaction (KB_TOPK) — the O(T·K) round inner loop
# ==========================================================================
#
# The full-matrix round head re-streams [T, N]-scale fits/argmax every
# bidding round even though (a) only the PENDING rows can bid and (b) node
# budgets only SHRINK between the cycle start and any round (gang reverts
# return exactly what accepted bids consumed, so idle/releasing never
# exceed their cycle-start values).  The compacted path exploits both:
#
#   pending bucket  — the solve's head runs on a [P] bucket of the cycle's
#     pending task rows (P ≪ T in steady state; the row map is an input);
#   candidate table — once per solve, at cycle-start budgets, each bucket
#     row's nodes are ranked by the EXACT round-head key (score_static
#     desc, tie_hash desc, node index asc) and the top-K kept.
#
# Exactness invariant (why first-fit-over-the-table == full argmax): the
# table is the exact lexicographic top-K among cycle-start-FEASIBLE nodes;
# any node outside the table has key ≤ every table entry's key; a round's
# currently-fitting nodes are a subset of cycle-start-feasible (budgets
# only shrink); so whenever ANY table entry fits, the two-key argmax over
# the fitting table entries is the full-matrix argmax.  A row whose table
# entries ALL stop fitting while the table was truncated (> K feasible
# nodes at build) is EXHAUSTED: the same round re-enters the full-matrix
# head for exactly those rows (a lax.cond — steady rounds with no
# exhaustion never pay it), so compacted-vs-full is bit-exact by
# construction, not by tolerance.

#: sort-key of NEG — table entries at or below it are invalid padding
_I32_MIN = jnp.int32(-(2 ** 31))


def f32_sort_key(x: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving map f32 → i32 (finite inputs; the solve's scores
    are finite by construction): integer compare of the keys equals float
    compare of the values, so the candidate build can run entirely in
    exact integer arithmetic.  ``x + 0.0`` canonicalizes -0.0 to +0.0
    first (exact identity for every other value): float compare treats
    the two zeros as EQUAL, and the raw bit patterns would order them —
    a custom extra_rows score emitting -0.0 must not break the
    bit-exactness contract with the float-comparing full-matrix oracle.
    Zero-canonical inputs make the map a bijection (``_inv_sort_key``)."""
    b = jax.lax.bitcast_convert_type(x + jnp.float32(0.0), jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def _neg_key() -> jnp.ndarray:
    return f32_sort_key(jnp.float32(NEG))


def lex_topk(skey: jnp.ndarray, hash_: jnp.ndarray, idx0: jnp.ndarray,
             K: int, block: int = 64):
    """Exact per-row lexicographic top-K of (skey desc, hash desc,
    position asc) over [P, M] — ``jnp.argmax``'s first-max-index semantics
    extended to K extractions.  Returns ``(idx, skey, hash)`` [P, K] in
    descending key order (full-tie entries in ascending position order).

    XLA's CPU ``sort``/``top_k`` are comparator-bound (≈50× a reduction
    pass at [2k, 2k]); this is a blocked tournament instead: per-block
    two-key winner triples once, then K extraction steps that re-reduce
    ONLY the winning block under a (val, hash, position) threshold — no
    per-step scatter into the [P, M] operands, which stay read-only.
    ``idx0`` carries the caller's global identity per position (a
    broadcast arange+offset for a build over a node block; the stored
    global indices for a cross-shard merge)."""
    P, M = skey.shape
    C = min(block, M)
    Mp = -(-M // C) * C
    pad = Mp - M
    if pad:
        skey = jnp.pad(skey, ((0, 0), (0, pad)), constant_values=-(2 ** 31))
        hash_ = jnp.pad(hash_, ((0, 0), (0, pad)), constant_values=-1)
        idx0 = jnp.pad(idx0, ((0, 0), (0, pad)), constant_values=-1)
    B = Mp // C
    s3 = skey.reshape(P, B, C)
    h3 = hash_.reshape(P, B, C)
    bval = jnp.max(s3, axis=-1)
    btie = s3 >= bval[..., None]
    bh = jnp.max(jnp.where(btie, h3, -2), axis=-1)
    bcol = jnp.argmax(jnp.where(btie, h3, -2), axis=-1).astype(jnp.int32)
    rows = jnp.arange(P)
    carange = jnp.arange(C, dtype=jnp.int32)[None, :]

    def step(k, state):
        bval, bh, bcol, oi, os, oh = state
        # global two-key argmax over the per-block winners; first block
        # among full ties = lowest position (blocks are position-ordered)
        gv = jnp.max(bval, axis=1)
        tie = bval >= gv[:, None]
        ghv = jnp.max(jnp.where(tie, bh, -2), axis=1)
        gb = jnp.argmax(jnp.where(tie, bh, -2), axis=1).astype(jnp.int32)
        col = jnp.take_along_axis(bcol, gb[:, None], 1)[:, 0]
        flat = gb * C + col
        oi = jax.lax.dynamic_update_slice(
            oi, jnp.take_along_axis(idx0, flat[:, None], 1), (0, k))
        os = jax.lax.dynamic_update_slice(os, gv[:, None], (0, k))
        oh = jax.lax.dynamic_update_slice(oh, ghv[:, None], (0, k))
        # winning block re-reduces under the extracted threshold: keep
        # strictly-lower keys, or equal keys at LATER positions (extraction
        # order is monotone, so the threshold subsumes all prior ones)
        cols_ = (gb * C)[:, None] + carange
        gs = jnp.take_along_axis(skey, cols_, 1)
        gh2 = jnp.take_along_axis(hash_, cols_, 1)
        keep = (gs < gv[:, None]) | ((gs == gv[:, None]) & (
            (gh2 < ghv[:, None])
            | ((gh2 == ghv[:, None]) & (cols_ > flat[:, None]))))
        gs = jnp.where(keep, gs, _I32_MIN)
        nv = jnp.max(gs, axis=1)
        nt = gs >= nv[:, None]
        nh = jnp.max(jnp.where(nt, gh2, -2), axis=1)
        nc = jnp.argmax(jnp.where(nt, gh2, -2), axis=1).astype(jnp.int32)
        bval = bval.at[rows, gb].set(nv)
        bh = bh.at[rows, gb].set(nh)
        bcol = bcol.at[rows, gb].set(nc)
        return bval, bh, bcol, oi, os, oh

    init = (bval, bh, bcol, jnp.zeros((P, K), jnp.int32),
            jnp.full((P, K), _I32_MIN), jnp.full((P, K), -1, jnp.int32))
    *_, oi, os, oh = jax.lax.fori_loop(0, K, step, init)
    return oi, os, oh


def lex_topk3(skey: jnp.ndarray, hash_: jnp.ndarray, idx: jnp.ndarray,
              K: int, block: int = 64):
    """Exact per-row top-K of (skey desc, hash desc, **idx asc**) with the
    index as an EXPLICIT third key — :func:`lex_topk` generalized past its
    positional-tie assumption (it breaks full ties by input POSITION,
    which equals the index order only when the caller's columns are
    index-sorted).  The warm-table merge concatenates a carried table with
    a fresh changed-node block, neither index-contiguous — pre-sorting by
    index would cost a [P, W+C] comparator sort per solve (XLA's CPU sort
    is ~50× a reduction pass — the very cost lex_topk exists to avoid),
    so the tournament carries the index and reduces it with a min.

    Requires per-row-unique indices among valid entries (the merge
    guarantees it: stored nodes are distinct and changed stored entries
    are removed before their fresh versions join).  Returns ``(idx, skey,
    hash)`` [P, K] in descending lex order."""
    P, M = skey.shape
    C = min(block, M)
    Mp = -(-M // C) * C
    pad = Mp - M
    if pad:
        skey = jnp.pad(skey, ((0, 0), (0, pad)), constant_values=-(2 ** 31))
        hash_ = jnp.pad(hash_, ((0, 0), (0, pad)), constant_values=-1)
        idx = jnp.pad(idx, ((0, 0), (0, pad)),
                      constant_values=(1 << 30))
    B = Mp // C
    s3 = skey.reshape(P, B, C)
    h3 = hash_.reshape(P, B, C)
    i3 = idx.reshape(P, B, C)
    BIG = jnp.int32(1 << 30)

    def block_reduce(s, h, i):
        bval = jnp.max(s, axis=-1)
        t1 = s >= bval[..., None]
        bh = jnp.max(jnp.where(t1, h, -2), axis=-1)
        t2 = t1 & (h == bh[..., None])
        bidx = jnp.min(jnp.where(t2, i, BIG), axis=-1)
        return bval, bh, bidx

    bval, bh, bidx = block_reduce(s3, h3, i3)
    barange = jnp.arange(B, dtype=jnp.int32)[None, :]

    def step(k, state):
        bval, bh, bidx, oi, os, oh = state
        gv = jnp.max(bval, axis=1)
        t1 = bval >= gv[:, None]
        ghv = jnp.max(jnp.where(t1, bh, -2), axis=1)
        t2 = t1 & (bh == ghv[:, None])
        gidx = jnp.min(jnp.where(t2, bidx, BIG), axis=1)
        # indices are per-row unique → exactly one block holds the winner
        gb = jnp.argmax(t2 & (bidx == gidx[:, None]), axis=1).astype(
            jnp.int32
        )
        oi = jax.lax.dynamic_update_slice(oi, gidx[:, None], (0, k))
        os = jax.lax.dynamic_update_slice(os, gv[:, None], (0, k))
        oh = jax.lax.dynamic_update_slice(oh, ghv[:, None], (0, k))
        # gather ONLY the winning block, re-reduce it under the extracted
        # threshold (keep entries strictly lex-below (gv, ghv, gidx)), and
        # fold the fresh triple back with a broadcast select over the
        # [P, B] stats — per-step work stays O(P·C), and no .at scatter
        # (XLA CPU scatters serialize per row and dominated the step)
        cols_ = (gb * C)[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        gs = jnp.take_along_axis(skey, cols_, 1)
        gh2 = jnp.take_along_axis(hash_, cols_, 1)
        gi2 = jnp.take_along_axis(idx, cols_, 1)
        keep = (gs < gv[:, None]) | ((gs == gv[:, None]) & (
            (gh2 < ghv[:, None])
            | ((gh2 == ghv[:, None]) & (gi2 > gidx[:, None]))))
        nv, nh, ni = block_reduce(
            jnp.where(keep, gs, _I32_MIN)[:, None, :],
            gh2[:, None, :], gi2[:, None, :],
        )
        win = barange == gb[:, None]
        bval = jnp.where(win, nv, bval)
        bh = jnp.where(win, nh, bh)
        bidx = jnp.where(win, ni, bidx)
        return bval, bh, bidx, oi, os, oh

    init = (bval, bh, bidx, jnp.zeros((P, K), jnp.int32),
            jnp.full((P, K), _I32_MIN), jnp.full((P, K), -1, jnp.int32))
    *_, oi, os, oh = jax.lax.fori_loop(0, K, step, init)
    return oi, os, oh


def _remap_rows(sparse_idx: jnp.ndarray, pend_rows: jnp.ndarray) -> jnp.ndarray:
    """Map sparse per-task row indices (affinity/preference corrections)
    into pending-bucket slots; rows outside the bucket park at -1 (their
    corrections can only affect non-pending rows, which the head masks)."""
    eq = sparse_idx[:, None] == pend_rows[None, :]          # [Ks, P]
    hit = jnp.any(eq, axis=1) & (sparse_idx >= 0)
    slot = jnp.argmax(eq, axis=1).astype(jnp.int32)
    return jnp.where(hit, slot, -1)


def pend_view(snap: DeviceSnapshot, pend_rows: jnp.ndarray) -> DeviceSnapshot:
    """``snap`` with the task axis gathered to the [P] pending bucket
    (``pend_rows`` global task rows, -1 padding).  Per-element math over
    the view equals the same rows of the full matrices — the bit-exactness
    contract shared with the shard_map block view.  Padding slots carry
    row 0's data with valid/pending forced off, so every consumer masks
    them out."""
    T = snap.task_req.shape[0]
    safe = jnp.clip(pend_rows, 0, T - 1)
    live = pend_rows >= 0

    def g(arr):
        return arr[safe]

    return snap._replace(
        task_req=g(snap.task_req),
        task_resreq=g(snap.task_resreq),
        task_job=g(snap.task_job),
        task_prio=g(snap.task_prio),
        task_creation=g(snap.task_creation),
        task_status=g(snap.task_status),
        task_valid=g(snap.task_valid) & live,
        task_pending=g(snap.task_pending) & live,
        task_best_effort=g(snap.task_best_effort),
        task_sel_bits=g(snap.task_sel_bits),
        task_sel_impossible=g(snap.task_sel_impossible),
        task_tol_bits=g(snap.task_tol_bits),
        task_node=g(snap.task_node),
        task_critical=g(snap.task_critical),
        task_needs_host=g(snap.task_needs_host),
        task_aff_idx=_remap_rows(snap.task_aff_idx, pend_rows),
        task_pref_idx=_remap_rows(snap.task_pref_idx, pend_rows),
    )


def compact_candidates(view_p: DeviceSnapshot, pend_rows: jnp.ndarray,
                       idle0: jnp.ndarray, releasing0: jnp.ndarray,
                       quanta: jnp.ndarray, config: AllocateConfig, n0=0):
    """The per-solve candidate build over one node block: rank the block's
    nodes per bucket row by the exact (score_static, tie_hash, index) key
    at the CYCLE-START budgets and keep the top ``config.topk``.

    Returns ``(idx, skey, hash, n_feas, score_static, tie_hash)`` — the
    [P, K] table triple in descending key order, the per-row feasible
    count (the truncation test), and the [P, N_blk] score/hash planes
    (the single-device path reuses them for the exhaustion re-entry).
    ``n0`` offsets node indices and the tie hash to GLOBAL coordinates for
    shard-local blocks, exactly like the shard_map round head."""
    K = config.topk
    P = view_p.task_req.shape[0]
    N_blk = idle0.shape[0]
    safe_rows = jnp.maximum(pend_rows, 0)
    tie_hash = tie_break_hash_rows(
        safe_rows, jnp.arange(N_blk, dtype=jnp.int32) + n0
    )
    static_ok = static_predicates(view_p)
    score = score_matrix(view_p, config.weights)
    score_static = jnp.where(static_ok, score, NEG)
    fit0 = fits(view_p.task_req, idle0, quanta)
    fit0_rel = jax.lax.cond(
        jnp.any(releasing0 > 0.0),
        lambda rel: fits(view_p.task_req, rel, quanta),
        lambda rel: jnp.zeros_like(fit0),
        releasing0,
    )
    masked0 = jnp.where(fit0 | fit0_rel, score_static, NEG)
    skey0 = f32_sort_key(masked0)
    neg_key = _neg_key()
    # dtype pinned: the count rides the shard merge's i32 payload and must
    # stay i32 under the jaxpr audit's x64 probe
    n_feas = jnp.sum(skey0 > neg_key, axis=1, dtype=jnp.int32)
    idx0 = jnp.broadcast_to(
        jnp.arange(N_blk, dtype=jnp.int32)[None, :] + n0, (P, N_blk)
    )
    ki, ks, kh = lex_topk(skey0, tie_hash, idx0, K)
    return ki, ks, kh, n_feas, score_static, tie_hash


def make_compact_head(cand_idx, cand_skey, cand_hash, truncated,
                      req_p, quanta, N: int, fallback_fn):
    """Build the compacted round head: ``head(idle, releasing, pending) ->
    (best, has, chose_idle, exhausted_count)``, all [P]-axis — the
    compacted solve runs :func:`allocate_rounds` NATIVELY on the bucket
    view (its task axis is shape-generic; the what-if probe's gang-axis
    solve is the precedent), so the per-round [T]-sized sorts and segment
    scans of the rank/gate/conflict machinery shrink to [P] too.

    Per round the head gathers ONLY the K candidate nodes' live budgets
    ([P, K, R]), two-key-argmaxes the fitting entries' stored keys (exact
    by the module invariant), and re-enters ``fallback_fn(idle, releasing,
    pending_exh) -> (best_p, has_p, chose_p)`` — the full-matrix head over
    the bucket — for exhausted rows only, under a lax.cond that steady
    rounds never execute."""
    valid = cand_skey > _neg_key()
    safe_idx = jnp.clip(cand_idx, 0, N - 1)

    def head(idle, releasing, pending):
        idle_k = idle[safe_idx]                              # [P, K, R]
        fit_idle = jnp.all(req_p[:, None, :] <= idle_k + quanta, axis=-1)
        fit_rel = jax.lax.cond(
            jnp.any(releasing > 0.0),
            lambda rel: jnp.all(
                req_p[:, None, :] <= rel[safe_idx] + quanta, axis=-1
            ),
            lambda rel: jnp.zeros_like(fit_idle),
            releasing,
        )
        fit_k = valid & (fit_idle | fit_rel) & pending[:, None]
        sk = jnp.where(fit_k, cand_skey, _I32_MIN)
        best_sk = jnp.max(sk, axis=1)
        hk = jnp.where(sk >= best_sk[:, None], cand_hash, -1)
        # first position among (key, hash) ties = lowest node index — the
        # table stores full ties in ascending index order
        pos = jnp.argmax(hk, axis=1)
        has_p = jnp.any(fit_k, axis=1)
        best_p = jnp.take_along_axis(cand_idx, pos[:, None], 1)[:, 0]
        chose_p = jnp.take_along_axis(fit_idle, pos[:, None], 1)[:, 0]
        exh_p = pending & ~has_p & truncated

        def with_fallback(_):
            fb_best, fb_has, fb_chose = fallback_fn(idle, releasing, exh_p)
            return (
                jnp.where(exh_p, fb_best, best_p),
                jnp.where(exh_p, fb_has, has_p),
                jnp.where(exh_p, fb_chose, chose_p),
            )

        best_p2, has_p2, chose_p2 = jax.lax.cond(
            jnp.any(exh_p), with_fallback,
            lambda _: (best_p, has_p, chose_p), None,
        )
        # dtype pinned: the count rides a while-loop carry, which must stay
        # i32 under the jaxpr audit's x64 probe
        return best_p2, has_p2, chose_p2, jnp.sum(exh_p, dtype=jnp.int32)

    return head


def scatter_bucket_result(res: AllocateResult, pend_rows: jnp.ndarray,
                          T: int) -> AllocateResult:
    """Re-express a bucket-axis solve result on the full [T] task axis:
    assigned/pipelined scatter at the bucket's global rows (padding slots
    land in the dropped T slot of a [T+1] buffer — the segment-sum idiom;
    negative indices must never reach a scatter).  Every other field is
    already global ([N, R] ledgers, [J]/[Q] aggregates, scalars).

    Exactness of the bucket-axis solve itself: every schedulable-pending
    row is IN the bucket (the dispatch guarantees it), non-bucket rows can
    never bid or place, their zero contributions drop out of every f32
    prefix/segment sum exactly (x + 0.0 == x), and the bucket preserves
    ascending global row order (np.flatnonzero), so every stable-sort tie
    in the rank machinery resolves identically to the full program."""
    scat = jnp.where(pend_rows >= 0, pend_rows, T)
    assigned = jnp.full(T + 1, -1, jnp.int32).at[scat].set(res.assigned)[:T]
    pipelined = jnp.zeros(T + 1, bool).at[scat].set(res.pipelined)[:T]
    return res._replace(assigned=assigned, pipelined=pipelined)


def make_bucket_fallback(view_p: DeviceSnapshot, score_static_p, tie_hash_p,
                         quanta):
    """The exhaustion re-entry for a bucket whose full score/hash planes
    are at hand: the full-matrix head restricted to the [P] bucket —
    literally :func:`round_head_parts`' masked two-key argmax over the
    [P, N] planes, masked to the exhausted rows."""
    req_p = view_p.task_req

    def fallback(idle, releasing, pending_exh):
        fit_idle = fits(req_p, idle, quanta)
        fit_rel = jax.lax.cond(
            jnp.any(releasing > 0.0),
            lambda rel: fits(req_p, rel, quanta),
            lambda rel: jnp.zeros_like(fit_idle),
            releasing,
        )
        masked = jnp.where(
            (fit_idle | fit_rel) & pending_exh[:, None], score_static_p, NEG
        )
        best_p, has_p = _best_node(masked, tie_hash_p)
        chose_p = jnp.take_along_axis(fit_idle, best_p[:, None], 1)[:, 0]
        return best_p, has_p, chose_p

    return fallback


@partial(jax.jit, static_argnames=("config",))
def allocate_topk_solve(snap: DeviceSnapshot, pend_rows: jnp.ndarray,
                        config: AllocateConfig) -> AllocateResult:
    """The compacted allocate solve: identical outputs to
    :func:`allocate_solve` (the KB_TOPK=0 oracle), computed on the [P]
    pending bucket × [P, K] candidate table instead of the [T, N]
    matrices.  ``pend_rows`` [P] i32 must cover every schedulable-pending
    task row (-1 padding); ``config.topk`` = K > 0.  The dispatch
    (actions/allocate.py) owns bucket/K selection and the full-path
    fallbacks for shapes where compaction cannot win."""
    T = snap.task_req.shape[0]
    N = snap.node_idle.shape[0]
    K = config.topk
    view_p = pend_view(snap, pend_rows)
    ki, ks, kh, n_feas, score_static_p, tie_hash_p = compact_candidates(
        view_p, pend_rows, snap.node_idle, snap.node_releasing,
        snap.quanta, config,
    )
    truncated = n_feas > K
    fallback = make_bucket_fallback(
        view_p, score_static_p, tie_hash_p, snap.quanta
    )
    head = make_compact_head(
        ki, ks, kh, truncated, view_p.task_req, snap.quanta, N, fallback,
    )
    # the rounds run NATIVELY on the bucket view — the rank / queue-gate /
    # conflict machinery's per-round sorts and segment scans all shrink
    # from [T] to [P] (see scatter_bucket_result for the exactness story)
    res = allocate_rounds(
        view_p, config, None, snap.node_idle, snap.node_releasing,
        snap.node_used, compact_head=head,
    )
    return scatter_bucket_result(res, pend_rows, T)


# ==========================================================================
# Warm-started incremental allocate (KB_WARM) — the cross-cycle candidate
# table carry + assignment repair
# ==========================================================================
#
# KB_TOPK made the ROUNDS O(P·K), but the candidate-table BUILD still
# re-ranks every bucket row against every node once per solve — the last
# O(P·N) cost in the cycle's dominant phase.  The warm path promotes the
# table to a PERSISTENT cross-cycle structure: the dispatch carries the
# [P, W] table on device between solves and each cycle only
#
#   re-ranks the INVALIDATED rows  (new/bucket-shifted rows, rows whose
#     own task features moved, eroded rows — a sub-bucket
#     compact_candidates at a fixed rung, not [P, N]);
#   merges the CHANGED NODES' fresh keys ([P, C] — C = the node rows the
#     resident scatter deltas moved since the last solve) into every
#     carried row.
#
# Exactness (why the carried table keeps the compact-head invariant —
# "exact descending lex prefix of the currently-cycle-start-feasible
# nodes"):
#
#   INV: every node ABSENT from a row's valid entries either changed since
#   the last refresh (so its fresh key is in this merge), or its key —
#   unchanged, because ALL of its key inputs are unchanged — is lex-BELOW
#   the row's last valid entry θ.
#
#   The merge removes the changed nodes' stale entries, inserts their
#   fresh keys, re-extracts the top W, and CUTS every merged entry that
#   falls lex-below θ: above θ the merged set provably contains every
#   node (unchanged ones were already stored; changed ones are fresh), so
#   the kept prefix is the exact current top-J — and the cut re-
#   establishes INV for the next cycle (cut entries are ≥ the extraction's
#   dropped ones, so everything absent is below the new θ).  A cut or an
#   extraction overflow marks the row TRUNCATED; a truncated row whose
#   valid entries all die in-round re-enters the full-matrix head the
#   SAME round (the KB_TOPK fallback, with the [P, N] planes computed
#   lazily inside the cond), so bit-exactness never depends on the table
#   being deep — only on it being an exact prefix.  Rows whose prefix
#   erodes below the nominal K report in the `eroded` output and the host
#   planner re-ranks them next cycle.
#
#   Cross-cycle soundness rides on the same two facts as KB_TOPK: budgets
#   only SHRINK within a solve (the table stays an upper bound all
#   rounds), and between solves state moves only at rows the resident
#   scatters (api/resident.py) know about — which is exactly where the
#   invalidation comes from.  KB_WARM=0 keeps the per-solve cold build as
#   the bit-exactness oracle, same contract as KB_TOPK=0 / KB_SHARD_MAP=0.


def node_view(snap: DeviceSnapshot, node_rows: jnp.ndarray) -> DeviceSnapshot:
    """``snap`` with the node axis gathered to ``node_rows`` (-1 padding →
    dead columns: node_valid forced off so static predicates fail).  The
    per-element contract of the shard_map block view, applied to an
    arbitrary node subset: every live column of the view equals the same
    column of the full matrices, which is what makes the warm merge's
    fresh [P, C] keys bit-equal to a full rebuild's."""
    N = snap.node_idle.shape[0]
    safe = jnp.clip(node_rows, 0, N - 1)
    live = node_rows >= 0

    def g(arr):
        return arr[safe]

    def g1(arr):  # [K?, N] sparse rows — node axis is axis 1
        return arr[:, safe]

    return snap._replace(
        node_idle=g(snap.node_idle),
        node_releasing=g(snap.node_releasing),
        node_used=g(snap.node_used),
        node_alloc=g(snap.node_alloc),
        node_valid=g(snap.node_valid) & live,
        node_sched=g(snap.node_sched),
        node_label_bits=g(snap.node_label_bits),
        node_taint_bits=g(snap.node_taint_bits),
        task_aff_mask=g1(snap.task_aff_mask),
        task_pref_node=g1(snap.task_pref_node),
        task_pref_pod=g1(snap.task_pref_pod),
    )


def fresh_block_skey(view_pc: DeviceSnapshot, quanta: jnp.ndarray,
                     config: AllocateConfig) -> jnp.ndarray:
    """[P, C] sort keys of the changed-node columns at the CURRENT
    cycle-start budgets — exactly ``compact_candidates``' key derivation
    restricted to a node subset (``view_pc`` = the pend view node-gathered
    at the changed rows).  The zero-releasing skip mirrors the shard_map
    block head's per-block test: exact for solver-pending rows either
    way (see local_round_head)."""
    static_ok = static_predicates(view_pc)
    score = score_matrix(view_pc, config.weights)
    score_static = jnp.where(static_ok, score, NEG)
    fit0 = fits(view_pc.task_req, view_pc.node_idle, quanta)
    fit0_rel = jax.lax.cond(
        jnp.any(view_pc.node_releasing > 0.0),
        lambda rel: fits(view_pc.task_req, rel, quanta),
        lambda rel: jnp.zeros_like(fit0),
        view_pc.node_releasing,
    )
    return f32_sort_key(jnp.where(fit0 | fit0_rel, score_static, NEG))


#: fresh candidates inserted per row per merge — rows where more changed
#: nodes belong in the top-W are φ-cut: still EXACT (the cut re-founds
#: the prefix invariant and marks the row truncated), just thinner, and
#: the spare-fill refresh budget re-ranks them on rung padding slots.
#: E prices the merge's tournament (its extraction steps are the merge's
#: dominant cost at CPU dispatch granularity), so it is sized to the
#: steady-state insertion rate (~W·C/N), not the burst worst case
FRESH_E = 8


def _lex_ge(s, h, i, ts, th, ti):
    """Entry (s, h, i) lex-at-or-above threshold (ts, th, ti) under the
    table order (skey desc, hash desc, idx asc)."""
    return (s > ts) | ((s == ts) & ((h > th) | ((h == th) & (i <= ti))))


def warm_refresh_table(t_idx, t_skey, t_hash, t_trunc, row_map, rows_m,
                       changed_nodes, skey_c, hash_c,
                       ri, rs, rh, trunc_i, rerank_slots,
                       N: int, k_min: int):
    """One cycle's table maintenance, in exact integer arithmetic over the
    [M] live prefix (M = ``row_map``'s length — the merge rung; rows past
    M are bucket padding and stay empty by induction): permute the carried
    table into the new bucket order (``row_map`` — old slot per new slot,
    -1 = fresh row), remove the changed nodes' stale entries, INSERT their
    fresh keys, θ/φ-cut, and overwrite the re-ranked sub-bucket's rows
    with their fresh [Pi, W] builds at ``rerank_slots``.

    The insert is a COUNTING merge, not a re-extraction: only the top
    FRESH_E fresh candidates per row are ranked (a short tournament over
    [M, C]), each surviving entry's merged position is a comparison count
    (kept-stored are already sorted; [M, W, E] lex compares rank both
    sides), and two rank-scatters place everything — per-solve cost is
    O(E) extraction steps instead of O(W), which is what lets a warm
    cycle undercut the cold build's K-step extraction at all.  Exactness:
    fresh candidates beyond the top E are all lex-below the E-th extracted
    key φ (a strict bound — indices are unique), so cutting the merged
    table at lexmax(θ, φ) keeps it an exact prefix; cut rows mark
    truncated and the erosion flag re-ranks them next cycle.

    Returns ``(idx, skey, hash, trunc, eroded)`` — the refreshed FULL
    [P, W] table (rows past M carried through untouched) plus the [P]
    erosion flag (truncated AND fewer than ``k_min`` valid entries)."""
    P, W = t_skey.shape
    M = row_map.shape[0]
    E = FRESH_E
    neg = _neg_key()
    BIG = jnp.int32(1 << 30)
    # ---- 1. permute the live prefix into the new bucket order --------
    live = row_map >= 0
    safe = jnp.clip(row_map, 0, M - 1)
    idx = jnp.where(live[:, None], t_idx[:M][safe], 0)
    skey = jnp.where(live[:, None], t_skey[:M][safe], _I32_MIN)
    hsh = jnp.where(live[:, None], t_hash[:M][safe], -1)
    # a fresh (carried-in) row starts TRUNCATED: its empty table claims
    # nothing, so until the re-rank overwrite below fills it, the head
    # must treat it as incomplete (exhaustion-fallback territory) — the
    # planner always re-ranks fresh rows, but correctness must not
    # depend on that scheduling
    trunc = jnp.where(live, t_trunc[:M][safe], True)
    # ---- 2. θ per row: the last valid entry, PRE-removal -------------
    valid = skey > neg
    vcnt = jnp.sum(valid, axis=1, dtype=jnp.int32)
    last = jnp.clip(vcnt - 1, 0, W - 1)[:, None]
    has_any = vcnt > 0
    th_s = jnp.where(has_any, jnp.take_along_axis(skey, last, 1)[:, 0], neg)
    th_h = jnp.where(
        has_any, jnp.take_along_axis(hsh, last, 1)[:, 0],
        jnp.int32(2 ** 31 - 1),
    )
    th_i = jnp.where(
        has_any, jnp.take_along_axis(idx, last, 1)[:, 0], jnp.int32(-1)
    )
    # ---- 3. remove the changed nodes' stale entries ------------------
    changed_mask = jnp.zeros(N + 1, bool).at[
        jnp.where(changed_nodes >= 0, changed_nodes, N)
    ].set(True, mode="drop")[:N]
    keep = valid & ~changed_mask[jnp.clip(idx, 0, N - 1)]
    skey = jnp.where(keep, skey, _I32_MIN)
    # ---- 4. top-E of the fresh block (short tournament) --------------
    C = changed_nodes.shape[0]
    idx_c = jnp.broadcast_to(changed_nodes[None, :], (M, C))
    fresh_ok = (changed_nodes >= 0)[None, :] & (skey_c > neg)
    fi, fs, fh = lex_topk3(
        jnp.where(fresh_ok, skey_c, _I32_MIN), hash_c, idx_c, E
    )
    f_valid = fs > neg
    # φ: the E-th extracted fresh key — every non-extracted fresh
    # candidate is strictly lex-below it (indices unique)
    phi_live = f_valid[:, E - 1]
    ph_s, ph_h, ph_i = fs[:, E - 1], fh[:, E - 1], fi[:, E - 1]
    # ---- 5. gather-based two-sorted-list merge -----------------------
    # kept-stored entries keep their relative (sorted) order and the
    # fresh top-E is sorted by extraction; merged output j = lexmax of
    # the two heads after consuming j entries.  Everything is gathers +
    # small broadcast counts — XLA CPU scatters serialize per row and
    # dominated the first (rank-scatter) formulation of this merge.
    kp = jnp.cumsum(keep.astype(jnp.int32), axis=1) - keep
    kept_cnt = jnp.sum(keep, axis=1, dtype=jnp.int32)
    jcols = jnp.arange(W, dtype=jnp.int32)[None, :]
    # position (in stored-entry coordinates) of the j-th KEPT entry — one
    # [M, W+1] inverse scatter instead of a [M, W, W] compare+argmax
    kth_kept = jnp.zeros((M, W + 1), jnp.int32).at[
        jnp.arange(M)[:, None], jnp.where(keep, kp, W)
    ].set(
        jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (M, W)),
        mode="drop",
    )[:, :W]                                             # [M, W]
    # fresh rank of each top-E entry among the merged output: its own
    # position + kept-stored entries lex-above it
    gt = _lex_ge(          # stored strictly above fresh (no equal keys)
        skey[:, :, None], hsh[:, :, None], idx[:, :, None],
        fs[:, None, :], fh[:, None, :], fi[:, None, :],
    )
    fresh_rank = jnp.arange(E, dtype=jnp.int32)[None, :] + jnp.sum(
        gt & keep[:, :, None], axis=1, dtype=jnp.int32
    )
    # fresh entries consumed before output j → the two head cursors
    b = jnp.sum(
        f_valid[:, None, :] & (fresh_rank[:, None, :] < jcols[:, :, None]),
        axis=2, dtype=jnp.int32,
    )                                                    # [M, W]
    a = jcols - b

    def g(arr, cur, ok, fill):
        v = jnp.take_along_axis(arr, jnp.clip(cur, 0, arr.shape[1] - 1), 1)
        return jnp.where(ok, v, fill)

    s_ok = a < kept_cnt[:, None]
    sp = g(kth_kept, a, s_ok, 0)
    hs_s = g(skey, sp, s_ok, _I32_MIN)
    hs_h = g(hsh, sp, s_ok, jnp.int32(-1))
    hs_i = g(idx, sp, s_ok, BIG)
    f_ok = (b < E) & jnp.take_along_axis(
        f_valid, jnp.clip(b, 0, E - 1), 1)
    hf_s = g(fs, b, f_ok, _I32_MIN)
    hf_h = g(fh, b, f_ok, jnp.int32(-1))
    hf_i = g(fi, b, f_ok, BIG)
    take_f = f_ok & ~(s_ok & _lex_ge(hs_s, hs_h, hs_i, hf_s, hf_h, hf_i))
    ns = jnp.where(take_f, hf_s, hs_s)
    nh = jnp.where(take_f, hf_h, hs_h)
    ni = jnp.where(take_f, hf_i, hs_i)
    overflow = (
        kept_cnt + jnp.sum(f_valid, axis=1, dtype=jnp.int32)
    ) > W
    # ---- 6. cut at lexmax(θ, φ): above both, the merged set provably
    # contains every node, so the kept prefix is exact -----------------
    ge = _lex_ge(ns, nh, ni, th_s[:, None], th_h[:, None], th_i[:, None])
    ge &= ~phi_live[:, None] | _lex_ge(
        ns, nh, ni, ph_s[:, None], ph_h[:, None], ph_i[:, None]
    )
    cut_any = jnp.any((ns > neg) & ~ge, axis=1)
    ns = jnp.where((ns > neg) & ge, ns, _I32_MIN)
    # a LIVE φ means non-extracted fresh candidates may exist below it —
    # the table can no longer claim completeness even when nothing was
    # cut (an empty-but-complete row gaining > E feasible changed nodes
    # keeps every merged entry above both thresholds, yet the 9th+ fresh
    # candidates are absent: without trunc the exhaustion fallback would
    # never re-enter for them)
    trunc = trunc | cut_any | overflow | phi_live
    # ---- 7. overwrite the re-ranked sub-bucket's rows ----------------
    scat = jnp.where(rerank_slots >= 0, rerank_slots, M)

    def over(dst, upd):
        pad = jnp.zeros((1,) + dst.shape[1:], dst.dtype)
        return jnp.concatenate([dst, pad], 0).at[scat].set(
            upd, mode="drop"
        )[:M]

    ni = over(ni, ri)
    ns = over(ns, rs)
    nh = over(nh, rh)
    trunc = over(trunc, trunc_i)
    # ---- 8. erosion flag + full-table assembly -----------------------
    # STAGGERED thresholds: θ-cuts thin every carried row at roughly the
    # same per-cycle rate, so a single shared floor would mature whole
    # re-rank cohorts at once — a periodic rung-spiking wave (measured:
    # a quiet er≈100 steady state punctuated by er≈1100 spikes).  Each
    # row instead refreshes at its own hashed depth in [k_min, W), which
    # spreads the cohort across the thinning trajectory; the flag is a
    # scheduling signal only (a fully eroded table still answers exactly
    # via the exhaustion fallback), so the stagger cannot affect results.
    vcnt2 = jnp.sum(ns > neg, axis=1, dtype=jnp.int32)
    spread = jnp.int32(max(W - k_min, 1))
    jitter = jax.lax.shift_right_logical(
        jnp.maximum(rows_m, 0) * jnp.int32(_H1), jnp.int32(16)
    ) % spread
    eroded = trunc & (vcnt2 < k_min + jitter)
    upd = jax.lax.dynamic_update_slice
    return (
        upd(t_idx, ni, (0, 0)),
        upd(t_skey, ns, (0, 0)),
        upd(t_hash, nh, (0, 0)),
        upd(t_trunc, trunc, (0,)),
        upd(jnp.zeros(P, bool), eroded, (0,)),
    )


def make_lazy_bucket_fallback(view_p: DeviceSnapshot, pend_rows, quanta,
                              config: AllocateConfig):
    """The warm path's exhaustion re-entry: the full-matrix head over the
    bucket with the [P, N] score/hash planes computed INSIDE the cond —
    the whole point of the carry is that steady cycles never build those
    planes, so the fallback must not hoist them (the sharded compacted
    body's fallback is the precedent)."""
    safe_rows = jnp.maximum(pend_rows, 0)
    N = view_p.node_idle.shape[0]

    def fallback(idle, releasing, pending_exh):
        static_ok = static_predicates(view_p)
        score = score_matrix(view_p, config.weights)
        ss = jnp.where(static_ok, score, NEG)
        tie = tie_break_hash_rows(
            safe_rows, jnp.arange(N, dtype=jnp.int32)
        )
        return make_bucket_fallback(view_p, ss, tie, quanta)(
            idle, releasing, pending_exh
        )

    return fallback


def _warm_allocate_solve(snap: DeviceSnapshot, pend_rows,
                         t_idx, t_skey, t_hash, t_trunc,
                         row_map, changed_nodes, rerank_rows, rerank_slots,
                         config: AllocateConfig, k_min: int):
    """The warm-started compacted allocate solve: identical outputs to
    :func:`allocate_topk_solve` (and therefore to the KB_TOPK=0 full
    program) computed against the CARRIED candidate table, refreshed
    in-program by :func:`warm_refresh_table`.  ``config.topk`` is the
    STORED width W (the dispatch carries W = K + WARM_WIDTH_MARGIN so
    θ/φ-cut erosion rarely reaches the refresh floor); ``k_min`` is that
    floor (the dispatch passes K/4 — a thin table still answers exactly,
    so the floor trades re-rank traffic against fallback probability).

    Returns ``(AllocateResult, (idx, skey, hash, trunc), eroded)`` — the
    refreshed table stays on device for the next cycle's carry (the jit
    wrapper donates the stale table buffers off-CPU)."""
    T = snap.task_req.shape[0]
    N = snap.node_idle.shape[0]
    M = row_map.shape[0]
    view_p = pend_view(snap, pend_rows)
    # fresh keys for the changed-node columns over the [M] live prefix
    # (row_map's length IS the merge rung — the planner sizes it over the
    # live bucket rows so padding rows pay nothing), at cycle-start state
    rows_m = pend_rows[:M]
    view_pm = pend_view(snap, rows_m)
    view_pc = node_view(view_pm, changed_nodes)
    skey_c = fresh_block_skey(view_pc, snap.quanta, config)
    hash_c = tie_break_hash_rows(
        jnp.maximum(rows_m, 0), jnp.maximum(changed_nodes, 0)
    )
    # full re-rank of the invalidated sub-bucket (compact_candidates at
    # the rerank rung — the only [·, N] work of a steady warm cycle)
    view_i = pend_view(snap, rerank_rows)
    ri, rs, rh, n_feas, _ss, _tie = compact_candidates(
        view_i, rerank_rows, snap.node_idle, snap.node_releasing,
        snap.quanta, config,
    )
    ni, ns, nh, trunc, eroded = warm_refresh_table(
        t_idx, t_skey, t_hash, t_trunc, row_map, rows_m, changed_nodes,
        skey_c, hash_c, ri, rs, rh, n_feas > config.topk, rerank_slots,
        N, k_min,
    )
    fallback = make_lazy_bucket_fallback(view_p, pend_rows, snap.quanta,
                                         config)
    head = make_compact_head(
        ni, ns, nh, trunc, view_p.task_req, snap.quanta, N, fallback,
    )
    res = allocate_rounds(
        view_p, config, None, snap.node_idle, snap.node_releasing,
        snap.node_used, compact_head=head,
    )
    return scatter_bucket_result(res, pend_rows, T), (ni, ns, nh, trunc), eroded


#: argument positions of the carried table buffers — donated off-CPU so
#: the refresh writes in place (the resident scatter's donation contract)
WARM_TABLE_ARGNUMS = (2, 3, 4, 5)

_WARM_SOLVE = None


def warm_solve_fn():
    """The shared jitted warm solve — module-level memo (the _scatter_fn
    idiom): donation is backend-dependent, so the wrapper is built on
    first use, and every cache instance reuses one compiled
    specialization set per (shape, config) key."""
    global _WARM_SOLVE
    if _WARM_SOLVE is None:
        donate = (
            () if jax.default_backend() == "cpu" else WARM_TABLE_ARGNUMS
        )
        _WARM_SOLVE = jitstats.register(
            "warm_allocate_solve",
            jax.jit(_warm_allocate_solve,
                    static_argnames=("config", "k_min"),
                    donate_argnums=donate),
        )
    return _WARM_SOLVE


def warm_allocate_solve(snap, pend_rows, table, plan, config, k_min):
    """Dispatch-facing warm solve: ``table`` = the carried (idx, skey,
    hash, trunc) device arrays, ``plan`` = the host planner's (row_map,
    changed_nodes, rerank_rows, rerank_slots) int32 arrays."""
    t_idx, t_skey, t_hash, t_trunc = table
    row_map, changed, rr, rslots = plan
    return warm_solve_fn()(
        snap, pend_rows, t_idx, t_skey, t_hash, t_trunc,
        row_map, changed, rr, rslots, config=config, k_min=k_min,
    )


@jax.jit
def failure_histogram_bucket_solve(snap: DeviceSnapshot,
                                   pend_rows) -> jnp.ndarray:
    """:func:`failure_histogram_solve` computed on the [P] pending bucket
    instead of re-walking [T, N]: every consumer reads histogram rows only
    for unplaced PENDING tasks, all of which the dispatch's bucket covers,
    and each task's row is a node-axis reduction independent of the other
    task rows — so the bucket rows are bit-equal to the full program's and
    the non-bucket rows (never read) scatter back as zeros."""
    from kube_batch_tpu.ops.feasibility import (
        FeasibilityMasks,
        N_REASONS,
        failure_histogram,
    )

    T = snap.task_req.shape[0]
    view_p = pend_view(snap, pend_rows)
    static_ok = static_predicates(view_p)
    fit0_idle = fits(view_p.task_req, snap.node_idle, snap.quanta)
    fit0_rel = fits(view_p.task_req, snap.node_releasing, snap.quanta)
    h = failure_histogram(
        view_p,
        FeasibilityMasks(
            static_ok, fit0_idle, fit0_rel,
            static_ok & (fit0_idle | fit0_rel),
        ),
    )
    scat = jnp.where(pend_rows >= 0, pend_rows, T)
    return jnp.zeros((T + 1, N_REASONS), jnp.int32).at[scat].set(h)[:T]


# retrace accounting (utils/jitstats): the bench asserts these stay flat
# across steady-state cycles — shape-bucketed snapshots must hit the jit
# cache every cycle after warmup
jitstats.register("allocate_solve", allocate_solve)
jitstats.register("allocate_topk_solve", allocate_topk_solve)
jitstats.register("failure_histogram_solve", failure_histogram_solve)
jitstats.register("failure_histogram_bucket_solve",
                  failure_histogram_bucket_solve)

"""Batched what-if / admission probe — the query plane's solve.

The scheduler's write path answers "where does this gang go?" by committing
a Statement; the serve/ query plane answers "where WOULD it go?" without
committing anything.  This kernel scores B speculative gangs against the
device-resident snapshot columns in ONE dispatch: each gang is vmapped
through the SAME solve machinery the committed cycle runs —
:func:`ops.assignment.allocate_rounds` for placement and the
:mod:`ops.eviction` victim machinery for the hypothetical preemption set —
restricted to a task axis of just the gang's members.

Oracle-exactness contract (the tests' bit-match invariant): on a frozen
snapshot, a gang reported feasible at nodes X must bind to exactly X when
actually submitted.  Three properties make that structural rather than
approximate:

- the probe view's per-element inputs (requests, selector/toleration bits,
  queue/job rows, the proportion ``queue_request`` bump the real submission
  would cause) equal what the committed snapshot-with-gang would carry at
  the gang's rows;
- the tie-break hash is computed at the GLOBAL task rows the gang would
  occupy on submission (``ColumnStore.peek_task_rows`` — the row allocator
  is deterministic against a frozen cache), via the shared
  :func:`ops.assignment.tie_break_hash_rows`;
- the round machinery is the same code: ``allocate_rounds`` with a [G, N]
  head, and the eviction probe mirrors ``evict_rounds``'s victim
  selection / caps / coverage lines at full task-axis scale.

Probe semantics: the gang is solved ALONE against the frozen snapshot
(admission-probe semantics).  Other pending work that lands in the same
real cycle can still out-compete the gang at submission time — that race is
inherent to any what-if and is what the lease's ``snapshot_version`` lets
clients reason about.

Modeled scope: the probe answers for the allocate/preempt solve plus the
enqueue action's FULL admission gate — both the cluster-capability test
(1.2×total − used) and the queue-state ``JobEnqueueable`` veto
(proportion.go:211-233): a gang naming a known queue is also checked
against that queue's capability minus its current allocation, exactly the
test :mod:`actions.enqueue` applies at enqueue time.  Best-effort members
(every semantic request below the resource quanta — including an empty
request map) are never solver-pending, so an all-best-effort gang reports
``feasible: false`` with an empty fit-error histogram even though the
backfill action would bind exactly such pods; the backfill path is the one
remaining documented non-goal (README "Query plane", ROADMAP follow-ons).

Shapes are jit-stable: B is the batcher's fixed batch bucket, G the gang
bucket (padded members have ``valid`` off), so steady-state serving never
retraces (the serving bench asserts it).  Registered in the jaxpr audit so
KBT101-104 gate the probe like the solves.

Sharding: the N-scale blocks (round head, eviction bids, fit-error
histogram, used-capacity sum) are factored out as the ``head`` / ``bid_fn``
/ ``hist_fn`` / ``overcommit_idle`` parameters of
:func:`probe_gang_core`; everything else (the allocate rounds, verdicts,
victim selection) is shared verbatim.  parallel/shard_solve.py substitutes
explicit-collective block versions (local [G, N_loc] compute + the same
two-key pargmax decomposition the sharded solves use) so the shard_map
probe is bit-exact against this single-device program by construction.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kube_batch_tpu.api.snapshot import DeviceSnapshot
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.ops import ordering
from kube_batch_tpu.ops.assignment import (
    NEG,
    AllocateConfig,
    _best_node,
    allocate_rounds,
    round_head_parts,
    tie_break_hash_rows,
)
from kube_batch_tpu.ops.eviction import (
    EvictConfig,
    claim_winners,
    gang_slack0,
    pick_victims,
    victim_running,
)
from kube_batch_tpu.ops.feasibility import (
    FeasibilityMasks,
    failure_histogram,
    fits,
)
from kube_batch_tpu.utils import jitstats

#: the enqueue action's 20% overcommit (enqueue.go:74-81) — the admission
#: verdict mirrors it
OVERCOMMIT_FACTOR = 1.2


class ProbeBatch(NamedTuple):
    """B speculative gangs, padded to the (B, G) buckets.

    Every member of a gang shares the gang's selector/toleration bits and
    priority (the dominant what-if shape: N identical replicas); per-member
    requests still vary via ``req``."""

    req: jnp.ndarray             # [B, G, R] f32 — member requests (InitResreq == Resreq)
    valid: jnp.ndarray           # [B, G] bool — live members (G is padded)
    min_avail: jnp.ndarray       # [B] i32 — gang MinAvailable
    queue: jnp.ndarray           # [B] i32 — queue row; -1 = unknown queue
    prio: jnp.ndarray            # [B] i32
    sel_bits: jnp.ndarray        # [B, W] u32 — required label bits
    sel_impossible: jnp.ndarray  # [B] bool — selector wants a pair no node has
    tol_bits: jnp.ndarray        # [B, Wt] u32 — tolerated taint bits
    min_res: jnp.ndarray         # [B, R] f32 — PodGroup MinResources (admission verdict)
    has_min_res: jnp.ndarray     # [B] bool — absent → unconditional promotion


class ProbeResult(NamedTuple):
    assigned: jnp.ndarray      # [B, G] i32 — node index, -1 unplaced
    pipelined: jnp.ndarray     # [B, G] bool — placed on Releasing budget
    committed: jnp.ndarray     # [B] bool — the gang commit gate's verdict
    feasible: jnp.ndarray      # [B] bool — every valid member placed
    reasons: jnp.ndarray       # [B, G, N_REASONS] i32 — per-member fit-error histogram
    enqueue_ok: jnp.ndarray    # [B] bool — capability gate + queue JobEnqueueable veto
    claim_node: jnp.ndarray    # [B, G] i32 — eviction claim node, -1 (preempt probe)
    victims: jnp.ndarray       # [B, T] bool — hypothetical eviction set
    evict_covered: jnp.ndarray  # [B] bool — eviction claims passed the commit gate


def _gang_view(snap: DeviceSnapshot, req, valid, min_avail, queue, prio,
               sel_bits, sel_impossible, tol_bits) -> DeviceSnapshot:
    """``snap`` with the task axis replaced by the gang's G member rows and
    the speculative job APPENDED as job row J (a fresh row, so no live row
    is ever clobbered; the job's row index is immaterial to the math — it
    only keys segment sums).  ``queue_request`` gets the gang's request
    added at its queue row, exactly what proportion's session open would
    compute after a real submission."""
    G, R = req.shape
    N = snap.node_alloc.shape[0]
    Q = snap.queue_weight.shape[0]
    i32 = jnp.int32

    # BestEffort = empty semantic InitResreq (mirrors build_snapshot); such
    # members are never solver-pending
    from kube_batch_tpu.ops import fairness

    sem = fairness.semantic_mask(R)
    best_effort = jnp.all(req[:, sem] < snap.quanta[sem], axis=1)
    pending = valid & ~best_effort
    # member creation order = submission order (clients POST pods in member
    # order, creation_index ascending) — only the RELATIVE order among the
    # gang's members matters (they are the sole candidates)
    creations = jnp.max(snap.task_creation) + 1 + jnp.arange(G, dtype=i32)

    qsafe = jnp.clip(queue, 0, Q - 1)
    gang_req = jnp.sum(jnp.where(pending[:, None], req, 0.0), axis=0)
    queue_request = snap.queue_request.at[qsafe].add(
        jnp.where(queue >= 0, gang_req, 0.0)
    )

    def app(arr, value, dtype=None):
        row = jnp.asarray(value, arr.dtype if dtype is None else dtype)
        return jnp.concatenate([arr, row[None]])

    J = snap.job_min_avail.shape[0]  # the appended job's row index
    return snap._replace(
        task_req=req,
        task_resreq=req,
        task_job=jnp.full(G, J, i32),
        task_prio=jnp.full(G, prio, i32),
        task_creation=creations,
        task_status=jnp.where(
            valid, i32(int(TaskStatus.PENDING)), i32(int(TaskStatus.UNKNOWN))
        ),
        task_valid=valid,
        task_pending=pending,
        task_best_effort=best_effort,
        task_sel_bits=jnp.broadcast_to(sel_bits[None, :], (G,) + sel_bits.shape),
        task_sel_impossible=jnp.full(G, sel_impossible),
        task_tol_bits=jnp.broadcast_to(tol_bits[None, :], (G,) + tol_bits.shape),
        task_node=jnp.full(G, -1, i32),
        task_critical=jnp.zeros(G, bool),
        task_needs_host=jnp.zeros(G, bool),
        task_aff_idx=jnp.full(1, -1, i32),
        task_aff_mask=jnp.ones((1, N), bool),
        task_pref_idx=jnp.full(1, -1, i32),
        task_pref_node=jnp.zeros((1, N), jnp.float32),
        task_pref_pod=jnp.zeros((1, N), jnp.float32),
        aff_terms=None,  # a what-if gang carries no inter-pod term
        job_min_avail=app(snap.job_min_avail, min_avail),
        job_ready=app(snap.job_ready, 0),
        job_queue=app(snap.job_queue, qsafe),
        job_prio=app(snap.job_prio, prio),
        job_creation=app(snap.job_creation, jnp.max(snap.job_creation) + 1),
        job_valid=app(snap.job_valid, queue >= 0),
        job_schedulable=app(snap.job_schedulable, True),
        job_allocated=jnp.concatenate(
            [snap.job_allocated, jnp.zeros((1, snap.job_allocated.shape[1]),
                                           jnp.float32)]
        ),
        queue_request=queue_request,
    )


def overcommit_idle(snap: DeviceSnapshot) -> jnp.ndarray:
    """[R] — the enqueue action's capability budget: Σ allocatable×1.2 −
    Σ used over valid nodes (enqueue.go:74-81).  Gang-independent, so the
    dispatch computes it ONCE outside the vmap; the shard_map body replaces
    it with a local sum + psum."""
    used = jnp.sum(
        jnp.where(snap.node_valid[:, None], snap.node_used, 0.0), axis=0
    )
    return jnp.maximum(snap.total * OVERCOMMIT_FACTOR - used, 0.0)


def _admission_verdict(idle, quanta, min_res, has_min_res,
                       queue_alloc, queue_cap, queue_known):
    """The enqueue action's admission core for ONE speculative podgroup:
    MinResources ≤ the overcommitted idle budget, tolerating a sub-quantum
    excess (enqueue.go:74-81,102-117; ops/admission.gate_scan's fit test
    with an empty prior admission set — the probe's gang is the only
    candidate), AND the queue-state ``JobEnqueueable`` veto
    (proportion.go:211-233): MinResources plus the queue's current
    allocation must fit the queue's capability, with the same sub-quantum
    tolerance (actions/enqueue.py's ``need − cap < quanta`` test).  An
    unknown or invalid queue skips the veto — the reference treats a
    missing queue attribute as enqueueable.  No MinResources →
    unconditional promotion (enqueue.go:102-105)."""
    fits_cap = jnp.all((min_res <= idle) | (min_res - idle < quanta))
    need = min_res + queue_alloc
    fits_queue = jnp.all((need <= queue_cap) | (need - queue_cap < quanta))
    return ~has_min_res | (fits_cap & (~queue_known | fits_queue))


def _evict_probe(snap: DeviceSnapshot, req, pending, queue, min_avail,
                 assigned0, bid_fn, config: EvictConfig, n_nodes: int):
    """Hypothetical preempt pass for one gang: which nodes would its
    unplaced members claim, and which running victims would be evicted —
    built ON :mod:`ops.eviction`'s shared victim machinery
    (:func:`~ops.eviction.victim_running` / :func:`~ops.eviction.claim_winners`
    / :func:`~ops.eviction.pick_victims`), with claimants restricted to the
    gang's members, so the probe's victim eligibility, reverse-task-order
    selection, gang slack cap, coverage recheck, and commit gate are
    literally the solve's lines rather than a ~90-line mirror of them.  For
    a speculative job every same-queue RUNNING task is another job's — the
    reference's preempt victim filter (preempt.go:113-121) reduces to the
    queue test.

    ``bid_fn(claimant_ok, cap) -> (best, has)`` is the only [G, N]-scale
    block (the masked two-key argmax over per-node evictable capacity);
    the single-device and shard_map paths supply their own (bit-exact)
    versions.  ``n_nodes`` is the GLOBAL node count — every other array
    here is task-axis or [N]-sized replicated math."""
    G = req.shape[0]
    T = snap.task_req.shape[0]
    N = n_nodes
    J = snap.job_min_avail.shape[0]
    Q = snap.queue_weight.shape[0]
    i32 = jnp.int32

    task_queue = snap.job_queue[snap.task_job]
    running = victim_running(snap)
    victim_rank = ordering.multisort_ranks(
        [snap.task_prio, -snap.task_creation]
    )
    slack0 = gang_slack0(snap, config)

    q_ok = (queue >= 0) & (queue < Q)
    claimant_base = pending & (assigned0 < 0) & q_ok
    # one job's claimants: the virtual rank among them is the subrank order
    # (equal priority, ascending creation) — the member index
    rank_g = jnp.arange(G, dtype=i32)
    vn = jnp.clip(snap.task_node, 0, N - 1)

    def round_body(state):
        claim_node, evicted, i, _ = state
        placed = claim_node >= 0

        evict_cnt = jax.ops.segment_sum(
            evicted.astype(i32), snap.task_job, num_segments=J
        )
        slack_rem = slack0 - evict_cnt
        victim_ok = running & ~evicted
        if config.victim_conformance:
            victim_ok &= ~snap.task_critical
        if config.victim_gang:
            victim_ok &= slack_rem[snap.task_job] > 0
        vq = victim_ok & (task_queue == queue)

        # per-node evictable capacity for the gang's queue (the one-hot
        # gather of evict_rounds' per-queue scatter selects exactly this row)
        vreq = jnp.where(vq[:, None], snap.task_resreq, 0.0)
        cap = jax.ops.segment_sum(
            vreq, jnp.where(vq, snap.task_node, N), num_segments=N + 1
        )[:N]                                                    # [N, R]

        claimant_ok = claimant_base & ~placed
        best, has = bid_fn(claimant_ok, cap)
        has &= claimant_ok

        # one winner per node: lowest member rank (the gang's claimant axis
        # plugged into the solve's winner selection)
        is_winner, winner_member, node_has_claim = claim_winners(
            has, best, rank_g, N
        )
        node_req = jnp.where(
            node_has_claim[:, None], req[jnp.maximum(winner_member, 0)],
            jnp.inf,
        )                                                        # [N, R]

        # the solve's victim machinery: reverse-task-order selection, gang
        # slack cap (no proportion budget — preempt semantics), coverage
        vmask = vq & node_has_claim[vn]
        final_take, covered = pick_victims(
            snap, vmask, node_req, node_has_claim, victim_rank, slack_rem,
            config, N,
        )

        new_claim = is_winner & covered[jnp.clip(best, 0, N - 1)]
        claim_node = jnp.where(new_claim, best, claim_node)
        evicted = evicted | final_take
        return (claim_node, evicted, i + 1, jnp.any(new_claim))

    def round_cond(state):
        *_, i, progress = state
        return (i < config.rounds) & progress

    claim_node, evicted, _, _ = jax.lax.while_loop(
        round_cond,
        round_body,
        (jnp.full(G, -1, i32), jnp.zeros(T, bool), i32(0), jnp.bool_(True)),
    )

    if config.gang:
        # preempt commit gate: ready (placements the allocate pass kept) +
        # pipelined claims must reach MinAvailable, else claims revert and
        # victims un-evict (preempt.go:127-137) — one job, so wholesale
        n_ready = jnp.sum((assigned0 >= 0).astype(i32))
        n_pipe = jnp.sum((claim_node >= 0).astype(i32))
        job_ok = (n_ready + n_pipe) >= min_avail
        claim_node = jnp.where(job_ok, claim_node, -1)
        evicted &= job_ok
    else:
        job_ok = jnp.any(claim_node >= 0)
    return claim_node, evicted, job_ok


def probe_gang_core(snap: DeviceSnapshot, view: DeviceSnapshot, g: ProbeBatch,
                    config: AllocateConfig, evict_config: EvictConfig,
                    with_evictions: bool, *, head, bid_fn, hist_fn,
                    oc_idle, idle0, rel0, used0, n_nodes: int) -> ProbeResult:
    """One gang's full probe given the N-scale blocks: the allocate rounds,
    commit/feasibility verdicts, admission verdict, and eviction probe —
    shared verbatim by the single-device path below and the shard_map body
    (parallel/shard_solve.py), so the two paths can only diverge inside
    ``head``/``bid_fn``/``hist_fn``, each of which is bit-exact by the same
    decomposition argument as the sharded solves."""
    res = allocate_rounds(view, config, head, idle0, rel0, used0)
    J = snap.job_min_avail.shape[0]  # the appended job's row
    committed = res.committed[J]
    feasible = jnp.all(~view.task_pending | (res.assigned >= 0))
    # an empty or all-best-effort gang is not a solver verdict: backfill —
    # not this solve — would bind sub-quanta pods (module docstring)
    feasible &= jnp.any(view.task_pending)
    reasons = hist_fn()
    Q = snap.queue_valid.shape[0]
    qsafe = jnp.clip(g.queue, 0, Q - 1)
    queue_known = (g.queue >= 0) & (g.queue < Q) & snap.queue_valid[qsafe]
    enqueue_ok = _admission_verdict(
        oc_idle, snap.quanta, g.min_res, g.has_min_res,
        snap.queue_alloc[qsafe], snap.queue_capability[qsafe], queue_known,
    )

    if with_evictions:
        claim_node, victims, evict_ok = _evict_probe(
            snap, g.req, view.task_pending, g.queue, g.min_avail,
            res.assigned, bid_fn, evict_config, n_nodes,
        )
    else:
        G = g.req.shape[0]
        claim_node = jnp.full(G, -1, jnp.int32)
        victims = jnp.zeros(snap.task_req.shape[0], bool)
        evict_ok = jnp.bool_(False)
    return ProbeResult(
        assigned=res.assigned,
        pipelined=res.pipelined,
        committed=committed,
        feasible=feasible,
        reasons=reasons,
        enqueue_ok=enqueue_ok,
        claim_node=claim_node,
        victims=victims,
        evict_covered=evict_ok,
    )


def probe_body(snap: DeviceSnapshot, batch: ProbeBatch,
               probe_rows: jnp.ndarray, config: AllocateConfig,
               evict_config: EvictConfig = EvictConfig(mode="preempt"),
               with_evictions: bool = False) -> ProbeResult:
    """The single-device probe program (unjitted — :func:`probe_solve` is
    the jitted entry, parallel/mesh.py's pjit oracle re-jits this same body
    with mesh shardings).

    ``probe_rows`` [G] i32 — the global task rows the next G submitted pods
    would occupy (shared across the batch: every gang is an INDEPENDENT
    hypothetical starting from the same frozen allocator state)."""
    N = snap.node_alloc.shape[0]
    tie_hash = tie_break_hash_rows(
        probe_rows, jnp.arange(N, dtype=jnp.int32)
    )
    oc_idle = overcommit_idle(snap)

    def one(g: ProbeBatch) -> ProbeResult:
        view = _gang_view(
            snap, g.req, g.valid, g.min_avail, g.queue, g.prio,
            g.sel_bits, g.sel_impossible, g.tol_bits,
        )
        head, static_ok, score = round_head_parts(view, config, tie_hash)

        def bid_fn(claimant_ok, cap):
            feas = static_ok & claimant_ok[:, None]
            feas &= jnp.all(
                g.req[:, None, :] <= cap[None, :, :] + snap.quanta, axis=-1
            )
            masked = jnp.where(feas, score, NEG)
            return _best_node(masked, tie_hash)

        def hist_fn():
            # per-member fit-error histogram at CYCLE-START budgets — the
            # same program failure_histogram_solve runs for the submitted
            # gang's rows
            fit_idle0 = fits(view.task_req, snap.node_idle, snap.quanta)
            fit_rel0 = fits(view.task_req, snap.node_releasing, snap.quanta)
            return failure_histogram(
                view,
                FeasibilityMasks(
                    static_ok, fit_idle0, fit_rel0,
                    static_ok & (fit_idle0 | fit_rel0),
                ),
            )

        return probe_gang_core(
            snap, view, g, config, evict_config, with_evictions,
            head=head, bid_fn=bid_fn, hist_fn=hist_fn, oc_idle=oc_idle,
            idle0=snap.node_idle, rel0=snap.node_releasing,
            used0=snap.node_used, n_nodes=N,
        )

    return jax.vmap(one)(batch)


probe_solve = partial(jax.jit, static_argnames=(
    "config", "evict_config", "with_evictions"))(probe_body)
probe_solve.__doc__ = """B gangs against one snapshot in one dispatch
(the jitted :func:`probe_body`)."""

# retrace accounting: the serving bench asserts the probe stays a jit cache
# hit across varying batch fill (B and G are padded buckets)
jitstats.register("probe_solve", probe_solve)

"""reclaim action (actions/reclaim/reclaim.go) — cross-queue eviction,
device-solved.

The reference scans every node per starved task serially (reclaim.go:107-199).
Here ops/eviction.evict_solve proposes (claimant → node, victims) on device;
the host replays each claim through the real plugin callbacks
(ssn.reclaimable tier-intersection) so semantics stay authoritative: victims
are evicted (immediately — reclaim holds no Statement, reclaim.go:166-179)
only when the validated set still covers the claimant, then the claimant
pipelines onto the freed resources."""

from __future__ import annotations

import contextlib
import logging
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from kube_batch_tpu import metrics
from kube_batch_tpu.api.cluster_info import ClusterInfo
from kube_batch_tpu.api.snapshot import build_snapshot
from kube_batch_tpu.framework.interface import Action
from kube_batch_tpu.framework.session import FitFailure
from kube_batch_tpu.ops.eviction import EvictConfig

logger = logging.getLogger("kube_batch_tpu")


def _cluster_view(ssn) -> ClusterInfo:
    """Session → ClusterInfo. ALL jobs are included — the Pending-phase gate
    (reclaim.go:58-62 / preempt.go:59-63) applies to claimants only, via the
    snapshot's job_schedulable flag; Pending-phase jobs' Running tasks remain
    in the victim pool and their allocations in the fairness state."""
    cluster = ClusterInfo(ssn.spec)
    cluster.nodes = ssn.nodes
    cluster.queues = ssn.queues
    cluster.jobs = ssn.jobs
    return cluster


# plugins registering each Evictable fn kind (SURVEY.md §2.4)
_VICTIM_REGISTRANTS = {
    "reclaim": ("gang", "conformance", "proportion"),
    "preempt": ("gang", "conformance", "drf"),
}


def victim_gates(ssn, mode: str):
    """The set of plugins whose victim veto binds: the reference's Evictable
    dispatch takes the FIRST tier with any voting plugin
    (session_plugins.go:100-182) — later tiers never constrain victims."""
    registrants = _VICTIM_REGISTRANTS[mode]
    flag = "enabled_reclaimable" if mode == "reclaim" else "enabled_preemptable"
    for tier in ssn.tiers:
        voters = {
            opt.name
            for opt in tier.plugins
            if opt.name in registrants and getattr(opt, flag)
        }
        if voters:
            return voters
    return set()


class EvictDispatchPlan(NamedTuple):
    """What one evict dispatch will run, decided on the host before
    anything touches the device (:func:`plan_evict_dispatch`)."""

    mesh: Optional[object]   # the mesh the solve shards over; None = one device
    impl: Optional[str]      # "pjit" where shard_map is demoted, else None
    pend_rows: Optional[np.ndarray]  # the [P] pending bucket the bids run
    #                          on; None = the whole task axis
    claimants: int           # pending rows
    bucket: Optional[int]    # the one bucket the task axis compacts into
    sentinel: bool           # the invariant tail is fused behind the solve
    engaged: Tuple[str, ...]  # the guard fast paths the program engages
    audit: bool              # the shadow oracle runs behind the solve


def plan_evict_dispatch(snap, config: EvictConfig, guard) -> EvictDispatchPlan:
    """Choose the program of one evict dispatch from what the host can
    observe.  The claimant axis of the bids first: the pending bucket
    wherever the pending set fits the one bucket the task axis' shape gives
    (allocate's rule, shared), else the whole task axis — what the input
    shows, no knob.  Then whether a mesh exists and the cluster is wide
    enough to shard; on it, the guard's demotion (a tripped shard_map path
    runs the pjit oracle until its half-open probe re-promotes it) and
    whether the shadow audit falls due.  Touches no device."""
    from kube_batch_tpu.actions.allocate import plan_pend_bucket
    from kube_batch_tpu.parallel.mesh import (
        default_mesh,
        resolve_impl,
        should_shard,
    )

    pend_rows, claimants, bucket = plan_pend_bucket(snap)
    mesh, impl, engaged = None, None, ()
    if should_shard(snap.node_alloc.shape[0]):
        mesh = default_mesh()
        pend_rows = None  # the sharded bodies bid on the task axis
        impl = None if guard.allow("shard_map") else "pjit"
        if resolve_impl(impl) == "shard_map":
            engaged = ("shard_map",)
    return EvictDispatchPlan(
        mesh=mesh, impl=impl, pend_rows=pend_rows, claimants=claimants,
        bucket=bucket, sentinel=guard.enabled, engaged=engaged,
        audit=bool(engaged) and guard.audit_due(config.mode),
    )


def solve_claims(ssn, mode: str):
    """Run the eviction solve and decode to [(claimant_key, node_name,
    [victim_keys...])] in device claim order."""
    if not ssn.jobs or not ssn.nodes:
        return [], None
    cols = ssn.columns
    if cols is not None:
        if not cols.has_schedulable_pending():
            return [], None  # no claimants anywhere — idle cycle
        if not cols.has_running_victims():
            # nothing is running, so the evict solve is vacuous (victims
            # must be RUNNING on a node) — e.g. every first cycle of a
            # fresh cluster under the shipped 5-action conf
            return [], None
        snap, meta = cols.device_snapshot(ssn)
    else:
        snap, meta = build_snapshot(
            _cluster_view(ssn), excluded_nodes=ssn.session_excluded_nodes
        )
    gates = victim_gates(ssn, mode)
    # the idle-fit claimant gate (a declared improvement over reclaim.go —
    # PARITY "known divergences") is sound only when allocate actually runs
    # after reclaim to place the skipped claimants, and only when the
    # device fit is exact for them.  action_names is set by the scheduler
    # loop; with no pipeline information (direct action invocation) the
    # gate FAILS CLOSED to the reference behavior — an optimization whose
    # soundness depends on pipeline shape must not assume one.
    names = getattr(ssn, "action_names", None)
    idle_gate = (
        mode == "reclaim"
        # `reclaim.referenceExact: "true"` restores reclaim.go's behavior:
        # evict even for claimants free capacity could satisfy (PARITY.md)
        and not ssn.conf_flag("reclaim.referenceExact")
        and not ssn.host_only_predicates
        and names is not None
        and "allocate" in names
        and "reclaim" in names
        and names.index("allocate") > names.index("reclaim")
    )
    # the releasing gate (PARITY "known divergences"): a claimant that fits a
    # node's idle plus what that node's RELEASING victims have promised is
    # left to allocate, in both modes — sound under the same conditions as
    # the idle gate, but wherever allocate stands in the pipeline: what it
    # cannot pipeline this cycle it binds once the victims' DELETE drained
    releasing_gate = (
        not ssn.conf_flag(f"{mode}.referenceExact")
        and not ssn.host_only_predicates
        and names is not None
        and "allocate" in names
    )
    config = EvictConfig(
        mode=mode,
        idle_gate=idle_gate,
        releasing_gate=releasing_gate,
        gang=ssn.plugin_enabled("gang"),
        drf=ssn.plugin_enabled("drf"),
        proportion=ssn.plugin_enabled("proportion"),
        victim_gang="gang" in gates,
        victim_conformance="conformance" in gates,
        victim_proportion="proportion" in gates,
        victim_drf="drf" in gates,
        weights=ssn.score_weights,
    )
    from kube_batch_tpu.actions.allocate import republish_query_lease
    from kube_batch_tpu.api.columns import resident_snap
    from kube_batch_tpu.guard import guard_of
    from kube_batch_tpu.obs.trace import tracer_of
    from kube_batch_tpu.parallel.mesh import call, program

    gp = guard_of(ssn.cache)
    tracer = tracer_of(ssn.cache)
    plan = plan_evict_dispatch(snap, config, gp)
    mesh, pend_rows, engaged = plan.mesh, plan.pend_rows, list(plan.engaged)
    rows = () if pend_rows is None else (pend_rows,)
    audit_dev = None
    # device-resident feature cache (see allocate's dispatch): the decode
    # below keeps reading the ORIGINAL host-backed snap
    with tracer.device_span("solve_dispatch", cols=cols, action=mode) as sp:
        dev = resident_snap(cols, snap, mesh)
        out = call(
            program("evict", mesh, plan.impl, config, plan.sentinel),
            mesh, dev, *rows, config=config)
        result, *sentinel = out if plan.sentinel else (out,)
        if plan.audit:
            # shadow oracle (tier 2): the pjit program on the same
            # snapshot, read back only after the host decode below
            audit_dev = call(
                program("evict", mesh, "pjit", config), mesh, dev)
    tracer.note_evict_dispatch(
        sp, mode, "sharded" if mesh is not None else "single", engaged,
        compact=pend_rows is not None, claimants=plan.claimants,
        bucket=plan.bucket,
    )
    # this swap retired the what-if lease on donating backends — re-arm it
    # off the same (memoized) resident snapshot so serving doesn't stay
    # dark until the next cycle's allocate
    republish_query_lease(ssn, snap, meta)
    # kbt: allow[KBT010] the evict pass's ONE sanctioned readback — batched
    # (three per-field np.asarray reads were three blocking transfers;
    # flagged by KBT010's first dogfood run); the guard sentinel's verdict
    # + histogram ride it
    with tracer.device_span("device_wait", action=mode) as sp_wait:
        (claim_node, evicted, victim_claimant, rounds_run, gated, verdict,
         vhist, echeck) = (
            jax.device_get(  # kbt: allow[KBT010] the annotated choke point ^
                (result.claim_node, result.evicted, result.victim_claimant,
                 result.rounds_run, result.gated_releasing,
                 sentinel[0] if sentinel else np.int32(0),
                 sentinel[1] if sentinel else None,
                 sentinel[2] if sentinel else np.int32(0))
            )
        )
    claim_node = claim_node[: meta.n_tasks]
    evicted = evicted[: meta.n_tasks]
    victim_claimant = victim_claimant[: meta.n_tasks]
    tracer.note_evict_solve(
        sp_wait, mode, int(rounds_run), int(np.sum(claim_node >= 0)),
        int(np.sum(evicted)),
    )
    metrics.register_evict_claims(mode, "gated_releasing", int(gated))

    if sentinel:
        from kube_batch_tpu.api.types import TaskStatus as _TS
        from kube_batch_tpu.guard import consume_sentinel

        # host cross-checks: a claim must target a row the HOST believes
        # pending, a victim one the HOST believes RUNNING — the device
        # copies of those columns are exactly what a corruption flips; the
        # eligibility-checksum compare, histogram folding, bundle dump,
        # and resident+lease heal live in the SHARED consumer
        host_pending = np.asarray(snap.task_pending)[: meta.n_tasks]
        host_status = np.asarray(snap.task_status)[: meta.n_tasks]
        host_bad = int(
            np.sum((claim_node >= 0) & ~host_pending)
            + np.sum(evicted & (host_status != int(_TS.RUNNING)))
        )
        if not consume_sentinel(
            gp, mode, ssn, snap, dev, config, int(verdict), vhist,
            int(echeck), engaged, host_bad=host_bad, pend_rows=pend_rows,
        ):
            # condemned solve → fail closed: NO evictions from it
            return [], None

    task_job = np.asarray(snap.task_job)[: meta.n_tasks]

    def ref(ti: int):
        return (meta.job_uids[int(task_job[ti])], meta.task_keys[int(ti)])

    victims_by_claim: Dict[int, List[tuple]] = defaultdict(list)
    for vi in np.flatnonzero(evicted):
        ci = int(victim_claimant[vi])
        if ci >= 0:
            victims_by_claim[ci].append(ref(vi))
    claims = []
    for ti in np.flatnonzero(claim_node >= 0):
        claims.append(
            (ref(ti), meta.node_names[int(claim_node[ti])],
             victims_by_claim.get(int(ti), []))
        )
    if audit_dev is not None:
        # kbt: allow[KBT010] post-decode audit readback — the oracle solve
        # ran overlapped with the host decode above
        a_claim, a_evicted, a_vc = jax.device_get(
            (audit_dev.claim_node, audit_dev.evicted,
             audit_dev.victim_claimant)
        )
        n = meta.n_tasks
        mism = int(
            np.sum(a_claim[:n] != claim_node)
            + np.sum(a_evicted[:n] != evicted)
            + np.sum(a_vc[:n] != victim_claimant)
        )
        from kube_batch_tpu.guard import make_heal, sentinel_bundle_thunk

        gp.note_audit(
            mode, engaged, mism == 0,
            detail=f"{mode} shard_map-vs-pjit mismatch at {mism} rows",
            dump=sentinel_bundle_thunk(
                gp, mode, dev, config,
                {"audit_mismatches": mism, "engaged": engaged},
            ),
            heal=make_heal(ssn),
        )
        if mism:
            # the fast path is already demoted; the claims decoded above
            # came from the MISMATCHED program — fail closed for this cycle
            return [], meta
    return claims, meta


class ReplayTally:
    """What one action's replay made of the solve's claims: the
    ``evict_replay`` span's attributes and ``volcano_evict_claims_total``,
    set from the same counts when :meth:`replaying` ends, and the cache's
    half of the evictions the session made meanwhile (``pending``), which
    the cache hears of in one ``bulk_evict`` before the span closes."""

    def __init__(self, ssn, mode: str, claims: int):
        self.ssn, self.mode, self.claims = ssn, mode, claims
        self.committed = self.host_rejected = self.uncovered = 0
        self.victims = self.commits = 0
        self.pending: list = []  # [(task, reason, claimant)], claim order

    @classmethod
    @contextlib.contextmanager
    def replaying(cls, ssn, mode: str, claims: list):
        """The ``evict_replay`` span around one action's replay."""
        from kube_batch_tpu.obs.trace import tracer_of

        tally = cls(ssn, mode, len(claims))
        with tracer_of(ssn.cache).span("evict_replay") as span:
            try:
                yield tally
            finally:
                # what the session moved the cache must hear of
                tally.flush()
            tally.close(span)

    def commit(self, n_victims: int) -> None:
        self.committed += 1
        self.victims += n_victims

    def flush(self) -> None:
        """Hand the cache the evictions kept in ``pending``."""
        if self.pending:
            items, self.pending = self.pending, []
            self.commits += 1
            self.ssn.cache.bulk_evict(items)

    def close(self, span) -> None:
        span.set(claims=self.claims, victims=self.victims,
                 rejected=self.host_rejected + self.uncovered,
                 commits=self.commits)
        for outcome in ("committed", "host_rejected", "uncovered"):
            metrics.register_evict_claims(
                self.mode, outcome, getattr(self, outcome))


def covering_prefix(task, victims: list) -> int:
    """How many of ``victims``, in the order given, it takes to cover
    ``task`` in EVERY dimension (reclaim.go:150-163, preempt.go:219-237);
    0 when all of them together do not."""
    covered = task.init_resreq.less_equal
    freed = task.init_resreq.spec.empty()
    for n, victim in enumerate(victims, 1):
        freed.add_(victim.resreq)
        if covered(freed):
            return n
    return 0


def find_task(ssn, ref: tuple):
    """(job_uid, task_key) → session TaskInfo, O(1)."""
    job = ssn.jobs.get(ref[0])
    return job.tasks.get(ref[1]) if job is not None else None


class ReclaimAction(Action):
    name = "reclaim"

    def execute(self, ssn) -> None:
        claims, _ = solve_claims(ssn, "reclaim")
        if not claims:
            return
        with ReplayTally.replaying(ssn, "reclaim", claims) as tally:
            for claim in claims:
                self._replay(ssn, tally, *claim)

    def _replay(self, ssn, tally, claimant_ref, node_name, victim_refs):
        task = find_task(ssn, claimant_ref)
        if task is None or not victim_refs:
            tally.host_rejected += 1
            return
        # host predicate re-check (reclaim.go:124), only for constraints
        # the device mask approximates (rich affinity / host ports /
        # pressure gates)
        node = ssn.nodes.get(node_name)
        try:
            if node is not None and (
                task.needs_host_predicate or ssn.host_only_predicates
            ):
                ssn.predicate(task, node)
        except FitFailure as e:
            logger.info("reclaim claim %s→%s rejected by host predicate: %s",
                        claimant_ref, node_name, e.reason)
            tally.host_rejected += 1
            return
        preemptees = [
            v.clone() for v in (find_task(ssn, r) for r in victim_refs)
            if v is not None
        ]
        # host validation net: the real tier-intersected verdict
        # (proportion deserved, gang survival, conformance) on the
        # device-selected set only — O(claims), not O(T × N)
        victims = ssn.reclaimable(task, preemptees)
        if not victims:
            tally.host_rejected += 1
            return
        # sufficiency: victims must cover the claimant in EVERY dimension
        # (reclaim.go:150-163) — checked before any eviction happens
        n = covering_prefix(task, victims)
        if not n:
            logger.info(
                "reclaim claim %s→%s lost victims to host validation, skipped",
                claimant_ref, node_name,
            )
            tally.uncovered += 1
            return
        # immediate evict, no Statement: the session's ledgers move now (the
        # next claim's validation reads them), the cache hears at the end
        # of the action
        ssn.evict_batch(victims[:n], "reclaim", claimant=task,
                        later=tally.pending)
        ssn.pipeline(task, node_name)
        tally.commit(n)

"""preempt action (actions/preempt/preempt.go) — same-queue preemption,
device-solved phase 1 + host phase 2.

Phase 1 (inter-job within a queue, preempt.go:110-137): ops/eviction's
preempt-mode solve proposes (preemptor → node, victims) honoring conformance,
gang slack, and DRF share dominance; the host replays each preemptor job
through a Statement — evictions + pipelines commit only when the job reaches
Pipelined, mirroring the reference's commit gate.

The solve dispatch is GUARDED (kube_batch_tpu/guard): ``solve_claims``
(shared with reclaim) runs the sentinel-fused eviction program, consumes
its invariant verdict + host eligibility cross-checks, and FAILS CLOSED —
returning zero claims — when the solve is condemned, so no preemption can
ever be replayed from a corrupted or divergent result.

Phase 2 (intra-job task-priority rebalancing, preempt.go:145-174) stays a
host loop but only runs for jobs where a pending task outranks a running one
— the common all-equal-priority case short-circuits to nothing.

Spans: ``preempt_replay`` (phase 1's replay, around ``evict_replay``, which
both evict actions share: claimant jobs, Statements opened, committed and
discarded) and ``preempt_phase2`` (the walk of every job: jobs walked,
Statements opened), both under ``action:preempt``;
``volcano_evict_statements_total{action="preempt",outcome}`` counts the
same Statements."""

from __future__ import annotations

import logging
from collections import defaultdict
from typing import Callable, Dict, List, Tuple

from kube_batch_tpu import metrics
from kube_batch_tpu.actions.reclaim import (
    ReplayTally,
    covering_prefix,
    find_task,
    solve_claims,
)
from kube_batch_tpu.api.task_info import TaskInfo
from kube_batch_tpu.api.types import PodGroupPhase, TaskStatus
from kube_batch_tpu.framework.interface import Action
from kube_batch_tpu.framework.session import FitFailure
from kube_batch_tpu.obs.trace import tracer_of
from kube_batch_tpu.utils.priority_queue import PriorityQueue

logger = logging.getLogger("kube_batch_tpu")


class PreemptAction(Action):
    name = "preempt"

    def execute(self, ssn) -> None:
        self._phase1(ssn)
        self._phase2(ssn)

    # ---- phase 1: inter-job within queue (device-solved) ---------------
    def _phase1(self, ssn) -> None:
        claims, _ = solve_claims(ssn, "preempt")
        if not claims:
            return
        with tracer_of(ssn.cache).span("preempt_replay") as span:
            with ReplayTally.replaying(ssn, "preempt", claims) as tally:
                opened, committed = self._replay(ssn, tally, claims)
            span.set(claims=len(claims), statements=opened,
                     committed=committed, discarded=opened - committed)
        _count_statements(opened, committed)

    def _replay(self, ssn, tally, claims) -> Tuple[int, int]:
        """Replay ``claims``; returns (Statements opened, committed)."""
        # group claims by preemptor job — the Statement boundary
        by_job: Dict[str, List[Tuple[TaskInfo, str, List[tuple]]]] = defaultdict(list)
        for claimant_ref, node_name, victim_refs in claims:
            task = find_task(ssn, claimant_ref)
            if task is not None and victim_refs:
                by_job[task.job].append((task, node_name, victim_refs))
            else:
                tally.host_rejected += 1

        opened = committed = 0
        for job_uid, job_claims in by_job.items():
            job = ssn.jobs.get(job_uid)
            if job is None:
                tally.host_rejected += len(job_claims)
                continue
            stmt = ssn.statement()
            opened += 1
            staged = []  # victims evicted for each claim of this Statement
            for task, node_name, victim_refs in job_claims:
                # host predicate re-check (preempt.go:191), only for
                # host-only constraints (see allocate replay)
                node = ssn.nodes.get(node_name)
                try:
                    if node is not None and (
                        task.needs_host_predicate or ssn.host_only_predicates
                    ):
                        ssn.predicate(task, node)
                except FitFailure:
                    tally.host_rejected += 1
                    continue
                preemptees = [
                    v.clone() for v in (find_task(ssn, r) for r in victim_refs)
                    if v is not None and v.status == TaskStatus.RUNNING
                ]
                victims = ssn.preemptable(task, preemptees)
                if not victims:
                    tally.host_rejected += 1
                    continue
                # evict lowest-task-order first (preempt.go:219-237), as
                # many as cover the claimant in every dimension
                ordered = _lowest_first(ssn, victims)
                evicted = covering_prefix(task, ordered)
                if not evicted:
                    tally.uncovered += 1
                    continue
                stmt.evict_batch(ordered[:evicted], "preempt", claimant=task)
                stmt.pipeline(task, node_name)
                staged.append(evicted)
            if ssn.job_pipelined(job):
                stmt.commit()  # its evictions reach the cache in one call
                tally.commits += bool(staged)
                committed += 1
                for evicted in staged:
                    tally.commit(evicted)
            else:
                stmt.discard()
                tally.host_rejected += len(staged)
        return opened, committed

    # ---- phase 2: intra-job (host, guarded) ----------------------------
    def _phase2(self, ssn) -> None:
        with tracer_of(ssn.cache).span("preempt_phase2") as span:
            opened = self._rebalance(ssn)
            span.set(jobs=len(ssn.jobs), statements=opened)
        _count_statements(opened, opened)

    def _rebalance(self, ssn) -> int:
        """Phase 2; returns the Statements it opened (each commits,
        preempt.go:168)."""
        opened = 0
        for job in ssn.jobs.values():
            # claimant gates (preempt.go:59-63): enqueued jobs in known queues
            if job.pod_group and job.pod_group.phase == PodGroupPhase.PENDING:
                continue
            if job.queue not in ssn.queues:
                continue
            pending = job.task_status_index.get(TaskStatus.PENDING, {})
            running = job.task_status_index.get(TaskStatus.RUNNING, {})
            if not pending or not running:
                continue
            # cheap skip: the reference runs phase 2 unconditionally
            # (preempt.go:145-174); we gate on the tiered task-order plugin
            # verdict — preempt only when some enabled plugin (priority, or a
            # custom task_order) says the best pending task outranks the
            # worst running one. The creation-index tie-break deliberately
            # does NOT open the gate: evicting an equal-rank sibling for its
            # slot is zero-gain work.  `preempt.referenceExact: "true"` on
            # any conf tier restores the reference's ungated phase 2
            # (PARITY.md "known divergences").
            if not ssn.conf_flag("preempt.referenceExact"):
                to = ssn.task_order_fn
                best_p = None
                for t in pending.values():
                    if best_p is None or to(t, best_p):
                        best_p = t
                worst_r = None
                for t in running.values():
                    if worst_r is None or to(worst_r, t):
                        worst_r = t
                verdict = ssn.task_order_plugin_verdict(best_p, worst_r)
                if verdict == 0:
                    # no task-order plugin voted (e.g. priority disabled in
                    # conf): fall back to comparing the extreme raw
                    # priorities — NOT best_p/worst_r, which were picked by
                    # the degenerate creation-order comparator and need not
                    # carry the extreme priorities
                    hi = max(t.priority for t in pending.values())
                    lo = min(t.priority for t in running.values())
                    verdict = -1 if hi > lo else 1
                if verdict >= 0:
                    continue  # nothing to rebalance
            tq = PriorityQueue(less=ssn.task_order_fn)
            for task in pending.values():
                tq.push(task)
            while tq:
                preemptor = tq.pop()

                def intra_job_filter(task: TaskInfo) -> bool:
                    return (
                        task.status == TaskStatus.RUNNING
                        and preemptor.job == task.job
                    )

                stmt = ssn.statement()
                opened += 1
                assigned = self._preempt_host(ssn, stmt, preemptor, intra_job_filter)
                stmt.commit()  # phase 2 commits unconditionally (preempt.go:168)
                if not assigned:
                    break
        return opened

    def _preempt_host(
        self,
        ssn,
        stmt,
        preemptor: TaskInfo,
        victim_filter: Callable[[TaskInfo], bool],
    ) -> bool:
        """Sequential preemption for one task (preempt.go:180-260)."""
        candidates = []
        for node in ssn.nodes.values():
            try:
                ssn.predicate(preemptor, node)
            except FitFailure:
                continue
            candidates.append((ssn.node_order(preemptor, node), node))
        candidates.sort(key=lambda sn: -sn[0])

        for _, node in candidates:
            preemptees = [t.clone() for t in node.tasks.values() if victim_filter(t)]
            victims = ssn.preemptable(preemptor, preemptees)
            if not victims:
                continue
            ordered = _lowest_first(ssn, victims)
            evicted = covering_prefix(preemptor, ordered)
            if not evicted:
                continue  # victims must cover every dimension
            stmt.evict_batch(ordered[:evicted], "preempt")
            stmt.pipeline(preemptor, node.name)
            return True
        return False


def _count_statements(opened: int, committed: int) -> None:
    for outcome, n in (("opened", opened), ("committed", committed),
                       ("discarded", opened - committed)):
        metrics.register_evict_statements("preempt", outcome, n)


def _lowest_first(ssn, victims: List[TaskInfo]) -> List[TaskInfo]:
    """``victims`` in the order preempt evicts them: lowest task order
    first (preempt.go:219-237)."""
    vq = PriorityQueue(less=lambda l, r: not ssn.task_order_fn(l, r))
    for v in victims:
        vq.push(v)
    ordered = []
    while vq:
        ordered.append(vq.pop())
    return ordered

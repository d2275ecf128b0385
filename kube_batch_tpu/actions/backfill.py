"""backfill action (actions/backfill/backfill.go:42-93): place BestEffort
tasks (empty InitResreq) on the first node passing the plugin predicates —
no scoring, immediate allocate.

BEYOND-REFERENCE: non-BestEffort backfill — the reference's own acknowledged
TODO (backfill.go:87).  When the allocate replay discarded placements
host-side (a gang that failed its JobReady gate after host predicate
rejections, a volume-demoted job that could not re-place), the capacity
those discards freed is stranded for the rest of the cycle: the device solve
already ran and the reference's sequential loop has likewise moved on.  The
real-request pass re-runs the allocate solve over the live post-replay
snapshot, restricted to GANG-SAFE claimants — jobs already at or above
MinAvailable, or non-gangs (MinAvailable ≤ 1) — so no partial gang can ever
commit, and replays the result through the standard vectorized path.
Disabled with `backfill.realRequests: "false"` on any conf tier.
Pinned by tests/test_conformance.py TestRealRequestBackfill."""

from __future__ import annotations

import logging

from kube_batch_tpu.api.job_info import FitError, FitErrors
from kube_batch_tpu.api.types import PodGroupPhase, TaskStatus
from kube_batch_tpu.framework.interface import Action
from kube_batch_tpu.framework.session import FitFailure

logger = logging.getLogger("kube_batch_tpu")


class BackfillAction(Action):
    name = "backfill"

    def execute(self, ssn) -> None:
        self._best_effort(ssn)
        self._real_requests(ssn)

    # ---- reference semantics: BestEffort first-fit ----------------------
    def _best_effort(self, ssn) -> None:
        for job in ssn.jobs.values():
            if job.pod_group and job.pod_group.phase == PodGroupPhase.PENDING:
                continue
            pending = list(job.task_status_index.get(TaskStatus.PENDING, {}).values())
            for task in pending:
                if not task.best_effort:
                    continue
                fit_errors = FitErrors()
                for node in ssn.nodes.values():
                    try:
                        ssn.predicate(task, node)
                    except FitFailure as e:
                        fit_errors.set_node_error(
                            node.name, FitError(task, node.name, [e.reason])
                        )
                        continue
                    ssn.allocate(task, node.name)
                    break
                else:
                    job.nodes_fit_errors[task.uid] = fit_errors
                    ssn.note_fit_state(job)

    # ---- beyond-reference: stranded-capacity real-request pass ----------
    def _real_requests(self, ssn) -> None:
        if not ssn.jobs or not ssn.nodes:
            return
        if not ssn.conf_flag("backfill.realRequests", default=True):
            return
        # the pass re-pays a full [T, N] solve, so it only runs when the
        # allocate action actually stranded capacity this cycle; without
        # that signal the post-allocate pending set is exactly the set the
        # solve just failed, and re-solving is wasted work.  The signal
        # rides the SESSION (set by allocate's discard path): the action
        # registry is a process-global singleton, and reading its counter
        # here crossed wires between scheduler instances sharing a process
        # (tests, the simulator's many schedulers) — round-5 ADVICE #5
        if not getattr(ssn, "host_discards", 0):
            return
        import jax
        import numpy as np

        from kube_batch_tpu.actions.allocate import (
            AllocateAction,
            build_session_snapshot,
            dispatch_allocate_solve,
            session_allocate_config,
        )

        cols = ssn.columns
        if cols is not None:
            if not cols.has_schedulable_pending():
                return
        else:
            # isolated sessions: object-level pre-gate before paying the
            # full snapshot rebuild — any gang-safe job with pending tasks?
            def _safe_pending(job):
                if job.pod_group and job.pod_group.phase == PodGroupPhase.PENDING:
                    return False
                if not job.task_status_index.get(TaskStatus.PENDING):
                    return False
                return job.min_available <= 1 or job.ready()

            if not any(_safe_pending(j) for j in ssn.jobs.values()):
                return
        snap, meta = build_session_snapshot(ssn)
        # gang-safe claimants only: a job at/above MinAvailable can take
        # extra placements without atomicity risk; a MinAvailable ≤ 1 job is
        # not a gang.  An unready gang stays excluded — committing part of
        # it is exactly what allocate's discard just prevented.
        safe_np = (
            (np.asarray(snap.job_min_avail) <= 1)
            | (np.asarray(snap.job_ready) >= np.asarray(snap.job_min_avail))
        ) & np.asarray(snap.job_schedulable)
        # cheap host pre-check BEFORE the [T, N] solve: the common trigger —
        # a discarded unready gang being the only pending work — must not
        # re-pay the cycle's dominant cost for a guaranteed-empty result
        task_job = np.asarray(snap.task_job)[: meta.n_tasks]
        eligible = (
            np.asarray(snap.task_pending)[: meta.n_tasks]
            & np.asarray(snap.task_valid)[: meta.n_tasks]
            & np.asarray(snap.job_valid)[task_job]
            & safe_np[task_job]
        )
        if not eligible.any():
            return
        import jax.numpy as jnp

        snap = snap._replace(
            job_schedulable=snap.job_schedulable & jnp.asarray(safe_np)
        )
        from kube_batch_tpu.guard import OracleUnfit, guard_of
        from kube_batch_tpu.obs.trace import tracer_of

        gp = guard_of(ssn.cache)
        tracer = tracer_of(ssn.cache)
        config = session_allocate_config(ssn)
        try:
            with tracer.device_span("solve_dispatch", cols=cols,
                                    action="backfill") as sp_solve:
                result, mode, _topk, ginfo = dispatch_allocate_solve(
                    snap, config, cols=cols, guard=gp
                )
        except OracleUnfit as e:
            gp.fail_closed("backfill", str(e))
            return
        tracer.note_solve_dispatch(sp_solve, "backfill", mode,
                                   ginfo["engaged"])
        # this swap retired the what-if lease on donating backends — re-arm
        # it off the same (memoized) resident snapshot.  The gang-safe
        # job_schedulable mask above is probe-invisible: a probe's task
        # axis is ONLY the speculative gang (its appended job row is the
        # sole j_sched consulted), so this snapshot is oracle-equivalent
        # for serving
        from kube_batch_tpu.actions.allocate import republish_query_lease

        republish_query_lease(ssn, snap, meta)
        sentinel = ginfo["sentinel"]
        with tracer.device_span("device_wait", action="backfill"):
            # kbt: allow[KBT010] the backfill pass's one sanctioned
            # readback — the guard sentinel's verdict + histogram ride it
            assigned, pipelined, verdict, vhist, echeck = jax.device_get(
                (result.assigned, result.pipelined,
                 sentinel[0] if sentinel is not None else np.int32(0),
                 sentinel[1] if sentinel is not None else None,
                 sentinel[2] if sentinel is not None else np.int32(0))
            )
        assigned = assigned[: meta.n_tasks]
        pipelined = pipelined[: meta.n_tasks]
        if sentinel is not None:
            from kube_batch_tpu.guard import consume_assignment_sentinel

            if not consume_assignment_sentinel(
                gp, "backfill", ssn, snap, meta, ginfo,
                int(verdict), vhist, int(echeck), assigned,
            ):
                # condemned solve → fail closed: strand the capacity for
                # this cycle rather than bind from an unlawful result
                return
        if not (assigned >= 0).any():
            return
        n = int((assigned >= 0).sum())
        logger.info("backfill real-request pass placing %d stranded tasks", n)
        # replay through a throwaway action instance so the allocate
        # action's recorded phases/fallback stay those of the main pass
        helper = AllocateAction()
        meta.terms_exact = ginfo["dev"].aff_terms is not None
        helper._replay(ssn, snap, meta, assigned, pipelined, task_job)

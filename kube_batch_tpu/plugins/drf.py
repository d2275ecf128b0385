"""drf plugin (plugins/drf/drf.go) — dominant-resource fairness at job level.

Registers: Preemptable (preemptor's post-allocation share must stay ≤
victim-job's post-eviction share), JobOrder (lower share first), and event
handlers keeping per-job allocated/share incrementally updated during the
session (drf.go:135-154). The device solve reproduces the same ordering via
virtual drf shares (ops/ordering.py); this host state drives preempt/reclaim.
"""

from __future__ import annotations

from typing import Dict, List

from kube_batch_tpu.api.job_info import JobInfo
from kube_batch_tpu.api.resources import Resource
from kube_batch_tpu.api.task_info import TaskInfo
from kube_batch_tpu.api.types import is_allocated
from kube_batch_tpu.framework.interface import Plugin
from kube_batch_tpu.framework import session as fw

SHARE_DELTA = 1e-6  # drf.go:33


class _JobAttr:
    __slots__ = ("allocated", "_share", "_dirty", "_gen")

    def __init__(self, allocated: Resource):
        self.allocated = allocated
        self._share = 0.0
        self._dirty = True
        self._gen = 0


class DrfPlugin(Plugin):
    name = "drf"

    def __init__(self, arguments=None):
        super().__init__(arguments)
        self.total: Resource | None = None
        self.job_attrs: Dict[str, _JobAttr] = {}
        # columnar mode: per-job-row allocated matrix the attrs' Resources
        # are views into; _generation invalidates every cached share after a
        # vectorized update
        self._arr = None
        self._generation = 0

    def _share(self, attr: _JobAttr) -> float:
        # recomputed lazily on read: the allocate replay fires thousands of
        # batch events whose shares nothing reads until preempt/reclaim
        if attr._dirty or attr._gen != self._generation:
            attr._share = attr.allocated.share(self.total)
            attr._dirty = False
            attr._gen = self._generation
        return attr._share

    def on_session_open(self, ssn: fw.Session) -> None:
        self.total = ssn.total_allocatable().clone()
        cols = ssn.columns
        if cols is not None:
            # columnar session: one matrix copy seeds every job's allocated
            # state; attrs are built LAZILY on first read, wrapping rows
            # zero-copy — the headline allocate cycle never reads a share
            # (ordering runs on device), so eagerly building 12.5k attr
            # objects was pure open-session overhead.  Per-task events from
            # evictions write the same rows the vectorized allocate updates,
            # so every path composes.
            self._arr = cols.j_alloc.copy()
        else:
            for job in ssn.jobs.values():
                # job.allocated IS the sum of allocated-status task resreqs —
                # the ledger add_task/bulk_transition maintain (job_info.py);
                # re-deriving it per task was the session-open hot loop
                self.job_attrs[job.uid] = _JobAttr(job.allocated.clone())

        wrap = ssn.spec.wrap_vec

        def attr_for(uid: str):
            """The job's attr, lazily wrapping its _arr row in columnar
            sessions; None for unknown jobs."""
            attr = self.job_attrs.get(uid)
            if attr is None and self._arr is not None:
                job = ssn.jobs.get(uid)
                if job is not None and job._row >= 0:
                    attr = self.job_attrs[uid] = _JobAttr(
                        wrap(self._arr[job._row])
                    )
            return attr

        def preemptable(preemptor: TaskInfo, preemptees: List[TaskInfo]) -> List[TaskInfo]:
            """(drf.go:85-110)"""
            lattr = attr_for(preemptor.job)
            if lattr is None:
                return []
            lalloc = lattr.allocated.add(preemptor.resreq)
            ls = lalloc.share(self.total)
            allocations: Dict[str, Resource] = {}
            victims: List[TaskInfo] = []
            for ee in preemptees:
                rattr = attr_for(ee.job)
                if rattr is None:
                    continue
                if ee.job not in allocations:
                    allocations[ee.job] = rattr.allocated.clone()
                ralloc = allocations[ee.job]
                if not ee.resreq.less_equal(ralloc):
                    continue
                ralloc.sub_(ee.resreq)
                rs = ralloc.share(self.total)
                if ls < rs or abs(ls - rs) <= SHARE_DELTA:
                    victims.append(ee)
            return victims

        def job_order(l: JobInfo, r: JobInfo) -> int:
            """(drf.go:114-132) lower dominant share first."""
            la = attr_for(l.uid)
            ra = attr_for(r.uid)
            ls = self._share(la) if la is not None else 0.0
            rs = self._share(ra) if ra is not None else 0.0
            if ls == rs:
                return 0
            return -1 if ls < rs else 1

        def on_allocate(event: fw.Event) -> None:
            attr = attr_for(event.task.job)
            if attr is not None:
                attr.allocated.add_(event.task.resreq)
                attr._dirty = True

        def on_deallocate(event: fw.Event) -> None:
            attr = attr_for(event.task.job)
            if attr is not None:
                attr.allocated.sub_(event.task.resreq)
                attr._dirty = True

        def on_batch_allocate(job: JobInfo, tasks, total_resreq) -> None:
            # linear in resreq: one presummed add per job ≡ per-task events
            attr = attr_for(job.uid)
            if attr is not None:
                attr.allocated.add_(total_resreq)
                attr._dirty = True

        def on_batch_deallocate(job: JobInfo, tasks, total_resreq) -> None:
            # the evict verbs' mirror: one presummed sub per job of a claim
            attr = attr_for(job.uid)
            if attr is not None:
                attr.allocated.sub_(total_resreq)
                attr._dirty = True

        def on_columnar_allocate(cols, job_sums) -> None:
            # one matrix add for the whole replay ≡ 12.5k batch events
            self._arr += job_sums
            self._generation += 1

        ssn.add_fn(fw.PREEMPTABLE, self.name, preemptable)
        ssn.add_fn(fw.JOB_ORDER, self.name, job_order)
        ssn.add_event_handler(
            fw.EventHandler(
                allocate_func=on_allocate, deallocate_func=on_deallocate,
                batch_allocate_func=on_batch_allocate,
                columnar_allocate_func=(
                    on_columnar_allocate if self._arr is not None else None
                ),
                batch_deallocate_func=on_batch_deallocate,
            )
        )

    def on_session_close(self, ssn: fw.Session) -> None:
        self.total = None
        self.job_attrs = {}
        self._arr = None

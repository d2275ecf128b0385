"""proportion plugin (plugins/proportion/proportion.go) — weighted max-min
fair queue capacity.

Registers: QueueOrder (lower share first), Reclaimable (victim's queue must
stay ≥ deserved), Overused, JobEnqueueable (capability cap), and event
handlers keeping per-queue allocation live. The deserved waterfill here is
the host (numpy) twin of ops/fairness.proportion_deserved used by the device
solve.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from kube_batch_tpu.api.job_info import JobInfo
from kube_batch_tpu.api.queue_info import QueueInfo
from kube_batch_tpu.api.resources import Resource
from kube_batch_tpu.api.task_info import TaskInfo
from kube_batch_tpu.api.types import TaskStatus, is_allocated
from kube_batch_tpu.framework.interface import Plugin
from kube_batch_tpu.framework import session as fw


class _QueueAttr:
    __slots__ = ("queue", "weight", "deserved", "allocated", "request",
                 "_share", "_dirty", "_gen")

    def __init__(self, queue: QueueInfo, spec):
        self.queue = queue
        self.weight = queue.weight
        self.deserved = spec.empty()
        self.allocated = spec.empty()
        self.request = spec.empty()
        self._share = 0.0
        self._dirty = True
        self._gen = 0


class ProportionPlugin(Plugin):
    name = "proportion"

    def __init__(self, arguments=None):
        super().__init__(arguments)
        self.total: Resource | None = None
        self.queue_attrs: Dict[str, _QueueAttr] = {}
        # columnar mode: [nq, R] allocated matrix the attrs wrap + a
        # job-row → attr-index map for the vectorized allocate events
        self._qalloc = None
        self._jq_rows = None
        self._jq_vals = None
        self._generation = 0

    def _share(self, attr: _QueueAttr) -> float:
        """share = dominant allocated/deserved (proportion.go:265-277),
        recomputed lazily on read — the allocate replay fires thousands of
        batch events whose shares nothing reads until queue ordering."""
        if attr._dirty or attr._gen != self._generation:
            attr._share = _dominant(attr.allocated, attr.deserved)
            attr._dirty = False
            attr._gen = self._generation
        return attr._share

    def on_session_open(self, ssn: fw.Session) -> None:
        spec = ssn.spec
        self.total = ssn.total_allocatable().clone()
        cols = ssn.columns
        if cols is not None and getattr(ssn, "rows_synced", False):
            # columnar session: the open-time row sync already derived
            # session membership and queue rows (j_sess/j_queue — delta
            # against the previous cycle when churn allows), so queue attrs
            # are one segment-sum over the job ledger matrices: no per-job
            # Python loop at all (proportion.go:67-99)
            rows = np.flatnonzero(cols.j_sess)
            qrows = cols.j_queue[rows]
            capQ = cols.queues.cap
            alloc_m = np.zeros((capQ, spec.n))
            request_m = np.zeros((capQ, spec.n))
            np.add.at(alloc_m, qrows, cols.j_alloc[rows])
            np.add.at(request_m, qrows, cols.j_alloc[rows] + cols.j_pend[rows])
            self._qalloc, self._jq_rows, self._jq_vals = alloc_m, rows, qrows
            wrap = spec.wrap_vec
            for qi in np.unique(qrows).tolist():
                qinfo = ssn.queues.get(cols.queue_names[qi])
                if qinfo is None:
                    continue  # queue row/dict skew — attr-less queues fail open
                attr = _QueueAttr(qinfo, spec)
                attr.allocated = wrap(alloc_m[qi])
                attr.request = wrap(request_m[qi])
                self.queue_attrs[qinfo.name] = attr
        else:
            # queue attrs from jobs present this session (proportion.go:67-99)
            for job in ssn.jobs.values():
                if job.queue not in ssn.queues:
                    continue
                attr = self.queue_attrs.get(job.queue)
                if attr is None:
                    attr = _QueueAttr(ssn.queues[job.queue], spec)
                    self.queue_attrs[job.queue] = attr
                # request = allocated + pending (proportion.go:87-99), both
                # read straight off the JobInfo ledgers — no task iteration
                attr.allocated.add_(job.allocated)
                attr.request.add_(job.allocated)
                attr.request.add_(job.pending_request)
        self._waterfill(spec)

        def queue_order(l: QueueInfo, r: QueueInfo) -> int:
            la = self.queue_attrs.get(l.name)
            ra = self.queue_attrs.get(r.name)
            ls = self._share(la) if la else 0.0
            rs = self._share(ra) if ra else 0.0
            if ls == rs:
                return 0
            return -1 if ls < rs else 1

        def reclaimable(reclaimer: TaskInfo, reclaimees: List[TaskInfo]) -> List[TaskInfo]:
            """(proportion.go:171-196) victim OK if its queue stays ≥ deserved."""
            victims: List[TaskInfo] = []
            allocations: Dict[str, Resource] = {}
            for ee in reclaimees:
                job = ssn.jobs.get(ee.job)
                if job is None or job.queue not in self.queue_attrs:
                    continue
                attr = self.queue_attrs[job.queue]
                if job.queue not in allocations:
                    allocations[job.queue] = attr.allocated.clone()
                alloc = allocations[job.queue]
                if not ee.resreq.less_equal(alloc):
                    continue
                alloc.sub_(ee.resreq)
                # semantic dims only — pods is capacity, not fairness
                if attr.deserved.less_equal_semantic(alloc):
                    victims.append(ee)
            return victims

        def overused_fn(queue: QueueInfo) -> bool:
            attr = self.queue_attrs.get(queue.name)
            if attr is None:
                return False
            # semantic dims only — pods is capacity, not fairness
            return attr.deserved.less_equal_semantic(attr.allocated)

        def job_enqueueable(job: JobInfo) -> bool:
            """(proportion.go:211-233) capability quota not exceeded."""
            queue = ssn.queues.get(job.queue)
            attr = self.queue_attrs.get(job.queue)
            if queue is None or attr is None:
                return True
            capability = queue.queue.capability
            if not capability:
                return True
            cap = ssn.spec.empty()
            for name, v in capability.items():
                if name in ssn.spec:
                    cap.vec[ssn.spec.index(name)] = float(v)
            min_res = ssn.spec.empty()
            for name, v in (job.pod_group.min_resources or {}).items():
                if name in ssn.spec:
                    min_res.vec[ssn.spec.index(name)] = float(v)
            return min_res.add(attr.allocated).less_equal(cap)

        def on_allocate(event: fw.Event) -> None:
            job = ssn.jobs.get(event.task.job)
            if job and job.queue in self.queue_attrs:
                attr = self.queue_attrs[job.queue]
                attr.allocated.add_(event.task.resreq)
                attr._dirty = True

        def on_deallocate(event: fw.Event) -> None:
            job = ssn.jobs.get(event.task.job)
            if job and job.queue in self.queue_attrs:
                attr = self.queue_attrs[job.queue]
                attr.allocated.sub_(event.task.resreq)
                attr._dirty = True

        def on_batch_allocate(job: JobInfo, tasks, total_resreq) -> None:
            # linear in resreq: one presummed add per queue ≡ per-task events
            if job.queue in self.queue_attrs:
                attr = self.queue_attrs[job.queue]
                attr.allocated.add_(total_resreq)
                attr._dirty = True

        def on_batch_deallocate(job: JobInfo, tasks, total_resreq) -> None:
            # the evict verbs' mirror: one presummed sub per job of a claim
            if job.queue in self.queue_attrs:
                attr = self.queue_attrs[job.queue]
                attr.allocated.sub_(total_resreq)
                attr._dirty = True

        def on_columnar_allocate(cols, job_sums) -> None:
            # one segment-sum for the whole replay ≡ 12.5k batch events
            np.add.at(self._qalloc, self._jq_vals, job_sums[self._jq_rows])
            self._generation += 1

        ssn.add_fn(fw.QUEUE_ORDER, self.name, queue_order)
        ssn.add_fn(fw.RECLAIMABLE, self.name, reclaimable)
        ssn.add_fn(fw.OVERUSED, self.name, overused_fn)
        ssn.add_fn(fw.JOB_ENQUEUEABLE, self.name, job_enqueueable)
        ssn.add_event_handler(
            fw.EventHandler(
                allocate_func=on_allocate, deallocate_func=on_deallocate,
                batch_allocate_func=on_batch_allocate,
                columnar_allocate_func=(
                    on_columnar_allocate if self._qalloc is not None else None
                ),
                batch_deallocate_func=on_batch_deallocate,
            )
        )

    def _waterfill(self, spec) -> None:
        """deserved by weighted max-min (proportion.go:101-154); host twin of
        ops/fairness.proportion_deserved."""
        attrs = list(self.queue_attrs.values())
        if not attrs:
            return
        remaining = self.total.vec.copy()
        met = [False] * len(attrs)
        for _ in range(max(len(attrs) * 2, 16)):
            if not np.any(remaining > 1e-6) or all(met):
                break
            weights = np.array(
                [a.weight if not m else 0.0 for a, m in zip(attrs, met)]
            )
            tw = weights.sum()
            if tw <= 0:
                break
            for i, attr in enumerate(attrs):
                if met[i]:
                    continue
                inc = remaining * (weights[i] / tw)
                new = attr.deserved.vec + inc
                if np.all(attr.request.vec <= new + 1e-6):
                    new = np.minimum(new, attr.request.vec)
                    met[i] = True
                attr.deserved = spec.from_vec(new)
            granted = sum(a.deserved.vec for a in attrs)
            remaining = np.maximum(self.total.vec - granted, 0.0)

    def on_session_close(self, ssn: fw.Session) -> None:
        self.total = None
        self.queue_attrs = {}
        self._qalloc = self._jq_rows = self._jq_vals = None


def _dominant(alloc: Resource, deserved: Resource) -> float:
    # max over semantic dims of alloc/deserved, 0 where deserved is 0 —
    # exactly Resource.share's contract (native fast path)
    return alloc.share(deserved)

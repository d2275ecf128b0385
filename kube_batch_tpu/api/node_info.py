"""NodeInfo — per-node resource accounting with the three-way status algebra.

Mirrors pkg/scheduler/api/node_info.go:28-222. The critical piece is the
AddTask/RemoveTask algebra (node_info.go:165-222): a task's effect on the
node's (Idle, Used, Releasing) triple depends on its status —

    Releasing task:  Releasing += r ; Idle -= r ; Used += r
    Pipelined task:  Releasing -= r            ; Used += r
    other allocated: Idle -= r                 ; Used += r

so that "fits in Releasing" (allocate.go:176-184) means: the request fits in
resources that are on their way back. The same algebra is replicated
tensor-side in ops/assignment.py; this host copy is authoritative for ingest
and for the host-path actions (preempt/reclaim/backfill).
"""

from __future__ import annotations

import itertools
from typing import Dict, Optional

import numpy as np

from kube_batch_tpu.api.pod import Node
from kube_batch_tpu.api.resources import Resource, ResourceSpec, PODS
from kube_batch_tpu.api.task_info import TaskInfo
from kube_batch_tpu.api.types import TaskStatus, is_allocated
from kube_batch_tpu.utils.assertions import graft_assert


def _node_resource(node: Node, spec: ResourceSpec, which: str) -> Resource:
    src = node.allocatable if which == "allocatable" else node.capacity
    r = spec.empty()
    for name, v in src.items():
        if name in spec:
            r.vec[spec.index(name)] = float(v)
    return r


class NodeInfo:
    def __init__(self, node: Optional[Node], spec: ResourceSpec):
        self.spec = spec
        self.name: str = node.name if node else ""
        self.node: Optional[Node] = node
        self.tasks: Dict[str, TaskInfo] = {}
        if node is not None:
            self.allocatable = _node_resource(node, spec, "allocatable")
            self.capability = _node_resource(node, spec, "capacity")
        else:
            self.allocatable = spec.empty()
            self.capability = spec.empty()
        self.idle = self.allocatable.clone()
        self.used = spec.empty()
        self.releasing = spec.empty()
        # status each resident task was ACCOUNTED under (see task algebra)
        self._acct: Dict[str, TaskStatus] = {}
        # ColumnStore binding (api/columns.py): when bound, the five ledger
        # Resources above are views into the store's [N, R] matrices
        self._cols = None
        self._row: int = -1
        self._set_state()

    # -- state machine (node_info.go:110-134) -----------------------------
    def _set_state(self) -> None:
        """setNodeState (node_info.go:110-134): UnInitialized when no node
        object yet, OutOfSync when resident pods use more than the node's
        allocatable, else Ready — NotReady nodes are excluded from snapshots
        (cache.go:595-597). The state is STORED, recomputed only on set_node
        like the reference: mid-session task churn must not flip readiness
        (a Pipelined overlay legitimately pushes used above allocatable
        while the capacity it borrows is still Releasing)."""
        if self.node is None:
            self._state = "UnInitialized"
        elif not self.used.less_equal(self.allocatable):
            self._state = "OutOfSync"
        elif not self.node.ready:
            self._state = "NotReady"
        else:
            self._state = "Ready"

    @property
    def state(self) -> str:
        return self._state

    @property
    def ready(self) -> bool:
        return self._state == "Ready"

    def set_node(self, node: Node) -> None:
        """Update the node object, rebuilding (Idle, Used, Releasing) from the
        new allocatable and replaying every resident task's status algebra
        (node_info.go:137-162 SetNode). The replay matters when pods were
        ingested before their node: their add_task skipped accounting because
        node was None.

        The replay is underflow-tolerant: when resident tasks use more than
        the new allocatable (pods landed before a smaller node object, or the
        node shrank), Idle clamps at zero and the `state` property reports
        OutOfSync — excluding the node from snapshots until usage reconciles
        (node_info.go:110-134; the reference instead skips the rebuild and
        keeps stale accounting — same observable contract, NotReady node)."""
        self.name = node.name
        self.node = node
        alloc = _node_resource(node, self.spec, "allocatable")
        cap = _node_resource(node, self.spec, "capacity")
        idle_v = alloc.vec.copy()
        used_v = self.spec.empty().vec
        rel_v = self.spec.empty().vec
        acct = self._acct
        for key, t in self.tasks.items():
            r = t.resreq.vec
            acct[key] = t.status  # re-account under the live status
            if t.status == TaskStatus.RELEASING:
                rel_v += r
                idle_v -= r
                used_v += r
            elif t.status == TaskStatus.PIPELINED:
                rel_v -= r
                used_v += r
            elif is_allocated(t.status):
                idle_v -= r
                used_v += r
            t.node_name = node.name
        np.maximum(idle_v, 0.0, out=idle_v)
        np.maximum(rel_v, 0.0, out=rel_v)
        if self._cols is None:
            self.allocatable = alloc
            self.capability = cap
            self.idle = Resource(idle_v, self.spec)
            self.used = Resource(used_v, self.spec)
            self.releasing = Resource(rel_v, self.spec)
        else:
            # column-bound: write through the ledger views in place so the
            # store's matrices stay the single source of truth; an actual
            # allocatable change invalidates the device-resident n_alloc
            if not np.array_equal(self.allocatable.vec, alloc.vec):
                self._cols.bump_node_features()
            self._note_ledger()
            self.allocatable.vec[:] = alloc.vec
            self.capability.vec[:] = cap.vec
            self.idle.vec[:] = idle_v
            self.used.vec[:] = used_v
            self.releasing.vec[:] = rel_v
        self._set_state()
        if self._cols is not None:
            self._cols.sync_node_meta(self)

    # -- task algebra (node_info.go:165-222) ------------------------------
    # The reference clones each task into the node ("Node will hold a copy
    # of task to make sure the status change will not impact resource in
    # node", node_info.go:165-168). Here the node stores the caller's task
    # object directly and records the status it ACCOUNTED under in the
    # `_acct` side table — remove_task reverses from _acct, so a later
    # in-place status mutation on the task still can't desynchronize the
    # algebra, and the 50k-placement replay skips 50k task clones. Readers
    # of node.tasks see live status (the reference's SetNode replay reads
    # live status the same way).
    def demote_to_placeholder(self) -> None:
        """Forget the Node object but KEEP the resident task registrations —
        the inverse of the pod-before-node ingest placeholder. Used when a
        node is deleted while pods are still bound to it: the tasks outlive
        the Node (the reference keeps their NodeName too), accounting zeroes
        out, the node drops out of snapshots (state UnInitialized), and a later
        re-add replays everything through set_node."""
        self.node = None
        if self._cols is None:
            # unbound: rebind fresh Resources — clones share allocatable/
            # capability objects and must not see the zeroing
            self.allocatable = self.spec.empty()
            self.capability = self.spec.empty()
            self.idle = self.spec.empty()
            self.used = self.spec.empty()
            self.releasing = self.spec.empty()
        else:
            # column-bound: the ledger views are the store's matrices —
            # zero them in place.  n_alloc is a CACHED feature column and
            # sync_node_meta early-returns below (no Node object), so the
            # invalidation must happen here
            for res in (self.idle, self.used, self.releasing,
                        self.allocatable, self.capability):
                res.vec[:] = 0.0
            self._cols.bump_node_features()
            self._note_ledger()
        self._set_state()
        if self._cols is not None:
            self._cols.sync_node_meta(self)

    def _note_ledger(self) -> None:
        """Dirty-row choke point: every (Idle, Used, Releasing, Allocatable)
        write funnels one mark to the ColumnStore so the device snapshot's
        float32 twins refresh exactly the touched rows
        (columns.node_ledgers32)."""
        if self._cols is not None:
            self._cols.note_node_ledger(self._row)

    def add_task(self, task: TaskInfo) -> None:
        key = task.key()
        graft_assert(key not in self.tasks, f"duplicate task {key} on node {self.name}")
        status = task.status
        if self.node is not None:
            self._note_ledger()
            r = task.resreq
            if status == TaskStatus.RELEASING:
                self.releasing.add_(r)
                self.idle.sub_(r)
                self.used.add_(r)
            elif status == TaskStatus.PIPELINED:
                self.releasing.sub_(r)
                self.used.add_(r)
            elif is_allocated(status):
                self.idle.sub_(r)
                self.used.add_(r)
            # terminal/pending statuses don't touch accounting
        task.node_name = self.name
        self.tasks[key] = task
        self._acct[key] = status

    def remove_task(self, task: TaskInfo) -> None:
        key = task.key()
        existing = self.tasks.get(key)
        graft_assert(existing is not None, f"task {key} not on node {self.name}")
        if existing is not None:
            status = self._acct.pop(key, existing.status)
            if self.node is not None:
                self._note_ledger()
                r = existing.resreq
                if status == TaskStatus.RELEASING:
                    self.releasing.sub_(r)
                    self.idle.add_(r)
                    self.used.sub_(r)
                elif status == TaskStatus.PIPELINED:
                    self.releasing.add_(r)
                    self.used.sub_(r)
                elif is_allocated(status):
                    self.idle.add_(r)
                    self.used.sub_(r)
        self.tasks.pop(key, None)

    def update_task(self, task: TaskInfo) -> None:
        """delete + add (node_info.go:225-233)."""
        self.remove_task(task)
        self.add_task(task)

    def bulk_add_tasks(self, alloc_tasks, pipe_tasks, alloc_sum, pipe_sum) -> None:
        """Batched add_task for the vectorized allocate replay.  `alloc_tasks`
        carry an AllocatedStatus, `pipe_tasks` are Pipelined; `alloc_sum` /
        `pipe_sum` are the presummed Resources over each group.  The status
        algebra (node_info.go:165-222) collapses to two vector ops per group;
        per-task work is the dict insert + _acct record."""
        tasks = self.tasks
        acct = self._acct
        name = self.name
        for group in (alloc_tasks, pipe_tasks):
            for task in group:
                key = task._key
                if key in tasks:  # avoid building the message on the hot path
                    graft_assert(False, f"duplicate task {key} on node {self.name}")
                task.node_name = name
                tasks[key] = task
                acct[key] = task.status
        if self.node is not None:
            self._note_ledger()
            self.idle.sub_(alloc_sum)
            self.used.add_(alloc_sum)
            self.used.add_(pipe_sum)
            self.releasing.sub_(pipe_sum)

    def bulk_release(self, tasks, resreq_sum) -> None:
        """Batched update_task for a claim's victims: `tasks` have just gone
        RELEASING and were accounted here under an AllocatedStatus;
        `resreq_sum` is the presummed Resource over them.  For such a task
        remove_task + add_task net to `releasing += r` (idle and used come
        back where they were), so the group is ONE vector op and one ledger
        mark; per-task work is the `_acct` stamp and the dict store that
        makes the moved object the one this node holds (a cloned session's
        node keeps its own copies).  Any other accounting in the group
        sends the whole group through update_task, task by task."""
        acct = self._acct
        if not all(is_allocated(acct.get(t._key)) for t in tasks):
            for task in tasks:
                self.update_task(task)
            return
        mine = self.tasks
        for task in tasks:
            key = task._key
            mine[key] = task
            acct[key] = TaskStatus.RELEASING
        if self.node is not None:
            self._note_ledger()
            self.releasing.add_(resreq_sum)

    def bulk_register_tasks(self, alloc_tasks, pipe_tasks) -> None:
        """Task-dict/acct registration ONLY, for the columnar allocate
        replay: the (Idle, Used, Releasing) algebra was already applied to
        this node's ledger views by whole-matrix column ops.  End state
        equals bulk_add_tasks'."""
        tasks = self.tasks
        acct = self._acct
        name = self.name
        for group, status in (
            (alloc_tasks, TaskStatus.BINDING),
            (pipe_tasks, TaskStatus.PIPELINED),
        ):
            for task in group:
                key = task._key
                if key in tasks:
                    graft_assert(False, f"duplicate task {key} on node {name}")
                task._node_name = name
                tasks[key] = task
                acct[key] = status

    def clone(self) -> "NodeInfo":
        # direct copy of the accounting triple instead of replaying every
        # resident task's status algebra (the triple already reflects it);
        # skips __init__ (which would rebuild allocatable/capability from
        # the node dicts) — allocatable/capability are rebound on set_node,
        # never mutated in place, so the clone shares them. Tasks ARE cloned:
        # the session mutates its copies' statuses in place.
        n = NodeInfo.__new__(NodeInfo)
        n._cols = None    # clones are never column-bound
        n._row = -1
        n.spec = self.spec
        n.name = self.name
        n.node = self.node
        # a bound node's allocatable/capability are live column views that
        # set_node mutates in place — the clone needs value semantics
        if self._cols is None:
            n.allocatable = self.allocatable
            n.capability = self.capability
        else:
            n.allocatable = self.allocatable.clone()
            n.capability = self.capability.clone()
        n.idle = self.idle.clone()
        n.used = self.used.clone()
        n.releasing = self.releasing.clone()
        n.tasks = {key: t.clone() for key, t in self.tasks.items()}
        n._acct = dict(self._acct)
        n._state = self._state  # stored state carries over (not recomputed)
        return n

    @property
    def pod_count(self) -> int:
        return len(self.tasks)

    def __repr__(self) -> str:
        return f"NodeInfo({self.name} idle={self.idle} used={self.used} releasing={self.releasing})"

"""Session — the per-cycle runtime (framework/session.go, session_plugins.go,
statement.go, framework.go).

A Session owns one immutable-ish snapshot of the cluster (deep-cloned by the
cache), the tier-configured plugin callbacks, and the mutation verbs
(Allocate/Pipeline/Evict) whose committed effects flow back to the cache as
bind/evict calls. The TPU divergence: the hot allocate path doesn't use the
per-task verbs — it runs the device solve (ops/assignment.py) over the
snapshot tensors and then *replays* the resulting assignment through the same
verbs so host state, event handlers, and the binder see exactly the
sequential semantics.
"""

from __future__ import annotations

import itertools
import uuid
from typing import Callable, Dict, List, Optional, Tuple

from kube_batch_tpu.api.cluster_info import ClusterInfo
from kube_batch_tpu.api.job_info import JobInfo
from kube_batch_tpu.api.node_info import NodeInfo
from kube_batch_tpu.api.pod import PodGroupCondition
from kube_batch_tpu.api.queue_info import QueueInfo
from kube_batch_tpu.api.task_info import TaskInfo
from kube_batch_tpu.api.types import (
    ALLOCATED_STATUSES,
    PodGroupPhase,
    TaskStatus,
    queue_phase_counts,
)
from kube_batch_tpu.framework.conf import Tier
from kube_batch_tpu import metrics

# fn-kind names used in the per-plugin registries
JOB_ORDER, QUEUE_ORDER, TASK_ORDER = "job_order", "queue_order", "task_order"
JOB_READY, JOB_PIPELINED, JOB_VALID = "job_ready", "job_pipelined", "job_valid"
JOB_ENQUEUEABLE, OVERUSED = "job_enqueueable", "overused"
PREEMPTABLE, RECLAIMABLE = "preemptable", "reclaimable"
PREDICATE, NODE_ORDER = "predicate", "node_order"

_ENABLE_FIELD = {
    JOB_ORDER: "enabled_job_order",
    QUEUE_ORDER: "enabled_queue_order",
    TASK_ORDER: "enabled_task_order",
    JOB_READY: "enabled_job_ready",
    JOB_PIPELINED: "enabled_job_pipelined",
    JOB_VALID: None,  # JobValid has no enable switch (session_plugins.go:244)
    JOB_ENQUEUEABLE: None,
    OVERUSED: None,
    PREEMPTABLE: "enabled_preemptable",
    RECLAIMABLE: "enabled_reclaimable",
    PREDICATE: "enabled_predicate",
    NODE_ORDER: "enabled_node_order",
}


class Event:
    """Allocate/Deallocate event (framework/event.go:24-32)."""

    def __init__(self, task: TaskInfo):
        self.task = task


class EventHandler:
    """Allocate/Deallocate hooks (framework/event.go:24-32).

    `batch_allocate_func(job, tasks, total_resreq)` is an optional
    TPU-rebuild extension: a handler whose per-task effect is linear in
    task.resreq (drf's job share, proportion's queue allocation) can expose
    one call per job with the presummed resreq, letting the vectorized
    allocate replay skip the per-task event loop. Handlers without it are
    fired per task even on the bulk path — semantics never depend on it.

    `columnar_allocate_func(cols, job_sums)` is the fully-vectorized form:
    one call per replay with the [capJ, R] per-job-row resreq sums (zeros for
    untouched jobs).  The columnar allocate replay requires every handler
    with allocate-side effects to provide it (actions/allocate.py gates on
    that), so no handler can silently miss events.

    `batch_deallocate_func(job, tasks, total_resreq)` is the mirror of
    `batch_allocate_func` for the evict verbs: one call per job of a claim's
    victims with their resreq presummed.  Handlers without it are fired per
    task (`Session.fire_batch_deallocations`)."""

    def __init__(self, allocate_func=None, deallocate_func=None,
                 batch_allocate_func=None, columnar_allocate_func=None,
                 batch_deallocate_func=None):
        self.allocate_func = allocate_func
        self.deallocate_func = deallocate_func
        self.batch_allocate_func = batch_allocate_func
        self.columnar_allocate_func = columnar_allocate_func
        self.batch_deallocate_func = batch_deallocate_func


class FitFailure(Exception):
    """A predicate rejection with a reason (api.FitError analog)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class Session:
    def __init__(self, cache, cluster: ClusterInfo, tiers: List[Tier],
                 exclusive: bool = False, open_reuse=None,
                 dirty_jobs=frozenset()):
        self.uid = str(uuid.uuid4())
        self.cache = cache
        self.spec = cluster.spec
        self.jobs: Dict[str, JobInfo] = cluster.jobs
        self.nodes: Dict[str, NodeInfo] = cluster.nodes
        self.queues: Dict[str, QueueInfo] = cluster.queues
        # exclusive (no-clone) session: jobs/nodes ARE the cache's objects;
        # the cache defers ingest until close and close_session unwinds
        # session-only state (pipelined placements)
        self.exclusive = exclusive
        # the cache's persistent ColumnStore, exposed to plugins for
        # vectorized session-open state (None for isolated sessions, whose
        # cloned objects are not column-bound)
        self.columns = getattr(cache, "columns", None) if exclusive else None
        # every task Pipelined this session (Statement.pipeline /
        # Session.pipeline / the bulk replay) — session-only state the
        # exclusive close must revert (a cloned session just dies)
        self.pipelined_tasks: List[TaskInfo] = []
        # every task set ALLOCATED this session (Session.allocate /
        # Statement.allocate).  ALLOCATED only becomes durable via dispatch
        # (→ BINDING); residue whose job never turned ready is session-only
        # state too — exclusive close reverts whatever is still ALLOCATED
        # (the reference's clone takes it to the grave, session.go:286-294)
        self.allocated_tasks: List[TaskInfo] = []
        self.tiers = tiers
        self.plugins: List = []
        # plugin-fn registries: kind → {plugin_name: fn}
        self._fns: Dict[str, Dict[str, Callable]] = {}
        self.event_handlers: List[EventHandler] = []
        # device-solve knobs populated by plugins at session open
        from kube_batch_tpu.ops.scoring import ScoreWeights

        self.score_weights = ScoreWeights()
        # set by plugins whose predicates the device mask can't encode;
        # forces per-placement host re-validation for every job
        self.host_only_predicates = False
        # node names a plugin excludes for this whole session (task-
        # independent vetoes like the pressure gates) — both snapshot
        # builders fold these into node_sched, so the device mask stays
        # exact and the replay stays on the fast path
        self.session_excluded_nodes: set = set()
        # PodGroup statuses as they stood at open (session.go:102-105), used
        # by the job updater to detect condition-only updates (rate-limited)
        # — essential in exclusive mode, where the session mutates the
        # authoritative PodGroup in place and a post-hoc compare is vacuous.
        # Exclusive sessions also clear per-session diagnostic state on the
        # live objects in the same pass — a cloned session starts clean
        # because clone() does this (job_info.go:295-329); the no-clone path
        # must, or stale fit errors replay forever (and grow unboundedly).
        #
        # `open_reuse` (cache/dirty.py OpenCache) is the delta form of this
        # pass: the cache maintained the at-open statuses across cycles
        # (session_view_delta refreshed the dirty jobs), and only jobs known
        # to carry fit diagnostics — cache.fit_state_jobs, populated by
        # note_fit_state at every write site — pay the clearing visit.
        self.pod_group_status_at_open: Dict[str, tuple] = {}
        if exclusive and open_reuse is not None:
            fit_jobs = cache.fit_state_jobs
            for uid in (fit_jobs | set(dirty_jobs)) if fit_jobs or dirty_jobs else ():
                job = self.jobs.get(uid)
                if job is None:
                    continue
                if job.nodes_fit_delta:
                    job.nodes_fit_delta = {}
                if job.nodes_fit_errors:
                    job.nodes_fit_errors = {}
                if job.job_fit_errors:
                    job.job_fit_errors = ""
            fit_jobs.clear()
            self.pod_group_status_at_open = dict(open_reuse.pg_status)
        else:
            at_open = self.pod_group_status_at_open
            for job in self.jobs.values():
                if exclusive:
                    if job.nodes_fit_delta:
                        job.nodes_fit_delta = {}
                    if job.nodes_fit_errors:
                        job.nodes_fit_errors = {}
                    if job.job_fit_errors:
                        job.job_fit_errors = ""
                pg = job.pod_group
                if pg is not None:
                    at_open[job.uid] = (pg.phase, pg.running, pg.failed,
                                        pg.succeeded)
        # set by open_session once the ColumnStore's session job-row arrays
        # (j_sess & friends) are synced for this cycle — the gate, the close
        # status pass, and the device snapshot all read them
        self.rows_synced = False
        self._total_alloc_cache = None
        # job uids given an Unschedulable=True condition THIS session —
        # saves the close pass a per-job scan over conditions lists
        self.unschedulable_marked: set = set()
        # jobs the open gate dropped (gang-invalid, session.go:107-124) —
        # their podgroups still count toward QueueStatus phase counts
        self.gate_dropped_jobs: List[JobInfo] = []
        # jobs whose placements the allocate replay DISCARDED host-side this
        # cycle (JobReady failures after host predicate rejections, volume
        # demotion dead-ends) — the backfill action's real-request pass keys
        # off this. Carried on the session, NOT the process-global action
        # registry singleton: multiple Scheduler/cache instances in one
        # process (tests, the simulator) must not cross wires (round-5 ADVICE #5)
        self.host_discards = 0
        # the staged StatusFlush, stashed here by close_session as soon as
        # staging succeeds: if the close's own finally raises afterwards,
        # the pipelined caller recovers the flush from the session instead
        # of dropping writes whose stage-time bookkeeping already committed
        self.staged_flush = None

    def drop_job(self, uid: str) -> None:
        """Remove a job from the session (open-gate drops).  The caller is
        responsible for clearing the job's j_sess row when the session rows
        are already synced (open_session's gate does)."""
        del self.jobs[uid]

    def session_rows(self):
        """(rows[int], jobs_list) of the CURRENT session job set, straight
        off the synced j_sess column — the shared basis for the vectorized
        gate, the gang close sweep, and the columnar close status pass.
        Columnar sessions only; requires rows_synced."""
        import numpy as np

        cols = self.columns
        rows = np.flatnonzero(cols.j_sess)
        job_by_row = cols.job_by_row
        return rows, [job_by_row[r] for r in rows.tolist()]

    def note_fit_state(self, job: JobInfo) -> None:
        """Record that `job` now carries per-session fit diagnostics
        (nodes_fit_delta / nodes_fit_errors / job_fit_errors) — the delta
        session open clears exactly these jobs instead of probing all of
        them.  Every write site of those fields must call this."""
        fit_jobs = getattr(self.cache, "fit_state_jobs", None)
        if fit_jobs is not None:
            fit_jobs.add(job.uid)

    def total_allocatable(self):
        """Σ allocatable over the session's nodes (the drf/proportion
        cluster total, drf.go:57-62 / proportion.go:67-74), computed once
        per session — vectorized over the node columns when bound, else the
        object loop."""
        if self._total_alloc_cache is not None:
            return self._total_alloc_cache
        cols = self.columns
        total = self.spec.empty()
        # session nodes are exactly the Ready rows (session_view filters on
        # node.ready, which n_valid mirrors) — checked cheaply; any mismatch
        # falls back to the authoritative object loop
        if (
            cols is not None
            and len(self.nodes) > 64
            and int(cols.n_valid.sum()) == len(self.nodes)
        ):
            total.vec = cols.n_alloc[cols.n_valid].sum(axis=0)
        else:
            for node in self.nodes.values():
                total.add_(node.allocatable)
        self._total_alloc_cache = total
        return total

    # ---- registration (session_plugins.go:25-97) ------------------------
    def add_fn(self, kind: str, plugin_name: str, fn: Callable) -> None:
        self._fns.setdefault(kind, {})[plugin_name] = fn

    def add_score_row(self, name: str, fn: Callable, weight: float = 1.0) -> None:
        """Register a DEVICE score row: fn(snap: DeviceSnapshot) -> [T, N]
        f32, summed into the compiled solve's score matrix with `weight` —
        the NodeOrder/BatchNodeOrder extension surface
        (session_plugins.go:392-492) at the tensor level.  A plugin whose
        scoring policy also matters on the host replay paths should
        additionally register a host scorer via add_fn(NODE_ORDER, ...).
        Use a module-level fn: the row set is part of the jit cache key, so
        a fresh lambda per session forces a recompile every cycle."""
        self.score_weights = self.score_weights._replace(
            extra_rows=self.score_weights.extra_rows + ((name, fn, weight),)
        )

    def add_event_handler(self, handler: EventHandler) -> None:
        self.event_handlers.append(handler)

    def _enabled(self, kind: str, opt) -> bool:
        field = _ENABLE_FIELD[kind]
        return True if field is None else getattr(opt, field)

    def _iter_fns(self, kind: str):
        """Yield (tier_index, fn) for enabled plugins, in tier order."""
        fns = self._fns.get(kind, {})
        for ti, tier in enumerate(self.tiers):
            for opt in tier.plugins:
                fn = fns.get(opt.name)
                if fn is not None and self._enabled(kind, opt):
                    yield ti, fn

    def plugin_enabled(self, name: str) -> bool:
        return any(opt.name == name for tier in self.tiers for opt in tier.plugins)

    def conf_flag(self, key: str, default: bool = False) -> bool:
        """A free-form boolean argument searched across every tier's plugin
        Arguments (arguments.go:26-66) — the conf surface for action-level
        toggles: the sanctioned-divergence escape
        hatches `preempt.referenceExact` / `reclaim.referenceExact`
        (PARITY.md "known divergences")."""
        for tier in self.tiers:
            for opt in tier.plugins:
                v = opt.arguments.get(key)
                if v is not None:
                    return str(v).strip().lower() in ("1", "true", "yes")
        return default

    def enabled_plugin_names(self, kind: str) -> set:
        """Names of plugins with an enabled fn of `kind` registered — lets the
        vectorized allocate replay prove the gang arithmetic gate is the only
        JobReady veto before taking the fast path."""
        fns = self._fns.get(kind, {})
        return {
            opt.name
            for tier in self.tiers
            for opt in tier.plugins
            if opt.name in fns and self._enabled(kind, opt)
        }

    def ordered_enabled_plugins(self, kind: str) -> List[str]:
        """Enabled voter names of `kind` in tiered dispatch order (the
        _iter_fns iteration order) — the enqueue column gate derives its
        vectorized ordering keys in exactly this significance order."""
        fns = self._fns.get(kind, {})
        return [
            opt.name
            for tier in self.tiers
            for opt in tier.plugins
            if opt.name in fns and self._enabled(kind, opt)
        ]

    # ---- tiered dispatch ------------------------------------------------
    def _order(self, kind: str, l, r, l_info: Tuple, r_info: Tuple) -> bool:
        """First non-zero verdict wins; fallback CreationTimestamp-then-UID
        (session_plugins.go:281-305)."""
        for _, fn in self._iter_fns(kind):
            v = fn(l, r)
            if v != 0:
                return v < 0
        return l_info < r_info

    def job_order_fn(self, l: JobInfo, r: JobInfo) -> bool:
        return self._order(JOB_ORDER, l, r, (l.creation_index, l.uid), (r.creation_index, r.uid))

    def queue_order_fn(self, l: QueueInfo, r: QueueInfo) -> bool:
        return self._order(QUEUE_ORDER, l, r, (l.name,), (r.name,))

    def task_order_fn(self, l: TaskInfo, r: TaskInfo) -> bool:
        return self._order(
            TASK_ORDER, l, r, (l.pod.creation_index, l.uid), (r.pod.creation_index, r.uid)
        )

    def task_order_plugin_verdict(self, l: TaskInfo, r: TaskInfo) -> int:
        """The tiered plugin verdict alone (<0 l first, 0 no plugin voted),
        WITHOUT the creation-timestamp fallback — for callers that must
        distinguish 'a plugin prefers l' from 'mere tie-break order', e.g.
        preempt's phase-2 worth-it gate."""
        for _, fn in self._iter_fns(TASK_ORDER):
            v = fn(l, r)
            if v != 0:
                return v
        return 0

    def _veto(self, kind: str, obj) -> bool:
        """All enabled plugins must pass (JobReady session_plugins.go:202-220)."""
        for _, fn in self._iter_fns(kind):
            if not fn(obj):
                return False
        return True

    def job_ready(self, job: JobInfo) -> bool:
        return self._veto(JOB_READY, job)

    def job_pipelined(self, job: JobInfo) -> bool:
        return self._veto(JOB_PIPELINED, job)

    def job_enqueueable(self, job: JobInfo) -> bool:
        return self._veto(JOB_ENQUEUEABLE, job)

    def job_valid(self, job: JobInfo) -> Optional[str]:
        """First failing plugin's reason, None = valid
        (session_plugins.go:244-260)."""
        for _, fn in self._iter_fns(JOB_VALID):
            reason = fn(job)
            if reason is not None:
                return reason
        return None

    def overused(self, queue: QueueInfo) -> bool:
        """Any plugin saying overused wins (session_plugins.go:185-199)."""
        return any(fn(queue) for _, fn in self._iter_fns(OVERUSED))

    def _victims(self, kind: str, actor: TaskInfo, candidates: List[TaskInfo]):
        """Per-tier intersection; first tier with a non-None verdict wins
        (session_plugins.go:100-182). None = no plugin in the tier voted;
        [] = plugins voted and vetoed everything."""
        for ti, tier in enumerate(self.tiers):
            victims: Optional[List[TaskInfo]] = None
            init = False
            for opt in tier.plugins:
                fn = self._fns.get(kind, {}).get(opt.name)
                if fn is None or not self._enabled(kind, opt):
                    continue
                cand = fn(actor, candidates)
                if not init:
                    victims, init = cand, True
                elif victims is not None:
                    cand_uids = {c.uid for c in (cand or [])}
                    victims = [v for v in victims if v.uid in cand_uids]
            if victims is not None:
                return victims
        return None

    def preemptable(self, preemptor: TaskInfo, preemptees: List[TaskInfo]):
        return self._victims(PREEMPTABLE, preemptor, preemptees)

    def reclaimable(self, reclaimer: TaskInfo, reclaimees: List[TaskInfo]):
        return self._victims(RECLAIMABLE, reclaimer, reclaimees)

    def predicate(self, task: TaskInfo, node: NodeInfo) -> None:
        """All enabled predicates must pass; raises FitFailure
        (session_plugins.go:372-389)."""
        for _, fn in self._iter_fns(PREDICATE):
            fn(task, node)  # raises FitFailure

    def node_order(self, task: TaskInfo, node: NodeInfo) -> float:
        """Additive score (session_plugins.go:392-412)."""
        return sum(fn(task, node) for _, fn in self._iter_fns(NODE_ORDER))

    # ---- verbs (session.go:199-363) -------------------------------------
    def _fire(self, allocate: bool, task: TaskInfo) -> None:
        for eh in self.event_handlers:
            fn = eh.allocate_func if allocate else eh.deallocate_func
            if fn is not None:
                fn(Event(task))

    def fire_batch_allocations(self, job: JobInfo, tasks, total_resreq) -> None:
        """Fire allocate events for `tasks` (all of one job) — one call per
        handler that supports batching (with `total_resreq` presummed over the
        tasks), the per-task loop for handlers that don't."""
        for eh in self.event_handlers:
            if eh.batch_allocate_func is not None:
                eh.batch_allocate_func(job, tasks, total_resreq)
            elif eh.allocate_func is not None:
                for t in tasks:
                    eh.allocate_func(Event(t))

    def fire_batch_deallocations(self, job: JobInfo, tasks, total_resreq) -> None:
        """The deallocate twin of :meth:`fire_batch_allocations`."""
        for eh in self.event_handlers:
            if eh.batch_deallocate_func is not None:
                eh.batch_deallocate_func(job, tasks, total_resreq)
            elif eh.deallocate_func is not None:
                for t in tasks:
                    eh.deallocate_func(Event(t))

    def fire_columnar_allocations(self, cols, job_sums) -> None:
        """One vectorized allocate-event pass for the whole replay
        (job_sums: [capJ, R] per-job-row resreq sums)."""
        for eh in self.event_handlers:
            if eh.columnar_allocate_func is not None:
                eh.columnar_allocate_func(cols, job_sums)

    def all_handlers_columnar(self) -> bool:
        """True when every handler with allocate-side effects supports the
        columnar form — the allocate replay's gate for the vectorized path."""
        return all(
            eh.columnar_allocate_func is not None
            or (eh.allocate_func is None and eh.batch_allocate_func is None)
            for eh in self.event_handlers
        )

    def pipeline(self, task: TaskInfo, hostname: str) -> None:
        job = self.jobs.get(task.job)
        if job is not None:
            job.update_task_status(task, TaskStatus.PIPELINED)
        task.node_name = hostname
        node = self.nodes.get(hostname)
        if node is not None:
            node.add_task(task)
        self.pipelined_tasks.append(task)
        self._fire(True, task)

    def allocate(self, task: TaskInfo, hostname: str) -> None:
        """Allocate + (when the job turns ready) dispatch every Allocated
        task to the binder (session.go:252-296)."""
        self.cache.allocate_volumes(task, hostname)
        job = self.jobs.get(task.job)
        if job is not None:
            job.update_task_status(task, TaskStatus.ALLOCATED)
        task.node_name = hostname
        node = self.nodes.get(hostname)
        if node is not None:
            node.add_task(task)
        self.allocated_tasks.append(task)
        self._fire(True, task)
        if job is not None and self.job_ready(job):
            for t in list(job.task_status_index.get(TaskStatus.ALLOCATED, {}).values()):
                self.dispatch(t)

    def dispatch(self, task: TaskInfo) -> None:
        self.cache.bind_volumes(task)
        self.cache.bind(task, task.node_name)
        job = self.jobs.get(task.job)
        if job is not None:
            job.update_task_status(task, TaskStatus.BINDING)

    def _release(self, victims) -> List[TaskInfo]:
        """The session's half of an eviction, for ``victims`` as a group:
        the victims of one job go RELEASING in one ``bulk_transition``,
        those on one node in one ``bulk_release``, and every handler hears
        of a job's victims once, each with the group's resreq presummed.
        The end state is that of the per-victim verbs (``update_task_status``,
        ``update_task`` and a deallocate event a victim).

        What moves is the session's RESIDENT task of each victim's key: a
        victim handed in as a copy (the evict actions validate clones) only
        names it.  Returns the residents, in the order given."""
        jobs_get = self.jobs.get
        by_job: Dict[str, tuple] = {}  # uid -> (job, its victims)
        by_node: Dict[Optional[str], list] = {}
        moved = []
        for v in victims:
            job = jobs_get(v.job)
            if job is None:
                # not this session's to move (the per-victim verbs left its
                # ledgers where they were too); the handlers still hear
                self._fire(False, v)
                moved.append(v)
                continue
            own = job.tasks.get(v._key)
            if own is None:
                job.add_task(v)  # not resident yet: from here on it is
                own = v
            moved.append(own)
            group = by_job.get(v.job)
            if group is None:
                by_job[v.job] = (job, [own])
            else:
                group[1].append(own)
            group = by_node.get(own.node_name)
            if group is None:
                by_node[own.node_name] = [own]
            else:
                group.append(own)
        for job, tasks in by_job.values():
            total = self._resreq_sum(tasks)
            flip = [t for t in tasks if t.status in ALLOCATED_STATUSES]
            if len(flip) == len(tasks):
                job.bulk_transition(tasks, TaskStatus.RELEASING, total)
            else:  # bulk_transition wants one allocated-ness flip a call
                rest = [t for t in tasks if t.status not in ALLOCATED_STATUSES]
                job.bulk_transition(flip, TaskStatus.RELEASING,
                                    self._resreq_sum(flip))
                job.bulk_transition(rest, TaskStatus.RELEASING, None)
            self.fire_batch_deallocations(job, tasks, total)
        for name, tasks in by_node.items():
            node = self.nodes.get(name)
            if node is not None:
                node.bulk_release(tasks, self._resreq_sum(tasks))
        return moved

    def _resreq_sum(self, tasks):
        """The summed resreq of ``tasks``, to read and not to write: a
        single task's own Resource stands for its sum."""
        if len(tasks) == 1:
            return tasks[0].resreq
        total = self.spec.empty()
        for t in tasks:
            total.add_(t.resreq)
        return total

    def evict_batch(self, victims, reason: str,
                    claimant: Optional[TaskInfo] = None,
                    later: Optional[list] = None) -> None:
        """Evict ``victims`` (one claim's) for ``claimant``: the session's
        ledgers move once (:meth:`_release`), and the cache is told in one
        ``bulk_evict``: at once, or by whoever owns ``later`` when it is
        given (the cache's half is appended there; reclaim's replay hands a
        whole action's over when it ends)."""
        items = [(t, reason, claimant) for t in self._release(victims)]
        if later is not None:
            later.extend(items)
        else:
            self.cache.bulk_evict(items)

    def evict(self, task: TaskInfo, reason: str,
              claimant: Optional[TaskInfo] = None) -> None:
        """The batch of one."""
        self.cache.evict(self._release([task])[0], reason, claimant)

    def statement(self) -> "Statement":
        return Statement(self)

    def update_job_condition(self, job: JobInfo, condition: PodGroupCondition) -> None:
        """Upsert by type (session.go:366-388)."""
        if job.pod_group is None:
            return
        if (
            condition.type == "Unschedulable"
            and condition.status == "True"
            and condition.transition_id == self.uid
        ):
            self.unschedulable_marked.add(job.uid)
        cols = self.columns
        if cols is not None and job._cols is cols and job._row >= 0:
            # conditions feed the close pass's need-record set and its
            # touched-row visit — the delta close must see mid-cycle writes
            cols.j_has_conds[job._row] = True
            cols.j_touched[job._row] = True
        for i, c in enumerate(job.pod_group.conditions):
            if c.type == condition.type:
                job.pod_group.conditions[i] = condition
                return
        job.pod_group.conditions.append(condition)


class Statement:
    """All-or-nothing op log (statement.go:29-337): verbs mutate session
    state immediately and append ops; Commit replays against the cache,
    Discard undoes in reverse."""

    def __init__(self, ssn: Session):
        self.ssn = ssn
        self.operations: List[Tuple[str, tuple]] = []

    # -- session-visible verbs -------------------------------------------
    def evict_batch(self, victims, reason: str,
                    claimant: Optional[TaskInfo] = None) -> None:
        """``Session.evict_batch`` with the cache's half kept for
        :meth:`commit`; the operations hold the resident tasks that moved."""
        for own in self.ssn._release(victims):
            self.operations.append(("evict", (own, reason, claimant)))

    def evict(self, reclaimee: TaskInfo, reason: str,
              claimant: Optional[TaskInfo] = None) -> None:
        self.evict_batch([reclaimee], reason, claimant)

    def pipeline(self, task: TaskInfo, hostname: str) -> None:
        job = self.ssn.jobs.get(task.job)
        if job is not None:
            job.update_task_status(task, TaskStatus.PIPELINED)
        task.node_name = hostname
        node = self.ssn.nodes.get(hostname)
        if node is not None:
            node.add_task(task)
        self.ssn.pipelined_tasks.append(task)
        self.ssn._fire(True, task)
        self.operations.append(("pipeline", (task, hostname)))

    def allocate(self, task: TaskInfo, hostname: str) -> None:
        self.ssn.cache.allocate_volumes(task, hostname)
        job = self.ssn.jobs.get(task.job)
        if job is not None:
            job.update_task_status(task, TaskStatus.ALLOCATED)
        task.node_name = hostname
        node = self.ssn.nodes.get(hostname)
        if node is not None:
            node.add_task(task)
        self.ssn.allocated_tasks.append(task)
        self.ssn._fire(True, task)
        self.operations.append(("allocate", (task, hostname)))

    # -- terminal ---------------------------------------------------------
    def commit(self) -> None:
        # eviction-free statements (the allocate action's gang commits) batch
        # every bind under one cache lock; mixed statements replay in order
        if not any(name == "evict" for name, _ in self.operations):
            allocs = [args for name, args in self.operations if name == "allocate"]
            if allocs:
                for task, _ in allocs:
                    self.ssn.cache.bind_volumes(task)
                self.ssn.cache.bulk_bind(
                    [(task, task.node_name) for task, _ in allocs]
                )
                for task, _ in allocs:
                    job = self.ssn.jobs.get(task.job)
                    if job is not None:
                        job.update_task_status(task, TaskStatus.BINDING)
            self.operations = []
            return
        cache = self.ssn.cache
        evictions: list = []  # consecutive evicts reach the cache in one call
        for name, args in self.operations:
            if name == "evict":
                evictions.append(args)
            elif name == "pipeline":
                pass  # session-only state (statement.go pipeline no-ops on commit)
            elif name == "allocate":
                if evictions:
                    cache.bulk_evict(evictions)
                    evictions = []
                task, _ = args
                self.ssn.cache.bind_volumes(task)
                self.ssn.cache.bind(task, task.node_name)
                job = self.ssn.jobs.get(task.job)
                if job is not None:
                    job.update_task_status(task, TaskStatus.BINDING)
        if evictions:
            cache.bulk_evict(evictions)
        self.operations = []

    def discard(self) -> None:
        for name, args in reversed(self.operations):
            if name == "evict":
                self._unevict(args[0])
            elif name == "pipeline":
                task, _ = args
                self._unpipeline(task)
            elif name == "allocate":
                task, _ = args
                self._unallocate(task)
        self.operations = []

    # -- inverses (statement.go unevict/unpipeline/unallocate) ------------
    def _unevict(self, task: TaskInfo) -> None:
        job = self.ssn.jobs.get(task.job)
        if job is not None:
            job.update_task_status(task, TaskStatus.RUNNING)
        node = self.ssn.nodes.get(task.node_name)
        if node is not None:
            node.update_task(task)
        self.ssn._fire(True, task)

    def _unpipeline(self, task: TaskInfo) -> None:
        _undo_placement(self.ssn, task, release_volumes=False)
        self.ssn._fire(False, task)

    def _unallocate(self, task: TaskInfo) -> None:
        # release_volumes frees the PV reservation the allocate took — a
        # discarded gang must not hold volumes across cycles and starve
        # other claimants
        _undo_placement(self.ssn, task, release_volumes=True)
        self.ssn._fire(False, task)


# ---- session lifecycle (framework/framework.go:30-62) -------------------

def open_session(cache, tiers: List[Tier], plugin_options=None,
                 isolated: bool = False) -> Session:
    """Open a scheduling session: drop gang-invalid jobs (marking them
    unschedulable, session.go:107-124) and run every configured plugin's
    OnSessionOpen.

    Default is the EXCLUSIVE (no-clone) mode: the session takes ownership of
    the cache's own objects for the cycle — ingest and repair mutations are
    deferred by the cache until close, exactly the once-per-cycle staleness
    the reference's deep-cloned snapshot has, without paying the 50k-task
    clone or the commit-time double bookkeeping (the reference clones
    because informer goroutines race the session, cache.go:584-654; here
    the gate provides the same isolation). Session-only state (Pipelined
    placements) is unwound at close. `isolated=True` forces the reference's
    deep-clone behavior — callers that want to inspect a what-if session
    without touching the cache.

    Exclusive opens are INCREMENTAL when churn allows (cache/dirty.py):
    the per-job open structures — membership view, resolved priorities,
    at-open PodGroup statuses, fit-state clears, the column job-row arrays —
    are deltas against the previous cycle keyed on the cache's dirty sets,
    with the full rebuild as the bit-exact fallback for high churn,
    queue/priority-class row-space changes, or a cold cache."""
    from kube_batch_tpu.framework.interface import get_plugin_builder

    use_delta = False
    delta = None
    oc = None
    if isolated:
        cluster = cache.snapshot()
        ssn = Session(cache, cluster, tiers)
    else:
        cache.begin_exclusive_session()
        try:
            oc = getattr(cache, "open_cache", None)
            take = getattr(cache, "take_dirty", None)
            delta = take() if take is not None else None
            use_delta = (
                delta is not None
                and oc is not None
                and getattr(cache, "columns", None) is not None
                and getattr(cache, "delta_enabled", False)
                and oc.valid
                and not (delta.full or delta.queues_changed
                         or delta.priority_classes_changed)
                and len(delta.jobs) <= max(
                    32, cache.delta_churn_threshold * len(oc.jobs)
                )
            )
            if use_delta:
                cache.last_open_path = "delta"
                cache.last_churn = delta.churn_fraction(len(oc.jobs))
                cluster = cache.session_view_delta(delta)
            else:
                if delta is not None:
                    cache.last_open_path = "full"
                    cache.last_churn = delta.churn_fraction(len(cache.jobs))
                cluster = cache.session_view()
        except BaseException:
            cache.end_exclusive_session()
            raise
        try:
            ssn = Session(cache, cluster, tiers, exclusive=True,
                          open_reuse=oc if use_delta else None,
                          dirty_jobs=delta.jobs if use_delta else frozenset())
            if not use_delta and oc is not None:
                # reseed the cross-cycle open cache from this full rebuild —
                # BEFORE the gate mutates ssn.jobs (same dict as cluster.jobs)
                cache.rebuild_open_cache(cluster,
                                         ssn.pod_group_status_at_open)
        except BaseException:
            # same contract as the guards around it: never leave the gate
            # stuck, and never trust half-updated cross-cycle open state
            if oc is not None:
                oc.invalidate()
                cache.dirty.mark_full()
            cache.end_exclusive_session()
            raise
    try:
        cols = ssn.columns
        if cols is not None:
            # sync the column job-row arrays (j_sess membership, j_min,
            # j_queue, j_prio, j_creation, j_sched) before plugin opens —
            # proportion's vectorized open, the gang gate, the device
            # snapshot, and the close status pass all read them
            if use_delta:
                cols.sync_session_rows(ssn, dirty_uids=delta.jobs,
                                       restore_rows=oc.gate_dropped_rows)
            else:
                cols.sync_session_rows(ssn)
            ssn.rows_synced = True
        from kube_batch_tpu.obs.trace import tracer_of

        tracer = tracer_of(cache)
        for tier in tiers:
            for opt in tier.plugins:
                plugin = get_plugin_builder(opt.name)(opt.arguments)
                ssn.plugins.append(plugin)
                # the span IS the measurement (rule KBT014): the plugin
                # latency histogram feeds from its stamps
                with tracer.span("plugin:" + opt.name + ".open") as sp:
                    plugin.on_session_open(ssn)
                metrics.observe_plugin_latency(
                    opt.name, "OnSessionOpen", sp.dur_us
                )
        # gang-validity gate after plugins registered their JobValid fns.
        # Columnar sessions prefilter with one counts-matrix expression when
        # gang is the only JobValid voter (its verdict IS the count compare,
        # gang.go:48-69) — only the normally-sparse invalid set walks the
        # full dispatch for its reason string.
        valid_voters = set(ssn._fns.get(JOB_VALID, {}).keys())
        if cols is not None and ssn.rows_synced and valid_voters <= {"gang"} \
                and ssn.jobs:
            if not valid_voters:
                gate_jobs = []
            else:
                import numpy as np

                from kube_batch_tpu.api.columns import VALID_STATUSES

                rows, jobs_list = ssn.session_rows()
                valid_num = cols.j_counts[rows][:, VALID_STATUSES].sum(axis=1)
                gate_jobs = [
                    (jobs_list[i].uid, jobs_list[i])
                    for i in np.flatnonzero(valid_num < cols.j_min[rows])
                ]
        else:
            gate_jobs = list(ssn.jobs.items())
        dropped_rows = set()
        for uid, job in gate_jobs:
            reason = ssn.job_valid(job)
            if reason is not None:
                ssn.update_job_condition(
                    job,
                    PodGroupCondition(
                        type="Unschedulable",
                        status="True",
                        transition_id=ssn.uid,
                        reason="NotEnoughPods",
                        message=reason,
                    ),
                )
                cache.record_job_status_event(job)
                ssn.gate_dropped_jobs.append(job)
                ssn.drop_job(uid)
                if cols is not None and job._cols is cols and job._row >= 0:
                    # the dropped job leaves the device snapshot too; its
                    # row is remembered so the next delta open re-admits it
                    # for the gate's re-vote
                    cols.j_sess[job._row] = False
                    dropped_rows.add(job._row)
        if oc is not None:
            oc.gate_dropped_rows = dropped_rows
    except BaseException:
        if ssn.exclusive:
            # never leave the gate stuck; and a half-opened session may have
            # consumed dirty marks without refreshing the open cache — force
            # the next open to rebuild from scratch
            invalidate = getattr(cache, "open_cache", None)
            if invalidate is not None:
                invalidate.invalidate()
                cache.dirty.mark_full()
            cache.end_exclusive_session()
        raise
    return ssn


def job_status(ssn: Session, job: JobInfo) -> None:
    """Derive and set the PodGroup phase/counts (session.go:151-189).

    Shadow PodGroups (synthesized for plain pods, cache/util.go:42-60) carry
    NO durable phase: in the reference the jobUpdater's CRD write fails for
    them and the informer-fed mirror keeps the phase empty, so an
    unschedulable plain pod is retried every cycle even without the enqueue
    action.  The no-clone session must reproduce that by not writing the
    phase onto the synthesized object."""
    pg = job.pod_group
    if pg is None:
        return
    if pg.shadow:
        pg.running = len(job.task_status_index.get(TaskStatus.RUNNING, {}))
        pg.failed = len(job.task_status_index.get(TaskStatus.FAILED, {}))
        pg.succeeded = len(job.task_status_index.get(TaskStatus.SUCCEEDED, {}))
        return
    unschedulable = any(
        c.type == "Unschedulable" and c.status == "True" and c.transition_id == ssn.uid
        for c in pg.conditions
    )
    running = len(job.task_status_index.get(TaskStatus.RUNNING, {}))
    if running and unschedulable:
        pg.phase = PodGroupPhase.UNKNOWN
    else:
        allocated = job.task_num(
            TaskStatus.BOUND, TaskStatus.BINDING, TaskStatus.RUNNING, TaskStatus.ALLOCATED
        )
        if allocated >= pg.min_member:
            pg.phase = PodGroupPhase.RUNNING
        elif pg.phase != PodGroupPhase.INQUEUE:
            pg.phase = PodGroupPhase.PENDING
    pg.running = running
    pg.failed = len(job.task_status_index.get(TaskStatus.FAILED, {}))
    pg.succeeded = len(job.task_status_index.get(TaskStatus.SUCCEEDED, {}))


def _undo_placement(ssn: Session, task: TaskInfo, release_volumes: bool) -> None:
    """The shared placement-undo core: status→PENDING, node removal,
    node_name cleared, and (for allocates) volume reservation release.
    Used by Statement discard inverses (which additionally fire deallocate
    events) and the exclusive-close residue revert (which doesn't — plugin
    session state dies with the session anyway)."""
    job = ssn.jobs.get(task.job)
    if job is not None and task.key() in job.tasks:
        job.update_task_status(task, TaskStatus.PENDING)
    node = ssn.nodes.get(task.node_name) if task.node_name else None
    if node is not None and task.key() in node.tasks:
        node.remove_task(task)
    task.node_name = None
    if release_volumes:
        task.volume_ready = False
        release = getattr(ssn.cache.volume_binder, "release_task", None)
        if release is not None:
            release(task.uid)


def _revert_residue(ssn: Session, tasks: List[TaskInfo], expected: TaskStatus,
                    release_volumes: bool) -> None:
    """Revert session-only placements still in `expected` status back to
    PENDING on the live objects (exclusive close; the reference's clone takes
    such state to the grave). The status guard makes this idempotent —
    dispatched / discarded / transitioned tasks are skipped."""
    for task in tasks:
        if task.status != expected:
            continue
        _undo_placement(ssn, task, release_volumes)


def _close_status_columnar(ssn: Session) -> None:
    """The close-session status pass driven by the counts matrix: phase
    derivation (job_status) becomes vectorized arithmetic; per-job work is
    paid only by jobs whose status changed or that have something to report.
    End state equals the per-job loop's.

    DELTA form (this PR): the j_counts choke points already know which jobs
    moved — every count write (JobInfo's index choke points, the columnar
    replay's vectorized update), every session row re-sync (dirty jobs at
    open; ALL rows on a full-rebuild open), and every mid-cycle phase/
    condition write stamps ``cols.j_touched``.  A row NOT stamped since the
    last close provably has identical derivation inputs (counts, phase,
    min_member, unschedulable marks), so its phase/count writes would be
    no-ops and its at-open compare would read unchanged — the per-job visit
    therefore covers only touched rows plus the standing need-record set
    (stuck tasks, Pending/Unknown phases, condition-bearing jobs, PDB jobs
    with Pending tasks), and the per-queue phase counts come off the
    j_phase column as one bincount over every session row.
    ``KB_DELTA_CLOSE=0`` forces the full visit (the bit-exact oracle the
    equivalence tests compare against).

    The count columns are pulled into plain Python lists once (numpy scalar
    indexing inside the visit loop costs more than the loop body) and the
    per-job conditions scan is replaced by the session's unschedulable-mark
    set (update_job_condition records the uids as it writes the conditions —
    transition_id == ssn.uid is exactly 'marked this session')."""
    import os

    import numpy as np

    from kube_batch_tpu.api.columns import CODE_PHASE, N_PHASES, PHASE_CODE

    cols = ssn.columns
    rows_all = np.flatnonzero(cols.j_sess)
    jc = cols.j_counts
    PEND_I, ALLOC_I = int(TaskStatus.PENDING), int(TaskStatus.ALLOCATED)
    pend_code = PHASE_CODE[PodGroupPhase.PENDING]
    unk_code = PHASE_CODE[PodGroupPhase.UNKNOWN]
    delta_close = os.environ.get("KB_DELTA_CLOSE", "").strip().lower() not in (
        "0", "false", "off", "no"
    )
    if delta_close and rows_all.size:
        phase_codes = cols.j_phase[rows_all]
        stuck_rows = (jc[rows_all, PEND_I] + jc[rows_all, ALLOC_I]) > 0
        visit = (
            cols.j_touched[rows_all]
            | stuck_rows
            | (phase_codes == pend_code)
            | (phase_codes == unk_code)
            | cols.j_has_conds[rows_all]
            | (~cols.j_has_pg[rows_all] & cols.j_pdb[rows_all]
               & (jc[rows_all, PEND_I] > 0))
        )
        rows = rows_all[visit]
    else:
        rows = rows_all
    jobs_list = [cols.job_by_row[r] for r in rows.tolist()]
    counts = cols.j_counts[rows]
    running_l = counts[:, int(TaskStatus.RUNNING)].tolist()
    failed_l = counts[:, int(TaskStatus.FAILED)].tolist()
    succ_l = counts[:, int(TaskStatus.SUCCEEDED)].tolist()
    pending_l = counts[:, int(TaskStatus.PENDING)].tolist()
    # phase derives from pg.min_member, NOT job.min_available (minav): a job
    # carrying both a PodGroup and a PDB has min_available overwritten by
    # the PDB while job_status (session.go:151-189) still compares against
    # the PodGroup's MinMember
    alloc_l = (
        counts[:, int(TaskStatus.BOUND)]
        + counts[:, int(TaskStatus.BINDING)]
        + counts[:, int(TaskStatus.RUNNING)]
        + counts[:, int(TaskStatus.ALLOCATED)]
    ).tolist()
    # tasks stuck Pending/Allocated → fit-error conditions must be written
    # (record_job_status_event's has_stuck gate, cache.go:704-719)
    stuck_l = (
        counts[:, int(TaskStatus.PENDING)] + counts[:, int(TaskStatus.ALLOCATED)]
    ).tolist()
    prev_map = ssn.pod_group_status_at_open
    prev_get = prev_map.get
    unsched_marked = ssn.unschedulable_marked
    RUNNING, PENDING, UNKNOWN, INQUEUE = (
        PodGroupPhase.RUNNING, PodGroupPhase.PENDING,
        PodGroupPhase.UNKNOWN, PodGroupPhase.INQUEUE,
    )
    record_event = ssn.cache.record_job_status_event
    updates = []
    append = updates.append
    rows_l = rows.tolist()
    j_phase = cols.j_phase
    for i, job in enumerate(jobs_list):
        pg = job.pod_group
        if pg is None:
            if job.pdb is not None and pending_l[i]:
                record_event(job)
            continue
        r, f, s = running_l[i], failed_l[i], succ_l[i]
        if pg.shadow:
            # no durable phase for synthesized groups (see job_status) —
            # but changed counts still write, like the per-job path
            pg.running, pg.failed, pg.succeeded = r, f, s
            changed = prev_get(job.uid) != (pg.phase, r, f, s)
            if changed or stuck_l[i]:
                append((job, changed, bool(stuck_l[i])))
            continue
        if r and job.uid in unsched_marked:
            phase = UNKNOWN
        elif alloc_l[i] >= pg.min_member:
            phase = RUNNING
        elif pg.phase != INQUEUE:
            phase = PENDING
        else:
            phase = pg.phase
        pg.phase, pg.running, pg.failed, pg.succeeded = phase, r, f, s
        j_phase[rows_l[i]] = PHASE_CODE[phase]
        changed = prev_get(job.uid) != (phase, r, f, s)
        need_record = bool(stuck_l[i]) or phase is PENDING or phase is UNKNOWN
        if changed or need_record or pg.conditions:
            append((job, changed, need_record))
    # per-queue podgroup-phase counts (QueueStatus writeback): one bincount
    # over EVERY session row's j_phase — visited rows were just rewritten,
    # unvisited rows' phases provably could not move this cycle
    qcounts: Dict[str, dict] = {}
    if rows_all.size:
        qmask = cols.j_has_pg[rows_all] & ~cols.j_shadow[rows_all]
        sel = rows_all[qmask]
        pcodes = cols.j_phase[sel]
        ok = pcodes >= 0
        sel, pcodes = sel[ok], pcodes[ok]
        if sel.size:
            pairs = cols.j_queue[sel].astype(np.int64) * N_PHASES + pcodes
            bc = np.bincount(
                pairs, minlength=cols.queues.cap * N_PHASES
            ).reshape(cols.queues.cap, N_PHASES)
            for qi in np.flatnonzero(bc.any(axis=1)).tolist():
                qc = queue_phase_counts()
                for code in range(N_PHASES):
                    qc[CODE_PHASE[code].value.lower()] = int(bc[qi, code])
                qcounts[cols.queue_names[qi]] = qc
    _count_gate_dropped(ssn, qcounts)
    # consumed: ingest that lands after this point (deferred mutations,
    # residue reverts) re-stamps rows for the next cycle's visit
    cols.j_touched[:] = False
    return updates, qcounts


def _count_gate_dropped(ssn: Session, qcounts: Dict[str, dict]) -> None:
    """Fold the podgroups of gang-invalid jobs (deleted from ssn.jobs by the
    open gate, session.go:107-124) into the queue phase counts — QueueStatus
    counts podgroups by phase, not by session membership; without this a
    queue whose only podgroups are gang-invalid would zero out while the
    cluster still holds its Pending groups."""
    for job in ssn.gate_dropped_jobs:
        pg = job.pod_group
        if pg is None or pg.shadow or job.queue not in ssn.queues:
            continue
        qc = qcounts.get(job.queue)
        if qc is None:
            qc = qcounts[job.queue] = queue_phase_counts()
        qc[(pg.phase or PodGroupPhase.PENDING).value.lower()] += 1


def close_session(ssn: Session, stage_flush: bool = False,
                  release: bool = True):
    """Plugin close hooks then the job updater (framework.go:55-62 +
    job_updater.go:33-122, sans the 16-worker pool — the host loop is cold).
    Exclusive sessions additionally unwind Pipelined placements (session-only
    state, gone with a cloned session) and release the cache gate.

    ``release=False`` leaves that last step, :func:`release_session`, to the
    caller, who MUST make it: the pipelined cycle re-arms the what-if lease
    in between, on the state every later reader will see (the status pass
    has stamped, the session-only placements are unwound) and while nothing
    else can move it.

    ``stage_flush=True`` is the pipelined cycle's close: the status pass
    still DERIVES everything synchronously (phase writes, dirty stamps,
    rate-limit bookkeeping, queue-delta decisions — all the state the next
    session open depends on), but the egress half is returned as a
    value-snapshotted ``StatusFlush`` for the writeback stage to run
    overlapped with the next cycle, and the async binder drain is left to
    that same stage (``_inflight_bind_hosts`` protects deferred ingest
    against the unacked window).  Serial callers get ``None`` and identical
    behavior to before the split — stage + run back-to-back."""
    from kube_batch_tpu.obs.trace import tracer_of

    tracer = tracer_of(ssn.cache)
    flush = None
    try:
        for plugin in ssn.plugins:
            with tracer.span("plugin:" + plugin.name + ".close") as sp:
                plugin.on_session_close(ssn)
            metrics.observe_plugin_latency(
                plugin.name, "OnSessionClose", sp.dur_us
            )
        if ssn.columns is not None and ssn.rows_synced and ssn.jobs:
            updates, qcounts = _close_status_columnar(ssn)
            flush = ssn.staged_flush = ssn.cache.stage_status_flush(
                updates, qcounts)
            if not stage_flush:
                ssn.cache.run_status_flush(flush)
                flush = ssn.staged_flush = None
        else:
            qcounts: Dict[str, dict] = {}
            for job in ssn.jobs.values():
                if job.pod_group is None:
                    # PDB-defined jobs get events only, no status writeback
                    # (job_updater.go:108-111; unschedulable iff tasks stay
                    # Pending, cache.go:699)
                    if job.pdb is not None and job.task_status_index.get(
                        TaskStatus.PENDING
                    ):
                        ssn.cache.record_job_status_event(job)
                    continue
                job_status(ssn, job)
                pg = job.pod_group
                if not pg.shadow and pg.phase is not None:
                    qc = qcounts.setdefault(job.queue, queue_phase_counts())
                    qc[pg.phase.value.lower()] += 1
                ssn.cache.update_job_status(
                    job, prev_status=ssn.pod_group_status_at_open.get(job.uid)
                )
            _count_gate_dropped(ssn, qcounts)
            if stage_flush:
                # the pipelined loop reaches this branch only for EMPTY
                # sessions (exclusive sessions always carry columns): the
                # per-job loop above did nothing, and the queue zero-outs
                # must go through the same staged handoff — an inline write
                # here would race the previous cycle's writeback worker,
                # breaking the single-status-writer design
                flush = ssn.staged_flush = ssn.cache.stage_status_flush(
                    (), qcounts)
            else:
                ssn.cache.update_queue_statuses(qcounts)
    finally:
        if ssn.exclusive:
            # revert surviving Pipelined placements: they exist only inside
            # a session (the reference's clone takes them to the grave;
            # statement.go pipeline no-ops on commit) — next cycle re-derives
            # them from fresh Releasing capacity
            _revert_residue(ssn, ssn.pipelined_tasks, TaskStatus.PIPELINED,
                            release_volumes=False)
            # likewise ALLOCATED residue: allocate only becomes durable via
            # dispatch (ALLOCATED→BINDING when the job turns ready); a task
            # still ALLOCATED here belongs to a job that never became ready
            # this cycle (e.g. backfill into an unready gang) and must not
            # leak node/volume accounting onto the authoritative cache
            _revert_residue(ssn, ssn.allocated_tasks, TaskStatus.ALLOCATED,
                            release_volumes=True)
            if not stage_flush:
                # drain binder acks BEFORE applying deferred ingest: a
                # deferred pod update must observe the durable bindings
                # (pod.node_name) this cycle produced, or it would clobber
                # them.  The pipelined close leaves the drain to the
                # writeback stage — deferred ingest racing the unacked
                # window is protected by the cache's in-flight bind map.
                drain = getattr(ssn.cache, "flush_binds", None)
                if drain is not None:
                    drain()
        if release:
            release_session(ssn)
    return flush


def release_session(ssn: Session) -> None:
    """The last step of a close: an exclusive session hands the cache back
    (the mutations deferred during the cycle apply, in order) and the
    session lets go of everything it held."""
    if ssn.exclusive:
        ssn.cache.end_exclusive_session()
    ssn.jobs = {}
    ssn.nodes = {}
    ssn.queues = {}
    ssn.plugins = []
    ssn.pipelined_tasks = []
    ssn.allocated_tasks = []

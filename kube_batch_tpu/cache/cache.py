"""SchedulerCache — the host-side cluster mirror (pkg/scheduler/cache).

Mirrors cache.go:71-736 + event_handlers.go: a mutex-guarded in-memory image
of pods/nodes/podgroups/queues/priorityclasses, fed by event-handler calls
(the standalone analog of the 10 informers wired at cache.go:256-336), with
Bind/Evict egress through pluggable Binder/Evictor seams, a failed-write
resync queue, and a deep-clone Snapshot consumed by each session.

The device snapshot (api/snapshot.py) is built *from* the session's clone;
this cache stays pure host Python — it is not on the hot path (one snapshot
per cycle)."""

from __future__ import annotations

import logging
import threading
from collections import Counter
from typing import Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from kube_batch_tpu.api.cluster_info import ClusterInfo
from kube_batch_tpu.api.job_info import JobInfo
from kube_batch_tpu.api.node_info import NodeInfo
from kube_batch_tpu.api.pod import Node, Pod, PodGroup, PriorityClass, Queue
from kube_batch_tpu.api.queue_info import QueueInfo
from kube_batch_tpu.api.resources import DEFAULT_SPEC, ResourceSpec
from kube_batch_tpu.api.task_info import TaskInfo, job_id_for_pod
from kube_batch_tpu.api.types import (
    PodGroupPhase,
    TaskStatus,
    is_allocated,
    queue_phase_counts,
)
from kube_batch_tpu.cache.fake import (
    FakeBinder,
    FakeEvictor,
    FakeStatusUpdater,
    FakeVolumeBinder,
)
from kube_batch_tpu.k8s.transport import CircuitOpenError
from kube_batch_tpu.utils import telemetry
from kube_batch_tpu.utils.assertions import graft_assert

logger = logging.getLogger("kube_batch_tpu")


class EventLog:
    """The k8s Events recorder analog: an append-only record of
    (kind, object_key, message) tuples with BOUNDED retention (the k8s
    event recorder's queue is bounded too; this is a diagnostic record, not
    a durable store).

    `append_scheduled_batch` records a whole cycle's Scheduled events by
    REFERENCE to the dispatcher's staged list and expands them lazily on
    iteration — building 50k tuples inside the bind drain cost ~30 ms of
    the close phase for a record nothing reads on the hot path.  Because a
    batch pins its staged (task, hostname, pod) triples, the retention
    bound matters doubly: once the log exceeds `max_events`, the oldest
    entries (and the object graphs a batch holds) are dropped and counted."""

    __slots__ = ("_entries", "_n", "max_events", "dropped")

    def __init__(self, max_events: int = 200_000):
        from collections import deque

        self._entries = deque()
        self._n = 0
        self.max_events = max_events
        self.dropped = 0

    def _trim(self) -> None:
        while self._n > self.max_events and len(self._entries) > 1:
            e = self._entries.popleft()
            k = len(e) if type(e) is _ScheduledBatch else 1
            self._n -= k
            self.dropped += k

    def append(self, ev: tuple) -> None:
        self._entries.append(ev)
        self._n += 1
        self._trim()

    def extend(self, evs) -> None:
        for ev in evs:
            self.append(ev)

    def append_scheduled_batch(self, staged) -> None:
        """staged: [(task, hostname, pod)] — key/hostname are read at
        iteration time (both immutable once the bind dispatched)."""
        batch = _ScheduledBatch(staged)
        self._entries.append(batch)
        self._n += len(batch)
        self._trim()

    def clear(self) -> None:
        self._entries.clear()
        self._n = 0

    def __iter__(self):
        for e in list(self._entries):
            if type(e) is _ScheduledBatch:
                yield from e
            else:
                yield e

    def __len__(self) -> int:
        return self._n

    def __bool__(self) -> bool:
        return bool(self._entries)


class StatusFlush:
    """One cycle's staged status egress — the value-snapshotted handoff
    between the close-derive stage and the writeback stage (see
    SchedulerCache.stage_status_flush / run_status_flush).  Carries no live
    session or job references by construction: PodGroup CLONES to write,
    pre-rendered event/condition ops, decided queue writes, the queue shed
    count, and the degraded verdict taken at stage time."""

    __slots__ = ("to_write", "ops", "qwrites", "shed_queues", "degraded")

    def __init__(self, to_write, ops, qwrites, shed_queues, degraded):
        self.to_write = to_write
        self.ops = ops
        self.qwrites = qwrites
        self.shed_queues = shed_queues
        self.degraded = degraded

    def __bool__(self) -> bool:
        return bool(self.to_write or self.ops or self.qwrites
                    or self.shed_queues)


class _ScheduledBatch:
    __slots__ = ("_staged",)

    def __init__(self, staged):
        self._staged = staged

    def __iter__(self):
        for task, hostname, pod in self._staged:
            if pod is not None:
                yield ("Scheduled", task._key, hostname)

    def __len__(self):
        return sum(1 for _t, _h, pod in self._staged if pod is not None)


class SchedulerCache:
    def __init__(
        self,
        spec: ResourceSpec = DEFAULT_SPEC,
        scheduler_name: str = "volcano",
        default_queue: str = "default",
        binder=None,
        evictor=None,
        status_updater=None,
        volume_binder=None,
        resolve_priority: bool = True,
    ):
        self.spec = spec
        self.scheduler_name = scheduler_name
        self.default_queue = default_queue
        # the persistent columnar host model (api/columns.py): rows assigned
        # at ingest, ledgers shared as views, snapshots built from columns
        from kube_batch_tpu.api.columns import ColumnStore

        self.columns = ColumnStore(spec)
        # cross-cycle churn bookkeeping (cache/dirty.py): ingest handlers
        # stamp a monotonic version + per-kind dirty sets so a low-churn
        # session open can hand out a delta against the previous cycle's
        # open state instead of re-deriving every per-job structure
        from kube_batch_tpu.cache.dirty import DirtyTracker, OpenCache

        self.dirty = DirtyTracker()
        self.open_cache = OpenCache()
        # jobs carrying per-session fit diagnostics (nodes_fit_delta/
        # nodes_fit_errors/job_fit_errors) — the delta open clears exactly
        # these instead of probing all 12.5k jobs (Session.note_fit_state)
        self.fit_state_jobs: set = set()
        import os as _os

        self.delta_enabled = _os.environ.get(
            "KB_SNAPSHOT_DELTA", "1"
        ).strip().lower() not in ("0", "false", "off", "no")
        # fraction of session jobs dirty above which the open falls back to
        # the full rebuild (delta bookkeeping would cost more than it saves)
        self.delta_churn_threshold = float(
            _os.environ.get("KB_DELTA_CHURN_THRESHOLD", "0.25")
        )
        # diagnostics: which path the most recent open took, and its churn
        self.last_open_path = "full"
        self.last_churn = 0.0
        # dirty-tracker version token of the most recent session open — the
        # query plane's snapshot_version (serve/lease.py): a lease published
        # for cycle N reports exactly the ingest state that open consumed
        self.last_open_version = 0
        # its twin at the other end of the session: the version as the last
        # exclusive session handed the cache back, its own binds, evictions
        # and close-time status stamps included, read before the deferred
        # events apply (None until a session has closed).  The cycle stamps
        # its re-armed what-if lease with it, and a floor wake that finds
        # the tracker still there knows nothing was applied since
        self.last_close_version: Optional[int] = None
        # the serve/ query plane, when one is attached (QueryPlane.__init__
        # sets it); the allocate action publishes its per-cycle lease here
        self.query_plane = None
        # --priority-class toggle (options.go:30, consumed cache.go:352,378)
        self.resolve_priority = resolve_priority
        self.binder = binder if binder is not None else FakeBinder()
        self.evictor = evictor if evictor is not None else FakeEvictor()
        # the standalone deployment's eviction feed (cache/evictions.py),
        # when the entry point installed one: GET /v1/evictions serves it
        self.eviction_log = None
        # observation of evictions in flight: when each was ordered and for
        # whom (victim key → (perf_counter, claimant key), closed by the
        # victim's DELETE), how many victims of each claimant are in flight,
        # and the claimants that were ever given victims
        self._evict_ordered_at: Dict[str, Tuple[float, str]] = {}
        self._evict_in_flight: Dict[str, int] = {}
        self._evict_claimants: Set[str] = set()
        self.status_updater = status_updater or FakeStatusUpdater()
        self.volume_binder = volume_binder or FakeVolumeBinder()
        self._lock = threading.RLock()
        self.jobs: Dict[str, JobInfo] = {}
        self.nodes: Dict[str, NodeInfo] = {}
        self.queues: Dict[str, QueueInfo] = {}
        self.priority_classes: Dict[str, PriorityClass] = {}
        self.default_priority: int = 0
        # failed bind/evict tasks awaiting resync (cache.go:559-581) — a
        # bounded backoff queue with poison quarantine (cache/resync.py)
        # instead of the seed's flat retry-every-tick list
        from kube_batch_tpu.cache.resync import ResyncQueue

        self.resync = ResyncQueue(
            backoff_cap=int(_os.environ.get("KB_RESYNC_BACKOFF_CAP", "8")),
            poison_after=int(_os.environ.get("KB_RESYNC_POISON", "5")),
            max_entries=int(_os.environ.get("KB_RESYNC_MAX", "4096")),
        )
        # degraded-cycle signal: while True (set by the scheduler when the
        # cycle's soft time budget elapsed) or while the writeback breaker
        # is open, close-time status flushes shed to the async pool / skip
        self.shed_status_writes = False
        # pod store: the standalone source of truth the resync loop re-GETs
        # from (the apiserver analog)
        self.pods: Dict[str, Pod] = {}
        self.events = EventLog()  # (kind, object_key, message) record
        # last written PodScheduled condition per pod key (dedup,
        # cache.go:151-173 podConditionHaveUpdate)
        self.pod_conditions: Dict[str, dict] = {}
        # per-job earliest next condition-only status write (job_updater.go:20-31)
        self._status_next_write: Dict[str, float] = {}
        # last written QueueStatus counts per queue (delta suppression)
        self._queue_status_written: Dict[str, dict] = {}
        # async dispatcher for binder calls (the `go func` at cache.go:478):
        # cache bookkeeping stays under the lock, the API write happens off
        # the scheduling cycle; failures re-enter via resync_task
        self._dispatch_pool = None
        self._dispatch_futures: List = []
        # leaf mutex over the futures list: the writeback worker's
        # flush_binds races the cycle thread's _dispatch_async in the
        # pipelined loop (never held across a join or a binder call)
        self._dispatch_mu = threading.Lock()
        # close-time status-writeback pool (jobUpdater's 16 workers,
        # job_updater.go:18) — created lazily for parallel-safe updaters
        self._status_pool = None
        # background repair loop (cache.go:342-384) — started by run()
        self._repair_thread: Optional[threading.Thread] = None
        self._repair_stop = threading.Event()
        # initial-sync barrier (WaitForCacheSync analog, cache.go:363-384)
        self._synced = threading.Event()
        # exclusive-session gate: while a scheduling cycle owns the cache
        # (the no-clone session mode), ingest/repair mutations are DEFERRED
        # and applied at session close — the same once-per-cycle staleness an
        # informer snapshot has, without paying the deep clone
        self._session_active = False
        self._deferred: List = []
        # read-side ingest staging (the pipelined loop's ingest stage): when
        # enabled, the public ingest surface appends (fn, args) under a small
        # LEAF lock instead of contending on the big lock, so a watch/ingest
        # thread never stalls behind a snapshot or replay in progress; the
        # cycle applies the whole buffer under ONE big-lock acquisition at
        # its ingest stage (drain_staged_ingest)
        self._ingest_lock = threading.Lock()
        self._ingest_staged: List = []
        self.ingest_staging = False
        # thread idents currently applying ingest DIRECTLY (the staged
        # drain, a batched apply): their re-entrant handler calls must
        # not re-stage.  A SET, not a single slot — the cycle's drain
        # and a /v1 batch apply can overlap, and a shared slot's
        # save/restore would clobber the other thread's marker (the
        # drain would then re-stage its own events and apply nothing).
        # Adds/discards of own ident only; reads are GIL-atomic.
        self._direct_apply_threads: set = set()
        # threads inside the cycle's staged-ingest DRAIN specifically:
        # their dirty advances must not re-wake the trigger (see
        # _dirty_advanced) — a subset of the direct appliers
        self._cycle_drain_threads: set = set()
        # the event-driven cycle trigger's wake callback (pipeline.py
        # CycleTrigger.notify): fired on staged ingest arrival and on dirty
        # version advances that happen outside a session (repair rebuilds,
        # deferred-ingest application) — never on the cycle's own close-time
        # status bookkeeping, which would re-trigger every cycle
        self._ingest_listener = None
        self.dirty.on_advance = self._dirty_advanced
        # binder dispatches in flight (pod key → hostname), staged when the
        # async dispatcher takes a batch and cleared by its ack/failure:
        # update_pod consults it so a client update arriving between the
        # dispatch and the ack cannot clobber the in-flight binding (the
        # pipelined loop overlaps the binder drain with the next cycle's
        # ingest, which widens that window from ~0 to a whole stage)
        self._inflight_bind_hosts: Dict[str, str] = {}
        # pod-arrival timestamps (key → perf_counter) for the arrival→
        # bind-decision latency histogram; stamped at ingest for pending
        # unbound owned pods, popped at the bind decision or pod deletion
        self._arrival_ts: Dict[str, float] = {}
        # bind decisions made so far (bind / bulk_bind, under the big lock);
        # the event-driven loop reads its growth over a cycle as progress
        self.binds_total = 0
        # the order in which the live pods' binds were decided (pod key ->
        # its number among all binds so far): ``GET /v1/bindings`` reports
        # it, so a client can walk the binds as they were made (an
        # order-sensitive check such as required inter-pod affinity's)
        self.bind_seq: Dict[str, int] = {}

    # ------------------------------------------------------------------
    # exclusive-session gate (no-clone session mode)
    # ------------------------------------------------------------------
    def begin_exclusive_session(self) -> None:
        with self._lock:
            graft_assert(not self._session_active,
                         "nested exclusive sessions are not supported")
            self._session_active = True

    def end_exclusive_session(self) -> None:
        """Release the cycle's ownership and apply every mutation that
        arrived during it, in order."""
        with self._lock:
            self._session_active = False
            self.last_close_version = self.dirty.version
            deferred, self._deferred = self._deferred, []
            for fn, args in deferred:
                try:
                    fn(*args)
                except Exception:  # noqa: BLE001 — one bad event must not
                    logger.exception("deferred ingest event failed")

    def _gate(self, fn, *args) -> bool:
        """Returns True when the mutation was deferred (session active)."""
        if self._session_active:
            self._deferred.append((fn, args))
            return True
        return False

    # ------------------------------------------------------------------
    # ingest staging + event trigger (the pipelined loop's ingest stage)
    # ------------------------------------------------------------------
    def _dirty_advanced(self) -> None:
        """DirtyTracker version-advance hook: wake the cycle trigger for
        out-of-session churn (ingest, repair rebuilds, deferred events).
        In-session advances are the cycle's own bookkeeping — the deferred
        events that carry real churn re-stamp when they apply at close.
        The cycle's OWN staged-ingest drain is suppressed too: the session
        about to open consumes exactly that churn, and re-waking would
        schedule a guaranteed no-op follow-up cycle after every burst.  A
        direct batch apply (ingest_batch with staging off) is NOT a drain
        — its one coalesced advance must wake the loop."""
        # kbt: allow[KBT301] lock-free wake hint — a stale read costs at
        # most one extra (cheap, idempotent) trigger wake, never a miss
        if self._session_active:
            return
        if threading.get_ident() in self._cycle_drain_threads:
            return
        fn = self._ingest_listener
        if fn is not None:
            fn()

    def set_ingest_signal(self, fn) -> None:
        """Register (or clear, fn=None) the event-trigger wake callback.
        Must never block: it runs under the cache's big lock from dirty
        stamps and under the ingest staging lock from _stage."""
        self._ingest_listener = fn

    def enable_ingest_staging(self) -> None:
        with self._ingest_lock:
            self.ingest_staging = True

    def disable_ingest_staging(self) -> None:
        with self._ingest_lock:
            self.ingest_staging = False
        self.drain_staged_ingest()

    def _stage(self, fn, *args) -> bool:
        """Stage an ingest mutation instead of applying it (True when
        staged).  OFF by default (one attribute read); the drain thread
        itself always applies directly (its re-entrant calls must not
        re-stage).  The wake signal fires OUTSIDE the staging lock so the
        trigger's condition lock stays unordered against it."""
        # kbt: allow[KBT301] double-checked peek — re-read under the lock
        if not self.ingest_staging:
            return False
        # kbt: allow[KBT301] own-ident set membership is GIL-atomic
        if threading.get_ident() in self._direct_apply_threads:
            return False
        with self._ingest_lock:
            if not self.ingest_staging:
                return False
            self._ingest_staged.append((fn, args))
        fn2 = self._ingest_listener
        if fn2 is not None:
            fn2()
        return True

    def _note_staged_arrival(self, obj) -> None:
        """Arrival→decision clocks start at TRUE ingest: a staged pending
        pod is stamped when it lands in the staging buffer, not when the
        next cycle's drain applies it — otherwise the latency metric
        undercounts the stage→drain wait in exactly the mode it exists to
        measure.  The apply-time stamp in _add_task is conditional on the
        key being absent, so this earlier stamp survives the drain.
        Setdefault on a plain dict is GIL-atomic; non-pod kinds no-op."""
        if isinstance(obj, Pod) and obj.node_name is None:
            # kbt: allow[KBT301] setdefault on a plain dict is GIL-atomic
            self._arrival_ts.setdefault(obj.key(), telemetry.perf_counter())

    def drain_staged_ingest(self) -> int:
        """Apply every staged ingest event under ONE big-lock acquisition —
        the pipeline's ingest stage.  Events apply in arrival order; a bad
        event logs and is skipped (informer handler semantics)."""
        with self._ingest_lock:
            staged, self._ingest_staged = self._ingest_staged, []
        if not staged:
            return 0
        ident = threading.get_ident()
        # kbt: allow[KBT301] own-ident set ops are GIL-atomic: each thread
        # only ever adds/discards ITS OWN ident, so no two threads contend
        # on the same element and a torn composite read is impossible
        nested = ident in self._direct_apply_threads
        # kbt: allow[KBT301] own-ident set add is GIL-atomic (see above)
        self._direct_apply_threads.add(ident)
        self._cycle_drain_threads.add(ident)
        try:
            with self._lock:
                for fn, args in staged:
                    try:
                        fn(*args)
                    except Exception:  # noqa: BLE001 — one bad event
                        logger.exception("staged ingest event failed")
        finally:
            self._cycle_drain_threads.discard(ident)
            if not nested:
                # kbt: allow[KBT301] own-ident set discard is GIL-atomic
                self._direct_apply_threads.discard(ident)
        return len(staged)

    def ingest_batch(self, ops) -> int:
        """Apply ``[(fn, obj)]`` ingest operations under one lock
        acquisition and ONE dirty-version advance (the batched ``/v1/*``
        ingest path: high-QPS clients pay a single lock round-trip per
        batch, and the lease/delta version token moves once).  With
        staging enabled the whole batch stages under one staging-lock
        acquisition + one wake instead.

        Returns the number of operations APPLIED (staging: accepted for the
        next cycle's drain).  A handler that raises drops only its own
        element — callers compare against ``len(ops)`` to detect partial
        failure."""
        if not ops:
            return 0
        if (self.ingest_staging  # kbt: allow[KBT301] double-checked peek
                # kbt: allow[KBT301] own-ident set membership is GIL-atomic
                and threading.get_ident() not in self._direct_apply_threads):
            with self._ingest_lock:
                if self.ingest_staging:
                    self._ingest_staged.extend(
                        (fn, (obj,)) for fn, obj in ops
                    )
                    staged = True
                else:
                    staged = False
            if staged:
                for _fn, obj in ops:
                    self._note_staged_arrival(obj)
                fn2 = self._ingest_listener
                if fn2 is not None:
                    fn2()
                return len(ops)
        with self._lock:
            # mark this thread as a direct applier so a handler re-entered
            # here never re-stages (staging could flip on mid-batch)
            ident = threading.get_ident()
            nested = ident in self._direct_apply_threads
            self._direct_apply_threads.add(ident)
            self.dirty.hold_version()
            applied = 0
            try:
                for fn, obj in ops:
                    try:
                        fn(obj)
                        applied += 1
                    except Exception:  # noqa: BLE001 — one bad event
                        logger.exception("batched ingest event failed")
            finally:
                self.dirty.release_version()
                if not nested:
                    self._direct_apply_threads.discard(ident)
        return applied

    # ------------------------------------------------------------------
    # background repair loops (cache.go:342-384)
    # ------------------------------------------------------------------
    def run(self, resync_period: float = 1.0) -> None:
        """Start the background repair thread — the processResyncTask +
        processCleanupJob goroutines (cache.go:342-384, 533-581). Idempotent;
        the thread drains err_tasks and collects terminated jobs every
        resync_period seconds until stop()."""
        if self._repair_thread is not None and self._repair_thread.is_alive():
            return
        self._repair_stop = threading.Event()
        stop = self._repair_stop

        def loop():
            while not stop.wait(resync_period):
                try:
                    self.process_resync_tasks()
                    self.process_cleanup_jobs()
                except Exception:  # noqa: BLE001 — repair must not die
                    logger.exception("cache repair iteration failed")

        self._repair_thread = threading.Thread(
            target=loop, name="kb-cache-repair", daemon=True
        )
        self._repair_thread.start()

    def mark_synced(self) -> None:
        """Signal that the initial cluster sync is complete (the informer
        HasSynced analog) — set by load_state, by POST /v1/sync on the ingest
        API, or implicitly by the wait timeout below."""
        self._synced.set()

    def wait_for_cache_sync(self, timeout: Optional[float] = None) -> bool:
        """WaitForCacheSync (cache.go:363-384): block the scheduling loop
        until the initial state has landed. Standalone there are no LIST
        watermarks, so "synced" is an explicit signal (mark_synced / the
        ingest API's sync barrier) with a bounded wait: on timeout the loop
        proceeds with whatever arrived — convergence-by-re-running covers a
        late-arriving remainder exactly like any other cluster change."""
        if timeout is None:
            return self._synced.is_set()
        ok = self._synced.wait(timeout)
        if not ok:
            logger.warning(
                "cache sync signal not received within %.1fs; scheduling over "
                # kbt: allow[KBT301] log-only dict sizes — a stale count is fine
                "%d nodes / %d jobs as-is", timeout, len(self.nodes), len(self.jobs),
            )
        return ok

    def stop(self) -> None:
        self._repair_stop.set()
        if self._repair_thread is not None:
            self._repair_thread.join(timeout=5.0)
            self._repair_thread = None
        # drain + retire the async bind dispatcher so a stopped cache is
        # quiescent (no lingering kb-dispatch thread, no post-stop binder
        # calls); _dispatch_async lazily recreates the pool if needed again
        pool, self._dispatch_pool = self._dispatch_pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        with self._dispatch_mu:
            self._dispatch_futures = []
        spool, self._status_pool = self._status_pool, None
        if spool is not None:
            spool.shutdown(wait=True)
        # the PV ledger owns a lazy pv-writes pool (cache/volume.py);
        # FakeVolumeBinder has no close — seam-probe like the other
        # volume_binder capabilities above
        close = getattr(self.volume_binder, "close", None)
        if close is not None:
            close()

    # ------------------------------------------------------------------
    # ingest: pods (event_handlers.go:42-200)
    # ------------------------------------------------------------------
    def _owns(self, pod: Pod) -> bool:
        """Informer filter (cache.go:283-305): our scheduler's pods, or pods
        already bound anywhere (needed for node accounting)."""
        return pod.scheduler_name == self.scheduler_name or pod.node_name is not None

    def _resolve_pod_priority(self, pod: Pod) -> None:
        if not self.resolve_priority:
            return
        if pod.priority == 0 and pod.priority_class:
            pc = self.priority_classes.get(pod.priority_class)
            if pc is not None:
                pod.priority = pc.value
        elif pod.priority == 0 and self.default_priority:
            pod.priority = self.default_priority

    def _get_or_create_job(self, task: TaskInfo, pod: Pod) -> JobInfo:
        """(event_handlers.go:42-67) jobs keyed by group annotation; plain
        pods owned by this scheduler get a shadow PodGroup with minMember=1
        (cache/util.go:42-60)."""
        job = self.jobs.get(task.job)
        if job is None:
            job = JobInfo(task.job, self.spec)
            self.jobs[task.job] = job
            self.columns.bind_job(job)
        if job.pod_group is None and pod.group_name is None and job.pdb is None:
            shadow = PodGroup(
                name=pod.name,
                namespace=pod.namespace,
                min_member=1,
                queue=self.default_queue,
                creation_index=pod.creation_index,
                shadow=True,
            )
            job.set_pod_group(shadow)
        return job

    def add_pod(self, pod: Pod) -> None:
        if self._stage(self.add_pod, pod):
            self._note_staged_arrival(pod)
            return
        with self._lock:
            if self._gate(self.add_pod, pod):
                return
            if pod.key() in self.pods:
                # informer semantics are add-or-update: a duplicate ADDED
                # (watch reconnect races, replayed seeds) must upsert, not
                # trip the duplicate-task invariant.  Checked BEFORE the
                # ownership gate: the new state may have LEFT our ownership
                # (rebound to another scheduler) and update_pod drops the
                # stale cached task either way
                self.update_pod(pod)
                return
            if not self._owns(pod):
                return
            self._resolve_pod_priority(pod)
            self.pods[pod.key()] = pod
            task = TaskInfo(pod, self.spec)
            self._add_task(task, pod)

    def _add_task(self, task: TaskInfo, pod: Pod) -> None:
        job = self._get_or_create_job(task, pod)
        self.dirty.note_pod(task._key)
        self.dirty.note_job(job.uid)
        if task.node_name is None:
            # arrival→bind-decision latency clock starts at first ingest of
            # an unbound pod; kubelet status replays keep the original stamp
            ts = self._arrival_ts.get(task._key)
            if ts is None:
                ts = self._arrival_ts[task._key] = telemetry.perf_counter()
            # the gang's clock starts with its first undecided member's
            if job.first_arrival is None or not job.has_undecided():
                job.first_arrival = ts
            else:
                job.first_arrival = min(job.first_arrival, ts)
        job.add_task(task)
        self.columns.bind_task(task, job)
        if task.node_name:
            node = self.nodes.get(task.node_name)
            if node is None:
                # pod arrived before its node: hold a nodeless NodeInfo;
                # set_node replays accounting when the node shows up
                node = NodeInfo(None, self.spec)
                node.name = task.node_name
                self.nodes[task.node_name] = node
                self.columns.bind_node(node)
            node.add_task(task)

    def update_pod(self, pod: Pod) -> None:
        """delete + add (event_handlers.go:116-130).

        pod.spec.nodeName is write-once and scheduler-owned (k8s semantics:
        clients can't unbind via update; the Binding subresource sets it):
        an incoming update without a node keeps the stored pod's binding —
        without this, a client update raced against the scheduler's own bind
        (or deferred past it by the exclusive-session gate) would clobber the
        placement and the next cycle would double-bind the pod."""
        if self._stage(self.update_pod, pod):
            self._note_staged_arrival(pod)
            return
        with self._lock:
            if self._gate(self.update_pod, pod):
                return
            stored = self.pods.get(pod.key())
            if stored is not None and not pod.node_name:
                # an UNACKED async bind counts as a binding too: the
                # pipelined loop drains the binder behind the next cycle's
                # ingest, so an update landing in that window must keep the
                # dispatched placement (the ack or the failure handler
                # settles it); _dispatch_async clears a failed dispatch's
                # optimistic stamp
                pod.node_name = (stored.node_name
                                 or self._inflight_bind_hosts.get(pod.key()))
            # an external change to a QUARANTINED pod releases it back into
            # the ordinary flow — the rebuild below IS its fresh resync
            self.resync.release(pod.key())
            # the arrival→decision clock starts at FIRST ingest: a status
            # replay on a still-pending pod must not reset it through the
            # delete+add rebuild below
            t_arr = self._arrival_ts.get(pod.key())
            # the add below would immediately recreate a placeholder the
            # delete retired — keep it alive across an update, or every
            # status event for such a pod flushes the node feature cache
            self._delete_pod_locked(pod, retire_placeholder=not self._owns(pod))
            if self._owns(pod):
                self._resolve_pod_priority(pod)
                self.pods[pod.key()] = pod
                if t_arr is not None and not pod.node_name:
                    self._arrival_ts[pod.key()] = t_arr
                self._add_task(TaskInfo(pod, self.spec), pod)

    def delete_pod(self, pod: Pod) -> None:
        if self._stage(self.delete_pod, pod):
            return
        with self._lock:
            if self._gate(self.delete_pod, pod):
                return
            self._delete_pod_locked(pod)
            # a real DELETE (not a status replay's or a repair's rebuild):
            # if the pod was a victim, its order has been released
            self._evict_claimants.discard(pod.key())
            ordered_at = self._evict_withdrawn_locked(pod.key())
        if ordered_at is not None:
            from kube_batch_tpu import metrics

            metrics.observe_eviction_release_latency(
                (telemetry.perf_counter() - ordered_at) * 1e3)

    def _delete_pod_locked(self, pod: Pod, retire_placeholder: bool = True,
                           forget_resync: bool = True) -> None:
        self.pods.pop(pod.key(), None)
        self.pod_conditions.pop(pod.key(), None)  # fresh pod ⇒ fresh dedup
        self._arrival_ts.pop(pod.key(), None)
        self._inflight_bind_hosts.pop(pod.key(), None)
        self.bind_seq.pop(pod.key(), None)
        if forget_resync:
            # external change/delete: all repair bookkeeping (incl. the
            # quarantine) starts over. The resync pass's OWN delete+add
            # rebuild passes False — it must not erase the very attempt
            # history whose backoff it implements.
            self.resync.forget(pod.key())
        self.dirty.note_pod(pod.key())
        self.dirty.note_job(job_id_for_pod(pod))
        release = getattr(self.volume_binder, "release_task", None)
        if release is not None:
            release(pod.uid)  # free assumed-but-unbound PV reservations
        job_id = job_id_for_pod(pod)
        job = self.jobs.get(job_id)
        if job is not None:
            task = job.tasks.get(pod.key())
            if task is not None:
                job.delete_task(task)
                node = self.nodes.get(task.node_name) if task.node_name else None
                if node is not None and task.key() in node.tasks:
                    node.remove_task(task)
                    # a deleted-node placeholder exists only to carry its
                    # residents; the last one leaving retires it
                    if retire_placeholder and node.node is None and not node.tasks:
                        self.nodes.pop(node.name, None)
                        self.columns.free_node(node)
                self.columns.free_task(task)
            self._maybe_collect_job(job)

    def _maybe_collect_job(self, job: JobInfo) -> None:
        """processCleanupJob analog (cache.go:533-557, JobTerminated
        helpers.go:102-106): drop a job once it has no tasks, no (non-shadow)
        PodGroup, and no PDB."""
        if (
            not job.tasks
            and (job.pod_group is None or job.pod_group.shadow)
            and job.pdb is None
        ):
            if self.jobs.pop(job.uid, None) is not None:
                self.dirty.note_job(job.uid)
                self.columns.free_job(job)
                from kube_batch_tpu import metrics

                metrics.prune_job_series(job.uid)
            self._status_next_write.pop(job.uid, None)

    # ------------------------------------------------------------------
    # ingest: nodes (event_handlers.go:261-360)
    # ------------------------------------------------------------------
    def add_node(self, node: Node) -> None:
        if self._stage(self.add_node, node):
            return
        with self._lock:
            if self._gate(self.add_node, node):
                return
            self.dirty.note_node(node.name)
            existing = self.nodes.get(node.name)
            if existing is None:
                info = NodeInfo(node, self.spec)
                self.nodes[node.name] = info
                self.columns.bind_node(info)
            else:
                existing.set_node(node)
            # topology-restricted PVs evaluate their nodeSelectorTerms
            # against these labels in the volume ledger (cache/volume.py)
            set_labels = getattr(self.volume_binder, "set_node_labels", None)
            if set_labels is not None:
                set_labels(node.name, node.labels)

    def update_node(self, node: Node) -> None:
        self.add_node(node)

    def delete_node(self, name: str) -> None:
        if self._stage(self.delete_node, name):
            return
        with self._lock:
            if self._gate(self.delete_node, name):
                return
            node = self.nodes.get(name)
            if node is None:
                return
            self.dirty.note_node(name)
            # a gone node can't attach volumes: drop its labels so ledger
            # reachability fails closed for it immediately
            forget = getattr(self.volume_binder, "forget_node_labels", None)
            if forget is not None:
                forget(name)
            if node.tasks:
                # resident pods outlive the Node object (their NodeName
                # persists, like the reference's); demote to the nodeless
                # placeholder the pod-before-node ingest uses instead of
                # orphaning them — a re-added node then replays their
                # accounting via set_node, and a kubelet update can't
                # re-account a task into already-consumed fresh capacity
                node.demote_to_placeholder()
                return
            self.nodes.pop(name)
            self.columns.free_node(node)

    # ------------------------------------------------------------------
    # ingest: podgroups (event_handlers.go:362-481)
    # ------------------------------------------------------------------
    def add_pod_group(self, pg: PodGroup) -> None:
        if self._stage(self.add_pod_group, pg):
            return
        with self._lock:
            if self._gate(self.add_pod_group, pg):
                return
            if not pg.queue:
                pg.queue = self.default_queue  # default fill
            job_id = pg.key()
            self.dirty.note_job(job_id)
            job = self.jobs.get(job_id)
            if job is None:
                job = JobInfo(job_id, self.spec)
                self.jobs[job_id] = job
                self.columns.bind_job(job)
            job.set_pod_group(pg)

    def update_pod_group(self, pg: PodGroup) -> None:
        self.add_pod_group(pg)

    def delete_pod_group(self, key: str) -> None:
        if self._stage(self.delete_pod_group, key):
            return
        with self._lock:
            if self._gate(self.delete_pod_group, key):
                return
            self.dirty.note_job(key)
            job = self.jobs.get(key)
            if job is not None:
                job.pod_group = None
                if not job.tasks:
                    if self.jobs.pop(key, None) is not None:
                        self.columns.free_job(job)
            self._status_next_write.pop(key, None)

    # ------------------------------------------------------------------
    # ingest: pod disruption budgets — the legacy gang source
    # (event_handlers.go:484-594)
    # ------------------------------------------------------------------
    def add_pdb(self, pdb) -> None:
        """setPDB: the job is keyed by the PDB's controller UID (the same
        key owner-linked pods land on, cache/util.go:42-46); min-available
        comes from the PDB; queue is always the default (PDB has no queue
        concept, event_handlers.go:497-498)."""
        if not pdb.owner:
            logger.error("PodDisruptionBudget %s has no controller; ignored",
                         pdb.name)
            return
        if self._stage(self.add_pdb, pdb):
            return
        with self._lock:
            if self._gate(self.add_pdb, pdb):
                return
            job_id = f"{pdb.namespace}/{pdb.owner}"
            self.dirty.note_job(job_id)
            job = self.jobs.get(job_id)
            if job is None:
                job = JobInfo(job_id, self.spec)
                self.jobs[job_id] = job
                self.columns.bind_job(job)
            # a shadow PodGroup synthesized for owner pods that arrived
            # before their PDB yields to the PDB as the gang source (its
            # min_member=1 would otherwise mask the PDB's min-available and
            # divert status writeback from the events-only path)
            if job.pod_group is not None and job.pod_group.shadow:
                job.pod_group = None
            job.set_pdb(pdb)
            job.queue = self.default_queue

    def update_pdb(self, pdb) -> None:
        self.add_pdb(pdb)

    def delete_pdb(self, pdb) -> None:
        if not pdb.owner:
            return
        if self._stage(self.delete_pdb, pdb):
            return
        with self._lock:
            if self._gate(self.delete_pdb, pdb):
                return
            job = self.jobs.get(f"{pdb.namespace}/{pdb.owner}")
            if job is None:
                return
            self.dirty.note_job(job.uid)
            job.unset_pdb()
            if job.tasks and job.pod_group is None:
                # re-synthesize the shadow PodGroup the PDB displaced so the
                # owner's pods keep scheduling as singletons (divergence
                # from the reference, which leaves the job excluded from
                # snapshots — cache.go:625-633 — until its pods are deleted)
                any_pod = next(iter(job.tasks.values())).pod
                job.set_pod_group(PodGroup(
                    name=any_pod.name,
                    namespace=any_pod.namespace,
                    min_member=1,
                    queue=self.default_queue,
                    creation_index=any_pod.creation_index,
                    shadow=True,
                ))
            self._maybe_collect_job(job)

    # ------------------------------------------------------------------
    # ingest: queues / priority classes (event_handlers.go:597-785)
    # ------------------------------------------------------------------
    def add_queue(self, queue: Queue) -> None:
        if self._stage(self.add_queue, queue):
            return
        with self._lock:
            if self._gate(self.add_queue, queue):
                return
            self.dirty.mark_queues()
            qinfo = QueueInfo(queue)
            self.queues[queue.name] = qinfo
            self.columns.bind_queue(qinfo)

    def update_queue(self, queue: Queue) -> None:
        self.add_queue(queue)

    def delete_queue(self, name: str) -> None:
        if self._stage(self.delete_queue, name):
            return
        with self._lock:
            if self._gate(self.delete_queue, name):
                return
            self.dirty.mark_queues()
            self.queues.pop(name, None)
            # a recreated queue must get a fresh status write even when its
            # first counts happen to equal the deleted one's last record
            self._queue_status_written.pop(name, None)
            self.columns.free_queue(name)

    def add_priority_class(self, pc: PriorityClass) -> None:
        if not self.resolve_priority:
            return  # informer not wired when disabled (cache.go:352,378)
        if self._stage(self.add_priority_class, pc):
            return
        with self._lock:
            if self._gate(self.add_priority_class, pc):
                return
            self.dirty.mark_priority_classes()
            self.priority_classes[pc.name] = pc
            if pc.global_default:
                self.default_priority = pc.value

    def delete_priority_class(self, name: str) -> None:
        if self._stage(self.delete_priority_class, name):
            return
        with self._lock:
            if self._gate(self.delete_priority_class, name):
                return
            self.dirty.mark_priority_classes()
            pc = self.priority_classes.pop(name, None)
            if pc is not None and pc.global_default:
                self.default_priority = 0

    # ------------------------------------------------------------------
    # egress: bind / evict (cache.go:404-487)
    # ------------------------------------------------------------------
    def _own_task(self, task: TaskInfo) -> Optional[TaskInfo]:
        job = self.jobs.get(task.job)
        return job.tasks.get(task.key()) if job else None

    def bind(self, task: TaskInfo, hostname: str) -> None:
        """Mark Binding in the cache, then call the binder; a binder failure
        queues the task for resync (cache.go:447-487; synchronous here — the
        async goroutine is replaced by the resync repair path)."""
        with self._lock:
            if not self._session_active:
                own = self._own_task(task)
                if own is not None:
                    job = self.jobs[task.job]
                    job.update_task_status(own, TaskStatus.BINDING)
                    own.node_name = hostname
                    node = self.nodes.get(hostname)
                    if node is not None and own.key() not in node.tasks:
                        node.add_task(own)
            # exclusive session: the session already holds this very task in
            # the right state; the caller (Statement/dispatch) finishes the
            # BINDING transition itself
            pod = self.pods.get(task.key())
            t0, gangs = None, []
            if pod is not None:
                self.binds_total += 1
                self.bind_seq[task.key()] = self.binds_total
                t0 = self._arrival_ts.pop(task.key(), None)
                if t0 is not None:
                    gangs = self._gangs_decided_locked({task.job})
        if t0 is not None:
            self._observe_decisions([t0], telemetry.perf_counter(), gangs)
        try:
            if pod is not None:
                self.binder.bind(pod, hostname)
                # binding ack → durable in the pod store (the apiserver
                # Binding subresource analog)
                pod.node_name = hostname
                self.events.append(("Scheduled", task.key(), hostname))
                if self.resync.has_history():
                    with self._lock:
                        self.resync.note_success(task.key())
        except CircuitOpenError:
            # egress failing fast — park without charging the poison budget
            logger.warning("bind of %s parked: egress breaker open", task.key())
            self.resync_task(task, reason="breaker-open")
        except Exception as e:  # noqa: BLE001 — repair path mirrors resyncTask
            logger.error("bind of %s to %s failed: %s", task.key(), hostname, e)
            self.resync_task(task)

    def bulk_bind(self, tasks_hosts, job_sums=None, node_sums=None) -> None:
        """bind() for a batch under ONE lock acquisition — the allocate
        replay's commit takes this path with every placement of the cycle;
        per-task semantics are identical to bind().  Job and node accounting
        are applied groupwise (bulk_transition / bulk_add_tasks) with
        presummed resreq, so the per-task work is the dict moves and the
        binder call.

        `job_sums` / `node_sums` optionally carry the replay's already-
        computed resreq segment sums as {key: (task_count, vec)}; a presum is
        trusted only when its count matches the group actually applied here
        AND every task's resreq Resource is the identical object the session
        snapshot cloned (TaskInfo.clone shares resreq; a mid-cycle pod update
        replaces the TaskInfo with a fresh Resource, making the session's sum
        stale) — otherwise the group falls back to accumulation."""
        with self._lock:
            if self._session_active:
                # exclusive (no-clone) session: the replay already applied
                # job/node accounting on these very objects — only stage the
                # binder dispatch + Scheduled events.  task.pod IS the stored
                # pod here (ingest replaces the TaskInfo with the pod, and
                # deletes are deferred while the session owns the cache), so
                # the per-task store lookup is skipped.  The dispatch itself
                # runs AFTER the lock releases, like the non-exclusive path:
                # the executor's first submit spawns its worker thread, and
                # blocking on a thread start under the cache's big lock is
                # exactly what the lockdep check flags (and flagged here)
                staged = [(t, h, t.pod) for t, h in tasks_hosts]
            else:
                staged = self._bulk_bind_locked(tasks_hosts, job_sums, node_sums)
            arrivals, gangs, now = self._note_bind_decisions_locked(staged)
        self._observe_decisions(arrivals, now, gangs)
        self._dispatch_async(staged)

    def _note_bind_decisions_locked(self, staged) -> tuple:
        """Mark every staged dispatch in flight (update_pod's unacked-bind
        guard) and close the arrival→decision latency clocks; returns the
        arrival stamps of the pods decided, the gangs these binds completed
        (:meth:`_gangs_decided_locked`) and the decision time (observed
        outside the lock)."""
        now = telemetry.perf_counter()
        pop_ts = self._arrival_ts.pop
        inflight = self._inflight_bind_hosts
        arrivals = []
        jobs = set()
        binds = 0
        for task, hostname, pod in staged:
            if pod is None:
                continue
            binds += 1
            inflight[task._key] = hostname
            self.bind_seq[task._key] = self.binds_total + binds
            t0 = pop_ts(task._key, None)
            if t0 is not None:
                arrivals.append(t0)
                jobs.add(task.job)
        self.binds_total += binds
        return arrivals, self._gangs_decided_locked(jobs), now

    def _gangs_decided_locked(self, job_ids) -> list:
        """[(first arrival stamp, tasks)] of the gangs among ``job_ids``
        that a bind has just left with no undecided member, and stops
        their clocks (a member that arrives later starts a new one)."""
        out = []
        for job_id in job_ids:
            job = self.jobs.get(job_id)
            if job is None or job.first_arrival is None:
                continue
            if not job.has_undecided():
                out.append((job.first_arrival, len(job.tasks)))
                job.first_arrival = None
        return out

    def left_schedulable_pending(self, binds_before: int) -> bool:
        """Whether bind decisions were made since ``binds_total`` read
        ``binds_before`` AND schedulable pods are pending still (the mask
        of the allocate action's idle-cycle skip) — what the event-driven
        loop wakes itself for after a cycle.  Takes the big lock: an
        out-of-session mutation may be re-growing the columns."""
        with self._lock:
            return (self.binds_total > binds_before
                    and self.columns.has_schedulable_pending())

    def owes_a_cycle(self) -> Optional[str]:
        """Why a cycle started now would have something to do, or None for
        a cache on which it would decide nothing and write nothing (what an
        idle tick of the event-driven loop asks before it opens a session).
        Nothing is owed when no mutation was applied since the last session
        handed the cache back (any ingest, repair rebuild or deferred event
        moves the tracker past ``last_close_version``: that session's close
        derived every status after its own binds), no task is pending,
        schedulable or not (one that fits nowhere keeps its retry and its
        events every period), the open's gang gate dropped no job (each
        open marks those Unschedulable again), and no session PodGroup is
        in a phase the close reports every cycle or enqueue acts on
        (Pending, Unknown).  Takes the big lock, like
        :meth:`left_schedulable_pending`."""
        with self._lock:
            if self._session_active:
                return "session"
            if self.dirty.version != self.last_close_version:
                return "churn"
            cols = self.columns
            if cols.has_pending():
                return "pending"
            if self.open_cache.gate_dropped_rows:
                return "gang_invalid"
            if cols.has_unsettled_phase():
                return "phase"
        return None

    def _observe_decisions(self, arrivals, now: float, gangs=()) -> None:
        """The arrival→decision latency of the pods bound at ``now``, from
        their arrival stamps: the histogram (and the bench's exact-sample
        sink), its span-stamped twin on the cycle's trace record (an SLO
        breach arms a flight-recorder dump), and the tracer's split of it
        into the wait for the deciding cycle and the rest.  ``gangs`` are
        the gangs these binds completed, each from its first arrival."""
        if not arrivals:
            return
        from kube_batch_tpu import metrics

        lat_ms = [(now - t0) * 1e3 for t0 in arrivals]
        metrics.observe_decision_latencies(lat_ms)
        metrics.observe_gang_decision_latencies(
            [((now - t0) * 1e3, size) for t0, size in gangs])
        tr = getattr(self, "tracer", None)
        if tr is not None:
            tr.note_decision_latencies(lat_ms)
            tr.note_decision_parts(arrivals, now)

    def _settle_inflight(self, entries, bound: bool) -> None:
        """Clear in-flight bind markers once the dispatcher settled them.
        ``entries`` is [(key, pod, hostname)].  For FAILED dispatches, an
        optimistic stamp that update_pod copied onto a REPLACEMENT pod
        object is rolled back (the apiserver never bound it) and the pod is
        marked dirty so the repair rebuild re-derives it as Pending."""
        from kube_batch_tpu.api.task_info import job_id_for_pod as _jid

        now = telemetry.perf_counter()
        with self._lock:
            for key, pod, hostname in entries:
                if self._inflight_bind_hosts.get(key) == hostname:
                    del self._inflight_bind_hosts[key]
                if not bound:
                    cur = self.pods.get(key)
                    # the failed pod's original arrival clock was closed at
                    # its (failed) decision — re-arm it at settle time so
                    # the repair path's eventual re-decision produces a
                    # latency sample instead of silently undercounting
                    # exactly the slow retried binds.  Only for pods still
                    # IN the store: a pod deleted while its dispatch was in
                    # flight must not leak a never-popped entry.
                    if cur is not None:
                        self._arrival_ts.setdefault(key, now)
                    if (cur is not None and cur is not pod
                            and cur.node_name == hostname):
                        cur.node_name = None
                        self.dirty.note_pod(key)
                        self.dirty.note_job(_jid(cur))

    def _bulk_bind_locked(self, tasks_hosts, job_sums, node_sums) -> list:
        """The non-exclusive bulk_bind body: apply job/node accounting under
        the (held) cache lock and return the staged binder dispatch."""
        pods_get = self.pods.get
        staged = []
        jobs_get = self.jobs.get
        nodes_get = self.nodes.get
        by_job: Dict[str, list] = {}
        by_node: Dict[str, list] = {}
        # the allocate replay emits binds grouped by job — run-length
        # the job lookup instead of paying two dict probes per task
        prev_job_uid = None
        job = None
        jlst: list = []
        stale_jobs: set = set()
        stale_nodes: set = set()
        for task, hostname in tasks_hosts:
            key = task._key
            if task.job != prev_job_uid:
                prev_job_uid = task.job
                job = jobs_get(task.job)
                jlst = by_job.get(task.job)
                if jlst is None and job is not None:
                    jlst = by_job[task.job] = []
            own = job.tasks.get(key) if job is not None else None
            if own is not None:
                if own.resreq is not task.resreq:  # pod updated mid-cycle
                    stale_jobs.add(task.job)
                    stale_nodes.add(hostname)
                own.node_name = hostname
                jlst.append(own)
                node = nodes_get(hostname)
                if node is not None and key not in node.tasks:
                    nlst = by_node.get(hostname)
                    if nlst is None:
                        nlst = by_node[hostname] = []
                    nlst.append(own)
            staged.append((task, hostname, pods_get(key)))
        nR = self.spec.n
        for job_uid, owns in by_job.items():
            job = self.jobs[job_uid]
            # bulk_transition needs a homogeneous allocated-ness flip;
            # a rebound task may already carry an allocated status
            flip = [t for t in owns if not is_allocated(t.status)]
            noflip = [t for t in owns if is_allocated(t.status)]
            if flip:
                pre = None
                if (
                    job_sums is not None and not noflip
                    and job_uid not in stale_jobs
                ):
                    entry = job_sums.get(job_uid)
                    if entry is not None and entry[0] == len(flip):
                        pre = entry[1]
                if pre is None:
                    # tight accumulation beats np.sum-over-list at gang sizes
                    pre = np.zeros(nR)
                    for t in flip:
                        pre += t.resreq.vec
                pre_r = self.spec.wrap_vec(pre)
                job.bulk_transition(flip, TaskStatus.BINDING, pre_r,
                                    pending_sum=pre_r)
            if noflip:
                job.bulk_transition(noflip, TaskStatus.BINDING, self.spec.empty())
        for hostname, owns in by_node.items():
            node = self.nodes[hostname]
            pre = None
            if node_sums is not None and hostname not in stale_nodes:
                entry = node_sums.get(hostname)
                if entry is not None and entry[0] == len(owns):
                    pre = entry[1]
            if pre is None:
                pre = np.zeros(nR)
                for t in owns:
                    pre += t.resreq.vec
            node.bulk_add_tasks(owns, [], self.spec.wrap_vec(pre), self.spec.empty())
        return staged

    def _dispatch_async(self, staged) -> None:
        """Run the binder calls off-cycle (the async goroutine,
        cache.go:478-484); cache state was already updated under the lock."""
        bind_many = getattr(self.binder, "bind_many", None)

        def run():
            if bind_many is not None:
                # batch path: one call for the whole cycle's placements (the
                # per-pod loop competes with the scheduling thread for the
                # GIL); per-task failure isolation falls back to bind()
                pairs = [(pod, hostname) for task, hostname, pod in staged
                         if pod is not None]
                try:
                    bind_many(pairs)
                    # the binder's ack makes the binding durable in the pod
                    # store (the apiserver Binding subresource analog):
                    # resync/rebuild and stale client updates now see it
                    for pod, hostname in pairs:
                        pod.node_name = hostname
                    self._settle_inflight(
                        [(pod.key(), pod, h) for pod, h in pairs], bound=True
                    )
                    self.events.append_scheduled_batch(staged)
                    if self.resync.has_history():
                        with self._lock:
                            for pod, _h in pairs:
                                self.resync.note_success(pod.key())
                    return
                except CircuitOpenError:
                    # egress failing fast: park the WHOLE batch for resync
                    # without a per-pod call (or a per-pod log line) — the
                    # degraded cycle keeps solving, decisions wait it out
                    logger.warning(
                        "binder breaker open; parking %d binds for resync",
                        len(pairs))
                    self._settle_inflight(
                        [(pod.key(), pod, h) for pod, h in pairs], bound=False
                    )
                    for task, hostname, pod in staged:
                        if pod is not None:
                            self.resync_task(task, reason="breaker-open")
                    return
                except Exception:  # noqa: BLE001 — retry per-task below
                    logger.exception("bind_many failed; retrying per task")
            breaker_parked = 0
            acked, failed = [], []
            for task, hostname, pod in staged:
                try:
                    if pod is not None:
                        self.binder.bind(pod, hostname)
                        pod.node_name = hostname  # binding ack (see above)
                        acked.append((task._key, pod, hostname))
                        self.events.append(("Scheduled", task._key, hostname))
                        if self.resync.has_history():
                            with self._lock:
                                self.resync.note_success(task._key)
                except CircuitOpenError:
                    breaker_parked += 1
                    failed.append((task._key, pod, hostname))
                    self.resync_task(task, reason="breaker-open")
                except Exception as e:  # noqa: BLE001 — resyncTask repair path
                    logger.error("bind of %s to %s failed: %s", task._key, hostname, e)
                    failed.append((task._key, pod, hostname))
                    self.resync_task(task)
            if acked:
                self._settle_inflight(acked, bound=True)
            if failed:
                self._settle_inflight(failed, bound=False)
            if breaker_parked:
                logger.warning("binder breaker open; parked %d binds for "
                               "resync", breaker_parked)

        from concurrent.futures import ThreadPoolExecutor

        if self._dispatch_pool is None:
            self._dispatch_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kb-dispatch"
            )
        # submit OUTSIDE the mutex: the pool's first submit spawns its
        # worker thread, and Thread.start blocks on the thread's started
        # event — a blocking call no lock may be held across (lockdep)
        fut = self._dispatch_pool.submit(run)
        with self._dispatch_mu:
            # leaf mutex: the pipelined loop's writeback worker drains binds
            # (flush_binds) concurrently with the cycle thread staging the
            # NEXT cycle's dispatch — an unguarded prune/rebind here could
            # drop a freshly appended future from tracking
            self._dispatch_futures = [
                f for f in self._dispatch_futures if not f.done()
            ]
            self._dispatch_futures.append(fut)

    def flush_binds(self, timeout: Optional[float] = None) -> None:
        """Wait for every in-flight async binder call — tests and the bench
        use this to observe a deterministic post-cycle state."""
        with self._dispatch_mu:
            pending = list(self._dispatch_futures)
        for f in pending:
            f.result(timeout=timeout)
        with self._dispatch_mu:
            self._dispatch_futures = [
                f for f in self._dispatch_futures if not f.done()
            ]

    def evict(self, task: TaskInfo, reason: str,
              claimant: Optional[TaskInfo] = None) -> None:
        """(cache.go:404-444)  ``reason`` is the action that ordered it,
        ``claimant`` the task it makes room for.  The batch of one."""
        self._evict_many([(task, reason, claimant)], "single")

    def bulk_evict(self, items) -> None:
        """evict() for a batch, ``[(task, reason, claimant)]`` in the order
        the evictions were decided (reclaim's replay hands over an action's,
        a Statement its own on commit): ONE acquisition of the lock, one
        append to the events, the counters and the feed; per-task semantics
        are evict()'s, a failed evictor call parks that task alone."""
        self._evict_many(items, "bulk")

    def _evict_many(self, items, path: str) -> None:
        from kube_batch_tpu import metrics

        if not items:
            return
        now = telemetry.perf_counter()
        repeats = []
        with self._lock:
            if not self._session_active:
                for task, _, _ in items:
                    own = self._own_task(task)
                    if own is not None:
                        job = self.jobs[task.job]
                        job.update_task_status(own, TaskStatus.RELEASING)
                        node = (self.nodes.get(own.node_name)
                                if own.node_name else None)
                        if node is not None:
                            node.update_task(own)
            # exclusive session: the session already moved these very tasks
            # to Releasing and re-accounted their nodes; re-applying here
            # would double-charge (the session may since have pipelined a
            # claimant onto the freed Releasing budget)
            pods_get = self.pods.get
            ordered_at = self._evict_ordered_at
            in_flight = self._evict_in_flight
            claimants = self._evict_claimants
            # a claimant given victims in an earlier cycle already is a
            # repeat: read what is in flight for it BEFORE this batch adds
            for whose in {c._key for _, _, c in items if c is not None}:
                if whose in claimants:
                    repeats.append(
                        "in_flight" if in_flight.get(whose, 0) > 0
                        else "released")
                claimants.add(whose)
            staged = []
            for task, reason, claimant in items:
                key = task._key
                pod = pods_get(key)
                if pod is None:
                    continue
                whose = claimant._key if claimant is not None else ""
                # the release clock and the in-flight count start with the
                # order; a failed evictor call takes its own back below
                fresh = key not in ordered_at
                if fresh:
                    ordered_at[key] = (now, whose)
                    in_flight[whose] = in_flight.get(whose, 0) + 1
                staged.append((task, reason, whose, pod, fresh))
        for earlier in repeats:
            metrics.register_evict_repeat_claim(earlier)
        metrics.register_evict_commit(items[0][1], path)
        evict = self.evictor.evict
        done = []
        for entry in staged:
            task, _, _, pod, fresh = entry
            try:
                evict(pod)
            except CircuitOpenError:
                logger.warning("evict of %s parked: egress breaker open",
                               task.key())
                parked_for = "breaker-open"
            except Exception as e:  # noqa: BLE001
                logger.error("evict of %s failed: %s", task.key(), e)
                parked_for = "error"
            else:
                done.append(entry)
                continue
            if fresh:
                with self._lock:
                    self._evict_withdrawn_locked(task._key)
            self.resync_task(task, reason=parked_for)
        if not done:
            return
        self.events.extend(
            [("Evict", task._key, reason) for task, reason, _, _, _ in done])
        for action, n in Counter(entry[1] for entry in done).items():
            metrics.register_eviction(action, n)
        log = self.eviction_log
        if log is not None:
            log.record_many(
                [(task._key, task.node_name or "", reason, whose)
                 for task, reason, whose, _, _ in done])

    def _evict_withdrawn_locked(self, key: str) -> Optional[float]:
        """Take ``key``'s eviction out of flight (its DELETE drained, or
        the order never went out); when it was ordered, if it was."""
        ordered = self._evict_ordered_at.pop(key, None)
        if ordered is None:
            return None
        left = self._evict_in_flight.get(ordered[1], 0) - 1
        if left > 0:
            self._evict_in_flight[ordered[1]] = left
        else:
            self._evict_in_flight.pop(ordered[1], None)
        return ordered[0]

    # volume seams (cache.go:189-209; real ledger in cache/volume.py,
    # no-op fake by default)
    def allocate_volumes(self, task: TaskInfo, hostname: str) -> None:
        self.volume_binder.allocate_volumes(task, hostname)
        task.volume_ready = True

    def bind_volumes(self, task: TaskInfo) -> None:
        self.volume_binder.bind_volumes(task)

    def volume_feasible(self, task: TaskInfo, hostname: str) -> bool:
        probe = getattr(self.volume_binder, "volume_feasible", None)
        return probe(task, hostname) if probe is not None else True

    # ------------------------------------------------------------------
    # repair: resync (cache.go:559-581, event_handlers.go:96-122)
    # ------------------------------------------------------------------
    @property
    def err_tasks(self) -> List[TaskInfo]:
        """The pending repair backlog (read-only view; the queue itself
        lives at ``self.resync``). Kept for the seed's observers/tests."""
        with self._lock:
            return self.resync.pending_tasks()

    def resync_task(self, task: TaskInfo, reason: str = "error") -> None:
        """Park a failed bind/evict decision for repair (cache.go:447-487).
        ``reason="breaker-open"`` marks a decision the egress breaker
        refused locally — it backs off but never counts toward the poison
        budget (the server never saw it)."""
        from kube_batch_tpu import metrics

        with self._lock:
            counted = self.resync.park(task, reason)
            depth, quarantined = len(self.resync), len(self.resync.quarantined)
        if counted:  # a quarantined key's park is a no-op — don't count it
            metrics.register_resync_parked(reason)
        metrics.set_resync_depth(depth, quarantined)

    def _resync_one_locked(self, task: TaskInfo) -> None:
        """Re-sync one errored task from the pod store: gone → forget;
        present → rebuild (delete + add)."""
        pod = self.pods.get(task.key())
        if pod is None:
            self.resync.forget(task.key())
            return
        self._delete_pod_locked(pod, forget_resync=False)
        self.pods[pod.key()] = pod
        self._add_task(TaskInfo(pod, self.spec), pod)

    def process_resync_tasks(self) -> None:
        """One repair pass over the backoff queue: due tasks rebuild from
        the pod store (and re-place next cycle); tasks that exhausted their
        poison budget are shelved with a PodScheduled condition instead of
        retrying forever."""
        from kube_batch_tpu import metrics

        poisoned: List[TaskInfo] = []
        with self._lock:
            if self._session_active:
                return  # a cycle owns the cache; retry next repair tick
            self.resync.apply(self._resync_one_locked, poisoned.append)
            depth, quarantined = len(self.resync), len(self.resync.quarantined)
        for task in poisoned:
            logger.error(
                "task %s failed %d bind/evict repairs; quarantined until an "
                "external change to its pod", task.key(),
                self.resync.poison_after,
            )
            self.task_unschedulable(
                task,
                f"bind/evict failed {self.resync.poison_after} times; "
                "quarantined pending an external pod change",
            )
        metrics.set_resync_depth(depth, quarantined)

    def rebuild_from_pod_store(self) -> None:
        """Re-list recovery (the informer re-list + WaitForCacheSync analog,
        cache.go:342-384): rebuild every job's and node's task state from the
        authoritative pod store. The scheduler loop invokes this after a
        cycle dies mid-mutation in exclusive-session mode, where the session
        objects ARE the cache and a half-applied replay would otherwise leak
        phantom allocations. Completed bindings survive the rebuild because
        every binder ack writes pod.node_name (the Binding subresource
        analog); in-flight unacked binds rebuild as Pending and re-place
        next cycle."""
        with self._lock:
            # everything below mutates task/job state wholesale — the next
            # open must not trust any cross-cycle delta state
            self.dirty.mark_full()
            self.open_cache.invalidate()
            spec = self.spec
            for job in self.jobs.values():
                for task in job.tasks.values():
                    self.columns.free_task(task)
                job.tasks.clear()
                job.task_status_index.clear()
                # in-place zeroing: the ledgers may be live column views
                # (api/columns.py) — rebinding would orphan them
                job.allocated.vec[:] = 0.0
                job.total_request.vec[:] = 0.0
                job.pending_request.vec[:] = 0.0
                job._note_alloc()
                if job._cols is not None:
                    job._cols.j_counts[job._row] = 0
                    job._cols.j_touched[job._row] = True
                job.nodes_fit_delta = {}
                job.nodes_fit_errors = {}
            for node in self.nodes.values():
                node.tasks.clear()
                node._acct.clear()
                if node._cols is not None:
                    node._cols.note_node_ledger(node._row)
                node.idle.vec[:] = node.allocatable.vec
                node.used.vec[:] = 0.0
                node.releasing.vec[:] = 0.0
                node._set_state()
                if node._cols is not None:
                    node._cols.sync_node_meta(node)
            for pod in list(self.pods.values()):
                if not self._owns(pod):
                    continue
                self._resolve_pod_priority(pod)
                self._add_task(TaskInfo(pod, spec), pod)
            for job in list(self.jobs.values()):
                self._maybe_collect_job(job)
        logger.warning("cache rebuilt from the pod store (%d pods, %d jobs)",
                       # kbt: allow[KBT301] log-only sizes — stale is fine
                       len(self.pods), len(self.jobs))

    def failover_recover(self) -> Dict:
        """Warm-standby takeover (leader failover): rebuild the host model
        from the pod store (the re-list a fresh leader performs anyway),
        then revalidate the surviving per-cycle device caches
        (columns.revalidate_resident — version token + check_consistency).
        On success the compiled executables and resident buffers are KEPT:
        the next cycle's mirror diffs absorb any divergence as ordinary
        scatter deltas, so failover pays no recompile/re-upload. Only a
        failed revalidation cold-starts the residency.

        Also flushes the repair queue's quarantine: the new leader's
        rebuilt state supersedes the old leader's failure history."""
        from kube_batch_tpu import metrics

        self.rebuild_from_pod_store()
        with self._lock:
            report = self.columns.revalidate_resident(self)
            # the rebuild re-derived every task from the store — stale
            # failure history must not shelve tasks the new leader never
            # saw fail
            self.resync.reset_history()
        metrics.register_leader_failover(report["mode"])
        logger.warning(
            "leader failover recovery: %s (resident tokens %s%s)",
            report["mode"], report["resident_tokens"],
            f"; errors: {report['errors']}" if report["errors"] else "",
        )
        return report

    def process_cleanup_jobs(self) -> None:
        """processCleanupJob analog (cache.go:533-557): sweep-collect jobs
        that are terminated per JobTerminated (helpers.go:102-106 — no real
        PodGroup AND no tasks). Tasks always leave through delete_pod, which
        also clears the pod store and node task copies; this sweep is the
        belt-and-braces pass for jobs that lost their last task on a code
        path that didn't call _maybe_collect_job."""
        with self._lock:
            if self._session_active:
                return  # a cycle owns the cache; retry next repair tick
            for job in list(self.jobs.values()):
                self._maybe_collect_job(job)

    # ------------------------------------------------------------------
    # status egress (cache.go:688-736)
    # ------------------------------------------------------------------
    def task_unschedulable(self, task: TaskInfo, message: str) -> None:
        """PodScheduled=False condition + FailedScheduling event for one task
        (cache.go:500-525), deduplicated like podConditionHaveUpdate
        (cache.go:151-173)."""
        self._task_unschedulable_key(task.key(), message)

    def _task_unschedulable_key(self, key: str, message: str,
                                require_pod: bool = False) -> None:
        """task_unschedulable by pod key.  ``require_pod=True`` (the
        pipelined writeback stage) skips the dedup record when the pod has
        since left the store — a staged condition must not plant a stale
        dedup entry that would suppress a recreated pod's first write."""
        cond = {
            "type": "PodScheduled",
            "status": "False",
            "reason": "Unschedulable",
            "message": message,
        }
        with self._lock:
            if self.pod_conditions.get(key) == cond:
                return  # no-op update suppressed
            pod = self.pods.get(key)
            if pod is not None or not require_pod:
                self.pod_conditions[key] = cond
        if pod is not None:
            self.status_updater.update_pod_condition(pod, cond)
        self.events.append(("FailedScheduling", key, message))

    def record_job_status_event(self, job: JobInfo) -> None:
        """Unschedulable event (gated like RecordJobStatusEvent,
        cache.go:688-702: non-shadow PodGroup in Pending/Unknown phase, or a
        PDB job with Pending tasks) + fit-error conditions for Allocated and
        Pending tasks (cache.go:704-719). Called once per job at session
        close via update_job_status / the PDB events-only path."""
        self._apply_status_ops(self._render_job_status_ops(job))

    def _render_job_status_ops(self, job: JobInfo) -> list:
        """record_job_status_event's effects as VALUE-snapshotted ops
        (("event", tuple) / ("cond", key, message)) — the pipelined close
        renders them while the session's fit diagnostics are still live and
        hands the list across the stage boundary; applying them later reads
        no session state.  record_job_status_event == render + apply, so
        serial and staged closes share one rendering."""
        pg = job.pod_group
        shadow = pg is not None and pg.shadow
        pg_unsched = (
            pg is not None
            and not shadow
            and pg.phase in (PodGroupPhase.PENDING, PodGroupPhase.UNKNOWN)
        )
        pdb_unsched = job.pdb is not None and bool(
            job.task_status_index.get(TaskStatus.PENDING)
        )
        has_stuck = job.task_status_index.get(TaskStatus.ALLOCATED) or \
            job.task_status_index.get(TaskStatus.PENDING)
        if not (pg_unsched or pdb_unsched or has_stuck):
            return []  # nothing to report — skip the fit-error rendering
        base = job.job_fit_errors or job.fit_error()
        ops = []
        if pg_unsched or pdb_unsched:
            ops.append(("event", ("Unschedulable", job.uid, base)))
        for status in (TaskStatus.ALLOCATED, TaskStatus.PENDING):
            for task in job.task_status_index.get(status, {}).values():
                fe = job.nodes_fit_errors.get(task.uid)
                ops.append(("cond", task.key(),
                            fe.error() if fe is not None else base))
        return ops

    def _apply_status_ops(self, ops, staged: bool = False) -> None:
        for op in ops:
            if op[0] == "event":
                self.events.append(op[1])
            else:
                self._task_unschedulable_key(op[1], op[2], require_pod=staged)

    def update_job_status(self, job: JobInfo, prev_status=None) -> None:
        """Write the session's derived PodGroup status back to the
        authoritative store (UpdatePodGroup, cache.go:722-736).

        Condition-only updates (phase and counts unchanged) are rate-limited
        to one write per minute plus jitter, like the jobUpdater
        (job_updater.go:20-31,55-100) — conditions churn every cycle for a
        stuck job, and the write stream must not."""
        import random
        import time as _time

        pg = job.pod_group
        if pg is None:
            return
        write = True
        with self._lock:
            own = self.jobs.get(job.uid)
            if own is None:
                return  # job deleted mid-cycle — nothing to write status for
            own_pg = own.pod_group if own is not None else None
            if prev_status is not None:
                # exclusive session: own_pg IS pg (mutated in place), so the
                # change detection compares against the status saved at open
                # (session.go:102-105 podGroupStatus)
                condition_only = prev_status == (
                    pg.phase, pg.running, pg.failed, pg.succeeded
                )
            else:
                condition_only = (
                    own_pg is not None
                    and own_pg.phase == pg.phase
                    and (own_pg.running, own_pg.failed, own_pg.succeeded)
                    == (pg.running, pg.failed, pg.succeeded)
                )
            # kbt: allow[KBT001] status-write rate-limit cadence is wall-clock
            # by design (job_updater.go:20-31); scheduling decisions never read it
            now = _time.monotonic()
            if condition_only and now < self._status_next_write.get(job.uid, 0.0):
                write = False  # rate-limited; session state already updated
            if write:
                self._status_next_write[job.uid] = now + 60.0 + random.uniform(0, 30.0)
                if own_pg is not None:
                    own_pg.phase = pg.phase
                    own_pg.conditions = list(pg.conditions)
                    own_pg.running = pg.running
                    own_pg.failed = pg.failed
                    own_pg.succeeded = pg.succeeded
                # the authoritative PodGroup changed: the next delta open
                # must re-read this job's status/schedulability
                self.dirty.note_job(job.uid)
        if write:
            if self._status_degraded():
                from kube_batch_tpu import metrics

                metrics.register_status_writes_shed(1)
            else:
                self.status_updater.update_pod_group(pg)
        # events accompany every status pass, rate-limited or not, once per
        # job per close (UpdateJobStatus → RecordJobStatusEvent,
        # cache.go:722-736); task_unschedulable dedups the conditions
        self.record_job_status_event(job)

    def update_job_statuses_bulk(self, updates) -> None:
        """The exclusive close's status pass: update_job_status semantics for
        a pre-filtered batch under one lock.  `updates` is
        [(job, changed, need_record)]; exclusive sessions mutate the
        authoritative PodGroup in place, so the own_pg copy-back of the
        per-job path is a no-op here and only the rate-limit bookkeeping,
        the updater call, and event recording remain.

        Implemented as stage + run back-to-back: the pipelined close runs
        the same two halves with a stage boundary between them, so serial
        and overlapped writeback are one code path by construction."""
        self.run_status_flush(self.stage_status_flush(updates))

    def stage_status_flush(self, updates, qcounts=None) -> "StatusFlush":
        """The synchronous half of the close-time status pass — the
        double-buffer handoff for the pipelined cycle.  EVERYTHING the next
        session open depends on happens here, before the cycle ends: the
        dirty stamps for changed jobs (the delta open re-reads exactly
        them), the rate-limit window bookkeeping, the queue-status delta
        decisions, and the degraded verdict.  What crosses the stage
        boundary is value-snapshotted: PodGroup status CLONES (the live
        object mutates again next cycle; the reference's jobUpdater writes
        an informer copy the same way), pre-rendered event/condition ops,
        and the decided queue writes — run_status_flush reads no session
        or live-job state.

        The rate-limit jitter (60s + U[0,30), job_updater.go:20-31) is
        drawn as one numpy batch."""
        import time as _time

        to_write = []
        ops: List = []
        with self._lock:
            # kbt: allow[KBT001] same wall-clock rate-limit cadence as
            # update_job_status above — write-stream pacing, not scenario time
            now = _time.monotonic()
            next_write = self._status_next_write
            jitter = np.random.uniform(60.0, 90.0, size=len(updates)).tolist()
            note_job = self.dirty.note_job
            for i, (job, changed, need_record) in enumerate(updates):
                pg = job.pod_group
                if pg is None or self.jobs.get(job.uid) is None:
                    continue  # deleted mid-cycle: no write, no events
                if changed:
                    # phase/counts moved this cycle (exclusive close mutates
                    # the authoritative PodGroup in place) — the next delta
                    # open re-reads exactly these jobs' open-state
                    note_job(job.uid)
                if need_record:
                    ops.extend(self._render_job_status_ops(job))
                if not changed and now < next_write.get(job.uid, 0.0):
                    continue  # condition-only churn, rate-limited
                next_write[job.uid] = now + jitter[i]
                to_write.append(pg.clone())
            qwrites, shed_queues = self._stage_queue_statuses_locked(qcounts)
        return StatusFlush(to_write, ops, qwrites, shed_queues,
                           self._status_degraded())

    def run_status_flush(self, flush: "StatusFlush") -> None:
        """The egress half: pod-group writes, rendered events/conditions,
        then the queue-status writes — the serial close's order.  Runs on
        the cycle thread (serial) or the pipeline's writeback worker
        (overlapped); either way it touches only the flush's snapshots plus
        the updater/event seams.

        Degraded cycles (soft budget elapsed / writeback breaker open at
        stage time) shed the flush — async pool for parallel-safe updaters,
        skip otherwise.  Status writes are re-derived every close, so the
        next healthy cycle converges; what matters now is that the
        scheduling loop keeps ticking instead of stalling in egress."""
        updater = self.status_updater
        to_write = flush.to_write
        parallel_safe = getattr(updater, "parallel_safe", False)
        if to_write and flush.degraded:
            from kube_batch_tpu import metrics

            metrics.register_status_writes_shed(len(to_write))
            logger.warning("degraded cycle: shedding %d status writes%s",
                           len(to_write),
                           " to the async pool" if parallel_safe else "")
            if parallel_safe:
                self._update_pod_groups_pooled(to_write, wait=False)
        elif len(to_write) > 16 and parallel_safe:
            try:
                self._update_pod_groups_pooled(to_write)
            except Exception:  # noqa: BLE001 — re-derived next close
                logger.exception("pooled podgroup status writes failed")
        else:
            for pg in to_write:
                # per-write guard: one failing updater call must not abort
                # the remaining writes, the rendered event/condition ops, or
                # the queue writes below — the stage already recorded those
                # queue deltas as written, so skipping them here would
                # suppress the external QueueStatus until the counts change
                try:
                    updater.update_pod_group(pg)
                except Exception:  # noqa: BLE001 — re-derived next close
                    logger.exception("podgroup status write failed")
        self._apply_status_ops(flush.ops, staged=True)
        if flush.shed_queues:
            from kube_batch_tpu import metrics

            metrics.register_status_writes_shed(flush.shed_queues)
        write = getattr(updater, "update_queue_status", None)
        for name, c in flush.qwrites:
            try:
                write(name, c)
            except Exception as e:  # noqa: BLE001 — next close re-derives
                logger.error("queue status write %s failed: %s", name, e)
                with self._lock:
                    # un-record so the next close retries the delta
                    # kbt: allow[KBT002] dict .get on the delta-record map
                    # (the "queue" in its name is QueueStatus, not a Queue)
                    if self._queue_status_written.get(name) == c:
                        del self._queue_status_written[name]

    def _stage_queue_statuses_locked(self, counts) -> tuple:
        """Decide the per-queue status deltas (caller holds the lock):
        returns ([(name, counts)], shed_count).  Bookkeeping is recorded
        optimistically at stage time so the NEXT cycle's delta decisions
        never race the flush; a failed write un-records (run_status_flush)."""
        if counts is None:
            return [], 0
        write = getattr(self.status_updater, "update_queue_status", None)
        if write is None:
            return [], 0
        if self._status_degraded():
            # deltas-only writeback: an unwritten count stays "dirty" in
            # _queue_status_written and lands on the next healthy close
            return [], len(counts)
        # queues previously written but absent from this cycle's counts
        # (their podgroups all left) zero out rather than going stale
        zero = queue_phase_counts()
        names = set(counts) | set(self._queue_status_written)
        qwrites = []
        for name in names:
            if self.queues.get(name) is None:
                continue  # deleted mid-cycle
            c = counts.get(name, zero)
            if self._queue_status_written.get(name) == c:
                continue
            self._queue_status_written[name] = dict(c)
            qwrites.append((name, dict(c)))
        return qwrites, 0

    def update_queue_statuses(self, counts: Dict[str, dict]) -> None:
        """Write changed per-queue podgroup-phase counts (QueueStatus,
        types.go:195-204) through the StatusUpdater seam. BEYOND the
        reference — it declares the fields but never fills them; here the
        close pass hands the counts it already derived and only deltas are
        written. Updaters without the seam (older fakes) are skipped."""
        with self._lock:
            qwrites, shed = self._stage_queue_statuses_locked(counts)
        self.run_status_flush(StatusFlush([], [], qwrites, shed, False))

    def _status_degraded(self) -> bool:
        """Should close-time status flushes shed? True while the scheduler
        flagged a blown cycle budget, or while the updater reports its
        writeback path failing fast (K8sBackend.degraded → breaker open)."""
        if self.shed_status_writes:
            return True
        probe = getattr(self.status_updater, "degraded", None)
        return bool(probe()) if probe is not None else False

    def _update_pod_groups_pooled(self, pgs, wait: bool = True) -> None:
        """16-worker status writeback (the jobUpdater's ParallelizeUntil,
        job_updater.go:18,51-53). Per-object failures log and continue —
        the next cycle re-derives and re-writes (convergence by re-running,
        the reference ignores UpdatePodGroup errors the same way).
        ``wait=False`` is the degraded cycle's shed: the writes drain on
        the pool behind the ticking loop (stop() still reaps them)."""
        from concurrent.futures import ThreadPoolExecutor

        if self._status_pool is None:
            self._status_pool = ThreadPoolExecutor(
                max_workers=16, thread_name_prefix="kb-status"
            )
        update = self.status_updater.update_pod_group

        def write(pg):
            try:
                update(pg)
            except Exception as e:  # noqa: BLE001
                logger.error("podgroup status write %s/%s failed: %s",
                             pg.namespace, pg.name, e)

        if wait:
            list(self._status_pool.map(write, pgs))
        else:
            for pg in pgs:
                self._status_pool.submit(write, pg)

    # ------------------------------------------------------------------
    # snapshot (cache.go:584-654)
    # ------------------------------------------------------------------
    def _job_in_session(self, uid: str, job: JobInfo) -> bool:
        """Membership filter shared by snapshot() and session_view(): jobs
        enter a session with a PodGroup or a PDB (cache.go:625-633) and a
        known queue."""
        if job.pod_group is None and job.pdb is None:
            return False
        if job.queue not in self.queues:
            logger.warning("job %s queue %s not found, skipped", uid, job.queue)
            return False
        return True

    def _resolve_job_priority(self, job: JobInfo) -> int:
        """PriorityClass resolution (cache.go:610-620): named class if it
        exists, else the global default — recomputed every session so a
        deleted class stops conferring its value."""
        pc = self.priority_classes.get(
            job.pod_group.priority_class
        ) if job.pod_group and job.pod_group.priority_class else None
        if pc is not None:
            return pc.value
        return self.default_priority

    def snapshot(self) -> ClusterInfo:
        """Deep-clone ready nodes, all queues, and every job that has a
        PodGroup and whose queue exists."""
        with self._lock:
            ci = ClusterInfo(self.spec)
            for name, node in self.nodes.items():
                if node.ready:
                    ci.nodes[name] = node.clone()
            for name, q in self.queues.items():
                ci.queues[name] = q.clone()
            for uid, job in self.jobs.items():
                if not self._job_in_session(uid, job):
                    continue
                clone = job.clone()
                clone.priority = self._resolve_job_priority(job)
                ci.jobs[uid] = clone
            return ci

    def take_dirty(self):
        """Consume the accumulated ingest churn (one exclusive open's input).
        Taken under the lock so it races nothing; during the session the
        ingest gate defers mutations, so no marks land mid-cycle except the
        cache's own status writebacks at close."""
        with self._lock:
            delta = self.dirty.take()
            self.last_open_version = delta.version
            return delta

    def session_view_delta(self, delta) -> ClusterInfo:
        """session_view() by delta: refresh only the dirty jobs in the
        persistent open cache (cache/dirty.py), then hand the session
        shallow copies.  End state is bit-exact with session_view() — the
        same membership filter and priority resolution run, just only for
        jobs whose inputs could have moved since the last open."""
        oc = self.open_cache
        with self._lock:
            ci = ClusterInfo(self.spec)
            ci.nodes = {
                name: n for name, n in self.nodes.items() if n.ready
            }
            ci.queues = dict(self.queues)
            jobs = oc.jobs
            pg_status = oc.pg_status
            queues = self.queues
            pcs_get = self.priority_classes.get
            default_prio = self.default_priority
            for uid in delta.jobs:
                job = self.jobs.get(uid)
                member = (
                    job is not None
                    and (job.pod_group is not None or job.pdb is not None)
                )
                if member and job.queue not in queues:
                    logger.warning(
                        "job %s queue %s not found, skipped", uid, job.queue
                    )
                    member = False
                if not member:
                    jobs.pop(uid, None)
                    pg_status.pop(uid, None)
                    continue
                pg = job.pod_group
                pc = (
                    pcs_get(pg.priority_class)
                    if pg is not None and pg.priority_class else None
                )
                job.priority = pc.value if pc is not None else default_prio
                jobs[uid] = job
                if pg is not None:
                    pg_status[uid] = (pg.phase, pg.running, pg.failed,
                                      pg.succeeded)
                else:
                    pg_status.pop(uid, None)
            ci.jobs = dict(jobs)
            return ci

    def rebuild_open_cache(self, cluster: ClusterInfo, pg_status) -> None:
        """Reseed the cross-cycle open cache after a FULL session open —
        `cluster.jobs`/`pg_status` are the freshly derived structures the
        session was just handed."""
        oc = self.open_cache
        oc.jobs = dict(cluster.jobs)
        oc.pg_status = dict(pg_status)
        oc.gate_dropped_rows = set()
        oc.valid = True
        # the full open cleared every session job's fit diagnostics
        self.fit_state_jobs.clear()

    def session_view(self) -> ClusterInfo:
        """The exclusive (no-clone) session's ClusterInfo: the same
        membership filters as snapshot(), as shallow views over the live
        objects — caller must hold the exclusive-session gate.  The
        membership/priority checks are inlined (vs the shared helpers the
        cold snapshot() uses): this loop runs over every job every cycle."""
        with self._lock:
            ci = ClusterInfo(self.spec)
            ci.nodes = {
                name: n for name, n in self.nodes.items() if n.ready
            }
            ci.queues = dict(self.queues)
            jobs = {}
            queues = self.queues
            pcs_get = self.priority_classes.get
            default_prio = self.default_priority
            for uid, job in self.jobs.items():
                pg = job.pod_group
                if pg is None and job.pdb is None:
                    continue
                if job.queue not in queues:
                    logger.warning(
                        "job %s queue %s not found, skipped", uid, job.queue
                    )
                    continue
                pc = pcs_get(pg.priority_class) if pg is not None and pg.priority_class else None
                job.priority = pc.value if pc is not None else default_prio
                jobs[uid] = job
            ci.jobs = jobs
            return ci

"""ColumnStore — the persistent columnar host model.

Round-2 verdict: rebuilding 50k-row SoA arrays from Python TaskInfo objects
every cycle (and re-materializing per-job/per-node bookkeeping on replay) was
the reference's deep-clone cost (cache.go:584-654) reborn in Python — ~940 ms
of host work per cycle around a ~310 ms device solve.  This module makes the
host model itself columnar and persistent:

- The cache owns one ColumnStore.  Rows are assigned when objects are
  ingested (pods → task rows, jobs → job rows, nodes/queues likewise) and
  freed when they leave; row indices are stable for an object's lifetime.
- The object model's *ledgers* (JobInfo.allocated/total/pending_request,
  NodeInfo.idle/used/releasing/allocatable/capability) become views into
  [cap, R] float64 matrices: every in-place `add_`/`sub_` through the object
  API writes the column, and every vectorized column op is seen by the
  objects.  Single source of truth, no double bookkeeping.
- Per-job *status counts* ([capJ, n_statuses] int32) are maintained by
  JobInfo's index choke points, so gang readiness / job phase derivation /
  session-open validity become one matrix expression instead of 12.5k
  Python property chains.
- TaskInfo.status / .node_name become properties whose setters mirror into
  the t_status / t_node columns — every status flip anywhere in the tree
  (statements, replay, residue revert, ingest) keeps the columns current.

The per-cycle device snapshot then degenerates to: a cheap job-metadata scan,
a handful of [cap, R] casts, and derived masks — O(columns), not O(objects).
Capacities grow in the same shape buckets the device snapshot pads to
(snapshot.bucket), so the row space IS the padded device axis and the solve's
assignment vector indexes rows directly.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Set

import numpy as np

from kube_batch_tpu.api.resources import Resource, ResourceSpec
from kube_batch_tpu.api.snapshot import (
    BITS,
    HARD_TAINT_EFFECTS,
    UNBOUNDED,
    DeviceSnapshot,
    SnapshotMeta,
    _pack_bits,
    _TaintView,
    bucket,
)
from kube_batch_tpu.api.types import (
    CRITICAL_NAMESPACE,
    CRITICAL_PRIORITY_CLASSES,
    PodGroupPhase,
    TaskStatus,
)

logger = logging.getLogger("kube_batch_tpu")

N_STATUS = len(TaskStatus)
# columns summed for gang readiness (job_info.go:367-380 ReadyTaskNum)
READY_STATUSES = (
    int(TaskStatus.BOUND), int(TaskStatus.BINDING), int(TaskStatus.RUNNING),
    int(TaskStatus.ALLOCATED), int(TaskStatus.SUCCEEDED),
)
# ValidTaskNum statuses (job_info.go:394-409)
VALID_STATUSES = READY_STATUSES + (
    int(TaskStatus.PENDING), int(TaskStatus.PIPELINED),
)

# PodGroup phase ↔ int code for the j_phase column (−1 = no phase yet)
PHASE_CODE: Dict[PodGroupPhase, int] = {
    p: i for i, p in enumerate(PodGroupPhase)
}
CODE_PHASE: List[PodGroupPhase] = list(PodGroupPhase)
N_PHASES = len(CODE_PHASE)


def resident_snap(cols, snap, mesh=None):
    """The call-site shape for the device-resident snapshot cache: swap in
    cached device arrays when a ColumnStore backs the session, pass the
    snapshot through untouched otherwise.  The per-cycle columns and the
    task feature columns ride the packed-delta cache (api/resident.py) —
    one single-device program a swap when `mesh` is None, NamedSharding-
    placed programs on the mesh-sharded solve path; the node feature
    columns ride the version-keyed cache (resident_features).

    Memoized on the exact `snap` object (and mesh): a repeat call — the
    same cycle's oracle or histogram dispatch, the lease publish after the
    solve — returns the IDENTICAL device snapshot with no diff, no version
    bump and no dispatch, for as long as no other swap has run since."""
    if cols is None:
        return snap
    def swapped():
        """The resident cache this path holds and the swaps it has made:
        the memo stands only while neither has moved."""
        cache = cols._per_cycle_dev.get(mesh)
        return cache, 0 if cache is None else cache.version

    memo = cols._resident_memo
    if (memo is not None and memo[0] is snap and memo[1] is mesh
            and memo[2] == swapped()):
        return memo[3]
    out = snap
    if mesh is not None and snap.aff_terms is not None:
        # the in-solve rule of the required inter-pod terms runs on one
        # device; a sharded solve's termed placements are re-validated by
        # the host predicate at replay, as all of them were before
        out = out._replace(aff_terms=None)
    out = cols.resident_features(cols.per_cycle_resident(out, mesh=mesh),
                                 mesh=mesh)
    cols._resident_memo = (snap, mesh, swapped(), out)
    return out


def _grow(arr: np.ndarray, cap: int) -> np.ndarray:
    new = np.zeros((cap,) + arr.shape[1:], arr.dtype)
    new[: arr.shape[0]] = arr
    return new


class _Axis:
    """Row allocator: stable rows + LIFO free list, capacities in the same
    buckets the device snapshot pads to."""

    def __init__(self, floor: int = 8):
        self.cap = bucket(0, floor)
        self.n_live = 0
        self._free: List[int] = list(range(self.cap - 1, -1, -1))

    def alloc(self) -> Optional[int]:
        """Next free row, or None when the axis must grow first."""
        if not self._free:
            return None
        self.n_live += 1
        return self._free.pop()

    def grown_cap(self) -> int:
        return bucket(self.cap + 1)

    def on_grown(self, new_cap: int) -> None:
        self._free.extend(range(new_cap - 1, self.cap - 1, -1))
        self.cap = new_cap

    def free(self, row: int) -> None:
        self.n_live -= 1
        self._free.append(row)

    def peek(self, k: int) -> List[int]:
        """The rows the next k ``alloc()`` calls would hand out, WITHOUT
        mutating the allocator — the query plane's tie-hash oracle: a gang
        submitted against a frozen cache lands exactly on these rows
        (alloc pops the free list LIFO; growth extends it so grown rows
        hand out ascending from the old capacity)."""
        out: List[int] = []
        i = len(self._free) - 1
        grown = self.cap
        for _ in range(k):
            if i >= 0:
                out.append(self._free[i])
                i -= 1
            else:
                out.append(grown)
                grown += 1
        return out


class ColumnStore:
    def __init__(self, spec: ResourceSpec):
        self.spec = spec
        R = spec.n
        self.R = R

        # ---- task axis --------------------------------------------------
        self.tasks = _Axis()
        capT = self.tasks.cap
        self.t_init32 = np.zeros((capT, R), np.float32)   # InitResreq
        self.t_res32 = np.zeros((capT, R), np.float32)    # Resreq
        self.t_resreq64 = np.zeros((capT, R), np.float64)  # exact ledger rows
        self.t_job = np.zeros(capT, np.int32)
        self.t_prio = np.zeros(capT, np.int32)
        self.t_creation = np.zeros(capT, np.int32)
        self.t_status = np.zeros(capT, np.int32)
        self.t_node = np.full(capT, -1, np.int32)
        self.t_valid = np.zeros(capT, bool)
        self.t_best_effort = np.zeros(capT, bool)
        self.t_critical = np.zeros(capT, bool)
        self.t_needs_host = np.zeros(capT, bool)
        # rows whose ONLY host-side constraint is inter-pod terms: exact on
        # the device wherever the solve carries the in-solve rule
        # (DeviceSnapshot.aff_terms), so allocate's replay trusts them there
        self.t_terms_only = np.zeros(capT, bool)
        self.t_sel_bits = np.zeros((capT, 1), np.uint32)
        self.t_sel_impossible = np.zeros(capT, bool)
        self.t_tol_bits = np.zeros((capT, 1), np.uint32)
        self.task_by_row: List = [None] * capT
        # sparse feature registries: rows whose pods carry selectors /
        # tolerations (inter-pod and preferred terms: self.affinity below)
        self._sel_rows: Set[int] = set()
        self._tol_rows: Set[int] = set()
        self._ported_rows: Set[int] = set()  # tasks carrying hostPorts

        # ---- job axis ---------------------------------------------------
        self.jobs = _Axis()
        capJ = self.jobs.cap
        self.j_alloc = np.zeros((capJ, R), np.float64)
        self.j_total = np.zeros((capJ, R), np.float64)
        self.j_pend = np.zeros((capJ, R), np.float64)
        # persistent float32 twin of j_alloc, refreshed only at rows the
        # dirty choke points touched (JobInfo's allocated add_/sub_, the
        # columnar replay's vectorized += , row bind/free) — the device
        # snapshot reads this instead of paying a full [capJ, R] cast every
        # cycle (the node ledgers' dirty-row treatment, applied to jobs)
        self.j_alloc32 = np.zeros((capJ, R), np.float32)
        self._j_alloc_dirty = np.ones(capJ, bool)
        self.j_counts = np.zeros((capJ, N_STATUS), np.int32)
        self.job_by_row: List = [None] * capJ
        # per-cycle scratch (filled by the job scan in device_snapshot)
        self.j_min = np.zeros(capJ, np.int32)
        self.j_queue = np.zeros(capJ, np.int32)
        self.j_prio = np.zeros(capJ, np.int32)
        self.j_creation = np.zeros(capJ, np.int32)
        self.j_sess = np.zeros(capJ, bool)
        self.j_sched = np.zeros(capJ, bool)
        # PodGroup metadata rows, maintained by the same session row sync
        # (delta across cycles) — the enqueue admission gate and the delta
        # close-session status pass read these instead of walking objects
        self.j_has_pg = np.zeros(capJ, bool)
        self.j_shadow = np.zeros(capJ, bool)
        self.j_pdb = np.zeros(capJ, bool)
        self.j_phase = np.full(capJ, -1, np.int8)   # PHASE_CODE, -1 = none
        self.j_has_conds = np.zeros(capJ, bool)
        self.j_has_minres = np.zeros(capJ, bool)
        self.j_minres = np.zeros((capJ, R), np.float32)
        # rows whose close-pass inputs may have moved since the last status
        # pass: every j_counts choke point (api/job_info.py), the columnar
        # replay's vectorized count update, the session row sync, and
        # mid-cycle phase/condition writes stamp it; close_session visits
        # exactly these rows (plus the standing need-record set) and clears
        self.j_touched = np.zeros(capJ, bool)

        # ---- node axis --------------------------------------------------
        self.nodes = _Axis()
        capN = self.nodes.cap
        self.n_idle = np.zeros((capN, R), np.float64)
        self.n_rel = np.zeros((capN, R), np.float64)
        self.n_used = np.zeros((capN, R), np.float64)
        self.n_alloc = np.zeros((capN, R), np.float64)
        self.n_cap = np.zeros((capN, R), np.float64)
        # persistent float32 twins of the ledger matrices, refreshed only at
        # rows the dirty choke points touched (NodeInfo's task algebra, the
        # columnar replay, bind/free/set_node) — the device snapshot reads
        # these instead of paying four full [capN, R] casts every cycle
        self.n_idle32 = np.zeros((capN, R), np.float32)
        self.n_rel32 = np.zeros((capN, R), np.float32)
        self.n_used32 = np.zeros((capN, R), np.float32)
        self.n_alloc32 = np.zeros((capN, R), np.float32)
        self._node_ledger_dirty = np.ones(capN, bool)
        self.n_valid = np.zeros(capN, bool)   # Ready
        self.n_sched = np.zeros(capN, bool)   # not Unschedulable
        self.n_label_bits = np.zeros((capN, 1), np.uint32)
        self.n_taint_bits = np.zeros((capN, 1), np.uint32)
        self.node_by_row: List = [None] * capN
        self.node_rows: Dict[str, int] = {}   # name → row
        self.node_names: List[str] = [""] * capN

        # ---- queue axis -------------------------------------------------
        self.queues = _Axis()
        capQ = self.queues.cap
        self.q_weight = np.ones(capQ, np.float32)
        self.q_cap = np.full((capQ, R), UNBOUNDED, np.float32)
        self.q_valid = np.zeros(capQ, bool)
        self.queue_by_row: List = [None] * capQ
        self.queue_rows: Dict[str, int] = {}
        self.queue_names: List[str] = [""] * capQ

        # ---- inter-pod (anti-)affinity: match-count planes ---------------
        from kube_batch_tpu.api.affinity_planes import AffinityPlanes

        self.affinity = AffinityPlanes(self)
        # what the last device snapshot derived from them (the tracer's
        # counters: obs/trace.py note_affinity)
        self.last_affinity: Dict = {}

        # ---- label / taint interning (monotone tables) ------------------
        self.label_pair_bit: Dict[tuple, int] = {}
        self.taint_bit: Dict[tuple, int] = {}
        # set when the label/taint universe changed in a way that can affect
        # already-packed task bitsets (new pair/taint interned, node labels
        # changed): next device_snapshot recomputes the sparse task rows
        self._task_bits_dirty = False

        # ---- device-resident feature cache ------------------------------
        # The ingest-static snapshot columns (task requests/bits/priorities,
        # node allocatable/bits) change only at the ingest choke points that
        # bump the per-axis feature versions.  The node columns (nodes do
        # not churn) are re-uploaded whole by resident_features() ONLY when
        # their version moved; the task columns, which every pod that comes
        # or goes writes a row of, ride the resident swap's packed delta
        # (api/resident.py), which skips their diff while the task version
        # stands still — per-cycle host→device traffic is the rows that
        # changed, the SURVEY §7.3 one-transfer-in budget.  Disabled with
        # KB_DEVICE_CACHE=0.
        self.task_feature_version = 0
        self.node_feature_version = 0
        self._dev_cache: Dict = {}
        # resident_snap's memo: (host snap, mesh, (cache, its version), out)
        self._resident_memo = None
        # per-cycle device-resident caches (api/resident.py), keyed by mesh
        # (None = the single-device scatter cache): the truly per-cycle
        # snapshot columns stay alive on device between cycles — sharded
        # NamedSharding placements on the mesh path — and are refreshed by
        # scatter deltas instead of full uploads.  A mesh CHANGE drops the
        # old mesh's cache wholesale (the reshard/mesh-change fallback: the
        # fresh cache full-uploads once, then deltas resume).
        self._per_cycle_dev: Dict = {}
        # serve/ query-plane seam: a context-manager factory the resident
        # swap runs inside (serve/lease.LeaseBroker.swap_guard) — it
        # serializes the swap's donating scatters against in-flight probe
        # dispatches and retires the published lease whose buffers the
        # donation would invalidate.  None (the default) is a no-op: the
        # write path pays nothing until a query plane attaches.
        self.resident_swap_guard = None
        # which path the most recent session row-sync took ("delta"|"full")
        # — surfaced in the bench JSON and the sim's longitudinal report
        self.last_snapshot_path = "full"
        # warm-started allocate (KB_WARM): carried candidate-table states,
        # one per (mesh, impl) dispatch slot (api/resident.WarmTableState).
        # Dropped wholesale on axis growth, resident drops, and mesh
        # changes — the table's node/task indices must never outlive the
        # coordinate system they were ranked in (ISSUE 14 satellite: a
        # reserve()-triggered re-grow must invalidate, never index-shift).
        self._warm_tables: Dict = {}

    # ==================================================================
    # task axis
    # ==================================================================
    def bind_task(self, task, job) -> None:
        """Assign a row and fill the static columns. Called by the cache
        after job.add_task; `job` must already be bound."""
        row = self.tasks.alloc()
        if row is None:
            self._grow_tasks()
            row = self.tasks.alloc()
        pod = task.pod
        self.t_init32[row] = task.init_resreq.vec
        self.t_res32[row] = task.resreq.vec
        self.t_resreq64[row] = task.resreq.vec
        self.t_job[row] = job._row
        self.t_prio[row] = task.priority
        self.t_creation[row] = pod.creation_index
        self.t_status[row] = int(task.status)
        self.t_node[row] = (
            self.node_rows.get(task.node_name, -1)
            if task.node_name is not None else -1
        )
        self.t_valid[row] = True
        self.t_best_effort[row] = task.best_effort
        self.t_critical[row] = (
            pod.priority_class in CRITICAL_PRIORITY_CLASSES
            or task.namespace == CRITICAL_NAMESPACE
        )
        self.t_needs_host[row] = task.needs_host_predicate
        self.t_terms_only[row] = task.inter_pod_terms_only
        # sparse features
        if pod.node_selector or pod.affinity is not None:
            self._sel_rows.add(row)
            self._fill_sel_bits(row, task)
        if pod.tolerations:
            self._tol_rows.add(row)
            self._fill_tol_bits(row, task)
        self.affinity.bind_row(row, pod, int(self.t_node[row]))
        if pod.host_ports:
            self._ported_rows.add(row)
        self.task_by_row[row] = task
        # bind LAST: property setters (status/node_name) skip the store
        # until both row and store are attached.  The job's status counts
        # were already incremented by job.add_task's index choke point.
        task._row = row
        task._store = self
        self.task_feature_version += 1

    def free_task(self, task) -> None:
        row = getattr(task, "_row", -1)
        if row < 0 or task._store is not self:
            return
        task._store = None
        task._row = -1
        self.affinity.free_row(row, int(self.t_node[row]))
        self.t_valid[row] = False
        self.t_status[row] = 0
        self.t_node[row] = -1
        self.t_best_effort[row] = False
        self.t_terms_only[row] = False
        if row in self._sel_rows:
            self._sel_rows.discard(row)
            self.t_sel_bits[row] = 0
            self.t_sel_impossible[row] = False
        if row in self._tol_rows:
            self._tol_rows.discard(row)
            self.t_tol_bits[row] = 0
        self._ported_rows.discard(row)
        self.task_by_row[row] = None
        self.tasks.free(row)
        self.task_feature_version += 1

    def _grow_tasks(self) -> None:
        cap = self.tasks.grown_cap()
        for name in ("t_init32", "t_res32", "t_resreq64", "t_job", "t_prio",
                     "t_creation", "t_status", "t_valid", "t_best_effort",
                     "t_critical", "t_needs_host", "t_terms_only",
                     "t_sel_bits", "t_sel_impossible", "t_tol_bits"):
            setattr(self, name, _grow(getattr(self, name), cap))
        tn = np.full(cap, -1, np.int32)
        tn[: self.t_node.shape[0]] = self.t_node
        self.t_node = tn
        self.task_by_row.extend([None] * (cap - self.tasks.cap))
        self.affinity.grow_tasks(cap)
        self.tasks.on_grown(cap)
        # a task-axis re-grow moves the bucket rung the warm allocate
        # compacts into — drop the carried candidate tables wholesale
        # rather than index-shift them (plan_topk_bucket lifetime gap)
        self.drop_warm_tables()

    def _fill_sel_bits(self, row: int, task) -> None:
        """Required label pairs → bits (the device predicate's sound
        over-approximation; see snapshot.build_snapshot for the encoding
        contract)."""
        pod = task.pod
        required_pairs = list(pod.node_selector.items()) if pod.node_selector else []
        aff = pod.affinity
        if aff is not None and len(aff.node_terms) == 1:
            required_pairs += [
                (key, values[0])
                for key, op, values in aff.node_terms[0]
                if op == "In" and len(values) == 1
            ]
        bits: List[int] = []
        impossible = False
        for kv in required_pairs:
            b = self.label_pair_bit.get(kv)
            if b is None:
                impossible = True  # no node carries this pair (yet)
            else:
                bits.append(b)
        self.t_sel_bits[row] = _pack_bits(bits, self.t_sel_bits.shape[1])
        self.t_sel_impossible[row] = impossible

    def _fill_tol_bits(self, row: int, task) -> None:
        tols = task.pod.tolerations
        bits = [
            bit
            for (tk, tv, te), bit in self.taint_bit.items()
            if any(tol.tolerates(_TaintView(tk, tv, te)) for tol in tols)
        ]
        self.t_tol_bits[row] = _pack_bits(bits, self.t_tol_bits.shape[1])

    def adopt_task_row(self, old, new) -> None:
        """Transfer a row binding when a clone replaces the resident task
        object under the same key (update_task_status with a session copy).
        Static columns stay valid — the clone shares the pod and the resreq
        Resources; the mutable columns re-sync from the adopter."""
        row = old._row
        old._store = None
        old._row = -1
        new._row = row
        new._store = self
        self.task_by_row[row] = new
        self.t_status[row] = int(new._status)
        self.task_node_changed(row, new._node_name)

    # called by TaskInfo property setters ------------------------------
    def task_status_changed(self, row: int, status: int) -> None:
        self.t_status[row] = status

    def task_node_changed(self, row: int, node_name) -> None:
        new = self.node_rows.get(node_name, -1) if node_name is not None else -1
        self.affinity.move_row(row, int(self.t_node[row]), new)
        self.t_node[row] = new

    def set_task_nodes(self, rows: np.ndarray, nodes: np.ndarray) -> None:
        """``task_node_changed`` for whole arrays of rows and node rows (the
        columnar replay's bulk bind)."""
        self.affinity.move_rows(rows, self.t_node[rows], nodes)
        self.t_node[rows] = nodes

    # ==================================================================
    # job axis
    # ==================================================================
    def bind_job(self, job) -> None:
        row = self.jobs.alloc()
        if row is None:
            self._grow_jobs()
            row = self.jobs.alloc()
        # copy current ledgers into the rows, then rebind the job's Resource
        # objects as views (contiguous f64 rows — the .vec setter keeps them
        # zero-copy)
        self.j_alloc[row] = job.allocated.vec
        self._j_alloc_dirty[row] = True
        self.j_total[row] = job.total_request.vec
        self.j_pend[row] = job.pending_request.vec
        job.allocated.vec = self.j_alloc[row]
        job.total_request.vec = self.j_total[row]
        job.pending_request.vec = self.j_pend[row]
        counts = self.j_counts[row]
        counts[:] = 0
        for status, bucket_ in job.task_status_index.items():
            counts[int(status)] = len(bucket_)
        self.job_by_row[row] = job
        job._row = row
        job._cols = self
        self.j_touched[row] = True

    def free_job(self, job) -> None:
        row = getattr(job, "_row", -1)
        if row < 0 or job._cols is not self:
            return
        job._cols = None
        job._row = -1
        # session-row state must not leak onto the row's next tenant (the
        # delta row-sync only rewrites rows of dirty jobs)
        self.j_sess[row] = False
        self.j_sched[row] = False
        self.j_has_pg[row] = False
        self.j_shadow[row] = False
        self.j_pdb[row] = False
        self.j_phase[row] = -1
        self.j_has_conds[row] = False
        self.j_has_minres[row] = False
        self.j_minres[row] = 0.0
        self.j_touched[row] = True
        # give the job back private buffers (copies of its final state)
        job.allocated.vec = self.j_alloc[row].copy()
        job.total_request.vec = self.j_total[row].copy()
        job.pending_request.vec = self.j_pend[row].copy()
        self.j_alloc[row] = 0.0
        self._j_alloc_dirty[row] = True
        self.j_total[row] = 0.0
        self.j_pend[row] = 0.0
        self.j_counts[row] = 0
        self.job_by_row[row] = None
        self.jobs.free(row)

    def _grow_jobs(self) -> None:
        cap = self.jobs.grown_cap()
        for name in ("j_alloc", "j_alloc32", "j_total", "j_pend", "j_counts",
                     "j_min",
                     "j_queue", "j_prio", "j_creation", "j_sess", "j_sched",
                     "j_has_pg", "j_shadow", "j_pdb",
                     "j_has_conds", "j_has_minres", "j_minres", "j_touched"):
            setattr(self, name, _grow(getattr(self, name), cap))
        dirty = np.ones(cap, bool)  # grown rows refresh on first read
        dirty[: self._j_alloc_dirty.shape[0]] = self._j_alloc_dirty
        self._j_alloc_dirty = dirty
        j_phase = np.full(cap, -1, np.int8)
        j_phase[: self.j_phase.shape[0]] = self.j_phase
        self.j_phase = j_phase
        self.job_by_row.extend([None] * (cap - self.jobs.cap))
        self.jobs.on_grown(cap)
        # rebind every bound job's ledger views onto the new buffers
        for row, job in enumerate(self.job_by_row):
            if job is not None:
                job.allocated.vec = self.j_alloc[row]
                job.total_request.vec = self.j_total[row]
                job.pending_request.vec = self.j_pend[row]

    # ==================================================================
    # node axis
    # ==================================================================
    def bind_node(self, node) -> None:
        row = self.nodes.alloc()
        if row is None:
            self._grow_nodes()
            row = self.nodes.alloc()
        self.node_by_row[row] = node
        self.node_rows[node.name] = row
        self.node_names[row] = node.name
        node._row = row
        node._cols = self
        self.n_idle[row] = node.idle.vec
        self.n_rel[row] = node.releasing.vec
        self.n_used[row] = node.used.vec
        self.n_alloc[row] = node.allocatable.vec
        self.n_cap[row] = node.capability.vec
        node.idle.vec = self.n_idle[row]
        node.releasing.vec = self.n_rel[row]
        node.used.vec = self.n_used[row]
        node.allocatable.vec = self.n_alloc[row]
        node.capability.vec = self.n_cap[row]
        self.node_feature_version += 1  # fresh n_alloc / bit rows on this row
        self._node_ledger_dirty[row] = True
        self.sync_node_meta(node)
        # resident tasks bound before their node rows resolve to -1;
        # repoint them now that the name has a row
        self.affinity.nodes_changed()
        for t in node.tasks.values():
            if getattr(t, "_row", -1) >= 0 and t._store is self:
                self.affinity.move_row(t._row, int(self.t_node[t._row]), row)
                self.t_node[t._row] = row

    def free_node(self, node) -> None:
        row = getattr(node, "_row", -1)
        if row < 0 or node._cols is not self:
            return
        node._cols = None
        node._row = -1
        node.idle.vec = self.n_idle[row].copy()
        node.releasing.vec = self.n_rel[row].copy()
        node.used.vec = self.n_used[row].copy()
        node.allocatable.vec = self.n_alloc[row].copy()
        node.capability.vec = self.n_cap[row].copy()
        for arr in (self.n_idle, self.n_rel, self.n_used, self.n_alloc, self.n_cap):
            arr[row] = 0.0
        self._node_ledger_dirty[row] = True
        self.n_valid[row] = False
        self.n_sched[row] = False
        self.n_label_bits[row] = 0
        self.n_taint_bits[row] = 0
        self.node_by_row[row] = None
        self.node_rows.pop(node.name, None)
        self.node_names[row] = ""
        # tasks still referencing the freed row (bound pods of a deleted
        # node) must not alias whatever node reuses it
        residents = np.flatnonzero(self.t_node == row)
        self.affinity.move_rows(
            residents, self.t_node[residents],
            np.full(residents.size, -1, np.int32))
        self.t_node[residents] = -1
        self.affinity.nodes_changed()
        self.nodes.free(row)
        self.node_feature_version += 1

    def _grow_nodes(self) -> None:
        cap = self.nodes.grown_cap()
        for name in ("n_idle", "n_rel", "n_used", "n_alloc", "n_cap",
                     "n_valid", "n_sched", "n_label_bits", "n_taint_bits",
                     "n_idle32", "n_rel32", "n_used32", "n_alloc32"):
            setattr(self, name, _grow(getattr(self, name), cap))
        dirty = np.ones(cap, bool)
        dirty[: self._node_ledger_dirty.shape[0]] = self._node_ledger_dirty
        self._node_ledger_dirty = dirty
        self.node_by_row.extend([None] * (cap - self.nodes.cap))
        self.node_names.extend([""] * (cap - self.nodes.cap))
        self.nodes.on_grown(cap)
        self.affinity.grow_nodes(cap)
        # node-axis growth changes the node-index space the carried
        # candidate tables rank over — wholesale drop, never index-shift
        self.drop_warm_tables()
        for row, node in enumerate(self.node_by_row):
            if node is not None:
                node.idle.vec = self.n_idle[row]
                node.releasing.vec = self.n_rel[row]
                node.used.vec = self.n_used[row]
                node.allocatable.vec = self.n_alloc[row]
                node.capability.vec = self.n_cap[row]

    def sync_node_meta(self, node) -> None:
        """Refresh validity/schedulability/label/taint bits after set_node
        (or bind). Interns new label pairs / taints; growth of the universe
        marks task bitsets dirty for recompute at next snapshot.

        the node feature version bumps only when a CACHED node column
        (label/taint bits; n_alloc via set_node's own change check) actually
        changed —
        kubelet heartbeats with unchanged content must not flush the
        device-resident cache every cycle."""
        row = node._row
        self.n_valid[row] = node.ready
        obj = node.node
        self.n_sched[row] = obj is not None and not obj.unschedulable
        if obj is None:
            return
        before_labels = len(self.label_pair_bit)
        before_taints = len(self.taint_bit)
        for kv in obj.labels.items():
            self.label_pair_bit.setdefault(kv, len(self.label_pair_bit))
        for t in obj.taints:
            if t.effect in HARD_TAINT_EFFECTS:
                self.taint_bit.setdefault(
                    (t.key, t.value, t.effect), len(self.taint_bit)
                )
        W = max(1, -(-len(self.label_pair_bit) // BITS))
        Wt = max(1, -(-len(self.taint_bit) // BITS))
        if W > self.n_label_bits.shape[1]:
            self.n_label_bits = _grow_width(self.n_label_bits, W)
            self.t_sel_bits = _grow_width(self.t_sel_bits, W)
        if Wt > self.n_taint_bits.shape[1]:
            self.n_taint_bits = _grow_width(self.n_taint_bits, Wt)
            self.t_tol_bits = _grow_width(self.t_tol_bits, Wt)
        if len(self.label_pair_bit) != before_labels or len(self.taint_bit) != before_taints:
            self._task_bits_dirty = True
        label_row = _pack_bits(
            [self.label_pair_bit[kv] for kv in obj.labels.items()],
            self.n_label_bits.shape[1],
        )
        taint_row = _pack_bits(
            [
                self.taint_bit[(t.key, t.value, t.effect)]
                for t in obj.taints
                if t.effect in HARD_TAINT_EFFECTS
            ],
            self.n_taint_bits.shape[1],
        )
        if not (
            np.array_equal(self.n_label_bits[row], label_row)
            and np.array_equal(self.n_taint_bits[row], taint_row)
        ):
            self.node_feature_version += 1
            self.affinity.nodes_changed()
        self.n_label_bits[row] = label_row
        self.n_taint_bits[row] = taint_row

    # ==================================================================
    # queue axis
    # ==================================================================
    def bind_queue(self, qinfo) -> None:
        existing = self.queue_rows.get(qinfo.name)
        if existing is not None:
            row = existing
            old = self.queue_by_row[row]
            if old is not None and old is not qinfo:
                old._row, old._cols = -1, None
        else:
            row = self.queues.alloc()
            if row is None:
                self._grow_queues()
                row = self.queues.alloc()
            self.queue_rows[qinfo.name] = row
            self.queue_names[row] = qinfo.name
        self.queue_by_row[row] = qinfo
        qinfo._row = row
        qinfo._cols = self
        self.q_weight[row] = qinfo.weight
        self.q_valid[row] = True
        if qinfo.queue.capability:
            # dims a capability dict does not name are capped at 0 — the
            # JobEnqueueable closure's exact encoding (plugins/proportion.py,
            # mirrored by build_snapshot), consumed by the probe's admission
            # veto; only a cap-less queue is UNBOUNDED
            cap = np.zeros(self.R, np.float32)
            for name, v in qinfo.queue.capability.items():
                if name in self.spec:
                    cap[self.spec.index(name)] = v
        else:
            cap = np.full(self.R, UNBOUNDED, np.float32)
        self.q_cap[row] = cap

    def free_queue(self, name: str) -> None:
        row = self.queue_rows.pop(name, None)
        if row is None:
            return
        q = self.queue_by_row[row]
        if q is not None:
            q._row, q._cols = -1, None
        self.queue_by_row[row] = None
        self.q_valid[row] = False
        self.q_weight[row] = 1.0
        self.q_cap[row] = UNBOUNDED
        self.queue_names[row] = ""
        self.queues.free(row)

    def _grow_queues(self) -> None:
        cap = self.queues.grown_cap()
        q_weight = np.ones(cap, np.float32)
        q_weight[: self.queues.cap] = self.q_weight
        self.q_weight = q_weight
        q_cap = np.full((cap, self.R), UNBOUNDED, np.float32)
        q_cap[: self.queues.cap] = self.q_cap
        self.q_cap = q_cap
        self.q_valid = _grow(self.q_valid, cap)
        self.queue_by_row.extend([None] * (cap - self.queues.cap))
        self.queue_names.extend([""] * (cap - self.queues.cap))
        self.queues.on_grown(cap)

    # ==================================================================
    # capacity reservation
    # ==================================================================
    def reserve(self, n_tasks: int = 0, n_nodes: int = 0, n_jobs: int = 0,
                n_queues: int = 0) -> None:
        """Pre-grow axes to cover an expected peak so steady-state count
        wobble stays inside one shape bucket — the jit cache then hits every
        cycle (zero retraces after warmup).  Axis capacity never shrinks, so
        this is a one-way warmup knob."""
        while self.tasks.cap < n_tasks:
            self._grow_tasks()
        while self.nodes.cap < n_nodes:
            self._grow_nodes()
        while self.jobs.cap < n_jobs:
            self._grow_jobs()
        while self.queues.cap < n_queues:
            self._grow_queues()

    # ==================================================================
    # per-session job-row sync (delta or full)
    # ==================================================================
    def _sync_job_row(self, job, queue_rows_get) -> None:
        """Derive one session job's row state (shared by both sync paths —
        the delta path is bit-exact because it IS this same derivation)."""
        row = job._row
        if row < 0 or job._cols is not self:
            return  # foreign/unbound job (isolated-session object)
        self.j_touched[row] = True  # re-synced ⇒ the close pass must visit
        qi = queue_rows_get(job.queue, -1)
        if qi < 0:
            self.j_sess[row] = False
            return
        self.j_sess[row] = True
        self.j_min[row] = job.min_available
        self.j_queue[row] = qi
        self.j_prio[row] = job.priority
        self.j_creation[row] = job.creation_index
        pg = job.pod_group
        self.j_sched[row] = pg is None or pg.phase != PodGroupPhase.PENDING
        # PodGroup metadata for the enqueue gate + delta close status pass
        self.j_has_pg[row] = pg is not None
        self.j_pdb[row] = job.pdb is not None
        if pg is None:
            self.j_shadow[row] = False
            self.j_phase[row] = -1
            self.j_has_conds[row] = False
            self.j_has_minres[row] = False
            self.j_minres[row] = 0.0
            return
        self.j_shadow[row] = pg.shadow
        self.j_phase[row] = (
            PHASE_CODE[pg.phase] if pg.phase is not None else -1
        )
        self.j_has_conds[row] = bool(pg.conditions)
        mr = pg.min_resources
        # `is None`, NOT truthiness: an EMPTY min_resources dict takes the
        # walk's budgeted branch (zero request — always fits, but still
        # subject to JobEnqueueable), only a missing one promotes
        # unconditionally (enqueue.go:102-105)
        if mr is not None:
            self.j_has_minres[row] = True
            vec = np.zeros(self.R, np.float32)
            spec = self.spec
            for name, v in mr.items():
                if name in spec:
                    vec[spec.index(name)] = float(v)
            self.j_minres[row] = vec
        else:
            self.j_has_minres[row] = False
            self.j_minres[row] = 0.0

    def sync_session_rows(self, ssn, dirty_uids=None, restore_rows=()) -> None:
        """Fill the session-scoped job-row arrays (j_sess membership, j_min,
        j_queue, j_prio, j_creation, j_sched) for an exclusive session.

        ``dirty_uids=None`` is the full rescan (one Python pass over every
        session job — the previous per-cycle cost).  A set re-derives ONLY
        those uids against the live objects: rows of jobs that left the
        session clear, dirty members re-fill, everything else keeps last
        cycle's values — which are still exact because every input
        (membership, min_available, queue row, priority, creation, phase)
        moves only through choke points that stamp the dirty set.
        ``restore_rows`` re-admits rows the previous gate dropped; this
        cycle's gate re-votes on them immediately after."""
        queue_rows_get = self.queue_rows.get
        if dirty_uids is None:
            self.last_snapshot_path = "full"
            self.j_sess[:] = False
            self.j_sched[:] = False
            for job in ssn.jobs.values():
                self._sync_job_row(job, queue_rows_get)
            return
        self.last_snapshot_path = "delta"
        jobs_get = ssn.jobs.get
        job_by_row = self.job_by_row
        for row in restore_rows:
            job = job_by_row[row]
            if job is not None and jobs_get(job.uid) is job:
                self.j_sess[row] = True
        cache_jobs_get = ssn.cache.jobs.get
        for uid in dirty_uids:
            job = jobs_get(uid)
            if job is None:
                # left the session (deleted, or membership lost): clear the
                # row it may still hold on the authoritative cache object
                job = cache_jobs_get(uid)
                if job is not None and job._cols is self and job._row >= 0:
                    self.j_sess[job._row] = False
                continue
            self._sync_job_row(job, queue_rows_get)

    # ==================================================================
    # per-cycle device snapshot
    # ==================================================================
    def schedulable_pending_mask(self) -> np.ndarray:
        """[capT] bool — tasks the allocate/evict solves can act on (Pending,
        not BestEffort, live row). The single definition behind both the
        device snapshot's task_pending and the actions' idle-cycle skip —
        the skip is sound precisely because it is this same mask."""
        return (
            (self.t_status == int(TaskStatus.PENDING))
            & ~self.t_best_effort
            & self.t_valid
        )

    def has_schedulable_pending(self) -> bool:
        return bool(np.any(self.schedulable_pending_mask()))

    def has_pending(self) -> bool:
        """Whether any live task is Pending, BestEffort ones (backfill's)
        and ones no solve could place included."""
        return bool(np.any(
            (self.t_status == int(TaskStatus.PENDING)) & self.t_valid))

    def has_unsettled_phase(self) -> bool:
        """Whether a session job's PodGroup is Pending (enqueue's
        candidates) or Unknown: the phases the close-time status pass
        visits and reports every cycle, whatever else moved."""
        phases = self.j_phase[self.j_sess]
        return bool(np.any(
            (phases == PHASE_CODE[PodGroupPhase.PENDING])
            | (phases == PHASE_CODE[PodGroupPhase.UNKNOWN])))

    def peek_task_rows(self, k: int) -> List[int]:
        """The task rows the next k ingested pods would occupy (no
        mutation) — the what-if probe's tie-hash oracle (ops/probe.py):
        score ties in the solve break on a per-(task-row, node) hash, so a
        probe that answers for rows the gang will NOT get could report a
        different max-score node than the committed solve picks.  Exact
        against a frozen cache; concurrent ingest shifts the allocator and
        the probe's answer degrades to any-of-the-tied-nodes (the verdict
        and score are row-independent)."""
        return self.tasks.peek(k)

    def excluded_node_rows(self, ssn) -> List[int]:
        """Row indices of the session's excluded nodes (pressure gates) —
        the single fold every columnar placement path uses, so a new path
        can't silently miss the exclusion."""
        if not ssn.session_excluded_nodes:
            return []
        rows_get = self.node_rows.get
        return [
            r for r in (rows_get(n) for n in ssn.session_excluded_nodes)
            if r is not None
        ]

    def has_running_victims(self) -> bool:
        """True when any live task is RUNNING on a node — the necessary
        condition for the evict solve to produce a claim (victims must be
        running, ops/eviction.py's `running` mask)."""
        return bool(np.any(
            (self.t_status == int(TaskStatus.RUNNING))
            & self.t_valid
            & (self.t_node >= 0)
        ))

    def refresh_task_bits(self) -> None:
        """Recompute sparse task bitsets after the label/taint universe
        changed (new pair can un-impossible a selector; new taint needs a
        toleration verdict). Only the sparse rows pay."""
        if not self._task_bits_dirty:
            return
        self._task_bits_dirty = False
        self.task_feature_version += 1
        for row in self._sel_rows:
            self._fill_sel_bits(row, self.task_by_row[row])
        for row in self._tol_rows:
            self._fill_tol_bits(row, self.task_by_row[row])

    # snapshot field → backing column of the version-keyed NODE feature
    # cache (the task feature columns are api/resident.py's
    # TASK_FEATURE_FIELDS: pod churn rewrites their rows every burst, so
    # they ride the resident swap's delta instead)
    FEATURE_FIELDS = {
        # n_alloc32: the dirty-row-refreshed f32 twin (node_ledgers32) — the
        # device snapshot build always refreshes it before any dispatch
        "node_alloc": "n_alloc32",
        "node_label_bits": "n_label_bits",
        "node_taint_bits": "n_taint_bits",
    }

    def bump_node_features(self) -> None:
        self.node_feature_version += 1

    # ---- node-ledger dirty rows (the f32 cast choke point) -----------
    def note_node_ledger(self, row: int) -> None:
        """Mark one node row's ledgers (idle/releasing/used/allocatable)
        changed — every write path calls this (NodeInfo's task algebra and
        set_node, bind/free, the columnar replay's matrix updates), so the
        per-cycle float32 refresh pays exactly the touched rows instead of
        four full-matrix casts."""
        self._node_ledger_dirty[row] = True

    def note_node_ledger_rows(self, rows) -> None:
        self._node_ledger_dirty[rows] = True

    # ---- job-alloc dirty rows (the j_alloc f32 cast choke point) -----
    def note_job_alloc(self, row: int) -> None:
        """Mark one job row's allocated ledger changed — every write path
        calls this (JobInfo's allocated add_/sub_ via _note_alloc, the
        columnar replay's vectorized +=, bind/free/grow, the cache's
        snapshot-less resets), so the per-cycle float32 refresh pays
        exactly the touched rows instead of a full [capJ, R] cast."""
        self._j_alloc_dirty[row] = True

    def note_job_alloc_rows(self, rows) -> None:
        self._j_alloc_dirty[rows] = True

    def job_alloc32(self) -> np.ndarray:
        """The persistent float32 twin of j_alloc, refreshed at exactly the
        dirty rows (the node-ledger twin treatment applied to the job
        axis — previously a full-matrix astype every device_snapshot)."""
        dirty = self._j_alloc_dirty
        if dirty.any():
            rows = np.flatnonzero(dirty)
            self.j_alloc32[rows] = self.j_alloc[rows]
            dirty[:] = False
        return self.j_alloc32

    def node_ledgers32(self):
        """(idle32, rel32, used32, alloc32) — the persistent float32 ledger
        twins, refreshed at exactly the dirty rows."""
        dirty = self._node_ledger_dirty
        if dirty.any():
            rows = np.flatnonzero(dirty)
            self.n_idle32[rows] = self.n_idle[rows]
            self.n_rel32[rows] = self.n_rel[rows]
            self.n_used32[rows] = self.n_used[rows]
            self.n_alloc32[rows] = self.n_alloc[rows]
            dirty[:] = False
        return self.n_idle32, self.n_rel32, self.n_used32, self.n_alloc32

    def per_cycle_resident(self, snap, mesh=None):
        """Swap the per-cycle snapshot columns and the task feature columns
        for their device-resident copies, refreshed by one packed delta
        (api/resident.py) — sharded placements when `mesh` is given.
        Shares the KB_DEVICE_CACHE kill switch with the node feature
        cache."""
        import os

        if os.environ.get("KB_DEVICE_CACHE", "").strip().lower() in (
            "0", "false", "off", "no"
        ):
            return snap
        cache = self._per_cycle_dev.get(mesh)
        if cache is None:
            from kube_batch_tpu.api.resident import (
                PerCycleDeviceCache,
                ShardedPerCycleDeviceCache,
            )

            cache = (
                PerCycleDeviceCache() if mesh is None
                else ShardedPerCycleDeviceCache(mesh)
            )
            # keep at most ONE resident cache — the dispatch path that just
            # ran.  A mesh change (reshard / device-set change) drops the
            # old mesh's residency so stale placements never feed a solve;
            # a path flip (node axis crossing the shard gate, KB_SHARD
            # toggles) likewise frees the abandoned path's device copies
            # instead of holding a dead full set of per-cycle columns for
            # the process lifetime.  Either way the fresh cache
            # full-uploads once and deltas resume.
            for stale in [k for k in self._per_cycle_dev if k is not mesh]:
                del self._per_cycle_dev[stale]
                # the abandoned path's carried candidate tables rank over
                # the dropped cache's coordinate system — drop with it
                for wkey in [k for k, st in self._warm_tables.items()
                             if st.mesh is stale]:
                    del self._warm_tables[wkey]
            self._per_cycle_dev[mesh] = cache
        guard = self.resident_swap_guard
        if guard is not None:
            # the swap's program DONATES every resident buffer a published
            # lease may still reference — the guard (serve/lease.py)
            # excludes probe dispatches for the swap's duration and retires
            # the stale lease on donating backends
            with guard():
                out = cache.swap(snap, self.task_feature_version)
        else:
            out = cache.swap(snap, self.task_feature_version)
        # feed this swap's row-exact delta record to the warm-table carry
        # (idempotent per cache version — a memoized repeat swap
        # re-notifies the same record harmlessly)
        for st in self._warm_tables.values():
            if st.mesh is mesh:
                st.absorb(cache.delta_record, cache.version)
        return out

    def resident_counters(self) -> Dict[str, Dict[str, int]]:
        """Per-path scatter-delta counters ("single" / "sharded") for the
        bench artifact and the sim's longitudinal report."""
        out: Dict[str, Dict[str, int]] = {}
        for key, cache in self._per_cycle_dev.items():
            out["single" if key is None else "sharded"] = cache.counters()
        return out

    def export_delta_record(self, mesh=None):
        """The last resident swap's row-exact delta record + dirty-tracker
        version token, for the replication publisher
        (replicate/publisher.py) — the same knowledge the warm-table carry
        absorbs, so the wire stream rides the scatter diff instead of
        re-deriving it.  ``(None, 0)`` when this path has no resident
        cache (KB_DEVICE_CACHE=0, or no solve dispatched yet); the
        publisher then self-diffs against its own mirrors."""
        cache = self._per_cycle_dev.get(mesh)
        if cache is None:
            return None, 0
        return dict(cache.delta_record), int(cache.version)

    def drop_resident(self) -> None:
        """Cold-start the device residency — the per-cycle scatter caches
        AND the version-keyed static feature cache: the next solve dispatch
        pays a full upload + prewarm.  The warm-standby path calls this
        only when revalidation FAILS; the guard plane calls it on every
        integrity trip (the self-heal for a corrupted resident buffer —
        a static feature column is as corruptible as a per-cycle one, so
        both caches go).  The carried warm-allocate candidate tables go
        with them: they were ranked against the dropped buffers, and a
        guard heal must not leave a possibly-corrupt ranking behind."""
        self._per_cycle_dev.clear()
        self._dev_cache.clear()
        self._resident_memo = None
        self.drop_warm_tables()

    # ---- warm-started allocate: carried candidate tables (KB_WARM) ----
    def warm_table_state(self, mesh=None, impl=None):
        """The carried candidate-table state for one (mesh, impl) dispatch
        slot — created lazily; the state self-resets on shape/config key
        changes (api/resident.WarmTableState)."""
        from kube_batch_tpu.api.resident import WarmTableState

        key = (mesh, impl)
        st = self._warm_tables.get(key)
        if st is None:
            st = self._warm_tables[key] = WarmTableState(mesh=mesh,
                                                         impl=impl)
        return st

    def drop_warm_tables(self) -> None:
        """Wholesale drop of every carried candidate table (axis growth,
        resident drops, guard heals): the next warm dispatch cold-builds."""
        self._warm_tables.clear()

    def revalidate_resident(self, cache) -> Dict:
        """Warm-standby revalidation (leader failover): decide whether the
        surviving per-cycle device caches may keep serving after the host
        model was rebuilt from the pod store.

        KEEP when every resident cache has synced at least one snapshot
        (version token > 0) and the rebuilt store passes
        ``check_consistency`` — the mirrors then describe a state the next
        swap's vectorized diff can reconcile with ordinary scatter deltas,
        so the compiled executables and resident buffers survive and
        failover pays no recompile/re-upload. DROP (cold start) on any
        consistency error or an unsynced cache — a mirror of unknown
        provenance must not feed a solve.  (The replication follower's
        restart re-adoption — replicate/follower.py
        ``FollowerApplier.revalidate_resident`` — applies the same
        keep-iff-synced contract to its wire-fed resident cache.)"""
        errors = [str(e) for e in self.check_consistency(cache)]
        tokens = {
            ("single" if key is None else "sharded"): rc.version
            for key, rc in self._per_cycle_dev.items()
        }
        ok = not errors and all(v > 0 for v in tokens.values())
        if not ok and self._per_cycle_dev:
            self.drop_resident()
        return {
            "mode": "warm" if ok else "cold",
            "resident_tokens": tokens,
            "errors": errors,
        }

    def resident_features(self, snap, mesh=None):
        """`snap` with the node feature arrays (allocatable, label / taint
        bits) swapped for cached DEVICE-RESIDENT copies, re-uploaded whole
        only when the node feature version moved since the last call —
        nodes do not churn, so steady-state cycles ship none of them.  (The
        task feature columns ride the resident swap's packed delta,
        per_cycle_resident: a pod that comes or goes moves its rows, not
        the columns.)  `mesh` selects the placement (the mesh solve needs
        mesh-sharded uploads; committed single-device arrays would be
        rejected by its in_shardings).  Callers keep using the ORIGINAL
        host-backed snap for numpy reads — only the returned copy goes to
        the solve.  KB_DEVICE_CACHE=0 disables."""
        import os

        if os.environ.get("KB_DEVICE_CACHE", "").strip().lower() in (
            "0", "false", "off", "no"
        ):
            return snap
        import jax

        shardings = None
        if mesh is not None:
            from kube_batch_tpu.parallel.mesh import snapshot_shardings

            shardings = snapshot_shardings(mesh)
        cache = self._dev_cache.setdefault(mesh, {})
        version = self.node_feature_version
        updates = {}
        for field, col in self.FEATURE_FIELDS.items():
            ver, arr = cache.get(field, (-1, None))
            host = getattr(self, col)
            if ver != version or arr.shape != host.shape:
                arr = (
                    jax.device_put(host, getattr(shardings, field))
                    if shardings is not None else jax.device_put(host)
                )
                cache[field] = (version, arr)
            updates[field] = arr
        return snap._replace(**updates)

    def device_snapshot(self, ssn):
        """Build the (DeviceSnapshot, SnapshotMeta) pair for an EXCLUSIVE
        session straight from the columns.  Row space == device axis: the
        assignment vector indexes task rows; node/job indices are rows.

        Per-cycle work: the session job-row sync (already done by
        open_session for exclusive sessions — delta when churn allows; the
        full rescan runs here only for sessions that skipped it), the
        sparse affinity/preference rows, a few [cap, R] float32 casts, and
        vectorized derived masks.  Everything else is already columnar.
        """
        self.refresh_task_bits()
        spec = self.spec
        capT, capN = self.tasks.cap, self.nodes.cap
        capJ, capQ = self.jobs.cap, self.queues.cap

        # ---- job rows (session membership + object-owned metadata) ------
        # open_session syncs these (delta against the previous cycle when
        # churn is low) and marks the session; direct callers — tests, the
        # backfill real-request pass on hand-built sessions — get the full
        # rescan here
        if not getattr(ssn, "rows_synced", False):
            self.sync_session_rows(ssn)
        j_min, j_queue, j_prio = self.j_min, self.j_queue, self.j_prio
        j_creation, j_sess, j_sched = self.j_creation, self.j_sess, self.j_sched

        counts = self.j_counts
        job_ready = counts[:, READY_STATUSES].sum(axis=1, dtype=np.int32)

        # ---- queue aggregates (proportion.go:84-99 semantics) -----------
        sess_rows = np.flatnonzero(j_sess)
        queue_alloc = np.zeros((capQ, self.R), np.float32)
        queue_request = np.zeros((capQ, self.R), np.float32)
        if sess_rows.size:
            qr = j_queue[sess_rows]
            np.add.at(queue_alloc, qr, self.j_alloc[sess_rows].astype(np.float32))
            np.add.at(
                queue_request, qr,
                (self.j_alloc[sess_rows] + self.j_pend[sess_rows]).astype(np.float32),
            )

        # ---- derived task masks -----------------------------------------
        t_status = self.t_status
        task_pending = self.schedulable_pending_mask()

        # ---- sparse affinity / preference rows --------------------------
        # derived from the match-count planes for the pending rows alone
        # (api/affinity_planes.py); a bound row costs nothing here
        (task_aff_idx, task_aff_mask, task_pref_idx, task_pref_node,
         task_pref_pod, aff_terms, aff_stats) = self.affinity.snapshot_rows(
            task_pending)
        self.last_affinity = aff_stats
        if aff_stats["changed_nodes"] is not None:
            # a carried candidate row reads these planes: what moved joins
            # the next warm plan's invalidation (api/resident.py)
            for st in self._warm_tables.values():
                st.note_term_rows(aff_stats["changed_nodes"],
                                  aff_stats["rerank_rows"])

        node_valid = self.n_valid
        # node ledgers: persistent f32 twins refreshed at the dirty rows
        # only (the per-cycle full-matrix casts this replaces were the last
        # O(nodes) host cost of the snapshot build)
        idle32, rel32, used32, alloc32 = self.node_ledgers32()
        # session-level node exclusions (pressure gates): fold into
        # node_sched so the device predicate is exact
        node_sched = self.n_sched
        excluded_rows = self.excluded_node_rows(ssn)
        if excluded_rows:
            node_sched = node_sched.copy()
            node_sched[excluded_rows] = False
        total = (
            self.n_alloc[node_valid].sum(axis=0).astype(np.float32)
            if node_valid.any() else np.zeros(self.R, np.float32)
        )

        snap = DeviceSnapshot(
            task_req=self.t_init32,
            task_resreq=self.t_res32,
            task_job=self.t_job,
            task_prio=self.t_prio,
            task_creation=self.t_creation,
            task_status=t_status,
            task_valid=self.t_valid,
            task_pending=task_pending,
            task_best_effort=self.t_best_effort,
            task_sel_bits=self.t_sel_bits,
            task_sel_impossible=self.t_sel_impossible,
            task_tol_bits=self.t_tol_bits,
            task_node=self.t_node,
            task_critical=self.t_critical,
            task_needs_host=self.t_needs_host,
            task_aff_idx=task_aff_idx,
            task_aff_mask=task_aff_mask,
            task_pref_idx=task_pref_idx,
            task_pref_node=task_pref_node,
            task_pref_pod=task_pref_pod,
            aff_terms=aff_terms,
            node_idle=idle32,
            node_releasing=rel32,
            node_used=used32,
            node_alloc=alloc32,
            node_valid=node_valid,
            node_sched=node_sched,
            node_label_bits=self.n_label_bits,
            node_taint_bits=self.n_taint_bits,
            job_min_avail=j_min,
            job_ready=job_ready,
            job_queue=j_queue,
            job_prio=j_prio,
            job_creation=j_creation,
            job_valid=j_sess,
            job_schedulable=j_sched,
            job_allocated=self.job_alloc32(),
            queue_weight=self.q_weight,
            queue_capability=self.q_cap,
            queue_alloc=queue_alloc,
            queue_request=queue_request,
            queue_valid=self.q_valid,
            total=total,
            quanta=spec.quanta.astype(np.float32),
        )
        meta = SnapshotMeta(
            spec=spec,
            task_keys=[t._key if t is not None else "" for t in self.task_by_row],
            node_names=self.node_names,
            job_uids=[j.uid if j is not None else "" for j in self.job_by_row],
            queue_names=self.queue_names,
            label_pair_bit=self.label_pair_bit,
            taint_bit=self.taint_bit,
            n_tasks=capT,
            n_nodes=capN,
            n_jobs=capJ,
            n_queues=capQ,
            task_objs=self.task_by_row,
            job_objs=self.job_by_row,
            node_objs=self.node_by_row,
            task_resreq64=self.t_resreq64,
            task_needs_host=self.t_needs_host,
            task_terms_only=self.t_terms_only,
        )
        meta.live_nodes = int(node_valid.sum())
        return snap, meta

    # ==================================================================
    # debug / test support
    # ==================================================================
    def check_consistency(self, cache) -> List[str]:
        """Compare the columns against the object model; returns a list of
        discrepancy descriptions (empty = consistent).  O(objects) — test
        and debug use only."""
        errs: List[str] = []
        seen_rows = set()
        for uid, job in cache.jobs.items():
            row = getattr(job, "_row", -1)
            if row < 0:
                errs.append(f"job {uid} unbound")
                continue
            if not np.allclose(self.j_alloc[row], job.allocated.vec):
                errs.append(f"job {uid} allocated mismatch")
            if not np.allclose(self.j_pend[row], job.pending_request.vec):
                errs.append(f"job {uid} pending mismatch")
            if not np.allclose(self.j_total[row], job.total_request.vec):
                errs.append(f"job {uid} total mismatch")
            for s in TaskStatus:
                want = len(job.task_status_index.get(s, {}))
                got = int(self.j_counts[row, int(s)])
                if want != got:
                    errs.append(
                        f"job {uid} count[{s.name}] = {got}, objects say {want}"
                    )
            for t in job.tasks.values():
                trow = getattr(t, "_row", -1)
                if trow < 0:
                    errs.append(f"task {t._key} unbound")
                    continue
                seen_rows.add(trow)
                if int(self.t_status[trow]) != int(t.status):
                    errs.append(f"task {t._key} status col {self.t_status[trow]} != {int(t.status)}")
                # t_node means "node row the task is ACCOUNTED on": a task
                # whose node was deleted and re-added keeps its node_name but
                # is not resident on the fresh NodeInfo until its next pod
                # event re-attaches it (the reference's convergence), so the
                # column is rightly -1 there.  The expectation derives from
                # the OBJECT model (cache.nodes), not the store's own
                # indexes, so index corruption can't self-validate.
                want_node = -1
                if t.node_name:
                    node_obj = cache.nodes.get(t.node_name)
                    if node_obj is not None and t._key in node_obj.tasks:
                        want_node = getattr(node_obj, "_row", -1)
                if int(self.t_node[trow]) != want_node:
                    errs.append(f"task {t._key} node col {self.t_node[trow]} != {want_node}")
                if self.t_job[trow] != row:
                    errs.append(f"task {t._key} job col {self.t_job[trow]} != {row}")
                if not self.t_valid[trow]:
                    errs.append(f"task {t._key} row not valid")
        if int(self.t_valid.sum()) != len(seen_rows):
            errs.append(
                f"{int(self.t_valid.sum())} valid task rows but {len(seen_rows)} live tasks"
            )
        for name, node in cache.nodes.items():
            row = getattr(node, "_row", -1)
            if row < 0:
                errs.append(f"node {name} unbound")
                continue
            for label, col, vec in (
                ("idle", self.n_idle, node.idle.vec),
                ("used", self.n_used, node.used.vec),
                ("releasing", self.n_rel, node.releasing.vec),
                ("allocatable", self.n_alloc, node.allocatable.vec),
            ):
                if not np.allclose(col[row], vec):
                    errs.append(f"node {name} {label} mismatch")
            if bool(self.n_valid[row]) != node.ready:
                errs.append(f"node {name} valid flag mismatch")
        for name, q in cache.queues.items():
            if self.queue_rows.get(name) is None:
                errs.append(f"queue {name} unbound")
        # the match-count planes against a scan of every row: a missed
        # t_node choke point shows up here
        if self.affinity.live_signatures and not np.array_equal(
            self.affinity.cnt, self.affinity.rebuilt_counts()
        ):
            errs.append("affinity match-count plane differs from a rebuild")
        # the f32 ledger twins must track the f64 ledgers exactly once the
        # dirty rows are flushed — a missed note_node_ledger choke point
        # (a new ledger write path) shows up here
        self.node_ledgers32()
        for label, f32, f64 in (
            ("idle32", self.n_idle32, self.n_idle),
            ("rel32", self.n_rel32, self.n_rel),
            ("used32", self.n_used32, self.n_used),
            ("alloc32", self.n_alloc32, self.n_alloc),
        ):
            if not np.array_equal(f32, f64.astype(np.float32)):
                rows = np.flatnonzero(
                    np.any(f32 != f64.astype(np.float32), axis=1)
                )[:8]
                errs.append(
                    f"node ledger twin {label} stale at rows {rows.tolist()}"
                    " (missed note_node_ledger choke point)"
                )
        # same contract for the job-alloc twin (note_job_alloc choke)
        self.job_alloc32()
        if not np.array_equal(self.j_alloc32, self.j_alloc.astype(np.float32)):
            rows = np.flatnonzero(np.any(
                self.j_alloc32 != self.j_alloc.astype(np.float32), axis=1
            ))[:8]
            errs.append(
                f"job alloc twin stale at rows {rows.tolist()}"
                " (missed note_job_alloc choke point)"
            )
        return errs


def _grow_width(arr: np.ndarray, words: int) -> np.ndarray:
    new = np.zeros((arr.shape[0], words), arr.dtype)
    new[:, : arr.shape[1]] = arr
    return new

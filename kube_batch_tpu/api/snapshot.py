"""DeviceSnapshot — the cluster state as device-resident SoA tensors.

This is the TPU-native replacement for the reference's per-session object
snapshot (cache.go:584-654 Snapshot + cluster_info.go). Instead of deep-cloned
Go object graphs walked by 16-worker loops, one scheduling cycle ships a
structure-of-arrays image of (tasks × R, nodes × R, jobs, queues) to the
device once, runs the compiled feasibility/score/fairness/assignment programs
on it, and ships one assignment vector back (SURVEY.md §7.1).

Label/selector/taint matching is pre-compiled host-side into bitsets
(SURVEY.md §7.3 "string/label matching on device"): every distinct (key,value)
label pair carried by any node gets a bit; a task's node-selector becomes a
required-bits mask; every distinct node taint gets a bit and a task's
tolerations become a tolerated-bits mask. The device then evaluates
selector/taint predicates as pure bitwise ops.

All axes are padded to power-of-two buckets so jit specializes on a small set
of shapes (SURVEY.md §7.3 "dynamic shapes").
"""

from __future__ import annotations

import dataclasses
import math
import operator
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from kube_batch_tpu.api.cluster_info import ClusterInfo
from kube_batch_tpu.api.resources import ResourceSpec
from kube_batch_tpu.api.types import (
    CRITICAL_NAMESPACE,
    CRITICAL_PRIORITY_CLASSES,
    PodGroupPhase,
    TaskStatus,
    is_allocated,
)

BITS = 32
# Effects that hard-exclude a node (PreferNoSchedule is a soft preference the
# reference handles in scoring, not predicates).
HARD_TAINT_EFFECTS = ("NoSchedule", "NoExecute")
# Capability value meaning "unbounded" (queue without a Capability cap).
UNBOUNDED = np.float32(3.4e38)


_task_key = operator.attrgetter("_key")


def bucket(n: int, floor: int = 8) -> int:
    """Shape bucket ≥ max(n, floor) — bounds jit recompiles while keeping
    padding waste low at scale: powers of two up to 4096, then multiples of
    1024 (divisible by any power-of-two mesh axis ≤ 1024, and ≤2.5% waste
    at the 50k/5k north-star sizes vs 64%/23% for pure powers of two)."""
    n = max(n, floor)
    if n <= 4096:
        return max(floor, 1 << max(0, math.ceil(math.log2(n))))
    return -(-n // 1024) * 1024


class DeviceSnapshot(NamedTuple):
    """The per-cycle tensor image. All arrays live on device; rows beyond the
    live count are padding with their `*_valid` bit off."""

    # tasks [T, ...]
    task_req: "np.ndarray"          # [T, R] f32 — InitResreq (allocate fits on this)
    task_resreq: "np.ndarray"       # [T, R] f32 — Resreq (node accounting uses this)
    task_job: "np.ndarray"          # [T] i32 — index into job axis (0 for padding)
    task_prio: "np.ndarray"         # [T] i32
    task_creation: "np.ndarray"     # [T] i32
    task_status: "np.ndarray"       # [T] i32 — TaskStatus values
    task_valid: "np.ndarray"        # [T] bool
    task_pending: "np.ndarray"      # [T] bool — Pending and not BestEffort
    task_best_effort: "np.ndarray"  # [T] bool
    task_sel_bits: "np.ndarray"     # [T, W] u32 — required label bits
    task_sel_impossible: "np.ndarray"  # [T] bool — selector wants a pair no node has
    task_tol_bits: "np.ndarray"     # [T, Wt] u32 — tolerated taint bits
    task_node: "np.ndarray"         # [T] i32 — bound node index, -1 unbound
    task_critical: "np.ndarray"     # [T] bool — conformance-protected
    #                                 (conformance.go:42-59)
    task_needs_host: "np.ndarray"   # [T] bool — carries host-only constraints
    #                                 (ports/rich affinity); the reclaim
    #                                 idle-fit gate exempts these (their
    #                                 device fit is approximate)
    # sparse inter-pod-affinity correction (predicates.go:278-296): rows of
    # a [K, N] allow mask for the K tasks carrying required pod
    # (anti-)affinity terms, evaluated against snapshot-time placements;
    # the host predicate re-validates against live state at replay
    task_aff_idx: "np.ndarray"      # [K] i32 — task index, -1 padding
    task_aff_mask: "np.ndarray"     # [K, N] bool — allowed nodes (padding: True)
    # sparse preferred-affinity score rows (nodeorder.go:188-247 priorities)
    # for the Kp tasks carrying preferred node/pod terms
    task_pref_idx: "np.ndarray"     # [Kp] i32 — task index, -1 padding
    task_pref_node: "np.ndarray"    # [Kp, N] f32 — preferred-node-affinity score
    task_pref_pod: "np.ndarray"     # [Kp, N] f32 — preferred-pod-(anti)affinity score
    # nodes [N, ...]
    node_idle: "np.ndarray"         # [N, R] f32
    node_releasing: "np.ndarray"    # [N, R] f32
    node_used: "np.ndarray"         # [N, R] f32
    node_alloc: "np.ndarray"        # [N, R] f32 — allocatable
    node_valid: "np.ndarray"        # [N] bool — Ready (node_info.go:110-134)
    node_sched: "np.ndarray"        # [N] bool — not Unschedulable (predicates.go:181-192)
    node_label_bits: "np.ndarray"   # [N, W] u32
    node_taint_bits: "np.ndarray"   # [N, Wt] u32 — hard-effect taints present
    # jobs [J, ...]
    job_min_avail: "np.ndarray"     # [J] i32
    job_ready: "np.ndarray"         # [J] i32 — ReadyTaskNum at snapshot time
    job_queue: "np.ndarray"         # [J] i32 — index into queue axis
    job_prio: "np.ndarray"          # [J] i32
    job_creation: "np.ndarray"      # [J] i32
    job_valid: "np.ndarray"         # [J] bool — gang-valid and in a known queue
    job_schedulable: "np.ndarray"   # [J] bool — passes the Pending-phase gate
    job_allocated: "np.ndarray"     # [J, R] f32 — for DRF shares
    # queues [Q, ...]
    queue_weight: "np.ndarray"      # [Q] f32
    queue_capability: "np.ndarray"  # [Q, R] f32 (UNBOUNDED iff no Capability;
    #                                 a capability dict zeroes unnamed dims —
    #                                 the JobEnqueueable closure's encoding)
    queue_alloc: "np.ndarray"       # [Q, R] f32
    queue_request: "np.ndarray"     # [Q, R] f32 — total request of queue's jobs
    queue_valid: "np.ndarray"       # [Q] bool
    # cluster
    total: "np.ndarray"             # [R] f32 — Σ allocatable over valid nodes
    quanta: "np.ndarray"            # [R] f32 — comparison quanta
    # the in-solve half of the required inter-pod terms
    # (api/affinity_planes.AffinityTerms): with it the allocate rounds count
    # pods placed earlier in the same solve, and the rows of task_aff_idx
    # are exact on the device.  None (no pytree leaf: the programs trace as
    # they did before the field existed) where no live row carries a
    # required term, for snapshots built from objects, and on the sharded
    # paths, where the host predicate re-validates at replay as before
    aff_terms: object = None


#: the fields that are arrays in every snapshot (what a guard bundle stores
#: and the replication stream ships); ``aff_terms`` is a group of arrays or
#: None and stays with the process that built it
ARRAY_FIELDS = tuple(f for f in DeviceSnapshot._fields if f != "aff_terms")


@dataclasses.dataclass
class SnapshotMeta:
    """Host-side index maps for decoding device results back to objects."""

    spec: ResourceSpec
    task_keys: List[str]            # task index → "ns/name"
    node_names: List[str]           # node index → name
    job_uids: List[str]             # job index → JobInfo.uid
    queue_names: List[str]          # queue index → name
    label_pair_bit: Dict[Tuple[str, str], int]
    taint_bit: Dict[Tuple[str, str, str], int]
    n_tasks: int
    n_nodes: int
    n_jobs: int
    n_queues: int
    # direct object references in device-index order (the session's own
    # objects) — the vectorized allocate replay addresses placements by index
    # instead of per-placement dict lookups
    task_objs: List = dataclasses.field(default_factory=list)
    job_objs: List = dataclasses.field(default_factory=list)
    node_objs: List = dataclasses.field(default_factory=list)
    # [nT, R] float64 resreq (NOT init_resreq, and not the f32 device cast) —
    # segment sums over this match the host Resource ledgers bit-exactly
    task_resreq64: "np.ndarray" = None
    # [nT] bool — task carries host-only constraints (ports, rich affinity)
    task_needs_host: "np.ndarray" = None
    # rows whose only host-side constraint is inter-pod terms (columnar
    # snapshots only): trusted at replay when the solve carried aff_terms,
    # which the action that dispatched it says here
    task_terms_only: "np.ndarray" = None
    terms_exact: bool = False

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        return (len(self.task_keys), len(self.node_names), len(self.job_uids), len(self.queue_names))


def _pad_bool(arr: "np.ndarray", n: int) -> "np.ndarray":
    """[k] bool → [n] bool, padding False."""
    out = np.zeros(n, bool)
    out[: arr.shape[0]] = arr
    return out


def _pack_bits(bit_indices: List[int], words: int) -> np.ndarray:
    out = np.zeros(words, dtype=np.uint32)
    for b in bit_indices:
        out[b // BITS] |= np.uint32(1 << (b % BITS))
    return out


def build_snapshot(
    cluster: ClusterInfo,
    pad: bool = True,
    excluded_nodes=(),
) -> Tuple[DeviceSnapshot, SnapshotMeta]:
    """Flatten a host ClusterInfo into the SoA tensor image.

    Only gang-valid jobs in known queues contribute schedulable tasks (the
    session-open drop of invalid jobs, session.go:107-124, is applied by the
    caller; here job_valid additionally guards padding). Every task of every
    job is included (the kernels need resident tasks for accounting), but only
    Pending non-BestEffort tasks are marked task_pending.
    """
    spec = cluster.spec
    R = spec.n

    queues = sorted(cluster.queues.values(), key=lambda q: q.name)
    queue_idx = {q.name: i for i, q in enumerate(queues)}
    jobs = sorted(cluster.jobs.values(), key=lambda j: j.uid)
    nodes = sorted((n for n in cluster.nodes.values()), key=lambda n: n.name)
    node_idx = {n.name: i for i, n in enumerate(nodes)}

    tasks = []
    for ji, j in enumerate(jobs):
        for t in sorted(j.tasks.values(), key=_task_key):
            tasks.append((t, ji))

    nT, nN, nJ, nQ = len(tasks), len(nodes), len(jobs), len(queues)
    T = bucket(nT) if pad else max(nT, 1)
    N = bucket(nN) if pad else max(nN, 1)
    J = bucket(nJ) if pad else max(nJ, 1)
    Q = bucket(nQ) if pad else max(nQ, 1)

    # ---- label / taint interning over the node universe -----------------
    label_pair_bit: Dict[Tuple[str, str], int] = {}
    taint_bit: Dict[Tuple[str, str, str], int] = {}
    for n in nodes:
        if n.node is None:
            continue
        for k, v in n.node.labels.items():
            label_pair_bit.setdefault((k, v), len(label_pair_bit))
        for taint in n.node.taints:
            if taint.effect in HARD_TAINT_EFFECTS:
                taint_bit.setdefault((taint.key, taint.value, taint.effect), len(taint_bit))
    W = max(1, -(-len(label_pair_bit) // BITS))
    Wt = max(1, -(-len(taint_bit) // BITS))

    # ---- tasks ----------------------------------------------------------
    task_req = np.zeros((T, R), np.float32)
    task_resreq = np.zeros((T, R), np.float32)
    task_job = np.zeros(T, np.int32)
    task_prio = np.zeros(T, np.int32)
    task_creation = np.zeros(T, np.int32)
    task_status = np.full(T, int(TaskStatus.UNKNOWN), np.int32)
    task_valid = np.zeros(T, bool)
    task_pending = np.zeros(T, bool)
    task_best_effort = np.zeros(T, bool)
    task_sel_bits = np.zeros((T, W), np.uint32)
    task_sel_impossible = np.zeros(T, bool)
    task_tol_bits = np.zeros((T, Wt), np.uint32)
    task_node = np.full(T, -1, np.int32)
    task_critical = np.zeros(T, bool)
    aff_tasks: List[int] = []   # tasks needing an inter-pod-affinity row
    pref_tasks: List[int] = []  # tasks with preferred (soft) affinity terms
    task_keys: List[str] = []

    taint_list = list(taint_bit.items())  # [((k,v,effect), bit)]
    # columnar bulk fill (list comprehensions + one numpy write per column —
    # ~5× faster than a per-task field loop at the 50k scale)
    task_objs: List = []
    task_resreq64 = np.zeros((nT, R), np.float64)
    task_needs_host = np.zeros(nT, bool)
    if nT:
        task_objs = [t for t, _ in tasks]
        task_keys.extend(t._key for t in task_objs)
        resreq_rows = [t.resreq.vec for t in task_objs]
        task_resreq64 = np.stack(resreq_rows)  # .vec is already float64
        task_resreq[:nT] = task_resreq64
        # init_resreq is the same Resource object as resreq for pods without
        # init containers (task_info.py) — reuse the stack when nothing differs
        if all(t.init_resreq is t.resreq for t in task_objs):
            task_req[:nT] = task_resreq[:nT]
        else:
            task_req[:nT] = np.stack([t.init_resreq.vec for t in task_objs])
        task_needs_host = np.fromiter(
            (t.needs_host_predicate for t in task_objs), bool, count=nT
        )
        task_job[:nT] = [ji for _, ji in tasks]
        task_prio[:nT] = [t.priority for t in task_objs]
        task_creation[:nT] = [t.pod.creation_index for t in task_objs]
        statuses = np.fromiter(
            (int(t.status) for t in task_objs), np.int32, count=nT
        )
        task_status[:nT] = statuses
        task_valid[:nT] = True
        # BestEffort = empty semantic InitResreq (vectorized is_empty)
        m = spec.semantic_mask
        task_best_effort[:nT] = np.all(
            task_req[:nT][:, m] < spec.quanta[None, m], axis=1
        )
        task_pending[:nT] = (statuses == int(TaskStatus.PENDING)) & ~task_best_effort[:nT]
        task_node[:nT] = [
            node_idx.get(t.node_name, -1) if t.node_name is not None else -1
            for t in task_objs
        ]
        task_critical[:nT] = [
            t.pod.priority_class in CRITICAL_PRIORITY_CLASSES
            or t.namespace == CRITICAL_NAMESPACE
            for t in task_objs
        ]
    # sparse per-task features: bitsets, affinity and preference rows — only
    # tasks actually carrying selectors/tolerations/affinity walk this path;
    # one cheap comprehension picks them so the plain-pod common case pays a
    # single attribute read instead of the full branch ladder
    sparse = [
        (i, t) for i, (t, _) in enumerate(tasks)
        if t.pod.affinity is not None or t.pod.node_selector or t.pod.tolerations
    ]
    for i, t in sparse:
        pod = t.pod
        if pod.affinity is not None and (
            pod.affinity.pod_affinity or pod.affinity.pod_anti_affinity
        ):
            aff_tasks.append(i)
        if pod.affinity is not None and pod.affinity.has_preferences():
            pref_tasks.append(i)
        # required label pairs → bits: node-selector terms (MatchNodeSelector,
        # predicates.go:194-205) plus single-term node-affinity whose
        # In-requirements carry one value (necessary AND sufficient for that
        # term). Multi-term affinity (OR) or richer operators stay host-side —
        # the allocate replay re-validates every proposed placement through
        # the predicates plugin, so the device mask only needs to be a sound
        # over-approximation of feasibility.
        if pod.node_selector or pod.affinity is not None:
            required_pairs = list(pod.node_selector.items())
            if pod.affinity is not None and len(pod.affinity.node_terms) == 1:
                required_pairs += [
                    (key, values[0])
                    for key, op, values in pod.affinity.node_terms[0]
                    if op == "In" and len(values) == 1
                ]
            sel_bits: List[int] = []
            for k, v in required_pairs:
                b = label_pair_bit.get((k, v))
                if b is None:
                    task_sel_impossible[i] = True  # no node carries this pair
                else:
                    sel_bits.append(b)
            if sel_bits:
                task_sel_bits[i] = _pack_bits(sel_bits, W)
        # tolerations → tolerated-taint bits (PodToleratesNodeTaints,
        # predicates.go:220-231): bit set iff some toleration tolerates taint
        if pod.tolerations and taint_list:
            tol_bits = [
                bit
                for (tk, tv, te), bit in taint_list
                if any(
                    tol.tolerates(_TaintView(tk, tv, te)) for tol in pod.tolerations
                )
            ]
            task_tol_bits[i] = _pack_bits(tol_bits, Wt)

    # ---- nodes ----------------------------------------------------------
    node_idle = np.zeros((N, R), np.float32)
    node_releasing = np.zeros((N, R), np.float32)
    node_used = np.zeros((N, R), np.float32)
    node_alloc = np.zeros((N, R), np.float32)
    node_valid = np.zeros(N, bool)
    node_sched = np.zeros(N, bool)
    node_label_bits = np.zeros((N, W), np.uint32)
    node_taint_bits = np.zeros((N, Wt), np.uint32)
    node_names: List[str] = []
    for i, n in enumerate(nodes):
        node_names.append(n.name)
        node_idle[i] = n.idle.vec
        node_releasing[i] = n.releasing.vec
        node_used[i] = n.used.vec
        node_alloc[i] = n.allocatable.vec
        node_valid[i] = n.ready
        if n.node is not None:
            # session-level exclusions (pressure gates) fold into the
            # schedulability bit like Unschedulable (predicates.go:233-276)
            node_sched[i] = (
                not n.node.unschedulable and n.name not in excluded_nodes
            )
            node_label_bits[i] = _pack_bits(
                [label_pair_bit[(k, v)] for k, v in n.node.labels.items()], W
            )
            node_taint_bits[i] = _pack_bits(
                [
                    taint_bit[(t.key, t.value, t.effect)]
                    for t in n.node.taints
                    if t.effect in HARD_TAINT_EFFECTS
                ],
                Wt,
            )

    # ---- jobs -----------------------------------------------------------
    job_min_avail = np.zeros(J, np.int32)
    job_ready = np.zeros(J, np.int32)
    job_queue = np.zeros(J, np.int32)
    job_prio = np.zeros(J, np.int32)
    job_creation = np.zeros(J, np.int32)
    job_valid = np.zeros(J, bool)
    job_schedulable = np.zeros(J, bool)
    job_allocated = np.zeros((J, R), np.float32)
    job_uids: List[str] = []
    for i, j in enumerate(jobs):
        job_uids.append(j.uid)
        job_min_avail[i] = j.min_available
        job_ready[i] = j.ready_task_num
        job_queue[i] = queue_idx.get(j.queue, 0)
        job_prio[i] = j.priority
        job_creation[i] = j.creation_index
        job_valid[i] = j.queue in queue_idx
        phase = j.pod_group.phase if j.pod_group else None
        job_schedulable[i] = phase != PodGroupPhase.PENDING
        job_allocated[i] = j.allocated.vec

    # ---- queues ---------------------------------------------------------
    queue_weight = np.ones(Q, np.float32)
    queue_capability = np.full((Q, R), UNBOUNDED, np.float32)
    queue_alloc = np.zeros((Q, R), np.float32)
    queue_request = np.zeros((Q, R), np.float32)
    queue_valid = np.zeros(Q, bool)
    queue_names: List[str] = []
    for i, q in enumerate(queues):
        queue_names.append(q.name)
        queue_weight[i] = q.weight
        queue_valid[i] = True
        if q.queue.capability:
            # a capability dict caps every dim it does NOT name at 0 — the
            # JobEnqueueable closure builds its cap from spec.empty()
            # (plugins/proportion.py), and the probe's admission veto must
            # read the same encoding; only a cap-less queue is unbounded
            queue_capability[i] = 0.0
            for name, v in q.queue.capability.items():
                if name in spec:
                    queue_capability[i, spec.index(name)] = v
    for i, j in enumerate(jobs):
        qi = job_queue[i]
        queue_alloc[qi] += job_allocated[i]
        # proportion's request counts AllocatedStatus + Pending tasks only
        # (proportion.go:84-99), not the job's whole total_request
        for t in j.tasks.values():
            if t.status == TaskStatus.PENDING or is_allocated(t.status):
                queue_request[qi] += t.resreq.vec

    # sparse inter-pod-affinity rows, evaluated host-side at snapshot time
    # (the string/label matching stays host-precompiled, SURVEY.md §7.3).
    # This is the ONE builder that scans objects for them: isolated and
    # hand-built sessions are small, and it is the oracle the columnar
    # store's match-count planes (api/affinity_planes.py) are tested against
    K = max(1, len(aff_tasks))
    task_aff_idx = np.full(K, -1, np.int32)
    task_aff_mask = np.ones((K, N), bool)
    if aff_tasks:
        from kube_batch_tpu.plugins.predicates import pod_affinity_ok

        node_objs = list(nodes)
        for k, ti in enumerate(aff_tasks):
            task_aff_idx[k] = ti
            t = tasks[ti][0]
            for ni, n in enumerate(node_objs):
                task_aff_mask[k, ni] = pod_affinity_ok(t, n, node_objs)

    Kp = max(1, len(pref_tasks))
    task_pref_idx = np.full(Kp, -1, np.int32)
    task_pref_node = np.zeros((Kp, N), np.float32)
    task_pref_pod = np.zeros((Kp, N), np.float32)
    if pref_tasks:
        from kube_batch_tpu.plugins.nodeorder import (
            preferred_node_affinity_score,
            preferred_pod_affinity_score,
        )

        node_objs = list(nodes)
        for k, ti in enumerate(pref_tasks):
            task_pref_idx[k] = ti
            t = tasks[ti][0]
            for ni, n in enumerate(node_objs):
                task_pref_node[k, ni] = preferred_node_affinity_score(t, n)
                task_pref_pod[k, ni] = preferred_pod_affinity_score(t, n, node_objs)
        # min-max normalize the pod-affinity row to the 0..10 priority scale
        # per task across real nodes (InterPodAffinityPriority's reduce) so a
        # large term weight can't dominate the other bounded score rows
        from kube_batch_tpu.plugins.nodeorder import minmax_scale_rows

        nreal = len(node_objs)
        task_pref_pod[:, :nreal] = minmax_scale_rows(task_pref_pod[:, :nreal])

    total = node_alloc[node_valid].sum(axis=0).astype(np.float32) if nN else np.zeros(R, np.float32)

    snap = DeviceSnapshot(
        task_req=task_req,
        task_resreq=task_resreq,
        task_job=task_job,
        task_prio=task_prio,
        task_creation=task_creation,
        task_status=task_status,
        task_valid=task_valid,
        task_pending=task_pending,
        task_best_effort=task_best_effort,
        task_sel_bits=task_sel_bits,
        task_sel_impossible=task_sel_impossible,
        task_tol_bits=task_tol_bits,
        task_node=task_node,
        task_critical=task_critical,
        task_needs_host=_pad_bool(task_needs_host, T),
        task_aff_idx=task_aff_idx,
        task_aff_mask=task_aff_mask,
        task_pref_idx=task_pref_idx,
        task_pref_node=task_pref_node,
        task_pref_pod=task_pref_pod,
        node_idle=node_idle,
        node_releasing=node_releasing,
        node_used=node_used,
        node_alloc=node_alloc,
        node_valid=node_valid,
        node_sched=node_sched,
        node_label_bits=node_label_bits,
        node_taint_bits=node_taint_bits,
        job_min_avail=job_min_avail,
        job_ready=job_ready,
        job_queue=job_queue,
        job_prio=job_prio,
        job_creation=job_creation,
        job_valid=job_valid,
        job_schedulable=job_schedulable,
        job_allocated=job_allocated,
        queue_weight=queue_weight,
        queue_capability=queue_capability,
        queue_alloc=queue_alloc,
        queue_request=queue_request,
        queue_valid=queue_valid,
        total=total,
        quanta=spec.quanta.astype(np.float32),
    )
    meta = SnapshotMeta(
        spec=spec,
        task_keys=task_keys,
        node_names=node_names,
        job_uids=job_uids,
        queue_names=queue_names,
        label_pair_bit=label_pair_bit,
        taint_bit=taint_bit,
        n_tasks=nT,
        n_nodes=nN,
        n_jobs=nJ,
        n_queues=nQ,
        task_objs=task_objs,
        job_objs=list(jobs),
        node_objs=list(nodes),
        task_resreq64=task_resreq64,
        task_needs_host=task_needs_host,
    )
    return snap, meta


class _TaintView:
    """Duck-typed taint for Toleration.tolerates during interning."""

    __slots__ = ("key", "value", "effect")

    def __init__(self, key: str, value: str, effect: str):
        self.key = key
        self.value = value
        self.effect = effect

"""TaskInfo — the scheduler's view of one pod.

Mirrors pkg/scheduler/api/job_info.go:36-124: UID, owning Job, Resreq (sum of
container requests), InitResreq (max of that sum with each init container,
pod_info.go:53-73), NodeName, Status, Priority, and a backref to the ingested
Pod object.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional, Set, Tuple

from kube_batch_tpu.api.pod import Pod, GROUP_NAME_ANNOTATION
from kube_batch_tpu.api.resources import Resource, ResourceSpec, PODS
from kube_batch_tpu.api.types import TaskStatus, pod_phase_to_status

logger = logging.getLogger("kube_batch_tpu")
_warned_unknown_scalars: Set[Tuple[Tuple[str, ...], str]] = set()


def job_id_for_pod(pod: Pod) -> str:
    """JobID for a pod (job_info.go:56-66): namespace/group-name if the
    group annotation is present; else the pod's controller UID
    (cache/util.go:42-46 — pods sharing an owner share a job, which is how a
    PodDisruptionBudget on the owner gangs them); else the pod's own
    namespace/name (a shadow single-task job will be synthesized)."""
    group = pod.group_name
    if group:
        return f"{pod.namespace}/{group}"
    if pod.owner:
        return f"{pod.namespace}/{pod.owner}"
    return f"{pod.namespace}/{pod.name}"


def _requests_to_resource(requests: Dict[str, float], spec: ResourceSpec) -> Resource:
    vec = spec.empty()
    for name, v in requests.items():
        if name in spec:
            vec.vec[spec.index(name)] = float(v)
        else:
            # The reference models every scalar it sees (resource_info.go:99-127);
            # our dense axis is fixed at cache construction, so an unmodeled
            # scalar can't gate placement — warn once so misconfigured specs
            # don't silently overcommit that resource.
            key = (spec.names, name)
            if key not in _warned_unknown_scalars:
                _warned_unknown_scalars.add(key)
                logger.warning(
                    "dropping request for resource %r not in cluster ResourceSpec %s",
                    name,
                    spec.names,
                )
    vec.vec[spec.index(PODS)] = 1.0  # every task occupies one pod slot
    return vec


class TaskInfo:
    __slots__ = (
        "uid",
        "job",
        "name",
        "namespace",
        "resreq",
        "init_resreq",
        "_node_name",
        "_status",
        "priority",
        "volume_ready",
        "pod",
        "_key",
        "_row",
        "_store",
    )

    def __init__(self, pod: Pod, spec: ResourceSpec):
        # column binding first: the status/node_name property setters below
        # mirror into the cache's ColumnStore once bound (api/columns.py)
        self._row: int = -1
        self._store = None
        self.uid: str = pod.uid
        self.job: str = job_id_for_pod(pod)
        self.name: str = pod.name
        self.namespace: str = pod.namespace
        # Resreq = sum of app-container requests (job_info.go:73-80)
        self.resreq: Resource = _requests_to_resource(pod.requests, spec)
        # InitResreq = max(Resreq, each init container) (pod_info.go:53-73);
        # ingest supplies the already-maxed init_requests map. Without init
        # containers InitResreq IS Resreq — share the object (Resources are
        # immutable-by-convention; snapshot build exploits the identity)
        if pod.init_requests:
            self.init_resreq: Resource = self.resreq.clone()
            self.init_resreq.set_max_(_requests_to_resource(pod.init_requests, spec))
        else:
            self.init_resreq = self.resreq
        self._node_name: Optional[str] = pod.node_name
        self._status: TaskStatus = pod_phase_to_status(pod.phase, pod.node_name, pod.deleting)
        self.priority: int = pod.priority
        self.volume_ready: bool = False
        self.pod: Pod = pod
        self._key: str = f"{pod.namespace}/{pod.name}"

    # ---- column-mirrored mutable state ----------------------------------
    # status and node_name are the two fields that change after ingest;
    # routing every write through these setters is what keeps the persistent
    # ColumnStore current no matter which code path mutates a task
    # (statements, bulk replay, residue revert, resync).
    @property
    def status(self) -> TaskStatus:
        return self._status

    @status.setter
    def status(self, value: TaskStatus) -> None:
        self._status = value
        store = self._store
        if store is not None:
            store.t_status[self._row] = int(value)

    @property
    def node_name(self) -> Optional[str]:
        return self._node_name

    @node_name.setter
    def node_name(self, value: Optional[str]) -> None:
        self._node_name = value
        store = self._store
        if store is not None:
            store.task_node_changed(self._row, value)

    @property
    def best_effort(self) -> bool:
        """BestEffort = empty InitResreq (is_empty already ignores the pods
        dimension) — these are skipped by allocate (allocate.go:126-129) and
        placed by backfill (backfill.go:55-89)."""
        return self.init_resreq.is_empty()

    @property
    def needs_host_predicate(self) -> bool:
        """True when the task carries constraints some device program only
        approximates: host ports and node-affinity terms richer than one
        single-value In term (no program encodes them), and required
        inter-pod (anti-)affinity (the evict programs and the sharded
        allocate solves read the snapshot-time mask alone; the one-device
        allocate solve also counts same-solve placements and is exact: see
        ``inter_pod_terms_only``). A replay re-validates these on the host
        wherever its solve was approximate — everything else (ready /
        unschedulable nodes, selectors, taints, resource fit, max-pods) is
        exact on device."""
        pod = self.pod
        if pod.host_ports:
            return True
        aff = pod.affinity
        if aff is None:
            return False
        if aff.pod_affinity or aff.pod_anti_affinity:
            return True
        return self.rich_node_affinity

    @property
    def rich_node_affinity(self) -> bool:
        """Required node-affinity terms the label bits cannot encode: more
        than one term, or a requirement other than one single-value In."""
        aff = self.pod.affinity
        terms = aff.node_terms if aff is not None else None
        if not terms:
            return False
        if len(terms) > 1:
            return True
        return any(
            op != "In" or len(values) != 1 for (_, op, values) in terms[0]
        )

    @property
    def inter_pod_terms_only(self) -> bool:
        """True when required inter-pod (anti-)affinity terms are the ONLY
        reason for ``needs_host_predicate``: no host port, no node-affinity
        term the label bits cannot encode.  The columnar store answers those
        terms from its match-count planes and the allocate solve counts
        same-solve placements (DeviceSnapshot.aff_terms), so such a task is
        exact on the device there and its job keeps the bulk replay."""
        pod = self.pod
        aff = pod.affinity
        if pod.host_ports or aff is None:
            return False
        if not (aff.pod_affinity or aff.pod_anti_affinity):
            return False
        return not self.rich_node_affinity

    def clone(self) -> "TaskInfo":
        """Copy with value semantics for the mutable fields (status,
        node_name).  resreq/init_resreq are SHARED, not copied: a task's
        request vectors are frozen at ingest (nothing in the tree mutates
        them in place — accounting always happens on node/job ledgers), and
        cloning them was the dominant cost of the cache snapshot and of the
        node-side task copies at the 50k scale.  Anyone adding in-place
        mutation of task resreq must restore the deep copy here."""
        t = TaskInfo.__new__(TaskInfo)
        t._row = -1       # clones are never column-bound (isolated sessions)
        t._store = None
        t.uid = self.uid
        t.job = self.job
        t.name = self.name
        t.namespace = self.namespace
        t.resreq = self.resreq
        t.init_resreq = self.init_resreq
        t._node_name = self._node_name
        t._status = self._status
        t.priority = self.priority
        t.volume_ready = self.volume_ready
        t.pod = self.pod
        t._key = self._key
        return t

    def key(self) -> str:
        return self._key

    def __repr__(self) -> str:
        return (
            f"TaskInfo({self.namespace}/{self.name} job={self.job} "
            f"status={self.status.name} node={self.node_name} req={self.resreq})"
        )

"""The follower side of the replication stream.

A follower process pulls ``GET /v1/replicate?since=<applied>`` frames
from the leader over :class:`~kube_batch_tpu.k8s.transport.ApiTransport`
(the same retry/breaker machinery every apiserver call rides), applies
them to its own host snapshot copy, refreshes its own device-resident
per-cycle cache with the SAME scatter discipline the leader uses
(api/resident.py — the wire rows ARE the scatter rows), and publishes a
SnapshotLease into its own serve/ stack.  The full query plane — lease
broker, micro-batcher, probe kernel — then answers ``/v1/whatif`` (and
``/v1/whatif/sweep``) byte-identically to the leader for the same
applied state.

Chain discipline mirrors WarmTableState's escalate-to-cold: a delta
whose ``prev_seq``/``prev_version`` does not name exactly the applied
state is REFUSED, counted as a gap, and the next pull forces
``since=-1`` — the leader answers with a synthesized full snapshot.  A
full frame re-adopts WARM: each field is diffed in place against the
copy already held, so unchanged device buffers (and the resident
cache's compiled scatter specializations) survive the resync — the
follower-side analog of ``ColumnStore.revalidate_resident``.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from kube_batch_tpu import metrics
from kube_batch_tpu.replicate import stream

logger = logging.getLogger("kube_batch_tpu")

#: idle poll cadence when the leader answers heartbeats
POLL_S_DEFAULT = 0.02


def _poll_s() -> float:
    from kube_batch_tpu.serve.batcher import _env_float

    return _env_float("KB_REPL_POLL_S", POLL_S_DEFAULT)


class FollowerColumns:
    """Just enough ColumnStore surface for QueryPlane to attach: the
    plane installs its broker's swap guard here, and the applier runs
    its resident swaps inside that guard — the same exclusion contract
    the leader's per_cycle_resident honors."""

    def __init__(self) -> None:
        self.resident_swap_guard = None


class FollowerCache:
    """The read-only cache shim a follower process serves from: enough
    SchedulerCache surface for ``make_handler`` + QueryPlane + the
    observability accessors (tracer_of/guard_of/alerts_of attach to any
    object), with every ingest mutator rejecting — cluster state enters
    a follower ONLY through the replication stream."""

    _INGEST = (
        "update_pod", "delete_pod", "add_node", "delete_node",
        "add_pod_group", "delete_pod_group", "add_queue", "delete_queue",
        "add_priority_class", "delete_priority_class", "add_pdb",
        "delete_pdb",
    )

    def __init__(self, spec=None) -> None:
        from kube_batch_tpu.api.resources import ResourceSpec

        # replaced by the wire spec on the first applied record; the
        # default only parses requests until a lease exists (which the
        # batcher answers 503 anyway)
        self.spec = spec if spec is not None else ResourceSpec()
        self.columns = FollowerColumns()
        self._lock = threading.Lock()
        self.queues: dict = {}
        self.jobs: dict = {}
        self.volume_binder = None
        self.query_plane = None
        for name in self._INGEST:
            setattr(self, name, self._read_only)

    def _read_only(self, *_a, **_k):
        raise ValueError(
            "follower is a read-only replica; ingest on the leader")

    def ingest_batch(self, ops):
        self._read_only()

    def mark_synced(self) -> None:
        pass


class FollowerApplier:
    """Applies decoded replication records: host-array scatter/full
    apply, meta-table patching, device residency, lease publish."""

    def __init__(self, cache: FollowerCache, query_plane, tracer=None) -> None:
        from kube_batch_tpu.api.resident import PerCycleDeviceCache

        self.cache = cache
        self.qp = query_plane
        self.tracer = tracer
        self.fields: Dict[str, np.ndarray] = {}
        self.tables: Optional[dict] = None
        self.applied_seq = 0
        self.applied_version = 0
        self.head_seq = 0
        self.head_version = 0
        self.resident = PerCycleDeviceCache()
        self._static_dev: Dict[str, Tuple[int, object]] = {}
        self._stamp: Dict[str, int] = {}
        self._spec_cache: Tuple[tuple, object] = ((), None)
        # diagnostics (tests/smoke evidence)
        self.applied_records = 0
        self.heartbeats = 0
        self.gaps = 0
        self.full_adoptions = 0
        query_plane.head_fn = self.head

    def head(self) -> Tuple[int, int]:
        """The leader head as of the last fetched frame — the staleness
        bound every verdict this plane serves carries."""
        return (self.head_seq, self.head_version)

    # ---- record application ---------------------------------------------
    def apply(self, frame: bytes) -> str:
        """Consume one wire frame; returns ``"applied"``, ``"heartbeat"``
        or ``"resync"`` (the caller's next pull must force a full)."""
        rec = stream.decode_record(frame)
        self.head_seq = max(self.head_seq, rec.head_seq)
        self.head_version = max(self.head_version, rec.head_version)
        metrics.set_replication_lag(max(0, self.head_seq - self.applied_seq))
        if rec.kind == stream.HEARTBEAT:
            self.heartbeats += 1
            return "heartbeat"
        try:
            if rec.kind == stream.DELTA:
                if (not self.fields
                        or rec.prev_seq != self.applied_seq
                        or rec.prev_version != self.applied_version):
                    # the WarmTableState escalation analog: a chain gap
                    # (missed record, version skip, reconnect) demotes to
                    # a full-snapshot resync instead of guessing
                    self.gaps += 1
                    metrics.register_replication_resync()
                    return "resync"
                self._apply_delta(rec)
            else:
                self._adopt_full(rec)
        except (KeyError, IndexError, ValueError) as e:
            logger.warning("replication apply failed (%s); forcing resync", e)
            self.gaps += 1
            metrics.register_replication_resync()
            return "resync"
        self.applied_seq = rec.seq
        self.applied_version = rec.version
        self.applied_records += 1
        self._publish(rec)
        metrics.register_replication_applied(rec.kind)
        metrics.set_replication_lag(max(0, self.head_seq - self.applied_seq))
        return "applied"

    def _bump(self, field: str) -> None:
        self._stamp[field] = self._stamp.get(field, 0) + 1

    def _apply_delta(self, rec) -> None:
        for field, arr in rec.full.items():
            self.fields[field] = arr
            self._bump(field)
        for field, (rows, vals) in rec.delta.items():
            tgt = self.fields[field]
            if rows.size and (rows.min() < 0 or rows.max() >= tgt.shape[0]):
                raise ValueError(f"delta rows out of range for {field}")
            tgt[rows] = vals
            self._bump(field)
        self.tables = stream.apply_meta_patch(self.tables, rec.meta)

    def _adopt_full(self, rec) -> None:
        """Warm re-adoption: diff each incoming full field against the
        copy already held so unchanged fields keep their stamps (and the
        resident cache keeps their device buffers) — the follower-side
        revalidate_resident."""
        from kube_batch_tpu.api.resident import changed_rows
        from kube_batch_tpu.api.snapshot import ARRAY_FIELDS

        missing = [f for f in ARRAY_FIELDS if f not in rec.full]
        if missing:
            raise ValueError(f"full record missing fields {missing[:3]}")
        for field, arr in rec.full.items():
            cur = self.fields.get(field)
            if (cur is None or cur.shape != arr.shape
                    or cur.dtype != arr.dtype):
                self.fields[field] = arr
                self._bump(field)
                continue
            rows = changed_rows(cur, arr)
            if rows.size:
                cur[rows] = arr[rows]
                self._bump(field)
        self.tables = rec.meta
        self.full_adoptions += 1

    # ---- residency + lease publish --------------------------------------
    def _spec_for(self, lease_wire):
        from kube_batch_tpu.api.resources import ResourceSpec

        names = tuple(lease_wire.get("scalar_names", ()))
        cached_names, cached = self._spec_cache
        if cached is None or cached_names != names:
            cached = ResourceSpec(names)
            self._spec_cache = (names, cached)
        return cached

    def _publish(self, rec) -> None:
        import jax

        from kube_batch_tpu.api.resident import SWAP_FIELDS
        from kube_batch_tpu.api.snapshot import ARRAY_FIELDS, DeviceSnapshot
        from kube_batch_tpu.serve.lease import SnapshotLease

        spec = self._spec_for(rec.lease)
        meta = stream.build_snapshot_meta(self.tables, spec)
        config = stream.config_from_wire(rec.lease["config"])
        evict_config = stream.config_from_wire(rec.lease["evict_config"])
        host_snap = DeviceSnapshot(
            **{f: self.fields[f] for f in ARRAY_FIELDS})
        span = (self.tracer.span("replicate_apply", seq=rec.seq,
                                 kind=rec.kind)
                if self.tracer is not None else None)
        with self.qp.broker.swap_guard():
            if span is not None:
                span.__enter__()
            try:
                dev_snap = self.resident.swap(host_snap)
                updates = {}
                for field in ARRAY_FIELDS:
                    if field in SWAP_FIELDS:
                        continue
                    stamp = self._stamp.get(field, 0)
                    cached = self._static_dev.get(field)
                    if cached is None or cached[0] != stamp:
                        cached = (stamp, jax.device_put(self.fields[field]))
                        self._static_dev[field] = cached
                    updates[field] = cached[1]
                dev_snap = dev_snap._replace(**updates)
            finally:
                if span is not None:
                    span.__exit__(None, None, None)
        lease = SnapshotLease(
            snap=dev_snap, meta=meta, version=rec.version, config=config,
            evict_config=evict_config, mesh=None,
            probe_rows=tuple(int(r) for r in rec.lease["probe_rows"]),
            queue_rows={k: int(v)
                        for k, v in rec.lease["queue_rows"].items()},
            unmodeled_gates=tuple(rec.lease["unmodeled_gates"]),
            seq=rec.seq,
        )
        self.cache.spec = spec
        self.qp.broker.publish(lease)
        metrics.set_whatif_snapshot_version(rec.version)

    def revalidate_resident(self) -> dict:
        """Re-adoption check after a pull-loop restart — the
        ColumnStore.revalidate_resident contract: a resident cache that
        has synced at least one snapshot is KEPT (buffers + compiled
        scatter specializations survive; the next swap absorbs residual
        divergence as ordinary deltas), anything else drops to cold."""
        from kube_batch_tpu.api.resident import PerCycleDeviceCache

        if self.resident.version > 0 and self.fields:
            return {"mode": "warm",
                    "resident_version": self.resident.version}
        self.resident = PerCycleDeviceCache()
        self._static_dev.clear()
        return {"mode": "cold", "resident_version": 0}


class ReplicationFollower:
    """The pull loop: transport + applier + the follower's query plane.
    ``start()`` runs it on a daemon thread; tests drive :meth:`run_once`
    synchronously."""

    def __init__(self, leader_url: str, cache: Optional[FollowerCache] = None,
                 query_plane=None, poll_s: Optional[float] = None,
                 transport=None, tracer=None, timeout: float = 30.0) -> None:
        from kube_batch_tpu.k8s.transport import ApiTransport

        self.cache = cache if cache is not None else FollowerCache()
        if query_plane is None:
            from kube_batch_tpu.serve.plane import QueryPlane

            query_plane = QueryPlane(self.cache)
        self.qp = query_plane
        self.applier = FollowerApplier(self.cache, query_plane, tracer=tracer)
        self.transport = transport if transport is not None \
            else ApiTransport(leader_url, role="replicate")
        self.poll_s = _poll_s() if poll_s is None else poll_s
        self.timeout = timeout
        self._force_full = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.pull_errors = 0

    def run_once(self) -> str:
        """One pull + apply; returns the applier outcome (or ``"error"``
        on a transport failure — the loop just polls again; the breaker
        and retry policy inside the transport do the pacing)."""
        since = -1 if self._force_full else self.applier.applied_seq
        try:
            frame = self.transport.get_bytes(
                f"/v1/replicate?since={since}", timeout=self.timeout)
        except Exception as e:  # noqa: BLE001 — transport already classified
            self.pull_errors += 1
            logger.debug("replication pull failed: %s", e)
            return "error"
        outcome = self.applier.apply(frame)
        if outcome == "resync":
            self._force_full = True
        elif outcome == "applied":
            self._force_full = False
        return outcome

    def _loop(self) -> None:
        # on (re)start, decide warm-vs-cold residency exactly once — the
        # warm-standby re-adoption contract
        mode = self.applier.revalidate_resident()
        logger.info("replication follower loop starting (%s residency)",
                    mode["mode"])
        while not self._stop.is_set():
            outcome = self.run_once()
            if outcome in ("heartbeat", "error"):
                # kbt: allow[KBT011] idle poll cadence — caught-up (or
                # disconnected) followers pace their next pull; applied
                # records loop immediately to drain the backlog
                self._stop.wait(self.poll_s)

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="kb-follower-pull")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()

"""The leader side of the replication stream.

:class:`ReplicationPublisher` hangs off the scheduler cache
(``cache.replication``); :meth:`QueryPlane.publish_session` calls
:meth:`publish_cycle` right after the resident swap, BEFORE the broker
publish, so the lease it installs carries the record's sequence number.

The call is two-phase, overlapped exactly like the scheduler's staged
writeback: the cycle thread only allocates the sequence number, captures
the host array references (the cycle never mutates a captured snapshot)
and joins the PREVIOUS cycle's encode; the diff + frame encode runs on a
one-worker executor while the next cycle solves.  ``drain_pipeline``
joins the in-flight encode through :meth:`barrier`.

The publisher keeps its own host mirrors of ALL snapshot fields (not
just the device cache's per-cycle set) and diffs them with the SAME
:func:`~kube_batch_tpu.api.resident.changed_rows` the scatter refresh
uses — so the wire deltas are row-exact and independent of
KB_DEVICE_CACHE / mesh choice.  For the fields the resident swap
refreshes (per-cycle AND task feature columns) it trusts the swap's own
delta record as a fast path whenever the dirty
tracker advanced by exactly one (``ColumnStore.export_delta_record``);
any other cadence falls back to the self-diff.  The mirrors double as
the source for synthesized full-snapshot resync frames when a
follower's ``since`` token falls off the ring.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Optional, Tuple

import numpy as np

from kube_batch_tpu import metrics
from kube_batch_tpu.envutil import env_int
from kube_batch_tpu.replicate import stream

logger = logging.getLogger("kube_batch_tpu")

#: encoded frames retained for delta serving; a follower further behind
#: than the ring gets a synthesized full-snapshot frame instead
RING_SIZE = env_int("KB_REPL_RING", 64)


def _lease_wire(lease) -> dict:
    """The SnapshotLease extras a follower cannot derive from the arrays:
    configs, probe rows, queue rows, unmodeled gates, the resource axis."""
    config = lease.config
    evict = lease.evict_config
    weights = config.weights
    if weights.extra_rows:
        # host callables cannot cross the wire; publish_session already
        # strips them for its own lease, so this is belt-and-braces
        config = config._replace(weights=weights._replace(extra_rows=()))
    if evict.weights.extra_rows:
        evict = evict._replace(
            weights=evict.weights._replace(extra_rows=()))
    return {
        "config": stream.config_to_wire(config),
        "evict_config": stream.config_to_wire(evict),
        "probe_rows": [int(r) for r in lease.probe_rows],
        "queue_rows": {k: int(v) for k, v in lease.queue_rows.items()},
        "unmodeled_gates": list(lease.unmodeled_gates),
        "scalar_names": list(lease.meta.spec.names[3:]),
    }


class ReplicationPublisher:
    def __init__(self, ring_size: Optional[int] = None, tracer=None) -> None:
        self.ring_size = RING_SIZE if ring_size is None else ring_size
        self.tracer = tracer
        self._lock = threading.RLock()
        self._mirror: Dict[str, np.ndarray] = {}
        self._meta_tables: Optional[dict] = None
        self._lease_wire: Optional[dict] = None
        self._ring: deque = deque()     # (seq, frame bytes)
        self._full_cache: Optional[Tuple[int, bytes]] = None
        self._next_seq = 0              # allocated on the cycle thread
        self._head_seq = 0              # advanced when the encode lands
        self._head_version = 0
        self._last_cache_version = 0    # dirty-tracker token at last publish
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="kb-replicate")
        self._pending: Optional[Future] = None
        self._closed = False
        # diagnostics (smoke/bench evidence)
        self.records = {stream.FULL: 0, stream.DELTA: 0}
        self.heartbeats = 0
        self.bytes_published = 0
        self.hint_fields = 0            # swap fields served by the
        self.diff_fields = 0            # resident delta record vs self-diff
        self.encode_errors = 0

    # ---- cycle thread ----------------------------------------------------
    def publish_cycle(self, snap, meta, lease, delta_hint=None,
                      cache_version: int = 0) -> int:
        """Allocate and return this cycle's record seq; the diff + encode
        is deferred to the worker (joined by the NEXT publish_cycle or by
        :meth:`barrier`).  ``delta_hint`` is the resident swap's own delta
        record (field → rows | None-for-full) with its version token."""
        self.barrier()
        with self._lock:
            if self._closed:
                return self._head_seq
            self._next_seq += 1
            seq = self._next_seq
            hint_ok = (
                delta_hint is not None
                and bool(self._mirror)
                and cache_version == self._last_cache_version + 1
            )
            self._last_cache_version = cache_version
        from kube_batch_tpu.api.snapshot import ARRAY_FIELDS

        fields = {f: np.asarray(getattr(snap, f)) for f in ARRAY_FIELDS}
        tables = stream.meta_tables(meta)
        lease_wire = _lease_wire(lease)
        version = int(lease.version)
        hint = dict(delta_hint) if hint_ok else None
        # the encode's span goes on the record of the cycle that published
        # (as the writeback's does): the cycle's last publish, the lease
        # re-arm, is encoded after the cycle has ended
        record = self.tracer.current if self.tracer is not None else None
        self._pending = self._pool.submit(
            self._encode_cycle, seq, version, fields, tables, lease_wire,
            hint, record)
        return seq

    def barrier(self) -> None:
        """Join the in-flight encode (the scheduler's drain hook — the
        replication analog of awaiting the staged writeback)."""
        fut, self._pending = self._pending, None
        if fut is not None:
            fut.result()

    def close(self) -> None:
        self.barrier()
        with self._lock:
            self._closed = True
        self._pool.shutdown(wait=True)

    def invalidate(self) -> None:
        """Drop the mirrors — the next record is a full snapshot (the
        guard plane's demotion hook: state the leader no longer trusts
        must not keep feeding deltas)."""
        with self._lock:
            self._mirror.clear()
            self._meta_tables = None
            self._full_cache = None

    # ---- worker ----------------------------------------------------------
    def _encode_cycle(self, seq, version, fields, tables, lease_wire, hint,
                      record=None):
        try:
            span = (self.tracer.cycle_span("replicate_encode", record,
                                           seq=seq)
                    if self.tracer is not None else None)
            if span is not None:
                with span:
                    self._encode_locked(seq, version, fields, tables,
                                        lease_wire, hint)
            else:
                self._encode_locked(seq, version, fields, tables,
                                    lease_wire, hint)
        except Exception:
            # a half-updated mirror must never feed another delta — drop
            # everything so the next record is a clean full snapshot
            with self._lock:
                self.encode_errors += 1
            logger.exception("replication encode failed; next record full")
            self.invalidate()

    def _encode_locked(self, seq, version, fields, tables, lease_wire, hint):
        from kube_batch_tpu.api.resident import PerCycleDeviceCache, changed_rows

        with self._lock:
            cold = not self._mirror
            swap_fields = resident_swap_fields()
            full: Dict[str, np.ndarray] = {}
            delta: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
            for field, host in fields.items():
                mirror = self._mirror.get(field)
                if (mirror is None or mirror.shape != host.shape
                        or mirror.dtype != host.dtype):
                    full[field] = host
                    self._mirror[field] = host.copy()
                    continue
                if hint is not None and field in swap_fields:
                    if field not in hint:
                        self.hint_fields += 1
                        continue  # the swap proved this field clean
                    rows = hint[field]
                    if (isinstance(rows, np.ndarray)
                            and (rows.size == 0
                                 or (0 <= rows.min()
                                     and rows.max() < host.shape[0]))):
                        self.hint_fields += 1
                        changed = rows.astype(np.int64, copy=False)
                    else:
                        changed = changed_rows(mirror, host)
                        self.diff_fields += 1
                else:
                    changed = changed_rows(mirror, host)
                    self.diff_fields += 1
                if changed.size == 0:
                    continue
                slots = int(changed.size)
                payload = PerCycleDeviceCache._payload_bytes(slots, host)
                if payload >= host.nbytes:
                    full[field] = host
                    self._mirror[field] = host.copy()
                else:
                    vals = np.ascontiguousarray(host[changed])
                    delta[field] = (changed.astype(np.int32), vals)
                    mirror[changed] = vals
            if cold or self._meta_tables is None:
                kind, meta_out = stream.FULL, tables
            else:
                kind = stream.DELTA
                meta_out = stream.meta_patch(self._meta_tables, tables)
            rec = stream.ReplicationRecord(
                kind=kind, seq=seq, version=version,
                prev_seq=(-1 if kind == stream.FULL else self._head_seq),
                prev_version=(-1 if kind == stream.FULL
                              else self._head_version),
                head_seq=seq, head_version=version,
                full=full, delta=delta, meta=meta_out, lease=lease_wire)
            frame = stream.encode_record(rec)
            self._meta_tables = tables
            self._lease_wire = lease_wire
            self._ring.append((seq, frame))
            while len(self._ring) > self.ring_size:
                self._ring.popleft()
            self._full_cache = None
            self._head_seq = seq
            self._head_version = version
            self.records[kind] += 1
            self.bytes_published += len(frame)
        metrics.register_replication_record(kind, len(frame))

    # ---- serving (HTTP threads) -----------------------------------------
    def record_for(self, since: int) -> bytes:
        """The frame a follower at applied-seq ``since`` should consume
        next: its exact successor delta when the ring still holds it, a
        heartbeat when it is caught up, a synthesized full snapshot
        otherwise (cold start, ring fall-off, or an explicit ``since=-1``
        resync request)."""
        with self._lock:
            head_seq, head_version = self._head_seq, self._head_version
            if head_seq == 0 or since >= head_seq:
                self.heartbeats += 1
                return self._heartbeat(head_seq, head_version)
            if since >= 0:
                for seq, frame in self._ring:
                    if seq == since + 1:
                        return frame
            return self._full_frame(head_seq, head_version)

    def _heartbeat(self, head_seq: int, head_version: int) -> bytes:
        rec = stream.ReplicationRecord(
            kind=stream.HEARTBEAT, seq=head_seq, version=head_version,
            prev_seq=-1, prev_version=-1,
            head_seq=head_seq, head_version=head_version,
            full={}, delta={}, meta={}, lease={})
        return stream.encode_record(rec)

    def _full_frame(self, head_seq: int, head_version: int) -> bytes:
        # caller holds the lock; cache per head so a herd of resyncing
        # followers pays one encode
        if self._full_cache is not None and self._full_cache[0] == head_seq:
            return self._full_cache[1]
        rec = stream.ReplicationRecord(
            kind=stream.FULL, seq=head_seq, version=head_version,
            prev_seq=-1, prev_version=-1,
            head_seq=head_seq, head_version=head_version,
            full=dict(self._mirror), delta={},
            meta=self._meta_tables or {}, lease=self._lease_wire or {})
        frame = stream.encode_record(rec)
        self._full_cache = (head_seq, frame)
        self.records[stream.FULL] += 1
        self.bytes_published += len(frame)
        metrics.register_replication_record(stream.FULL, len(frame))
        return frame

    def counters(self) -> dict:
        with self._lock:
            return {
                "head_seq": self._head_seq,
                "head_version": self._head_version,
                "records_full": self.records[stream.FULL],
                "records_delta": self.records[stream.DELTA],
                "heartbeats": self.heartbeats,
                "bytes_published": self.bytes_published,
                "hint_fields": self.hint_fields,
                "diff_fields": self.diff_fields,
                "encode_errors": self.encode_errors,
                "ring": len(self._ring),
            }


def resident_swap_fields():
    """The field set a resident swap refreshes and records (lazy import —
    resident.py pulls jitstats)."""
    from kube_batch_tpu.api.resident import SWAP_FIELDS

    return SWAP_FIELDS

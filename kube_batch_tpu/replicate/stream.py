"""KBR1 — the replication stream's wire format.

One frame per cycle:

    b"KBR1" | u32 header length (big-endian) | UTF-8 JSON header | payload

The header carries the record identity (seq / version / prev chain / the
leader's head at send time), the decode tables (SnapshotMeta name lists
and bit maps — full on ``kind="full"``, patches on ``kind="delta"``),
the lease extras a follower needs to rebuild a byte-identical
SnapshotLease (config, evict config, probe rows, queue rows, unmodeled
gates, the resource-spec scalar names), and an array directory: for each
payload array its name, dtype, shape and byte offset into the payload.

Array naming mirrors the resident cache's scatter discipline
(api/resident.py): a field arrives either FULL (``f:<field>``) or as a
row-exact scatter pair (``d:<field>:rows`` int32 + ``d:<field>:vals``);
a clean field is simply absent.  A delta frame whose payload would reach
the full array's bytes is escalated to full by the publisher — the same
break-even the device scatter path uses.

Record kinds:

- ``"full"``      — every field full, full decode tables.  Sent for the
  first cycle, and synthesized from the leader's mirrors for any
  follower whose ``since`` token falls off the ring (the resync path).
- ``"delta"``     — changed rows only, table patches; ``prev_seq`` /
  ``prev_version`` name the exact predecessor state it applies to.
- ``"heartbeat"`` — no payload; carries the leader head so an idle
  follower still reports fresh staleness.

Configs cross the wire as tagged NamedTuple dicts via a closed registry
(AllocateConfig / EvictConfig / ScoreWeights) — ``ScoreWeights.extra_rows``
holds host callables and is forced empty by the publisher before encode.
This module is jax-free: framing is pure numpy + json.
"""

from __future__ import annotations

import json
from typing import Dict, List, NamedTuple, Tuple

import numpy as np

MAGIC = b"KBR1"

#: record kinds (header ``kind`` field)
FULL, DELTA, HEARTBEAT = "full", "delta", "heartbeat"


class ReplicationRecord(NamedTuple):
    """One decoded frame — the publisher builds these, the follower
    applies them."""

    kind: str           # "full" | "delta" | "heartbeat"
    seq: int            # this record's cycle sequence number
    version: int        # dirty-tracker version token at this cycle
    prev_seq: int       # delta chain predecessor (-1 for full/heartbeat)
    prev_version: int
    head_seq: int       # leader head at send time (staleness source)
    head_version: int
    full: Dict[str, np.ndarray]                       # field → full array
    delta: Dict[str, Tuple[np.ndarray, np.ndarray]]   # field → (rows, vals)
    meta: dict          # decode tables (full) or table patches (delta)
    lease: dict         # config/evict/probe_rows/queue_rows/gates/spec


# ---- config wire ---------------------------------------------------------

def _config_registry():
    """The closed set of NamedTuple config types that may cross the wire.
    Imported lazily — the registry members pull in jax-adjacent modules."""
    from kube_batch_tpu.ops.assignment import AllocateConfig
    from kube_batch_tpu.ops.eviction import EvictConfig
    from kube_batch_tpu.ops.scoring import ScoreWeights

    return {t.__name__: t for t in (AllocateConfig, EvictConfig, ScoreWeights)}


def config_to_wire(cfg):
    """Tagged-dict encoding of a registered config NamedTuple (recursing
    into nested registered members); scalars pass through."""
    reg = _config_registry()
    if type(cfg).__name__ in reg and isinstance(cfg, tuple):
        fields = {}
        for name, val in zip(cfg._fields, cfg):
            fields[name] = config_to_wire(val)
        return {"__cfg__": type(cfg).__name__, "fields": fields}
    if isinstance(cfg, tuple):
        return {"__tuple__": [config_to_wire(v) for v in cfg]}
    if isinstance(cfg, (bool, int, float, str)) or cfg is None:
        return cfg
    raise TypeError(f"config value {cfg!r} is not wire-serializable")


def config_from_wire(obj):
    """Inverse of :func:`config_to_wire`."""
    if isinstance(obj, dict) and "__cfg__" in obj:
        from kube_batch_tpu.ops.assignment import without_removed_fields

        cls = _config_registry()[obj["__cfg__"]]
        kwargs = {k: config_from_wire(v) for k, v in obj["fields"].items()}
        # a leader not yet upgraded still sends what this version removed
        return cls(**without_removed_fields(kwargs))
    if isinstance(obj, dict) and "__tuple__" in obj:
        return tuple(config_from_wire(v) for v in obj["__tuple__"])
    return obj


# ---- frame encode / decode ----------------------------------------------

def encode_record(rec: ReplicationRecord) -> bytes:
    """Serialize a record to one KBR1 frame."""
    arrays: List[dict] = []
    buffers: List[bytes] = []
    offset = 0

    def add(name: str, arr: np.ndarray) -> None:
        nonlocal offset
        a = np.ascontiguousarray(arr)
        buf = a.tobytes()
        arrays.append({"name": name, "dtype": a.dtype.str,
                       "shape": list(a.shape), "offset": offset,
                       "nbytes": len(buf)})
        buffers.append(buf)
        offset += len(buf)

    for field in sorted(rec.full):
        add(f"f:{field}", rec.full[field])
    for field in sorted(rec.delta):
        rows, vals = rec.delta[field]
        add(f"d:{field}:rows", np.asarray(rows, np.int32))
        add(f"d:{field}:vals", vals)

    header = {
        "kind": rec.kind, "seq": rec.seq, "version": rec.version,
        "prev_seq": rec.prev_seq, "prev_version": rec.prev_version,
        "head_seq": rec.head_seq, "head_version": rec.head_version,
        "meta": rec.meta, "lease": rec.lease, "arrays": arrays,
    }
    hbytes = json.dumps(header, separators=(",", ":")).encode()
    return b"".join([MAGIC, len(hbytes).to_bytes(4, "big"), hbytes, *buffers])


def decode_record(buf: bytes) -> ReplicationRecord:
    """Parse one KBR1 frame.  Decoded arrays are fresh writable copies —
    the follower applies scatters in place on the full-field arrays it
    adopted, so views into the network buffer would be a trap."""
    if len(buf) < 8 or buf[:4] != MAGIC:
        raise ValueError("not a KBR1 replication frame")
    hlen = int.from_bytes(buf[4:8], "big")
    if len(buf) < 8 + hlen:
        raise ValueError("truncated KBR1 header")
    header = json.loads(buf[8:8 + hlen].decode())
    payload = buf[8 + hlen:]

    decoded: Dict[str, np.ndarray] = {}
    for ent in header["arrays"]:
        start, n = ent["offset"], ent["nbytes"]
        if start + n > len(payload):
            raise ValueError(f"truncated KBR1 payload at {ent['name']}")
        arr = np.frombuffer(payload[start:start + n],
                            dtype=np.dtype(ent["dtype"]))
        decoded[ent["name"]] = arr.reshape(ent["shape"]).copy()

    full: Dict[str, np.ndarray] = {}
    delta: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
    for name, arr in decoded.items():
        if name.startswith("f:"):
            full[name[2:]] = arr
        elif name.startswith("d:") and name.endswith(":rows"):
            field = name[2:-5]
            delta[field] = (arr, decoded[f"d:{field}:vals"])

    return ReplicationRecord(
        kind=header["kind"], seq=header["seq"], version=header["version"],
        prev_seq=header["prev_seq"], prev_version=header["prev_version"],
        head_seq=header["head_seq"], head_version=header["head_version"],
        full=full, delta=delta, meta=header["meta"], lease=header["lease"],
    )


# ---- meta tables ---------------------------------------------------------

_NAME_LISTS = ("task_keys", "node_names", "job_uids", "queue_names")


def meta_tables(meta) -> dict:
    """SnapshotMeta → the JSON-clean decode tables a follower needs to
    rebuild it (object references and host-side caches excluded)."""
    return {
        "task_keys": list(meta.task_keys),
        "node_names": list(meta.node_names),
        "job_uids": list(meta.job_uids),
        "queue_names": list(meta.queue_names),
        "label_pair_bit": [[k, v, b] for (k, v), b
                           in sorted(meta.label_pair_bit.items())],
        "taint_bit": [[k, v, e, b] for (k, v, e), b
                      in sorted(meta.taint_bit.items())],
        "counts": [meta.n_tasks, meta.n_nodes, meta.n_jobs, meta.n_queues],
    }


def meta_patch(prev: dict, cur: dict) -> dict:
    """The delta-record table patch taking ``prev`` tables to ``cur``:
    name lists ship only their changed entries (+ the new length); the
    bit maps ship whole whenever they changed at all — bit REUSE after a
    churn-out would silently corrupt selector decoding otherwise, and
    the maps are small."""
    patch: dict = {"counts": cur["counts"]}
    for key in _NAME_LISTS:
        p, c = prev[key], cur[key]
        changed = {str(i): v for i, v in enumerate(c)
                   if i >= len(p) or p[i] != v}
        patch[key] = {"len": len(c), "set": changed}
    for key in ("label_pair_bit", "taint_bit"):
        if prev[key] != cur[key]:
            patch[key] = cur[key]
    return patch


def apply_meta_patch(tables: dict, patch: dict) -> dict:
    """Apply a :func:`meta_patch` to a follower's current tables."""
    out = dict(tables)
    out["counts"] = patch["counts"]
    for key in _NAME_LISTS:
        ent = patch[key]
        lst = list(out[key])[:ent["len"]]
        lst.extend([""] * (ent["len"] - len(lst)))
        for i, v in ent["set"].items():
            lst[int(i)] = v
        out[key] = lst
    for key in ("label_pair_bit", "taint_bit"):
        if key in patch:
            out[key] = patch[key]
    return out


def build_snapshot_meta(tables: dict, spec):
    """Follower-side SnapshotMeta from wire tables: decode tables only —
    the host object references (task_objs/job_objs/node_objs) and the
    64-bit host shadows stay empty, which is exactly the subset the
    probe/decode path consumes."""
    from kube_batch_tpu.api.snapshot import SnapshotMeta

    n_tasks, n_nodes, n_jobs, n_queues = tables["counts"]
    return SnapshotMeta(
        spec=spec,
        task_keys=list(tables["task_keys"]),
        node_names=list(tables["node_names"]),
        job_uids=list(tables["job_uids"]),
        queue_names=list(tables["queue_names"]),
        label_pair_bit={(k, v): b for k, v, b in tables["label_pair_bit"]},
        taint_bit={(k, v, e): b for k, v, e, b in tables["taint_bit"]},
        n_tasks=n_tasks, n_nodes=n_nodes, n_jobs=n_jobs, n_queues=n_queues,
    )

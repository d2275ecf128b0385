"""Guard diagnostics bundles — self-contained trip captures for offline
fast-vs-oracle triage.

On every guard trip the dispatching action dumps the EXACT solve problem
it condemned: the device snapshot's columns (the post-resident-swap arrays
the solve consumed — a corrupted resident word is captured corrupted),
the solve configuration, the compaction plan, the knob environment, and
the violation report.  The write uses cache/persistence.py's atomic idiom
(write into a temp sibling, ``os.replace`` into place) so a crash mid-dump
never leaves a half bundle that replays differently.

``python -m kube_batch_tpu.sim --replay-bundle <dir>`` reloads a bundle
and re-runs the condemned program AND its oracle (KB_TOPK=0 /
full-matrix) on the captured snapshot, sentinel-fused both ways —
deterministic reproduction of the trip without the cluster, the workload,
or the timing that produced it.

Bundle layout: ``<dir>/meta.json`` (config, knobs, violation report,
invariant names) + ``<dir>/arrays.npz`` (every DeviceSnapshot field, plus
``pend_rows`` when the compacted path was engaged).  ScoreWeights
``extra_rows`` (registered score functions) are not serializable — the
replay notes their names and runs without them.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
from typing import Dict, Optional

import numpy as np

logger = logging.getLogger("kube_batch_tpu")

_KNOBS = (
    "KB_TOPK", "KB_SHARD_MAP", "KB_SHARD", "KB_TASK_SHARDS",
    "KB_GUARD", "KB_AUDIT_EVERY", "KB_GUARD_COOLDOWN", "KB_DEVICE_CACHE",
    "KB_SNAPSHOT_DELTA", "KB_PIPELINE", "JAX_PLATFORMS",
)


def bundle_dir() -> str:
    return os.environ.get("KB_GUARD_DIR", "").strip() or "guard-bundles"


def _weights_dict(weights) -> Dict:
    d = weights._asdict()
    extra = d.pop("extra_rows", ()) or ()
    d["extra_row_names"] = [name for (name, _fn, _w) in extra]
    return d


def _config_dict(config) -> Dict:
    d = config._asdict()
    w = d.pop("weights", None)
    if w is not None:
        d["weights"] = _weights_dict(w)
    return d


def dump_bundle(action: str, snap, config, report: Dict,
                pend_rows: Optional[np.ndarray] = None,
                directory: Optional[str] = None) -> str:
    """Write one diagnostics bundle; returns its path.  ``snap`` is the
    DeviceSnapshot the condemned solve consumed (device or host-backed —
    read back here, once, on the rare trip path)."""
    import jax

    from kube_batch_tpu.api.snapshot import ARRAY_FIELDS
    from kube_batch_tpu.ops.invariants import INVARIANT_NAMES

    root = directory or bundle_dir()
    os.makedirs(root, exist_ok=True)
    # kbt: allow[KBT010] trip-path readback — the bundle must capture the
    # exact (possibly corrupted) device bytes the solve consumed
    host = jax.device_get(snap)
    arrays = {f: np.asarray(getattr(host, f)) for f in ARRAY_FIELDS}
    if host.aff_terms is not None:
        arrays.update({f"aff_terms.{k}": np.asarray(v)
                       for k, v in host.aff_terms._asdict().items()})
    if pend_rows is not None:
        arrays["pend_rows"] = np.asarray(pend_rows)
    meta = {
        "schema": 1,
        "action": action,
        "config": _config_dict(config),
        "config_kind": type(config).__name__,
        "report": report,
        "invariant_names": list(INVARIANT_NAMES),
        "knobs": {k: os.environ.get(k, "") for k in _KNOBS},
        "has_pend_rows": pend_rows is not None,
    }
    # atomic publish: build the whole bundle in a temp sibling dir, then
    # one rename — the persistence.py idiom, directory-shaped
    tmp = tempfile.mkdtemp(dir=root, prefix=".tmp-bundle-")
    try:
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f, indent=2, sort_keys=True)
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        serial = 0
        while True:
            final = os.path.join(root, f"trip-{action}-{serial:04d}")
            if not os.path.exists(final):
                try:
                    os.replace(tmp, final)
                    break
                except OSError:
                    pass  # lost the race to a concurrent dump — next serial
            serial += 1
            if serial > 9999:
                raise OSError("guard bundle directory full")
    except BaseException:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
        raise
    logger.warning("guard diagnostics bundle written: %s", final)
    return final


def load_bundle(path: str):
    """(DeviceSnapshot of host arrays, meta dict, pend_rows|None)."""
    from kube_batch_tpu.api.snapshot import ARRAY_FIELDS, DeviceSnapshot

    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    with np.load(os.path.join(path, "arrays.npz")) as z:
        arrays = {k: z[k] for k in z.files}
    pend_rows = arrays.pop("pend_rows", None)
    snap = DeviceSnapshot(**{f: arrays[f] for f in ARRAY_FIELDS})
    terms = {k.split(".", 1)[1]: v for k, v in arrays.items()
             if k.startswith("aff_terms.")}
    if terms:
        from kube_batch_tpu.api.affinity_planes import AffinityTerms

        snap = snap._replace(aff_terms=AffinityTerms(**terms))
    return snap, meta, pend_rows


def _rebuild_config(meta: Dict):
    from kube_batch_tpu.ops.assignment import (
        AllocateConfig,
        without_removed_fields,
    )
    from kube_batch_tpu.ops.eviction import EvictConfig
    from kube_batch_tpu.ops.scoring import ScoreWeights

    d = without_removed_fields(meta["config"])  # a bundle from before PR 29
    w = d.pop("weights", None)
    dropped = []
    if w is not None:
        w = dict(w)
        dropped = w.pop("extra_row_names", [])
        d["weights"] = ScoreWeights(**w)
    cls = AllocateConfig if meta["config_kind"] == "AllocateConfig" else EvictConfig
    return cls(**d), dropped


def replay_bundle(path: str) -> Dict:
    """Re-run a bundle's condemned program and its oracle, sentinel-fused
    both ways, on the captured snapshot — the deterministic offline
    fast-vs-oracle triage.  Returns a JSON-shaped report; ``reproduced``
    is True when the replay re-derives an integrity failure (a nonzero
    sentinel verdict on the fast program, or a fast-vs-oracle mismatch)."""
    import jax

    from kube_batch_tpu.ops.invariants import (
        INVARIANT_NAMES,
        allocate_sentinel_solve,
        allocate_topk_sentinel_solve,
        evict_sentinel_solve,
    )

    snap_host, meta, pend_rows = load_bundle(path)
    config, dropped_rows = _rebuild_config(meta)
    snap = jax.tree_util.tree_map(jax.numpy.asarray, snap_host)
    out: Dict = {
        "bundle": path,
        "action": meta["action"],
        "original_report": meta["report"],
        "weights_extra_rows_dropped": dropped_rows,
    }
    # device-vs-host divergence (the eligibility cross-check): the bundle
    # records the HOST's checksum at trip time; the captured snapshot is
    # the DEVICE's — a mismatch reproduces a flipped status/pending word
    # that the device-side invariants alone cannot see
    host_ck = meta["report"].get("host_checksum")
    ck_mismatch = False
    if host_ck is not None:
        from kube_batch_tpu.ops.invariants import eligibility_checksum

        dev_ck = int(jax.device_get(eligibility_checksum(snap))) & 0xFFFFFFFF
        ck_mismatch = dev_ck != (int(host_ck) & 0xFFFFFFFF)
        out["host_checksum_mismatch"] = ck_mismatch

    def _hist(h):
        h = np.asarray(h)
        return {n: int(c) for n, c in zip(INVARIANT_NAMES, h) if c}

    if meta["config_kind"] == "EvictConfig":
        res, v, h, _e = evict_sentinel_solve(
            snap, config,
            None if pend_rows is None else jax.numpy.asarray(pend_rows))
        claim, evicted, verdict = jax.device_get(
            (res.claim_node, res.evicted, v)
        )
        out.update(
            fast_verdict=int(verdict), fast_violations=_hist(jax.device_get(h)),
            claims=int((np.asarray(claim) >= 0).sum()),
            victims=int(np.asarray(evicted).sum()),
            reproduced=bool(int(verdict) != 0 or ck_mismatch),
        )
        return out

    # allocate-shaped: fast (as captured) vs oracle (every knob off)
    if pend_rows is not None and config.topk > 0:
        fast_res, fv, fh, _e = allocate_topk_sentinel_solve(
            snap, jax.numpy.asarray(pend_rows), config
        )
        fast_name = f"topk[K={config.topk}]"
    else:
        fast_res, fv, fh, _e = allocate_sentinel_solve(snap, config)
        fast_name = "full"
    oracle_cfg = config._replace(topk=0)
    orc_res, ov, oh, _oe = allocate_sentinel_solve(snap, oracle_cfg)
    (f_assigned, f_pipe, fv, fh, o_assigned, o_pipe, ov, oh) = jax.device_get(
        (fast_res.assigned, fast_res.pipelined, fv, fh,
         orc_res.assigned, orc_res.pipelined, ov, oh)
    )
    mismatch_rows = np.flatnonzero(
        (np.asarray(f_assigned) != np.asarray(o_assigned))
        | (np.asarray(f_pipe) != np.asarray(o_pipe))
    )
    out.update(
        fast_program=fast_name,
        fast_verdict=int(fv), fast_violations=_hist(fh),
        oracle_verdict=int(ov), oracle_violations=_hist(oh),
        fast_vs_oracle_mismatch_rows=mismatch_rows[:64].tolist(),
        fast_vs_oracle_mismatches=int(mismatch_rows.size),
        reproduced=bool(int(fv) != 0 or mismatch_rows.size or ck_mismatch),
    )
    return out

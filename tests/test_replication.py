"""Replicated follower read plane (replicate/): KBR1 wire round-trips,
frozen-snapshot leader/follower bit-match, delta-chain application under
churn, staleness bounds, gap→full-resync escalation, warm restart
re-adoption, and the server-side /v1/whatif/sweep search.

The bit-match tests are the subsystem's contract: a follower that has
applied the leader's record for cycle N must answer /v1/whatif (and
/v1/whatif/sweep) BYTE-identically to the leader frozen at cycle N —
same verdict, same placement, same staleness block."""

from __future__ import annotations

import json

import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.pod import Pod, PodGroup, Queue
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.framework.interface import get_action
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.replicate import stream
from kube_batch_tpu.replicate.follower import (
    FollowerApplier,
    FollowerCache,
    ReplicationFollower,
)
from kube_batch_tpu.replicate.publisher import ReplicationPublisher
from kube_batch_tpu.serve.plane import QueryPlane, WhatifError

from fixtures import GiB, build_cache, build_node, build_pod

CONF = load_scheduler_conf(None)


def _run(cache, names=("allocate",)):
    ssn = open_session(cache, CONF.tiers)
    try:
        for name in names:
            get_action(name).execute(ssn)
    finally:
        close_session(ssn)
    cache.flush_binds()


def _probe(qp: QueryPlane, body: dict) -> dict:
    fut = qp.submit(body)
    qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
    return fut.result(timeout=60)


def _sweep(qp: QueryPlane, body: dict) -> dict:
    fut = qp.submit_sweep(body)
    qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
    return fut.result(timeout=60)


def _canon(resp: dict) -> str:
    return json.dumps(resp, sort_keys=True)


class _LoopbackTransport:
    """In-process stand-in for ApiTransport.get_bytes — serves the
    publisher's ring directly, with a kill switch for reconnect tests."""

    def __init__(self, pub: ReplicationPublisher) -> None:
        self.pub = pub
        self.down = False

    def get_bytes(self, path: str, timeout: float = 60) -> bytes:
        if self.down:
            raise OSError("leader unreachable")
        since = int(path.rsplit("since=", 1)[1])
        return self.pub.record_for(since)


@pytest.fixture
def plane_factory():
    planes = []

    def make(cache, **kw):
        kw.setdefault("start_thread", False)
        qp = QueryPlane(cache, **kw)
        planes.append(qp)
        return qp

    yield make
    for qp in planes:
        qp.close()


@pytest.fixture
def leader(plane_factory):
    """A leader cache with a published lease and an attached publisher."""
    cache = build_cache(
        queues=[Queue(name="default", weight=1)],
        pod_groups=[PodGroup(name="run0", namespace="c1", min_member=1,
                             queue="default")],
        nodes=[build_node(f"n{i}", cpu=8000, mem=16 * GiB, pods=32)
               for i in range(4)],
        pods=[build_pod("c1", "r0", "n0", PodPhase.RUNNING,
                        {"cpu": 6000, "memory": 4 * GiB},
                        group_name="run0")],
    )
    qp = plane_factory(cache)
    cache.replication = pub = ReplicationPublisher()
    try:
        _run(cache)
        pub.barrier()
        yield cache, qp, pub
    finally:
        pub.close()


def _make_follower(pub, plane_factory):
    fcache = FollowerCache()
    fqp = plane_factory(fcache)
    f = ReplicationFollower("http://unused", cache=fcache, query_plane=fqp,
                            transport=_LoopbackTransport(pub), poll_s=0.001)
    return f, fqp


def _churn(cache, i):
    """One ingest step: a new single-member gang that will bind."""
    cache.add_pod_group(PodGroup(name=f"churn-{i}", namespace="c1",
                                 min_member=1, queue="default"))
    cache.add_pod(build_pod("c1", f"churn-{i}-0", None, PodPhase.PENDING,
                            {"cpu": 200, "memory": 256 << 20},
                            group_name=f"churn-{i}"))


# ==========================================================================
# KBR1 wire format
# ==========================================================================


class TestWireFormat:
    def _record(self, kind=stream.FULL, **kw):
        base = dict(
            kind=kind, seq=3, version=17, prev_seq=2, prev_version=16,
            head_seq=3, head_version=17,
            full={}, delta={}, meta={"counts": [1, 2, 3, 4]},
            lease={"probe_rows": [0, 1]},
        )
        base.update(kw)
        return stream.ReplicationRecord(**base)

    def test_full_frame_round_trip(self):
        full = {
            "a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.array([1, -2, 3], np.int64),
            "c": np.array([True, False]),
        }
        rec = self._record(full=full)
        out = stream.decode_record(stream.encode_record(rec))
        assert (out.kind, out.seq, out.version) == (stream.FULL, 3, 17)
        assert (out.head_seq, out.head_version) == (3, 17)
        assert out.meta == {"counts": [1, 2, 3, 4]}
        assert out.lease == {"probe_rows": [0, 1]}
        assert sorted(out.full) == ["a", "b", "c"]
        for k in full:
            assert out.full[k].dtype == full[k].dtype
            np.testing.assert_array_equal(out.full[k], full[k])
        # decoded arrays must be writable — the applier scatters in place
        out.full["a"][0, 0] = 99.0

    def test_delta_frame_round_trip(self):
        delta = {
            "x": (np.array([0, 5], np.int32),
                  np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)),
            "y": (np.array([2], np.int32), np.array([7], np.int64)),
        }
        rec = self._record(kind=stream.DELTA, delta=delta)
        out = stream.decode_record(stream.encode_record(rec))
        assert out.kind == stream.DELTA
        assert (out.prev_seq, out.prev_version) == (2, 16)
        assert sorted(out.delta) == ["x", "y"]
        for k, (rows, vals) in delta.items():
            np.testing.assert_array_equal(out.delta[k][0], rows)
            np.testing.assert_array_equal(out.delta[k][1], vals)

    def test_heartbeat_round_trip(self):
        rec = self._record(kind=stream.HEARTBEAT, prev_seq=-1,
                           prev_version=-1, meta={}, lease={})
        out = stream.decode_record(stream.encode_record(rec))
        assert out.kind == stream.HEARTBEAT
        assert not out.full and not out.delta

    def test_malformed_frames_rejected(self):
        rec = self._record(full={"a": np.zeros(4, np.float32)})
        frame = stream.encode_record(rec)
        with pytest.raises(ValueError):
            stream.decode_record(b"NOPE" + frame[4:])
        with pytest.raises(ValueError):
            stream.decode_record(frame[:6])          # truncated header len
        with pytest.raises(ValueError):
            stream.decode_record(frame[:-4])         # truncated payload

    def test_config_wire_round_trip(self):
        from kube_batch_tpu.ops.assignment import AllocateConfig
        from kube_batch_tpu.ops.eviction import EvictConfig

        for cfg in (AllocateConfig(), EvictConfig()):
            wire = stream.config_to_wire(cfg)
            json.dumps(wire)  # must be JSON-clean
            assert stream.config_from_wire(wire) == cfg
        with pytest.raises(TypeError):
            stream.config_to_wire(object())

    def test_config_from_a_leader_before_the_pallas_removal(self):
        """A leader not yet upgraded still sends ``use_pallas``: off, the
        follower builds the same config; on, it names a program that is
        gone."""
        from kube_batch_tpu.ops.assignment import AllocateConfig

        wire = stream.config_to_wire(AllocateConfig())
        wire["fields"]["use_pallas"] = False
        assert stream.config_from_wire(wire) == AllocateConfig()
        wire["fields"]["use_pallas"] = True
        with pytest.raises(ValueError, match="removed in PR 29"):
            stream.config_from_wire(wire)

    def test_meta_patch_round_trip(self):
        prev = {
            "task_keys": ["a/0", "a/1", "b/0"],
            "node_names": ["n0", "n1"],
            "job_uids": ["j0"],
            "queue_names": ["default"],
            "label_pair_bit": [["zone", "a", 0]],
            "taint_bit": [],
            "counts": [3, 2, 1, 1],
        }
        cur = {
            "task_keys": ["a/0", "c/0", "b/0", "c/1"],   # churn + growth
            "node_names": ["n0"],                        # shrink
            "job_uids": ["j0", "j1"],
            "queue_names": ["default"],
            "label_pair_bit": [["zone", "a", 0], ["zone", "b", 1]],
            "taint_bit": [["k", "v", "NoSchedule", 0]],
            "counts": [4, 1, 2, 1],
        }
        patch = stream.meta_patch(prev, cur)
        json.dumps(patch)
        assert stream.apply_meta_patch(prev, patch) == cur
        # unchanged lists travel as empty sets, unchanged maps are absent
        assert patch["queue_names"]["set"] == {}
        null = stream.meta_patch(cur, cur)
        assert "label_pair_bit" not in null and "taint_bit" not in null
        assert stream.apply_meta_patch(cur, null) == cur


# ==========================================================================
# leader/follower bit-match + delta chain
# ==========================================================================


BODY = {"queue": "default", "count": 2,
        "requests": {"cpu": 1500, "memory": 2 * GiB},
        "min_resources": {"cpu": 3000}}


class TestFollowerServing:
    def test_frozen_snapshot_bit_match(self, leader, plane_factory):
        cache, qp, pub = leader
        f, fqp = _make_follower(pub, plane_factory)
        assert f.run_once() == "applied"
        assert f.applier.applied_seq == 1
        r_leader = _probe(qp, BODY)
        r_follower = _probe(fqp, BODY)
        assert _canon(r_leader) == _canon(r_follower)
        assert r_follower["staleness"]["lag_cycles"] == 0
        # the sweep endpoint must agree bit-for-bit as well
        sweep_body = {"queue": "default", "max_count": 16,
                      "requests": {"cpu": 4000, "memory": 2 * GiB}}
        assert _canon(_sweep(qp, sweep_body)) == \
            _canon(_sweep(fqp, sweep_body))

    def test_delta_chain_under_churn_stays_bit_identical(
            self, leader, plane_factory):
        cache, qp, pub = leader
        f, fqp = _make_follower(pub, plane_factory)
        assert f.run_once() == "applied"
        lags = []
        for i in range(6):
            _churn(cache, i)
            _run(cache)
            pub.barrier()
            # pre-pull lag: how far the stream head ran ahead of this
            # follower — the staleness bound under per-cycle pulling
            rec = stream.decode_record(
                pub.record_for(f.applier.applied_seq))
            lags.append(rec.head_seq - f.applier.applied_seq)
            assert f.run_once() == "applied"
            assert _canon(_probe(qp, BODY)) == _canon(_probe(fqp, BODY))
        assert pub.counters()["records_delta"] >= 5, (
            "steady-state churn must travel as deltas, not full snapshots"
        )
        assert f.applier.applied_seq == 7
        assert float(np.percentile(lags, 99)) <= 1.0
        # caught up → the next pull is a heartbeat, not a re-send
        assert f.run_once() == "heartbeat"

    def test_meta_growth_crosses_the_wire(self, leader, plane_factory):
        """Churn that GROWS the row axes (new tasks/jobs) must decode on
        the follower — name lists patch, scatter rows stay in range."""
        cache, qp, pub = leader
        f, fqp = _make_follower(pub, plane_factory)
        f.run_once()
        for i in range(3):
            _churn(cache, 100 + i)
            _run(cache)
            pub.barrier()
            assert f.run_once() == "applied"
        body = {"queue": "default", "count": 1,
                "requests": {"cpu": 500, "memory": GiB}}
        assert _canon(_probe(qp, body)) == _canon(_probe(fqp, body))

    def test_follower_cache_rejects_ingest(self, leader, plane_factory):
        _, _, pub = leader
        f, _ = _make_follower(pub, plane_factory)
        with pytest.raises(ValueError, match="read-only replica"):
            f.cache.add_node(build_node("nx", cpu=1000, mem=GiB))
        with pytest.raises(ValueError, match="read-only replica"):
            f.cache.ingest_batch([])


# ==========================================================================
# gap → resync escalation, reconnect, warm restart
# ==========================================================================


class TestResyncAndRestart:
    def test_delta_gap_escalates_to_full_resync(self, leader, plane_factory):
        cache, qp, pub = leader
        f, fqp = _make_follower(pub, plane_factory)
        assert f.run_once() == "applied"
        for i in range(2):
            _churn(cache, i)
            _run(cache)
        pub.barrier()
        # feed the seq-3 delta to a follower at seq 1 — a chain gap; the
        # applier must refuse (not guess) and force the next pull full
        skipped = pub.record_for(2)
        assert stream.decode_record(skipped).kind == stream.DELTA
        assert f.applier.apply(skipped) == "resync"
        assert f.applier.gaps == 1
        assert f.applier.applied_seq == 1, "a refused record must not apply"
        f._force_full = True
        assert f.run_once() == "applied"
        assert f.applier.applied_seq == 3
        assert f.applier.full_adoptions >= 1
        assert _canon(_probe(qp, BODY)) == _canon(_probe(fqp, BODY))

    def test_ring_falloff_serves_synthesized_full(self, plane_factory):
        cache = build_cache(
            queues=[Queue(name="default", weight=1)],
            nodes=[build_node("n0", cpu=8000, mem=16 * GiB)],
        )
        qp = plane_factory(cache)
        cache.replication = pub = ReplicationPublisher(ring_size=1)
        try:
            _run(cache)
            for i in range(3):
                _churn(cache, i)
                _run(cache)
            pub.barrier()
            # a follower at seq 1 asks for seq 2 — long gone from a
            # 1-deep ring; the leader must synthesize a full from mirrors
            rec = stream.decode_record(pub.record_for(1))
            assert rec.kind == stream.FULL
            assert rec.seq == pub.counters()["head_seq"]
            f, fqp = _make_follower(pub, plane_factory)
            assert f.run_once() == "applied"
            assert _canon(_probe(qp, BODY)) == _canon(_probe(fqp, BODY))
        finally:
            pub.close()

    def test_reconnect_after_leader_outage(self, leader, plane_factory):
        cache, qp, pub = leader
        f, fqp = _make_follower(pub, plane_factory)
        assert f.run_once() == "applied"
        f.transport.down = True
        assert f.run_once() == "error"
        assert f.pull_errors == 1
        # leader kept cycling during the outage
        for i in range(2):
            _churn(cache, i)
            _run(cache)
        pub.barrier()
        f.transport.down = False
        # pull 1: the seq-2 delta is still in the ring → chain intact
        assert f.run_once() == "applied"
        assert f.run_once() == "applied"
        assert f.applier.applied_seq == 3
        assert _canon(_probe(qp, BODY)) == _canon(_probe(fqp, BODY))

    def test_restart_readopts_warm(self, leader, plane_factory):
        cache, qp, pub = leader
        f, fqp = _make_follower(pub, plane_factory)
        assert f.run_once() == "applied"
        app = f.applier
        # a synced applier re-adopts WARM: buffers + resident survive
        mode = app.revalidate_resident()
        assert mode["mode"] == "warm" and mode["resident_version"] > 0
        static_field = next(iter(app._static_dev))
        buf_before = app._static_dev[static_field][1]
        resident_before = app.resident
        # a forced full re-adoption of UNCHANGED state must keep every
        # stamp — same device buffers, no re-upload
        f._force_full = True
        assert f.run_once() == "applied"
        assert app._static_dev[static_field][1] is buf_before
        assert app.resident is resident_before
        assert _canon(_probe(qp, BODY)) == _canon(_probe(fqp, BODY))
        # a fresh applier (no synced state) starts cold
        f2, _ = _make_follower(pub, plane_factory)
        assert f2.applier.revalidate_resident()["mode"] == "cold"


# ==========================================================================
# /v1/whatif/sweep — server-side "how many replicas fit"
# ==========================================================================


class TestSweep:
    def test_sweep_matches_brute_force(self, leader, plane_factory):
        cache, qp, _ = leader
        body = {"queue": "default", "max_count": 16,
                "requests": {"cpu": 4000, "memory": 2 * GiB}}
        resp = _sweep(qp, body)
        # brute force: probe every count as its own all-or-nothing gang
        brute = 0
        for c in range(1, 17):
            r = _probe(qp, {"queue": "default", "count": c,
                            "requests": {"cpu": 4000, "memory": 2 * GiB}})
            if r["feasible"]:
                brute = c
        assert resp["max_fit"] == brute == 6
        assert resp["feasible"]
        assert resp["probes"] < 16, "binary search must beat linear scan"
        assert resp["staleness"]["lag_cycles"] == 0

    def test_sweep_infeasible_and_validation(self, leader, plane_factory):
        cache, qp, _ = leader
        none_fit = _sweep(qp, {"queue": "default", "max_count": 8,
                               "requests": {"cpu": 64000}})
        assert none_fit["max_fit"] == 0 and not none_fit["feasible"]
        with pytest.raises(WhatifError):
            qp.submit_sweep({"queue": "default", "max_count": 0,
                             "requests": {"cpu": 100}})
        with pytest.raises(WhatifError):
            qp.submit_sweep({"queue": "default", "max_count": 65,
                             "requests": {"cpu": 100}})
        with pytest.raises(WhatifError):
            qp.submit_sweep({"queue": "default", "max_count": 4,
                             "requests": {"cpu": 100}, "evictions": True})

"""The evict solves bid on the pending bucket (``ops/eviction.py``,
``pend_rows``): the compact program against the full one, field for field,
in both modes; the dispatch's choice between them and what it says on the
span and the counter; one compile for every pending count under one task
axis; the sentinel on a mis-scattered bucket; the guard's bundle."""

from __future__ import annotations

import os
import types

import jax
import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.actions.allocate import plan_pend_bucket, topk_bucket_for
from kube_batch_tpu.actions.reclaim import solve_claims
from kube_batch_tpu.api.pod import PodGroup, Queue
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.framework.conf import load_scheduler_conf, shipped_conf_path
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.metrics import metrics as m
from kube_batch_tpu.obs.trace import tracer_of
from kube_batch_tpu.ops.eviction import EvictConfig, evict_solve
from kube_batch_tpu.ops.invariants import evict_invariants, evict_sentinel_solve
from kube_batch_tpu.utils import jitstats
from tests.fixtures import GiB, build_cache, build_node, build_pod

MODES = ("reclaim", "preempt")


def gate_cases(mode):
    """EvictConfig keywords: the gates off, then every gate the mode has."""
    return ({}, {"idle_gate": mode == "reclaim", "releasing_gate": True})


def bucket_rows(snap, bucket: int) -> np.ndarray:
    """The pending rows of ``snap`` in a bucket of ``bucket`` slots, as the
    dispatch plans them, whatever rung the task axis would give."""
    rows = np.flatnonzero(np.asarray(snap.task_pending))
    assert rows.size <= bucket
    out = np.full(bucket, -1, np.int32)
    out[: rows.size] = rows
    return out


def assert_same(full, compact, where) -> None:
    for name in full._fields:
        a, b = np.asarray(getattr(full, name)), np.asarray(getattr(compact, name))
        assert a.dtype == b.dtype and a.shape == b.shape, (where, name)
        assert np.array_equal(a, b), (where, name)


# -- clusters -----------------------------------------------------------------


def cluster(nodes: int, pending: int, queues=("qa", "qb"), per_node: int = 4,
            pending_cpu: int = 2000, gang: int = 1, releasing: int = 0,
            idle_nodes: int = 0):
    """``nodes`` nodes of 8 cores, all but ``idle_nodes`` full of one-core
    pairs of queue ``qa``'s Running pods (``per_node`` jobs of two a node),
    and ``pending`` pods of ``pending_cpu`` in gangs of ``gang`` in the
    LAST queue, posted between the Running ones so that their rows are
    scattered over the task axis.  ``releasing`` of the Running pods are
    being deleted."""
    pgs, pods = [], []
    claim_q = queues[-1]
    n_gangs = -(-pending // gang)
    for g in range(n_gangs):
        pgs.append(PodGroup(name=f"claim{g}", namespace="c", min_member=gang,
                            queue=claim_q))
    every = max(1, (nodes * per_node) // max(pending, 1))
    posted = running = 0
    for n in range(nodes - idle_nodes):
        for j in range(per_node):
            name = f"run{n}-{j}"
            pgs.append(PodGroup(name=name, namespace="c", min_member=1,
                                queue=queues[0]))
            for k in range(2):
                pods.append(build_pod(
                    "c", f"{name}-{k}", f"n{n}", PodPhase.RUNNING,
                    {"cpu": 1000, "memory": GiB}, group_name=name,
                    deleting=running < releasing))
                running += 1
            if (n * per_node + j) % every == 0 and posted < pending:
                pods.append(build_pod(
                    "c", f"claim-{posted}", None, PodPhase.PENDING,
                    {"cpu": pending_cpu, "memory": GiB},
                    group_name=f"claim{posted // gang}", priority=10))
                posted += 1
    while posted < pending:
        pods.append(build_pod(
            "c", f"claim-{posted}", None, PodPhase.PENDING,
            {"cpu": pending_cpu, "memory": GiB},
            group_name=f"claim{posted // gang}", priority=10))
        posted += 1
    return build_cache(
        queues=[Queue(name=q, weight=1) for q in queues], pod_groups=pgs,
        nodes=[build_node(f"n{n}", cpu=8000, mem=64 * GiB)
               for n in range(nodes)],
        pods=pods)


def mixed_cluster(seed: int = 36):
    """Twelve nodes full of Running pairs of four queues' jobs, and forty
    claimants in gangs of one to three over the same four queues, with
    priorities, sizes and weights drawn from ``seed``: more claimants than
    nodes, so most bids collide and the virtual rank (queue share, job
    priority, gang need, drf share) picks every winner."""
    rng = np.random.default_rng(seed)
    queues = [Queue(name=f"q{i}", weight=int(w))
              for i, w in enumerate((1, 2, 3, 5))]
    pgs, pods = [], []
    for n in range(12):
        for j in range(4):
            name, q = f"run{n}-{j}", f"q{int(rng.integers(4))}"
            pgs.append(PodGroup(name=name, namespace="c", min_member=1,
                                queue=q))
            for k in range(2):
                pods.append(build_pod(
                    "c", f"{name}-{k}", f"n{n}", PodPhase.RUNNING,
                    {"cpu": 1000, "memory": GiB}, group_name=name,
                    priority=int(rng.integers(3))))
        for g in range(3):                  # claimant gangs between them
            gang = int(rng.integers(1, 4))
            name = f"claim{n}-{g}"
            pgs.append(PodGroup(name=name, namespace="c", min_member=gang,
                                queue=f"q{int(rng.integers(4))}"))
            prio = int(rng.integers(5, 9))
            for k in range(gang):
                if sum(p.phase == PodPhase.PENDING for p in pods) >= 40:
                    break
                pods.append(build_pod(
                    "c", f"{name}-{k}", None, PodPhase.PENDING,
                    {"cpu": int(rng.choice((1000, 2000, 3000))),
                     "memory": GiB}, group_name=name, priority=prio))
    return build_cache(
        queues=queues, pod_groups=pgs, pods=pods,
        nodes=[build_node(f"n{n}", cpu=8000, mem=64 * GiB)
               for n in range(12)])


class Opened:
    """A session over a cache, and its snapshot."""

    def __init__(self, cache):
        self.cache = cache
        self.conf = load_scheduler_conf(shipped_conf_path())
        self.ssn = open_session(cache, self.conf.tiers)
        self.ssn.action_names = list(self.conf.actions)
        cols = self.ssn.columns
        self.snap, self.meta = cols.device_snapshot(self.ssn)

    def close(self):
        close_session(self.ssn)
        self.cache.stop()


@pytest.fixture(scope="module")
def scattered():
    """Twelve two-core claimants of ``qb`` among 256 Running pods of ``qa``
    on 32 full nodes, one node's worth of them being deleted, two nodes
    idle: a task axis too small for the dispatch to compact."""
    o = Opened(cluster(nodes=34, pending=12, releasing=8, idle_nodes=2))
    yield o
    o.close()


@pytest.fixture(scope="module")
def tied():
    """Sixteen equal claimants, equal nodes, equal victims: every score
    ties, so the tie hash of the GLOBAL task row decides every bid."""
    o = Opened(cluster(nodes=8, pending=16, pending_cpu=1000))
    yield o
    o.close()


@pytest.fixture(scope="module")
def mixed():
    o = Opened(mixed_cluster())
    yield o
    o.close()


@pytest.fixture(scope="module")
def one_queue():
    """One queue: reclaim has no cross-queue victim, preempt's solve claims
    for gangs of two and its commit gate holds the incomplete ones back."""
    o = Opened(cluster(nodes=6, pending=9, queues=("qa",), gang=2))
    yield o
    o.close()


@pytest.fixture(scope="module")
def tiers():
    """``tests/test_tiers.py``'s gated snapshot: the rehearsal deployment of
    ``overcommit-50k-5k`` (2,048 task rows, so the dispatch's own bucket of
    512) with claimants whose evictions are in flight, new ones, and idle
    room for two."""
    from tests.test_tiers import _gated_snapshot

    old = os.environ.get("KB_SHARD")
    os.environ["KB_SHARD"] = "0"
    served, ssn, snap, meta = _gated_snapshot()

    o = types.SimpleNamespace(snap=snap, meta=meta, ssn=ssn,
                              cache=served.cache)
    try:
        yield o
    finally:
        close_session(ssn)
        served.close()
        if old is None:
            os.environ.pop("KB_SHARD", None)
        else:
            os.environ["KB_SHARD"] = old


FIXTURES = {
    # name -> bucket slots handed to the program
    "scattered": 16, "tied": 16, "mixed": 64, "one_queue": 64, "tiers": None,
}


@pytest.fixture
def opened(request):
    return request.getfixturevalue(request.param)


def pairs(o, name, mode, gates):
    snap = o.snap
    slots = FIXTURES[name]
    if slots is None:
        rows, pending, bucket = plan_pend_bucket(snap)
        assert rows is not None and bucket == 512 and pending <= 64
    else:
        rows = bucket_rows(snap, slots)
    ec = EvictConfig(mode=mode, **gates)
    full = jax.device_get(evict_solve(snap, ec))
    compact = jax.device_get(evict_solve(snap, ec, rows))
    return rows, full, compact


# -- the same result ----------------------------------------------------------


@pytest.mark.parametrize("gated", (False, True), ids=("plain", "gated"))
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("opened", list(FIXTURES), indirect=True)
def test_the_compact_program_returns_the_full_programs_result(
        opened, request, mode, gated):
    name = request.node.callspec.params["opened"]
    gates = gate_cases(mode)[gated]
    rows, full, compact = pairs(opened, name, mode, gates)
    assert_same(full, compact, (name, mode, gates))
    n = opened.meta.n_tasks
    claims = int((full.claim_node[:n] >= 0).sum())
    # what each fixture is there for
    if name == "scattered":
        live = rows[rows >= 0]
        assert live.size == 12 and np.any(np.diff(live) > 1)
        assert live[0] > 0 and live[-1] < n - 1        # no prefix, no suffix
        if mode == "preempt":
            assert claims == 0                  # no same-queue victim
        elif not gated:
            assert claims == 12
        else:
            # two idle nodes hold eight of the twelve, the node whose pods
            # are going the rest: the gates leave all of them to allocate
            assert claims == 0 and int(full.gated_releasing) > 0
    if name == "tied" and mode == "reclaim" and not gated:
        # sixteen equal bidders over eight equal nodes: one winner a node
        # a round, so the hash spread them and later rounds placed more
        assert claims == 16 and int(full.rounds_run) >= 2
    if name == "mixed" and not gated:
        # bids collide (forty claimants, twelve nodes), so the rank decided
        assert int(full.rounds_run) >= 3 and claims >= 6
    if name == "one_queue":
        if mode == "reclaim":
            assert claims == 0                          # no cross-queue victim
        elif not gated:
            # the ninth claimant's gang of two is incomplete: the commit
            # gate reverts it, on [T], after the bucket's claims landed
            assert claims == 8 and int(full.evicted.sum()) >= 16
    if name == "tiers":
        assert (int(full.gated_releasing) > 0) == gated


def test_a_bucket_that_is_mostly_padding_and_one_that_is_full(scattered):
    snap = scattered.snap
    ec = EvictConfig(mode="reclaim")
    full = jax.device_get(evict_solve(snap, ec))
    for slots in (12, 256):                 # not a slot to spare | 244 empty
        compact = jax.device_get(
            evict_solve(snap, ec, bucket_rows(snap, slots)))
        assert_same(full, compact, slots)


# -- the dispatch -------------------------------------------------------------


def _dispatch_spans(cache, mode):
    return [sp for rec in tracer_of(cache).recorder.records()
            for root in rec.spans for sp in _walk(root)
            if sp.name == "solve_dispatch" and sp.attrs.get("action") == mode
            and sp.attrs.get("program") == "evict"]


def _walk(span):
    yield span
    for child in span.children:
        yield from _walk(child)


#: name -> (nodes, pending, compact, bucket): 1,024 task rows compact into
#: 256 slots; 257 pending rows are one past them; 128 rows have no bucket
DISPATCHES = {
    "fits": (72, 40, True, 256),
    "one_past": (72, 257, False, 256),
    "axis_too_small": (8, 6, False, 0),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", list(DISPATCHES))
def test_the_dispatch_compacts_where_the_pending_set_fits_and_says_so(
        case, mode, monkeypatch):
    nodes, pending, compact, bucket = DISPATCHES[case]
    monkeypatch.setenv("KB_SHARD", "0")
    o = Opened(cluster(nodes=nodes, pending=pending))
    try:
        T = int(o.snap.task_req.shape[0])
        assert (topk_bucket_for(T) or 0) == bucket
        seen = []
        from kube_batch_tpu.ops import invariants

        real = invariants.evict_sentinel_solve

        def spy(dev, config, pend_rows=None):
            seen.append(pend_rows)
            return real(dev, config, pend_rows)

        monkeypatch.setattr(invariants, "evict_sentinel_solve", spy)
        before = dict(m.EVICT_SOLVE_COMPACTED._values)
        dispatches = dict(m.SOLVE_DISPATCHES._values)
        claims, _ = solve_claims(o.ssn, mode)
        tracer_of(o.cache).end_cycle()      # the implicit record, finalized
        (rows,) = seen
        assert (rows is not None) == compact
        if compact:
            assert rows.shape == (bucket,) and rows.dtype == np.int32
            assert np.array_equal(
                rows[:pending], np.flatnonzero(np.asarray(o.snap.task_pending)))
            assert (rows[pending:] == -1).all()
        (sp,) = _dispatch_spans(o.cache, mode)
        assert sp.attrs["compact"] is compact
        assert sp.attrs["claimants"] == pending
        assert sp.attrs["bucket"] == bucket
        assert sp.attrs["mode"] == "single"
        key = (mode, "true" if compact else "false")
        other = (mode, "false" if compact else "true")
        now = m.EVICT_SOLVE_COMPACTED._values
        assert now[key] - before.get(key, 0.0) == 1
        assert now[other] == before.get(other, 0.0)
        # the dispatch counter keeps its label values
        assert (m.SOLVE_DISPATCHES._values[(mode, "single", "evict")]
                - dispatches.get((mode, "single", "evict"), 0.0)) == 1
        if mode == "reclaim":
            assert len(claims) > 0
    finally:
        o.close()


def test_the_bucket_rule_is_allocates_own():
    """One planner: allocate's top-K dispatch and the evict dispatch read
    the same rows from the same function."""
    from kube_batch_tpu.actions.allocate import plan_topk_bucket

    o = Opened(cluster(nodes=72, pending=40))
    try:
        rows, pending, bucket = plan_pend_bucket(o.snap)
        topk_rows, k = plan_topk_bucket(o.snap, None, 8)
        assert k == 8 and np.array_equal(rows, topk_rows)
        assert (pending, bucket) == (40, 256)
    finally:
        o.close()


# -- one compile --------------------------------------------------------------


def test_three_pending_counts_under_one_task_axis_compile_once():
    ec = EvictConfig(mode="reclaim", idle_gate=True, releasing_gate=True,
                     rounds=7)              # a key no other test compiles
    opened = [Opened(cluster(nodes=72, pending=p)) for p in (3, 40, 200)]
    try:
        assert len({int(o.snap.task_req.shape[0]) for o in opened}) == 1
        before = jitstats.compile_counts()["evict_sentinel_solve"]
        claims = []
        for o in opened:
            rows, _, bucket = plan_pend_bucket(o.snap)
            assert bucket == 256
            res, verdict, _, _ = evict_sentinel_solve(o.snap, ec, rows)
            assert int(verdict) == 0
            claims.append(int((np.asarray(res.claim_node) >= 0).sum()))
        after = jitstats.compile_counts()["evict_sentinel_solve"]
        assert after - before == 1
        assert claims[0] < claims[1] < claims[2]
    finally:
        for o in opened:
            o.close()


# -- the guard ----------------------------------------------------------------


def test_the_sentinel_trips_on_a_mis_scattered_bucket(scattered):
    """The invariants read the [T] result the bucket's claims were scattered
    to: claims that land one row beside their claimants are claims of rows
    that are not pending, whose victims cover nothing."""
    snap = scattered.snap
    ec = EvictConfig(mode="reclaim")
    res, verdict, hist, _ = evict_sentinel_solve(
        snap, ec, bucket_rows(snap, 16))
    assert int(verdict) == 0 and int((np.asarray(res.claim_node) >= 0).sum())
    planted = res._replace(claim_node=np.roll(np.asarray(res.claim_node), 1))
    verdict, hist = jax.jit(evict_invariants, static_argnames=("config",))(
        snap, planted, config=ec)
    hist = np.asarray(hist)
    assert int(verdict) > 0
    assert hist[0] > 0 and hist[5] > 0      # ineligible claimant; coverage


def test_the_bundle_carries_the_bucket_and_replays_the_compact_program(
        scattered, tmp_path):
    from kube_batch_tpu.guard.bundle import (
        dump_bundle,
        load_bundle,
        replay_bundle,
    )

    snap = scattered.snap
    ec = EvictConfig(mode="reclaim", releasing_gate=True)
    rows = bucket_rows(snap, 16)
    full = jax.device_get(evict_solve(snap, ec))
    sizes = {}
    for name, pend_rows in (("compact", rows), ("full", None)):
        before = evict_sentinel_solve._cache_size()
        path = dump_bundle("reclaim", snap, ec, {"verdict": 0},
                           pend_rows=pend_rows, directory=str(tmp_path))
        _, meta, loaded = load_bundle(path)
        assert meta["has_pend_rows"] == (pend_rows is not None)
        assert (loaded is None) == (pend_rows is None)
        out = replay_bundle(path)
        assert out["fast_verdict"] == 0 and not out["reproduced"]
        assert out["claims"] == int((full.claim_node >= 0).sum()) > 0
        assert out["victims"] == int(full.evicted.sum())
        sizes[name] = evict_sentinel_solve._cache_size() - before
    assert np.array_equal(loaded if loaded is not None else rows, rows)
    # each replayed its own program: the bucket's, then the full one
    assert sizes == {"compact": 1, "full": 1}

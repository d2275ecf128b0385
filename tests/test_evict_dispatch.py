"""The evict dispatch is plan → program → call (actions/reclaim.py
``solve_claims``, which preempt rides): every leaf of {one device, the test
backend's 8-device mesh} x {reclaim, preempt} x {guard attached, none} x
{the pending set fits its bucket, does not}, plus the ``shard_map`` demotion
and an audit that falls due, each held to what ``plan_evict_dispatch``
chose, which memoized program the lookup (``parallel.mesh.program``) handed
out, and the claims of the bare single-device program on the same snapshot.

The expected table is written out below: it is the contract, not a
derivation from the code under test."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from kube_batch_tpu.actions import reclaim as reclaim_mod
from kube_batch_tpu.guard.plane import DEMOTED, GuardPlane
from kube_batch_tpu.ops import eviction, invariants
from kube_batch_tpu.parallel import mesh as mesh_mod
from kube_batch_tpu.utils import jitstats
from tests.test_evict_compact import Opened, _dispatch_spans, cluster

#: node capacity that shards the solve over the backend's devices
#: (mesh.SHARD_MIN_NODES); the clusters' own 72 nodes pad to 128, one device
MESH_N = 256
#: pending pods that fit / are one past the 256 slots that the clusters'
#: 1,024 task rows compact into
FITS, ONE_PAST = 40, 257

# id: (devices, mode, guard attached, pending, what the guard is told, want)
# want: what plan_evict_dispatch chose — impl, compact (a pending bucket is
# passed), engaged, audit — and every program looked up, in order, as
# (impl asked for, sentinel, the program): on one device the ops/ function,
# on the mesh the name it registers under
LEAVES = {}
for _mode in ("reclaim", "preempt"):
    LEAVES.update({
        f"single-{_mode}-bare-fits": ("single", _mode, False, FITS, None, dict(
            impl=None, compact=True, engaged=(), audit=False,
            lookups=[(None, False, "evict_solve")])),
        f"single-{_mode}-bare-past": (
            "single", _mode, False, ONE_PAST, None, dict(
                impl=None, compact=False, engaged=(), audit=False,
                lookups=[(None, False, "evict_solve")])),
        f"single-{_mode}-guard-fits": ("single", _mode, True, FITS, None, dict(
            impl=None, compact=True, engaged=(), audit=False,
            lookups=[(None, True, "evict_sentinel_solve")])),
        f"single-{_mode}-guard-past": (
            "single", _mode, True, ONE_PAST, None, dict(
                impl=None, compact=False, engaged=(), audit=False,
                lookups=[(None, True, "evict_sentinel_solve")])),
        # the sharded bodies bid on the task axis: no bucket, fit or not
        f"mesh8-{_mode}-bare-fits": ("mesh8", _mode, False, FITS, None, dict(
            impl=None, compact=False, engaged=("shard_map",), audit=False,
            lookups=[(None, False,
                      f"sharded_evict_solve[{_mode},shard_map]")])),
        f"mesh8-{_mode}-bare-past": (
            "mesh8", _mode, False, ONE_PAST, None, dict(
                impl=None, compact=False, engaged=("shard_map",), audit=False,
                lookups=[(None, False,
                          f"sharded_evict_solve[{_mode},shard_map]")])),
        f"mesh8-{_mode}-guard-fits": ("mesh8", _mode, True, FITS, None, dict(
            impl=None, compact=False, engaged=("shard_map",), audit=False,
            lookups=[(None, True,
                      f"sentinel_sharded_evict_solve[{_mode},shard_map]")])),
        f"mesh8-{_mode}-guard-past": (
            "mesh8", _mode, True, ONE_PAST, None, dict(
                impl=None, compact=False, engaged=("shard_map",), audit=False,
                lookups=[(None, True,
                          f"sentinel_sharded_evict_solve[{_mode},shard_map]"
                          )])),
    })
LEAVES.update({
    # shard_map demoted → the pjit oracle, which engages no fast path: the
    # audit that falls due has nothing to audit
    "demoted-shard_map": ("mesh8", "reclaim", True, FITS, "demote", dict(
        impl="pjit", compact=False, engaged=(), audit=False,
        lookups=[("pjit", True,
                  "sentinel_sharded_evict_solve[reclaim,pjit]")])),
    # the audit falls due: the bare pjit oracle behind the solve
    "audit-due": ("mesh8", "reclaim", True, FITS, "audit", dict(
        impl=None, compact=False, engaged=("shard_map",), audit=True,
        lookups=[(None, True,
                  "sentinel_sharded_evict_solve[reclaim,shard_map]"),
                 ("pjit", False, "sharded_evict_solve[reclaim,pjit]")])),
    # one device has no oracle to audit against, whatever the cadence
    "audit-due-single": ("single", "preempt", True, FITS, "audit", dict(
        impl=None, compact=True, engaged=(), audit=False,
        lookups=[(None, True, "evict_sentinel_solve")])),
})


def _is_program(fn, name: str) -> bool:
    """``fn`` is the memoized program ``name``: the ops/ function itself, or
    one of the mesh programs utils/jitstats tracks under that name (one per
    mesh and config the process has dispatched)."""
    single = {"evict_solve": eviction.evict_solve,
              "evict_sentinel_solve": invariants.evict_sentinel_solve}
    if name in single:
        return fn is single[name]
    return any(fn is f for n, f in jitstats._TRACKED if n == name)


class _Spy:
    """Records every plan and program the dispatch asks for."""

    def __init__(self, monkeypatch):
        self.plans, self.programs = [], []
        plan, program = reclaim_mod.plan_evict_dispatch, mesh_mod.program

        def spy_plan(*a, **kw):
            self.plans.append(plan(*a, **kw))
            return self.plans[-1]

        def spy_program(*a, **kw):
            self.programs.append((a, kw, program(*a, **kw)))
            return self.programs[-1][2]

        monkeypatch.setattr(reclaim_mod, "plan_evict_dispatch", spy_plan)
        monkeypatch.setattr(mesh_mod, "program", spy_program)


def _decode(result, meta, snap):
    """``solve_claims``' list, from an EvictResult on the host."""
    n = meta.n_tasks
    claim_node, evicted, victim_claimant = (
        np.asarray(a)[:n] for a in (
            result.claim_node, result.evicted, result.victim_claimant))
    task_job = np.asarray(snap.task_job)[:n]

    def ref(ti):
        return (meta.job_uids[int(task_job[ti])], meta.task_keys[int(ti)])

    return [
        (ref(ti), meta.node_names[int(claim_node[ti])],
         [ref(vi) for vi in np.flatnonzero(evicted & (victim_claimant == ti))])
        for ti in np.flatnonzero(claim_node >= 0)
    ]


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_dispatch_leaf(leaf, monkeypatch):
    devices, mode, guarded, pending, told, want = LEAVES[leaf]
    assert len(jax.devices()) == 8  # the mesh leaves need the test backend's
    for knob in ("KB_SHARD", "KB_SHARD_MAP", "KB_TASK_SHARDS"):
        monkeypatch.delenv(knob, raising=False)
    # preempt takes its victims from the claimants' own queue
    cache = cluster(nodes=72, pending=pending,
                    queues=("qa", "qb") if mode == "reclaim" else ("qa",))
    if devices == "mesh8":
        cache.columns.reserve(n_nodes=MESH_N)
    guard = GuardPlane(enabled=guarded,
                       audit_every=1 if told == "audit" else 10_000)
    if told == "demote":
        guard.audit_every = 1
        guard.paths["shard_map"].state = DEMOTED
    cache.guard_plane = guard
    o = Opened(cache)
    try:
        assert int(o.snap.task_req.shape[0]) == 1024
        spy = _Spy(monkeypatch)
        claims, meta = reclaim_mod.solve_claims(o.ssn, mode)

        # ---- what the plan chose ---------------------------------------
        (plan,) = spy.plans
        mesh = plan.mesh
        assert (mesh is None) == (devices == "single")
        if mesh is not None:
            assert dict(mesh.shape) == {mesh_mod.NODE_AXIS: 8}
        assert plan.impl == want["impl"]
        assert (plan.pend_rows is not None) is want["compact"]
        assert (plan.claimants, plan.bucket) == (pending, 256)
        assert plan.sentinel is guarded
        assert plan.engaged == want["engaged"]
        assert plan.audit is want["audit"]
        if want["compact"]:
            assert plan.pend_rows.shape == (256,)
            assert (plan.pend_rows >= 0).sum() == pending

        # ---- every program is the very object that is memoized ----------
        assert len(spy.programs) == len(want["lookups"])
        config = spy.programs[0][0][3]
        assert config.mode == mode
        for (args, statics, fn), (impl, sentinel, name) in zip(
                spy.programs, want["lookups"]):
            assert args[:4] == ("evict", mesh, impl, config)
            assert bool(args[4] if len(args) > 4 else False) is sentinel
            assert statics == {}
            assert _is_program(fn, name), name
            assert fn is mesh_mod.program(
                "evict", mesh, impl, config, sentinel)

        # ---- what it told the span and the guard --------------------------
        from kube_batch_tpu.obs.trace import tracer_of

        tracer_of(o.cache).end_cycle()
        (sp,) = _dispatch_spans(o.cache, mode)
        assert sp.attrs["mode"] == ("single" if mesh is None else "sharded")
        assert sp.attrs["compact"] is want["compact"]
        assert guard.audits_run == (1 if want["audit"] else 0)
        assert guard.audits_mismatched == 0 and guard.trips_total == 0

        # ---- and it claims what the bare one-device program claims -------
        bare = jax.device_get(eviction.evict_solve(o.snap, config))
        assert claims == _decode(bare, meta, o.snap)
        assert len(claims) > 0
    finally:
        o.close()

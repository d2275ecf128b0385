"""The allocate dispatch is plan → program → call: every leaf of {single
device, the test backend's 8-device mesh} x {full, topk, warm} x {guard
attached, none} and the three guard demotions, each held to what
``plan_allocate_dispatch`` chose, which memoized program the lookup
(``parallel.mesh.program``) handed out, the shape of the dispatch's return, and
the all-oracle program's placements on the same snapshot.

The expected table is written out below: it is the contract, not a
derivation from the code under test."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.actions import allocate as alloc_mod
from kube_batch_tpu.api.pod import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    Queue,
)
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.framework.conf import load_scheduler_conf, shipped_conf_path
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.guard.plane import DEMOTED, GuardPlane
from kube_batch_tpu.ops import assignment, invariants
from kube_batch_tpu.parallel import mesh as mesh_mod
from kube_batch_tpu.testing.synthetic import GiB
from kube_batch_tpu.utils import jitstats

CONF = load_scheduler_conf(shipped_conf_path())

#: node capacity that keeps the solve on one device (below
#: mesh.SHARD_MIN_NODES) / that shards it over the backend's devices
SINGLE_N, MESH_N = 64, 256

GINFO_KEYS = {"engaged", "sentinel", "pend_rows", "impl", "dev", "config"}


def _single(name):
    """The memoized single-device program ``name``: a jitted function of
    ops/assignment.py or ops/invariants.py, or what its ``*_fn`` getter
    returns."""
    obj = getattr(assignment, name, None) or getattr(invariants, name)
    return obj() if name.endswith("_fn") else obj


def _registered(name):
    """The mesh programs that utils/jitstats tracks under ``name`` (one per
    mesh and config the process has dispatched)."""
    return [f for n, f in jitstats._TRACKED if n == name]


# id: (devices, kind, guard attached, demoted path | None, expected)
# expected: what plan_allocate_dispatch chose — kind, impl, engaged,
# demoted, wstate is None — then the dispatch's ginfo["engaged"], the keys
# of topk_info (None = no compaction ran), and the program the leaf runs:
# on one device the ops/ function (or what its ``*_fn`` getter returns), on
# the mesh the name it registers under
LEAVES = {
    "single-full-bare": ("single", "full", False, None, dict(
        kind="full", impl=None, engaged=(), demoted=False, cold=True,
        ran=[], info=None, getter="allocate_solve")),
    "single-full-guard": ("single", "full", True, None, dict(
        kind="full", impl=None, engaged=(), demoted=False, cold=True,
        ran=[], info=None, getter="allocate_sentinel_solve")),
    "single-topk-bare": ("single", "topk", False, None, dict(
        kind="topk", impl=None, engaged=("topk",), demoted=False, cold=True,
        ran=["topk"], info={"k", "bucket"}, getter="allocate_topk_solve")),
    "single-topk-guard": ("single", "topk", True, None, dict(
        kind="topk", impl=None, engaged=("topk",), demoted=False, cold=True,
        ran=["topk"], info={"k", "bucket"},
        getter="allocate_topk_sentinel_solve")),
    "single-warm-bare": ("single", "warm", False, None, dict(
        kind="topk", impl=None, engaged=("topk",), demoted=False, cold=False,
        ran=["topk", "warm"], info={"k", "bucket", "warm"},
        getter="warm_solve_fn")),
    "single-warm-guard": ("single", "warm", True, None, dict(
        kind="topk", impl=None, engaged=("topk",), demoted=False, cold=False,
        ran=["topk", "warm"], info={"k", "bucket", "warm"},
        getter="warm_sentinel_solve_fn")),
    "mesh8-full-bare": ("mesh8", "full", False, None, dict(
        kind="full", impl=None, engaged=("shard_map",), demoted=False,
        cold=True, ran=["shard_map"], info=None,
        getter="sharded_allocate_solve[shard_map]")),
    "mesh8-full-guard": ("mesh8", "full", True, None, dict(
        kind="full", impl=None, engaged=("shard_map",), demoted=False,
        cold=True, ran=["shard_map"], info=None,
        getter="sentinel_sharded_allocate_solve[shard_map]")),
    "mesh8-topk-bare": ("mesh8", "topk", False, None, dict(
        kind="topk", impl=None, engaged=("shard_map", "topk"),
        demoted=False, cold=True, ran=["shard_map", "topk"],
        info={"k", "bucket"},
        getter="sharded_allocate_topk_solve[shard_map]")),
    "mesh8-topk-guard": ("mesh8", "topk", True, None, dict(
        kind="topk", impl=None, engaged=("shard_map", "topk"),
        demoted=False, cold=True, ran=["shard_map", "topk"],
        info={"k", "bucket"},
        getter="sentinel_sharded_allocate_topk_solve[shard_map]")),
    "mesh8-warm-bare": ("mesh8", "warm", False, None, dict(
        kind="topk", impl=None, engaged=("shard_map", "topk"),
        demoted=False, cold=False, ran=["shard_map", "topk", "warm"],
        info={"k", "bucket", "warm"},
        getter="sharded_warm_allocate_solve[shard_map]")),
    "mesh8-warm-guard": ("mesh8", "warm", True, None, dict(
        kind="topk", impl=None, engaged=("shard_map", "topk"),
        demoted=False, cold=False, ran=["shard_map", "topk", "warm"],
        info={"k", "bucket", "warm"},
        getter="sentinel_sharded_warm_allocate_solve[shard_map]")),
    # the demotions: topk → the full matrix, shard_map → the pjit oracle,
    # warm → the cold per-solve table build
    "demoted-topk": ("single", "topk", True, "topk", dict(
        kind="full", impl=None, engaged=(), demoted=True, cold=True,
        ran=[], info=None, getter="allocate_sentinel_solve")),
    "demoted-shard_map": ("mesh8", "topk", True, "shard_map", dict(
        kind="topk", impl="pjit", engaged=("topk",), demoted=True,
        cold=True, ran=["topk"], info={"k", "bucket"},
        getter="sentinel_sharded_allocate_topk_solve[pjit]")),
    "demoted-warm": ("single", "warm", True, "warm", dict(
        kind="topk", impl=None, engaged=("topk",), demoted=False, cold=True,
        ran=["topk"], info={"k", "bucket"},
        getter="allocate_topk_sentinel_solve")),
}


def _mk_cache(n_nodes_cap):
    cache = SchedulerCache()
    # capT 1024 gives the compaction plan its 256-row bucket; K=32 stays
    # under either node capacity, so KB_TOPK engages wherever it is on
    cache.columns.reserve(n_tasks=1024, n_nodes=n_nodes_cap)
    cache.add_queue(Queue(name="q0", uid="uq0", weight=1))
    for i in range(4):
        cache.add_node(Node(
            name=f"n{i}",
            allocatable={"cpu": 8000.0, "memory": 64 * GiB, "pods": 110.0},
        ))
    return cache


def _add_gang(cache, serial, size=2):
    g = f"g{serial}"
    cache.add_pod_group(PodGroup(
        name=g, namespace="t", uid=f"pg-{g}", min_member=size, queue="q0",
        creation_index=serial,
    ))
    for k in range(size):
        cache.add_pod(Pod(
            name=f"{g}-{k}", namespace="t", uid=f"pod-{g}-{k}",
            requests={"cpu": 500.0, "memory": 1 * GiB},
            annotations={GROUP_NAME_ANNOTATION: g},
            phase=PodPhase.PENDING, creation_index=serial * 100 + k,
        ))


class _Spy:
    """Records every plan and program the dispatch asks for."""

    def __init__(self, monkeypatch):
        self.plans, self.programs = [], []
        plan, program = alloc_mod.plan_allocate_dispatch, mesh_mod.program

        def spy_plan(*a, **kw):
            self.plans.append(plan(*a, **kw))
            return self.plans[-1]

        def spy_program(*a, **kw):
            self.programs.append((a, kw, program(*a, **kw)))
            return self.programs[-1][2]

        monkeypatch.setattr(alloc_mod, "plan_allocate_dispatch", spy_plan)
        monkeypatch.setattr(mesh_mod, "program", spy_program)


def _dispatch(cache, guard, warm):
    """One session's allocate-shaped dispatch and its all-oracle twin; the
    result is compared, never applied, so the gangs stay pending."""
    ssn = open_session(cache, CONF.tiers)
    try:
        snap, meta = alloc_mod.build_session_snapshot(ssn)
        config = alloc_mod.session_allocate_config(ssn)
        result, mode, info, ginfo = alloc_mod.dispatch_allocate_solve(
            snap, config, cols=ssn.columns, guard=guard, warm=warm)
        oracle = alloc_mod.dispatch_allocate_oracle(
            snap, config, ssn.columns, mode)
        assigned, want = jax.device_get((result.assigned, oracle.assigned))
        return (np.asarray(assigned)[: meta.n_tasks],
                np.asarray(want)[: meta.n_tasks], mode, info, ginfo, config)
    finally:
        close_session(ssn)


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_dispatch_leaf(leaf, monkeypatch):
    devices, kind, guarded, demote, want = LEAVES[leaf]
    assert len(jax.devices()) == 8  # the mesh leaves need the test backend's
    # KB_TOPK=0 is the suite's selector of the full-matrix program
    monkeypatch.setenv("KB_TOPK", "0" if kind == "full" else "32")
    for knob in ("KB_WARM", "KB_SHARD", "KB_SHARD_MAP", "KB_TASK_SHARDS"):
        monkeypatch.delenv(knob, raising=False)
    cache = _mk_cache(SINGLE_N if devices == "single" else MESH_N)
    guard = GuardPlane(enabled=True) if guarded else None
    warm = kind == "warm"
    _add_gang(cache, 0)
    if warm:
        # the first cycle builds the table this leaf's dispatch carries
        _dispatch(cache, guard, warm=True)
        _add_gang(cache, 1)
    if demote is not None:
        guard.paths[demote].state = DEMOTED
    spy = _Spy(monkeypatch)
    assigned, oracle, mode, info, ginfo, config = _dispatch(
        cache, guard, warm)

    # ---- what the plan chose -------------------------------------------
    (plan,) = spy.plans
    mesh = plan.mesh
    assert (mesh is None) == (devices == "single")
    if mesh is not None:
        assert dict(mesh.shape) == {mesh_mod.NODE_AXIS: 8}
    assert plan.kind == want["kind"]
    assert plan.impl == want["impl"]
    assert plan.engaged == want["engaged"]
    assert plan.demoted is want["demoted"]
    assert plan.sentinel is guarded
    assert (plan.wstate is None) == (not warm or demote == "warm")
    assert (plan.pend_rows is None) == (want["kind"] == "full")
    assert plan.k == (0 if plan.pend_rows is None else 32)

    # ---- what the dispatch returned --------------------------------------
    assert mode == ("single" if devices == "single" else "sharded")
    assert set(ginfo) == GINFO_KEYS
    assert ginfo["engaged"] == want["ran"]
    assert ginfo["impl"] == want["impl"]
    assert (ginfo["sentinel"] is None) == (not guarded)
    if guarded:
        verdict, hist, _checksum = ginfo["sentinel"]
        assert int(verdict) == 0 and not np.asarray(hist).any()
    if want["info"] is None:
        assert info is None
        assert ginfo["config"] == config
    else:
        assert set(info) == want["info"]
        assert (info["k"], info["bucket"]) == (32, 256)
        assert ginfo["config"].topk >= 32
    if "warm" in (want["info"] or ()):
        assert info["warm"]["cold"] is want["cold"]  # a CARRIED table

    # ---- the program is the very object that is memoized ----------------
    # (a demotion to the full matrix looks the bare program up first, for
    # its fit check; the all-oracle twin is looked up last)
    (args, statics_asked, program), oracle_lookup = spy.programs[-2:]
    assert len(spy.programs) == (3 if demote == "topk" else 2)
    assert oracle_lookup[0] == (
        "full", mesh, "pjit", config._replace(topk=0))
    run_kind = "warm" if "warm" in want["ran"] else want["kind"]
    statics = ({"k_min": alloc_mod.warm_k_min(32)} if run_kind == "warm"
               else {})
    if devices == "single":
        assert program is _single(want["getter"])
    else:
        assert any(program is fn for fn in _registered(want["getter"]))
        assert program is mesh_mod.program(
            run_kind, mesh, want["impl"], ginfo["config"], guarded, **statics)
    assert args == (run_kind, mesh, want["impl"], ginfo["config"], guarded)
    assert statics_asked == statics

    # ---- and it places what the all-oracle program places ---------------
    np.testing.assert_array_equal(assigned, oracle)
    assert (assigned >= 0).sum() >= 2  # the first gang, at the least

"""An evict solve whose gates leave no claimant ends at the gates
(``ops/eviction.py::evict_rounds``: everything below ``claimant_base`` is one
branch of a ``lax.cond`` on :func:`~kube_batch_tpu.ops.eviction.any_claimant`).

Each program (reclaim and preempt, on the pending bucket and on the task
axis, with the guard's sentinel and without) against THE SAME program with
the branch forced taken, which is the flow every solve had before: all
claimants gated by idle room; all gated by releasing room alone; one
claimant that survives the gates; no pending row at all.  Beside
``tests/test_evict_compact.py``, whose clusters these are; the compiled
program's shape is ``tests/test_tpu_compile.py``'s."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kube_batch_tpu.ops import eviction
from kube_batch_tpu.ops.eviction import EvictConfig, evict_solve
from kube_batch_tpu.ops.invariants import evict_sentinel_solve
from tests.test_evict_compact import (
    MODES,
    Opened,
    assert_same,
    bucket_rows,
    cluster,
)

#: name -> (cluster keywords, gate keywords): 34 nodes of 8 cores full of
#: ``qa``'s one-core Running pods, and ``qb``'s two-core claimants
CASES = {
    # two idle nodes hold eight such claimants: reclaim's idle gate leaves
    # all six to allocate (preempt has no idle gate: they bid)
    "idle_room": (dict(nodes=34, pending=6, idle_nodes=2),
                  dict(idle_gate=True)),
    # one node's eight pods are being deleted: room for four, in both modes
    "releasing_room": (dict(nodes=34, pending=4, releasing=8),
                       dict(releasing_gate=True)),
    # the fifth claimant has no room on the way: it bids
    "one_survivor": (dict(nodes=34, pending=5, releasing=8),
                     dict(releasing_gate=True)),
    "nothing_pending": (dict(nodes=34, pending=0),
                        dict(releasing_gate=True)),
}
#: the cases whose gates leave somebody to bid, by mode
BIDS = {("idle_room", "preempt"), ("one_survivor", "reclaim"),
        ("one_survivor", "preempt")}
SLOTS = 16


class Forced:
    """``program`` compiled with the branch always taken: the parent's
    flow, in which an empty solve runs its setup and one round."""

    def __init__(self, program):
        # a function of its own: jit caches a trace by the function traced
        def taken(snap, config, pend_rows=None):
            return program.__wrapped__(snap, config, pend_rows)

        self.jitted = jax.jit(taken, static_argnames=("config",))

    def __call__(self, *args):
        with pytest.MonkeyPatch.context() as mp:
            # read where evict_rounds is traced, and only there
            mp.setattr(eviction, "any_claimant",
                       lambda claimant_base: jnp.bool_(True))
            return self.jitted(*args)


PROGRAMS = {
    "plain": (evict_solve, Forced(evict_solve)),
    "sentinel": (evict_sentinel_solve, Forced(evict_sentinel_solve)),
}


@pytest.fixture(scope="module")
def snapshots():
    opened = {name: Opened(cluster(**kw)) for name, (kw, _) in CASES.items()}
    yield {name: o.snap for name, o in opened.items()}
    for o in opened.values():
        o.close()


@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("shape", ("compact", "full"))
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", list(CASES))
def test_a_solve_that_ends_at_its_gates_returns_what_the_rounds_would_have(
        snapshots, case, mode, shape, program):
    snap = snapshots[case]
    ec = EvictConfig(mode=mode, **CASES[case][1])
    args = (snap, ec) + (
        (bucket_rows(snap, SLOTS),) if shape == "compact" else ())
    ends, taken = PROGRAMS[program]
    got, was = jax.device_get(ends(*args)), jax.device_get(taken(*args))
    if program == "sentinel":
        # the invariants are checked on the empty result as on any other
        (got, verdict, hist, _), (was, was_verdict, was_hist, _) = got, was
        assert int(verdict) == int(was_verdict) == 0
        assert np.array_equal(hist, was_hist)
    where = (case, mode, shape, program)
    if (case, mode) in BIDS:
        # somebody bids: the parent's program to the last field
        assert_same(was, got, where)
        assert int(got.rounds_run) >= 1
        if (case, mode) == ("one_survivor", "reclaim"):
            assert int((got.claim_node >= 0).sum()) == 1
            assert int(got.evicted.sum()) == 2
        return
    # nobody bids: no round ran, where the parent ran one to find that out
    assert int(got.rounds_run) == 0 and int(was.rounds_run) == 1
    assert_same(was._replace(rounds_run=got.rounds_run), got, where)
    assert (got.claim_node == -1).all() and not got.evicted.any()
    assert (got.victim_claimant == -1).all()
    pending = int(np.asarray(snap.task_pending).sum())
    gated_by_releasing = pending if case == "releasing_room" else 0
    assert int(got.gated_releasing) == gated_by_releasing


def test_the_loop_reads_no_round_for_a_solve_that_ended_at_its_gates(
        monkeypatch):
    """Through the action: ``rounds`` on the evict ``device_wait`` span and
    ``volcano_solve_rounds_total{action}`` carry the 0."""
    from kube_batch_tpu.actions.reclaim import solve_claims
    from kube_batch_tpu.metrics import metrics as m
    from kube_batch_tpu.obs.trace import tracer_of
    from tests.test_evict_compact import _walk

    # 1,024 task rows: the dispatch's own bucket of 256; the six idle nodes
    # hold every claimant
    monkeypatch.setenv("KB_SHARD", "0")
    o = Opened(cluster(nodes=72, pending=20, idle_nodes=6))
    try:
        before = m.SOLVE_ROUNDS._values.get(("reclaim",), 0.0)
        claims, _ = solve_claims(o.ssn, "reclaim")
        tracer_of(o.cache).end_cycle()
        assert claims == []
        (wait,) = [sp for rec in tracer_of(o.cache).recorder.records()
                   for root in rec.spans for sp in _walk(root)
                   if sp.name == "device_wait"]
        assert wait.attrs["rounds"] == 0 and wait.attrs["claims"] == 0
        assert m.SOLVE_ROUNDS._values.get(("reclaim",), 0.0) == before
    finally:
        o.close()

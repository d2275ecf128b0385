"""The envelope deployment (``benchmark/configs/k8s-envelope-150k-5k.json``:
Kubernetes' published limit of 150,000 pods on 5,000 nodes, node axis
sharded over the four chips of a v5e-4 host) at a size the suite holds: the
same node size, 30 pods a node and gangs of 4 on 160 nodes, which pad to
the 256 at which ``parallel.mesh.should_shard`` turns the mesh on.

The served path (cache + ``Scheduler`` + the shipped five actions) is
churned for a few bursts and its binds are checked by the benchmark's plain
reference (``benchmark/reference.py``'s ``Ledger.check_binds``: numpy int64,
imports nothing of the program) to all-zero counts: on the suite's 8
virtual devices, on a 4-device mesh, and with ``KB_SHARD=0`` (a placement
has many right answers: the counts are compared, never the rows).  Under
the mesh every solve is dispatched ``sharded``, the guard's audit oracle
goes through the mesh and matches the fast path, and a demotion whose
target is over the device's budget fails closed."""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api import serialize
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.cache.fake import FakeBinder, FakeEvictor, FakeStatusUpdater
from kube_batch_tpu.cmd.server import _bindings
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.guard import GuardPlane
from kube_batch_tpu.metrics.metrics import GUARD_TRIPS, SOLVE_DISPATCHES
from kube_batch_tpu.obs.trace import solve_program, tracer_of
from kube_batch_tpu.parallel import mesh as mesh_mod
from kube_batch_tpu.scheduler import Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("bench_reference",
                  os.path.join(REPO, "benchmark", "reference.py"))
with open(os.path.join(REPO, "benchmark", "configs",
                       "rehearsal-envelope-4800-160.json")) as f:
    CONFIG = json.load(f)
BURST_GANGS = 5


class Served:
    """The cluster of ``CONFIG`` behind the served path, and the ledger of
    what was sent to it."""

    def __init__(self, seed: int, audit_every: int = 0, ledger=None):
        self.ledger = ledger or reference.Ledger(CONFIG, seed)
        self.cache = cache = SchedulerCache(
            binder=FakeBinder(), evictor=FakeEvictor(),
            status_updater=FakeStatusUpdater())
        self.guard = cache.guard_plane = GuardPlane(audit_every=audit_every)
        self.guard.host_cache = cache
        for q in self.ledger.queue_dicts():
            cache.add_queue(serialize.queue_from_dict(q))
        for n in self.ledger.node_dicts():
            cache.add_node(serialize.node_from_dict(n))
        self.post(*self.ledger.make_population())
        self.sched = Scheduler(cache, conf=load_scheduler_conf(os.path.join(
            REPO, "config", "kube-batch-tpu-conf.yaml")))
        self.before = dict(SOLVE_DISPATCHES._values)

    def post(self, pgs, pods) -> None:
        for pg in pgs:
            self.cache.add_pod_group(serialize.pod_group_from_dict(pg))
        for pod in pods:
            self.cache.update_pod(serialize.pod_from_dict(pod))
        self.ledger.add(pgs, pods)

    def delete(self, pgs, pods) -> None:
        for pod in pods:
            self.cache.delete_pod(serialize.pod_from_dict(pod))
        for pg in pgs:
            self.cache.delete_pod_group(
                serialize.pod_group_from_dict(pg).key())
        self.ledger.retire(pgs, pods)

    def burst(self) -> None:
        """Delete the oldest gangs, post as many new ones."""
        self.delete(*self.ledger.oldest_gangs(BURST_GANGS))
        gang, mix = CONFIG["gang"], CONFIG["request_mix"]
        self.post(*self.ledger.make_gangs(
            BURST_GANGS, gang["size"], gang["min_member"],
            mix["cpu_milli"], mix["memory_bytes"]))

    def cycles(self, most: int = 8) -> dict:
        """Cycles until every live pod is bound; the reference's counts."""
        for _ in range(most):
            self.sched.run_once_pipelined()
            self.sched.drain_pipeline()
            numbers = self.counts()
            if not numbers["unbound"]:
                break
        return numbers

    def binds(self) -> list:
        return [b for b in _bindings(self.cache)
                if b["status"] in reference.BOUND_STATUSES]

    def counts(self) -> dict:
        return self.ledger.check_binds(self.binds())[0]

    def dispatched(self) -> dict:
        """{(action, mode, program): dispatches of this drive}."""
        return {k: v - self.before.get(k, 0.0)
                for k, v in SOLVE_DISPATCHES._values.items()
                if v != self.before.get(k, 0.0)}

    def close(self) -> None:
        self.sched.close()
        self.cache.stop()


ZERO = dict.fromkeys(("unknown_pods", "unknown_nodes", "double_binds",
                      "nodes_over", "gangs_split", "unbound",
                      "overfit_binds"), 0)


def _churned(served: Served, bursts: int = 3) -> None:
    assert served.cycles() == ZERO      # the cold drain
    for _ in range(bursts):
        served.burst()
        assert served.cycles() == ZERO


@pytest.fixture
def four_device_mesh(monkeypatch):
    """``default_mesh()`` over four of the suite's devices: one host's."""
    monkeypatch.setattr(mesh_mod, "_default_mesh",
                        {1: mesh_mod.make_mesh(4)})


@pytest.mark.parametrize("devices", [8, 4])
def test_served_path_under_the_mesh_passes_the_reference(
        devices, request, monkeypatch):
    if devices == 4:
        request.getfixturevalue("four_device_mesh")
    monkeypatch.delenv("KB_SHARD", raising=False)
    assert dict(mesh_mod.default_mesh().shape) == {"nodes": devices}
    served = Served(seed=2700000000 + devices, audit_every=2)
    try:
        _churned(served)
        got = served.dispatched()
        # every solve went through the mesh: the cold drain as the full
        # matrix, the bursts over the compacted table
        assert got and {mode for _, mode, _ in got} == {"sharded"}, got
        assert got.get(("allocate", "sharded", "cold"), 0) >= 1
        assert sum(v for (a, _, p), v in got.items()
                   if a == "allocate" and p in ("topk", "warm")) >= 3
        # the audit's oracle went through the mesh too, and agreed
        guard = served.guard.state()
        assert guard["audits_run"] >= 1 and guard["audits_mismatched"] == 0
        assert guard["trips_total"] == 0 and guard["failed_closed"] == 0
        audits = [sp for rec in tracer_of(served.cache).recorder.records()
                  for root in rec.spans for sp in _walk(root)
                  if sp.name == "audit_dispatch"]
        assert audits and all(
            sp.attrs.get("mode") == "sharded" for sp in audits)
    finally:
        served.close()


def _walk(span):
    yield span
    for child in span.children:
        yield from _walk(child)


def test_the_same_seed_on_one_device_passes_the_same_check(monkeypatch):
    monkeypatch.setenv("KB_SHARD", "0")
    served = Served(seed=2700000008)
    try:
        _churned(served)
        got = served.dispatched()
        assert got and {mode for _, mode, _ in got} == {"single"}, got
    finally:
        served.close()


def test_a_demotion_over_the_budget_fails_closed(monkeypatch):
    """Compaction trips, so the next dispatch would run the full [T, N]
    matrix; the device (``KB_HBM_BUDGET``, the liveness audit's knob) is
    too small for it: nothing is solved, nothing binds, and the guard says
    so.  With room for it the same demotion lands on the oracle."""
    monkeypatch.delenv("KB_SHARD", raising=False)
    served = Served(seed=2700000016)
    try:
        assert served.cycles() == ZERO
        served.burst()
        assert served.cycles() == ZERO   # the compacted path has engaged
        served.guard.trip("allocate", ["topk"], reason="planted")
        assert not served.guard.allow("topk")
        unfit = GUARD_TRIPS._values.get(("allocate", "unfit"), 0.0)
        closed = served.guard.failed_closed
        cold = served.dispatched().get(("allocate", "sharded", "cold"), 0.0)

        monkeypatch.setenv("KB_HBM_BUDGET", "0.001")  # 1 MiB a device
        served.burst()
        served.sched.run_once_pipelined()
        served.sched.drain_pipeline()
        assert served.counts()["unbound"] == 4 * BURST_GANGS
        assert served.guard.failed_closed >= closed + 1
        assert GUARD_TRIPS._values[("allocate", "unfit")] >= unfit + 1
        assert served.dispatched().get(
            ("allocate", "sharded", "cold"), 0.0) == cold  # no program ran

        monkeypatch.setenv("KB_HBM_BUDGET", "16")
        assert not served.guard.allow("topk")    # still demoted
        served.sched.run_once_pipelined()
        served.sched.drain_pipeline()
        assert served.counts() == ZERO
        assert served.dispatched()[("allocate", "sharded", "cold")] > cold
    finally:
        served.close()


def test_the_first_unplaced_pod_compiles_nothing(monkeypatch):
    """The steady path's fit-error histogram is compiled with the first
    compacted solve: the first cycle that leaves a pod unplaced, which may
    come minutes into serving, runs it from the jit cache."""
    from kube_batch_tpu.metrics.metrics import JIT_COMPILES

    from kube_batch_tpu.framework.interface import get_action

    monkeypatch.delenv("KB_SHARD", raising=False)
    # the action outlives a test, as the jit cache does: start it unwarmed
    get_action("allocate")._fit_histograms_seen.clear()
    served = Served(seed=2700000032)
    try:
        _churned(served, bursts=2)
        tracer = tracer_of(served.cache)
        warmed = [sp for rec in tracer.recorder.records()
                  for root in rec.spans for sp in _walk(root)
                  if sp.name == "fit_histogram_dispatch"]
        assert len(warmed) == 1 and warmed[0].attrs.get("prewarm") is True
        assert "fit_errors" not in tracer.state()["span_counts"]
        # a gang that fits nowhere, beside a burst that does
        gang, mix = CONFIG["gang"], CONFIG["request_mix"]
        pgs, pods = served.ledger.make_gangs(
            1, gang["size"], gang["min_member"], [10_000_000],
            mix["memory_bytes"])
        served.post(pgs, pods)
        served.ledger.retire(pgs, pods)     # the reference never expects it
        served.burst()
        before = JIT_COMPILES._values[()]
        served.sched.run_once_pipelined()
        served.sched.drain_pipeline()
        assert tracer.state()["span_counts"]["fit_errors"] == 1
        assert JIT_COMPILES._values[()] == before
        assert served.counts() == ZERO
    finally:
        served.close()


def test_device_peaks_are_read_once_a_cycle_and_only_where_reported(
        monkeypatch):
    import types

    import jax

    from kube_batch_tpu.metrics import metrics as m

    monkeypatch.setattr(m.DEVICE_PEAK_BYTES, "_values", type(
        m.DEVICE_PEAK_BYTES._values)(float))
    m.refresh_device_peak_bytes()           # the CPU reports no statistics
    assert dict(m.DEVICE_PEAK_BYTES._values) == {}
    peaks = {0: 7 * 2 ** 30, 1: 5 * 2 ** 30}
    monkeypatch.setattr(jax, "local_devices", lambda: [
        types.SimpleNamespace(
            id=i, memory_stats=lambda i=i: {"peak_bytes_in_use": peaks[i]})
        for i in peaks])
    m.refresh_device_peak_bytes()
    assert dict(m.DEVICE_PEAK_BYTES._values) == {
        ("0",): float(peaks[0]), ("1",): float(peaks[1])}
    assert 'volcano_device_peak_bytes{device="1"}' in m.render_prometheus()
    # the loop refreshes it at the end of a cycle; a scrape never does
    peaks[1] = 6 * 2 ** 30
    m.render_prometheus()
    assert m.DEVICE_PEAK_BYTES._values[("1",)] == float(5 * 2 ** 30)
    served = Served(seed=2700000040)
    try:
        served.sched.run_once_pipelined()
        served.sched.drain_pipeline()
        assert m.DEVICE_PEAK_BYTES._values[("1",)] == float(peaks[1])
    finally:
        served.close()


@pytest.mark.parametrize("engaged,rebuilt,program", [
    ((), False, "cold"),
    (("shard_map",), False, "cold"),
    (("shard_map", "topk"), False, "topk"),
    (("topk", "warm"), False, "warm"),
    (("shard_map", "topk", "warm"), True, "topk"),
])
def test_program_label(engaged, rebuilt, program):
    assert solve_program(engaged, rebuilt) == program

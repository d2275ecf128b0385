"""Event-driven pipelined cycles: bit-exactness vs the serial oracle over
randomized churn, ingest staging semantics, trigger semantics, the in-flight
bind guard, and the budget-shed interaction with the overlapped close.

The pipelined loop's contract: same binds, same statuses, same queue
writebacks as the serial wait.Until loop — the overlap only moves WHEN the
egress happens, never WHAT it says.  These tests run the two modes over
identical seed-deterministic churn streams and diff the observable end
state.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import metrics as prom_metrics
from kube_batch_tpu.metrics.metrics import STATUS_WRITES_SHED
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.pod import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    Queue,
)
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.cache.cache import SchedulerCache, StatusFlush
from kube_batch_tpu.cache.fake import FakeBinder, FakeEvictor, FakeStatusUpdater
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.scheduler import CycleTrigger, Scheduler
from kube_batch_tpu.sim import kubelet as kl
from kube_batch_tpu.testing.synthetic import GiB


def _mk_cache(n_nodes=6, n_queues=2):
    cache = SchedulerCache(
        binder=FakeBinder(), evictor=FakeEvictor(),
        status_updater=FakeStatusUpdater(),
    )
    for q in range(n_queues):
        cache.add_queue(Queue(name=f"q{q}", uid=f"uq{q}", weight=q + 1))
    for i in range(n_nodes):
        cache.add_node(Node(
            name=f"n{i}",
            allocatable={"cpu": 16000.0, "memory": 64 * GiB, "pods": 110.0},
        ))
    return cache


def _mk_scheduler(cache) -> Scheduler:
    return Scheduler(cache, conf=load_scheduler_conf(None))


class _Churner:
    """Seed-deterministic churn through the real ingest surface — applied
    IDENTICALLY to the serial and pipelined caches each cycle."""

    def __init__(self, cache, seed, n_queues=2):
        self.cache = cache
        self.rng = np.random.default_rng(seed)
        self.n_queues = n_queues
        self.serial = 0
        self.gangs = []

    def add_gang(self):
        self.serial += 1
        g = f"g{self.serial}"
        size = int(self.rng.integers(1, 4))
        self.cache.add_pod_group(PodGroup(
            name=g, namespace="churn", uid=f"pg-{g}", min_member=size,
            queue=f"q{int(self.rng.integers(self.n_queues))}",
            creation_index=self.serial,
        ))
        for k in range(size):
            self.cache.add_pod(Pod(
                name=f"{g}-{k}", namespace="churn", uid=f"pod-{g}-{k}",
                requests={"cpu": float(self.rng.choice([250.0, 500.0, 1000.0])),
                          "memory": 1 * GiB},
                annotations={GROUP_NAME_ANNOTATION: g},
                phase=PodPhase.PENDING,
                creation_index=self.serial * 100 + k,
            ))
        self.gangs.append(g)

    def complete_gang(self):
        if not self.gangs:
            return
        g = self.gangs.pop(int(self.rng.integers(len(self.gangs))))
        job_uid = f"churn/{g}"
        job = self.cache.jobs.get(job_uid)
        keys = sorted(job.tasks.keys()) if job is not None else []
        for key in keys:
            kl.delete_pod(self.cache, key)
        self.cache.delete_pod_group(job_uid)

    def flip_statuses(self):
        pods = [p for p in self.cache.pods.values() if p.node_name]
        if not pods:
            return
        pods.sort(key=lambda p: p.key())
        for p in pods[: int(self.rng.integers(1, 3))]:
            if p.phase == PodPhase.PENDING:
                kl.set_running(self.cache, p.key(), p.node_name)
            elif p.phase == PodPhase.RUNNING and self.rng.random() < 0.5:
                kl.set_succeeded(self.cache, p.key())

    def step(self):
        r = self.rng.random()
        if r < 0.45:
            self.add_gang()
        elif r < 0.70:
            self.complete_gang()
        else:
            self.flip_statuses()


def _observable_state(cache) -> dict:
    """Everything the pipelined loop promises not to change: durable
    bindings, pod phases, podgroup statuses, conditions, queue writebacks."""
    pg_status = {}
    for uid, job in sorted(cache.jobs.items()):
        pg = job.pod_group
        if pg is not None:
            pg_status[uid] = (pg.phase, pg.running, pg.failed, pg.succeeded)
    return {
        "binds": dict(cache.binder.binds),
        "pods": {k: (p.node_name, p.phase)
                 for k, p in sorted(cache.pods.items())},
        "pg_status": pg_status,
        "conditions": dict(cache.pod_conditions),
        "queue_statuses": dict(cache.status_updater.queue_statuses),
    }


class TestPipelinedBitExact:
    @pytest.mark.parametrize("seed", [0, 11, 42])
    def test_pipelined_matches_serial_over_randomized_churn(self, seed):
        """Same churn stream, serial vs pipelined cycles: identical binds
        (no duplicates, no losses), identical pod/podgroup statuses,
        identical conditions and queue writebacks."""
        c_serial, c_pipe = _mk_cache(), _mk_cache()
        s_serial, s_pipe = _mk_scheduler(c_serial), _mk_scheduler(c_pipe)
        ch_serial = _Churner(c_serial, seed)
        ch_pipe = _Churner(c_pipe, seed)
        for _ in range(3):
            ch_serial.add_gang()
            ch_pipe.add_gang()
        for cycle in range(10):
            ch_serial.step()
            ch_pipe.step()
            s_serial.run_once()
            s_pipe.run_once_pipelined()
            s_pipe.drain_pipeline()
        want = _observable_state(c_serial)
        got = _observable_state(c_pipe)
        for field in want:
            assert got[field] == want[field], (
                f"seed={seed}: {field} diverged between serial and "
                f"pipelined cycles"
            )
        # no duplicate binds: every bound pod was dispatched exactly once
        keys = [k for k in c_pipe.binder.channel]
        assert len(keys) == len(set(keys)), "duplicate bind dispatch"

    def test_pipelined_with_staged_ingest_matches_serial(self):
        """The staged-ingest path (churn lands in the staging buffer, the
        cycle drains it under one lock) reaches the same end state as
        direct ingest + serial cycles."""
        c_serial, c_pipe = _mk_cache(), _mk_cache()
        s_serial, s_pipe = _mk_scheduler(c_serial), _mk_scheduler(c_pipe)
        c_pipe.enable_ingest_staging()
        ch_serial = _Churner(c_serial, 5)
        ch_pipe = _Churner(c_pipe, 5)
        for cycle in range(8):
            ch_serial.step()
            ch_pipe.step()  # staged, applied at the next cycle's drain
            s_serial.run_once()
            s_pipe.run_once_pipelined()
            s_pipe.drain_pipeline()
        # flush any residue and settle both sides one more cycle
        c_pipe.disable_ingest_staging()
        s_serial.run_once()
        s_pipe.run_once_pipelined()
        s_pipe.drain_pipeline()
        assert _observable_state(c_pipe) == _observable_state(c_serial)


class TestStagedIngest:
    def test_staged_events_invisible_until_drain(self):
        cache = _mk_cache(n_nodes=1)
        cache.enable_ingest_staging()
        pod = Pod(name="p0", namespace="ns", uid="u0",
                  requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                  creation_index=1)
        cache.add_pod(pod)
        assert "ns/p0" not in cache.pods
        assert cache.drain_staged_ingest() == 1
        assert "ns/p0" in cache.pods

    def test_staged_arrival_fires_wake_signal(self):
        cache = _mk_cache(n_nodes=1)
        wakes = []
        cache.set_ingest_signal(lambda: wakes.append(1))
        cache.enable_ingest_staging()
        cache.add_pod(Pod(name="p1", namespace="ns", uid="u1",
                          requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                          creation_index=1))
        assert wakes, "staged arrival must wake the cycle trigger"

    def test_direct_dirty_advance_fires_wake_signal(self):
        cache = _mk_cache(n_nodes=1)
        wakes = []
        cache.set_ingest_signal(lambda: wakes.append(1))
        cache.add_pod(Pod(name="p2", namespace="ns", uid="u2",
                          requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                          creation_index=1))
        assert wakes, "an un-staged ingest's dirty advance must wake too"

    def test_disable_drains_residue(self):
        cache = _mk_cache(n_nodes=1)
        cache.enable_ingest_staging()
        cache.add_pod(Pod(name="p3", namespace="ns", uid="u3",
                          requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                          creation_index=1))
        cache.disable_ingest_staging()
        assert "ns/p3" in cache.pods

    def test_drain_does_not_retrigger_its_own_cycle(self):
        """The cycle's drain applies churn the session about to open will
        consume — its dirty advances must not re-wake the trigger (which
        would schedule a guaranteed no-op follow-up cycle every burst)."""
        cache = _mk_cache(n_nodes=1)
        wakes = []
        cache.set_ingest_signal(lambda: wakes.append(1))
        cache.enable_ingest_staging()
        cache.add_pod(Pod(name="d0", namespace="ns", uid="ud0",
                          requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                          creation_index=1))
        staged_wakes = len(wakes)
        assert staged_wakes >= 1
        assert cache.drain_staged_ingest() == 1
        assert len(wakes) == staged_wakes, (
            "the drain's own applies re-woke the trigger"
        )

    def test_direct_batch_apply_still_wakes(self):
        """ingest_batch with staging OFF is real external churn — its one
        coalesced dirty advance must wake the loop (unlike the drain)."""
        cache = _mk_cache(n_nodes=1)
        wakes = []
        cache.set_ingest_signal(lambda: wakes.append(1))
        pod = Pod(name="d1", namespace="ns", uid="ud1",
                  requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                  creation_index=1)
        cache.ingest_batch([(cache.add_pod, pod)])
        assert wakes

    def test_staged_arrival_stamps_clock_at_stage_time(self):
        """The arrival→decision clock starts when the pod lands in the
        staging buffer, not when the next cycle's drain applies it."""
        cache = _mk_cache(n_nodes=1)
        cache.enable_ingest_staging()
        cache.add_pod(Pod(name="s0", namespace="ns", uid="us0",
                          requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                          creation_index=1))
        assert "ns/s0" in cache._arrival_ts, "stamp must precede the drain"
        t0 = cache._arrival_ts["ns/s0"]
        cache.drain_staged_ingest()
        assert cache._arrival_ts["ns/s0"] == t0, (
            "the drain's apply must keep the stage-time stamp"
        )

    def test_ingest_batch_reports_partial_failure(self):
        cache = _mk_cache(n_nodes=1)
        good = Pod(name="pf0", namespace="ns", uid="upf0",
                   requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                   creation_index=1)

        def boom(obj):
            raise ValueError("bad element")

        applied = cache.ingest_batch(
            [(cache.add_pod, good), (boom, object())])
        assert applied == 1, "only successful applies count"
        assert "ns/pf0" in cache.pods

    def test_ingest_batch_single_version_advance(self):
        cache = _mk_cache(n_nodes=1)
        v0 = cache.dirty.version
        pods = [
            Pod(name=f"b{i}", namespace="ns", uid=f"ub{i}",
                requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                creation_index=10 + i)
            for i in range(5)
        ]
        applied = cache.ingest_batch([(cache.add_pod, p) for p in pods])
        assert applied == 5
        assert all(f"ns/b{i}" in cache.pods for i in range(5))
        assert cache.dirty.version == v0 + 1, (
            "a batch advances the dirty version ONCE"
        )
        # per-kind dirty sets still carry every element for the delta open
        assert len(cache.dirty.pods) >= 5


class TestCycleTrigger:
    def test_notify_wakes_as_ingest(self):
        trig = CycleTrigger()
        trig.notify()
        t0 = time.monotonic()
        reason = trig.wait_for_work(time.monotonic(), 0.0, 5.0)
        assert reason == "ingest"
        assert time.monotonic() - t0 < 1.0

    def test_idle_wakes_at_the_floor(self):
        trig = CycleTrigger()
        start = time.monotonic()
        reason = trig.wait_for_work(start, 0.0, 0.08)
        assert reason == "floor"
        assert time.monotonic() - start >= 0.07

    def test_min_period_coalesces_bursts(self):
        """A signal raised immediately after the cycle start must still
        wait out the rate floor — bursts become one cycle per min_period."""
        trig = CycleTrigger()
        start = time.monotonic()
        trig.notify()
        reason = trig.wait_for_work(start, 0.08, 5.0)
        assert reason == "ingest"
        assert time.monotonic() - start >= 0.07

    def test_poll_consumes_pending(self):
        trig = CycleTrigger()
        trig.notify()
        assert trig.poll() is True
        assert trig.poll() is False

    def test_cross_thread_notify(self):
        trig = CycleTrigger()
        threading.Timer(0.03, trig.notify).start()
        reason = trig.wait_for_work(time.monotonic(), 0.0, 5.0)
        assert reason == "ingest"


class TestLeftoverTrigger:
    """The loop's own wake (``notify(leftover=True)``) on the sim's virtual
    clock: it has its own reason on the park spans, the rate floor holds
    for it, and an ingest signal beside it wins the reason."""

    def _trigger(self, **kw):
        from kube_batch_tpu.sim.clock import VirtualClock

        clock = VirtualClock(start=100.0)
        return CycleTrigger(clock=clock, **kw), clock

    def test_self_wake_reports_leftover(self):
        trig, clock = self._trigger()
        trig.notify(leftover=True)
        assert trig.wait_for_work(100.0, 0.0, 5.0) == "leftover"
        assert clock.monotonic() == 100.0  # neither floor nor idle tick
        # consumed: the next wait runs to the idle tick
        assert trig.wait_for_work(100.0, 0.0, 0.0) == "floor"

    def test_floor_holds_for_a_leftover_wake(self):
        """test_min_period_coalesces_bursts's form: a self-wake raised at
        the cycle's end still waits out the rate floor, and both park
        spans of the cycle it precedes say who woke it."""
        from kube_batch_tpu.obs.trace import Tracer

        tr = Tracer(enabled=True)
        trig, clock = self._trigger(tracer=tr)
        trig.notify(leftover=True)
        assert trig.wait_for_work(100.0, 0.08, 5.0) == "leftover"
        assert clock.monotonic() == pytest.approx(100.08)
        record = tr.begin_cycle("pipelined")
        tr.end_cycle()
        floor, event = record.spans
        assert (floor.name, event.name) == ("park:floor", "park:event")
        assert floor.attrs["woke_by"] == event.attrs["woke_by"] == "leftover"
        assert event.attrs["signalled_ms"] == pytest.approx(80.0)

    @pytest.mark.parametrize("ingest_first", [True, False])
    def test_ingest_beside_a_self_wake_reports_ingest(self, ingest_first):
        trig, clock = self._trigger()
        if ingest_first:
            trig.notify()
        trig.notify(leftover=True)
        if not ingest_first:
            clock.sleep(0.01)
            trig.notify()
        assert trig.wait_for_work(100.0, 0.0, 5.0) == "ingest"
        # one signal, one wake: nothing of the self-wake is left over
        assert trig.poll() is False

    def test_poll_consumes_a_self_wake_as_a_bool(self):
        trig, _ = self._trigger()
        trig.notify(leftover=True)
        assert trig.poll() is True
        assert trig.poll() is False


def _settle_counts() -> dict:
    m = prom_metrics.metrics
    counts = {by: m.SETTLE_HOLDS._values.get((by,), 0.0)
              for by in ("quiet", "cap", "stop")}
    counts["signals"] = m.SETTLE_SIGNALS._values.get((), 0.0)
    counts["held_ms"], counts["held"] = m.SETTLE_HELD._sum, m.SETTLE_HELD._count
    return counts


def _grown(before: dict) -> dict:
    """What the settle counters grew by, zeros left out."""
    after = _settle_counts()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


class TestSettleHold:
    """The trigger's settle hold on the virtual clock (``PacedCondition``:
    every wait moves the clock on instead of blocking, and raises the
    signals scripted to fall inside it): an ingest wake starts its cycle
    once no further signal has come for the quiet gap, or at the cap from
    the first unconsumed signal; signals are ms after t = 100 s, and the
    loop gets to look at ``look`` ms (the rest of a cycle and its floor)."""

    T0 = 100.0
    QUIET, CAP = 0.010, 0.050

    def _trigger(self, signals, look=0, tracer=None):
        from kube_batch_tpu.sim.clock import VirtualClock
        from tests.fixtures import PacedCondition

        clock = VirtualClock(start=self.T0)
        trig = CycleTrigger(clock=clock, tracer=tracer)
        at = [(self.T0 + ms / 1e3, kw) for ms, kw in signals]
        for t, kw in at:
            if t <= self.T0 + look / 1e3:  # raised before the loop looks
                clock.advance_to(t)
                trig.notify(**kw)
        clock.advance_to(self.T0 + look / 1e3)
        PacedCondition.install(
            trig, [s for s in at if s[0] > self.T0 + look / 1e3])
        return trig, clock

    @pytest.mark.parametrize(
        "signals, look, quiet, cap, starts, ended_by, folded", [
            # a second signal inside the quiet gap joins the same wake and
            # restarts the gap; a third, later than first + quiet, too
            pytest.param((0, 3), 0, QUIET, CAP, 13, "quiet", 2, id="folded"),
            pytest.param((0, 8, 16), 0, QUIET, CAP, 26, "quiet", 3,
                         id="gap-restarts"),
            pytest.param((0,), 0, QUIET, CAP, 10, "quiet", 1, id="lone"),
            # a continuous stream: the cap, from the FIRST signal
            pytest.param(tuple(range(0, 100, 4)), 0, QUIET, CAP, 50, "cap",
                         13, id="stream"),
            # a cap below the quiet gap (a cycle cheaper than 10 ms) wins
            pytest.param((0,), 0, QUIET, 0.002, 2, "cap", 1, id="cap<quiet"),
            # a signal that came mid-cycle pays what is left of its gap ...
            pytest.param((0,), 4, QUIET, CAP, 10, "quiet", 1, id="part-left"),
            # ... and nothing once it is older than the gap, or the cap
            pytest.param((0,), 30, QUIET, CAP, 30, None, 0, id="older-quiet"),
            pytest.param((0, 45), 55, 0.020, CAP, 55, None, 0,
                         id="older-cap"),
        ])
    def test_the_hold_ends_at_the_quiet_gap_or_the_cap(
            self, signals, look, quiet, cap, starts, ended_by, folded):
        from kube_batch_tpu.obs.trace import Tracer

        tr = Tracer(enabled=True)
        trig, clock = self._trigger([(ms, {}) for ms in signals], look,
                                    tracer=tr)
        before = _settle_counts()
        reason = trig.wait_for_work(self.T0 - 1.0, 0.0, 5.0, (quiet, cap))
        assert reason == "ingest"
        assert clock.monotonic() == pytest.approx(self.T0 + starts / 1e3)
        assert trig.poll() is False, "one wake, every signal consumed"
        record = tr.begin_cycle("pipelined")
        tr.end_cycle()
        event, = record.spans
        assert event.attrs["signalled_ms"] == pytest.approx(starts, abs=1e-3)
        if ended_by is None:
            assert _grown(before) == {} and event.children == []
            assert "settle" not in tr.span_counts
            return
        grown = _grown(before)
        assert grown.pop("held_ms") == pytest.approx(starts - look)
        assert grown == {ended_by: 1.0, "signals": float(folded), "held": 1}
        settle, = event.children
        gaps = [b - a for a, b in zip(signals, signals[1:])][:folded - 1]
        assert (settle.name, settle.attrs) == ("settle", {
            "q_ms": quiet * 1e3, "signals": folded, "ended_by": ended_by,
            "widest_gap_ms": pytest.approx(max(gaps, default=0.0)),
        })

    @pytest.mark.parametrize("signals, settle, reason, starts, grew", [
        pytest.param([(0, {"leftover": True})], (QUIET, CAP), "leftover", 0,
                     {}, id="leftover"),
        pytest.param([], (QUIET, CAP), "floor", 0, {}, id="floor"),
        pytest.param([(0, {"stop": True})], (QUIET, CAP), "ingest", 0, {},
                     id="stop"),
        # stop() lets a wake that is being held go at once
        pytest.param([(0, {}), (4, {"stop": True})], (QUIET, CAP), "ingest",
                     4, {"stop": 1.0, "signals": 2.0, "held": 1,
                         "held_ms": 4.0}, id="stop-mid-hold"),
        # no cycle measured yet (the EWMA is None): no window, no hold
        pytest.param([(0, {})], None, "ingest", 0, {}, id="no-ewma"),
    ])
    def test_what_never_holds(self, signals, settle, reason, starts, grew):
        trig, clock = self._trigger(signals)
        before = _settle_counts()
        assert trig.wait_for_work(self.T0, 0.0, 0.0, settle) == reason
        assert clock.monotonic() == pytest.approx(self.T0 + starts / 1e3)
        assert _grown(before) == pytest.approx(grew)
        # a stop() is consumed with its wake: the next ingest wake holds
        trig.notify()
        trig.wait_for_work(self.T0, 0.0, 0.0, (self.QUIET, self.CAP))
        assert clock.monotonic() == pytest.approx(
            self.T0 + starts / 1e3 + self.QUIET)

    def test_poll_knows_no_hold(self):
        """The sim's pacing consumes a signal at once, and with it the
        count the next wake's hold would report."""
        trig, clock = self._trigger([(0, {}), (0, {})])
        assert trig.poll() is True
        assert (trig._signals, clock.monotonic()) == (0, self.T0)


class TestAdaptiveMinPeriod:
    """KB_PERIOD_MIN unset → the trigger's coalescing floor tracks an EWMA
    of the cycle's own measured cost (a 200 ms solve shouldn't re-trigger
    every 50 ms; a 10 ms cycle shouldn't wait out 50); setting the env
    pins the static floor back."""

    def _sched(self, **env):
        import os

        saved = {k: os.environ.get(k) for k in ("KB_PERIOD_MIN",)}
        os.environ.pop("KB_PERIOD_MIN", None)
        os.environ.update(env)
        try:
            return Scheduler(_mk_cache(), conf=load_scheduler_conf(None),
                             schedule_period=1.0)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def test_adapts_to_measured_cost(self):
        sched = self._sched()
        assert not sched.min_period_pinned
        assert sched.min_period == pytest.approx(0.05)  # static default
        sched._note_cycle_cost(0.2)
        assert sched.cycle_cost_ewma == pytest.approx(0.2)
        assert sched.min_period == pytest.approx(0.2)
        # EWMA smoothing: a single fast outlier moves the floor by alpha
        sched._note_cycle_cost(0.0)
        expect = (1.0 - Scheduler.EWMA_ALPHA) * 0.2
        assert sched.cycle_cost_ewma == pytest.approx(expect)
        assert sched.min_period == pytest.approx(expect)

    def test_floor_and_ceiling_clamps(self):
        sched = self._sched()
        # degenerate fast cycles clamp at the busy-spin floor, not zero
        for _ in range(50):
            sched._note_cycle_cost(0.0)
        assert sched.min_period == pytest.approx(Scheduler.MIN_PERIOD_FLOOR)
        # a pathological cycle cost clamps at max_period (idle tick stays
        # reachable)
        for _ in range(50):
            sched._note_cycle_cost(100.0)
        assert sched.min_period == pytest.approx(sched.max_period)
        # negative (clock skew) samples are ignored
        ewma = sched.cycle_cost_ewma
        sched._note_cycle_cost(-1.0)
        assert sched.cycle_cost_ewma == ewma

    def test_env_pin_restores_static_floor(self):
        sched = self._sched(KB_PERIOD_MIN="0.123")
        assert sched.min_period_pinned
        assert sched.min_period == pytest.approx(0.123)
        sched._note_cycle_cost(0.5)
        # the EWMA still tracks (observability), the floor does not move
        assert sched.cycle_cost_ewma == pytest.approx(0.5)
        assert sched.min_period == pytest.approx(0.123)

    @pytest.mark.parametrize("pinned", [False, True])
    @pytest.mark.parametrize("cost, window", [
        (None, None),              # nothing measured: no hold
        (0.001, (0.005, 0.0005)),  # the quiet gap's lower clamp
        (0.100, (0.0125, 0.050)),  # an eighth and a half of a cycle
        (0.160, (0.020, 0.080)),
        (0.300, (0.025, 0.100)),   # both upper clamps
        (5.000, (0.025, 0.100)),   # the cold drain's cost
    ])
    def test_settle_window_follows_the_cycle_cost(self, cost, window, pinned):
        """The hold's two bounds are shares of the same EWMA, clamped; a
        pinned KB_PERIOD_MIN pins the floor, not the hold."""
        sched = self._sched(**({"KB_PERIOD_MIN": "0.123"} if pinned else {}))
        assert sched.settle_window() is None
        if cost is not None:
            sched._note_cycle_cost(cost)
        assert sched.settle_window() == pytest.approx(window)

    def test_pipelined_loop_feeds_the_ewma(self):
        """The real loop wires measured cycle costs into the floor: after a
        brief pipelined run of an idle cache, the EWMA is populated and the
        unpinned floor has left the static 50 ms default (fast idle cycles
        pull it down toward the busy-spin floor)."""
        sched = self._sched()
        sched.pipelined = True
        sched.max_period = 0.01  # tick fast so several cycles run
        t = threading.Thread(target=sched.run_forever, daemon=True)
        t.start()
        try:
            time.sleep(0.5)
        finally:
            sched.stop()
            t.join(timeout=5.0)
        assert sched.cycle_cost_ewma is not None
        assert sched.min_period < 0.05


class TestRunForeverPipelined:
    def test_burst_binds_and_shutdown_drains(self):
        """run_forever in pipelined mode: a pod staged mid-loop is bound
        without waiting out the idle period, and stop() drains every
        in-flight stage (staging buffer empty, writeback joined)."""
        cache = _mk_cache()
        sched = Scheduler(cache, conf=load_scheduler_conf(None),
                          schedule_period=5.0)
        sched.pipelined = True
        sched.min_period = 0.0
        sched.max_period = 5.0  # idle floor far beyond the test timeout
        t = threading.Thread(target=sched.run_forever, daemon=True)
        t.start()
        try:
            time.sleep(0.2)  # loop reaches its idle wait
            cache.add_pod_group(PodGroup(
                name="burst", namespace="ns", uid="pg-burst", min_member=1,
                queue="q0", creation_index=1,
            ))
            cache.add_pod(Pod(
                name="burst-0", namespace="ns", uid="u-burst",
                requests={"cpu": 500.0}, phase=PodPhase.PENDING,
                annotations={GROUP_NAME_ANNOTATION: "burst"},
                creation_index=2,
            ))
            # the arrival must schedule a cycle well before the 5 s floor
            assert cache.binder.event.wait(3.0), (
                "burst arrival did not trigger a cycle before the idle "
                "period"
            )
        finally:
            sched.stop()
            t.join(timeout=10.0)
        assert not t.is_alive()
        with cache._ingest_lock:  # guarded-access corroborator: hold the domain lock
            assert cache._ingest_staged == [], "shutdown must drain staging"
        assert sched._wb_future is None, "shutdown must join the writeback"
        assert cache.binder.binds.get("ns/burst-0") is not None

    def test_serial_oracle_knob(self, monkeypatch):
        monkeypatch.setenv("KB_PIPELINE", "0")
        sched = Scheduler(_mk_cache(n_nodes=1),
                          conf=load_scheduler_conf(None))
        assert sched.pipelined is False


def _wakes(*triggers) -> float:
    values = prom_metrics.metrics.TRIGGER_WAKES._values
    return sum(values.get((t,), 0.0) for t in triggers)


def _self_wakes() -> float:
    return prom_metrics.metrics.SELF_WAKES._values.get((), 0.0)


class TestLeftoverWake:
    """The event-driven loop wakes itself for what a cycle left behind: a
    cycle that bound pods and left schedulable ones pending is followed by
    the next as soon as the floor allows, not at the next event or the idle
    tick — and a cycle that binds nothing, or raises, never is.  The loop
    runs on a real thread; the test follows it by handshake (every entry
    into the trigger's wait is announced), never by sleeping."""

    UNFIT_CPU = 10_000_000.0  # no node of _mk_cache has a tenth of it

    def _pod(self, cache, name, cpu):
        cache.add_pod_group(PodGroup(
            name=name, namespace="ns", uid=f"pg-{name}", min_member=1,
            queue="q0", creation_index=1,
        ))
        cache.add_pod(Pod(
            name=f"{name}-0", namespace="ns", uid=f"u-{name}",
            requests={"cpu": cpu}, phase=PodPhase.PENDING,
            annotations={GROUP_NAME_ANNOTATION: name}, creation_index=2,
        ))

    def _loop(self, cache, max_period):
        """(scheduler, its log, the park handshake).  The log holds, in the
        loop's order: ("cycle", end time) after each cycle, ("park", the
        signal pending as the loop enters its wait) and (wake reason, wake
        time) when the wait returns; every "park" releases the semaphore
        once."""
        sched = Scheduler(cache, conf=load_scheduler_conf(None),
                          schedule_period=max_period)
        sched.pipelined = True
        sched.min_period_pinned, sched.min_period = True, 0.0
        log, parked = [], threading.Semaphore(0)
        cycle, wait = sched.run_once_pipelined, sched.trigger.wait_for_work

        def run_once_pipelined(*wake):
            try:
                return cycle(*wake)
            finally:
                log.append(("cycle", time.monotonic()))

        def wait_for_work(*args):
            log.append(("park", sched.trigger._pending))
            parked.release()
            reason = wait(*args)
            log.append((reason, time.monotonic()))
            return reason

        sched.run_once_pipelined = run_once_pipelined
        sched.trigger.wait_for_work = wait_for_work
        return sched, log, parked

    @staticmethod
    def _run(sched, log, parked, parks, timeout=120.0):
        """Run the loop until it has entered its wait ``parks`` times, then
        stop it; returns the log and when stop() was called."""
        t = threading.Thread(target=sched.run_forever, daemon=True)
        t.start()
        try:
            for _ in range(parks):
                assert parked.acquire(timeout=timeout), log
        finally:
            stopped_at = time.monotonic()
            sched.stop()
            t.join(timeout=30.0)
        assert not t.is_alive()
        return log, stopped_at

    def test_progress_beside_pending_pods_wakes_one_more_cycle(self):
        cache = _mk_cache()
        self._pod(cache, "fits", 500.0)
        self._pod(cache, "unfit", self.UNFIT_CPU)
        sched, log, parked = self._loop(cache, max_period=5.0)
        self_wakes0, wakes0 = _self_wakes(), _wakes("ingest", "floor")
        log, stopped_at = self._run(sched, log, parked, parks=2)
        # cycle 1 binds `fits` and leaves `unfit` pending -> the loop wakes
        # itself; cycle 2 binds nothing -> it parks with nothing pending,
        # and only stop()'s own signal ends that wait
        assert [e[0] for e in log] == [
            "cycle", "park", "leftover", "cycle", "park", "ingest"]
        assert [e[1] for e in log if e[0] == "park"] == ["leftover", None]
        assert log[2][1] - log[0][1] < 1.0, "woken at the 5 s idle tick"
        assert log[5][1] >= stopped_at, "a third cycle before stop()"
        assert cache.binder.binds.get("ns/fits-0") is not None
        assert "ns/unfit-0" not in cache.binder.binds
        assert _self_wakes() == self_wakes0 + 1
        # nothing is left out of the series cycles are counted from: two
        # cycles ran, and ingest + floor grew by two (the self-wake under
        # "ingest"; stop()'s wake stands in for the start-up cycle's none)
        assert _wakes("ingest", "floor") == wakes0 + 2
        assert _wakes("leftover") == 0.0, "a third label"
        # the ring's self-woken cycle says so on its park span
        park = sched.tracer.recorder.last_record().spans[0]
        assert (park.name, park.attrs["woke_by"]) == ("park:event", "leftover")

    def test_no_progress_beside_pending_pods_wakes_nothing(self):
        cache = _mk_cache()
        self._pod(cache, "unfit", self.UNFIT_CPU)
        sched, log, parked = self._loop(cache, max_period=0.05)
        self_wakes0 = _self_wakes()
        log, _ = self._run(sched, log, parked, parks=4)
        # the first cycle plus one per idle tick, none self-woken
        assert [e[1] for e in log if e[0] == "park"][:4] == [None] * 4
        reasons = [e[0] for e in log if e[0] not in ("cycle", "park")]
        assert reasons[:3] == ["floor"] * 3
        assert "leftover" not in reasons
        assert cache.binder.binds == {}
        assert _self_wakes() == self_wakes0

    def test_a_cycle_that_raises_does_not_self_wake(self):
        cache = _mk_cache()
        self._pod(cache, "fits", 500.0)
        self._pod(cache, "unfit", self.UNFIT_CPU)
        sched, log, parked = self._loop(cache, max_period=0.05)
        cycle, state = sched.run_once_pipelined, {"n": 0}

        def failing_after_its_binds(*wake):
            opened = cycle(*wake)
            state["n"] += 1
            if state["n"] == 1:
                # the bind is acknowledged before the recovery's re-list,
                # so no later cycle binds `fits` a second time
                sched.drain_pipeline()
                raise RuntimeError("planted: the cycle dies after binding")
            return opened

        sched.run_once_pipelined = failing_after_its_binds
        self_wakes0 = _self_wakes()
        log, _ = self._run(sched, log, parked, parks=3)
        assert cache.binder.binds.get("ns/fits-0") is not None
        # progress and pending pods, but the cycle failed: the recovery's
        # re-list or the idle tick wakes the loop, never the loop itself;
        # the cycles after it bind nothing
        assert "leftover" not in [e[0] for e in log]
        assert "leftover" not in [e[1] for e in log if e[0] == "park"]
        assert _self_wakes() == self_wakes0

    @pytest.mark.parametrize("site", ["bind", "bulk_bind"])
    def test_both_bind_sites_count_as_progress(self, site):
        """The tally is the cache's own, kept where the bind is made: a pod
        whose arrival->decision clock is gone (popped by an earlier, failed
        bind) still counts; pending pods without a bind are no reason to
        wake, and neither is a bind with nothing pending."""
        cache = _mk_cache()
        self._pod(cache, "fits", 500.0)
        self._pod(cache, "unfit", self.UNFIT_CPU)
        cache._arrival_ts.clear()
        before = cache.binds_total
        assert not cache.left_schedulable_pending(before)
        task = cache.jobs["ns/fits"].tasks["ns/fits-0"]
        if site == "bind":
            cache.bind(task, "n0")
        else:
            cache.bulk_bind([(task, "n0")])
        cache.flush_binds()
        assert cache.binds_total == before + 1
        assert cache.left_schedulable_pending(before)
        cache.delete_pod(cache.pods["ns/unfit-0"])
        assert not cache.left_schedulable_pending(before)


class TestOneCycleABurst:
    """Through the real loop and cache: a burst whose requests reach the
    staging buffer milliseconds apart is decided by ONE cycle.  The loop
    runs on a real thread against a clock only the test moves, so the hold
    ends when the test says, never by the machine's speed; the test follows
    the loop by handshake (its entry into the wait, and into the hold)."""

    CPU = 12000.0  # a node of _mk_cache holds one such pod, not two

    def _gang(self, name, index):
        pg = PodGroup(name=name, namespace="ns", uid=f"pg-{name}",
                      min_member=1, queue="q0", creation_index=index)
        pod = Pod(name=f"{name}-0", namespace="ns", uid=f"u-{name}",
                  requests={"cpu": self.CPU}, phase=PodPhase.PENDING,
                  annotations={GROUP_NAME_ANNOTATION: name},
                  creation_index=index)
        return pg, pod

    def test_delete_then_post_are_decided_by_one_cycle_in_arrival_order(self):
        from kube_batch_tpu.sim.clock import VirtualClock

        cache = _mk_cache(n_nodes=1)
        old_pg, old_pod = self._gang("old", 1)
        cache.add_pod_group(old_pg)
        cache.add_pod(old_pod)
        clock = VirtualClock(start=100.0)
        sched = Scheduler(cache, conf=load_scheduler_conf(None),
                          schedule_period=1e6, clock=clock)
        sched.pipelined = True
        sched.min_period_pinned, sched.min_period = True, 0.0
        # a cycle costs nothing on a clock that stands still: give the
        # hold the window a 200 ms cycle would
        sched.settle_window = lambda: (0.025, 0.1)
        parked, settling = threading.Semaphore(0), threading.Semaphore(0)
        wait, settle = sched.trigger.wait_for_work, sched.trigger._settle

        def wait_for_work(*args):
            parked.release()
            return wait(*args)

        def _settle(*args):
            settling.release()
            return settle(*args)

        sched.trigger.wait_for_work = wait_for_work
        sched.trigger._settle = _settle
        t = threading.Thread(target=sched.run_forever, daemon=True)
        t.start()
        try:
            # the start-up cycle binds `old` on the only node
            assert parked.acquire(timeout=120.0)
            assert cache.binder.event.wait(30.0)  # the writeback's drain
            assert cache.binder.binds == {"ns/old-0": "n0"}
            opens0 = sched.tracer.span_counts["session_open"]
            before = _settle_counts()
            # the burst: four requests, 1 ms apart, DELETEs first
            new_pg, new_pod = self._gang("new", 2)
            cache.delete_pod(cache.pods["ns/old-0"])
            assert settling.acquire(timeout=30.0), "the first signal wakes"
            for request in (lambda: cache.delete_pod_group(old_pg),
                            lambda: cache.add_pod_group(new_pg),
                            lambda: cache.add_pod(new_pod)):
                clock.sleep(0.001)
                request()
            # held: nothing is drained while the burst is still arriving
            with cache._ingest_lock:
                assert len(cache._ingest_staged) == 4
            clock.sleep(0.025)  # the quiet gap after the last request
            assert parked.acquire(timeout=120.0)
        finally:
            sched.stop()
            t.join(timeout=30.0)
        assert not t.is_alive()
        # ONE cycle drained all four, the DELETE before the POST: `new`
        # sits where `old` sat, which a drain in any other order, or a
        # cycle for the DELETEs alone, could not have decided at once
        assert sched.tracer.span_counts["session_open"] == opens0 + 1
        assert cache.binder.binds.get("ns/new-0") == "n0"
        assert "ns/old-0" not in cache.pods
        grown = _grown(before)
        assert grown.pop("held_ms") == pytest.approx(28.0)
        assert grown == {"quiet": 1.0, "signals": 4.0, "held": 1}
        record = next(r for r in reversed(sched.tracer.recorder.records())
                      if any(s.name == "ingest_drain"
                             and s.attrs.get("events") == 4
                             for s in r.spans))
        event = next(s for s in record.spans if s.name == "park:event")
        assert event.attrs["woke_by"] == "ingest"
        assert event.attrs["signalled_ms"] == pytest.approx(28.0)
        hold, = event.children
        assert (hold.name, hold.attrs) == ("settle", {
            "q_ms": 25.0, "signals": 4, "ended_by": "quiet",
            "widest_gap_ms": pytest.approx(1.0)})


def _quiescent_ticks() -> float:
    return prom_metrics.metrics.QUIESCENT_TICKS._values.get((), 0.0)


def _rearms() -> dict:
    return {k[0]: v
            for k, v in prom_metrics.metrics.LEASE_REARMS._values.items()}


SHIPPED_CONF = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "config", "kube-batch-tpu-conf.yaml")


class TestQuiescentTick:
    """A cycle leaves nothing owed, so a floor wake that finds nothing owed
    opens no session: after a deciding cycle the idle ticks cost no cycle,
    stay one idle period apart, and leave the adaptive floor, the settle
    window and the rate floor alone; anything owed means the whole cycle."""

    def _gang(self, cache, name, cpu=500.0, members=1, queue="q0"):
        cache.add_pod_group(PodGroup(
            name=name, namespace="ns", uid=f"pg-{name}", min_member=members,
            queue=queue, creation_index=1,
        ))
        for k in range(members):
            cache.add_pod(Pod(
                name=f"{name}-{k}", namespace="ns", uid=f"u-{name}-{k}",
                requests={"cpu": cpu}, phase=PodPhase.PENDING,
                annotations={GROUP_NAME_ANNOTATION: name}, creation_index=2,
            ))

    def test_floor_wakes_after_a_deciding_cycle_open_no_session(self):
        """The real loop on one thread and a clock only its waits move
        (``PacedCondition``): a cycle costs 200 ms of it, a tick half a
        millisecond."""
        from kube_batch_tpu.guard import guard_of
        from kube_batch_tpu.serve.plane import QueryPlane
        from kube_batch_tpu.sim.clock import VirtualClock
        from tests.fixtures import PacedCondition

        cache = _mk_cache()
        qp = QueryPlane(cache, start_thread=False)
        self._gang(cache, "a")
        clock = VirtualClock(start=100.0)
        sched = Scheduler(cache, conf=load_scheduler_conf(None),
                          schedule_period=1.0, clock=clock)
        sched.pipelined = True
        body, tick = sched._cycle_body, sched._idle_tick
        cycle, log = sched.run_once_pipelined, []

        def _cycle_body(*args):
            clock.sleep(0.2)
            return body(*args)

        def _idle_tick():
            clock.sleep(0.0005)
            return tick()

        def run_once_pipelined(wake):
            log.append((wake, round(clock.monotonic(), 4), cycle(wake)))
            return log[-1][2]

        sched._cycle_body, sched._idle_tick = _cycle_body, _idle_tick
        sched.run_once_pipelined = run_once_pipelined
        # 10 ms after the third tick a gang arrives (staged: the cache's
        # signal is the trigger's notify); the loop is stopped mid-park
        PacedCondition.install(sched.trigger, [
            (103.010, lambda: self._gang(cache, "b")),
            (106.5, sched.stop),
        ])
        guard = guard_of(cache)
        opens0 = sched.tracer.span_counts.get("session_open", 0)
        ticks0, floor0 = _quiescent_ticks(), _wakes("floor")
        rearms0, guard0 = _rearms(), guard.cycle
        try:
            sched.run_forever()
        finally:
            qp.close()
        # the start-up cycle decides `a`; three ticks, one idle period
        # apart from each other and from that cycle's START, open nothing;
        # the arrival is held for its quiet gap (an eighth of 200 ms) and
        # decided at once: the floor counts from the last cycle that opened
        # a session, 3 s ago, not from the tick 10 ms ago; three more ticks
        assert log == [
            (None, 100.0, True),
            ("floor", 101.0, False), ("floor", 102.0, False),
            ("floor", 103.0, False),
            ("ingest", 103.035, True),
            ("floor", 104.035, False), ("floor", 105.035, False),
            ("floor", 106.035, False),
        ]
        assert sorted(cache.binder.binds) == ["ns/a-0", "ns/b-0"]
        counts = sched.tracer.span_counts
        assert counts["session_open"] == opens0 + 2
        assert counts["idle_tick"] == 6
        assert counts["lease_rearm"] == 2
        assert "park:floor" not in counts, "a tick restarted the rate floor"
        assert _quiescent_ticks() == ticks0 + 6
        assert _wakes("floor") == floor0 + 6, "ticks are floor wakes still"
        assert guard.cycle == guard0 + 8, "cooldowns count ticks as cycles"
        grown = {k: v - rearms0.get(k, 0.0) for k, v in _rearms().items()}
        assert grown == {"published": 2.0, "ingest_pending": 0.0,
                         "not_owed": 0.0}
        # only the two cycles fed the EWMA (200 ms each on this clock), so
        # the floor and the hold's window are a 200 ms cycle's
        assert sched.cycle_cost_ewma == pytest.approx(0.2)
        assert sched.min_period == pytest.approx(0.2)
        assert sched.settle_window() == pytest.approx((0.025, 0.1))
        # the record of the cycle that decided `b` leads with the ticks
        # and the park before it, and holds the re-arm inside its close
        record = next(r for r in sched.tracer.recorder.records()
                      if any(s.name == "ingest_drain"
                             and s.attrs.get("events") == 2
                             for s in r.spans))
        names = [s.name for s in record.spans]
        assert names[:names.index("ingest_drain")] == [
            "park:event", "idle_tick", "park:event", "idle_tick",
            "park:event"][-names.index("ingest_drain"):]
        tick_span = next(s for s in record.spans if s.name == "idle_tick")
        assert tick_span.attrs["quiescent"] is True
        close = next(s for s in record.spans if s.name == "status_derive")
        rearm = close.children[-1]
        assert (rearm.name, rearm.attrs) == (
            "lease_rearm", {"outcome": "published"})
        assert "lease_rearm" not in names, "a stage of its own"
        assert qp.broker.current().version == cache.last_close_version \
            == cache.dirty.version

    # what is planted after the deciding cycle, and what the tick's span
    # then says is owed (None: nothing, the control)
    CASES = {
        "nothing-owed": (True, None),
        "nothing-owed-no-plane": (False, None),
        "staged-event": (True, "staged"),
        "resync-item": (True, "churn"),
        "conf-file-changed": (True, "conf"),
        "pod-that-fits-nowhere": (True, "pending"),
        "pending-phase-podgroup": (True, "phase"),
        "gang-invalid-podgroup": (True, "gang_invalid"),
        "cycle-that-raised": (True, "failed_cycle"),
        "lease-owed": (True, "lease"),
        "no-plane-pending-work": (False, "pending"),
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_anything_owed_means_the_whole_cycle(self, case, tmp_path):
        from kube_batch_tpu.serve.plane import QueryPlane

        plane, owed = self.CASES[case]
        cache = _mk_cache()
        qp = QueryPlane(cache, start_thread=False) if plane else None
        conf_file = tmp_path / "conf.yaml"
        with open(SHIPPED_CONF) as f:
            conf_file.write_text(f.read())
        sched = Scheduler(cache, conf_path=str(conf_file))
        try:
            self._gang(cache, "a", members=2)
            if owed == "pending":
                self._gang(cache, "unfit", cpu=10_000_000.0)
            if owed == "gang_invalid":
                cache.add_pod_group(PodGroup(
                    name="empty", namespace="ns", uid="pg-empty",
                    min_member=1, queue="q0", creation_index=1))
            assert sched.run_once_pipelined() is True
            sched.drain_pipeline()
            assert len(cache.binder.binds) == 2
            if owed == "staged":
                cache.enable_ingest_staging()
                self._gang(cache, "late")
            elif case == "resync-item":
                cache.resync_task(cache.jobs["ns/a"].tasks["ns/a-0"])
            elif owed == "conf":
                stamp = os.path.getmtime(conf_file) + 5
                os.utime(conf_file, (stamp, stamp))
            elif owed == "phase":
                # a gang of two with one member Running and one Succeeded
                # stays valid, has nothing pending, and falls back to
                # Pending: the close reports it every cycle
                for key in ("ns/a-0", "ns/a-1"):
                    kl.set_running(cache, key, cache.pods[key].node_name)
                kl.set_succeeded(cache, "ns/a-1")
                # the shipped conf's enqueue would promote it: without it
                sched.actions = [a for a in sched.actions
                                 if a.name != "enqueue"]
                assert sched.run_once_pipelined("floor") is True  # churn
                sched.drain_pipeline()
            elif owed == "failed_cycle":
                def boom(ssn):
                    raise RuntimeError("planted: the action dies")

                sched.actions = list(sched.actions)
                real, sched.actions[0] = sched.actions[0], type(
                    "Boom", (), {"name": "boom", "execute": staticmethod(
                        boom)})()
                with pytest.raises(RuntimeError):
                    sched.run_once_pipelined()
                sched.actions[0] = real
                sched.drain_pipeline()
            elif owed == "lease":
                qp.broker.retire()
            opens0 = sched.tracer.span_counts["session_open"]
            ticks0 = _quiescent_ticks()
            opened = sched.run_once_pipelined("floor")
            sched.drain_pipeline()
            span = sched.tracer._preceding[-1] if owed is None else next(
                s for s in sched.tracer.recorder.last_record().spans
                if s.name == "idle_tick" and not s.attrs["quiescent"])
            assert span.name == "idle_tick"
            assert span.attrs.get("owed") == owed
            assert opened is (owed is not None)
            assert sched.tracer.span_counts["session_open"] == \
                opens0 + (owed is not None)
            assert _quiescent_ticks() == ticks0 + (owed is None)
            if owed in ("staged", "churn", "conf", "failed_cycle", "lease"):
                # what was owed is settled: the next tick is quiescent
                assert sched.run_once_pipelined("floor") is False
            assert cache.columns.check_consistency(cache) == []
        finally:
            sched.close()
            if qp is not None:
                qp.close()


def _egress(cache) -> dict:
    """What left the cache: bind dispatches, status writes and pod
    conditions in order; events as a multiset (the binder's dispatch pool
    and the writeback worker both append, in no fixed order)."""
    return {
        "binds": list(cache.binder.channel),
        "pod_groups": [(pg.namespace, pg.name, pg.phase, pg.running,
                        pg.failed, pg.succeeded, len(pg.conditions))
                       for pg in cache.status_updater.pod_groups],
        "pod_conditions": list(cache.status_updater.pod_conditions),
        "events": sorted(cache.events),
        "evicts": list(cache.evictor.evicts),
    }


class TestQuiescentPremise:
    """The premise the quiescent tick rests on: the cycle it stands in for
    would have decided nothing and written nothing.  Two caches take the
    same randomized churn; after every deciding cycle one gets N floor
    wakes, the other N whole cycles.  They end in the same binds, pod and
    PodGroup statuses, conditions, queue writes and egress, and every whole
    cycle that stood where a tick was quiescent staged an empty flush."""

    TICKS = 2

    @pytest.mark.parametrize("seed, shipped, plane", [
        (0, False, False), (11, True, True), (42, True, False),
        (7, False, True),
    ])
    def test_ticks_and_whole_cycles_end_in_the_same_state(
            self, seed, shipped, plane):
        from kube_batch_tpu.serve.plane import QueryPlane

        sides = []
        for _ in range(2):
            cache = _mk_cache()
            sides.append((
                cache,
                Scheduler(cache, conf=load_scheduler_conf(
                    SHIPPED_CONF if shipped else None)),
                _Churner(cache, seed),
                QueryPlane(cache, start_thread=False) if plane else None,
            ))
        (c_tick, s_tick, ch_tick, _), (c_full, s_full, ch_full, _) = sides
        flushes = []
        stage = c_full.stage_status_flush

        def stage_status_flush(*args):
            flushes.append(stage(*args))
            return flushes[-1]

        c_full.stage_status_flush = stage_status_flush
        quiescent = full_instead = 0
        try:
            for ch in (ch_tick, ch_full):
                for _ in range(3):
                    ch.add_gang()
            for cycle in range(12):
                for _, sched, ch, _ in sides:
                    ch.step()
                    sched.run_once_pipelined()
                    sched.drain_pipeline()
                for _ in range(self.TICKS):
                    opened = s_tick.run_once_pipelined("floor")
                    s_tick.drain_pipeline()
                    del flushes[:]
                    s_full.run_once_pipelined()
                    s_full.drain_pipeline()
                    flush, = flushes
                    if opened:
                        full_instead += 1
                        continue
                    quiescent += 1
                    wrote = (flush.to_write, flush.ops, flush.qwrites,
                             flush.shed_queues)
                    assert wrote == ([], [], [], 0), (
                        f"seed={seed} cycle={cycle}: a whole cycle in a "
                        f"quiescent tick's place wrote {wrote}")
                want, got = _observable_state(c_full), _observable_state(
                    c_tick)
                for field in want:
                    assert got[field] == want[field], (
                        f"seed={seed} cycle={cycle}: {field} diverged")
                assert _egress(c_tick) == _egress(c_full)
            # the churn met both kinds of tick
            assert quiescent >= 6 and quiescent + full_instead == 24
            for cache, *_ in sides:
                assert cache.columns.check_consistency(cache) == []
        finally:
            for _, sched, _, qp in sides:
                sched.close()
                if qp is not None:
                    qp.close()


class TestBudgetShedOverlappedClose:
    def test_stage_captures_degraded_verdict(self):
        """The degraded verdict is taken at STAGE time (while the budget
        shed flag is visible on the cycle thread), not at writeback time —
        the overlapped flush sheds even though the flag has been reset by
        the time the worker runs."""
        cache = _mk_cache()
        ch = _Churner(cache, 3)
        ch.add_gang()
        sched = _mk_scheduler(cache)
        sched.run_once()  # settle: podgroups now have derived statuses
        ch.add_gang()
        ssn = open_session(cache, sched.conf.tiers)
        ssn.action_names = [a.name for a in sched.actions]
        for action in sched.actions:
            action.execute(ssn)
        cache.shed_status_writes = True
        try:
            flush = close_session(ssn, stage_flush=True)
        finally:
            cache.shed_status_writes = False
        assert flush is not None and flush.degraded, (
            "stage_status_flush must capture the shed verdict at stage time"
        )
        wrote_before = len(cache.status_updater.pod_groups)
        shed_before = STATUS_WRITES_SHED._values.get((), 0)
        cache.run_status_flush(flush)
        cache.flush_binds()
        assert len(cache.status_updater.pod_groups) == wrote_before, (
            "a degraded flush must shed the podgroup writes"
        )
        if flush.to_write:
            assert STATUS_WRITES_SHED._values.get((), 0) > \
                shed_before

    def test_statusflush_is_value_snapshotted(self):
        """The handoff carries CLONES: mutating the live PodGroup after
        staging must not change what the writeback writes."""
        cache = _mk_cache()
        ch = _Churner(cache, 9)
        ch.add_gang()
        sched = _mk_scheduler(cache)
        ssn = open_session(cache, sched.conf.tiers)
        ssn.action_names = [a.name for a in sched.actions]
        for action in sched.actions:
            action.execute(ssn)
        flush = close_session(ssn, stage_flush=True)
        assert isinstance(flush, StatusFlush)
        live = {id(j.pod_group) for j in cache.jobs.values()
                if j.pod_group is not None}
        for pg in flush.to_write:
            assert id(pg) not in live, (
                "staged podgroup writes must be clones, not live objects"
            )
        cache.run_status_flush(flush)
        cache.flush_binds()


class TestWritebackRobustness:
    def test_failed_cycle_still_flushes_staged_writeback(self):
        """A cycle that dies in an action has ALREADY staged its flush (and
        recorded its queue deltas as written) — the handoff must still
        reach the writeback stage, or those deltas are suppressed until the
        counts next change."""
        cache = _mk_cache()
        ch = _Churner(cache, 7)
        ch.add_gang()
        sched = _mk_scheduler(cache)
        sched.run_once_pipelined()
        sched.drain_pipeline()

        class Boom:
            name = "boom"

            def execute(self, ssn):
                raise RuntimeError("injected action failure")

        ch.add_gang()  # fresh queue counts for the failing cycle to derive
        sched.actions = sched.actions + [Boom()]
        try:
            with pytest.raises(RuntimeError):
                sched.run_once_pipelined()
        finally:
            sched.actions = sched.actions[:-1]
        sched.drain_pipeline()
        # the invariant: every queue delta recorded as written at stage
        # time was actually written by the overlapped flush
        assert cache.status_updater.queue_statuses == \
            cache._queue_status_written

    def test_one_failing_podgroup_write_does_not_abort_queue_writes(self):
        """A single updater exception in the pod-group write loop must not
        skip the remaining writes or the queue deltas the stage already
        recorded as written."""
        cache = _mk_cache()
        fails = {"n": 1}
        real = cache.status_updater.update_pod_group

        def flaky(pg):
            if fails["n"]:
                fails["n"] -= 1
                raise OSError("transient apiserver error")
            real(pg)

        cache.status_updater.update_pod_group = flaky
        ch = _Churner(cache, 13)
        ch.add_gang()
        ch.add_gang()
        sched = _mk_scheduler(cache)
        sched.run_once_pipelined()
        sched.drain_pipeline()
        assert fails["n"] == 0, "the flaky write fired"
        assert cache.status_updater.queue_statuses == \
            cache._queue_status_written


class TestCloseEdgeCases:
    def test_empty_session_close_stages_queue_writes(self):
        """A pipelined cycle with no jobs (the idle tick) takes the
        non-columnar close branch — its queue zero-outs must still cross
        the staged handoff, not write inline while the previous cycle's
        writeback worker may be running."""
        cache = _mk_cache()
        ch = _Churner(cache, 21)
        ch.add_gang()
        sched = _mk_scheduler(cache)
        sched.run_once_pipelined()
        sched.drain_pipeline()
        ch.complete_gang()  # empty cluster: next close zero-outs the queue
        ssn = open_session(cache, sched.conf.tiers)
        ssn.action_names = [a.name for a in sched.actions]
        for action in sched.actions:
            action.execute(ssn)
        writes_before = dict(cache.status_updater.queue_statuses)
        flush = close_session(ssn, stage_flush=True)
        assert flush is not None, "empty close must stage, not write inline"
        assert cache.status_updater.queue_statuses == writes_before, (
            "the close wrote inline instead of staging"
        )
        cache.run_status_flush(flush)
        cache.flush_binds()
        assert cache.status_updater.queue_statuses == \
            cache._queue_status_written

    def test_close_failure_after_staging_still_flushes(self):
        """end_exclusive_session raising AFTER the stage must not drop the
        flush — the scheduler recovers it from the session stash."""
        cache = _mk_cache()
        ch = _Churner(cache, 29)
        ch.add_gang()
        sched = _mk_scheduler(cache)
        sched.run_once_pipelined()
        sched.drain_pipeline()
        ch.add_gang()
        real_end = cache.end_exclusive_session
        fired = {"n": 0}

        def flaky_end():
            real_end()  # cache stays sane; the failure is after the work
            if fired["n"] == 0:
                fired["n"] = 1
                raise RuntimeError("injected close failure")

        cache.end_exclusive_session = flaky_end
        try:
            with pytest.raises(RuntimeError):
                sched.run_once_pipelined()
        finally:
            cache.end_exclusive_session = real_end
        sched.drain_pipeline()
        assert fired["n"] == 1
        assert cache.status_updater.queue_statuses == \
            cache._queue_status_written


class TestInflightBindGuard:
    def test_update_pod_keeps_unacked_dispatch(self):
        """A client update landing between the bind dispatch and its ack
        must keep the dispatched placement — the pipelined loop widens that
        window to a whole stage."""
        cache = _mk_cache(n_nodes=1)
        pod = Pod(name="w0", namespace="ns", uid="uw0",
                  requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                  creation_index=1)
        cache.add_pod(pod)
        cache._inflight_bind_hosts["ns/w0"] = "n0"
        update = Pod(name="w0", namespace="ns", uid="uw0",
                     requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                     creation_index=1)
        cache.update_pod(update)
        assert cache.pods["ns/w0"].node_name == "n0", (
            "unacked async bind clobbered by a stale client update"
        )

    def test_failed_dispatch_rolls_back_optimistic_stamp(self):
        cache = _mk_cache(n_nodes=1)
        pod = Pod(name="w1", namespace="ns", uid="uw1",
                  requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                  creation_index=1)
        cache.add_pod(pod)
        cache._inflight_bind_hosts["ns/w1"] = "n0"
        update = Pod(name="w1", namespace="ns", uid="uw1",
                     requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                     creation_index=1)
        cache.update_pod(update)  # copies the in-flight placement
        stored = cache.pods["ns/w1"]
        assert stored.node_name == "n0"
        # the dispatch FAILS: the optimistic stamp on the replacement pod
        # object must roll back (the apiserver never bound it)
        cache._settle_inflight([("ns/w1", pod, "n0")], bound=False)
        assert cache.pods["ns/w1"].node_name is None
        assert "ns/w1" not in cache._inflight_bind_hosts
        # the failed pod's latency clock re-arms (the repair re-decision
        # must produce a sample) ...
        assert "ns/w1" in cache._arrival_ts

    def test_failed_settle_for_deleted_pod_leaks_no_clock(self):
        # ... but a pod DELETED while its dispatch was in flight must not
        # plant a never-popped arrival entry
        cache = _mk_cache(n_nodes=1)
        pod = Pod(name="w2", namespace="ns", uid="uw2",
                  requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                  creation_index=1)
        cache.add_pod(pod)
        cache._inflight_bind_hosts["ns/w2"] = "n0"
        cache.delete_pod(pod)
        cache._settle_inflight([("ns/w2", pod, "n0")], bound=False)
        assert "ns/w2" not in cache._arrival_ts


class TestDecisionLatency:
    def test_bind_decision_observes_latency(self):
        sink = []
        prom_metrics.set_decision_latency_sink(sink)
        try:
            cache = _mk_cache()
            ch = _Churner(cache, 1)
            ch.add_gang()
            sched = _mk_scheduler(cache)
            sched.run_once()
        finally:
            prom_metrics.set_decision_latency_sink(None)
        assert sink, "bind decisions must observe arrival→decision latency"
        assert all(ms >= 0.0 for ms in sink)

    def test_latency_clock_survives_status_replays(self):
        """Kubelet status updates on a still-pending pod must not reset the
        arrival stamp (the clock starts at FIRST ingest)."""
        cache = _mk_cache()
        pod = Pod(name="l0", namespace="ns", uid="ul0",
                  requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                  creation_index=1)
        cache.add_pod(pod)
        t0 = cache._arrival_ts["ns/l0"]
        update = Pod(name="l0", namespace="ns", uid="ul0",
                     requests={"cpu": 100.0}, phase=PodPhase.PENDING,
                     creation_index=1)
        cache.update_pod(update)
        assert cache._arrival_ts["ns/l0"] == t0


class TestPipelinedSim:
    def test_event_trigger_beats_fixed_tick_p99(self):
        """Virtual-time evidence for the acceptance bar: on a trigger-bound
        workload the event-driven loop's arrival→decision p99 beats the
        fixed 1 s tick by ≥ 2× (it is bounded by min_period, not the
        period), with zero duplicate binds and the same jobs completed."""
        from kube_batch_tpu.sim.runner import run_preset

        serial = run_preset("smoke", seed=3)
        pipe = run_preset("smoke", seed=3, pipelined=True)
        assert pipe["bind_integrity"]["duplicate_binds"] == 0
        assert pipe["invariants"]["errors"] == []
        assert pipe["jobs"] == serial["jobs"]
        p99_serial = serial["pod_bind_latency_vt"]["p99"]
        p99_pipe = pipe["pod_bind_latency_vt"]["p99"]
        assert p99_pipe * 2 <= p99_serial, (
            f"pipelined p99 {p99_pipe} not ≥2× better than serial "
            f"{p99_serial}"
        )

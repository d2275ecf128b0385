"""Cycle tracing plane (kube_batch_tpu/obs): span semantics, Chrome
export validity, the flight recorder's anomaly windows, trace-on vs
trace-off decision bit-exactness over randomized churn, the pipelined
writeback overlap rendered as overlapping spans, the span-stamped
arrival→decision latencies and their two parts, the guard trip-rate alert
evaluator, and the span plane on the profiler's clock: parked-time spans,
per-name totals, the compile listener, the read plane's detached flush
tree, and the span names found on a jax.profiler trace's host plane."""

from __future__ import annotations

import json
import statistics
import threading
import time
import urllib.request

import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import metrics as prom_metrics
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.pod import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    Queue,
)
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.cache.fake import FakeBinder, FakeEvictor, FakeStatusUpdater
from kube_batch_tpu.framework.conf import load_scheduler_conf
from kube_batch_tpu.obs.alerts import AlertEvaluator
from kube_batch_tpu.obs.recorder import FlightRecorder
from kube_batch_tpu.obs.trace import (
    Tracer,
    chrome_trace,
    tracer_of,
    validate_chrome_trace,
)
from kube_batch_tpu.metrics import metrics as prom
from kube_batch_tpu.scheduler import CycleTrigger, Scheduler
from kube_batch_tpu.utils import telemetry
from kube_batch_tpu.sim import kubelet as kl
from kube_batch_tpu.testing.synthetic import GiB


def _mk_cache(n_nodes=4, n_queues=2):
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


def _add_gang(cache, serial, size=2, n_queues=2):
    g = f"g{serial}"
    cache.add_pod_group(PodGroup(
        name=g, namespace="tr", uid=f"pg-{g}", min_member=size,
        queue=f"q{serial % n_queues}", creation_index=serial,
    ))
    for k in range(size):
        cache.add_pod(Pod(
            name=f"{g}-{k}", namespace="tr", uid=f"pod-{g}-{k}",
            requests={"cpu": 500.0, "memory": 1 * GiB},
            annotations={GROUP_NAME_ANNOTATION: g},
            phase=PodPhase.PENDING,
            creation_index=serial * 100 + k,
        ))


class _Churner:
    """Seed-deterministic churn through the real ingest surface (the
    test_pipeline idiom) — applied identically to both caches."""

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
            name=g, namespace="tr", uid=f"pg-{g}", min_member=size,
            queue=f"q{int(self.rng.integers(self.n_queues))}",
            creation_index=self.serial,
        ))
        for k in range(size):
            self.cache.add_pod(Pod(
                name=f"{g}-{k}", namespace="tr", uid=f"pod-{g}-{k}",
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
        job_uid = f"tr/{g}"
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


# ---------------------------------------------------------------------------
# span mechanics
# ---------------------------------------------------------------------------


class TestSpans:
    def _tracer(self, tmp_path, **kw):
        return _tracer(tmp_path, **kw)

    def test_nesting_builds_a_tree(self, tmp_path):
        tr, rec = self._tracer(tmp_path)
        tr.begin_cycle("test")
        with tr.span("outer"):
            with tr.span("inner_a"):
                pass
            with tr.span("inner_b") as sp:
                sp.set(k=1)
        tr.end_cycle()
        records = rec.records()
        assert len(records) == 1
        spans = records[0].spans
        assert [s.name for s in spans] == ["outer"]
        assert [c.name for c in spans[0].children] == ["inner_a", "inner_b"]
        assert spans[0].children[1].attrs == {"k": 1}
        assert spans[0].t1 >= spans[0].children[1].t1

    def test_exception_closes_the_span(self, tmp_path):
        tr, rec = self._tracer(tmp_path)
        tr.begin_cycle("test")
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        tr.end_cycle()
        sp = rec.records()[0].spans[0]
        assert sp.t1 >= sp.t0
        assert sp.attrs["error"] == "RuntimeError"
        # the per-thread stack unwound — a follow-up span is a root again
        tr.begin_cycle("test2")
        with tr.span("after"):
            pass
        tr.end_cycle()
        assert [s.name for s in rec.records()[1].spans] == ["after"]

    def test_disabled_tracer_still_times_but_retains_nothing(self, tmp_path):
        rec = FlightRecorder(ring=4, directory=str(tmp_path))
        tr = Tracer(recorder=rec, enabled=False)
        tr.begin_cycle("test")
        with tr.span("stage") as sp:
            time.sleep(0.002)
        tr.end_cycle()
        assert sp.dur_ms > 0, "spans always stamp (metrics feed from them)"
        assert rec.records() == []
        assert tr.spans_total == 0

    def test_implicit_record_rolls_over(self, tmp_path):
        from kube_batch_tpu.obs.trace import IMPLICIT_ROLL

        tr, rec = self._tracer(tmp_path)
        for _ in range(IMPLICIT_ROLL + 5):
            with tr.span("direct"):
                pass
        assert rec.records(), "direct-driven spans must reach the ring"
        assert rec.records()[0].reason == "implicit"

    def test_virtual_time_stamps_follow_the_injected_clock(self, tmp_path):
        from kube_batch_tpu.sim.clock import VirtualClock

        clock = VirtualClock(start=7.0)
        tr, rec = self._tracer(tmp_path, clock=clock)
        tr.begin_cycle("vt")
        with tr.span("stage") as sp:
            clock.sleep(2.5)
        tr.end_cycle()
        assert sp.vt0 == 7.0 and sp.vt1 == 9.5
        assert rec.records()[0].vt0 == 7.0


# ---------------------------------------------------------------------------
# chrome export + validation
# ---------------------------------------------------------------------------


class TestChromeExport:
    def test_real_cycles_export_validates(self):
        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        for s in range(1, 4):
            _add_gang(cache, s)
            sched.run_once()
        doc = chrome_trace(cache.flight_recorder.records())
        assert validate_chrome_trace(doc) == []
        names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
        assert {"session_open", "status_derive", "action:allocate",
                "solve_dispatch"} <= names
        cache.stop()

    def test_validator_rejects_unbalanced_and_negative(self):
        bad = {"traceEvents": [
            {"name": "outer", "ph": "X", "ts": 0.0, "dur": 10.0,
             "pid": 1, "tid": 1},
            {"name": "child-too-long", "ph": "X", "ts": 5.0, "dur": 50.0,
             "pid": 1, "tid": 1},
        ]}
        assert validate_chrome_trace(bad), "nesting violation must report"
        neg = {"traceEvents": [
            {"name": "n", "ph": "X", "ts": 0.0, "dur": -1.0,
             "pid": 1, "tid": 1},
        ]}
        assert validate_chrome_trace(neg)
        assert validate_chrome_trace({"traceEvents": []})


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def _record(self, tr):
        tr.begin_cycle("t")
        with tr.span("s"):
            pass
        tr.end_cycle()

    def test_dump_captures_cycles_before_and_after(self, tmp_path):
        rec = FlightRecorder(ring=8, directory=str(tmp_path), post_cycles=2)
        tr = Tracer(recorder=rec, enabled=True)
        for _ in range(5):
            self._record(tr)
        rec.trigger("test_anomaly", detail="planted")
        assert rec.dumps == [], "dump waits out the post-trigger window"
        for _ in range(2):
            self._record(tr)
        assert len(rec.dumps) == 1
        meta = json.loads(
            (tmp_path / "flight-test_anomaly-0000" / "meta.json").read_text()
        )
        assert meta["reason"] == "test_anomaly"
        assert meta["cycles_before"] == 5
        assert meta["cycles_after"] == 2
        doc = json.loads(
            (tmp_path / "flight-test_anomaly-0000" / "trace.json").read_text()
        )
        assert validate_chrome_trace(doc) == []
        # atomic publish: no temp residue next to the dump
        assert not [p for p in tmp_path.iterdir()
                    if p.name.startswith(".tmp-")]

    def test_flush_publishes_armed_captures(self, tmp_path):
        rec = FlightRecorder(ring=8, directory=str(tmp_path), post_cycles=10)
        tr = Tracer(recorder=rec, enabled=True)
        self._record(tr)
        rec.trigger("end_of_run")
        assert rec.dumps == []
        out = rec.flush()
        assert len(out) == 1 and rec.dumps == out

    def test_ring_is_bounded(self, tmp_path):
        rec = FlightRecorder(ring=4, directory=str(tmp_path))
        tr = Tracer(recorder=rec, enabled=True)
        for _ in range(10):
            self._record(tr)
        stats = rec.stats()
        assert stats["cycles_resident"] == 4
        assert stats["cycles_recorded"] == 10

    def test_budget_shed_triggers_a_dump(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KB_CYCLE_BUDGET", "0.000001")
        monkeypatch.setenv("KB_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("KB_TRACE_POST", "1")
        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        sched.run_once_pipelined()  # overruns the 1µs budget → shed
        sched.run_once_pipelined()  # the post-trigger cycle
        sched.drain_pipeline()
        assert cache.flight_recorder.dumps, "shed must arm a flight dump"
        reasons = [t["reason"] for t in cache.flight_recorder.triggers]
        assert "budget_shed" in reasons
        cache.stop()


# ---------------------------------------------------------------------------
# inertness: trace on vs off — bit-identical decisions
# ---------------------------------------------------------------------------


class TestTraceInert:
    @pytest.mark.parametrize("seed", [3, 17])
    def test_trace_on_vs_off_decisions_identical(self, seed, monkeypatch):
        """Tracing must be provably inert: the same churn stream under
        KB_TRACE=1 and KB_TRACE=0 produces identical binds, statuses,
        conditions, and queue writebacks (serial and pipelined bodies)."""
        from kube_batch_tpu.serve.plane import QueryPlane

        monkeypatch.setenv("KB_TRACE", "0")
        c_off = _mk_cache()
        s_off = _mk_scheduler(c_off)
        assert not c_off.tracer.enabled
        monkeypatch.setenv("KB_TRACE", "1")
        c_on = _mk_cache()
        s_on = _mk_scheduler(c_on)
        assert c_on.tracer.enabled
        # a read plane on both: cycle 4 carries a what-if flush, whose span
        # tree (traced side only) must change no answer and no decision
        planes = [QueryPlane(c, start_thread=False) for c in (c_off, c_on)]
        whatif = {"queue": "q0", "count": 2,
                  "requests": {"cpu": 500, "memory": GiB}}
        ch_off, ch_on = _Churner(c_off, seed), _Churner(c_on, seed)
        for _ in range(3):
            ch_off.add_gang()
            ch_on.add_gang()
        for cycle in range(8):
            ch_off.step()
            ch_on.step()
            if cycle == 4:
                answers = []
                for qp in planes:
                    fut = qp.submit(dict(whatif))
                    qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1e6)
                    answers.append(fut.result(timeout=120))
                assert answers[0] == answers[1]
            if cycle % 2:
                s_off.run_once()
                s_on.run_once()
            else:
                s_off.run_once_pipelined()
                s_off.drain_pipeline()
                s_on.run_once_pipelined()
                s_on.drain_pipeline()
        assert _observable_state(c_on) == _observable_state(c_off)
        # and the traced side actually traced
        assert c_on.tracer.cycles_total >= 8
        assert c_on.tracer.spans_total > 0
        assert c_on.tracer.span_counts["whatif:flush"] == 1
        assert "whatif:flush" not in c_off.tracer.span_counts
        for qp in planes:
            qp.close()
        c_off.stop()
        c_on.stop()


# ---------------------------------------------------------------------------
# the pipelined overlap, visible in the trace
# ---------------------------------------------------------------------------


class TestPipelinedOverlap:
    def test_writeback_span_overlaps_next_cycle_compute(self, monkeypatch):
        """Cycle N's writeback span (its own worker-thread track) must
        overlap cycle N+1's session_open span in wall time — the exported
        trace renders the pipeline's overlap structure directly."""
        import kube_batch_tpu.scheduler as scheduler_mod

        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        sched.run_once_pipelined()  # warm compile out of the way
        orig_flush = cache.flush_binds
        # a handshake inside the two spans: cycle N's flush (inside its
        # writeback span) is held until cycle N+1 is inside session_open,
        # and that open does not return before the flush has started — the
        # overlap is made, not hoped for from a sleep that a loaded host
        # outlasts (or that ends before the worker thread is scheduled)
        flushing, opened = threading.Event(), threading.Event()
        real_open = scheduler_mod.open_session

        def held_flush():
            flushing.set()
            assert opened.wait(timeout=60), "the next cycle never opened"
            return orig_flush()

        def open_and_release(*args, **kw):
            ssn = real_open(*args, **kw)
            assert flushing.wait(timeout=60), "the writeback never started"
            opened.set()
            return ssn

        cache.flush_binds = held_flush
        _add_gang(cache, 2)
        sched.run_once_pipelined()   # cycle N: hands writeback to worker
        # hooked only now: cycle N's own open must not release its flush
        monkeypatch.setattr(scheduler_mod, "open_session", open_and_release)
        _add_gang(cache, 3)
        sched.run_once_pipelined()   # cycle N+1 computes under N's egress
        sched.drain_pipeline()
        records = cache.flight_recorder.records()
        wb = None
        nxt_open = None
        for i, rec in enumerate(records):
            wb_spans = [s for s in rec.spans if s.name == "writeback"]
            if wb_spans and i + 1 < len(records):
                opens = [s for s in records[i + 1].spans
                         if s.name == "session_open"]
                if opens:
                    wb, nxt_open = wb_spans[-1], opens[0]
                    if wb.t0 < nxt_open.t1 and nxt_open.t0 < wb.t1:
                        break
        assert wb is not None and nxt_open is not None
        assert wb.t0 < nxt_open.t1 and nxt_open.t0 < wb.t1, (
            "writeback must overlap the next cycle's compute"
        )
        assert wb.tid != nxt_open.tid, "writeback rides its own thread track"
        # and the chrome export of exactly this structure validates
        assert validate_chrome_trace(chrome_trace(records)) == []
        cache.stop()


# ---------------------------------------------------------------------------
# span-stamped arrival→decision latencies (satellite: latency-sink tests)
# ---------------------------------------------------------------------------


def _assert_one_cycle_kept_the_summary_of(cache, samples, deciding=1):
    """A record keeps no list of every pod's latency: the one cycle that
    decided ``samples`` (the last of ``deciding`` that decided anything)
    carries what the table of cycles reads (how many, the worst, the
    median, the 16 largest), equal to the histogram sink's samples."""
    tr = cache.tracer
    with tr._mu:
        assert tr.current is None  # finalized: the summary is made then
    decided = [rec.decisions for rec in cache.flight_recorder.records()
               if rec.decisions is not None]
    assert len(decided) == deciding
    decisions = decided[-1]
    largest = sorted((round(v, 3) for v in samples), reverse=True)
    assert decisions["decided"] == len(samples)
    assert decisions["worst_ms"] == largest[0]
    assert decisions["median_ms"] == pytest.approx(
        statistics.median(samples), abs=1e-3)
    assert decisions["top_ms"] == largest[:16]
    for rec in cache.flight_recorder.records():
        assert "decision_lat_ms" not in rec.attrs and rec._lat == []


class TestDecisionLatencySink:
    def test_direct_path_sink_and_spans_agree(self):
        """Direct (unstaged) ingest: every histogram/sink sample has a
        span-stamped twin on the cycle's trace record."""
        sink = []
        prom_metrics.set_decision_latency_sink(sink)
        try:
            cache = _mk_cache()
            sched = _mk_scheduler(cache)
            _add_gang(cache, 1)
            _add_gang(cache, 2)
            sched.run_once()
        finally:
            prom_metrics.set_decision_latency_sink(None)
        assert len(sink) == 4, "both 2-gangs decided"
        _assert_one_cycle_kept_the_summary_of(cache, sink)
        cache.stop()

    def test_staged_path_sink_and_spans_agree(self):
        """Staged ingest (the pipelined mode's path): the sink drains the
        same samples, and the stage-time arrival clock means the latency
        covers the stage→drain wait; span stamps match exactly."""
        sink = []
        prom_metrics.set_decision_latency_sink(sink)
        try:
            cache = _mk_cache()
            sched = _mk_scheduler(cache)
            cache.enable_ingest_staging()
            _add_gang(cache, 1)           # staged, not applied
            assert "tr/g1-0" in cache._arrival_ts
            time.sleep(0.01)              # a real stage→drain wait
            sched.run_once_pipelined()
            sched.drain_pipeline()
        finally:
            prom_metrics.set_decision_latency_sink(None)
            cache.disable_ingest_staging()
        assert len(sink) == 2
        assert min(sink) * 1.0 >= 10.0, (
            "stage-time clock must cover the stage→drain wait"
        )
        _assert_one_cycle_kept_the_summary_of(cache, sink)
        cache.stop()

    def test_slo_breach_arms_a_flight_dump(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KB_TRACE_SLO_MS", "0.000001")
        monkeypatch.setenv("KB_TRACE_DIR", str(tmp_path))
        # the breach fires MID-cycle (at the bind decision), so the
        # triggering cycle itself is the first post-trigger capture
        monkeypatch.setenv("KB_TRACE_POST", "1")
        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        sched.run_once()
        reasons = [t["reason"] for t in cache.flight_recorder.triggers]
        assert "slo_breach" in reasons
        assert cache.flight_recorder.dumps
        cache.stop()


# ---------------------------------------------------------------------------
# guard trip-rate alerting (obs/alerts)
# ---------------------------------------------------------------------------


class TestAlerts:
    def _plane(self):
        from kube_batch_tpu.guard.plane import GuardPlane

        return GuardPlane(enabled=True, audit_every=0, cooldown=4)

    def test_threshold_fires_and_resolves(self):
        gp = self._plane()
        ev = AlertEvaluator(threshold=2, window=4)
        gp.trip("allocate", ["topk"], reason="invariant", detail="t1")
        gp.end_cycle()
        fire = ev.evaluate(gp)
        assert fire.get("guard_trips") is False, "one trip under threshold"
        gp.trip("allocate", ["topk"], reason="invariant", detail="t2")
        gp.end_cycle()
        fire = ev.evaluate(gp)
        assert fire["guard_trips"] is True
        assert fire["guard_trips:topk"] is True
        assert ev.state()["alerts"]["guard_trips"]["fired_total"] == 1
        # the window slides past both trips → the alert resolves
        for _ in range(6):
            gp.end_cycle()
        fire = ev.evaluate(gp)
        assert fire["guard_trips"] is False
        assert ev.state()["alerts"]["guard_trips"]["fired_total"] == 1

    def test_gauge_follows_firing_state(self):
        from kube_batch_tpu.metrics.metrics import ALERTS_FIRING

        gp = self._plane()
        ev = AlertEvaluator(threshold=1, window=8)
        gp.trip("reclaim", ["shard_map"], reason="audit", detail="x")
        gp.end_cycle()
        ev.evaluate(gp)
        assert ALERTS_FIRING._values[("guard_trips",)] == 1.0
        assert ALERTS_FIRING._values[("guard_trips:shard_map",)] == 1.0

    def test_scheduler_cycle_evaluates_alerts(self, monkeypatch):
        """The L1 loop evaluates alerts on the guard's cycle clock — a
        corruption-style trip surfaces at /v1/alerts with no extra
        wiring."""
        monkeypatch.setenv("KB_ALERT_GUARD_TRIPS", "1")
        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        sched.run_once()  # attaches the guard plane via the dispatch
        gp = cache.guard_plane
        gp.trip("allocate", ["topk"], reason="invariant", detail="planted")
        sched.run_once()
        st = cache.alert_evaluator.state()
        assert st["alerts"]["guard_trips"]["firing"] is True
        cache.stop()


# ---------------------------------------------------------------------------
# the span plane's own counters: parked time, per-name totals, compiles,
# the read plane's detached tree, a decision's two parts
# ---------------------------------------------------------------------------


class _TickClock:
    """``telemetry.perf_counter`` stand-in: every read is one second after
    the last, so a span's duration is the number of reads inside it."""

    def __init__(self, start=0.0, step=1.0):
        self.t, self.step = start, step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def _counter(metric, *labels) -> float:
    return metric._values.get(labels, 0.0)


def _tracer(tmp_path, **kw):
    rec = FlightRecorder(ring=16, directory=str(tmp_path), post_cycles=0)
    return Tracer(recorder=rec, enabled=True, **kw), rec


class TestParkSpans:
    def test_park_spans_carry_woke_by_and_start_no_record(self, tmp_path):
        from kube_batch_tpu.sim.clock import VirtualClock

        clock = VirtualClock(start=100.0)
        tr, rec = _tracer(tmp_path)
        trig = CycleTrigger(clock=clock, tracer=tr)
        floors0 = prom.STAGE_LATENCY._count[("park:floor",)]
        events0 = prom.STAGE_LATENCY._count[("park:event",)]
        trig.notify()  # pending from t=100, consumed after the 50 ms floor
        assert trig.wait_for_work(100.0, 0.05, 1.0) == "ingest"
        # the idle tick: nothing pending and the period over -> "floor"
        assert trig.wait_for_work(100.0, 0.0, 0.05) == "floor"
        # root spans of the loop thread: on the stage histogram, counted ...
        assert prom.STAGE_LATENCY._count[("park:floor",)] == floors0 + 1
        assert prom.STAGE_LATENCY._count[("park:event",)] == events0 + 2
        assert tr.span_counts == {"park:floor": 1, "park:event": 2,
                                  "between": 2}
        # ... and no record of their own: no implicit one, nothing ringed
        assert tr.current is None and rec.records() == []
        assert tr.cycles_total == 0
        record = tr.begin_cycle("pipelined")
        with tr.span("session_open"):
            pass
        tr.end_cycle()
        # they lead the record of the cycle they precede
        assert [s.name for s in record.spans] == [
            "park:floor", "park:event", "park:event", "session_open"]
        floor, woken, idle = record.spans[:3]
        assert floor.attrs == {"before_cycle": record.cycle,
                               "woke_by": "ingest"}
        assert woken.attrs == {"before_cycle": record.cycle,
                               "woke_by": "ingest", "signalled_ms": 50.0}
        assert idle.attrs["woke_by"] == "floor"
        assert idle.attrs["signalled_ms"] == 0.0
        assert len(rec.records()) == 1

    def test_settle_is_a_child_of_park_event_and_inside_its_stage_sum(
            self, tmp_path, monkeypatch):
        """The trigger's settle hold is time the loop stays parked: its
        span sits INSIDE ``park:event``, so the stage sum of that root
        (what ``loop_accounted_ms_per_s`` adds up, root by root) contains
        the hold, no new root appears beside it, and ``/v1/trace`` totals
        the hold under its own name like any child."""
        from kube_batch_tpu.sim.clock import VirtualClock
        from tests.fixtures import PacedCondition

        # every stamp one second after the last: park:event is entered
        # (1), settle entered (2) and left (3), park:event left (4)
        monkeypatch.setattr(telemetry, "perf_counter", _TickClock())
        tr, _ = _tracer(tmp_path)
        trig = CycleTrigger(clock=VirtualClock(start=100.0), tracer=tr)
        PacedCondition.install(trig, [(100.003, {})])
        stages0 = dict(prom.STAGE_LATENCY._count)
        parked0 = prom.STAGE_LATENCY._sum[("park:event",)]
        trig.notify()
        assert trig.wait_for_work(100.0, 0.0, 5.0, (0.010, 0.050)) == "ingest"
        state = tr.state()
        assert state["span_counts"] == {"settle": 1, "park:event": 1}
        assert state["span_ms"] == {"settle": 1000.0, "park:event": 3000.0}
        grown = {k: n - stages0.get(k, 0)
                 for k, n in prom.STAGE_LATENCY._count.items()
                 if n != stages0.get(k, 0)}
        assert grown == {("park:event",): 1}, "a root beside park:event"
        assert prom.STAGE_LATENCY._sum[("park:event",)] - parked0 == 3000.0
        record = tr.begin_cycle("pipelined")
        tr.end_cycle()
        event, = record.spans
        assert [c.name for c in event.children] == ["settle"]
        assert event.children[0].attrs == {
            "q_ms": 10.0, "signals": 2, "ended_by": "quiet",
            "widest_gap_ms": pytest.approx(3.0)}
        assert record.to_dict()["spans"][0]["children"][0]["name"] == "settle"

    def test_parked_loop_that_never_cycles_again_keeps_few_spans(
            self, tmp_path):
        tr, _ = _tracer(tmp_path)
        for _ in range(50):
            with tr.park_span("park:event"):
                pass
        assert len(tr._preceding) <= 4
        assert tr.span_counts["park:event"] == 50


class TestSpanTotals:
    def test_span_ms_grows_by_a_childs_and_a_roots_duration(
            self, tmp_path, monkeypatch):
        monkeypatch.setattr(telemetry, "perf_counter", _TickClock())
        tr, _ = _tracer(tmp_path)
        tr.begin_cycle("test")          # read 1
        with tr.span("root"):           # 2 .. 5
            with tr.span("child"):      # 3 .. 4
                pass
        with tr.span("root"):           # 6 .. 7
            pass
        tr.end_cycle()
        state = tr.state()
        # the tick between the two roots is the loop's own time, by name
        assert state["span_counts"] == {"child": 1, "root": 2, "between": 1}
        assert state["span_ms"] == {"child": 1000.0, "root": 4000.0,
                                    "between": 1000.0}

    def test_disabled_tracer_totals_nothing(self, tmp_path):
        tr = Tracer(enabled=False)
        with tr.span("root"):
            with tr.span("child"):
                pass
        assert tr.span_ms == {} and tr.span_counts == {}


class TestCompileListener:
    def test_untracked_jit_compile_is_counted_and_stamped(self, tmp_path):
        import jax
        import jax.numpy as jnp

        tr, _ = _tracer(tmp_path)
        x = jnp.arange(7.0)
        x.block_until_ready()
        # a program utils/jitstats has never heard of
        fn = jax.jit(lambda v: v * 3.0 + 1.0)
        compiles0 = _counter(prom.JIT_COMPILES)
        backend0 = _counter(prom.JIT_COMPILE_SECONDS, "backend")
        traced0 = tr.retraces_attributed
        tr.begin_cycle("test")
        with tr.span("stage:first") as first:
            fn(x).block_until_ready()
        compiles1 = _counter(prom.JIT_COMPILES)
        assert compiles1 >= compiles0 + 1
        assert _counter(prom.JIT_COMPILE_SECONDS, "backend") > backend0
        assert first.attrs["compiles"] == compiles1 - compiles0
        assert first.attrs["compile_ms"] > 0
        with tr.span("stage:second") as second:
            fn(x).block_until_ready()
        tr.end_cycle()
        assert _counter(prom.JIT_COMPILES) == compiles1
        assert not second.attrs
        # jitstats never saw it: the old counter keeps its meaning
        assert tr.retraces_attributed == traced0

    def test_compile_outside_any_span_is_still_counted(self):
        import jax
        import jax.numpy as jnp

        Tracer(enabled=True)  # the listener is process-wide, registered once
        Tracer(enabled=True)
        x = jnp.arange(5.0)
        x.block_until_ready()
        compiles0 = _counter(prom.JIT_COMPILES)
        jax.jit(lambda v: v - 2.0)(x).block_until_ready()
        assert _counter(prom.JIT_COMPILES) == compiles0 + 1


class TestSolveRounds:
    @pytest.mark.parametrize("members,over", [(2, False), (9, True)])
    def test_rounds_and_over_budget_follow_the_solve(self, members, over):
        """Members that take a node each, over nodes whose scores differ:
        every bidder wants the same best node and a node admits one, so the
        gang takes a round a member.  Two fit in one pass of 6 rounds; nine
        need a second pass, which is what ``over_budget`` counts."""
        cache = SchedulerCache(
            binder=FakeBinder(), evictor=FakeEvictor(),
            status_updater=FakeStatusUpdater(),
        )
        cache.add_queue(Queue(name="q0", uid="uq0", weight=1))
        for i in range(12):
            cache.add_node(Node(
                name=f"h{i}",
                allocatable={"cpu": 6000.0 + 300 * i, "memory": 16 * GiB,
                             "pods": 110.0},
            ))
        cache.add_pod_group(PodGroup(
            name="wide", namespace="tr", uid="pg-wide", min_member=members,
            queue="q0", creation_index=1,
        ))
        for k in range(members):
            cache.add_pod(Pod(
                name=f"wide-{k}", namespace="tr", uid=f"pod-wide-{k}",
                requests={"cpu": 5000.0, "memory": 1 * GiB},
                annotations={GROUP_NAME_ANNOTATION: "wide"},
                phase=PodPhase.PENDING, creation_index=100 + k,
            ))
        sched = _mk_scheduler(cache)
        rounds0 = _counter(prom.SOLVE_ROUNDS, "allocate")
        over0 = _counter(prom.SOLVE_OVER_BUDGET, "allocate")
        sched.run_once()
        assert len(cache.binder.binds) == members
        allocate = next(s for s in cache.flight_recorder.records()[-1].spans
                        if s.name == "action:allocate")
        wait = next(s for s in allocate.children if s.name == "device_wait")
        rounds = wait.attrs["rounds"]
        assert (rounds > 6) == over and rounds <= 18
        assert wait.attrs["over_budget"] is over
        assert _counter(prom.SOLVE_ROUNDS, "allocate") - rounds0 == rounds
        assert _counter(prom.SOLVE_OVER_BUDGET, "allocate") - over0 == int(over)
        cache.stop()


class TestReadPlaneSpans:
    def test_flush_is_totalled_and_kept_out_of_the_records(self):
        from kube_batch_tpu.serve.plane import QueryPlane

        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        qp = QueryPlane(cache, start_thread=False)
        _add_gang(cache, 1)
        sched.run_once()  # publishes the lease
        tr, rec = cache.tracer, cache.flight_recorder
        ringed = [(r.cycle, len(r.spans)) for r in rec.records()]
        cycles0, current0 = tr.cycles_total, tr.current
        stages0 = set(prom.STAGE_LATENCY._count)
        waits0 = (prom.WHATIF_QUEUE_WAIT._sum, prom.WHATIF_QUEUE_WAIT._count)
        # the batcher's injected clock: the request sits 250 ms in the queue
        t_enqueue = qp.batcher.clock.monotonic()
        futs = [qp.submit({"queue": "q0", "count": 2,
                           "requests": {"cpu": 500, "memory": GiB}}),
                qp.submit_sweep({"queue": "q1", "max_count": 4,
                                 "requests": {"cpu": 500, "memory": GiB}})]
        t_flush = qp.batcher.clock.monotonic() + 0.25
        assert qp.batcher.tick(now=t_flush) == 2
        assert futs[0].result(timeout=120)["feasible"]
        assert futs[1].result(timeout=120)["max_fit"] == 4
        state = tr.state()
        for name in ("whatif:flush", "whatif:lease", "whatif:encode",
                     "whatif:probe", "whatif:decode", "whatif:deliver"):
            assert state["span_counts"][name] >= 1, name
            assert state["span_ms"][name] >= 0.0
        assert state["span_counts"]["whatif:flush"] == 1
        # one dispatch: the sweep's three counts ride the plain probe's
        assert state["span_counts"]["whatif:probe"] == qp.dispatches == 1
        assert (state["span_ms"]["whatif:flush"]
                >= state["span_ms"]["whatif:probe"])
        flush = state["last_detached"]["whatif:flush"]
        assert flush["attrs"]["seq"] == 1 and flush["attrs"]["batch"] == 2
        assert flush["attrs"]["lease_version"] == qp.broker.current().version
        assert flush["attrs"]["dispatches"] == 1
        assert flush["attrs"]["points"] == 1 + 3
        assert [c["name"] for c in flush["children"]][0] == "whatif:lease"
        # no record added to the ring, no span to a cycle's record, no
        # stage label on /metrics
        assert [(r.cycle, len(r.spans)) for r in rec.records()] == ringed
        assert tr.cycles_total == cycles0 and tr.current is current0
        assert set(prom.STAGE_LATENCY._count) == stages0
        assert not any("whatif:" in key for key in state["solve_dispatches"])
        # the wait in the batcher crosses threads: a counter, not a span
        waited = prom.WHATIF_QUEUE_WAIT._sum - waits0[0]
        assert prom.WHATIF_QUEUE_WAIT._count - waits0[1] == 2
        assert waited == pytest.approx(
            2 * (t_flush - t_enqueue) * 1e3, abs=2 * 50.0)
        assert waited >= 2 * 250.0
        qp.close()
        cache.stop()


    def test_a_flush_counts_its_dispatches_and_its_live_lanes(self):
        """`volcano_whatif_dispatch_points_total` grows by the lanes that
        carried a point, beside the dispatches and the flushes, so the
        dispatches a flush and the fill share fall out of /metrics; the
        flush's span carries both, each probe span its gang bucket."""
        from kube_batch_tpu.serve.plane import QueryPlane

        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        qp = QueryPlane(cache, start_thread=False)
        _add_gang(cache, 1)
        sched.run_once()
        before = (_counter(prom.WHATIF_DISPATCHES),
                  _counter(prom.WHATIF_DISPATCH_POINTS),
                  prom.WHATIF_BATCH_SIZE._count[()])
        # a member takes most of a node, so 4 fit: the grid (1, 2, 4, 8)
        # leaves 6 and then 5 to two refinement steps
        futs = [qp.submit({"queue": "q0", "count": 9,
                           "requests": {"cpu": 500, "memory": GiB}}),
                qp.submit_sweep({"queue": "q1", "max_count": 8,
                                 "requests": {"cpu": 10000, "memory": GiB}})]
        assert qp.batcher.tick(now=qp.batcher.clock.monotonic() + 1.0) == 2
        assert futs[0].result(timeout=120)["feasible"]
        assert futs[1].result(timeout=120)["max_fit"] == 4
        assert futs[1].result()["probes"] == 4 + 2
        assert _counter(prom.WHATIF_DISPATCHES) - before[0] == 3
        assert _counter(prom.WHATIF_DISPATCH_POINTS) - before[1] == 1 + 4 + 2
        assert prom.WHATIF_BATCH_SIZE._count[()] - before[2] == 1
        assert (qp.dispatches, qp.points) == (3, 7)
        flush = cache.tracer.state()["last_detached"]["whatif:flush"]
        assert flush["attrs"]["batch"] == 2
        assert flush["attrs"]["dispatches"] == 3
        assert flush["attrs"]["points"] == 7
        probes = [c["attrs"] for c in flush["children"]
                  if c["name"] == "whatif:probe"]
        # the window's first dispatch at the bucket of its widest point
        # (the plain request's 9 members), each step's single count at 8
        assert [(a["batch"], a["gang"]) for a in probes] == [
            (5, 16), (1, 8), (1, 8)]
        rendered = prom.render_prometheus()
        assert "volcano_whatif_dispatch_points_total" in rendered
        qp.close()
        cache.stop()


class TestDecisionParts:
    def _late_gang(self, cache):
        cache.add_pod_group(PodGroup(
            name="late", namespace="tr", uid="pg-late", min_member=2,
            queue="q0", creation_index=50,
        ))

        def member(k):
            return Pod(
                name=f"late-{k}", namespace="tr", uid=f"pod-late-{k}",
                requests={"cpu": 500.0, "memory": 1 * GiB},
                annotations={GROUP_NAME_ANNOTATION: "late"},
                phase=PodPhase.PENDING, creation_index=5000 + k,
            )

        return member

    def test_queue_wait_plus_in_cycle_part_is_the_kept_latency(
            self, monkeypatch):
        """The kept arrival→decision latency, unchanged, splits at the
        start of the deciding cycle; a pod that a cycle passed over counts
        once as a leftover, a pod bound by its first cycle never."""
        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        sched.run_once_pipelined()  # the solve compiles on the real clock
        sched.drain_pipeline()
        clock = _TickClock(start=1000.0, step=0.001)
        monkeypatch.setattr(telemetry, "perf_counter", clock)
        sink = []
        prom_metrics.set_decision_latency_sink(sink)
        wait0 = (prom.DECISION_QUEUE_WAIT._sum, prom.DECISION_QUEUE_WAIT._count)
        left0 = _counter(prom.DECISIONS_LEFTOVER)
        decided0 = prom.DECISION_LATENCY._count[()]
        try:
            member = self._late_gang(cache)
            cache.add_pod(member(0))       # half a gang: cycle A passes it over
            arrival_0 = clock.t
            clock.t += 2.0
            sched.run_once_pipelined()
            sched.drain_pipeline()
            assert sink == [] and _counter(prom.DECISIONS_LEFTOVER) == left0
            cache.add_pod(member(1))       # the gang is whole: cycle B binds
            arrival_1 = clock.t
            clock.t += 3.0
            sched.run_once_pipelined()
            sched.drain_pipeline()
        finally:
            prom_metrics.set_decision_latency_sink(None)
        assert len(sink) == 2
        assert prom.DECISION_LATENCY._count[()] == decided0 + 2
        deciding = cache.flight_recorder.records()[-1]
        _assert_one_cycle_kept_the_summary_of(cache, sink, deciding=2)
        # the first member waited through cycle A; two cycles had started
        # since it arrived when B bound it (the warm-up's, stamped on the
        # real clock, may count as a third)
        assert deciding.decisions["wait_ms"] == pytest.approx(
            (deciding.t0 - arrival_0) * 1e3, abs=1e-3)
        assert deciding.decisions["spanned"] in (2, 3)
        # wait = arrival -> the deciding cycle's start, for each pod
        waited = prom.DECISION_QUEUE_WAIT._sum - wait0[0]
        assert prom.DECISION_QUEUE_WAIT._count - wait0[1] == 2
        want = ((deciding.t0 - arrival_0) + (deciding.t0 - arrival_1)) * 1e3
        assert waited == pytest.approx(want, abs=1e-6)
        # the rest of the kept latency was spent inside that cycle: both
        # pods were bound at one instant of it
        in_cycle = (sum(sink) - waited) / 2
        bound_at = arrival_0 + max(sink) / 1e3
        assert in_cycle == pytest.approx((bound_at - deciding.t0) * 1e3,
                                         abs=1e-6)
        assert 0 < in_cycle < 1000.0
        # member 0 saw two cycles start since it arrived, member 1 one
        assert _counter(prom.DECISIONS_LEFTOVER) == left0 + 1
        sched.close()
        cache.stop()

    def test_bind_outside_any_cycle_waited_all_its_latency(self, tmp_path):
        tr, _ = _tracer(tmp_path)
        wait0 = (prom.DECISION_QUEUE_WAIT._sum, prom.DECISION_QUEUE_WAIT._count)
        left0 = _counter(prom.DECISIONS_LEFTOVER)
        tr.note_decision_parts([10.0, 11.0], now=12.0)
        assert prom.DECISION_QUEUE_WAIT._sum - wait0[0] == pytest.approx(3000.0)
        assert prom.DECISION_QUEUE_WAIT._count - wait0[1] == 2
        assert _counter(prom.DECISIONS_LEFTOVER) == left0

    @pytest.mark.parametrize("arrivals,waited_ms,leftover", [
        ([0.5], 3500.0, 1),             # bind(): one pod, the scalar form
        ([2.5], 1500.0, 0),
        ([0.5, 1.5, 2.5, 4.5], 7500.0, 2),   # bulk_bind(): searchsorted
    ])
    def test_one_pod_and_a_batch_split_alike(
            self, tmp_path, monkeypatch, arrivals, waited_ms, leftover):
        monkeypatch.setattr(telemetry, "perf_counter", _TickClock())
        tr, _ = _tracer(tmp_path)
        tr.begin_cycle("a")     # starts at 1.0
        tr.begin_cycle("b")     # 2.0 (finalizing "a" reads the clock: 3.0)
        tr.begin_cycle("c")     # 4.0, the deciding cycle
        assert list(tr._cycle_starts) == [1.0, 2.0, 4.0]
        wait0 = (prom.DECISION_QUEUE_WAIT._sum, prom.DECISION_QUEUE_WAIT._count)
        left0 = _counter(prom.DECISIONS_LEFTOVER)
        tr.note_decision_parts(arrivals, now=4.75)
        assert (prom.DECISION_QUEUE_WAIT._sum - wait0[0]
                == pytest.approx(waited_ms))
        assert prom.DECISION_QUEUE_WAIT._count - wait0[1] == len(arrivals)
        assert _counter(prom.DECISIONS_LEFTOVER) == left0 + leftover


class TestProfilerClock:
    def test_span_names_are_on_the_profilers_host_plane(self, tmp_path):
        """Every span is also a ``TraceAnnotation``: a short jax.profiler
        trace (CPU) around two pipelined cycles holds ``session_open`` and
        ``park:event`` on the host plane, read with ``ProfileData`` the way
        benchmark/tests/test_trace_reduce.py reads a chip trace."""
        import glob

        import jax
        from jax.profiler import ProfileData

        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        sched.run_once_pipelined()  # compile before the trace opens
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # as benchmark/serve.py traces
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _add_gang(cache, 2)
            sched.run_once_pipelined()
            sched.trigger.notify()
            assert sched.trigger.wait_for_work(
                sched.clock.monotonic(), 0.0, 1.0) == "ingest"
            _add_gang(cache, 3)
            sched.run_once_pipelined()
            sched.drain_pipeline()
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        assert len(found) == 1
        on_host = {}
        for plane in ProfileData.from_file(found[0]).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        on_host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.duration_ns))
        for name in ("session_open", "park:event", "action:allocate",
                     "solve_dispatch", "writeback"):
            assert name in on_host, name
        assert len(on_host["session_open"]) == 2
        # on one clock: the parked span lies between the two cycles' opens
        (open_a, _), (open_b, _) = sorted(on_host["session_open"])
        (parked, _), = on_host["park:event"]
        assert open_a < parked < open_b
        sched.close()
        cache.stop()


# ---------------------------------------------------------------------------
# HTTP surfaces
# ---------------------------------------------------------------------------


class TestTraceEndpoints:
    def test_v1_trace_and_alerts(self):
        from kube_batch_tpu.cmd.server import AdminServer

        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        sched.run_once()
        admin = AdminServer(cache, "127.0.0.1", 0)
        admin.start()
        try:
            base = f"http://127.0.0.1:{admin.port}"
            with urllib.request.urlopen(base + "/v1/trace") as r:
                trace = json.loads(r.read())
            assert trace["enabled"] is True
            assert trace["cycles_traced"] >= 1
            assert trace["last_cycle"] is not None
            names = {s["name"] for s in trace["last_cycle"]["spans"]}
            assert "session_open" in names
            assert trace["ring"]["capacity"] >= 2
            with urllib.request.urlopen(base + "/v1/alerts") as r:
                alerts = json.loads(r.read())
            assert "alerts" in alerts and "window_cycles" in alerts
            # the per-stage histogram rides /metrics
            with urllib.request.urlopen(base + "/metrics") as r:
                text = r.read().decode()
            assert "volcano_cycle_stage_latency_milliseconds" in text
        finally:
            admin.stop()
            cache.stop()

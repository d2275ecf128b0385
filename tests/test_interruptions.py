"""The interruption ledger (kube_batch_tpu/obs/interruptions.py and what it
extends in obs/trace.py, obs/recorder.py): garbage-collector pauses charged
to the root spans they stopped and to the gaps between them, the loop
watchdog's stalls on an injected clock and through the served path, the
compile log, the flight recorder's table of cycles and its kept list."""

from __future__ import annotations

import gc
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from kube_batch_tpu import metrics as prom_metrics
from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION
from kube_batch_tpu.metrics import metrics as prom
from kube_batch_tpu.obs import recorder as recorder_mod
from kube_batch_tpu.obs.interruptions import GC, LoopWatchdog
from kube_batch_tpu.obs.recorder import FlightRecorder
from kube_batch_tpu.obs.trace import Tracer
from kube_batch_tpu.scheduler import Scheduler
from kube_batch_tpu.sim.clock import VirtualClock
from kube_batch_tpu.utils.blocking import allow_blocking
from kube_batch_tpu.framework.conf import load_scheduler_conf

from tests.test_trace import (
    _Churner,
    _add_gang,
    _mk_cache,
    _mk_scheduler,
    _observable_state,
    _tracer,
)

ROW_COLUMNS = {
    "cycle", "reason", "t0", "dur_ms", "decided", "worst_ms", "median_ms",
    "wait_ms", "parked_ms", "settle_ms", "gc_ms", "gc_full", "compile_ms",
    "stall_ms", "spanned", "named_ms", "worst_at", "worst_named_ms",
}


@pytest.fixture
def quiet_collector():
    """No collection but the ones the test forces."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _gc_series():
    return (prom.GC_COLLECTIONS.values(), prom.GC_PAUSE_SECONDS.values())


def _stalls(phase):
    return (prom.LOOP_STALLS._values[(phase,)],
            prom.LOOP_STALL_SECONDS._values[(phase,)])


# ---------------------------------------------------------------------------
# (a), (b): the collector's pauses, on roots and between them
# ---------------------------------------------------------------------------


class TestGCPauses:
    def test_full_collection_in_a_root_is_charged_to_it(
            self, tmp_path, quiet_collector):
        tr, rec = _tracer(tmp_path)
        counts0, seconds0 = _gc_series()
        tr.begin_cycle("t")
        with tr.span("stage") as root:
            with tr.span("inner") as child:
                gc.collect(2)
        tr.end_cycle()
        assert root.attrs["gc_ms"] > 0 and root.attrs["gc_full"] == 1
        assert "gc_ms" not in (child.attrs or {}), "roots sample, not children"
        counts, seconds = _gc_series()
        assert counts[("2",)] == counts0[("2",)] + 1
        assert seconds[("2",)] > seconds0[("2",)]
        for young in ("0", "1"):
            assert counts[(young,)] == counts0[(young,)]
            assert seconds[(young,)] == seconds0[(young,)]
        # the pause in seconds and in the span's milliseconds is one number
        assert root.attrs["gc_ms"] == pytest.approx(
            (seconds[("2",)] - seconds0[("2",)]) * 1e3, abs=2e-3)
        # a record that carries a full collection is kept
        (row,) = tr.state()["kept"]
        assert row["why"] == ["gc_full"] and row["gc_full"] == 1
        assert row["gc_ms"] == root.attrs["gc_ms"]
        # and the page renders the same totals
        page = prom_metrics.render_prometheus()
        assert (f'volcano_gc_collections_total{{generation="2"}} '
                f'{counts[("2",)]:g}') in page

    def test_a_collection_on_another_thread_stops_the_open_root_too(
            self, tmp_path, quiet_collector):
        tr, _ = _tracer(tmp_path)
        tr.begin_cycle("t")
        with tr.span("stage") as root:
            other = threading.Thread(target=gc.collect, args=(2,))
            other.start()
            other.join(timeout=60)
            assert not other.is_alive()
        tr.end_cycle()
        assert root.attrs["gc_ms"] > 0 and root.attrs["gc_full"] == 1

    def test_between_totals_the_gap_of_two_roots_and_its_pause(
            self, tmp_path, quiet_collector):
        tr, rec = _tracer(tmp_path)
        tr.begin_cycle("t")
        with tr.span("first") as first:
            pass
        time.sleep(0.02)
        gc.collect(2)
        with tr.span("second") as second:
            pass
        tr.end_cycle()
        state = tr.state()
        assert state["span_counts"]["between"] == 1
        gap_ms = (second.t0 - first.t1) * 1e3
        assert gap_ms >= 20.0
        assert state["span_ms"]["between"] == pytest.approx(gap_ms, abs=1e-3)
        assert first._gap is None, "nothing before the tracer's first root"
        # the pause fell between the roots: neither carries it as its own
        assert "gc_ms" not in (first.attrs or {})
        assert "gc_ms" not in second.attrs
        assert 0 < second.attrs["gap_gc_ms"] <= gap_ms
        assert second.attrs["gap_gc_full"] == 1
        assert second.to_dict()["gap_ms"] == pytest.approx(gap_ms, abs=1e-3)
        (row,) = state["cycles"]
        assert row["gc_full"] == 1
        assert row["gc_ms"] == second.attrs["gap_gc_ms"]
        # named: the two roots and the pause, not the whole gap
        assert row["named_ms"] == pytest.approx(
            first.dur_ms + second.dur_ms + second.attrs["gap_gc_ms"],
            abs=2e-3)

    def test_kb_trace_0_counts_and_retains_nothing(
            self, tmp_path, quiet_collector):
        """(i) the counters move with retention off; no attribute, no
        record, no stall record."""
        rec = FlightRecorder(ring=4, directory=str(tmp_path))
        tr = Tracer(recorder=rec, enabled=False)
        counts0, _ = _gc_series()
        tr.begin_cycle("t")
        with tr.span("stage") as root:
            gc.collect(2)
        with tr.span("next"):
            pass
        tr.end_cycle()
        assert _gc_series()[0][("2",)] == counts0[("2",)] + 1
        assert root.attrs is None
        assert tr.span_counts == {} and rec.records() == []
        state = tr.state()
        assert state["cycles"] == [] and state["kept"] == []
        assert state["compiles"] == []
        # a watchdog over a disabled tracer counts its stalls and keeps none
        clock = VirtualClock(start=50.0)
        sched = _watched(clock, tracer_enabled=False)
        declared0, _ = _stalls("parked")
        sched.trigger.notify()
        clock.sleep(5.0)
        wd = LoopWatchdog(sched)
        wd.loop_tid = threading.get_ident()
        wd.check()
        assert _stalls("parked")[0] == declared0 + 1
        assert sched.tracer._stalls_waiting == []
        assert sched.tracer.state()["kept"] == []
        sched.cache.stop()


# ---------------------------------------------------------------------------
# (c), (d), (e): the loop's watchdog
# ---------------------------------------------------------------------------


def _watched(clock, tracer_enabled=True) -> Scheduler:
    """A scheduler on an injected clock whose loop is not running: the
    watchdog's check is called directly."""
    cache = _mk_cache()
    cache.tracer = Tracer(
        clock=clock, recorder=FlightRecorder(ring=4, post_cycles=0),
        enabled=tracer_enabled)
    cache.flight_recorder = cache.tracer.recorder
    sched = Scheduler(cache, conf=load_scheduler_conf(None), clock=clock)
    sched.min_period = 0.01
    sched.cycle_cost_ewma = 0.1
    return sched


def the_loop_thread_sits_here(release: threading.Event) -> None:
    release.wait(60)


class TestWatchdog:
    def test_unconsumed_signal_is_one_parked_stall_with_the_loops_stack(self):
        clock = VirtualClock(start=100.0)
        sched = _watched(clock)
        release = threading.Event()
        loop = threading.Thread(target=the_loop_thread_sits_here,
                                args=(release,), daemon=True)
        loop.start()
        wd = LoopWatchdog(sched)
        wd.loop_tid = loop.ident
        declared0, seconds0 = _stalls("parked")
        try:
            sched.trigger.notify()          # at 100.0
            clock.sleep(0.2)
            wd.check()                      # 200 ms: inside max(4 x 10, 250)
            assert _stalls("parked")[0] == declared0
            for _ in range(5):              # past it, however many ticks
                clock.sleep(0.1)
                wd.check()
            assert _stalls("parked") == (declared0 + 1, seconds0)
            (stall,) = sched.tracer._stalls_waiting
            assert stall["phase"] == "parked" and stall["dur_ms"] is None
            # at the first look past 250 ms + the settle hold's cap of 50
            assert stall["declared_after_ms"] == pytest.approx(400.0)
            assert any("the_loop_thread_sits_here" in frame
                       for frame in stall["stack"])
            assert stall["gc"]["collections"] == GC.collections
            assert stall["compiling"] == []
            # until a cycle begins it is a kept record of its own
            (row,) = sched.tracer.state()["kept"]
            assert row["cycle"] is None and row["why"] == ["stall"]
            assert row["stall"]["phase"] == "parked"
            # the loop moves again: the cycle starts at 100.9
            clock.sleep(0.2)
            assert sched.trigger.wait_for_work(
                clock.monotonic(), 0.0, 1.0) == "ingest"
            clock.sleep(3.0)                # the watchdog looks later
            wd.check()
            assert stall["dur_ms"] == pytest.approx(900.0)
            assert _stalls("parked")[1] == pytest.approx(seconds0 + 0.9)
            # and it rides the record of the cycle that followed
            rec = sched.tracer.begin_cycle("pipelined")
            sched.tracer.end_cycle()
            assert rec.stalls == [stall]
            (row,) = sched.tracer.state()["kept"]
            assert row["cycle"] == rec.cycle and row["why"] == ["stall"]
            assert row["stall_ms"] == pytest.approx(900.0)
            tree = sched.tracer.cycle_tree(rec.cycle)
            assert tree["stalls"][0]["stack"] == stall["stack"]
            # a new signal is a new stall
            sched.trigger.notify()
            clock.sleep(1.0)
            wd.check()
            assert _stalls("parked")[0] == declared0 + 2
        finally:
            release.set()
            loop.join(timeout=10)
            sched.cache.stop()

    def test_a_hold_inside_the_settle_cap_is_no_stall(self):
        clock = VirtualClock(start=100.0)
        sched = _watched(clock)
        sched.settle_window = lambda: (0.025, 0.1)
        wd = LoopWatchdog(sched)
        wd.loop_tid = threading.get_ident()
        declared0, _ = _stalls("parked")
        sched.trigger.notify()
        clock.sleep(0.34)       # past 250 ms, inside 250 ms + the cap
        wd.check()
        assert _stalls("parked")[0] == declared0
        assert sched.tracer._stalls_waiting == []
        # a leftover wake is the loop's own and never a stall
        assert sched.trigger.wait_for_work(
            clock.monotonic(), 0.0, 1.0) == "ingest"
        sched.trigger.notify(leftover=True)
        clock.sleep(5.0)
        wd.check()
        assert _stalls("parked")[0] == declared0
        sched.cache.stop()

    def test_root_span_held_open_is_one_cycle_stall_with_its_path(self):
        clock = VirtualClock(start=10.0)
        sched = _watched(clock)         # EWMA 0.1 s: the bound is 400 ms
        tr = sched.tracer
        wd = LoopWatchdog(sched)
        wd.loop_tid = threading.get_ident()
        declared0, seconds0 = _stalls("cycle")
        rec = tr.begin_cycle("pipelined")
        with tr.park_span("park:event"):
            clock.sleep(30.0)           # parked time is no stage
            wd.check()
        with tr.span("action:allocate"):
            with tr.span("solve_dispatch"):
                clock.sleep(0.3)
                wd.check()
                assert _stalls("cycle")[0] == declared0
                clock.sleep(0.3)
                for _ in range(3):
                    wd.check()
                assert _stalls("cycle") == (declared0 + 1, seconds0)
                (stall,) = rec.stalls
                assert stall["span_path"] == "action:allocate > solve_dispatch"
                assert any("test_root_span_held_open" in frame
                           for frame in stall["stack"])
                clock.sleep(1.4)
        wd.check()
        tr.end_cycle()
        assert stall["dur_ms"] == pytest.approx(2000.0)
        assert _stalls("cycle")[1] == pytest.approx(seconds0 + 2.0)
        (row,) = tr.state()["kept"]
        assert row["why"] == ["stall"] and row["stall_ms"] == 2000.0
        sched.cache.stop()

    def test_only_run_forever_has_one_and_stop_joins_it(self):
        def watchdogs():
            return [t for t in threading.enumerate()
                    if t.name == "kb-loop-watchdog"]

        before = set(threading.enumerate())
        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        sched.run_once()
        sched.run_once_pipelined()
        sched.drain_pipeline()
        assert sched._watchdog is None and watchdogs() == []
        loop = threading.Thread(target=sched.run_forever, daemon=True)
        loop.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and (
                sched._watchdog is None or sched._watchdog._thread is None):
            time.sleep(0.01)
        (thread,) = watchdogs()
        assert thread.daemon and sched._watchdog.loop_tid == loop.ident
        sched.stop()                     # joins the watchdog itself
        assert not thread.is_alive() and sched._watchdog is None
        loop.join(timeout=30)
        assert not loop.is_alive()
        sched.close()
        assert set(threading.enumerate()) - before == set()


# ---------------------------------------------------------------------------
# (f), (g): the table of cycles, the tree by number, the kept list
# ---------------------------------------------------------------------------


def _decide(tr, latencies_ms, now=1000.0):
    """One cycle that closes ``latencies_ms``."""
    rec = tr.begin_cycle("pipelined")
    with tr.span("action:allocate"):
        arrivals = [now - ms / 1e3 for ms in latencies_ms]
        tr.note_decision_latencies(list(latencies_ms))
        tr.note_decision_parts(arrivals, now)
    tr.end_cycle()
    return rec


class TestCycleTable:
    def test_one_row_a_record_and_worst_is_the_largest_sample(self):
        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        sink = []
        prom_metrics.set_decision_latency_sink(sink)
        try:
            for serial in (1, 2, 3):
                _add_gang(cache, serial)
                sched.run_once_pipelined()
                sched.drain_pipeline()
            sched.run_once_pipelined()      # decides nothing
            sched.drain_pipeline()
        finally:
            prom_metrics.set_decision_latency_sink(None)
        state = cache.tracer.state()
        rows = state["cycles"]
        records = cache.flight_recorder.records()
        assert [r["cycle"] for r in rows] == [rec.cycle for rec in records]
        assert all(set(r) == ROW_COLUMNS for r in rows)
        deciding = [r for r in rows if r["decided"]]
        assert [r["decided"] for r in deciding] == [2, 2, 2]
        for row, pair in zip(deciding, zip(sink[::2], sink[1::2])):
            assert row["worst_ms"] == round(max(pair), 3)
            assert row["median_ms"] == pytest.approx(sum(pair) / 2, abs=1e-3)
            assert row["spanned"] == 1 and row["wait_ms"] >= 0
            # direct drives: the whole interval lies under the cycle's roots
            # and the gaps between them
            assert 0 < row["worst_named_ms"] <= row["worst_ms"]
        idle = rows[-1]
        assert idle["decided"] == 0 and idle["worst_ms"] is None
        assert idle["named_ms"] > 0
        json.dumps(state)                   # the page serialises
        sched.close()
        cache.stop()

    def test_tree_by_number_and_404_after_rollover(self, monkeypatch):
        from kube_batch_tpu.cmd.server import AdminServer

        monkeypatch.setenv("KB_TRACE_RING", "4")
        cache = _mk_cache()
        sched = _mk_scheduler(cache)
        _add_gang(cache, 1)
        cache.tracer.note_stall(
            {"phase": "parked", "t0": 0.0, "dur_ms": 400.0},
            waits_for_cycle=True)
        sched.run_once()                    # carries a stall: kept
        sched.run_once()                    # nothing to keep it for
        first, plain = [r.cycle for r in cache.flight_recorder.records()]
        admin = AdminServer(cache, "127.0.0.1", 0)
        admin.start()

        def get(cycle):
            url = f"http://127.0.0.1:{admin.port}/v1/trace/cycles/{cycle}"
            with urllib.request.urlopen(url) as r:
                return json.loads(r.read())

        try:
            tree = get(plain)
            assert tree["cycle"] == plain and tree["reason"] == "serial"
            assert "session_open" in {s["name"] for s in tree["spans"]}
            for _ in range(5):
                sched.run_once()            # the ring of 4 rolls past both
            assert plain not in [
                r.cycle for r in cache.flight_recorder.records()]
            with pytest.raises(urllib.error.HTTPError) as gone:
                get(plain)
            assert gone.value.code == 404
            assert get(first)["cycle"] == first, "kept outside the rollover"
            with pytest.raises(urllib.error.HTTPError) as bad:
                get("nonsense")
            assert bad.value.code == 404
        finally:
            admin.stop()
            cache.stop()

    def test_a_kept_record_outlives_the_ring_and_the_list_is_bounded(
            self, tmp_path):
        rec = FlightRecorder(ring=4, directory=str(tmp_path), post_cycles=0)
        tr = Tracer(recorder=rec, enabled=True)

        def stalled_cycle():
            tr.note_stall({"phase": "parked", "t0": 1.0, "dur_ms": 300.0},
                          waits_for_cycle=True)
            return _decide(tr, [20.0])

        kept = stalled_cycle()
        for _ in range(10):
            _decide(tr, [20.0])
        assert kept.cycle not in [r.cycle for r in rec.records()]
        assert rec.find(kept.cycle) is kept
        cycles, pinned = rec.table()
        assert len(cycles) == 4
        assert [(r["cycle"], r["why"], r["stall_ms"]) for r in pinned] == [
            (kept.cycle, ["stall"], 300.0)]
        later = [stalled_cycle() for _ in range(recorder_mod.KEPT + 8)]
        _, pinned = rec.table()
        assert [r["cycle"] for r in pinned] == [
            r.cycle for r in later[-recorder_mod.KEPT:]]
        assert rec.find(kept.cycle) is None, "oldest out"

    def test_slow_rule_skips_the_cold_drain_and_catches_the_outlier(
            self, tmp_path):
        tr, rec = _tracer(tmp_path)
        slow0 = prom.SLOW_DECISIONS._values[()]
        _decide(tr, [30_000.0] * 50 + [31_000.0])    # the cold drain
        for i in range(12):                          # steady bursts
            _decide(tr, [180.0 + i, 200.0 + i])
        assert prom.SLOW_DECISIONS._values[()] == slow0
        assert tr.state()["kept"] == []
        slow = _decide(tr, [190.0, 260.0])           # above, not 100 ms
        assert prom.SLOW_DECISIONS._values[()] == slow0
        slow = _decide(tr, [190.0, 520.0])           # 100 ms and 2x above
        assert prom.SLOW_DECISIONS._values[()] == slow0 + 1
        (row,) = tr.state()["kept"]
        assert (row["cycle"], row["why"], row["worst_ms"]) == (
            slow.cycle, ["slow"], 520.0)
        _decide(tr, [190.0, 215.0])
        assert prom.SLOW_DECISIONS._values[()] == slow0 + 1

    def test_worst_decisions_interval_is_named_by_roots_parks_and_pauses(
            self, tmp_path, monkeypatch):
        """A pod that arrived while the loop was parked: its interval is
        the rest of the park, the gaps and the cycle's stages up to the
        bind; a gap has no name but for the pause that fell in it."""
        from kube_batch_tpu.utils import telemetry

        now = [100.0]
        monkeypatch.setattr(telemetry, "perf_counter", lambda: now[0])
        tr, _ = _tracer(tmp_path)
        with tr.park_span("park:event"):     # 100.0 .. 100.5
            now[0] = 100.5
        now[0] = 100.6                       # a gap of 100 ms, no name
        tr.begin_cycle("pipelined")
        with tr.span("session_open"):        # 100.6 .. 100.7
            now[0] = 100.7
        now[0] = 100.75                      # a gap of 50 ms
        with tr.span("action:allocate") as sp:   # 100.75 .. 100.9
            sp._gap = (sp._gap[0], 0.03, 1)  # 30 ms of it a full collection
            now[0] = 100.85
            tr.note_decision_latencies([650.0])
            tr.note_decision_parts([100.2], 100.85)
            now[0] = 100.9
        tr.end_cycle()
        (row,) = tr.state()["cycles"]
        assert row["worst_ms"] == 650.0 and row["wait_ms"] == 400.0
        # 300 of the park + 100 + the pause's 30 + 100 of allocate
        assert row["worst_named_ms"] == pytest.approx(530.0)
        assert row["parked_ms"] == pytest.approx(500.0)
        assert row["named_ms"] == pytest.approx(500 + 100 + 150 + 30)


# ---------------------------------------------------------------------------
# (h): the compile log
# ---------------------------------------------------------------------------


class TestCompileLog:
    def test_a_fresh_program_under_solve_dispatch_is_logged_with_its_rungs(
            self, tmp_path):
        import jax
        import numpy as np

        def fresh_program_of_this_test(x):
            return x * 3 + 1

        tr, rec = _tracer(tmp_path)
        operand = np.ones(7, np.float32)
        record = tr.begin_cycle("pipelined")
        with tr.span("action:allocate"):
            with tr.device_span("solve_dispatch") as sp:
                assert tr.compile_in_flight() == []
                jax.jit(fresh_program_of_this_test)(operand).block_until_ready()
            # the action names the program after the span has closed
            tr.note_solve_dispatch(
                sp, "allocate", "single", ["topk", "warm"], program="warm",
                bucket=256, rungs=(128, 64, 32))
        tr.end_cycle()
        (entry,) = [e for e in tr.state()["compiles"]
                    if "fresh_program_of_this_test" in e["fun_name"]]
        assert entry["cycle"] == record.cycle
        assert entry["path"] == "action:allocate > solve_dispatch"
        assert set(entry["ms"]) == {"trace", "lower", "backend"}
        assert entry["dispatch"] == {
            "span": "solve_dispatch", "mode": "single", "program": "warm",
            "engaged": ["topk", "warm"], "bucket": 256,
            "rungs": [128, 64, 32],
            **{k: v for k, v in sp.attrs.items()
               if k in ("compiles", "compile_ms", "retrace", "resident")}}
        assert sp.attrs["compiles"] == 1
        # (the operand's transfer may compile a program of its own)
        assert record.compile_ms >= sum(entry["ms"].values()) - 0.01
        (row,) = tr.state()["kept"]
        assert row["why"] == ["compile"] and row["compile_ms"] > 0
        # a second call compiles nothing and logs nothing
        before = len(tr.state()["compiles"])
        with tr.device_span("solve_dispatch"):
            jax.jit(fresh_program_of_this_test)(operand).block_until_ready()
        assert len(tr.state()["compiles"]) == before

    def test_the_warm_plan_says_its_three_rungs(self):
        """``note_solve_dispatch`` gets them from the plan's record."""
        from kube_batch_tpu.framework.interface import get_action

        cache = _mk_cache(n_nodes=8)
        sched = _mk_scheduler(cache)
        for serial in range(1, 4):
            _add_gang(cache, serial)
            sched.run_once()
        warm = get_action("allocate").last_warm
        dispatches = [
            sp for rec in cache.flight_recorder.records()
            for root in rec.spans for sp in root.children
            if sp.name == "solve_dispatch"]
        assert dispatches
        for sp in dispatches:
            if sp.attrs["program"] in ("warm", "topk") and "rungs" in sp.attrs:
                assert len(sp.attrs["rungs"]) == 3
                assert all(isinstance(r, int) and r > 0
                           for r in sp.attrs["rungs"])
        if warm is not None:
            assert dispatches[-1].attrs["rungs"] == warm["rungs"]
        cache.stop()


# ---------------------------------------------------------------------------
# (j): inert
# ---------------------------------------------------------------------------


class TestInert:
    @pytest.mark.parametrize("seed", [5])
    def test_churn_decides_the_same_with_the_hook_and_a_watchdog_running(
            self, seed, monkeypatch):
        """The randomized churn of tests/test_trace.py, one side with
        retention off, the other traced, watched by a watchdog that declares
        a stall in every cycle and interrupted by a full collection in
        every cycle: identical decisions.  The watched side runs on an
        injected clock and the watchdog's looks are scripted, as in
        :class:`TestWatchdog`: each cycle's ``resync`` root holds for more
        than the bound, the watchdog looks, and the collector runs — what
        a live watchdog and a collecting thread did only when the machine
        gave them a turn inside a cycle."""
        monkeypatch.setenv("KB_TRACE", "0")
        c_off = _mk_cache()
        s_off = _mk_scheduler(c_off)
        clock = VirtualClock(start=100.0)
        s_on = _watched(clock)
        c_on = s_on.cache
        wd = LoopWatchdog(s_on)
        wd.loop_tid = threading.get_ident()
        resync = c_on.process_resync_tasks
        looks = []

        def resync_under_watch():
            bound = max(wd.FACTOR * (s_on.cycle_cost_ewma or 0.0), wd.FLOOR_S)
            clock.sleep(bound + 0.05)
            wd.check()
            looks.append(gc.collect(2))
            return resync()

        c_on.process_resync_tasks = resync_under_watch
        declared0, _ = _stalls("cycle")
        ch_off, ch_on = _Churner(c_off, seed), _Churner(c_on, seed)
        try:
            for _ in range(3):
                ch_off.add_gang()
                ch_on.add_gang()
            for cycle in range(8):
                ch_off.step()
                ch_on.step()
                if cycle % 2:
                    s_off.run_once()
                    s_on.run_once()
                else:
                    s_off.run_once_pipelined()
                    s_off.drain_pipeline()
                    s_on.run_once_pipelined()
                    s_on.drain_pipeline()
        finally:
            wd.stop()
        assert _observable_state(c_on) == _observable_state(c_off)
        assert len(looks) == 8
        # a look finds the last cycle's stall closed or declares this one's
        assert _stalls("cycle")[0] == declared0 + 4
        state = c_on.tracer.state()
        assert sum("stall" in row["why"] for row in state["kept"]) == 4
        assert all(row["gc_full"] for row in state["cycles"])
        assert c_off.tracer.state()["kept"] == []
        s_off.close()
        s_on.close()
        c_off.stop()
        c_on.stop()


# ---------------------------------------------------------------------------
# the planted stall: a cycle's ingest drain behind a held cache lock
# ---------------------------------------------------------------------------


def holder_of_the_cache_lock(cache, holding: threading.Event,
                             release: threading.Event):
    with cache._lock:
        holding.set()
        with allow_blocking("the planted stall: this IS the fault"):
            release.wait(120)


def _frames_of(tid):
    frame, names = sys._current_frames().get(tid), []
    while frame is not None:
        names.append(frame.f_code.co_name)
        frame = frame.f_back
    return names


def _until(what, timeout=120.0):
    """Wait for a state another thread reaches (nothing is measured)."""
    deadline = time.monotonic() + timeout
    while not what():
        assert time.monotonic() < deadline
        time.sleep(0.005)


class TestPlantedStall:
    HOLD_S = 0.8

    def test_a_held_cache_lock_is_one_kept_stall_that_names_the_holder(
            self, monkeypatch):
        """The hold is scripted on the injected clock (latency telemetry
        reads it too, so the decision's wait is the hold): the loop's thread
        sits in its ingest drain behind the lock for 800 ms of it, and the
        watchdog looks when the script says."""
        from kube_batch_tpu.utils import telemetry

        clock = VirtualClock(start=100.0)
        monkeypatch.setattr(telemetry, "perf_counter", clock.monotonic)
        sched = _watched(clock)         # EWMA 0.1 s: the bound is 400 ms
        cache = sched.cache
        wd = LoopWatchdog(sched)

        def cycle():
            sched.run_once_pipelined()
            sched.drain_pipeline()

        _add_gang(cache, 1)
        cycle()                         # compiles the solve
        cache.enable_ingest_staging()
        holding, release = threading.Event(), threading.Event()
        holder = threading.Thread(
            target=holder_of_the_cache_lock, name="holder",
            args=(cache, holding, release))
        loop = threading.Thread(target=cycle, name="loop")
        try:
            holder.start()
            assert holding.wait(60)
            _add_gang(cache, 2, size=1)     # staged; the drain needs the lock
            declared0, seconds0 = _stalls("cycle")
            loop.start()
            wd.loop_tid = loop.ident
            _until(lambda: "drain_staged_ingest" in _frames_of(loop.ident))
            clock.sleep(0.3)
            wd.check()                      # inside max(4 x 100, 250) ms
            assert _stalls("cycle")[0] == declared0
            clock.sleep(0.2)
            for _ in range(3):              # past it, however many looks
                wd.check()
            assert _stalls("cycle") == (declared0 + 1, seconds0)
            clock.sleep(self.HOLD_S - 0.5)
        finally:
            release.set()
            holder.join(timeout=60)
            if loop.ident is not None:
                loop.join(timeout=120)
        assert not holder.is_alive() and not loop.is_alive()
        wd.check()                          # the next look closes it
        assert _stalls("cycle") == (
            declared0 + 1, pytest.approx(seconds0 + self.HOLD_S))
        assert cache.binder.binds.get("tr/g2-0")
        (row,) = [r for r in sched.tracer.state()["kept"]
                  if "stall" in r["why"]]
        assert row["stall_ms"] == pytest.approx(self.HOLD_S * 1e3)
        assert row["decided"] == 1
        # the decision waited out the hold, and all of that wait has a name
        assert row["worst_ms"] == pytest.approx(self.HOLD_S * 1e3)
        assert row["worst_named_ms"] >= 0.9 * row["worst_ms"]
        # the ring of 4 rolls past it and the kept list still has it
        for _ in range(5):
            cycle()
        state = sched.tracer.state()
        assert row["cycle"] not in [r["cycle"] for r in state["cycles"]]
        tree = sched.tracer.cycle_tree(row["cycle"])
        (stall,) = tree["stalls"]
        assert stall["phase"] == "cycle"
        assert stall["declared_after_ms"] == pytest.approx(500.0)
        assert stall["span_path"].startswith("ingest_drain")
        held_by = [frames for name, frames in stall["threads"].items()
                   if name.startswith("holder-")]
        assert held_by and any(
            "holder_of_the_cache_lock" in f for f in held_by[0])
        assert any("drain_staged_ingest" in f for f in stall["stack"])
        cache.disable_ingest_staging()
        sched.close()
        cache.stop()

    def test_the_served_loop_declares_it_too(self):
        """The same fault through the served path — a pod posted over HTTP,
        ``run_forever`` and its own watchdog thread on the wall clock — with
        no duration asked of the machine: the lock is held UNTIL the
        watchdog has declared, however long a loaded machine takes."""
        from kube_batch_tpu.cmd.server import AdminServer

        cache = _mk_cache()
        # a floor far beyond the test: only an ingest wakes the loop, so
        # the lock is taken while it is parked and the one stage that can
        # meet it is the late pod's drain (a 50 ms floor put whichever
        # stage of a tick was running behind the lock instead)
        sched = Scheduler(cache, conf=load_scheduler_conf(None),
                          schedule_period=5.0)
        admin = AdminServer(cache, "127.0.0.1", 0)
        admin.start()

        def post(kind, body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{admin.port}/v1/{kind}",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            with urllib.request.urlopen(req, timeout=30) as r:
                assert json.loads(r.read())["ok"] is True

        def send(name):
            """A one-pod gang in q0, as a client posts it."""
            post("podgroups", [{"name": name, "namespace": "st",
                                "uid": f"pg-{name}", "min_member": 1,
                                "queue": "q0"}])
            post("pods", [{"name": name, "namespace": "st",
                           "uid": f"u-{name}", "requests": {"cpu": 500.0},
                           "phase": "Pending",
                           "annotations": {GROUP_NAME_ANNOTATION: name}}])

        loop = threading.Thread(target=sched.run_forever, daemon=True)
        loop.start()
        holding, release = threading.Event(), threading.Event()
        holder = threading.Thread(
            target=holder_of_the_cache_lock, name="holder",
            args=(cache, holding, release))
        try:
            send("warm")                    # compiles the solve
            _until(lambda: cache.binder.binds.get("st/warm"))
            _until(lambda: not sched._watchdog._open)  # a stall itself
            _until(lambda: [sp.name.startswith("park:") for sp in
                            sched.tracer.open_spans_of(loop.ident)[:1]]
                   == [True])
            declared0, _ = _stalls("cycle")
            holder.start()
            assert holding.wait(60)
            send("late")                    # staged; the drain needs the lock
            _until(lambda: _stalls("cycle")[0] > declared0)
            release.set()
            _until(lambda: cache.binder.binds.get("st/late"))
            # the cycle's stall; the pod's POST may reach the trigger after
            # its PodGroup's has already woken the loop into the held drain,
            # and that signal, which no cycle can start for meanwhile, is
            # then a ``parked`` stall of its own beside it
            def held():
                return [stall for rec in cache.flight_recorder.records()
                        for stall in rec.stalls
                        if any(name.startswith("holder-")
                               for name in stall["threads"])]

            # the bind is out before its cycle's record reaches the ring
            _until(lambda: any(s["phase"] == "cycle" for s in held()))
            stalls = held()
            cycle = [s for s in stalls if s["phase"] == "cycle"]
            assert cycle, "a record in the ring carries the cycle's stall"
            assert {s["phase"] for s in stalls} <= {"cycle", "parked"}
            # the cycle's stall is the drain's (the parked one beside it
            # may name the same open span: it is declared while that runs)
            assert all(s["span_path"].startswith("ingest_drain")
                       for s in cycle)
            assert any("drain_staged_ingest" in f for f in cycle[-1]["stack"])
        finally:
            release.set()
            sched.stop()
            loop.join(timeout=30)
            if holder.ident is not None:
                holder.join(timeout=30)
            admin.stop()
        assert not loop.is_alive()

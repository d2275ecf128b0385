"""The interruption ledger — intervals in which the loop thread could not
make progress for a reason no span names.

Three sources feed it.  Two live here, the third beside the span plane it
extends (``obs/trace.py``: every compile JAX reports, logged with its
program, its span path and the rungs of the dispatch that paid).

**Garbage collections** (:class:`GCLedger`, one for the process).  CPython's
collector stops every thread under the GIL and a full (generation-2)
collection is linear in the tracked objects: tens to hundreds of
milliseconds at 50,000 pods.  One ``gc.callbacks`` hook stamps the
telemetry clock at ``start`` and ``stop`` and adds into per-generation
totals.  It takes no lock: the collector holds the GIL, does not re-enter,
and can run inside any allocation, one made under a metric's lock
included, so ``/metrics`` reads the totals when it renders
(``volcano_gc_collections_total`` / ``volcano_gc_pause_seconds_total``).
Root spans sample the totals at entry and exit (``gc_ms`` / ``gc_full``),
as device spans sample ``jitstats``; a full collection is also a
``gc:gen2`` annotation on the profiler's host plane, so the device's idle
gap it causes carries that name.

**Stalls** (:class:`LoopWatchdog`, owned by ``Scheduler.run_forever``).  A
look every 100 ms of wall time at state the loop already keeps declares a
stall when an ingest signal has been left unconsumed (``parked``) or the
loop thread's outermost span has been open (``cycle``) for longer than four
times what the loop expects and 250 ms.  Once per stall it takes the loop
thread's stack, every other thread's innermost frames, the open-span path,
the collector's totals and any compile in flight; the stall is closed with
its whole duration when the loop moves again, and the record rides the
cycle it belongs to into the flight recorder's kept list.
"""

from __future__ import annotations

import gc
import logging
import sys
import threading
from typing import Dict, List, Optional

from kube_batch_tpu import metrics
from kube_batch_tpu.utils import telemetry

logger = logging.getLogger("kube_batch_tpu")


class GCLedger:
    """Collections and pause seconds of the process's garbage collector,
    by generation.  Written by the collector's callback alone, read by
    anyone without a lock (a reader may see a count one ahead of its
    seconds for an instant)."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        # what a root span samples: seconds of every generation, and the
        # full collections
        self.pause_s = 0.0
        self.full = 0
        self._t0 = 0.0
        # the telemetry seam's clock as it is now: a test that swaps the
        # seam for a scripted clock must not have a collection read it
        self._clock = telemetry.perf_counter
        self._annotate = None
        self._annotation = None

    def install(self, annotate=None) -> None:
        """Hook the collector; the caller sees to it that one thread does
        (``obs.trace._listen_for_compiles``, under its lock).
        ``annotate(name)`` returns an entered profiler annotation."""
        if self._on_gc in gc.callbacks:
            return
        self._annotate = annotate
        metrics.metrics.GC_COLLECTIONS.poll = lambda: {
            (str(g),): float(n) for g, n in enumerate(self.collections)}
        metrics.metrics.GC_PAUSE_SECONDS.poll = lambda: {
            (str(g),): s for g, s in enumerate(self.seconds)}
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict) -> None:
        generation = info["generation"]
        if phase == "start":
            if generation == 2 and self._annotate is not None:
                # hundreds of young collections a second stay off the trace
                self._annotation = self._annotate("gc:gen2")
            self._t0 = self._clock()
            return
        t0, self._t0 = self._t0, 0.0
        if not t0:
            return  # hooked in the middle of this collection
        paused = self._clock() - t0
        self.seconds[generation] += paused
        self.collections[generation] += 1
        self.pause_s += paused
        if generation == 2:
            self.full += 1
            annotation, self._annotation = self._annotation, None
            if annotation is not None:
                annotation.__exit__(None, None, None)

    def totals(self) -> Dict:
        return {"collections": list(self.collections),
                "pause_s": [round(s, 6) for s in self.seconds]}


#: the process's collector ledger (hooked by the first Tracer)
GC = GCLedger()


def _frames(frame, limit: int) -> List[str]:
    """``file:line function`` of a thread's frames, innermost last: no
    source lines (linecache would read files on the watchdog's thread)."""
    out = []
    while frame is not None and len(out) < limit:
        code = frame.f_code
        out.append(f"{code.co_filename}:{frame.f_lineno} {code.co_name}")
        frame = frame.f_back
    out.reverse()
    return out


def _span_seconds(span, now: float) -> float:
    """How long ``span`` lasted, or has lasted at ``now`` (injected clock),
    on the clock its age was judged on."""
    if span.vt0 is not None:
        return (span.vt1 if span.vt1 is not None else now) - span.vt0
    return (span.t1 or telemetry.perf_counter()) - span.t0


class LoopWatchdog:
    """Declares the stalls of one ``Scheduler.run_forever`` loop.

    :meth:`check` is a plain function of the scheduler's injected clock and
    of what the loop already keeps (the trigger's unconsumed signal, the
    floor in force, the cycle-cost EWMA, the loop thread's open spans); the
    thread that calls it every :attr:`TICK_S` of wall time exists only
    between :meth:`start` (on the loop thread) and :meth:`stop`.  Both
    bounds are fixed multiples of the loop's own quantities, not knobs."""

    TICK_S = 0.1
    #: a stall is this many times what the loop expects, and at least 250 ms
    FACTOR = 4
    FLOOR_S = 0.25
    #: frames taken of the loop thread, and of every other thread
    LOOP_FRAMES = 64
    OTHER_FRAMES = 12

    def __init__(self, sched):
        self.sched = sched
        self.loop_tid: Optional[int] = None
        # phase -> (what identifies the stall, its record or None)
        self._open: Dict[str, tuple] = {}
        self._halt = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- the thread -------------------------------------------------------
    def start(self) -> None:
        """Watch the calling thread's loop."""
        self.loop_tid = threading.get_ident()
        thread = threading.Thread(
            target=self._run, name="kb-loop-watchdog", daemon=True)
        thread.start()
        # published once it runs: a stop() from another thread joins a
        # started thread or finds none (one it missed sees the halt at once)
        self._thread = thread

    def stop(self) -> None:
        """End the thread and close what is still open with its duration
        so far (the loop is going away, not moving again)."""
        self._halt.set()
        thread = self._thread
        if thread is not None and thread is not threading.current_thread():
            thread.join()
        now = self.sched.clock.monotonic()
        for phase, (key, _) in list(self._open.items()):
            self._close(phase, now - key if phase == "parked"
                        else _span_seconds(key, now))

    def _run(self) -> None:
        while not self._halt.wait(self.TICK_S):
            try:
                self.check()
            except Exception:  # noqa: BLE001 — diagnostics, never the loop
                logger.exception("loop watchdog check failed")

    # -- one look ---------------------------------------------------------
    def check(self) -> None:
        now = self.sched.clock.monotonic()
        self._check_parked(now)
        self._check_cycle(now)

    def _check_parked(self, now: float) -> None:
        """An ingest signal nobody has consumed: work is waiting and no
        cycle has started for it.  The settle hold keeps a signal for up to
        its cap on purpose, so the cap is no part of the stall."""
        sched = self.sched
        pending, since, consumed = sched.trigger.unconsumed()
        held = self._open.get("parked")
        if held is not None:
            if consumed is not None and consumed[0] == held[0]:
                self._close("parked", consumed[1] - held[0])
            elif pending is None or since != held[0]:
                self._close("parked", now - held[0])  # taken by poll()
            return
        if pending != "ingest":
            return
        settle = sched.settle_window()
        bound = (max(self.FACTOR * sched.min_period, self.FLOOR_S)
                 + (settle[1] if settle else 0.0))
        age = now - since
        if age > bound:
            self._declare("parked", since, age,
                          telemetry.perf_counter() - age)

    def _check_cycle(self, now: float) -> None:
        """The loop thread's outermost open span, parked time apart: a
        stage of the cycle that has not returned."""
        held = self._open.get("cycle")
        if held is not None:
            if held[0].t1:
                self._close("cycle", _span_seconds(held[0], now))
            return
        stack = self.sched.tracer.open_spans_of(self.loop_tid)
        root = stack[0] if stack else None
        if root is None or root.name.startswith("park:"):
            return
        if root.vt0 is not None:
            age = now - root.vt0
        elif root.t0:
            age = telemetry.perf_counter() - root.t0
        else:
            return  # pushed, not stamped yet
        ewma = self.sched.cycle_cost_ewma or 0.0
        if age > max(self.FACTOR * ewma, self.FLOOR_S):
            self._declare("cycle", root, age, root.t0)

    # -- the record -------------------------------------------------------
    def _declare(self, phase: str, key, age: float, t0: float) -> None:
        metrics.register_loop_stall(phase)
        tracer = self.sched.tracer
        record = None
        path = tracer.open_path(self.loop_tid)
        if tracer.enabled:
            frames = sys._current_frames()
            names = {t.ident: t.name for t in threading.enumerate()}
            me = threading.get_ident()
            record = {
                "phase": phase,
                "t0": round(t0, 6),
                "declared_after_ms": round(age * 1e3, 3),
                "dur_ms": None,  # until the loop moves again
                "span_path": path,
                "stack": _frames(frames.get(self.loop_tid),
                                 self.LOOP_FRAMES),
                "threads": {
                    f"{names.get(tid, '?')}-{tid}": _frames(
                        frame, self.OTHER_FRAMES)
                    for tid, frame in frames.items()
                    if tid not in (self.loop_tid, me)},
                "gc": GC.totals(),
                "compiling": tracer.compile_in_flight(),
            }
            tracer.note_stall(record, waits_for_cycle=phase == "parked")
        self._open[phase] = (key, record)
        logger.warning("scheduling loop stalled (%s) for %.0f ms so far, "
                       "under %s", phase, age * 1e3, path or "no span")

    def _close(self, phase: str, seconds: float) -> None:
        _, record = self._open.pop(phase)
        seconds = max(seconds, 0.0)
        metrics.observe_loop_stall_seconds(phase, seconds)
        if record is not None:
            record["dur_ms"] = round(seconds * 1e3, 3)

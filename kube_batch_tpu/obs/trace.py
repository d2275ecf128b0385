"""Cycle tracing plane — structured spans over the pipelined scheduling
cycle (the Dapper span model, Sigelman et al. 2010, sized for one process).

Every stage of the staged cycle — ingest drain, delta session open, solve
dispatch, device wait, host replay, status derive, the overlapped
writeback — runs inside a context-manager :class:`Span`; per-action and
per-plugin child spans nest under them through a per-thread stack.  Wall
time is stamped through the ONE sanctioned seam (``utils.telemetry``;
KBT001's deliberate exception), virtual time through the injected clock
(the sim's ``VirtualClock``), so a traced sim run attributes stages on the
same clock its report uses.  Device work is attributed via
``utils/jitstats``: a :meth:`Tracer.device_span` samples the jit
compile-specialization count and the resident-scatter counters at entry
and exit, so a retrace or an unexpected full re-upload is annotated onto
the exact span that paid it (``retrace``/scatter deltas).  Every compile
JAX itself reports (``jax.monitoring`` duration events: trace, lower,
backend) is counted process-wide with its seconds and stamped onto the
innermost span open on the compiling thread (``compiles``/``compile_ms``)
— programs nobody registered with jitstats included.

Every span also opens a ``jax.profiler.TraceAnnotation`` of its own name
for its own interval, so under a profiler session the span tree sits on
the host plane of the ``.xplane.pb`` beside the XLA events, on the
profiler's clock (with no session running it costs a fraction of a
microsecond).  The loop's parked time is two root spans
(``park:floor``/``park:event``, :meth:`Tracer.park_span`) retained on the
record of the cycle they precede; the read plane's flush tree
(``whatif:*``, :meth:`Tracer.detached_span`) is timed, annotated and
totalled but kept out of the cycle records.  ``span_counts``/``span_ms``
total every span by name, children and roots alike, and under ``between``
the time from one root of the cycle thread to the next.

What stops the loop for a reason no span names is on the same plane
(:mod:`kube_batch_tpu.obs.interruptions`): every root span samples the
garbage collector's pause totals at entry and exit (``gc_ms``/``gc_full``;
a pause stops every thread, so it is charged to whatever root was open on
any thread), the gap before a root carries the pause that fell in it
(``gap_gc_ms``), every compile is logged with its program, its span path
and the attributes of the dispatch span that paid (``compiles`` on
``/v1/trace``), and the stalls the loop's watchdog declares ride the
record of the cycle they belong to.

Complete per-cycle trace trees land in the flight recorder's ring
(:mod:`kube_batch_tpu.obs.recorder`) and export as Chrome trace-event
JSON (``chrome_trace``), so ``chrome://tracing`` / Perfetto render the
pipelined overlap directly — the writeback span rides its own thread
track and visibly overlaps the next cycle's compute.

Tracing is INERT by construction: spans only read clocks and counters,
never scheduling state — trace-on vs trace-off cycle decisions are
bit-identical (tests/test_trace.py pins this over randomized churn).
``KB_TRACE=0`` additionally disables retention (ring, attrs, device
sampling, dumps); spans still stamp their own wall time either way, so
the latency metrics they feed (action/plugin/stage histograms) never
change meaning with the knob.

KBT014 (kube_batch_tpu/analysis) enforces the discipline: in the
clock-seamed paths spans are created only via these context managers, and
span bodies read no raw ``time.*`` and no ad-hoc ``telemetry.perf_counter``
pairs — the span IS the measurement; metrics feed from ``Span.dur_us``.
"""

from __future__ import annotations

import bisect
import contextlib
import os
import threading
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from kube_batch_tpu import metrics
from kube_batch_tpu.envutil import env_flag
from kube_batch_tpu.obs.interruptions import GC
from kube_batch_tpu.utils import telemetry

import time as _time  # identity sentinel only: `clock is _time` ⇒ no vt


#: root spans per implicit record before it rolls into the ring — callers
#: that drive open/close directly (bench one_cycle, tests) never call
#: begin_cycle, and an unbounded current record would grow forever
IMPLICIT_ROLL = 512

#: where a finished ROOT span is retained (``Span._keep``): on the current
#: cycle's record (the default), on the record of the cycle that FOLLOWS it
#: (the loop's parked time), or nowhere (the read plane's flush tree)
_KEEP_CYCLE, _KEEP_NEXT, _KEEP_NONE = 0, 1, 2

#: cycle starts remembered for the leftover count — two are what the test
#: "two or more cycles drained ingest since the pod arrived" needs
_CYCLE_STARTS = 4

#: the newest backend compiles kept with their names (``compiles`` on
#: /v1/trace), and the largest decision latencies kept of one cycle
COMPILE_LOG = 64
TOP_LATENCIES = 16

#: the spans whose attributes say which program a compile under them was
#: for (``program``, ``engaged``, ``mode``, ``bucket``, ``rungs``, ...)
_DISPATCH_SPANS = ("solve_dispatch", "audit_dispatch", "whatif:probe")

# the open spans of each thread, innermost last.  ONE stack per thread for
# every tracer of the process: a cache has one tracer and a thread works for
# one cache at a time, and JAX's process-wide compile listener has to find
# the span the compiling thread is inside without knowing whose it is.
_OPEN = threading.local()
# the same stacks by thread id, for the one reader that is not the owner:
# the loop's watchdog looks at the loop thread's open spans from its own
_STACKS: Dict[int, list] = {}


def _open_spans() -> list:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
        if len(_STACKS) >= 64:
            alive = {t.ident for t in threading.enumerate()}
            for tid in [t for t in _STACKS if t not in alive]:
                del _STACKS[tid]
        _STACKS[threading.get_ident()] = stack
    return stack


_TraceAnnotation = None  # jax.profiler.TraceAnnotation, imported at first use


def _annotation(name: str):
    """An entered ``TraceAnnotation`` of ``name``."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    ann = _TraceAnnotation(name)
    ann.__enter__()
    return ann

#: jax.monitoring duration events of one compile -> the phase label
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_listener_lock = threading.Lock()
_listening = False
# thread id -> (phase, function name) of the compile phase that thread is
# in: what a stall record says was in flight
_COMPILING: Dict[int, tuple] = {}


def _on_compile_start(event: str, _value, fun_name: str = "", **_kw) -> None:
    """JAX's scalar listener: a compile phase is entered (it reports the
    phase's start time under the event's name)."""
    phase = _COMPILE_PHASES.get(event)
    if phase is not None:
        _COMPILING[threading.get_ident()] = (phase, fun_name)


def _on_compile_event(event: str, duration: float, fun_name: str = "",
                      **_kw) -> None:
    """JAX's duration listener (runs on the compiling thread): every trace,
    lowering and backend compile of the process with its seconds — also of
    programs ``utils/jitstats`` never heard of and of compiles outside any
    device span — stamped onto the innermost span that thread has open and
    logged, with the function's name, by that span's tracer."""
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    _COMPILING.pop(threading.get_ident(), None)
    metrics.register_jit_compile(phase, duration)
    stack = getattr(_OPEN, "stack", None)
    if stack:
        stack[-1]._note_compile(phase, duration)
        stack[-1]._tracer._log_compile(stack, phase, duration, fun_name)


def _listen_for_compiles() -> None:
    """Register the process-wide listeners, once: JAX's compile events and
    the garbage collector's callback."""
    global _listening
    with _listener_lock:
        if not _listening:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                _on_compile_event)
            monitoring.register_scalar_listener(_on_compile_start)
            GC.install(annotate=_annotation)
            _listening = True


class Span:
    """One traced region.  Created ONLY via the :class:`Tracer` context
    managers (rule KBT014); re-entrant use of a single instance is not
    supported — every ``span()`` call makes a fresh one."""

    __slots__ = ("name", "t0", "t1", "vt0", "vt1", "tid", "attrs",
                 "children", "_tracer", "_record", "_cols", "_c0", "_sc0",
                 "_keep", "_annotation", "_gc0", "_gap")

    def __init__(self, tracer: "Tracer", name: str,
                 record: Optional["CycleRecord"] = None,
                 cols=None, attrs: Optional[Dict] = None,
                 keep: int = _KEEP_CYCLE):
        self.name = name
        self.t0 = self.t1 = 0.0
        self.vt0 = self.vt1 = None
        self.tid = 0
        self.attrs = attrs
        self.children: List["Span"] = []
        self._tracer = tracer
        self._record = record  # explicit target (the writeback worker)
        self._cols = cols
        self._c0 = self._sc0 = None
        self._keep = keep
        self._annotation = None
        # a ROOT's samples of the collector's totals at entry, and the gap
        # since the cycle thread's last root: (seconds, pause seconds and
        # full collections that fell in it)
        self._gc0 = None
        self._gap = None

    # -- timing -----------------------------------------------------------
    @property
    def dur_ms(self) -> float:
        return (self.t1 - self.t0) * 1e3

    @property
    def dur_us(self) -> float:
        return (self.t1 - self.t0) * 1e6

    def set(self, **attrs) -> None:
        """Annotate the span (no-op when retention is disabled so the
        disabled tracer stays allocation-free on the attr path)."""
        if not self._tracer.enabled:
            return
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)

    def _note_compile(self, phase: str, secs: float) -> None:
        """One compile phase JAX reported while this span was the innermost
        open one on its thread: which stage paid, and how much."""
        if not self._tracer.enabled:
            return
        attrs = self.attrs
        if attrs is None:
            attrs = self.attrs = {}
        if phase == "backend":
            attrs["compiles"] = attrs.get("compiles", 0) + 1
        attrs["compile_ms"] = round(
            attrs.get("compile_ms", 0.0) + secs * 1e3, 3)

    # -- context manager --------------------------------------------------
    def __enter__(self) -> "Span":
        tracer = self._tracer
        self.tid = threading.get_ident()
        stack = _open_spans()
        root = not stack
        stack.append(self)
        # device-attribution sampling happens OUTSIDE the stamped window so
        # the counter reads never inflate the span's own duration — and
        # inside a guard: attribution must never hurt a cycle, and a probe
        # that raised AFTER the stack push would leak the entry and corrupt
        # this thread's nesting for good
        if tracer.enabled and self._cols is not None:
            try:
                from kube_batch_tpu.utils import jitstats

                self._c0 = jitstats.total_compiles()
                self._sc0 = _scatter_totals(self._cols)
            except Exception:  # noqa: BLE001
                self._c0 = self._sc0 = None
        clock = tracer.clock
        if clock is not None:
            self.vt0 = clock.monotonic()
        # the same interval on the profiler's clock: under a profiler
        # session the span shows on the host plane beside the XLA events
        # (with none running this is a fraction of a microsecond)
        self._annotation = _annotation(self.name)
        if not root:
            self.t0 = telemetry.perf_counter()
            return self
        # interruptions are charged to roots: the collector's totals now,
        # and, for a root of the cycle thread (a stage or parked time), how
        # long ago that thread's last root ended and what was collected
        # meanwhile — the loop's time outside every root span
        self._gc0 = gc0 = (GC.pause_s, GC.full)
        self.t0 = t0 = telemetry.perf_counter()
        if self._record is None and self._keep != _KEEP_NONE:
            last = getattr(_OPEN, "root_end", None)
            if last is not None and last[3] is tracer:
                self._gap = (t0 - last[0], gc0[0] - last[1], gc0[1] - last[2])
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = telemetry.perf_counter()
        tracer = self._tracer
        try:
            self._annotation.__exit__(exc_type, exc, tb)
            clock = tracer.clock
            if clock is not None:
                self.vt1 = clock.monotonic()
            if tracer.enabled and self._c0 is not None:
                from kube_batch_tpu.utils import jitstats

                compiles = jitstats.total_compiles() - self._c0
                if compiles:
                    # a retrace annotated onto the OWNING span — the signal
                    # the flat jit counters could never localize (a count of
                    # new specializations of the functions jitstats tracks;
                    # ``compiles`` is the compile listener's)
                    self.set(retrace=compiles)
                sc = _scatter_totals(self._cols)
                delta = {k: sc[k] - self._sc0.get(k, 0)
                         for k in sc if sc[k] != self._sc0.get(k, 0)}
                if delta:
                    self.set(resident=delta)
            if exc_type is not None:
                self.set(error=exc_type.__name__)
            gc0 = self._gc0
            if gc0 is not None:
                paused, full = GC.pause_s, GC.full
                if paused != gc0[0]:
                    self.set(gc_ms=round((paused - gc0[0]) * 1e3, 3))
                    if full != gc0[1]:
                        self.set(gc_full=full - gc0[1])
                if self._record is None and self._keep != _KEEP_NONE:
                    _OPEN.root_end = (self.t1, paused, full, tracer)
        except Exception:  # noqa: BLE001 — attribution only; the stack
            pass           # unwind below must ALWAYS run
        finally:
            stack = _open_spans()
            stack.pop()
            if stack and self._record is None:
                if tracer.enabled:
                    stack[-1].children.append(self)
                    tracer._count_span(self)
            else:
                tracer._close_root(self)
        return False

    # -- export -----------------------------------------------------------
    def to_dict(self) -> Dict:
        d: Dict = {"name": self.name, "dur_ms": round(self.dur_ms, 4)}
        if self.vt0 is not None:
            d["vt0"] = round(self.vt0, 6)
            if self.vt1 is not None:
                d["vt_dur"] = round(self.vt1 - self.vt0, 6)
        if self._gap is not None:
            d["gap_ms"] = round(self._gap[0] * 1e3, 4)
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d


def _scatter_totals(cols) -> Dict[str, int]:
    """Flattened per-path resident-cache counters ({path.counter: n}) —
    the delta between a device span's entry and exit attributes scatter /
    full-upload traffic to the owning dispatch."""
    out: Dict[str, int] = {}
    try:
        for path, c in cols.resident_counters().items():
            for k, v in c.items():
                out[f"{path}.{k}"] = int(v)
    except Exception:  # noqa: BLE001 — attribution must never hurt a cycle
        pass
    return out


class CycleRecord:
    """One cycle's complete trace tree.  Root spans are appended by the
    cycle thread; the overlapped writeback span arrives from its worker
    thread AFTER the record was finalized into the ring — appends are
    guarded by the tracer's lock."""

    __slots__ = ("cycle", "reason", "t0", "t1", "vt0", "vt1", "spans",
                 "attrs", "closed", "decisions", "stalls", "compile_ms",
                 "_lat", "_seen")

    def __init__(self, cycle: int, reason: str, t0: float,
                 vt0: Optional[float]):
        self.cycle = cycle
        self.reason = reason
        self.t0 = t0
        self.t1: Optional[float] = None
        self.vt0 = vt0
        self.vt1: Optional[float] = None
        self.spans: List[Span] = []
        self.attrs: Dict = {}
        self.closed = False
        # the arrival→decision latencies the cycle closed, summarised when
        # it is finalized (:meth:`summarize`): a record does not carry
        # 50,000 floats through the ring for a cold drain
        self.decisions: Optional[Dict] = None
        self._lat: List = []   # the samples, as observed, until then
        # [earliest arrival, worst latency (ms), when it was bound, cycles
        # started since that arrival] over the binds so far
        self._seen: Optional[List] = None
        # the interruptions that are no span attribute: the stalls the
        # loop's watchdog declared (obs/interruptions.py), and the seconds
        # of the compiles JAX reported under this cycle's spans (ms)
        self.stalls: List[Dict] = []
        self.compile_ms = 0.0

    def summarize(self) -> None:
        """Reduce the cycle's latency samples to what the table of cycles
        and the kept rule read: how many, the worst, the median, the
        :data:`TOP_LATENCIES` largest, and for the earliest arrival its
        wait until this cycle started and the cycles started since."""
        chunks, self._lat = self._lat, []
        if not chunks or self._seen is None:
            return
        lat = np.sort(
            np.concatenate([np.asarray(c, np.float64) for c in chunks]))
        n = int(lat.size)
        earliest, worst, worst_at, spanned = self._seen
        self.decisions = {
            "decided": n,
            "worst_ms": round(float(lat[-1]), 3),
            "median_ms": round(float(lat[(n - 1) // 2] + lat[n // 2]) / 2, 3),
            "top_ms": [round(float(v), 3)
                       for v in lat[::-1][:TOP_LATENCIES]],
            "wait_ms": round(max(self.t0 - earliest, 0.0) * 1e3, 3),
            "spanned": spanned,
            # the interval of the worst one, on the telemetry clock
            "worst_from": round(worst_at - worst / 1e3, 6),
            "worst_at": round(worst_at, 6),
        }

    def to_dict(self) -> Dict:
        d = {
            "cycle": self.cycle,
            "reason": self.reason,
            "t0": round(self.t0, 6),
            "dur_ms": (round((self.t1 - self.t0) * 1e3, 4)
                       if self.t1 is not None else None),
            "spans": [s.to_dict() for s in self.spans],
        }
        if self.vt0 is not None:
            d["vt0"] = round(self.vt0, 6)
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.decisions is not None:
            d["decisions"] = dict(self.decisions)
        if self.stalls:
            d["stalls"] = [dict(st) for st in self.stalls]
        if self.compile_ms:
            d["compile_ms"] = round(self.compile_ms, 3)
        return d


class Tracer:
    """The per-cache span recorder.  One instance per SchedulerCache
    (``tracer_of``); the Scheduler re-points ``clock`` at its injected
    clock so virtual-time stamps follow the sim."""

    def __init__(self, clock=None, recorder=None, enabled: Optional[bool] = None):
        self.enabled = (
            enabled if enabled is not None else env_flag("KB_TRACE", True)
        )
        # vt stamps only for a real injected clock — the wall-clock default
        # would duplicate t0/t1 into the vt fields
        self.clock = None if clock is None or clock is _time else clock
        self.recorder = recorder
        if recorder is not None:
            # a disabled tracer never feeds the ring, so the recorder must
            # not ARM captures either — an armed window that can never
            # settle (record_cycle is the settle path) would accumulate
            # forever on a long-running KB_TRACE=0 server
            recorder.enabled = self.enabled
        # arrival→decision SLO (ms) that arms a flight dump; 0 = off
        try:
            self.slo_ms = float(os.environ.get("KB_TRACE_SLO_MS", "0") or 0)
        except ValueError:
            self.slo_ms = 0.0
        self._mu = threading.Lock()
        self._next_cycle = 0  # the number begin_cycle gives out next
        self.current: Optional[CycleRecord] = None
        # parked-time spans waiting for the record of the cycle they precede
        self._preceding: List[Span] = []
        # the newest root of each detached tree (the read plane's last
        # flush), for /v1/trace: what a dump of the cycles cannot show
        self._last_detached: Dict[str, Span] = {}
        # when the last few cycles started: a cycle drains ingest first, so
        # a pod that arrived before two of these was passed over by a cycle
        self._cycle_starts: deque = deque(maxlen=_CYCLE_STARTS)
        # the newest backend compiles with their names, and the compile
        # each thread is in the middle of (trace, lower, then backend)
        self._compiles: deque = deque(maxlen=COMPILE_LOG)
        self._compile_open: Dict[int, Dict] = {}
        # stalls that wait for the record of the cycle that follows them
        self._stalls_waiting: List[Dict] = []
        # seed-stable longitudinal stats (the sim report's section)
        self.cycles_total = 0
        self.spans_total = 0
        self.span_counts: Dict[str, int] = {}
        # wall milliseconds by span name, children and roots alike (NOT
        # seed-stable, so not in stage_attribution; on /v1/trace only:
        # ~30 names would be ~60 more lines on every /metrics scrape)
        self.span_ms: Dict[str, float] = {}
        self.retraces_attributed = 0
        _listen_for_compiles()

    # -- cycle bracket ----------------------------------------------------
    def begin_cycle(self, reason: str = "tick") -> CycleRecord:
        """Open a new cycle record (finalizing any implicit predecessor);
        returns the record so the pipelined caller can hand it to the
        writeback worker."""
        vt0 = self.clock.monotonic() if self.clock is not None else None
        t0 = telemetry.perf_counter()
        with self._mu:
            rec = CycleRecord(self._take_cycle_number(), reason, t0, vt0)
            # the parked time before this cycle leads its record, and the
            # stalls of that time ride it
            rec.spans, self._preceding = self._preceding, []
            rec.stalls, self._stalls_waiting = self._stalls_waiting, []
            self._cycle_starts.append(t0)
            prev, self.current = self.current, rec
        if prev is not None:
            self._finalize(prev)
        return rec

    def _take_cycle_number(self) -> int:
        """The next record's number (caller holds ``_mu``)."""
        n = self._next_cycle
        self._next_cycle = n + 1
        return n

    def next_cycle_number(self) -> int:
        """The number the next ``begin_cycle`` will give its record — what
        a parked-time span says it precedes."""
        with self._mu:
            return self._next_cycle

    def end_cycle(self) -> None:
        with self._mu:
            rec, self.current = self.current, None
        if rec is not None:
            self._finalize(rec)

    def _finalize(self, rec: CycleRecord) -> None:
        rec.t1 = telemetry.perf_counter()
        if self.clock is not None:
            rec.vt1 = self.clock.monotonic()
        rec.closed = True
        rec.summarize()
        with self._mu:
            self.cycles_total += 1
        recorder = self.recorder
        if recorder is not None and self.enabled:
            recorder.record_cycle(rec)

    def _count_span(self, span: Span) -> None:
        with self._mu:
            self.spans_total += 1
            self.span_counts[span.name] = (
                self.span_counts.get(span.name, 0) + 1
            )
            self.span_ms[span.name] = (
                self.span_ms.get(span.name, 0.0) + span.dur_ms
            )
            if span.attrs and span.attrs.get("retrace"):
                self.retraces_attributed += int(span.attrs["retrace"])
            gap = span._gap
            if gap is not None:
                # the cycle thread's time between two of its roots: no
                # stage on /metrics, a name of its own here
                self.span_counts["between"] = (
                    self.span_counts.get("between", 0) + 1)
                self.span_ms["between"] = (
                    self.span_ms.get("between", 0.0) + gap[0] * 1e3)
                if gap[1] > 0:
                    span.set(gap_gc_ms=round(gap[1] * 1e3, 3))
                    if gap[2]:
                        span.set(gap_gc_full=gap[2])

    def _close_root(self, span: Span) -> None:
        """A span finished with no parent on its thread: attach it to its
        record (explicit for writeback spans, else the current cycle) and
        feed the per-stage latency surface.  The histogram observes even
        with KB_TRACE=0 — the knob disables RETENTION (ring, dumps, device
        attribution), never the latency metrics spans feed (the same
        contract as the action/plugin histograms reading sp.dur_us).

        The read plane's detached trees stay off the histogram too: their
        totals are on /v1/trace, and a stage label is 13 more lines on a
        page the benchmark's decision channel renders every few ms."""
        keep = span._keep
        if keep != _KEEP_NONE:
            metrics.observe_stage_latency(span.name, span.dur_ms)
        if self.enabled:
            self._count_span(span)
            if keep != _KEEP_CYCLE:
                with self._mu:
                    if keep == _KEEP_NONE:
                        self._last_detached[span.name] = span
                    else:
                        # bounded: a loop that parks and never cycles again
                        # (shutdown) must not grow the list
                        self._preceding = self._preceding[-3:] + [span]
                return
            with self._mu:
                rec = span._record
                if rec is None:
                    rec = self.current
                    if rec is None:
                        # direct-driven flows (bench one_cycle, tests) never
                        # bracket cycles — collect under an implicit record
                        rec = self.current = CycleRecord(
                            self._take_cycle_number(), "implicit", span.t0,
                            span.vt0
                        )
                rec.spans.append(span)
                roll = (rec is self.current
                        and rec.reason == "implicit"
                        and len(rec.spans) >= IMPLICIT_ROLL)
                if roll:
                    self.current = None
            if roll:
                self._finalize(rec)

    # -- span factories (rule KBT014: THE sanctioned constructors) --------
    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs=attrs or None)

    def device_span(self, name: str, cols=None, **attrs) -> Span:
        """A span that attributes device work: jit compile delta (retraces
        land on the owning span) and resident scatter/upload deltas."""
        return Span(self, name, cols=cols if self.enabled else None,
                    attrs=attrs or None)

    def cycle_span(self, name: str, record: Optional[CycleRecord],
                   **attrs) -> Span:
        """A root span explicitly targeted at ``record`` — the overlapped
        writeback stage runs on its own worker thread after its cycle's
        record was already finalized into the ring."""
        return Span(self, name, record=record, attrs=attrs or None)

    def park_span(self, name: str, **attrs) -> Span:
        """A root span of the loop's parked time between two cycles: it
        feeds the stage histogram like any root, and is retained on the
        record of the cycle it PRECEDES (no cycle is open while the loop
        is parked, and an implicit record each would flood the ring)."""
        return Span(self, name, attrs=attrs or None, keep=_KEEP_NEXT)

    def detached_span(self, name: str, **attrs) -> Span:
        """A root span of a plane that is no part of the cycle (the read
        plane's flush, ~28 a second): it and its children are timed,
        annotated for the profiler and totalled by name, and kept out of
        the cycle records and the ring, which the solving cycles would
        otherwise leave within seconds."""
        return Span(self, name, attrs=attrs or None, keep=_KEEP_NONE)

    @contextlib.contextmanager
    def tallied_span(self, name: str, seconds: float):
        """A span for work that was timed where it ran, in pieces (a tally
        kept inside a choke point many callers go through): opened after
        the work, as a child of the stage that held it, with its start set
        back by ``seconds``, so that its duration is the tally."""
        with self.span(name) as sp:
            sp.t0 -= seconds
            yield sp

    # -- cycle annotations -------------------------------------------------
    def note_solve_dispatch(self, span: Span, action: str, mode: str,
                            engaged, program: Optional[str] = None,
                            bucket: Optional[int] = None,
                            rungs=None) -> None:
        """Say which program a ``solve_dispatch`` span ran (``mode``,
        ``engaged``, ``program``) and count it on ``/metrics``
        (``volcano_solve_dispatches_total``), from the same values: the
        span's attributes and the counter cannot drift apart.  ``program``
        defaults to :func:`solve_program` of ``engaged``.  The counter
        moves with ``KB_TRACE=0`` too, like every metric a span feeds.
        ``bucket`` (the pending bucket of a compacted solve) and ``rungs``
        (a warm plan's ``[merge, rerank, changed]`` rungs) are the shapes
        the program was compiled for: a compile logged under this span
        (:meth:`_log_compile`) is named by them."""
        if program is None:
            program = solve_program(engaged)
        span.set(mode=mode, engaged=list(engaged), program=program)
        if bucket is not None:
            span.set(bucket=bucket)
        if rungs is not None:
            span.set(rungs=list(rungs))
        metrics.register_solve_dispatch(action, mode, program)

    def note_evict_dispatch(self, span: Span, action: str, mode: str,
                            engaged, *, compact: bool, claimants: int,
                            bucket: Optional[int]) -> None:
        """:meth:`note_solve_dispatch` for an evict solve
        (``program="evict"``), and on which claimant axis its bids ran:
        ``compact`` (the pending bucket, not the whole task axis),
        ``claimants`` (pending rows) and ``bucket`` (the one bucket the
        task axis compacts into; 0 where it is too small to have one) on
        the span, and ``volcano_evict_solve_compacted_total{action,
        compacted}`` from the same value."""
        self.note_solve_dispatch(span, action, mode, engaged,
                                 program="evict")
        span.set(compact=compact, claimants=claimants, bucket=bucket or 0)
        metrics.register_evict_solve_compacted(action, compact)

    def note_solve_rounds(self, span: Span, action: str, rounds: int,
                          per_pass: int) -> None:
        """Say on a ``device_wait`` span how many bidding rounds its solve
        ran (``rounds``) and whether that is more than one pass has
        (``over_budget``: a pass ended with work left and the next carried
        on), and count both on ``/metrics`` (``volcano_solve_rounds_total``,
        ``volcano_solve_over_budget_total``) from the same values."""
        over_budget = rounds > per_pass
        span.set(rounds=rounds, over_budget=over_budget)
        metrics.register_solve_rounds(action, rounds, over_budget)

    def note_evict_solve(self, span: Span, action: str, rounds: int,
                         claims: int, victims: int) -> None:
        """Say on an evict solve's ``device_wait`` span how many bidding
        rounds it ran and what it proposed (``claims`` claimants given a
        node, ``victims`` tasks to evict for them), and count the rounds on
        ``/metrics`` (``volcano_solve_rounds_total{action}``) from the same
        value.  What the host made of the claims is counted at the replay
        (``volcano_evict_claims_total``)."""
        span.set(rounds=rounds, claims=claims, victims=victims)
        metrics.register_solve_rounds(action, rounds, False)

    def note_topk_fallbacks(self, span: Span, action: str, exhausted: int,
                            reentries: int) -> None:
        """Say on a ``device_wait`` span how often its solve's candidate
        lists ran dry (``exhausted``: task-rounds with no candidate left
        that fit; ``reentries``: rounds that went back to the full matrix
        for it), and count both on ``/metrics``
        (``volcano_topk_exhausted_total``, ``volcano_topk_reentries_total``)
        from the same values."""
        span.set(exhausted=exhausted, reentries=reentries)
        metrics.register_topk_fallbacks(action, exhausted, reentries)

    def note_affinity_rows(self, span: Span, stats: Dict) -> None:
        """Say on an ``affinity_mask`` span what the device snapshot derived
        from the match-count planes (``required`` and ``preferred`` pending
        rows, ``rerank`` carried rows a moved term re-ranks), and count the
        rows on ``/metrics`` (``volcano_affinity_rows_total{kind}``) from
        the same values."""
        required = int(stats.get("required", 0))
        preferred = int(stats.get("preferred", 0))
        span.set(required=required, preferred=preferred,
                 rerank=len(stats.get("rerank_rows", ())))
        metrics.register_affinity_rows(required, preferred)

    def note_affinity_planes(self, span: Span, updates: int, signatures: int,
                             domains: int) -> None:
        """Say on an ``affinity_plane_update`` span how many cells of the
        planes the ingest it closes moved and how many selectors and
        topology domains are live, and put the three on ``/metrics``
        (``volcano_affinity_plane_updates_total``,
        ``volcano_affinity_signatures``, ``volcano_affinity_domains``)."""
        span.set(updates=updates, signatures=signatures, domains=domains)
        metrics.register_affinity_planes(updates, signatures, domains)

    def note_term_exclusions(self, span: Span, action: str, n: int) -> None:
        """Say on a ``device_wait`` span how many bidders of its solve a
        placement of the same solve turned away (``term_exclusions``), and
        count them (``volcano_inter_pod_exclusions_total{action}``)."""
        span.set(term_exclusions=n)
        metrics.register_inter_pod_exclusions(action, n)

    def note_cycle_attr(self, key: str, value) -> None:
        if not self.enabled:
            return
        with self._mu:
            rec = self.current
            if rec is not None:
                rec.attrs[key] = value

    def note_decision_latencies(self, ms_values) -> None:
        """Hand this cycle's arrival→decision samples to its trace record
        (the exact values the histogram/sink observe; the record keeps
        their summary, :meth:`CycleRecord.summarize` — test_trace pins it
        against the sink) and arm a flight dump on an SLO breach."""
        if not ms_values or not self.enabled:
            return
        with self._mu:
            rec = self.current
            if rec is not None:
                rec._lat.append(ms_values)
        if self.slo_ms > 0 and self.recorder is not None:
            worst = max(ms_values)
            if worst > self.slo_ms:
                self.recorder.trigger(
                    "slo_breach",
                    detail=f"arrival→decision {worst:.1f}ms > "
                           f"KB_TRACE_SLO_MS={self.slo_ms:g}",
                )

    def note_decision_parts(self, arrivals, now: float) -> None:
        """Split the arrival→decision latency of the pods bound at ``now``
        (``arrivals``: their arrival stamps on the telemetry clock): the
        wait until the deciding cycle started, and how many of them were
        passed over — bound after two or more cycles had started (and so
        drained ingest) since they arrived.  Observed with KB_TRACE=0 too:
        these are latency metrics, not retention."""
        if not arrivals:
            return
        with self._mu:
            rec = self.current
            starts = tuple(self._cycle_starts)
        # a bind outside any cycle (direct drives) waited all its latency
        t_cycle = rec.t0 if rec is not None else now
        if len(arrivals) == 1:
            # bind(): one pod a call, a hundred calls a backfill cycle
            earliest = arrivals[0]
            wait_ms = max(t_cycle - earliest, 0.0) * 1e3
            left = len(starts) - bisect.bisect_right(starts, earliest) >= 2
        else:
            # bulk_bind(): the 50,000-pod cold drain pays milliseconds
            arr = np.asarray(arrivals, dtype=np.float64)
            earliest = float(arr.min())
            wait_ms = float(np.clip(t_cycle - arr, 0.0, None).sum()) * 1e3
            left = int((len(starts) - np.searchsorted(
                starts, arr, side="right") >= 2).sum())
        metrics.observe_decision_queue_wait(wait_ms, len(arrivals))
        metrics.register_decisions_leftover(int(left))
        if rec is not None and self.enabled:
            # the cycle's worst decision: whose it was and what it spanned
            # (written by the one thread that binds for this record)
            worst = (now - earliest) * 1e3
            spanned = len(starts) - bisect.bisect_right(starts, earliest)
            seen = rec._seen
            if seen is None:
                rec._seen = [earliest, worst, now, spanned]
            else:
                if worst > seen[1]:
                    seen[1], seen[2] = worst, now
                if earliest < seen[0]:
                    seen[0], seen[3] = earliest, spanned

    # -- interruptions ----------------------------------------------------
    def _log_compile(self, stack, phase: str, secs: float,
                     fun_name: str) -> None:
        """One compile phase JAX reported on the thread whose open spans
        are ``stack`` (innermost last, this tracer's).  A compile is its
        trace, its lowering, then its backend compile; the entry is logged
        when the last is reported, with the function's name, the span path
        and the dispatch span (:data:`_DISPATCH_SPANS`) it ran under, whose
        attributes (``program``, ``rungs``, ...) are set after the span has
        closed and so are read when the log is."""
        if not self.enabled:
            return
        root = stack[0]
        with self._mu:
            entry = self._compile_open.get(root.tid)
            if entry is None or phase == "trace":
                # an outer function's trace is reported after those of the
                # functions it calls, and spans them: the last one stands
                rec = root._record
                if rec is None and root._keep == _KEEP_CYCLE:
                    rec = self.current
                entry = self._compile_open[root.tid] = {
                    "rec": rec,
                    "path": " > ".join(sp.name for sp in stack),
                    "dispatch": next(
                        (sp for sp in reversed(stack)
                         if sp.name in _DISPATCH_SPANS), None),
                    "ms": {},
                }
            entry["fun_name"] = fun_name
            entry["ms"][phase] = round(
                entry["ms"].get(phase, 0.0) + secs * 1e3, 3)
            if entry["rec"] is not None:
                entry["rec"].compile_ms += secs * 1e3
            if phase == "backend":
                self._compiles.append(self._compile_open.pop(root.tid))

    @staticmethod
    def _compile_entry(entry: Dict) -> Dict:
        rec, dispatch = entry["rec"], entry["dispatch"]
        out = {"cycle": rec.cycle if rec is not None else None,
               "fun_name": entry["fun_name"], "path": entry["path"],
               "ms": dict(entry["ms"])}
        if dispatch is not None:
            out["dispatch"] = dict(dispatch.attrs or {}, span=dispatch.name)
        return out

    def compile_in_flight(self) -> List[Dict]:
        """The compile phases threads are in right now."""
        return [{"thread": tid, "phase": phase, "fun_name": fun_name}
                for tid, (phase, fun_name) in list(_COMPILING.items())]

    def open_spans_of(self, tid: Optional[int]) -> list:
        """The open spans of thread ``tid``, outermost first (the thread's
        own list: look, do not touch)."""
        return _STACKS.get(tid) or []

    def open_path(self, tid: Optional[int]) -> str:
        return " > ".join(sp.name for sp in list(self.open_spans_of(tid)))

    def note_stall(self, stall: Dict, waits_for_cycle: bool) -> None:
        """Keep a stall the loop's watchdog declared: on the record of the
        cycle it interrupts, or, for a stall before a cycle
        (``waits_for_cycle``: nothing started while work waited) or with
        no cycle open, on the record of the cycle that follows.  Until that
        cycle begins the stall is a kept record of its own."""
        if not self.enabled:
            return
        with self._mu:
            rec = None if waits_for_cycle else self.current
            if rec is not None:
                rec.stalls.append(stall)
            else:
                self._stalls_waiting = self._stalls_waiting[-7:] + [stall]

    def anomaly(self, reason: str, detail: str = "") -> None:
        """Route a non-guard anomaly (budget shed, duplicate bind) to the
        flight recorder."""
        if self.recorder is not None and self.enabled:
            self.recorder.trigger(reason, detail=detail)

    # -- surfaces ---------------------------------------------------------
    def last_cycle(self) -> Optional[Dict]:
        recorder = self.recorder
        if recorder is None:
            return None
        with self._mu:
            rec = recorder.last_record()
        return rec.to_dict() if rec is not None else None

    def state(self) -> Dict:
        with self._mu:
            out = {
                "enabled": self.enabled,
                "cycles_traced": self.cycles_total,
                # the number the next record gets: what a reader of
                # `cycles` holds a window's rows against
                "next_cycle": self._next_cycle,
                "spans_total": self.spans_total,
                "span_counts": dict(self.span_counts),
                "span_ms": {k: round(v, 3) for k, v in self.span_ms.items()},
                "retraces_attributed": self.retraces_attributed,
                "last_detached": {k: sp.to_dict() for k, sp
                                  in self._last_detached.items()},
            }
            compiles = list(self._compiles)
            waiting = list(self._stalls_waiting)
        out["compiles"] = [self._compile_entry(e) for e in compiles]
        if self.recorder is not None:
            out["ring"] = self.recorder.stats()
            out["solve_dispatches"] = self._solve_dispatches()
            # built here, at GET time: nothing of the table is on the
            # cycle's path
            out["cycles"], out["kept"] = self.recorder.table(waiting)
        out["last_cycle"] = self.last_cycle()
        return out

    def cycle_tree(self, cycle: int) -> Optional[Dict]:
        """The whole tree of record ``cycle``, while the ring or the kept
        list holds it."""
        recorder = self.recorder
        rec = recorder.find(cycle) if recorder is not None else None
        return rec.to_dict() if rec is not None else None

    def _solve_dispatches(self) -> Dict[str, int]:
        """Tally of the allocate solve dispatches still in the ring, keyed
        ``mode[+engaged path...]`` ("single", "sharded+shard_map+topk+warm")
        — which program has been running.  ``last_cycle`` alone loses that
        to the next idle tick, a moment after the cycle that solved.
        Shadowed by ``volcano_solve_dispatches_total`` on ``/metrics``
        (:meth:`note_solve_dispatch`), which counts every dispatch since
        the start and not only those the ring still holds; kept for the
        readers that ask which program is running NOW."""
        tally: Dict[str, int] = {}

        def walk(spans) -> None:
            for sp in list(spans):
                attrs = sp.attrs or {}
                if (sp.name == "solve_dispatch" and "mode" in attrs
                        and attrs.get("action", "allocate") == "allocate"):
                    key = "+".join([attrs["mode"], *attrs.get("engaged", ())])
                    tally[key] = tally.get(key, 0) + 1
                walk(sp.children)

        for rec in self.recorder.records():
            walk(rec.spans)
        return tally

    def stage_attribution(self) -> Dict:
        """The seed-stable longitudinal summary for the sim report: span
        counts per stage plus the attributed retrace total — everything
        here is a function of the event stream, not the host's wall
        clock."""
        with self._mu:
            return {
                "cycles_traced": self.cycles_total,
                "spans_total": self.spans_total,
                "stages": dict(sorted(self.span_counts.items())),
                "retraces_attributed": self.retraces_attributed,
            }


def solve_program(engaged, rebuilt: bool = False) -> str:
    """The program label of an allocate-shaped dispatch, from the fast
    paths it engaged: ``warm`` (the compacted solve over the candidate
    table carried across cycles), ``topk`` (the compacted solve that built
    its table this solve: the per-solve build, or a warm plan that
    ``rebuilt`` every row), ``cold`` (the full [T, N] matrix)."""
    if "warm" in engaged and not rebuilt:
        return "warm"
    return "topk" if "topk" in engaged else "cold"


# --------------------------------------------------------------------------
# per-cache attach (the guard_of idiom)
# --------------------------------------------------------------------------

_ATTACH_LOCK = threading.Lock()


def tracer_of(cache, clock=None) -> Tracer:
    """THE per-cache tracer accessor: the scheduler, the actions, and the
    framework all reach tracing through here, so one cache has exactly one
    span plane and one flight-recorder ring.  ``clock`` (the Scheduler's
    injected clock) re-points virtual-time stamping on first attach."""
    tr = getattr(cache, "tracer", None)
    if tr is None:
        with _ATTACH_LOCK:
            tr = getattr(cache, "tracer", None)
            if tr is None:
                from kube_batch_tpu.obs.recorder import FlightRecorder

                rec = FlightRecorder.from_env()
                tr = Tracer(clock=clock, recorder=rec)
                cache.flight_recorder = rec
                cache.tracer = tr
    if clock is not None and clock is not _time and tr.clock is None:
        tr.clock = clock
    return tr


# --------------------------------------------------------------------------
# Chrome trace-event export + structural validation
# --------------------------------------------------------------------------


def chrome_trace(records) -> Dict:
    """Render cycle records as a Chrome trace-event document (`ph: "X"`
    complete events, µs timestamps) — load in ``chrome://tracing`` or
    Perfetto.  Thread ids are preserved, so the writeback stage rides its
    own track and the pipelined overlap is visible as spans of cycle N's
    writeback under cycle N+1's compute."""
    events: List[Dict] = []
    tid_names: Dict[int, str] = {}

    def emit(span: Span, cycle: int, depth: int) -> None:
        events.append({
            "name": span.name,
            "ph": "X",
            "ts": span.t0 * 1e6,
            "dur": max(span.t1 - span.t0, 0.0) * 1e6,
            "pid": 1,
            "tid": span.tid,
            "args": dict(span.attrs or {}, cycle=cycle, depth=depth),
        })
        if "writeback" in span.name:
            tid_names.setdefault(span.tid, "writeback")
        else:
            tid_names.setdefault(span.tid, "cycle")
        for child in span.children:
            emit(child, cycle, depth + 1)

    for rec in records:
        for span in rec.spans:
            emit(span, rec.cycle, 0)
    meta = [
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
         "args": {"name": name}}
        for tid, name in sorted(tid_names.items())
    ]
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def validate_chrome_trace(doc: Dict) -> List[str]:
    """Structural validation of an exported trace: every complete event
    carries a non-negative duration, per-thread events are properly nested
    (a deeper span lies inside its ancestor's bounds — balanced brackets),
    and timestamps are finite/monotonic per (thread, depth) stream.
    Returns the violations (empty = valid); the trace smoke and the tests
    gate on it."""
    errs: List[str] = []
    events = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    if not events:
        return ["no complete (ph=X) events"]
    by_tid: Dict[int, List[Dict]] = {}
    for e in events:
        if not isinstance(e.get("ts"), (int, float)) or e["ts"] != e["ts"]:
            errs.append(f"non-numeric ts on {e.get('name')}")
            continue
        if e.get("dur", -1) < 0:
            errs.append(f"negative dur on {e.get('name')}")
        by_tid.setdefault(e["tid"], []).append(e)
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: List[Dict] = []  # enclosing spans
        for e in evs:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] - 1e-3:
                stack.pop()
            if stack:
                outer = stack[-1]
                if e["ts"] + e["dur"] > outer["ts"] + outer["dur"] + 1e-3:
                    errs.append(
                        f"unbalanced nesting on tid {tid}: "
                        f"{e['name']} ends after its enclosing "
                        f"{outer['name']}"
                    )
            stack.append(e)
    return errs

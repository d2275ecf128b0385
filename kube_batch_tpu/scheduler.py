"""Scheduler — the L1 loop (pkg/scheduler/scheduler.go:38-102).

Holds the cache, the configured action pipeline, and the plugin tiers; each
tick opens a session (snapshot + plugin open), executes the actions in conf
order, and closes the session (status writeback). `run_forever` is the
wait.Until(runOnce, period) analog — and, by default, its PIPELINED
successor: the cycle is an explicitly staged pipeline

    ingest drain → delta session open → device solve → host replay
                 → status derive ║ writeback (status flush + binder drain)

where everything left of ║ runs on the cycle thread and the writeback
stage runs on a single worker, double-buffered: cycle N+1's ingest drain,
delta open, and solve dispatch proceed while cycle N's status flush and
async binder drain complete (the PR 3 fit-error-histogram overlap inside
allocate is the in-cycle instance of the same mechanism).  Cycle
triggering is event-driven: the cache's dirty-version advance wakes a
condition variable, so an arrival burst schedules immediately instead of
waiting out the reference's fixed 1 s tick, while an idle cluster ticks at
the slow floor; a cycle that bound pods and left schedulable ones pending
raises the trigger itself, so the next cycle starts as soon as the rate
floor allows.  A burst is several requests a few milliseconds apart, and
the first wakes the parked loop: the trigger therefore lets the burst
finish arriving (a settle hold of at most an eighth of a cycle's cost
without a further signal, half a cycle's in all) before it starts the ONE
cycle that decides it, where the first request's cycle used to bind
nothing and make the rest wait for a second.

A cycle leaves nothing owed, and an idle tick that finds nothing owed opens
no session.  The last act of a pipelined cycle, while its session still
owns the cache, is to publish the what-if lease again on the state its
binds left (:meth:`Scheduler._rearm_lease`), so what-ifs see a commit at
once and the tick after it has no lease to repair.  A floor wake then
drains what is staged, runs the resync queue, looks at the conf file, and
if none of that applied anything, no task is pending and no PodGroup is in
a phase the close reports every cycle (:meth:`SchedulerCache.owes_a_cycle`)
it runs no session and no action (:meth:`Scheduler._idle_tick`): a
fraction of a millisecond where a cycle that decides nothing cost tens to
hundreds, in the way of whatever burst arrived while it ran.  The tick
still counts as a floor wake and still advances the guard's cycle clock;
it feeds neither the cost EWMA nor the rate floor, which count from cycles
that opened a session.  Anything owed means the whole cycle, as before.

Knobs: ``KB_PIPELINE=0`` restores the serial wait.Until loop (the
bit-exactness oracle),
``KB_PERIOD_MIN`` pins the minimum spacing between cycle starts (rate
floor for bursts; unset, the floor ADAPTS to an EWMA of the cycle's own
measured cost — see :meth:`Scheduler._note_cycle_cost`), ``KB_PERIOD_MAX``
the idle tick period (default: the schedule period)."""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

from kube_batch_tpu import actions as _actions  # registers actions
from kube_batch_tpu.actions.allocate import (
    build_session_snapshot,
    republish_query_lease,
)
from kube_batch_tpu import plugins as _plugins  # registers plugin builders
from kube_batch_tpu.cache.cache import SchedulerCache
from kube_batch_tpu.framework.conf import SchedulerConfiguration, load_scheduler_conf
from kube_batch_tpu.framework.interface import Action, get_action
from kube_batch_tpu.envutil import env_flag
from kube_batch_tpu.framework.session import (
    close_session,
    open_session,
    release_session,
)
from kube_batch_tpu import metrics
from kube_batch_tpu.obs.alerts import alerts_of
from kube_batch_tpu.obs.interruptions import LoopWatchdog
from kube_batch_tpu.obs.trace import tracer_of
from kube_batch_tpu.utils import telemetry

logger = logging.getLogger("kube_batch_tpu")


class CycleTrigger:
    """Event-driven cycle pacing: the cache's dirty-version advance (and the
    staged-ingest arrival hook) call :meth:`notify`; the loop waits on the
    condition variable between cycles.  A pending signal — even one raised
    MID-cycle — wakes the next cycle as soon as the ``min_period`` rate
    floor allows; with no signal the loop idles until ``max_period`` since
    the last cycle start (the reference's 1 s tick becomes the slow floor).
    The loop raises the trigger itself, ``notify(leftover=True)``, after a
    cycle that bound pods and left schedulable ones pending
    (:meth:`Scheduler._run_forever_pipelined`): the cache keeps its own
    in-session dirty advances from the trigger, so nothing else would wake
    the loop for them before the idle tick.  Such a wake reports
    ``"leftover"`` unless an ingest signal came beside it, in either order.

    An ingest wake SETTLES before its cycle starts (:meth:`_settle`): a
    burst is a handful of requests milliseconds apart, each of which
    signals, and a cycle started on the first drains that one alone, binds
    nothing, and makes the others wait for it, the floor and a second
    cycle.  So the loop starts the cycle once no further ingest signal has
    come for a quiet gap, or once a cap has passed since the first
    unconsumed signal, whichever is first; every signal that lands
    meanwhile is folded into the same wake.  Both bounds are the caller's
    (:meth:`Scheduler.settle_window`: shares of the measured cycle cost);
    a signal already older than either when the floor ends holds nothing,
    and ``"leftover"`` and ``"floor"`` wakes and :meth:`Scheduler.stop`
    never hold.  ``poll()`` (the sim) knows no hold.

    Deadline arithmetic reads the INJECTED clock (the Scheduler's clock
    seam) so tests can pace it; the blocking itself is the condition
    variable's (real-time) wait, re-armed against the injected deadline
    each lap.

    The parked time is the loop's own: with a ``tracer`` the two phases of
    :meth:`wait_for_work` are root spans on the loop thread, ``park:floor``
    (the rate floor) and ``park:event`` (nothing pending, until a signal or
    the idle tick, then the settle hold as its child span ``settle``),
    kept on the record of the cycle they precede.

    The two deadlines count from different starts.  The rate floor spaces
    CYCLES: it counts from the start of the last cycle that opened a
    session, so a quiescent tick (a floor wake that found nothing owed and
    opened none) neither restarts it nor makes the event after it wait.
    The idle deadline counts from the loop's last wake of either kind, so
    ticks stay ``max_period`` apart whether or not they ran a cycle."""

    def __init__(self, clock=None, tracer=None):
        self.clock = clock if clock is not None else time
        self.tracer = tracer
        # the guard lock is created HERE (not Condition's default, which
        # would be born inside the threading module) so the runtime lockdep
        # checker tracks it: notify() under the cache's big lock records the
        # big→trigger edge, and any reverse nesting would report
        self._cond = threading.Condition(lock=threading.Lock())
        # the wake reason of the signal not yet consumed (None: no signal):
        # "ingest", or "leftover" while only the loop itself has asked
        self._pending: Optional[str] = None
        # when the first notify() not yet consumed came (injected clock)
        self._signalled_at = 0.0
        # the ingest signals not yet consumed: how many, when the last one
        # came, and the widest gap between two of them (what the settle
        # hold's quiet gap has to outlast to keep a burst in one cycle)
        self._signals = 0
        self._last_signal_at = 0.0
        self._widest_gap = 0.0
        # stop() asked for this wake: it is never held
        self._stopping = False
        # (when the signal the last wake consumed first came, when it was
        # consumed): how the loop's watchdog learns that a signal it saw
        # waiting was taken, and when
        self._consumed: Optional[tuple] = None

    def notify(self, leftover: bool = False, stop: bool = False) -> None:
        """Wake the loop (never blocks; safe from any thread, including
        under the cache's locks — the condition guard is a leaf).
        ``leftover`` marks the loop's own wake for what its last cycle left
        pending; an ingest signal beside it wins the wake reason.  ``stop``
        marks the wake of a loop that is shutting down, which the settle
        hold lets through at once."""
        with self._cond:
            now = self.clock.monotonic()
            if self._pending is None:
                self._signalled_at = now
                self._pending = "leftover" if leftover else "ingest"
            elif not leftover:
                self._pending = "ingest"
            if not leftover:
                if self._signals:
                    self._widest_gap = max(self._widest_gap,
                                           now - self._last_signal_at)
                self._signals += 1
                self._last_signal_at = now
            if stop:
                self._stopping = True
            self._cond.notify_all()

    def ingest_pending(self) -> bool:
        """Whether an ingest signal is waiting for the next cycle (not
        consumed): what the cycle asks before it re-arms the what-if lease
        at its commit, since the cycle that signal starts at once publishes
        from its own open."""
        with self._cond:
            return self._pending == "ingest"

    def unconsumed(self):
        """(the pending wake reason or None, when its first signal came,
        the last consumed signal's (came, consumed) or None), all on the
        injected clock: what the loop's watchdog
        (:class:`obs.interruptions.LoopWatchdog`) asks every tick."""
        with self._cond:
            return self._pending, self._signalled_at, self._consumed

    def poll(self) -> bool:
        """Consume a pending signal without waiting (the sim's virtual-time
        pacing asks 'would the trigger fire now?' instead of blocking)."""
        with self._cond:
            return self._take() is not None

    def _take(self) -> Optional[str]:
        """Consume the pending signal (the caller holds the guard)."""
        reason, self._pending = self._pending, None
        self._signals = 0
        self._widest_gap = 0.0
        self._stopping = False
        return reason

    def wait_for_work(self, cycle_start: float, min_period: float,
                      max_period: float, settle=None,
                      idle_from: Optional[float] = None) -> str:
        """Block until the next cycle should start; returns the wake reason
        (``"ingest"`` — signalled arrival churn; ``"leftover"`` — only the
        loop's own signal, for what its last cycle left pending;
        ``"floor"`` — the idle period elapsed).  The rate floor is enforced
        first: bursts coalesce into one cycle per ``min_period``, so neither
        a hot ingest stream nor a chain of self-wakes can busy-spin the
        solve.  ``settle`` is the ``(quiet gap, cap)`` in seconds of the
        hold an ingest wake pays for the rest of its burst (``None``: no
        hold).

        ``cycle_start`` is when the last cycle that opened a session
        started, and the rate floor counts from it alone: a quiescent tick
        (:meth:`Scheduler._idle_tick`) is no cycle to space the next one
        from, and an event that lands just after one starts its cycle at
        once.  ``idle_from`` is the loop's last wake of either kind
        (default ``cycle_start``), and the idle deadline counts from it, so
        quiescent ticks stay ``max_period`` apart and never spin."""
        clock = self.clock
        floor_sp = None
        floor_rem = min_period - (clock.monotonic() - cycle_start)
        if floor_rem > 0:
            with self._parked("park:floor") as floor_sp:
                clock.sleep(floor_rem)
        if idle_from is None:
            idle_from = cycle_start
        with self._parked("park:event") as event_sp:
            if not self._await_signal(idle_from + max_period):
                reason, signalled_ms = "floor", 0.0
            else:
                if settle is not None:
                    self._settle(*settle)
                with self._cond:
                    now = clock.monotonic()
                    signalled_ms = (now - self._signalled_at) * 1e3
                    self._consumed = (self._signalled_at, now)
                    reason = self._take()
        if floor_sp is not None:
            floor_sp.set(woke_by=reason)
        if event_sp is not None:
            # signalled_ms: how long the wake had been asked for when the
            # cycle started — the rest of the last cycle and the floor, for
            # a signal that came mid-cycle; the settle hold, for a burst's
            # first
            event_sp.set(woke_by=reason, signalled_ms=round(signalled_ms, 3))
        return reason

    def _parked(self, name: str):
        """The span of one parked phase (nothing, without a tracer)."""
        tracer = self.tracer
        if tracer is None:
            return contextlib.nullcontext()
        return tracer.park_span(
            name, before_cycle=tracer.next_cycle_number())

    def _await_signal(self, deadline: float) -> bool:
        """Park until a signal is pending (True; not consumed: only this
        thread consumes) or ``deadline`` has passed with none (False)."""
        clock = self.clock
        with self._cond:
            while self._pending is None:
                rem = deadline - clock.monotonic()
                if rem <= 0:
                    return False
                self._cond.wait(rem)
            return True

    def _settle_due(self, quiet: float, cap: float):
        """(when the pending wake's hold ends, what ends it), or None for a
        wake that is never held (the caller holds the guard)."""
        if self._pending != "ingest" or self._stopping:
            return None
        quiet_at = self._last_signal_at + quiet
        cap_at = self._signalled_at + cap
        return (quiet_at, "quiet") if quiet_at <= cap_at else (cap_at, "cap")

    def _settle(self, quiet: float, cap: float) -> None:
        """The settle hold of a pending ingest wake: wait, on the same
        condition variable and against the injected clock like
        :meth:`_await_signal`, until ``quiet`` seconds have passed since
        the last ingest signal or ``cap`` since the first unconsumed one.
        Each further signal restarts the quiet gap and is folded into this
        wake; the cap bounds the hold under a continuous stream.  A wake
        whose hold is already over when it is looked at (a signal that came
        mid-cycle and waited out the floor) pays nothing: no span, no
        count."""
        clock = self.clock
        with self._cond:
            due = self._settle_due(quiet, cap)
            if due is None or due[0] <= clock.monotonic():
                return
        tracer = self.tracer
        span = (tracer.span("settle", q_ms=round(quiet * 1e3, 3))
                if tracer is not None else contextlib.nullcontext())
        held_from = clock.monotonic()
        with span as sp:
            with self._cond:
                while True:
                    due = self._settle_due(quiet, cap)
                    if due is None:
                        ended_by = "stop"
                        break
                    end, ended_by = due
                    rem = end - clock.monotonic()
                    if rem <= 0:
                        break
                    self._cond.wait(rem)
                signals, widest = self._signals, self._widest_gap
            if sp is not None:
                sp.set(signals=signals, ended_by=ended_by,
                       widest_gap_ms=round(widest * 1e3, 3))
        metrics.register_settle_hold(
            ended_by, signals, (clock.monotonic() - held_from) * 1e3)


class Scheduler:
    def __init__(
        self,
        cache: SchedulerCache,
        conf: Optional[SchedulerConfiguration] = None,
        conf_path: Optional[str] = None,
        schedule_period: float = 1.0,
        on_cycle_end=None,
        clock=None,
    ):
        self.cache = cache
        # injected time source for the loop's pacing (monotonic() + sleep());
        # defaults to the wall clock. The virtual-time simulator
        # (kube_batch_tpu/sim) injects its VirtualClock so cycle pacing is
        # simulated time, while the latency *metrics* below stay wall-clock
        # (they measure real compute, not scenario time).
        self.clock = clock if clock is not None else time
        self.conf = conf if conf is not None else load_scheduler_conf(conf_path)
        # resolve actions at construction — unknown names raise (util.go:63-70)
        self.actions: List[Action] = [get_action(n) for n in self.conf.actions]
        self.schedule_period = schedule_period
        self.on_cycle_end = on_cycle_end  # e.g. state-file save (persistence.py)
        self._stop = False
        # conf hot-reload (the reference's stated-but-unimplemented design,
        # doc/design/plugin-conf.md — its code re-reads only at startup,
        # scheduler.go:70-83): when constructed from a path, the file's
        # mtime is checked each cycle and a changed, VALID conf swaps in at
        # the cycle boundary; a broken edit logs and keeps the running conf
        self._conf_path = conf_path if conf is None else None
        # NOTE: __init__ loaded the conf above, so this stat runs after the
        # load — an edit in that window would be lost. Re-stat BEFORE
        # re-reading in _maybe_reload_conf closes the window for the loop;
        # here, force one reload check on the first cycle instead.
        self._conf_mtime: Optional[float] = None
        # soft per-cycle time budget (seconds, KB_CYCLE_BUDGET; 0 = off):
        # a cycle that already overran it when the action pipeline finishes
        # sheds the close-time status flush to the cache's async pool and
        # keeps ticking, instead of stalling the loop in egress writeback
        self.cycle_budget = float(os.environ.get("KB_CYCLE_BUDGET", "0") or 0)
        # event-driven pipelined loop (the default; KB_PIPELINE=0 restores
        # the serial wait.Until loop as the bit-exactness oracle)
        self.pipelined = env_flag("KB_PIPELINE", True)
        # cycle-start spacing: bursts coalesce to one cycle per min_period;
        # an idle cluster ticks every max_period (default: today's period).
        # The floor is ADAPTIVE by default: it tracks an EWMA of the
        # cycle's own measured cost (_note_cycle_cost), so the coalescing
        # window follows the solve instead of a static 50 ms — a 200 ms
        # solve shouldn't be re-triggered every 50 ms, and a 10 ms cycle
        # shouldn't wait out 50.  Setting KB_PERIOD_MIN pins the static
        # value back (the escape hatch, like KB_PIPELINE=0).
        raw_min = os.environ.get("KB_PERIOD_MIN", "")
        self.min_period_pinned = bool(raw_min.strip())
        self.min_period = float(raw_min or min(0.05, schedule_period))
        self.max_period = float(
            os.environ.get("KB_PERIOD_MAX", "") or schedule_period
        )
        # EWMA of measured cycle cost (seconds) — the adaptive floor's p50
        # estimator; None until the first pipelined cycle completes
        self.cycle_cost_ewma: Optional[float] = None
        # the cycle tracing plane (kube_batch_tpu/obs): per-cache span
        # recorder + flight-recorder ring; virtual-time stamping follows
        # the injected clock so sim traces attribute on the report's clock
        self.tracer = tracer_of(cache, clock=self.clock)
        self.trigger = CycleTrigger(clock=self.clock, tracer=self.tracer)
        # the writeback stage: one worker, double-buffered — at most one
        # cycle's (status flush + binder drain) in flight while the next
        # cycle computes; _await_writeback is the stage barrier
        self._wb_pool: Optional[ThreadPoolExecutor] = None
        self._wb_future = None
        # the last cycle raised: what it left (and the loop's re-list
        # recovery) is for a whole cycle to look at, whatever woke the loop
        self._cycle_failed = False
        # run_forever's stall watchdog (obs/interruptions.py); the direct
        # drives (run_once*, the sim) pace themselves and have none
        self._watchdog: Optional[LoopWatchdog] = None

    def _stat_conf(self) -> Optional[float]:
        if not self._conf_path:
            return None
        try:
            return os.path.getmtime(self._conf_path)
        except OSError:
            return None

    def _maybe_reload_conf(self) -> None:
        if not self._conf_path:
            return
        mtime = self._stat_conf()
        if mtime is None or mtime == self._conf_mtime:
            return
        try:
            conf = load_scheduler_conf(self._conf_path)
            # resolve EVERYTHING the conf names before swapping: an unknown
            # action or plugin must reject the edit here, not crash every
            # subsequent open_session
            actions = [get_action(n) for n in conf.actions]
            from kube_batch_tpu.framework.interface import get_plugin_builder

            for tier in conf.tiers:
                for opt in tier.plugins:
                    get_plugin_builder(opt.name)
        except Exception as e:  # noqa: BLE001 — keep the running conf
            logger.error("scheduler conf reload failed (%s); keeping the "
                         "running configuration", e)
            self._conf_mtime = mtime  # don't re-log every cycle
            return
        if conf.actions != self.conf.actions or conf.tiers != self.conf.tiers:
            logger.info("scheduler conf hot-reloaded: actions=%s", conf.actions)
        self.conf, self.actions = conf, actions
        self._conf_mtime = mtime

    def run_once(self) -> None:
        """(scheduler.go:88-102) — the serial cycle: every stage inline,
        binder drain at the end, deterministic post-cycle state.  The
        pipelined loop runs the same stages via :meth:`run_once_pipelined`;
        this form stays the bit-exactness oracle (KB_PIPELINE=0)."""
        self._cycle(pipelined=False)

    def run_once_pipelined(self, wake: Optional[str] = None) -> bool:
        """One pipelined cycle: staged ingest drains under one lock, the
        session opens/solves/replays on this thread, the close DERIVES the
        status pass synchronously but hands the egress half (status flush +
        async binder drain) to the writeback worker — overlapped with the
        caller's next cycle.  :meth:`drain_pipeline` (or the next cycle's
        stage barrier) joins it.

        ``wake`` is the event-driven loop's wake reason.  On ``"floor"``
        (the idle period elapsed with no signal) the cycle first looks
        whether anything is owed (:meth:`_idle_tick`) and, where nothing
        is, opens no session: it returns False, a quiescent tick.  Every
        other call, ``run_once_pipelined()`` among them, runs the whole
        cycle and returns True."""
        return self._cycle(pipelined=True, wake=wake)

    def _cycle(self, pipelined: bool, wake: Optional[str] = None) -> bool:
        if pipelined and wake == "floor" and self._idle_tick():
            return False
        tracer = self.tracer
        # the cycle's trace record: every stage below runs inside a span;
        # the pipelined writeback attaches to THIS record from its worker
        # thread, so the exported trace shows the overlap structure
        record = tracer.begin_cycle("pipelined" if pipelined else "serial")
        self._cycle_failed = True  # until the body has returned
        try:
            self._cycle_body(pipelined, record)
        finally:
            tracer.end_cycle()
        self._cycle_failed = False
        return True

    def _idle_tick(self) -> bool:
        """A floor wake's look at whether anything is owed; True when
        nothing is, and the tick is then all there is of this cycle.

        A cycle leaves nothing owed: its close derived every status after
        its own binds, and its last act was to re-arm the what-if lease on
        the state it left (:meth:`_rearm_lease`).  So a floor wake (no
        ingest has signalled since) that applies nothing here (the staged
        ingest, the resync queue and the conf file are looked at as at the
        head of any cycle) and finds the cache as the last session left it,
        with nothing to decide or report (:meth:`SchedulerCache.
        owes_a_cycle`) and the lease still covering that state, opens no
        session and runs no action: the cycle it replaces would have
        decided nothing and written nothing
        (tests/test_pipeline.py::TestQuiescentTick).  It still advances
        the guard's cycle clock, whose cooldowns count cycles.  Anything
        else means the whole cycle, exactly as before; the span's ``owed``
        says what.

        The tick's time is a root span of the loop thread, kept like the
        parked time on the record of the cycle it precedes."""
        tracer = self.tracer
        with tracer.park_span(
                "idle_tick", before_cycle=tracer.next_cycle_number()) as sp:
            owed = self._owed()
            sp.set(quiescent=owed is None)
            if owed is not None:
                sp.set(owed=owed)
                return False
            self._end_cycle_clocks()
        metrics.register_quiescent_tick()
        return True

    def _owed(self) -> Optional[str]:
        """What makes this floor wake a whole cycle, or None."""
        cache = self.cache
        owes = getattr(cache, "owes_a_cycle", None)
        if owes is None:
            return "cache"
        if self._cycle_failed:
            return "failed_cycle"
        drain = getattr(cache, "drain_staged_ingest", None)
        if drain is not None:
            n_staged = drain()
            if n_staged:
                # applied here: the cycle's own drain will find none
                metrics.register_staged_ingest(n_staged)
                return "staged"
        resync = getattr(cache, "process_resync_tasks", None)
        if resync is not None:
            resync()  # a repair it applied moves the tracker: "churn"
        conf = self.conf
        self._maybe_reload_conf()
        if self.conf is not conf:
            return "conf"
        owed = owes()
        if owed is not None:
            return owed
        qp = getattr(cache, "query_plane", None)
        if qp is not None and qp.needs_publish(cache.last_close_version):
            return "lease"
        return None

    def _close_pipelined(self, ssn, rearm: bool):
        """The pipelined close, which hands the cache back in two steps
        with the lease re-arm between them: after the status pass has
        stamped the tracker and the session-only placements are unwound,
        before the deferred ingest applies.  Returns the staged flush.
        One stage, ``status_derive``, as the close has always been; the
        re-arm is its child span."""
        with self.tracer.span("status_derive"):
            try:
                flush = close_session(ssn, stage_flush=True, release=False)
                if rearm:
                    self._rearm_lease(ssn)
                return flush
            finally:
                release_session(ssn)

    def _rearm_lease(self, ssn) -> None:
        """The re-arm at the commit: the last thing a pipelined cycle does
        while its session still owns the cache.  If what the cycle did
        moved the tracker past the published lease (what its binds and
        evictions did to the statuses the close derives: a bind decision
        stamps nothing by itself) the lease is published again from a
        snapshot of the state the cycle leaves, stamped with the version
        read here; staged ingest is in neither, it waits in the staging
        buffer for the next drain.  So what-ifs see a commit at once and
        not an idle tick later, and that tick finds nothing owed.  With an
        ingest signal already pending the next cycle starts at once and
        publishes from its own open, so the re-arm is skipped."""
        cache = self.cache
        qp = getattr(cache, "query_plane", None)
        if qp is None or ssn.columns is None or not ssn.exclusive:
            return
        with self.tracer.span("lease_rearm") as sp:
            version = int(cache.dirty.version)
            if not qp.needs_publish(version):
                outcome = "not_owed"
            elif self.trigger.ingest_pending():
                outcome = "ingest_pending"
            elif republish_query_lease(
                    ssn, build=lambda: build_session_snapshot(ssn),
                    version=version):
                outcome = "published"
            else:
                outcome = "failed"  # logged where it failed, never raised
            sp.set(outcome=outcome)
        metrics.register_lease_rearm(outcome)

    def _end_cycle_clocks(self) -> None:
        """What counts cycles, quiescent ticks included."""
        # guard-plane breaker clock: demotion cooldowns and half-open
        # probes count in SCHEDULING CYCLES, not wall seconds, so the
        # state machine is deterministic under the sim's virtual clock
        guard = getattr(self.cache, "guard_plane", None)
        if guard is not None:
            guard.end_cycle()
            # trip-rate SLO alerting rides the same deterministic clock
            alerts_of(self.cache).evaluate(guard)
        # how full each device has been: once a cycle, never per scrape
        metrics.refresh_device_peak_bytes()

    def _cycle_body(self, pipelined: bool, record) -> None:
        tracer = self.tracer
        if pipelined:
            # ingest stage: everything the watch/ingest threads staged since
            # the last cycle applies under ONE cache-lock acquisition —
            # BEFORE the resync drain, so repair decisions see the freshest
            # pod store
            drain = getattr(self.cache, "drain_staged_ingest", None)
            if drain is not None:
                planes = getattr(getattr(self.cache, "columns", None),
                                 "affinity", None)
                if planes is not None:
                    since = planes.take_tally()  # the last cycle's binds
                with tracer.span("ingest_drain") as sp:
                    n_staged = drain()
                    sp.set(events=n_staged)
                    if planes is not None and planes.live_signatures:
                        # what keeping the match-count planes cost this
                        # drain: added up inside the row choke points it ran
                        # through
                        busy_s, updates = planes.take_tally()
                        with tracer.tallied_span("affinity_plane_update",
                                                 busy_s) as sp_pl:
                            tracer.note_affinity_planes(
                                sp_pl, updates + since[1],
                                planes.live_signatures,
                                planes.live_domains())
                            sp_pl.set(replay_ms=round(since[0] * 1e3, 3))
                metrics.register_staged_ingest(n_staged)
        # drain the resync queue at the cycle boundary: the background repair
        # tick (cache.go:563-581) skips while an exclusive session owns the
        # cache, and at small schedule periods sessions run nearly
        # back-to-back — this bound guarantees a failed bind/evict is
        # repaired within one cycle instead of racing for a gap
        resync = getattr(self.cache, "process_resync_tasks", None)
        if resync is not None:
            with tracer.span("resync"):
                resync()
        self._maybe_reload_conf()
        start = telemetry.perf_counter()
        # the soft budget reads the INJECTED clock (virtual elapsed inside
        # one run_once is 0 by construction, so simulated cycles never shed
        # nondeterministically; production's clock is the wall)
        budget_start = self.clock.monotonic() if self.cycle_budget > 0 else 0.0
        with tracer.span("session_open"):
            ssn = open_session(self.cache, self.conf.tiers)
        # the configured pipeline, for actions whose behavior depends on
        # what runs after them (reclaim's idle-fit claimant gate)
        ssn.action_names = [a.name for a in self.actions]
        staged_flush = None
        acted = False  # every action ran to its end
        try:
            for action in self.actions:
                # the span IS the measurement (rule KBT014): the action
                # latency histogram feeds from its stamps instead of an
                # ad-hoc perf_counter pair around the same region
                with tracer.span("action:" + action.name) as sp:
                    action.execute(ssn)
                metrics.observe_action_latency(action.name, sp.dur_us)
            acted = True
        finally:
            shed = (
                self.cycle_budget > 0
                and self.clock.monotonic() - budget_start > self.cycle_budget
            )
            if shed:
                logger.warning(
                    "cycle over its %.2fs soft budget before close; shedding "
                    "the status flush", self.cycle_budget)
                metrics.register_cycle_budget_exceeded()
                # a shed is a flight-recorder anomaly: the cycles around it
                # show WHERE the budget went
                tracer.anomaly(
                    "budget_shed",
                    detail=f"cycle over KB_CYCLE_BUDGET={self.cycle_budget}s",
                )
                self.cache.shed_status_writes = True
            try:
                # pipelined: the close stages the flush (degraded verdict
                # captured NOW, while the shed flag is visible) and skips
                # the inline binder drain — both run on the writeback worker
                if pipelined:
                    staged_flush = self._close_pipelined(ssn, rearm=acted)
                else:
                    with tracer.span("status_derive"):
                        staged_flush = close_session(ssn)
            finally:
                if shed:
                    self.cache.shed_status_writes = False
                if pipelined:
                    # stage barrier: at most one writeback generation in
                    # flight (double buffer) — join cycle N-1's egress, then
                    # hand off ours.  INSIDE the finally: a cycle that died
                    # in an action still staged its flush, and the stage
                    # already recorded the queue deltas / rate-limit windows
                    # as written — dropping the flush here would suppress
                    # those writes until the counts next change.  A close
                    # whose OWN finally raised after staging never returned
                    # the flush — recover it from the session stash.
                    if staged_flush is None:
                        staged_flush = getattr(ssn, "staged_flush", None)
                    with tracer.span("writeback_barrier"):
                        self._await_writeback()
                    self._submit_writeback(staged_flush, record)
        metrics.observe_e2e_latency((telemetry.perf_counter() - start) * 1e3)
        if not pipelined:
            # drain async binder dispatch (cache.go:478's goroutines) outside
            # the measured cycle so callers observe a deterministic
            # post-cycle state
            flush = getattr(self.cache, "flush_binds", None)
            if flush is not None:
                with tracer.span("bind_drain"):
                    flush()
        self._end_cycle_clocks()
        if self.on_cycle_end is not None:
            self.on_cycle_end()

    # ---- writeback stage (the overlapped half of the pipeline) ----------
    def _writeback(self, staged_flush, record=None) -> None:
        # the span targets the ORIGINATING cycle's record (already in the
        # ring) from this worker thread — chrome://tracing then shows it
        # overlapping the next cycle's compute on a separate track
        with self.tracer.cycle_span("writeback", record) as sp:
            if staged_flush:
                self.cache.run_status_flush(staged_flush)
            drain = getattr(self.cache, "flush_binds", None)
            if drain is not None:
                drain()
        metrics.observe_pipeline_overlap(sp.dur_ms)

    def _submit_writeback(self, staged_flush, record=None) -> None:
        if self._wb_pool is None:
            self._wb_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kb-writeback"
            )
        self._wb_future = self._wb_pool.submit(
            self._writeback, staged_flush, record
        )

    def _await_writeback(self) -> None:
        fut, self._wb_future = self._wb_future, None
        if fut is not None:
            try:
                fut.result()
            except Exception:  # noqa: BLE001 — next close re-derives
                logger.exception("writeback stage failed; statuses will "
                                 "re-derive next cycle")

    # EWMA smoothing of the adaptive coalescing floor, and its clamps: the
    # floor never drops below 5 ms (a degenerate idle cycle must not let a
    # hot ingest stream busy-spin the loop) and never exceeds max_period
    # (the idle tick must stay reachable)
    EWMA_ALPHA = 0.2
    MIN_PERIOD_FLOOR = 0.005

    def _note_cycle_cost(self, elapsed: float) -> None:
        """Feed one measured cycle cost (seconds, injected clock) into the
        adaptive min-period: EWMA-smooth it and, unless KB_PERIOD_MIN
        pinned a static floor, retarget the trigger's coalescing window to
        the smoothed cost."""
        if elapsed < 0:
            return
        prev = self.cycle_cost_ewma
        self.cycle_cost_ewma = (
            elapsed if prev is None
            else self.EWMA_ALPHA * elapsed + (1.0 - self.EWMA_ALPHA) * prev
        )
        if not self.min_period_pinned:
            self.min_period = min(
                max(self.cycle_cost_ewma, self.MIN_PERIOD_FLOOR),
                self.max_period,
            )

    # the settle hold's two bounds (CycleTrigger._settle), as shares of the
    # same EWMA: the hold risks the quiet gap to save a whole cycle, so it
    # asks for an eighth of one, within clamps that keep a burst's requests
    # (a few ms apart on the wire) inside it and a lone event's price small;
    # the cap, from the burst's first signal, is half a cycle and at most
    # 100 ms, so a continuous stream still gets a cycle that often
    SETTLE_QUIET_SHARE = 1 / 8
    SETTLE_QUIET_MIN = 0.005
    SETTLE_QUIET_MAX = 0.025
    SETTLE_CAP_SHARE = 1 / 2
    SETTLE_CAP_MAX = 0.1

    def settle_window(self):
        """The ``(quiet gap, cap)`` in seconds an ingest wake may be held
        for the rest of its burst, from the measured cycle cost (the EWMA
        is fed whether or not KB_PERIOD_MIN pins the floor); None, no
        hold, until a cycle has been measured."""
        ewma = self.cycle_cost_ewma
        if ewma is None:
            return None
        quiet = min(max(ewma * self.SETTLE_QUIET_SHARE,
                        self.SETTLE_QUIET_MIN), self.SETTLE_QUIET_MAX)
        return quiet, min(ewma * self.SETTLE_CAP_SHARE, self.SETTLE_CAP_MAX)

    def drain_pipeline(self) -> None:
        """Join the in-flight writeback stage and apply any still-staged
        ingest — the deterministic post-cycle state the serial run_once
        gives inline.  Tests, the sim, and shutdown call this."""
        self._await_writeback()
        # the replication publisher's encode stage overlaps the next cycle
        # exactly like the writeback worker — join it at the same barrier
        # so a drained pipeline has the cycle's record on the stream
        rep = getattr(self.cache, "replication", None)
        if rep is not None:
            rep.barrier()
        drain = getattr(self.cache, "drain_staged_ingest", None)
        if drain is not None:
            metrics.register_staged_ingest(drain())

    def _recover_failed_cycle(self) -> None:
        # exclusive (no-clone) sessions mutate the authoritative cache in
        # place: a cycle that died mid-mutation may have leaked partial
        # state — rebuild from the pod store (the informer re-list analog)
        # before the next cycle
        recover = getattr(self.cache, "rebuild_from_pod_store", None)
        if recover is not None:
            try:
                recover()
            except Exception:  # noqa: BLE001
                logger.exception("re-list recovery failed")

    def run_forever(self) -> None:
        """The L1 loop, preceded by cache.Run — the reference starts the
        cache's background repair loops (resync + cleanup) before ticking
        (scheduler.go:63-86, cache.go:342-384).  KB_PIPELINE=0 gives the
        reference's serial wait.Until(runOnce, period); the default is the
        event-driven pipelined loop (module docstring)."""
        cache_run = getattr(self.cache, "run", None)
        if cache_run is not None:
            cache_run(resync_period=min(self.schedule_period, 1.0))
        # re-arm after a prior stop(): the warm-standby loop re-enters
        # run_forever in the same process after a leadership loss
        self._stop = False
        # nobody is there to ask /debug/stacks when the loop stalls: a
        # watchdog looks at this thread's loop every 100 ms of wall time
        watchdog = LoopWatchdog(self)
        watchdog.start()
        self._watchdog = watchdog
        try:
            if self.pipelined:
                self._run_forever_pipelined()
                return
            while not self._stop:
                tick = self.clock.monotonic()
                try:
                    self.run_once()
                except Exception:  # noqa: BLE001 — next cycle self-corrects
                    logger.exception("scheduling cycle failed")
                    self._recover_failed_cycle()
                elapsed = self.clock.monotonic() - tick
                self.clock.sleep(max(self.schedule_period - elapsed, 0.0))
        finally:
            self._stop_watchdog()
            cache_stop = getattr(self.cache, "stop", None)
            if cache_stop is not None:
                cache_stop()

    def _stop_watchdog(self) -> None:
        """End and join the watchdog's thread (whoever comes first, the
        ending loop or :meth:`stop`; the other finds none)."""
        watchdog, self._watchdog = self._watchdog, None
        if watchdog is not None:
            watchdog.stop()

    def _run_forever_pipelined(self) -> None:
        """The event-driven pipelined loop (the caller holds the cache-run /
        cache-stop bracket).  Ingest staging routes watch churn through the
        leaf staging buffer, the dirty tracker's version advance wakes the
        trigger, and shutdown drains every in-flight stage before the cache
        stops.

        The loop wakes ITSELF for what a cycle left behind: a cycle that
        returned without raising, bound at least one pod and left
        schedulable pods pending (the solve's round cap passes gangs over
        that the next cycle places) raises the trigger, because nothing
        else would before the idle tick — the cache suppresses its own
        in-session dirty advances.  A self-woken cycle that binds nothing
        ends the chain, so pods that fit nowhere cost one extra cycle after
        each cycle that made progress and never a spin; every link binds at
        least one pod, so a chain is no longer than the backlog.  Evictions
        are no progress here: a victim's termination arrives as an ingest
        event.

        The wake reason goes to the cycle it starts
        (:meth:`run_once_pipelined`): a ``"floor"`` wake that finds nothing
        owed is a quiescent tick and opens no session.  Only a cycle that
        did open one feeds the cost EWMA (a tick's fraction of a
        millisecond would shrink the floor and the settle hold's quiet gap
        to their clamps and split bursts over two cycles again), restarts
        the rate floor and can leave something behind; every wake, a
        tick's too, restarts the idle deadline."""
        cache = self.cache
        left_behind = getattr(cache, "left_schedulable_pending", None)
        enable = getattr(cache, "enable_ingest_staging", None)
        signal = getattr(cache, "set_ingest_signal", None)
        if signal is not None:
            signal(self.trigger.notify)
        if enable is not None:
            enable()
        logger.info(
            "pipelined cycle loop: event-driven trigger, min_period=%.3fs "
            "max_period=%.3fs (KB_PIPELINE=0 for the serial oracle)",
            self.min_period, self.max_period,
        )
        wake = None  # what woke the loop for the cycle to come (start-up: nothing)
        floor_from = self.clock.monotonic()
        try:
            while not self._stop:
                tick = self.clock.monotonic()
                binds = getattr(cache, "binds_total", 0)
                try:
                    if self.run_once_pipelined(wake):
                        floor_from = tick
                        # successful cycles only: a fast-CRASHING cycle
                        # must not drag the adaptive floor down and turn
                        # the loop into a high-frequency crash retry.  And
                        # cycles only: a quiescent tick's fraction of a
                        # millisecond would shrink the floor and the settle
                        # hold's quiet gap to their clamps
                        self._note_cycle_cost(self.clock.monotonic() - tick)
                        if left_behind is not None and left_behind(binds):
                            self.trigger.notify(leftover=True)
                except Exception:  # noqa: BLE001 — next cycle self-corrects
                    logger.exception("scheduling cycle failed")
                    floor_from = tick
                    self._recover_failed_cycle()
                wake = self.trigger.wait_for_work(
                    floor_from, self.min_period, self.max_period,
                    self.settle_window(), tick,
                )
                metrics.register_trigger_wake(wake)
        finally:
            # shutdown drain: join the in-flight writeback, apply staged
            # ingest, and detach the trigger so a re-armed run_forever (the
            # warm-standby path) starts from a clean pipeline
            try:
                disable = getattr(cache, "disable_ingest_staging", None)
                if disable is not None:
                    disable()
                self.drain_pipeline()
            finally:
                if signal is not None:
                    signal(None)
                if self._wb_pool is not None:
                    self._wb_pool.shutdown(wait=True)
                    self._wb_pool = None

    def stop(self) -> None:
        self._stop = True
        # a stopping pipelined loop may be idling at the slow floor — wake it
        self.trigger.notify(stop=True)
        self._stop_watchdog()

    def close(self) -> None:
        """Retire the pipelined writeback pool with a bounded drain.
        run_forever's finally-block does this for the looped path; direct
        ``run_once_pipelined`` callers (tests, the sim harness) must call
        close() or leak the pool's non-daemon worker thread."""
        self.drain_pipeline()
        if self._wb_pool is not None:
            self._wb_pool.shutdown(wait=True)
            self._wb_pool = None

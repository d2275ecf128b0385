"""Flight recorder — the black box for the scheduling cycle.

A bounded ring (``KB_TRACE_RING``, default 256 cycles) of complete
per-cycle trace trees from the span recorder (obs/trace.py).  On an
anomaly — a guard-plane trip, a cycle-budget shed, an arrival→decision
SLO breach, a duplicate bind — the recorder snapshots the N cycles BEFORE
the trigger, arms a capture of the N cycles AFTER it, and publishes the
whole window as a self-contained dump directory:

    <dir>/flight-<reason>-<serial>/
        trace.json   — Chrome trace-event JSON (chrome://tracing/Perfetto
                       render the pipelined overlap directly)
        meta.json    — trigger reason/detail, window bounds, knobs

The write uses the guard-bundle idiom (build in a temp sibling,
``os.replace`` into place) so a crash mid-dump never leaves a half
capture.  Dump directory resolution: ``KB_TRACE_DIR``, else
``<KB_GUARD_DIR>/flight`` when the guard bundle dir is configured (trip
dumps land NEXT to the guard bundle for the same incident), else
``flight-recorder``.  ``KB_TRACE_POST`` (default 8) sets N — how many
post-trigger cycles each dump waits for before publishing.

The ring is also readable without a dump.  :meth:`FlightRecorder.table`
is one row a record (``cycles`` on ``GET /v1/trace``: what the cycle
decided, the worst and the median latency it closed, what preceded it, and
the interruptions it carries), built when somebody asks and never on the
cycle's path; ``GET /v1/trace/cycles/<n>`` is a record's whole tree.  And
the ring keeps what was slow: a record that carries a stall, a compile or a
full garbage collection, or whose worst decision stood out from the last
:data:`SLOW_WINDOW` deciding cycles, is pinned in a bounded list
(:data:`KEPT`, oldest out) that the ring's rollover does not touch.
"""

from __future__ import annotations

import bisect
import json
import logging
import os
import statistics
import tempfile
import threading
from collections import deque
from typing import Dict, List, Optional, Tuple

from kube_batch_tpu import metrics
from kube_batch_tpu.envutil import env_int

logger = logging.getLogger("kube_batch_tpu")

_KNOBS = (
    "KB_TRACE", "KB_TRACE_RING", "KB_TRACE_POST", "KB_TRACE_SLO_MS",
    "KB_PIPELINE", "KB_TOPK", "KB_SHARD_MAP", "KB_GUARD", "JAX_PLATFORMS",
)

#: in-memory bound on the trigger log (dumps on disk are the durable record)
MAX_TRIGGER_LOG = 64

#: records pinned outside the ring's rollover
KEPT = 32
#: a deciding cycle is slow when the worst latency it closed is this many
#: ms and this many times above the median of that quantity over the last
#: SLOW_WINDOW deciding cycles (so a cold drain and a warm-up, which move
#: the median with them, do not fill the list for ever)
SLOW_ABOVE_MS = 100.0
SLOW_TIMES = 2.0
SLOW_WINDOW = 32


def flight_dir() -> str:
    explicit = os.environ.get("KB_TRACE_DIR", "").strip()
    if explicit:
        return explicit
    guard = os.environ.get("KB_GUARD_DIR", "").strip()
    if guard:
        return os.path.join(guard, "flight")
    return "flight-recorder"


class FlightRecorder:
    def __init__(self, ring: Optional[int] = None,
                 directory: Optional[str] = None,
                 post_cycles: Optional[int] = None):
        self.ring_cap = ring if ring is not None else max(
            2, env_int("KB_TRACE_RING", 256)
        )
        self.directory = directory  # None → flight_dir() at dump time
        self.post_cycles = (
            post_cycles if post_cycles is not None
            else max(0, env_int("KB_TRACE_POST", 8))
        )
        # set False by a disabled Tracer: with no record_cycle feed, an
        # armed capture could never settle — trigger() then no-ops
        self.enabled = True
        self._mu = threading.Lock()
        self._ring: deque = deque(maxlen=self.ring_cap)
        # (record, why it was kept), and the worst latencies of the last
        # deciding cycles that the slow rule measures against
        self._kept: deque = deque(maxlen=KEPT)
        self._worst: deque = deque(maxlen=SLOW_WINDOW)
        # armed captures: trigger fired, waiting out their post window
        self._armed: List[Dict] = []
        self.cycles_recorded = 0
        self.triggers: deque = deque(maxlen=MAX_TRIGGER_LOG)
        self.dumps: List[str] = []
        self._serial = 0

    @classmethod
    def from_env(cls) -> "FlightRecorder":
        return cls()

    # ------------------------------------------------------------------
    def record_cycle(self, record) -> None:
        """Ring-append one finalized cycle record; settle armed captures
        whose post-trigger window completed (file I/O OUTSIDE the lock)."""
        due: List[Dict] = []
        why = self._why_kept(record)
        with self._mu:
            self._ring.append(record)
            self.cycles_recorded += 1
            if why:
                self._kept.append((record, why))
            for armed in self._armed:
                armed["post"].append(record)
                if len(armed["post"]) >= self.post_cycles:
                    due.append(armed)
            if due:
                self._armed = [a for a in self._armed if a not in due]
        for armed in due:
            self._publish(armed)

    def _why_kept(self, record) -> List[str]:
        """The reasons to pin ``record`` (called once, by the thread that
        finalizes it): the interruptions it carries, and the slow rule."""
        why = []
        if record.stalls:
            why.append("stall")
        if record.compile_ms:
            why.append("compile")
        if any(sp.attrs and ("gc_full" in sp.attrs
                             or "gap_gc_full" in sp.attrs)
               for sp in record.spans):
            why.append("gc_full")
        decisions = record.decisions
        if decisions is not None:
            worst, history = decisions["worst_ms"], self._worst
            if history:
                median = statistics.median(history)
                if (worst >= median + SLOW_ABOVE_MS
                        and worst >= SLOW_TIMES * median):
                    why.append("slow")
                    metrics.register_slow_decision()
            history.append(worst)
        return why

    def trigger(self, reason: str, detail: str = "") -> None:
        """One anomaly: snapshot the pre-trigger ring, arm the
        post-trigger capture.  With ``post_cycles == 0`` (or an idle
        process that never cycles again) the dump publishes immediately.

        No-ops when tracing is disabled (nothing feeds the ring, so a
        capture could never settle), and COALESCES repeat triggers: while
        a capture for ``reason`` is still armed, a new trigger of the same
        reason only logs — a sustained SLO breach or a trip storm must not
        arm one capture (each holding a full ring snapshot) per event."""
        if not self.enabled:
            return
        with self._mu:
            self.triggers.append({
                "reason": reason, "detail": detail,
                "cycle": self.cycles_recorded,
            })
            if any(a["reason"] == reason for a in self._armed):
                return  # coalesced into the already-armed capture
            armed = {
                "reason": reason,
                "detail": detail,
                "pre": list(self._ring),
                "post": [],
                "trigger_cycle": self.cycles_recorded,
            }
            if self.post_cycles > 0:
                self._armed.append(armed)
                armed = None
        if armed is not None:
            self._publish(armed)

    def flush(self) -> List[str]:
        """Publish every still-armed capture with whatever post-trigger
        cycles arrived (shutdown / end-of-run path: the sim and the smoke
        call this so a trigger near the end of a run still dumps)."""
        with self._mu:
            armed, self._armed = self._armed, []
        out = []
        for a in armed:
            path = self._publish(a)
            if path:
                out.append(path)
        return out

    # ------------------------------------------------------------------
    def _publish(self, armed: Dict) -> Optional[str]:
        from kube_batch_tpu.obs.trace import chrome_trace

        records = armed["pre"] + armed["post"]
        if not records:
            logger.warning("flight dump for %s skipped: empty ring",
                           armed["reason"])
            return None
        root = self.directory or flight_dir()
        try:
            os.makedirs(root, exist_ok=True)
            doc = chrome_trace(records)
            meta = {
                "schema": 1,
                "reason": armed["reason"],
                "detail": armed["detail"],
                "trigger_cycle": armed["trigger_cycle"],
                "cycles_before": len(armed["pre"]),
                "cycles_after": len(armed["post"]),
                "cycle_ids": [r.cycle for r in records],
                "knobs": {k: os.environ.get(k, "") for k in _KNOBS},
                "tree": [r.to_dict() for r in records],
            }
            # atomic publish: whole dump in a temp sibling, one rename —
            # the guard-bundle idiom, so a crash never leaves a half dump
            tmp = tempfile.mkdtemp(dir=root, prefix=".tmp-flight-")
            try:
                with open(os.path.join(tmp, "trace.json"), "w") as f:
                    json.dump(doc, f)
                with open(os.path.join(tmp, "meta.json"), "w") as f:
                    json.dump(meta, f, indent=2, sort_keys=True)
                while True:
                    final = os.path.join(
                        root, f"flight-{armed['reason']}-{self._serial:04d}"
                    )
                    if not os.path.exists(final):
                        try:
                            os.replace(tmp, final)
                            break
                        except OSError:
                            pass  # lost a concurrent-dump race — next serial
                    self._serial += 1
                    if self._serial > 9999:
                        raise OSError("flight recorder directory full")
            except BaseException:
                import shutil

                shutil.rmtree(tmp, ignore_errors=True)
                raise
        except Exception:  # noqa: BLE001 — diagnostics only, never the cycle
            logger.exception("flight recorder dump failed")
            return None
        with self._mu:
            self.dumps.append(final)
        metrics.register_flight_dump(armed["reason"])
        logger.warning("flight recorder dump written: %s", final)
        return final

    # ------------------------------------------------------------------
    def last_record(self):
        with self._mu:
            return self._ring[-1] if self._ring else None

    def records(self) -> list:
        with self._mu:
            return list(self._ring)

    def find(self, cycle: int):
        """Record ``cycle``, while the ring or the kept list holds it."""
        with self._mu:
            held = list(self._ring) + [rec for rec, _ in self._kept]
        return next((rec for rec in held if rec.cycle == cycle), None)

    def table(self, stalls_waiting=()) -> Tuple[List[Dict], List[Dict]]:
        """(``cycles``: one row a record in the ring, oldest first;
        ``kept``: the rows of the pinned records with ``why``, and one row
        for every stall that still waits for a cycle to ride)."""
        with self._mu:
            ring, kept = list(self._ring), list(self._kept)
        # the cycle thread's roots of everything held, in time order: a
        # decision's interval may reach back over the cycles before its own
        held = {id(rec): rec for rec in [rec for rec, _ in kept] + ring}
        roots = sorted(
            (sp.t0, sp.t1, sp._gap[1] if sp._gap else 0.0)
            for rec in held.values() for sp in list(rec.spans)
            if sp._record is None)
        stalls = [(st["t0"], st["t0"] + (st["dur_ms"] or 0.0) / 1e3)
                  for rec in held.values() for st in rec.stalls]
        cover = (roots, [r[0] for r in roots], stalls)
        cycles = [_row(rec, cover) for rec in ring]
        pinned = [dict(_row(rec, cover), why=why) for rec, why in kept]
        pinned += [{"cycle": None, "why": ["stall"], "t0": st["t0"],
                    "stall_ms": st["dur_ms"], "phase": st["phase"],
                    "stall": dict(st)} for st in stalls_waiting]
        return cycles, pinned

    def stats(self) -> Dict:
        with self._mu:
            return {
                "capacity": self.ring_cap,
                "cycles_recorded": self.cycles_recorded,
                "cycles_resident": len(self._ring),
                "post_cycles": self.post_cycles,
                "armed": len(self._armed),
                "triggers": list(self.triggers),
                "dumps": list(self.dumps),
            }


def _covered(intervals, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that lie under at least one of ``intervals``
    (sorted by their start)."""
    total, upto = 0.0, lo
    for a, b in intervals:
        if b <= upto:
            continue
        if a >= hi:
            break
        total += min(b, hi) - max(a, upto)
        upto = min(b, hi)
    return total


def _row(rec, cover) -> Dict:
    """One record as a row of the table of cycles.  ``named_ms`` is the
    time of the record's own roots on the cycle thread (parked time
    included) plus the collector's pauses in the gaps between them;
    ``worst_named_ms`` is how much of the worst decision's interval
    (earliest arrival to the bind) lies under a root of the cycle thread,
    a pause in the gap before one, or a declared stall (``cover``: the
    roots of every record held as (t0, t1, pause seconds in the gap
    before), sorted; their starts; the stalls' intervals)."""
    parked = settle = gc_ms = named = 0.0
    gc_full = 0
    for sp in list(rec.spans):
        attrs = sp.attrs or {}
        gc_ms += attrs.get("gc_ms", 0.0) + attrs.get("gap_gc_ms", 0.0)
        gc_full += attrs.get("gc_full", 0) + attrs.get("gap_gc_full", 0)
        if sp._record is not None:
            continue  # the writeback, on its own thread
        named += sp.dur_ms + attrs.get("gap_gc_ms", 0.0)
        if sp.name.startswith("park:"):
            parked += sp.dur_ms
            settle += sum(c.dur_ms for c in list(sp.children)
                          if c.name == "settle")
    row = {
        "cycle": rec.cycle, "reason": rec.reason, "t0": round(rec.t0, 6),
        "dur_ms": (round((rec.t1 - rec.t0) * 1e3, 3)
                   if rec.t1 is not None else None),
        "decided": 0, "worst_ms": None, "median_ms": None, "wait_ms": None,
        "spanned": None, "worst_at": None, "worst_named_ms": None,
        "parked_ms": round(parked, 3), "settle_ms": round(settle, 3),
        "gc_ms": round(gc_ms, 3), "gc_full": gc_full,
        "compile_ms": round(rec.compile_ms, 3),
        "stall_ms": round(sum(st["dur_ms"] or 0.0 for st in rec.stalls), 3),
        "named_ms": round(named, 3),
    }
    decisions = rec.decisions
    if decisions is not None:
        lo, hi = decisions["worst_from"], decisions["worst_at"]
        roots, starts, stalls = cover
        # the cycle thread's roots do not overlap: the one open at lo, and
        # every one that starts before hi (and the one after, for its gap)
        near = roots[max(bisect.bisect_right(starts, lo) - 1, 0):
                     bisect.bisect_left(starts, hi) + 1]
        under = sorted(
            [(t0, t1) for t0, t1, _ in near]
            + [(t0 - paused, t0) for t0, _, paused in near if paused]
            + [(a, b) for a, b in stalls if b > lo and a < hi])
        row.update(
            {k: decisions[k] for k in ("decided", "worst_ms", "median_ms",
                                       "wait_ms", "spanned", "worst_at")},
            worst_named_ms=round(_covered(under, lo, hi) * 1e3, 3))
    return row

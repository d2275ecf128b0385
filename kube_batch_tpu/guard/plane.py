"""GuardPlane — the per-fast-path health breaker (guard plane tier 3).

Generalizes the transport layer's :class:`k8s.transport.CircuitBreaker`
discipline from "is this apiserver reachable" to "is this solve fast path
producing lawful results": each demotable fast path (KB_TOPK compaction,
the shard_map collective bodies, the KB_WARM carried table) carries a
health state —

    healthy ──trip──▶ demoted ──KB_GUARD_COOLDOWN clean cycles──▶ probing
       ▲                 ▲                                           │
       └── clean probe ──┘◀──────────── trip during probe ───────────┘

A demoted path's dispatches run the ORACLE program (KB_TOPK=0 / pjit /
KB_WARM=0 — the same knobs the tests pin bit-exactness against);
``probing`` is the half-open state: the next dispatch runs the fast path
again under the sentinel, and one clean engaged cycle re-promotes.  Time
is counted in SCHEDULING CYCLES (the Scheduler's loop calls
:meth:`end_cycle`), not wall seconds, so the breaker is deterministic
under the simulator's virtual clock — the same reasoning that put the
resync queue's backoff in repair ticks.

Every trip additionally invokes the registered heal hook (the actions pass
``ColumnStore.drop_resident``): an HBM bit-flip in a resident column is
cured by the cold full re-upload the next dispatch pays, so the system
self-heals the data while demotion guards the code paths.  A trip also
dumps a diagnostics bundle (guard/bundle.py) when the caller supplies a
``dump`` thunk — lazily, so the snapshot serialization cost is only paid
on the (rare) trip path.

Thread-safety: every state transition happens under one leaf lock;
nothing blocks under it (bundle dumps and heals run outside).  A trip
racing an in-flight audit, or a mid-cycle conf reload swapping the
session's config, cannot wedge the state machine — tests/test_guard.py
pins both races.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Callable, Dict, List, Optional, Sequence

from kube_batch_tpu import metrics
from kube_batch_tpu.envutil import env_int

logger = logging.getLogger("kube_batch_tpu")

#: the demotable fast paths — each has a per-dispatch oracle knob the
#: demotion flips (actions/allocate.py dispatch + parallel/mesh.py impl
#: selection).  "warm" is the carried
#: candidate-table path (KB_WARM): demotion pins the compacted solve to
#: its cold per-solve build, and the trip heal drops the carried table
#: with the resident caches (ColumnStore.drop_resident)
FAST_PATHS = ("topk", "shard_map", "warm")

HEALTHY, DEMOTED, PROBING = "healthy", "demoted", "probing"


class PathHealth:
    """One fast path's breaker state (mutated under the plane's lock)."""

    def __init__(self, name: str):
        self.name = name
        self.state = HEALTHY
        self.clean_cycles = 0   # clean cycles since demotion
        self.trips = 0
        self.promotions = 0

    def snapshot(self) -> Dict:
        return {
            "state": self.state,
            "clean_cycles": self.clean_cycles,
            "trips": self.trips,
            "promotions": self.promotions,
        }


class GuardPlane:
    def __init__(self, enabled: Optional[bool] = None,
                 audit_every: Optional[int] = None,
                 cooldown: Optional[int] = None,
                 bundle_dir: Optional[str] = None):
        if enabled is None:
            enabled = os.environ.get("KB_GUARD", "").strip().lower() not in (
                "0", "false", "off", "no"
            )
        self.enabled = enabled
        self.audit_every = (
            audit_every if audit_every is not None
            else env_int("KB_AUDIT_EVERY", 64)
        )
        self.cooldown = (
            cooldown if cooldown is not None
            else max(1, env_int("KB_GUARD_COOLDOWN", 8))
        )
        self.bundle_dir = bundle_dir  # None → guard/bundle.py's env default
        self._lock = threading.Lock()
        self.paths: Dict[str, PathHealth] = {
            name: PathHealth(name) for name in FAST_PATHS
        }
        # per-action dispatch counters (the audit cadence) — dispatches,
        # not cycles, so direct action invocation (bench, tests) still
        # audits on schedule
        self._dispatches: Dict[str, int] = {}
        # engagement/trip bookkeeping for the current cycle
        self._cycle_engaged: set = set()
        self._cycle_tripped: set = set()
        self._ever_engaged: set = set()  # fast paths seen in this process
        self.cycle = 0
        # lifetime diagnostics (the sim report + tests read these)
        self.trips_total = 0
        self.failed_closed = 0      # condemned solves discarded
        self.audits_run = 0
        self.audits_mismatched = 0
        self.bundles: List[str] = []
        self.trip_log: List[Dict] = []
        self.cycle_of_last_trip = -1

    @classmethod
    def from_env(cls) -> "GuardPlane":
        return cls()

    # ------------------------------------------------------------------
    # dispatch-side queries
    # ------------------------------------------------------------------
    def allow(self, path: str) -> bool:
        """May this fast path run?  Demoted paths answer False (the
        dispatch selects the oracle); probing paths answer True — the
        half-open probe runs under the sentinel."""
        if not self.enabled:
            return True
        with self._lock:
            ph = self.paths.get(path)
            return ph is None or ph.state != DEMOTED

    def audit_due(self, action: str) -> bool:
        """True on every KB_AUDIT_EVERY-th dispatch of ``action`` — the
        shadow-oracle cadence.  Counted per dispatch (not per cycle) so
        direct action invocation still audits."""
        if not self.enabled or self.audit_every <= 0:
            return False
        with self._lock:
            n = self._dispatches.get(action, 0) + 1
            self._dispatches[action] = n
            return n % self.audit_every == 0

    # ------------------------------------------------------------------
    # verdict / audit consumption (the actions' choke points)
    # ------------------------------------------------------------------
    def consume_verdict(self, action: str, engaged: Sequence[str],
                        verdict: int, hist=None, detail: str = "",
                        dump: Optional[Callable[[], str]] = None,
                        heal: Optional[Callable[[], None]] = None) -> bool:
        """Record one sentinel verdict.  Returns True when the action may
        apply the result; False = the solve is condemned and the action
        must FAIL CLOSED (discard, dispatch nothing)."""
        if not self.enabled:
            return True
        with self._lock:
            self._ever_engaged.update(engaged)
        if int(verdict) == 0:
            with self._lock:
                self._cycle_engaged.update(engaged)
            return True
        with self._lock:
            self.failed_closed += 1
        self.trip(action, engaged, reason="invariant",
                  detail=detail or f"verdict={int(verdict)}",
                  hist=hist, dump=dump, heal=heal)
        return False

    def note_audit(self, action: str, engaged: Sequence[str], matched: bool,
                   detail: str = "",
                   dump: Optional[Callable[[], str]] = None,
                   heal: Optional[Callable[[], None]] = None) -> None:
        """Record one shadow-oracle comparison (tier 2)."""
        if not self.enabled:
            return
        with self._lock:
            self.audits_run += 1
        metrics.register_guard_audit("match" if matched else "mismatch")
        if matched:
            with self._lock:
                self._cycle_engaged.update(engaged)
            return
        with self._lock:
            self.audits_mismatched += 1
        self.trip(action, engaged, reason="audit", detail=detail,
                  dump=dump, heal=heal)

    def fail_closed(self, action: str, detail: str) -> None:
        """A demotion whose target does not fit the device (guard/fit.py):
        the action discards the cycle's solve — no program runs, nothing
        binds — and says so.  No path changes state: the breaker's clock
        runs on, and its half-open probe lets the fast path try again."""
        if not self.enabled:
            return
        with self._lock:
            self.failed_closed += 1
        metrics.register_guard_trip(action, "unfit")
        logger.error(
            "guard plane (%s): %s — failing closed: no solve, no binds "
            "this cycle", action, detail)

    def trip(self, action: str, engaged: Sequence[str], reason: str,
             detail: str = "", hist=None,
             dump: Optional[Callable[[], str]] = None,
             heal: Optional[Callable[[], None]] = None) -> None:
        """One integrity trip: demote the engaged fast paths, self-heal the
        resident data, dump the diagnostics bundle.  Idempotent per path —
        a second trip in the same cycle (the audit racing the sentinel)
        just re-confirms the demotion."""
        with self._lock:
            self.trips_total += 1
            self.cycle_of_last_trip = self.cycle
            targets = [n for n in engaged if n in self.paths]
            if not targets:
                # unattributable trip (e.g. a corrupted resident column
                # caught by a full-matrix solve's sentinel): conservatively
                # demote every non-demoted fast path that has engaged in
                # this process — a PROBING path's half-open window failed
                # too — the oracles run until clean cycles prove health,
                # and the heal hook cures the data either way
                targets = sorted(
                    p for p in self._ever_engaged
                    if self.paths[p].state != DEMOTED
                )
            record = {
                "cycle": self.cycle, "action": action, "reason": reason,
                "engaged": list(engaged), "demoted": list(targets),
                "detail": detail,
                "hist": list(map(int, hist)) if hist is not None else None,
            }
            self.trip_log.append(record)
            for name in targets:
                ph = self.paths[name]
                ph.trips += 1
                ph.state = DEMOTED
                ph.clean_cycles = 0
                self._cycle_tripped.add(name)
                metrics.set_guard_path_demoted(name, 1)
        metrics.register_guard_trip(action, reason)
        logger.error(
            "guard plane trip (%s/%s): %s — failing closed; demoted %s",
            action, reason, detail, targets or "no fast path",
        )
        # outside the lock: the heal touches the column store, the dump
        # serializes the snapshot and writes files.  A trip is also the
        # flight recorder's primary trigger — the cycle trace trees around
        # the condemned solve dump beside the guard bundle (obs/recorder).
        flight = getattr(getattr(self, "host_cache", None),
                         "flight_recorder", None)
        if flight is not None:
            try:
                flight.trigger(
                    "guard_trip", detail=f"{action}/{reason}: {detail}"
                )
            except Exception:  # noqa: BLE001 — diagnostics only
                logger.exception("flight-recorder trigger failed")
        if heal is not None:
            try:
                heal()
            except Exception:  # noqa: BLE001 — healing must not kill the cycle
                logger.exception("guard resident heal failed")
        if dump is not None:
            try:
                path = dump()
                if path:
                    with self._lock:
                        record["bundle"] = path
                        self.bundles.append(path)
            except Exception:  # noqa: BLE001 — diagnostics only
                logger.exception("guard bundle dump failed")

    # ------------------------------------------------------------------
    # cycle clock (Scheduler._cycle calls this once per cycle)
    # ------------------------------------------------------------------
    def end_cycle(self) -> None:
        """Advance the breaker clock: demoted paths accrue clean cycles
        toward their half-open probe; a probing path that ran engaged and
        clean this cycle re-promotes."""
        if not self.enabled:
            return
        with self._lock:
            self.cycle += 1
            for name, ph in self.paths.items():
                if name in self._cycle_tripped:
                    continue  # trip() already reset this path
                if ph.state == DEMOTED:
                    ph.clean_cycles += 1
                    if ph.clean_cycles >= self.cooldown:
                        ph.state = PROBING
                        logger.info(
                            "guard path %s half-open after %d clean cycles",
                            name, ph.clean_cycles,
                        )
                elif ph.state == PROBING and name in self._cycle_engaged:
                    ph.state = HEALTHY
                    ph.promotions += 1
                    metrics.set_guard_path_demoted(name, 0)
                    logger.info("guard path %s re-promoted (clean probe)",
                                name)
            self._cycle_engaged.clear()
            self._cycle_tripped.clear()

    def trip_series(self, since: int):
        """(cycle, trip_log[since:], new_len) under the plane's lock — the
        alert evaluator's incremental read (obs/alerts.py)."""
        with self._lock:
            return self.cycle, list(self.trip_log[since:]), len(self.trip_log)

    # ------------------------------------------------------------------
    def state(self) -> Dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "cycle": self.cycle,
                "cooldown": self.cooldown,
                "audit_every": self.audit_every,
                "trips_total": self.trips_total,
                "failed_closed": self.failed_closed,
                "audits_run": self.audits_run,
                "audits_mismatched": self.audits_mismatched,
                "bundles": list(self.bundles),
                "paths": {n: p.snapshot() for n, p in self.paths.items()},
            }


#: serializes the lazy attach below — GET /v1/guard (HTTP handler threads)
#: and the cycle's first dispatch can race it, and an unsynchronized
#: check-then-act could overwrite a plane that already holds breaker state
_ATTACH_LOCK = threading.Lock()


def guard_of(cache) -> GuardPlane:
    """THE per-cache guard plane accessor: every dispatch site goes through
    here, so the plane attaches lazily on first use and the whole pipeline
    (allocate, reclaim, preempt, backfill, enqueue) shares one breaker
    state per scheduler cache."""
    gp = getattr(cache, "guard_plane", None)
    if gp is None:
        with _ATTACH_LOCK:
            gp = getattr(cache, "guard_plane", None)
            if gp is None:
                gp = GuardPlane.from_env()
                # back-pointer for the flight-recorder trigger (trip());
                # the plane's own state machine never reads through it
                gp.host_cache = cache
                cache.guard_plane = gp
    return gp


# --------------------------------------------------------------------------
# the shared sentinel consumer — ONE copy of the readback-side plumbing
# (checksum cross-check, histogram folding, detail rendering, bundle thunk,
# heal) so the three dispatching actions cannot drift apart in what a trip
# records or how it self-heals.
# --------------------------------------------------------------------------


def make_heal(ssn):
    """The standard trip heal: drop the resident device caches (a
    corrupted column is cured by the next dispatch's full re-upload) AND
    retire the published what-if lease — a condemned solve's snapshot must
    not keep serving probes; serving waits for the next clean publish."""
    cols = ssn.columns
    qp = getattr(ssn.cache, "query_plane", None)

    def heal():
        if cols is not None:
            cols.drop_resident()
        if qp is not None:
            qp.broker.retire()

    return heal


def sentinel_bundle_thunk(gp: GuardPlane, action: str, dev_snap, config,
                          report, pend_rows=None):
    """Lazy diagnostics-bundle dump for a trip (shared by the sentinel
    consumer and the audit comparator) — captures the exact
    post-resident-swap snapshot the condemned solve consumed."""
    def dump():
        from kube_batch_tpu.guard.bundle import dump_bundle

        return dump_bundle(action, dev_snap, config, report,
                           pend_rows=pend_rows, directory=gp.bundle_dir)

    return dump


def consume_sentinel(gp: GuardPlane, action: str, ssn, snap, dev_snap,
                     config, verdict: int, vhist, echeck: int,
                     engaged, host_bad: int = 0, pend_rows=None,
                     extra_report=None) -> bool:
    """Consume one solve's fused sentinel outputs plus the host
    cross-checks: ``host_bad`` carries the action-specific count (e.g.
    assignments targeting rows the HOST doesn't believe pending); the
    device-vs-host eligibility checksum compare happens here, once.
    Host-side violations fold into slot 0 of the histogram so the trip
    log and the bundle tell one story regardless of which action fired.
    Returns True = lawful, apply the result; False = FAIL CLOSED."""
    import numpy as np

    from kube_batch_tpu.ops.invariants import (
        INVARIANT_NAMES,
        host_eligibility_checksum,
    )

    host_ck = host_eligibility_checksum(snap)
    if (int(echeck) & 0xFFFFFFFF) != host_ck:
        host_bad += 1
    total = int(verdict) + host_bad
    vhist = (
        np.zeros(len(INVARIANT_NAMES), np.int64) if vhist is None
        else np.asarray(vhist).astype(np.int64).copy()
    )
    vhist[0] += host_bad
    detail = ", ".join(
        f"{name}={int(c)}" for name, c in zip(INVARIANT_NAMES, vhist) if c
    )
    if host_bad:
        detail += f" (host eligibility cross-check: {host_bad})"
    report = {
        "verdict": int(total), "detail": detail, "engaged": list(engaged),
        "host_cross_check": host_bad, "host_checksum": host_ck,
    }
    if extra_report:
        report.update(extra_report)
    return gp.consume_verdict(
        action, engaged, total, hist=vhist, detail=detail,
        dump=sentinel_bundle_thunk(gp, action, dev_snap, config, report,
                                   pend_rows=pend_rows),
        heal=make_heal(ssn),
    )


def consume_assignment_sentinel(gp: GuardPlane, action: str, ssn, snap,
                                meta, ginfo, verdict: int, vhist,
                                echeck: int, assigned,
                                extra_report=None) -> bool:
    """The assignment-shaped consumer shared by allocate and backfill's
    real-request pass: ONE copy of the host cross-check (an assignment
    must target a row the HOST also believes pending — the device-resident
    pending column could be the corrupted thing) feeding
    :func:`consume_sentinel`, so the two actions cannot condemn different
    things for the same corruption."""
    import numpy as np

    host_bad = int(np.sum(
        (np.asarray(assigned) >= 0)
        & ~np.asarray(snap.task_pending)[: meta.n_tasks]
    ))
    return consume_sentinel(
        gp, action, ssn, snap, ginfo["dev"], ginfo["config"],
        int(verdict), vhist, int(echeck), ginfo["engaged"],
        host_bad=host_bad, pend_rows=ginfo.get("pend_rows"),
        extra_report=extra_report,
    )

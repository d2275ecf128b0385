"""Runtime lock-order validator — the Linux lockdep idea, sized for this
codebase.

Go gives the reference `go test -race`; this port's PR 1 writer-executor
race and the TokenBucket sleep-under-lock both slipped past review. The
validator instruments the locks our concurrent modules create and, while
the ordinary test suite runs, records per-thread held-lock sets to build
the lock-acquisition-order graph:

- **Order inversion**: thread 1 acquires A then B, thread 2 acquires B then
  A — a deadlock waiting for the right interleaving. Locks are grouped by
  CREATION SITE (module:line), the analog of lockdep's lock classes, so an
  inversion between any two instances of the same site pair is caught even
  when the individual test never deadlocks.  Detection is TRANSITIVE over
  the recorded acquisition graph: a new edge A→B is a violation whenever a
  path B→…→A already exists, so the 3-lock cycle A→B→C→A (no direct
  two-lock inversion anywhere) reports the moment its closing edge lands,
  with the full chain and each edge's first-observed stack.
- **Blocking under lock**: `time.sleep` / `Future.result` / `Event.wait`
  reached while the thread holds any tracked lock (the TokenBucket bug, as
  a runtime check).
- **Lock-hold / contention profile**: every tracked acquire records its
  acquire-WAIT (time blocked entering the lock) and, on release, its
  HOLD time, accumulated per lock class (creation site).  This is the
  profile the ROADMAP's "striped per-kind ingest locks (profile first)"
  item asks for: ``profile_report()`` ranks sites by total wait, so the
  bench's ``lock_profile`` section (and any lockdep-instrumented test
  run) can say whether the single staging buffer actually contends
  before anyone pays for striping.  Accumulation is PER-THREAD (merged
  at report time), so profiling adds no cross-thread synchronization to
  the very contention it measures.

`install()` patches `threading.Lock`/`RLock` with factories that return
instrumented locks ONLY when the creating frame belongs to one of the
target modules (default: cache/, cache/volume, cmd/server, k8s/watch,
metrics/) — stdlib and third-party locks are untouched. The pytest plugin
(`kube_batch_tpu.analysis.pytest_plugin`) installs this for the whole
suite and fails the run on violations.

Same-site nesting (two instances of one lock class held at once) is a
violation unless the region is wrapped in
``utils.blocking.allow_nesting("reason")``: two instances of one class
have no defined order between them, so undeclared nesting is an ordering
claim nobody wrote down (PR 2 skipped this case wholesale; the annotation
turns the skip into a validated declaration).  Sanctioned nesting records
no self-edge — an instance-level order inside one class is the
annotation's claim, not the graph's.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

from kube_batch_tpu.utils import blocking as _blocking

#: modules whose locks are instrumented by default — the concurrent core
DEFAULT_MODULE_PREFIXES = (
    "kube_batch_tpu.cache",
    "kube_batch_tpu.cmd.server",
    "kube_batch_tpu.k8s.watch",
    "kube_batch_tpu.metrics",
    # the pipelined loop's locks (the CycleTrigger condition guard): the
    # dirty-advance hook notifies UNDER the cache's big lock, so the
    # big→trigger edge — and any future reverse nesting — must be observed
    "kube_batch_tpu.scheduler",
    # the observability plane (tracer/recorder/alerts leaf locks) and the
    # guard plane: spans close from the cycle AND writeback threads, and
    # alert evaluation reads the guard's lock — their edges belong in the
    # graph
    "kube_batch_tpu.obs",
    "kube_batch_tpu.guard",
    # the read plane: two batcher workers flush at once, each through the
    # batcher's condition, the broker's and the plane's counter lock, while
    # the cycle publishes and swaps through the broker under its own locks
    "kube_batch_tpu.serve",
)

_REAL_LOCK = threading.Lock
_REAL_RLOCK = threading.RLock
_REAL_SLEEP = time.sleep
_REAL_FUTURE_RESULT = concurrent.futures.Future.result
_REAL_EVENT_WAIT = threading.Event.wait
#: wall clock for the contention profile — captured at import so the
#: profile is immune to any clock patching (lockdep itself patches sleep)
_REAL_PERF = time.perf_counter

# re-exported for detector-side callers; runtime code imports it from
# utils/blocking.py directly so annotating a region never pulls the lint
# engine into a scheduler process
allow_blocking = _blocking.allow_blocking


@dataclasses.dataclass
class Violation:
    kind: str  # "order-inversion" | "blocking-under-lock" |
    #            "undeclared-nesting" | "unguarded-access"
    description: str
    stack: str

    def render(self) -> str:
        return f"[{self.kind}] {self.description}\n{self.stack}"


def _stack(skip: int = 2, limit: int = 14) -> str:
    frames = traceback.format_stack()[:-skip]
    return "".join(frames[-limit:])


class LockdepState:
    """The acquisition-order graph + per-thread held sets + violations."""

    def __init__(self) -> None:
        # internal bookkeeping lock: a REAL lock, created before any
        # patching, never visible to the graph
        self._mu = _REAL_LOCK()
        # (site_a, site_b) -> stack where a->b was first observed
        self.edges: Dict[Tuple[str, str], str] = {}
        # site -> successor sites (the same graph as `edges`, shaped for
        # the transitive-cycle search)
        self._adj: Dict[str, set] = {}
        self.violations: List[Violation] = []
        # sites whose undeclared same-site nesting already reported (one
        # report per site, not one per occurrence)
        self._nested_sites: set = set()
        self._local = threading.local()
        # per-thread contention/hold accumulators (merged by
        # profile_report); entries: site → [n, wait_s, wait_max, hold_s,
        # hold_max] — per-thread so profiling never serializes the very
        # contention it measures
        self._profs: List[Dict[str, list]] = []

    def _path(self, src: str, dst: str) -> Optional[List[str]]:
        """A site path src → … → dst over the recorded acquisition edges
        (iterative DFS; the class graph is tiny), or None."""
        stack = [(src, [src])]
        seen = {src}
        while stack:
            node, path = stack.pop()
            for nxt in self._adj.get(node, ()):
                if nxt == dst:
                    return path + [dst]
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append((nxt, path + [nxt]))
        return None

    # -- held-set helpers --------------------------------------------------
    def _held(self) -> List[list]:
        held = getattr(self._local, "held", None)
        if held is None:
            held = self._local.held = []
        return held  # entries: [site, lock_id, depth, t_acquired]

    def _prof(self) -> Dict[str, list]:
        prof = getattr(self._local, "prof", None)
        if prof is None:
            prof = self._local.prof = {}
            with self._mu:
                self._profs.append(prof)
        return prof

    def _note_wait(self, site: str, wait: float) -> None:
        prof = self._prof()
        rec = prof.get(site)
        if rec is None:
            rec = prof[site] = [0, 0.0, 0.0, 0.0, 0.0]
        rec[0] += 1
        rec[1] += wait
        if wait > rec[2]:
            rec[2] = wait


    def held_sites(self) -> List[str]:
        return [e[0] for e in self._held()]

    # -- events ------------------------------------------------------------
    def on_acquired(self, site: str, lock_id: int,
                    wait: float = 0.0) -> None:
        self._note_wait(site, wait)
        held = self._held()
        for entry in held:
            if entry[1] == lock_id:
                entry[2] += 1  # reentrant RLock acquire
                return
        # same-site nesting: a DIFFERENT instance of this lock class is
        # already held.  Two instances of one class have no defined order,
        # so the nesting is an ordering claim — valid only when declared
        # via utils.blocking.allow_nesting("reason")
        if (
            any(e[0] == site for e in held)
            and not _blocking.nesting_allowed()
            and site not in self._nested_sites
        ):
            stack = _stack(skip=3)
            with self._mu:
                if site not in self._nested_sites:
                    self._nested_sites.add(site)
                    self.violations.append(Violation(
                        "same-site-nesting",
                        f"two instances of lock class {site} held by one "
                        "thread without an allow_nesting declaration — "
                        "per-object locks of one class have no defined "
                        "order; wrap the region in utils.blocking."
                        "allow_nesting(\"<order invariant>\") or impose a "
                        "global order",
                        stack,
                    ))
        # membership probe OUTSIDE the bookkeeping lock and BEFORE paying
        # traceback formatting: steady state (every edge already recorded —
        # the cache bind loops re-acquire the same pairs constantly) is a
        # couple of dict lookups; the GIL makes the dict read safe and the
        # locked re-check below closes the race
        candidates = [
            (hsite, site)
            for hsite, _hid, _d, _t in held
            # same-site pairs never enter the graph: a self-edge would be
            # an instant cycle, and declared nesting (allow_nesting) is an
            # instance-level claim, not a class-order edge
            if hsite != site
            and (hsite, site) not in self.edges
        ]
        if candidates:
            stack = _stack(skip=3)
            inversions = []
            with self._mu:
                for edge in candidates:
                    a, b = edge
                    if edge in self.edges:
                        continue  # raced in since the unlocked probe
                    # a NEW a->b edge closes a deadlock cycle iff a path
                    # b ->* a already exists — length 1 is the direct
                    # inversion, longer is the transitive A→B→C→A case
                    cycle = self._path(b, a)
                    self.edges[edge] = stack
                    self._adj.setdefault(a, set()).add(b)
                    if cycle is not None:
                        inversions.append((edge, cycle))
                for (a, b), cycle in inversions:
                    if len(cycle) == 2:
                        desc = (
                            f"lock order inverted: this thread acquired "
                            f"{a} then {b}, but {b} -> {a} was previously "
                            f"observed"
                        )
                        detail = (
                            f"--- {a} -> {b} acquired at:\n{stack}"
                            f"--- {b} -> {a} first observed at:\n"
                            f"{self.edges[(b, a)]}"
                        )
                    else:
                        chain = " -> ".join(cycle)
                        desc = (
                            f"lock order inverted (transitive): this thread "
                            f"acquired {a} then {b}, closing the cycle "
                            f"{a} -> {b} against the previously observed "
                            f"chain {chain}"
                        )
                        parts = [f"--- {a} -> {b} acquired at:\n{stack}"]
                        parts.extend(
                            f"--- {x} -> {y} first observed at:\n"
                            f"{self.edges[(x, y)]}"
                            for x, y in zip(cycle, cycle[1:])
                        )
                        detail = "".join(parts)
                    self.violations.append(
                        Violation("order-inversion", desc, detail)
                    )
        held.append([site, lock_id, 1, _REAL_PERF()])

    def on_released(self, lock_id: int) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i][1] == lock_id:
                held[i][2] -= 1
                if held[i][2] == 0:
                    hold = _REAL_PERF() - held[i][3]
                    rec = self._prof().get(held[i][0])
                    if rec is not None:
                        rec[3] += hold
                        if hold > rec[4]:
                            rec[4] = hold
                    del held[i]
                return

    def on_blocking_call(self, what: str) -> None:
        held = self.held_sites()
        if not held or _blocking.blocking_allowed():
            return
        with self._mu:
            self.violations.append(Violation(
                "blocking-under-lock",
                f"{what} while holding {', '.join(held)}",
                _stack(skip=3),
            ))

    def report(self) -> str:
        lines = [
            f"lockdep: {len(self.edges)} lock-order edges, "
            f"{len(self.violations)} violation(s)"
        ]
        for v in self.violations:
            lines.append(v.render())
        return "\n".join(lines)

    def profile_report(self) -> Dict[str, Dict[str, float]]:
        """Merged per-site contention/hold profile: site → {acquires,
        wait_ms_total, wait_ms_max, hold_ms_total, hold_ms_max}, the
        per-thread accumulators folded together."""
        with self._mu:
            profs = list(self._profs)
        merged: Dict[str, list] = {}
        for prof in profs:
            for site, rec in list(prof.items()):
                m = merged.setdefault(site, [0, 0.0, 0.0, 0.0, 0.0])
                m[0] += rec[0]
                m[1] += rec[1]
                m[2] = max(m[2], rec[2])
                m[3] += rec[3]
                m[4] = max(m[4], rec[4])
        return {
            site: {
                "acquires": m[0],
                "wait_ms_total": round(m[1] * 1e3, 3),
                "wait_ms_max": round(m[2] * 1e3, 3),
                "hold_ms_total": round(m[3] * 1e3, 3),
                "hold_ms_max": round(m[4] * 1e3, 3),
            }
            for site, m in sorted(
                merged.items(), key=lambda kv: -kv[1][1]
            )
        }


class TrackedLock:
    """A Lock/RLock wrapper feeding the lockdep state. `site` is the
    creation site (module:line) — the lock's class in lockdep terms."""

    def __init__(self, state: LockdepState, site: str, reentrant: bool = False):
        self._state = state
        self.site = site
        self._lock = _REAL_RLOCK() if reentrant else _REAL_LOCK()

    def acquire(self, blocking: bool = True, timeout: float = -1):
        t0 = _REAL_PERF()
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            self._state.on_acquired(self.site, id(self),
                                    wait=_REAL_PERF() - t0)
        return ok

    def release(self) -> None:
        self._state.on_released(id(self))
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        locked = getattr(self._lock, "locked", None)
        return locked() if locked is not None else False

    def __repr__(self) -> str:
        return f"<TrackedLock {self.site}>"


_installed: Optional["_Installation"] = None


class _Installation:
    def __init__(self, state: LockdepState, prefixes: Tuple[str, ...]):
        self.state = state
        self.prefixes = prefixes

    def _creation_site(self):
        """(module, module:line) of the frame that called the patched
        factory — two frames up from here: [0]=_creation_site, [1]=the
        factory, [2]=the code running `threading.Lock()`."""
        try:
            frame = sys._getframe(2)
        except ValueError:
            return "", "?"
        mod = frame.f_globals.get("__name__", "")
        return mod, f"{mod or '?'}:{frame.f_lineno}"

    def _tracked(self, mod: str) -> bool:
        return any(mod == p or mod.startswith(p + ".") for p in self.prefixes)

    # the patched factories (bound methods keep `self` out of the signature)
    def make_lock(self):
        mod, site = self._creation_site()
        if self._tracked(mod):
            return TrackedLock(self.state, site, reentrant=False)
        return _REAL_LOCK()

    def make_rlock(self):
        mod, site = self._creation_site()
        if self._tracked(mod):
            return TrackedLock(self.state, site, reentrant=True)
        return _REAL_RLOCK()


def install(prefixes: Tuple[str, ...] = DEFAULT_MODULE_PREFIXES) -> LockdepState:
    """Patch the lock factories + blocking primitives. Idempotent: a second
    install returns the active state."""
    global _installed
    if _installed is not None:
        return _installed.state
    state = LockdepState()
    inst = _Installation(state, prefixes)
    _installed = inst

    threading.Lock = inst.make_lock
    threading.RLock = inst.make_rlock

    def checked_sleep(seconds):
        state.on_blocking_call(f"time.sleep({seconds!r})")
        return _REAL_SLEEP(seconds)

    def checked_result(self, timeout=None):
        # an already-done future can't block — only flag a real wait
        if not self.done():
            state.on_blocking_call("Future.result()")
        return _REAL_FUTURE_RESULT(self, timeout)

    def checked_wait(self, timeout=None):
        if not self.is_set():
            state.on_blocking_call("Event.wait()")
        return _REAL_EVENT_WAIT(self, timeout)

    time.sleep = checked_sleep
    concurrent.futures.Future.result = checked_result
    threading.Event.wait = checked_wait
    return state


def uninstall() -> Optional[LockdepState]:
    """Restore the real primitives; returns the state for reporting."""
    global _installed
    if _installed is None:
        return None
    state = _installed.state
    threading.Lock = _REAL_LOCK
    threading.RLock = _REAL_RLOCK
    time.sleep = _REAL_SLEEP
    concurrent.futures.Future.result = _REAL_FUTURE_RESULT
    threading.Event.wait = _REAL_EVENT_WAIT
    _installed = None
    return state


def current_state() -> Optional[LockdepState]:
    return _installed.state if _installed is not None else None


# ---------------------------------------------------------------------------
# guarded-access corroborator (kbt-check tier D, analysis/races.py)
#
# The static analyzer infers, per class, which lock attribute dominates each
# shared attribute ("lock domains").  This runtime leg cross-validates the
# map the same way tier B's jaxpr audit corroborates tier A: hot shared
# structures are instrumented with a data descriptor that asserts, at access
# time, that the statically inferred domain lock is actually held by the
# accessing thread.  Static says "every access site holds _lock"; runtime
# says "and every access the suite actually executed did".
#
# Enforcement semantics:
# - An instance is CONFINED until a second distinct thread touches it —
#   single-thread instances (most unit-test fixtures) never enforce, so the
#   check only fires where a race is physically possible.
# - Ownership must be attributable: TrackedLock (held-set lookup) and
#   RLock/Condition (_is_owned) qualify; a plain untracked Lock records no
#   owner, so access under one is skipped rather than misreported.
# - `utils.blocking.allow_unguarded("reason")` regions are exempt — the
#   runtime analog of `# kbt: allow[KBT301]`.
# - Violations dedupe per (class, attr) and land in LockdepState.violations,
#   so the pytest plugin fails the run exactly like an order inversion.
# ---------------------------------------------------------------------------

_REAL_GET_IDENT = threading.get_ident


def _owned_by_current(lock) -> Optional[bool]:
    """Does the calling thread own `lock`?  None = ownership cannot be
    attributed (plain Lock, or a foreign object) — callers skip, never
    report, on None."""
    if lock is None:
        return None
    if isinstance(lock, TrackedLock):
        return any(e[1] == id(lock) for e in lock._state._held())
    owned = getattr(lock, "_is_owned", None)
    if owned is not None:
        try:
            return bool(owned())
        except Exception:  # noqa: BLE001 — a foreign _is_owned never reports
            return None
    return None


class _GuardedAttr:
    """Class-level data descriptor standing in for one instrumented plain
    instance attribute.  Values keep living in the instance `__dict__`
    under the same name (a data descriptor shadows the instance dict), so
    uninstalling the descriptor restores direct attribute access with the
    last value intact."""

    def __init__(self, install: "GuardedAccessInstallation", cls: type,
                 attr: str, lock_attr: str, sample: int = 1):
        self._install = install
        self._cls = cls
        self.attr = attr
        self.lock_attr = lock_attr
        self.sample = max(1, int(sample))
        self._count = 0  # benign data race: sampling only needs "roughly Nth"

    def __get__(self, inst, objtype=None):
        if inst is None:
            return self
        self._check(inst, "read")
        try:
            return inst.__dict__[self.attr]
        except KeyError:
            raise AttributeError(
                f"{type(inst).__name__!r} object has no attribute "
                f"{self.attr!r}"
            ) from None

    def __set__(self, inst, value) -> None:
        self._check(inst, "write")
        inst.__dict__[self.attr] = value

    def __delete__(self, inst) -> None:
        self._check(inst, "delete")
        inst.__dict__.pop(self.attr, None)

    def _check(self, inst, op: str) -> None:
        d = inst.__dict__
        idents = d.get("_kbt_guard_idents")
        if idents is None:
            idents = d.setdefault("_kbt_guard_idents", set())
        idents.add(_REAL_GET_IDENT())  # own-ident add: GIL-atomic
        if len(idents) < 2:
            return  # thread-confined so far — no race is possible yet
        self._count += 1
        if self.sample > 1 and self._count % self.sample:
            return
        if _blocking.unguarded_allowed():
            return
        # read the lock straight from the instance dict: the lock attr is
        # never itself instrumented, and __init__ ordering (value set
        # before the lock exists) degrades to a skip, not a crash
        if _owned_by_current(d.get(self.lock_attr)) is False:
            self._install._report(self, inst, op)


class GuardedAccessInstallation:
    """One batch of instrumented (class, attr, domain-lock) triples."""

    def __init__(self, state: LockdepState):
        self.state = state
        self._patched: List[Tuple[type, str]] = []
        self._reported: set = set()
        self._mu = _REAL_LOCK()

    def _report(self, desc: _GuardedAttr, inst, op: str) -> None:
        key = (desc._cls.__name__, desc.attr)
        if key in self._reported:
            return
        stack = _stack(skip=4)
        with self._mu:
            if key in self._reported:
                return
            self._reported.add(key)
        with self.state._mu:
            self.state.violations.append(Violation(
                "unguarded-access",
                f"{op} of {desc._cls.__name__}.{desc.attr} without holding "
                f"its inferred domain lock self.{desc.lock_attr} (tier D "
                "lock-domain map, analysis/races.py) on an instance already "
                "shared across threads — hold the lock or wrap the region "
                "in utils.blocking.allow_unguarded(\"<reason>\")",
                stack,
            ))

    def uninstall(self) -> None:
        for cls, attr in self._patched:
            if isinstance(cls.__dict__.get(attr), _GuardedAttr):
                delattr(cls, attr)
        self._patched = []


def install_guarded_access(specs, state: Optional[LockdepState] = None,
                           sample: int = 1) -> GuardedAccessInstallation:
    """Instrument `(module, class_name, attr, lock_attr)` tuples (the shape
    `races.runtime_domain_specs` returns, so the table is always the
    STATICALLY inferred one).  `state` defaults to the active lockdep
    state; violations appended there fail the plugin run."""
    import importlib

    if state is None:
        state = current_state()
    if state is None:
        state = LockdepState()
    inst = GuardedAccessInstallation(state)
    for module, cls_name, attr, lock_attr in specs:
        cls = getattr(importlib.import_module(module), cls_name)
        if isinstance(cls.__dict__.get(attr), _GuardedAttr):
            continue  # already instrumented (idempotent re-install)
        setattr(cls, attr, _GuardedAttr(inst, cls, attr, lock_attr, sample))
        inst._patched.append((cls, attr))
    return inst

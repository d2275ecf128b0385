"""SnapshotLease — the query plane's consistent read handle.

The per-cycle resident cache (api/resident.py) refreshes device columns
with DONATING scatters: the cycle's swap invalidates the very buffers a
concurrent reader might hold.  The broker makes reads safe anyway:

- the cycle publishes a lease AFTER its swap completes (the snapshot the
  solve consumed, whole — never a half-applied delta), stamped with the
  dirty-tracker version token of the open that built it;
- probe dispatches run inside :meth:`LeaseBroker.dispatch`, which counts
  the dispatch as an in-flight READER for the device round-trip;
- the cycle's swap runs inside :meth:`LeaseBroker.swap_guard`, which
  excludes new dispatches for the swap's duration and — on donating
  backends only — waits out in-flight readers before the scatters donate
  the buffers they may still reference.  On CPU, where api/resident.py
  skips donation, the old lease's arrays stay valid: the swap neither
  waits for readers nor retires the lease, and serving continues right
  through the cycle.

The broker's condition lock is held only for bookkeeping — never across a
device round-trip or a probe compile — so the cycle's publish path cannot
stall behind a cold dispatch.  (A COLD probe shape compiling inside a
dispatch still delays a donating swap that arrives mid-compile: the swap
must wait for the reader either way.  Steady-state shapes are jit-stable,
so this is a first-request cost per (B, G, evictions) bucket, not a
recurring one.)

Version tokens are monotonic: a query answered against lease N reports
``snapshot_version: N``, and N never decreases across responses.  Two
flushes are in flight at once (serve/batcher.py), so that takes a rule: a
flush registers the version it took in the same locked step that hands it
the lease (:meth:`LeaseBroker.dispatch` with a :class:`DeliveryTurn`), and
answers only once no flush that took an OLDER version is still to answer
(:meth:`DeliveryTurn.wait`).  Flushes of one version answer in any order,
and the wait comes after the lease's release, so it never holds a swap.  On
a donating backend a newer lease cannot exist until every reader of the
older one has released, which leaves the rule a thread descheduled between
its release and its answers; on CPU, where the old lease keeps serving
through a swap, the later flush can hold the NEWER lease and finish first.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, NamedTuple, Optional


class SnapshotLease(NamedTuple):
    """One published read handle — everything a probe dispatch needs."""

    snap: object          # DeviceSnapshot — per-cycle RESIDENT device columns
    meta: object          # SnapshotMeta — decode tables (names, bit maps)
    version: int          # dirty-tracker version token at the open
    config: object        # AllocateConfig the session implies
    evict_config: object  # EvictConfig (preempt) for the eviction probe
    mesh: object          # the solve mesh (None = single-device)
    probe_rows: tuple     # next-free task rows (the tie-hash oracle)
    queue_rows: Dict[str, int]  # queue name → row
    #: preempt victim gates the session's conf carries that the eviction
    #: probe does NOT model (drf/proportion) — surfaced per response as
    #: `unmodeled: [...]` so clients can't silently over-trust a verdict
    unmodeled_gates: tuple = ()
    #: replication-stream record sequence number this lease's state
    #: corresponds to (replicate/); 0 = unreplicated.  Every verdict's
    #: staleness block is ``head_seq - seq`` in cycles.
    seq: int = 0


class DeliveryTurn:
    """One flush's place in the order of answers (module docstring): made
    by :meth:`LeaseBroker.delivery`, stamped with the lease's version by
    :meth:`LeaseBroker.dispatch`, given up when ``delivery``'s block ends."""

    __slots__ = ("_broker", "version")

    def __init__(self, broker: "LeaseBroker") -> None:
        self._broker = broker
        self.version: Optional[int] = None  # None until a lease was taken

    def wait(self) -> None:
        """Block until no flush that took an older version than this one
        is still to answer.  The order is strict, so two flushes never wait
        for each other; a flush that took no lease does not wait at all."""
        if self.version is None:
            return
        broker = self._broker
        with broker._cond:
            broker._cond.wait_for(lambda: not any(
                t.version < self.version for t in broker._undelivered))


def _donation_active() -> bool:
    """api/resident.py donates the stale resident buffers everywhere but
    CPU — mirror its gate, so the broker retires leases and waits out
    readers exactly when a swap would invalidate their buffers."""
    import jax

    return jax.default_backend() != "cpu"


class LeaseBroker:
    def __init__(self) -> None:
        # the lock is made here, not inside threading, so that the runtime
        # lockdep checker tracks it (as CycleTrigger's)
        self._cond = threading.Condition(lock=threading.Lock())
        self._lease: Optional[SnapshotLease] = None
        self._readers = 0       # in-flight probe dispatches
        self._swapping = False  # a resident swap holds exclusivity
        # the turns of the flushes that took a lease and have not answered
        self._undelivered: List[DeliveryTurn] = []
        self.published = 0   # publish count (diagnostics)
        self.retired = 0     # swap-guard retirements (donating backends)

    # ---- write side (the cycle) -----------------------------------------
    def publish(self, lease: SnapshotLease) -> None:
        """Install a new lease.  Version must not regress — the dirty
        tracker is monotonic, so a regression means a stale publisher."""
        with self._cond:
            if self._lease is not None and lease.version < self._lease.version:
                return  # stale publisher (e.g. a re-entrant idle publish)
            self._lease = lease
            self.published += 1
            self._cond.notify_all()

    def retire(self) -> None:
        """Drop the published lease without a swap — the guard plane's
        condemned-snapshot path: a solve whose sentinel tripped must not
        keep serving what-ifs from the very columns it condemned.  Readers
        already inside a dispatch finish against their held reference; new
        dispatches wait for the next clean cycle's publish (or 503 on
        timeout) — failing closed beats answering from corrupt state."""
        with self._cond:
            if self._lease is not None:
                self._lease = None
                self.retired += 1

    @contextmanager
    def swap_guard(self):
        """The resident swap's exclusion region (wired through
        ``ColumnStore.resident_swap_guard``): new probe dispatches park
        for the swap's duration, and on donating backends the swap first
        waits out in-flight readers and retires the published lease whose
        buffers the scatters are about to invalidate (republished by the
        cycle after its solve dispatch)."""
        with self._cond:
            self._cond.wait_for(lambda: not self._swapping)
            self._swapping = True
            if _donation_active():
                # readers may hold the very buffers the swap donates
                self._cond.wait_for(lambda: self._readers == 0)
                if self._lease is not None:
                    self._lease = None
                    self.retired += 1
        try:
            yield
        finally:
            with self._cond:
                self._swapping = False
                self._cond.notify_all()

    # ---- read side (the batcher's flush) --------------------------------
    def current(self, timeout: Optional[float] = None) -> Optional[SnapshotLease]:
        """The live lease, waiting up to ``timeout`` for one to be
        published (None on timeout — the server maps it to 503)."""
        with self._cond:
            if self._lease is None and timeout:
                self._cond.wait_for(lambda: self._lease is not None,
                                    timeout=timeout)
            return self._lease

    @contextmanager
    def delivery(self):
        """A flush's turn in the order of answers: yields the
        :class:`DeliveryTurn` to hand to :meth:`dispatch` and to wait on
        before answering, and takes it off the list when the block ends,
        however it ends: a flush that failed must not hold the later ones."""
        turn = DeliveryTurn(self)
        try:
            yield turn
        finally:
            with self._cond:
                if turn in self._undelivered:
                    self._undelivered.remove(turn)
                    self._cond.notify_all()

    @contextmanager
    def dispatch(self, timeout: Optional[float] = None,
                 turn: Optional[DeliveryTurn] = None):
        """Probe-dispatch region: yields the lease (or None on timeout)
        registered as an in-flight reader, so a concurrent swap cannot
        donate the buffers mid-read.  The broker lock itself is NOT held
        across the device round-trip — publish() and other dispatches
        proceed concurrently.  A ``turn`` is stamped with the lease's
        version and listed as undelivered in the step that reads the lease:
        no newer version can be taken, let alone answered, in between."""
        with self._cond:
            if timeout:
                self._cond.wait_for(
                    lambda: self._lease is not None and not self._swapping,
                    timeout=timeout,
                )
            # a swap in flight parks the dispatch regardless of timeout —
            # the pre-rewrite lock gave exactly this unconditional wait
            self._cond.wait_for(lambda: not self._swapping)
            lease = self._lease
            if lease is not None:
                self._readers += 1
                if turn is not None:
                    turn.version = lease.version
                    self._undelivered.append(turn)
        try:
            yield lease
        finally:
            if lease is not None:
                with self._cond:
                    self._readers -= 1
                    self._cond.notify_all()

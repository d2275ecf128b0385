"""The eviction seam of a standalone deployment: a bounded, append-only log
of the evictions the scheduler ordered, served as ``GET
/v1/evictions?since=N`` (cmd/server.py).

With ``--master`` an eviction is a pod DELETE at the apiserver and the
kubelet and the watch do the rest (k8s/bind.py).  Without it nothing
delivered the order: the pod went ``RELEASING`` and stayed there.  The
program still deletes nothing by itself: termination is the client's (the
kubelet's) act and arrives as the ordinary ``DELETE /v1/pods``; this log
is how the client learns which pods to take away, in the shape of
``/v1/replicate?since=N``: a sequence number a client resumes from.

One writer at a time (``record_many`` takes the log's own lock; the cache
calls it once a batch of evictions), any number of readers, none of which takes a lock: a
reader copies the slots between its cursor and the sequence number it read
first, and keeps those whose own number says they were not overwritten
meanwhile.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

from kube_batch_tpu.api.pod import Pod

#: entries kept; a client further behind is told how many it missed
CAPACITY = 65536
#: entries in one answer at most
PAGE = 4096


class EvictionLog:
    """The standalone ``Evictor``: ``evict`` has nobody to call (the order
    is delivered by being served), ``record`` appends what the cache knows
    of it."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = int(capacity)
        self._ring: List[Optional[Tuple[int, str, str, str, str]]] = (
            [None] * self.capacity)
        self._next = 0
        self._mu = threading.Lock()

    def evict(self, pod: Pod) -> None:
        """The ``Evictor`` protocol (cache/interface.py)."""

    def record(self, pod: str, node: str, action: str, claimant: str) -> int:
        """Append one ordered eviction; returns its sequence number."""
        return self.record_many([(pod, node, action, claimant)])

    def record_many(self, entries) -> int:
        """Append ordered evictions, ``[(pod, node, action, claimant)]``, in
        the order given, under one acquisition of the log's lock; returns
        the first's sequence number.  ``_next`` moves an entry at a time,
        after the slot is written: a reader sees what a ``record`` a piece
        would have shown it, whole entries and no gap."""
        with self._mu:
            first = seq = self._next
            ring, cap = self._ring, self.capacity
            for pod, node, action, claimant in entries:
                ring[seq % cap] = (seq, pod, node, action, claimant)
                seq += 1
                self._next = seq
        return first

    def since(self, since: int, page: int = PAGE) -> dict:
        """The entries numbered ``since`` and later, oldest first, ``page``
        at most: ``{"next", "first", "evictions": [{"seq", "pod", "node",
        "action", "claimant"}]}``.  ``next`` is the cursor to ask with next
        time; ``first`` the oldest number still held (a client whose cursor
        is older missed ``first - since`` entries)."""
        # kbt: allow[KBT301] the lock-free read this log exists for: one int
        # read first, then slots that each carry their own number; a slot
        # overwritten meanwhile fails the check below and is left out
        end = self._next
        first = max(0, end - self.capacity)
        start = min(max(int(since), first), end)
        stop = min(end, start + max(1, int(page)))
        # kbt: allow[KBT301] as above: the list is never replaced, only its
        # slots are, each by one reference store
        ring, cap = self._ring, self.capacity
        out = []
        for n in range(start, stop):
            entry = ring[n % cap]
            if entry is not None and entry[0] == n:
                out.append({"seq": n, "pod": entry[1], "node": entry[2],
                            "action": entry[3], "claimant": entry[4]})
        return {"next": stop, "first": first, "evictions": out}

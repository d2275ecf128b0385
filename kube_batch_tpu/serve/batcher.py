"""MicroBatcher — the /v1/whatif front end's amortization engine.

Concurrent HTTP handler threads call :meth:`submit` and block on a future;
:data:`WORKERS` worker threads on one loop each collect requests into a
batch and hand it to the flush callback (the QueryPlane's probe dispatch),
so up to that many flushes are in flight: while one waits for the device,
the next takes its batch, encodes and dispatches.  Flush fires when EITHER
the batch bucket fills OR the oldest queued request's deadline window
elapses — so a lone request pays at most ``window`` extra latency while a
burst of hundreds rides one device dispatch.  A batch belongs to the one
worker that took it (``_take`` runs under the condition), and the callback
has to be safe to run on two threads at once.

Knobs (all overridable per instance; env defaults):

- ``KB_WHATIF_BATCH``   — batch bucket (max requests per dispatch), default 16
- ``KB_WHATIF_WINDOW_MS`` — flush deadline from first enqueue, default 5 ms
- ``KB_WHATIF_QUEUE``   — bounded queue depth; overflow rejects the request
  immediately (503 at the HTTP layer) instead of building unbounded backlog

The clock is injected for the deadline/overflow tests (a stubbed clock +
``tick()`` drives the flush logic deterministically without the thread).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Callable, List, Optional, Tuple

from kube_batch_tpu import metrics
from kube_batch_tpu.envutil import env_int


#: flushes in flight at once (the worker threads on the one loop).  A
#: constant, not a knob: the device's share of a flush is about half (it
#: works 8.5-10.8 ms inside a host span of 16.7-18.8 ms; PERF.md section 6,
#: chip runs of PR 45), so a second flush finds the device free while the
#: first is on the host and a third would find it taken.
WORKERS = 2


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class QueueFull(Exception):
    """The bounded request queue is at capacity — shed, don't buffer."""


class MicroBatcher:
    def __init__(
        self,
        flush: Callable[[List[Tuple[object, Future]]], None],
        max_batch: Optional[int] = None,
        window_s: Optional[float] = None,
        max_queue: Optional[int] = None,
        clock=time,
        start_thread: bool = True,
    ):
        self._flush = flush
        self.max_batch = max_batch if max_batch is not None else env_int(
            "KB_WHATIF_BATCH", 16)
        self.window_s = window_s if window_s is not None else _env_float(
            "KB_WHATIF_WINDOW_MS", 5.0) / 1e3
        self.max_queue = max_queue if max_queue is not None else env_int(
            "KB_WHATIF_QUEUE", 1024)
        self.clock = clock
        # the lock is made here, not inside threading, so that the runtime
        # lockdep checker tracks it (as CycleTrigger's)
        self._cond = threading.Condition(lock=threading.Lock())
        self._pending: deque = deque()  # (request, future, enqueue_t)
        self._stopped = False
        self.rejected = 0
        self._threads = [
            threading.Thread(target=self._loop, daemon=True,
                             name=f"whatif-batcher-{i}")
            for i in range(WORKERS if start_thread else 0)
        ]
        for t in self._threads:
            t.start()

    # ---- producer side ---------------------------------------------------
    def submit(self, request) -> Future:
        """Enqueue one request; the returned future resolves with the
        flush callback's per-request answer (or QueueFull immediately when
        the bounded queue is at capacity)."""
        fut: Future = Future()
        with self._cond:
            if self._stopped:
                fut.set_exception(QueueFull("batcher stopped"))
                return fut
            if len(self._pending) >= self.max_queue:
                self.rejected += 1
                fut.set_exception(QueueFull(
                    f"whatif queue at capacity ({self.max_queue})"))
                return fut
            self._pending.append((request, fut, self.clock.monotonic()))
            self._cond.notify_all()
        return fut

    def depth(self) -> int:
        with self._cond:
            return len(self._pending)

    # ---- flush logic (thread-driven in production, tick-driven in tests) -
    def _due(self, now: float) -> bool:
        """Flush condition under the lock: bucket full or window elapsed."""
        if not self._pending:
            return False
        if len(self._pending) >= self.max_batch:
            return True
        return now - self._pending[0][2] >= self.window_s

    def _take(self, now: float) -> List[Tuple[object, Future]]:
        """The next batch off the queue (caller holds the condition).  How
        long each request sat here is observed at ``now``, the start of its
        flush: the wait crosses threads, so no span can hold it."""
        n = min(self.max_batch, len(self._pending))
        out, waited = [], 0.0
        for _ in range(n):
            req, fut, enqueued = self._pending.popleft()
            out.append((req, fut))
            waited += now - enqueued
        metrics.observe_whatif_queue_wait(waited * 1e3, n)
        return out

    def tick(self, now: Optional[float] = None) -> int:
        """Flush if due; returns the number of requests flushed.  The unit
        tests drive this directly with a stubbed clock, one flush at a
        time on the caller's thread; a worker thread is just tick() in a
        wait loop."""
        now = self.clock.monotonic() if now is None else now
        with self._cond:
            if not self._due(now):
                return 0
            batch = self._take(now)
        self._run_flush(batch)
        return len(batch)

    def _run_flush(self, batch: List[Tuple[object, Future]]) -> None:
        try:
            self._flush(batch)
        except Exception as e:  # noqa: BLE001 — a failed dispatch fails ITS batch only
            for _req, fut in batch:
                if not fut.done():
                    fut.set_exception(e)

    def _await_batch(self) -> Optional[List[Tuple[object, Future]]]:
        """The next due batch (caller holds the condition), None once
        stopped.  Waits until tick's OWN flush condition holds — _due is
        the single flush policy (bucket full, or the FIRST queued
        request's window elapsed; submit notifies on fill, the timed wait
        tracks the window deadline) — and looks again after every wait:
        the other worker may have taken what this one was waiting for."""
        while not self._stopped:
            if not self._pending:
                self._cond.wait()
                continue
            now = self.clock.monotonic()
            if self._due(now):
                return self._take(now)
            # > 0 here: an elapsed window makes _due true (a clock race
            # just means an immediate recheck)
            self._cond.wait(
                max(self._pending[0][2] + self.window_s - now, 0.0))
        return None

    def _loop(self) -> None:
        while True:
            with self._cond:
                batch = self._await_batch()
            if batch is None:
                break
            self._run_flush(batch)
        # drain on stop: fail whatever is still queued
        with self._cond:
            leftovers = list(self._pending)
            self._pending.clear()
        for _req, fut, _t in leftovers:
            if not fut.done():
                fut.set_exception(QueueFull("batcher stopped"))

    def stop(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=5)

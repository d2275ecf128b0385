"""MicroBatcher — the /v1/whatif front end's amortization engine.

Concurrent HTTP handler threads call :meth:`submit` and block on a future;
one worker thread collects requests into a batch and hands it to the flush
callback (the QueryPlane's probe dispatch).  Flush fires when EITHER the
batch bucket fills OR the oldest queued request's deadline window elapses
— so a lone request pays at most ``window`` extra latency while a burst of
hundreds rides one device dispatch.

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
        self._cond = threading.Condition()
        self._pending: deque = deque()  # (request, future, enqueue_t)
        self._stopped = False
        self.rejected = 0
        self._thread: Optional[threading.Thread] = None
        if start_thread:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="whatif-batcher"
            )
            self._thread.start()

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
        tests drive this directly with a stubbed clock; the worker thread
        is just tick() in a wait loop."""
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

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._pending and not self._stopped:
                    self._cond.wait()
                if self._stopped:
                    break
                # wait until tick's OWN flush condition holds — _due is
                # the single flush policy (bucket full, or the FIRST
                # queued request's window elapsed; submit notifies on
                # fill, the timed wait tracks the window deadline)
                while (not self._due(self.clock.monotonic())
                       and not self._stopped):
                    remaining = (
                        self._pending[0][2] + self.window_s
                        - self.clock.monotonic()
                    )
                    # remaining > 0 here: an elapsed window makes _due
                    # true (a clock race just means an immediate recheck)
                    self._cond.wait(max(remaining, 0.0))
                if self._stopped:
                    break
                batch = self._take(self.clock.monotonic())
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
        if self._thread is not None:
            self._thread.join(timeout=5)

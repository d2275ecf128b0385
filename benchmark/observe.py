"""How the client sees a decision: a scrape of ``/metrics`` at a fixed
period for the cumulative count of bind decisions, ``D(t)``.

``volcano_arrival_to_decision_latency_milliseconds_count`` grows by one for
every bind of a pod that arrived unbound, renders as an exact integer and
takes no cache lock.  (A poll of ``/v1/bindings`` builds, sorts and
serialises every bound row under the cache lock, and would time itself.)
A burst that was due at ``t`` with ``A`` pods posted up to and including
it is decided at the first scrape at which ``D >= A``.
"""

from __future__ import annotations

import bisect
import re
import threading
import time

DECISIONS = b"volcano_arrival_to_decision_latency_milliseconds_count{} "
LEASE_VERSION = b"volcano_whatif_snapshot_version{} "
_SERIES = re.compile(
    rb"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)\{(?P<labels>[^}]*)\} (?P<v>\S+)$",
    re.M)


def _value_after(text: bytes, key: bytes, default: float = 0.0) -> float:
    i = text.find(key)
    if i < 0:
        return default
    j = text.find(b"\n", i)
    return float(text[i + len(key):j if j >= 0 else len(text)])


def parse_metrics(text: bytes) -> dict:
    """{(name, labels string): value} of one /metrics page."""
    return {(m["name"].decode(), m["labels"].decode()): float(m["v"])
            for m in _SERIES.finditer(text)}


class Scraper:
    """A thread that reads ``D`` every ``period_s`` from ``start()`` to
    ``stop()`` and keeps (time received, D, lease version)."""

    def __init__(self, server, period_s: float):
        self.server = server
        self.period_s = period_s
        self.times: list = []
        self.counts: list = []
        self.versions: list = []
        self.errors = 0
        self._cv = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(target=self._run, name="scraper",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop = True
        self._thread.join(timeout=30)

    def scrape_once(self):
        status, text = self.server.raw("GET", "/metrics", timeout=30.0)
        now = time.monotonic()
        if status != 200:
            raise OSError(f"/metrics answered {status}")
        return now, int(_value_after(text, DECISIONS)), int(
            _value_after(text, LEASE_VERSION))

    def _run(self) -> None:
        due = time.monotonic()
        while not self._stop:
            try:
                now, count, version = self.scrape_once()
            except (OSError, ValueError):
                self.errors += 1
                time.sleep(self.period_s)
                continue
            with self._cv:
                self.times.append(now)
                self.counts.append(count)
                self.versions.append(version)
                self._cv.notify_all()
            due = max(due + self.period_s, now)
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)

    def wait_count(self, want: int, timeout: float) -> float:
        """Block until a scrape shows ``D >= want``; returns that scrape's
        time, or None at the timeout."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while not self.counts or self.counts[-1] < want:
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self._cv.wait(min(left, 1.0))
            return self.times[bisect.bisect_left(self.counts, want)]

    def decided_at(self, want: int):
        """Time of the first scrape with ``D >= want`` (None if none)."""
        i = bisect.bisect_left(self.counts, want)
        return self.times[i] if i < len(self.counts) else None

    def version_at(self, t: float) -> int:
        """The lease version the last scrape before ``t`` saw."""
        i = bisect.bisect_right(self.times, t) - 1
        return self.versions[i] if i >= 0 else 0


def percentile(values: list, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; every request of the window is in ``values``."""
    if not values:
        raise ValueError("no samples")
    s = sorted(values)
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)

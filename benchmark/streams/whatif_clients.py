"""Closed-loop what-if clients — autoscalers and admission hooks that each
wait for their reply.  Each client sends a seeded stream: ``probe_share``
of ``POST /v1/whatif`` (count from ``counts``, member request from the
configuration's mix, queue Zipf(1.0) over the queues) and the rest
``POST /v1/whatif/sweep`` (``max_count``).  One request in ``block`` asks
for a member larger than any node, which no state of the cluster fits.

Inside the window only what holds under churn is checked: every verdict
carries a ``snapshot_version``; a giant is never feasible; a sweep's
``max_fit`` lies in [0, max_count] and agrees with its ``feasible``; a
request from the mix of at most six members, which the half-empty cluster
holds thousands of times over, is feasible (a larger gang binds in one
cycle only if the nodes tried first hold several members each: reported,
not judged).  The exact comparison with the ledger follows
the window (run.py, reference.py).

A 503 is the plane's "not now, ask again": a resident swap retires the
lease, and a dispatch that waits longer than the plane's own limit for the
next one refuses its whole batch.  A client that needs the answer asks
again, so each client here does, at once, and the request's latency runs
from its first send to its answer, refusals included.  It has failed only
if no answer came within server.REFUSAL_PATIENCE_S, or any other error did.

params: clients, probe_share, counts, max_count, block, warm_requests,
warm_counts (one probe of each, so that every gang bucket the sweeps'
searches can reach is compiled before the window).
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np


class Stream:
    def __init__(self, ctx, params: dict, seed: int, seconds: float):
        self.ctx, self.p, self.seed, self.seconds = ctx, params, seed, seconds
        queues = [q["name"] for q in ctx.config["queues"]]
        zipf = 1.0 / np.arange(1, len(queues) + 1)
        self.queues, self.zipf = queues, zipf / zipf.sum()
        # per client: [(t_send, ms, version or None, bad, unanswered, 503s)]
        self.records = []

    def requests(self, rng):
        """A client's endless stream of (path, body, kind).  It comes in
        blocks of ``block`` requests that all hold the same work: the same
        number of sweeps, of probes of each count, of each request size of
        the mix and one giant, in an order drawn from the seed, so that a
        seed changes the order of the work and never its amount."""
        mix, node = self.ctx.config["request_mix"], self.ctx.config["node"]
        sizes = [(float(c), float(m)) for c in mix["cpu_milli"]
                 for m in mix["memory_bytes"]]
        block = int(self.p["block"])
        n_sweeps = round(block * (1.0 - self.p["probe_share"]))
        counts = self.p["counts"]
        kinds = ["sweep"] * n_sweeps + [
            int(counts[i % len(counts)]) for i in range(block - n_sweeps)]
        while True:
            order = rng.permutation(block)
            giant = int(rng.integers(block))
            size_order = rng.permutation(len(sizes))
            for j, k in enumerate(order):
                cpu, mem = sizes[size_order[j % len(sizes)]]
                kind = "giant" if j == giant else (
                    "sweep" if kinds[k] == "sweep" else "probe")
                if kind == "giant":
                    cpu = 2.0 * node["cpu_milli"]
                queue = self.queues[int(rng.choice(len(self.queues),
                                                   p=self.zipf))]
                req = {"cpu": cpu, "memory": mem}
                if kinds[k] == "sweep":
                    yield "/v1/whatif/sweep", {
                        "queue": queue, "requests": req,
                        "max_count": int(self.p["max_count"])}, kind
                else:
                    yield "/v1/whatif", {"queue": queue, "count": kinds[k],
                                         "requests": req}, kind

    @staticmethod
    def faults(resp: dict, body: dict, kind: str) -> int:
        bad = 0 if "snapshot_version" in resp else 1
        sweep = "max_count" in body
        if sweep:
            fit = resp.get("max_fit")
            if not isinstance(fit, int) or not 0 <= fit <= body["max_count"]:
                bad += 1
            elif bool(resp.get("feasible")) != (fit >= 1):
                bad += 1
        if kind == "giant":
            bad += 1 if resp.get("feasible") else 0
        elif not resp.get("feasible") and (sweep or body["count"] <= 6):
            # one cycle places a gang in six bidding rounds, each of which
            # fills a node: up to six members always land where there is
            # room, more only if the nodes tried first hold several
            bad += 1
        return bad

    def _warm_one(self, path: str, body: dict) -> None:
        # the first request of a shape compiles its probe program, and the
        # plane answers 503 past its own time limit meanwhile
        status, raw, _ = self.ctx.server.post_until_answered(
            path, json.dumps(body).encode(), 300.0)
        if status != 200:
            raise self.ctx.failure(
                f"warm-up {path} answered {status}: {raw[:200]!r}")

    def warm(self) -> None:
        """Every (count bucket, sweep) shape once, so the probe programs
        are compiled before the window."""
        stream = self.requests(np.random.default_rng([self.seed, 0x3A]))
        for _ in range(int(self.p["warm_requests"])):
            path, body, _kind = next(stream)
            self._warm_one(path, body)
        mix = self.ctx.config["request_mix"]
        small = {"cpu": float(min(mix["cpu_milli"])),
                 "memory": float(min(mix["memory_bytes"]))}
        for count in self.p.get("warm_counts", ()):
            self._warm_one("/v1/whatif", {
                "queue": self.queues[0], "count": int(count),
                "requests": small})

    def _client(self, c: int, out: list) -> None:
        ctx = self.ctx
        stream = self.requests(np.random.default_rng([self.seed, 0x3B, c]))
        t_end = ctx.t_window + self.seconds
        while True:
            path, body, kind = next(stream)
            t0 = time.monotonic()
            if t0 >= t_end:
                return
            try:
                status, raw, refusals = ctx.server.post_until_answered(
                    path, json.dumps(body).encode())
            except OSError:
                status, raw, refusals = None, b"", 0
            ms = (time.monotonic() - t0) * 1e3
            if status != 200:
                out.append((t0, ms, None, 1, True, refusals))
            else:
                resp = json.loads(raw)
                out.append((t0, ms, resp.get("snapshot_version"),
                            self.faults(resp, body, kind), False, refusals))

    def run(self) -> None:
        self.records = [[] for _ in range(int(self.p["clients"]))]
        threads = [threading.Thread(target=self._client, args=(c, out),
                                    name=f"whatif-{c}", daemon=True)
                   for c, out in enumerate(self.records)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def finish(self) -> None:
        ctx = self.ctx
        rows = [r for out in self.records for r in out]
        unanswered = sum(1 for r in rows if r[4])
        done = [r for r in rows if not r[4]]
        ctx.samples["whatif_ms"] = [r[1] for r in done]
        ctx.samples["whatif_refusals"] = [r[5] for r in rows]
        ctx.samples["whatif_staleness"] = [
            max(0, ctx.scraper.version_at(r[0]) - r[2])
            for r in done if r[2] is not None]
        ctx.numbers["whatif_in_window_bad"] = (
            ctx.numbers.get("whatif_in_window_bad", 0)
            + sum(r[3] for r in done))
        # which refusals the plane itself gave for want of a lease (the
        # rest are the handler's own time limit)
        before, after = ctx.metrics_pages.get("window", ({}, {}))
        key = ("volcano_whatif_requests_total", 'verdict="error"')
        ctx.notes["whatif_refusals"] = sum(r[5] for r in rows)
        ctx.notes["whatif_plane_errors"] = (after.get(key, 0.0)
                                            - before.get(key, 0.0))
        ctx.attempted += len(rows)
        ctx.failed += unanswered

"""Open-loop churn: at each due time one DELETE of ``gangs`` bound gangs
(pods, then PodGroups) and one POST of as many new ones (PodGroups, then
pods), as list bodies, whether or not the last burst has been decided.
Every pod stays schedulable: what is deleted frees at least what is posted
asks for.

Every seed gets the same set of gaps in another order, and the same
number of bursts, so a seed changes when the bursts fall and what they ask
for, never how much work the window holds.  ``gaps`` names the set:
``near_even`` (the default) stretches or shrinks each 1/rate by up to
``jitter`` (``n`` evenly spread factors in [1 - jitter, 1 + jitter]);
``exponential`` is a Poisson process's, the n - 1 gaps at the evenly spread
quantiles of an exponential of mean 1/rate (10 ms to 4.6/rate for 50), so
that bursts bunch, share cycles and leave long pauses as independent
arrivals do.

params: rate (bursts/s), gangs (per burst), gaps, jitter, warm_sizes, settled_ms,
warm_audits (0), max_warm_bursts (200), prefix (of the sample names, "").
"""

from __future__ import annotations

import json
import time

import numpy as np


class Stream:
    def __init__(self, ctx, params: dict, seed: int, seconds: float):
        self.ctx, self.p = ctx, params
        self.rng = np.random.default_rng([seed, 0xC4])
        self.prefix = params.get("prefix", "")
        self.n = max(1, int(params["rate"] * seconds))
        if params.get("gaps", "near_even") == "exponential":
            # n - 1 gaps between n bursts, the first burst half a mean gap in
            gaps = -np.log1p(-(np.arange(self.n - 1) + 0.5) / (self.n - 1))
            gaps *= (self.n - 1) / max(gaps.sum(), 1e-9)
            self.rng.shuffle(gaps)
            self.due = (0.5 + np.concatenate(
                ([0.0], np.cumsum(gaps)))) / params["rate"]
        else:
            jitter = float(params["jitter"])
            gaps = 1.0 + jitter * np.linspace(-1.0, 1.0, self.n)
            self.rng.shuffle(gaps)
            # the first burst is due half a gap in, the last half a gap
            # before the window closes
            self.due = (np.cumsum(gaps) - 0.5 * gaps[0]) / params["rate"]
        self.window = [self._plan() for _ in range(self.n)]

    def _plan(self, times: int = 1):
        """A burst of ``times`` x ``gangs`` new gangs, rendered."""
        mix = self.ctx.config["request_mix"]
        gang = self.ctx.config["gang"]
        pgs, pods = self.ctx.ledger.make_gangs(
            times * int(self.p["gangs"]), int(gang["size"]),
            int(gang["min_member"]), mix["cpu_milli"], mix["memory_bytes"])
        return (pgs, pods, json.dumps(pgs).encode(), json.dumps(pods).encode())

    def _send(self, burst) -> tuple:
        """One burst; returns (seconds it took, cumulative pods posted)."""
        ctx, (pgs, pods, pgs_body, pods_body) = self.ctx, burst
        old_pgs, old_pods = ctx.ledger.oldest_gangs(len(pgs))
        t0 = time.monotonic()
        ctx.server.send_raw("DELETE", "pods", json.dumps(old_pods).encode(),
                            len(old_pods))
        ctx.server.send_raw("DELETE", "podgroups",
                            json.dumps(old_pgs).encode(), len(old_pgs))
        ctx.server.send_raw("POST", "podgroups", pgs_body, len(pgs))
        ctx.server.send_raw("POST", "pods", pods_body, len(pods))
        took = time.monotonic() - t0
        ctx.ledger.retire(old_pgs, old_pods)
        ctx.ledger.add(pgs, pods)
        return took, ctx.posted(len(pods))

    def warm(self) -> None:
        """Bursts until nothing is left to compile and the loop has settled.

        First one burst of each size in ``warm_sizes`` (multiples of
        ``gangs``), each waited for: the program's warm solve is compiled
        per rung of pending rows (128, 256, ...) and of changed nodes (64,
        512, 4096), and a rung met for the first time inside the window
        stalls the loop for seconds.  Then plain bursts until all of this
        holds: the last three were each decided within ``settled_ms``
        (after a cold drain of C seconds the program's adaptive coalescing
        floor, an average of cycle costs that starts at C, holds the loop
        to one cycle a second for about ln(C)/0.22 cycles, and a burst then
        waits 1-3 s); and the guard's shadow oracle has run ``warm_audits``
        times (it audits every 64th fast-path dispatch, and its first audit
        compiles the oracle program on the loop thread for tens of seconds).
        Each is waited for, as the window's bursts are in effect: at the
        cell's rate a burst is decided before the next is due.  A loop that
        has not settled after ``max_warm_bursts`` ends the run."""
        ctx, p = self.ctx, self.p
        for k in p["warm_sizes"]:
            _, target = self._send(self._plan(int(k)))
            if ctx.scraper.wait_count(target, 180.0) is None:
                raise ctx.failure("a warm-up burst was never decided")
        last = []  # ms the last three bursts took
        for i in range(int(p.get("max_warm_bursts", 200))):
            if (len(last) == 3 and max(last) <= float(p["settled_ms"])
                    and self._audited()):
                ctx.notes[self.prefix + "warm_bursts"] = i
                return
            t0 = time.monotonic()
            _, target = self._send(self._plan())
            t_dec = ctx.scraper.wait_count(target, 180.0)
            if t_dec is None:
                raise ctx.failure("a warm-up burst was never decided")
            last = (last + [(t_dec - t0) * 1e3])[-3:]
            time.sleep(max(0.0, 0.25 - (time.monotonic() - t0)))
        raise ctx.failure(
            f"the loop never settled: the last warm-up bursts took {last} ms")

    def _audited(self) -> bool:
        want = int(self.p.get("warm_audits", 0))
        return not want or self.ctx.server.get("/v1/guard")["audits_run"] >= want

    def run(self) -> None:
        ctx = self.ctx
        self.sent = []
        for due, burst in zip(self.due, self.window):
            delay = ctx.t_window + due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t_send = time.monotonic()
            took, target = self._send(burst)
            self.sent.append((ctx.t_window + due, t_send, took, target))

    def finish(self) -> None:
        ctx, pre = self.ctx, self.prefix
        lat, late, post = [], [], []
        undecided = 0
        for t_due, t_send, took, target in self.sent:
            t_dec = ctx.scraper.decided_at(target)
            if t_dec is None:
                undecided += 1
                continue
            lat.append((t_dec - t_due) * 1e3)
            late.append((t_send - t_due) * 1e3)
            post.append(took * 1e3)
        ctx.samples[pre + "burst_latency_ms"] = lat
        ctx.samples[pre + "generator_late_ms"] = late
        ctx.samples[pre + "ingest_post_ms"] = post
        ctx.attempted += self.n
        ctx.failed += undecided + (self.n - len(self.sent))

"""Closed loop, one client, as the reference's kubemark density test makes
its batches one after another: POST a batch of plain pods, wait until the
scheduler has decided them all, DELETE the batch, next.  Before the first
batch of the window one gang with ``minMember`` = its size lands; its
latency is one sample of its own.

params: batch (pods), cpu_milli, gang {size, min_member, cpu_milli},
warm_batches (at least), settled_ms, max_warm_batches (200).
"""

from __future__ import annotations

import json
import time


class Stream:
    def __init__(self, ctx, params: dict, seed: int, seconds: float):
        self.ctx, self.p = ctx, params
        self.seconds = seconds

    def _gang(self) -> tuple:
        g = self.p["gang"]
        pgs, pods = self.ctx.ledger.make_gangs(
            1, int(g["size"]), int(g["min_member"]), [int(g["cpu_milli"])], [0])
        return pgs, pods

    def _land(self, pgs: list, pods: list) -> tuple:
        """POST, wait for the decisions; (latency s, POST s) or None."""
        ctx = self.ctx
        t0 = time.monotonic()
        if pgs:
            ctx.server.send("POST", "podgroups", pgs)
        ctx.server.send_raw("POST", "pods", json.dumps(pods).encode(),
                            len(pods))
        t_posted = time.monotonic()
        ctx.ledger.add(pgs, pods)
        t_dec = ctx.scraper.wait_count(ctx.posted(len(pods)), 60.0)
        if t_dec is None:
            return None
        return t_dec - t0, t_posted - t0

    def _remove(self, pgs: list, pods: list) -> None:
        self.ctx.server.send("DELETE", "pods", pods)
        if pgs:
            self.ctx.server.send("DELETE", "podgroups", pgs)
        self.ctx.ledger.retire(pgs, pods)

    def _batch(self) -> list:
        return self.ctx.ledger.make_pods(
            int(self.p["batch"]), int(self.p["cpu_milli"]), 0)

    def warm(self) -> None:
        pgs, pods = self._gang()
        if self._land(pgs, pods) is None:
            raise self.ctx.failure("the warm-up gang was never decided")
        self._remove(pgs, pods)
        # batches until the loop has settled (see churn_bursts.warm): the
        # last three each decided within settled_ms
        last = []
        for i in range(int(self.p.get("max_warm_batches", 200))):
            if (i >= int(self.p["warm_batches"]) and len(last) == 3
                    and max(last) <= float(self.p["settled_ms"])):
                self.ctx.notes["warm_batches"] = i
                return
            pods = self._batch()
            got = self._land([], pods)
            if got is None:
                raise self.ctx.failure("a warm-up batch was never decided")
            last = (last + [got[0] * 1e3])[-3:]
            self._remove([], pods)
        raise self.ctx.failure(
            f"the loop never settled: the last warm-up batches took {last} ms")

    def run(self) -> None:
        ctx = self.ctx
        self.lat, self.post, self.undecided, self.gang_ms = [], [], 0, None
        pgs, pods = self._gang()
        got = self._land(pgs, pods)
        if got is None:
            self.undecided += 1
        else:
            self.gang_ms = got[0] * 1e3
        t_end = ctx.t_window + self.seconds
        while True:
            pods = self._batch()
            got = self._land([], pods)
            if got is None:
                self.undecided += 1
                break
            self.lat.append(got[0] * 1e3)
            self.post.append(got[1] * 1e3)
            if time.monotonic() >= t_end:
                # the last batch stays: a DELETE is applied by the next
                # cycle, and the check that follows must find the cluster
                # as the ledger has it
                break
            self._remove([], pods)

    def finish(self) -> None:
        ctx = self.ctx
        ctx.samples["burst_latency_ms"] = self.lat
        ctx.samples["ingest_post_ms"] = self.post
        ctx.notes["gang_100_ms"] = self.gang_ms
        ctx.attempted += len(self.lat) + 1 + self.undecided - (
            1 if self.gang_ms is None else 0)
        ctx.failed += self.undecided

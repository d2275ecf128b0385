"""Open-loop churn of gangs that differ: ``churn_bursts`` over the ledger of
``reference_gangmix`` (a GPU column, each gang its own size and queue),
which this stream installs in ``ctx.ledger`` before the cluster is loaded.

At each due time one DELETE of the oldest bound gangs, as many as free at
least what the burst asks for, and one POST of ``gangs`` new gangs, whether
or not the last burst has been decided.  Every pod asks one GPU, so every
pod stays schedulable.

A seed changes when the bursts fall and in what order the gangs come,
never how much work the window holds: the window's ``n x gangs`` gangs have
the sizes and the queues that ``reference_gangmix.counts`` gives for that
many (at 50 x 25 the multiset 750 / 150 / 150 / 125 / 38 / 25 / 8 / 4 of
the sizes 1..128, and the Zipf quantiles of the 14 queues), dealt over the
bursts in seeded order so that no burst holds two gangs of ``APART``
members or more.

``warm()`` knows that plan.  The program compiles its warm solve per rung
of pending rows and of changed nodes, and keeps each rung for three plans
after the burst that needed it (api/resident.py), so which programs a
window meets depends on the order of its bursts.  The warm-up therefore
walks the window's own plan once with fresh gangs of the same sizes in the
same order, each burst waited for, from the same state the window will
start from (three ordinary bursts), and only then settles as
``churn_bursts`` does.

The program pads its job axis to multiples of 1,024 rows, never shrinks it,
and a growth drops every carried table and compiles every program of the
steady path anew (18.6 s in the window of the one run that met it; PERF.md
section 6, PR 31).  The window's live gangs wander by a few hundred, because
a burst deletes as many gangs as cover its pods and posts ``gangs``, so the
warm-up first stretches that axis: it posts ``stretch_gangs`` PodGroups with
no pod and deletes them again.  The axis is a shape the window uses.

params: rate (bursts/s), gangs (per burst), jitter, settled_ms,
stretch_gangs (0), warm_audits (0), max_warm_bursts (200), prefix ("").
"""

from __future__ import annotations

import json
import time

import numpy as np

import reference_gangmix
from observe import percentile
from streams import churn_bursts

#: a burst holds at most one gang of this many members or more
APART = 64
#: bursts of the ordinary kind sent before the walk of the plan and at
#: least as many after it: a rung is dropped after three plans under it
ORDINARY_BEFORE = 3


class Stream(churn_bursts.Stream):
    def __init__(self, ctx, params: dict, seed: int, seconds: float):
        ctx.ledger = reference_gangmix.Ledger(ctx.config, seed)
        self.ctx, self.p = ctx, params
        n = max(1, int(params["rate"] * seconds))
        self.plan = self._deal(n, np.random.default_rng([seed, 0x6D]))
        self._next = iter(self.plan)
        super().__init__(ctx, dict(params, gaps="near_even"), seed, seconds)

    def _deal(self, n: int, rng) -> list:
        """[(sizes, queues)] of the window's ``n`` bursts."""
        ledger, per = self.ctx.ledger, int(self.p["gangs"])
        sizes = ledger.gang_sizes(n * per)
        big = sizes >= APART
        if int(big.sum()) > n:
            raise self.ctx.failure(
                f"{int(big.sum())} gangs of {APART} or more do not deal "
                f"over {n} bursts one apiece")
        bursts = [[] for _ in range(n)]
        for b, size in zip(rng.permutation(n), rng.permutation(sizes[big])):
            bursts[b].append(int(size))
        rest = iter(rng.permutation(sizes[~big]))
        for burst in bursts:
            burst.extend(int(next(rest)) for _ in range(per - len(burst)))
            rng.shuffle(burst)
        queues = ledger.gang_queues(n * per)
        queues = iter([queues[i] for i in rng.permutation(len(queues))])
        return [(burst, [next(queues) for _ in burst]) for burst in bursts]

    def _render(self, sizes, queues):
        pgs, pods = self.ctx.ledger.make_mix(sizes, queues)
        return (pgs, pods, json.dumps(pgs).encode(), json.dumps(pods).encode())

    def _plan(self):
        """The next burst of the window's plan, rendered."""
        return self._render(*next(self._next))

    def _ordinary(self):
        """A burst of the sizes ``gangs`` gangs have by the shares alone
        (none of ``APART`` or more at the cell's 25), in the queues that
        many have."""
        ledger = self.ctx.ledger
        sizes = ledger.gang_sizes(int(self.p["gangs"]))
        sizes = sizes[sizes < APART]
        return self._render(sizes, ledger.gang_queues(len(sizes)))

    def _send(self, burst) -> tuple:
        """One burst; returns (seconds it took, cumulative pods posted)."""
        ctx, (pgs, pods, pgs_body, pods_body) = self.ctx, burst
        old_pgs, old_pods = ctx.ledger.oldest_covering(len(pods))
        t0 = time.monotonic()
        if old_pods:
            ctx.server.send_raw("DELETE", "pods",
                                json.dumps(old_pods).encode(), len(old_pods))
            ctx.server.send_raw("DELETE", "podgroups",
                                json.dumps(old_pgs).encode(), len(old_pgs))
        ctx.server.send_raw("POST", "podgroups", pgs_body, len(pgs))
        ctx.server.send_raw("POST", "pods", pods_body, len(pods))
        took = time.monotonic() - t0
        ctx.ledger.retire(old_pgs, old_pods)
        ctx.ledger.add(pgs, pods)
        return took, ctx.posted(len(pods))

    def _send_and_wait(self, burst) -> float:
        """One warm-up burst, waited for; the ms it took to be decided."""
        t0 = time.monotonic()
        _, target = self._send(burst)
        t_dec = self.ctx.scraper.wait_count(target, 180.0)
        if t_dec is None:
            raise self.ctx.failure("a warm-up burst was never decided")
        time.sleep(max(0.0, 0.25 - (time.monotonic() - t0)))
        return (t_dec - t0) * 1e3

    def _stretch(self, n: int) -> None:
        """Grow the job axis by ``n`` rows and leave them free."""
        ledger = self.ctx.ledger
        pgs, _ = ledger.make_mix([1] * n, ledger.gang_queues(n))
        self.ctx.server.send("POST", "podgroups", pgs)
        self.ctx.server.send("DELETE", "podgroups", pgs)

    def warm(self) -> None:
        ctx, p = self.ctx, self.p
        t0 = time.monotonic()
        self._stretch(int(p.get("stretch_gangs", 0)))
        for _ in range(ORDINARY_BEFORE):
            self._send_and_wait(self._ordinary())
        walked = [self._send_and_wait(self._render(sizes, queues))
                  for sizes, queues in self.plan]
        ctx.notes[self.prefix + "warm_walk_s"] = time.monotonic() - t0
        ctx.notes[self.prefix + "warm_walk_worst_ms"] = max(walked)
        last = []  # ms the last three bursts took
        for i in range(int(p.get("max_warm_bursts", 200))):
            if (len(last) == ORDINARY_BEFORE
                    and max(last) <= float(p["settled_ms"])
                    and self._audited()):
                ctx.notes[self.prefix + "warm_bursts"] = i
                return
            last = (last + [self._send_and_wait(self._ordinary())]
                    )[-ORDINARY_BEFORE:]
        raise ctx.failure(
            f"the loop never settled: the last warm-up bursts took {last} ms")

    def finish(self) -> None:
        """``churn_bursts``' samples, and the window's latencies apart by
        whether the burst held a gang of ``APART`` or more (in the notes:
        what PERF.md's breakdown of the two kinds of burst reads)."""
        super().finish()
        ctx, pre = self.ctx, self.prefix
        kinds: dict = {"large": [], "ordinary": []}
        pods: dict = {"large": [], "ordinary": []}
        for (sizes, _), (t_due, _, _, target) in zip(self.plan, self.sent):
            t_dec = ctx.scraper.decided_at(target)
            if t_dec is not None:
                kind = "large" if max(sizes) >= APART else "ordinary"
                kinds[kind].append((t_dec - t_due) * 1e3)
                pods[kind].append(sum(sizes))
        for kind, lat in kinds.items():
            ctx.samples[f"{pre}burst_latency_ms.{kind}"] = lat
            if lat:
                ctx.notes[f"{pre}{kind}_bursts"] = {
                    "n": len(lat), "p50_ms": percentile(lat, 0.5),
                    "max_ms": max(lat), "pods_min": min(pods[kind]),
                    "pods_max": max(pods[kind])}

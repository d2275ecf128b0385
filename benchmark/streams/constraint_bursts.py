"""Open-loop replacement churn of pods that carry inter-pod terms:
``churn_bursts`` over the ledger of ``reference_constraints`` (five pod
templates with labels and (anti-)affinity terms, nodes with a zone), which
this stream installs in ``ctx.ledger`` before the cluster is loaded.

At each due time one DELETE of the oldest ``per_template`` live pods of each
template and one POST of as many new ones of each, in seeded order, whether
or not the last burst has been decided: the population stays
``pods_per_template`` a template, and every pod stays schedulable (what is
deleted frees what is posted asks for; one pod per node of a hostname
anti-affinity group leaves three fifths of the nodes open).  A seed changes
when the bursts fall and the order of the pods inside a burst, never how
much work the window holds.

The check that follows the run needs the order of the binds, which only the
program can give (``GET /v1/bindings?seq=1``), so the constructor asks for it
before any pod is loaded.  A program that cannot say ends the run there, in
seconds: it is also one that answers these terms by scanning objects and
does not finish one cycle of this deployment (4,000 resident termed rows x
5,000 nodes of Python calls a cycle), and a load that hangs is worse than a
run that fails.

``warm()`` is ``churn_bursts``': one burst of each size in ``warm_sizes``
(multiples of the burst: the program's sparse termed rows stand on rungs of
64, 512, 4096, and the pending bucket on rungs of its own), then plain bursts
until three in a row were decided within ``settled_ms`` and the guard's
oracle has audited; before and after it the ledger reads every bind with its
number, so that a pod a later burst deletes is still known to the walk
(``/v1/bindings`` lists live pods only).  ``finish()``: ``churn_bursts``'
samples, and the two controls of ``reference_constraints.Ledger.controls``
into the notes.

params: rate (bursts/s), per_template (pods of each template a burst),
jitter, warm_sizes, settled_ms, warm_audits (0), max_warm_bursts (200),
prefix ("").
"""

from __future__ import annotations

import json
import time

import numpy as np

import reference
import reference_constraints
from streams import churn_bursts


class Stream(churn_bursts.Stream):
    def __init__(self, ctx, params: dict, seed: int, seconds: float):
        # a program that cannot give the order of its binds answers 404, and
        # the run ends here (module docstring)
        ctx.server.get("/v1/bindings?seq=1")
        ctx.ledger = reference_constraints.Ledger(ctx.config, seed)
        self.order = np.random.default_rng([seed, 0xAF])
        self.targets: dict = {}    # burst -> decisions that cover its pods
        super().__init__(ctx, dict(params, gangs=0), seed, seconds)

    def _plan(self, times: int = 1):
        pods = self.ctx.ledger.make_burst(
            times * int(self.p["per_template"]), self.order)
        return (pods, json.dumps(pods).encode())

    def _send(self, burst) -> tuple:
        """One burst; returns (seconds it took, cumulative pods posted)."""
        ctx, (pods, body) = self.ctx, burst
        per = len(pods) // len(ctx.ledger.templates)
        old = ctx.ledger.oldest_of_each(per)
        t0 = time.monotonic()
        ctx.server.send_raw("DELETE", "pods", json.dumps(old).encode(),
                            len(old))
        ctx.server.send_raw("POST", "pods", body, len(pods))
        took = time.monotonic() - t0
        ctx.ledger.retire([], old)
        ctx.ledger.add([], pods, burst=True)
        burst, target = ctx.ledger._bursts, ctx.posted(len(pods))
        ctx.ledger.delete_sent[burst] = t0
        self.targets[burst] = target
        return took, target

    def _read_binds(self) -> list:
        ctx = self.ctx
        binds = [b for b in ctx.server.get("/v1/bindings?seq=1")
                 if b["status"] in reference.BOUND_STATUSES]
        ctx.ledger.note_binds(binds)
        return binds

    def warm(self) -> None:
        self._read_binds()     # the load, before a warm-up burst deletes any
        super().warm()
        self._read_binds()     # every pod a burst of the window will delete

    def finish(self) -> None:
        super().finish()
        ctx = self.ctx
        ctx.ledger.decided.update(
            {burst: ctx.scraper.decided_at(target)
             for burst, target in self.targets.items()})
        binds = self._read_binds()
        t0 = time.monotonic()
        ctx.notes.update(ctx.ledger.controls(binds))
        ctx.notes["controls_s"] = time.monotonic() - t0
        ctx.ledger.check_binds(binds)
        ctx.notes["term_counts"] = dict(ctx.ledger.term_counts)

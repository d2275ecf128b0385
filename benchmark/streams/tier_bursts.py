"""Open-loop arrivals of production gangs on a full cluster:
``churn_bursts`` over the ledger of ``reference_tiers`` (priority tiers,
evictable and protected pods, the eviction feed), which this stream installs
in ``ctx.ledger`` before the cluster is loaded, with a kubelet stand-in.

At each due time one POST of ``gangs`` production gangs, the same multiset
of queues in every burst (round-robin from the first queue), whether or not
the last burst has been decided.  Nothing is deleted by the stream: room is
made by the scheduler, which orders best-effort pods evicted.  The
**stand-in** is what a kubelet is to a ``--master`` deployment: it reports
the loaded pods Running once the cold drain has bound them (only Running
pods are victims), and from then on polls ``GET /v1/evictions?since=N``
every ``standin_period_ms`` and DELETEs what the feed names in one request
(grace 0).  Evicted pods are not posted again inside the run, so every pod
binds at most once and the decisions counter still reaches exactly the
pods posted.

The constructor posts the PriorityClasses and asks the feed once, before
any pod is loaded: a program without ``/v1/evictions`` fails in seconds.

``warm()``: Running reports; ``stretch_gangs`` PodGroups with no pod, posted
and deleted again (the program pads its job axis to multiples of 1,024 and
never shrinks it: the window's 625 new PodGroups must not carry it over a
multiple); one burst of each size in ``warm_sizes`` (multiples of
``gangs``, as in ``churn_bursts``: the program's warm solve is compiled per
rung of pending rows, 128, 256, 512, ..., and two bursts that come to share
a cycle in the window met a rung no warm-up had compiled, 5-17 s each, and
the run collapsed: 3 of 12 on the chip); the window's own bursts, at least
``min_warm_bursts`` and until three in a row were decided within
``settled_ms`` (at most ``max_warm_bursts``: each takes 100 pods' worth of
evictable capacity that the window needs; at least, because the bursts that
follow the first evictions are another regime, 1.2-2.4 s each while the
idle room's fragments last, and a window that holds ten of them and fifteen
of the later kind has its median on the boundary between the two); then
small pods that fit idle, one cycle each, until the guard's shadow oracle
has audited once more.  The oracle is compiled for the axes it is given and
audits every 64th allocate dispatch: an audit that meets a wider task axis
than the last one compiles the oracle in the serving loop, 1.8 s from the
machine's compile cache and 21 s without (2 of 12 runs collapsed on it in
the window; since allocate runs on, 2.7 dispatches a burst, the 64th fell
among the last plain bursts and 2 of 4 runs never settled).  So the same
ticks also follow the sized bursts, which grow the task axis as far as it
goes: the audit that compiles comes there, the one after the plain bursts
and the one ~64 dispatches into the window find the oracle compiled.

``finish()``: ``churn_bursts``' samples; then **the eviction edge round**
(``edge_rounds`` pairs of one-pod production gangs through the same served
path, ``run.py::edge_round`` is not used): the *exact* pod asks all the
CPU that reclaim may take for its queue on the node that offers most (its
cross-queue victims' sum: the victims alone have to cover a claimant, as in
reclaim.go:150-163, so a node's idle is no part of it) and has to bind after
exactly those are evicted; the *over* pod asks ``OVER_MILLI`` more than any
node would have free with the victims of any one claimant gone (idle plus
evictable, the most over the queues), may never bind and may cause no
eviction.  The round stays where the answer is unique: a request above
every node's idle (only an eviction lets the exact pod in, and reclaim's
cross-queue victims on the node that offers most are the one set that
covers it), an over pod that no later round's release makes room for (a
claimant of another queue frees pods of this one's: the most over the
queues, not this queue's own), one pair at a time on a quiescent cluster.
Then the controls,
into the notes: the same pods against a victim plane summed in
``control.edge`` (have to come out wrong), and ``reference_tiers.place``
with ``control.placement`` (has to leave a node over).

params: rate (bursts/s), gangs (per burst), jitter, settled_ms,
warm_sizes (none), min_warm_bursts (0), max_warm_bursts (40),
stretch_gangs (0), warm_audits (0: no ticks),
standin_period_ms (10), edge_rounds (12), prefix ("").
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np

import reference
import reference_tiers
from observe import percentile
from streams import churn_bursts

TIER = "production"
#: a tick of the warm-up: one pod of the lowest tier that fits idle anywhere
TICK_TIER, TICK_CPU = "free", 250
#: memory of a tick and of the edge round's pods (the edge is the CPU's)
SMALL_MEM = 1 << 30


class Stream(churn_bursts.Stream):
    def __init__(self, ctx, params: dict, seed: int, seconds: float):
        ctx.ledger = reference_tiers.Ledger(ctx.config, seed)
        self.cursor = int(ctx.server.get("/v1/evictions?since=0")["next"])
        ctx.server.send("POST", "priorityclasses",
                        ctx.ledger.priority_class_dicts())
        self.release_ms: list = []
        self._stop = threading.Event()
        self._standin = threading.Thread(
            target=self._serve_evictions, name="kubelet-standin", daemon=True)
        self._standin_error = None
        super().__init__(ctx, dict(params, gaps="near_even"), seed, seconds)

    # -- the bursts ----------------------------------------------------------

    def _plan(self, times: int = 1):
        pgs, pods = self.ctx.ledger.make_tier(
            TIER, times * int(self.p["gangs"]))
        return (pgs, pods, json.dumps(pgs).encode(), json.dumps(pods).encode())

    def _send(self, burst) -> tuple:
        """One burst; returns (seconds it took, cumulative pods posted)."""
        ctx, (pgs, pods, pgs_body, pods_body) = self.ctx, burst
        t0 = time.monotonic()
        ctx.server.send_raw("POST", "podgroups", pgs_body, len(pgs))
        ctx.server.send_raw("POST", "pods", pods_body, len(pods))
        took = time.monotonic() - t0
        ctx.ledger.add(pgs, pods)
        return took, ctx.posted(len(pods))

    # -- the kubelet stand-in ------------------------------------------------

    def _report_running(self) -> None:
        """Every loaded pod is bound: report it Running, as its kubelet
        would (a status update keeps the binding: cache.update_pod)."""
        ledger = self.ctx.ledger
        pods = [dict(p, phase="Running") for p in ledger.pod_dicts.values()]
        self.ctx.server.send("POST", "pods", pods)
        ledger.running.update(ledger.pod_dicts)

    def _serve_evictions(self) -> None:
        ctx, period = self.ctx, float(self.p.get("standin_period_ms", 10)) / 1e3
        try:
            while not self._stop.is_set():
                t0 = time.monotonic()
                page = ctx.server.get(f"/v1/evictions?since={self.cursor}")
                self.cursor = int(page["next"])
                doomed = ctx.ledger.note_evictions(page["evictions"])
                if doomed:
                    ctx.server.send("DELETE", "pods", doomed)
                    ctx.ledger.note_released(doomed)
                    self.release_ms.append((time.monotonic() - t0) * 1e3)
                self._stop.wait(max(0.0, period - (time.monotonic() - t0)))
        except Exception as e:  # noqa: BLE001 — carried to finish()
            self._standin_error = f"{type(e).__name__}: {e}"

    # -- set-up ----------------------------------------------------------------

    def _audits_run(self) -> int:
        return int(self.ctx.server.get("/v1/guard")["audits_run"])

    def _stretch(self, n: int) -> None:
        """Grow the job axis by ``n`` rows and leave them free."""
        if n:
            pgs, _ = self.ctx.ledger.make_tier(
                TICK_TIER, n, sizes=[1] * n, cpus=[TICK_CPU] * n,
                mem=SMALL_MEM)
            self.ctx.server.send("POST", "podgroups", pgs)
            self.ctx.server.send("DELETE", "podgroups", pgs)

    def _tick_until_audited(self) -> int:
        """One small pod a cycle until the shadow oracle has run again."""
        ctx, ledger, sent = self.ctx, self.ctx.ledger, []
        before = self._audits_run()
        while self._audits_run() == before:
            if len(sent) >= 256:
                raise ctx.failure("the guard never audited in the warm-up")
            pgs, pods = ledger.make_tier(TICK_TIER, 1, sizes=[1],
                                         cpus=[TICK_CPU], mem=SMALL_MEM)
            ctx.server.send("POST", "podgroups", pgs)
            ctx.server.send("POST", "pods", pods)
            ledger.add(pgs, pods)
            sent.append((pgs, pods))
            if ctx.scraper.wait_count(ctx.posted(1), 180.0) is None:
                raise ctx.failure("a warm-up tick was never decided")
        if sent:
            pgs = [g for gs, _ in sent for g in gs]
            pods = [p for _, ps in sent for p in ps]
            ctx.server.send("DELETE", "pods", pods)
            ctx.server.send("DELETE", "podgroups", pgs)
            ledger.retire(pgs, pods)
        return len(sent)

    def warm(self) -> None:
        ctx, p = self.ctx, self.p
        t0 = time.monotonic()
        self._report_running()
        self._standin.start()
        ctx.notes[self.prefix + "running_report_s"] = time.monotonic() - t0
        self._stretch(int(p.get("stretch_gangs", 0)))
        for k in p.get("warm_sizes", ()):
            _, target = self._send(self._plan(int(k)))
            if ctx.scraper.wait_count(target, 180.0) is None:
                raise ctx.failure("a warm-up burst was never decided")
        if int(p.get("warm_audits", 0)):
            # the sized bursts have grown the task axis as far as it will
            # go: the audit that compiles the oracle for it comes here, not
            # at whichever of the plain bursts makes the 64th dispatch
            ctx.notes[self.prefix + "warm_early_ticks"] = (
                self._tick_until_audited())
        last = []  # ms the last three bursts took
        for i in range(int(p.get("max_warm_bursts", 40)) + 1):
            if (i >= int(p.get("min_warm_bursts", 0)) and len(last) == 3
                    and max(last) <= float(p["settled_ms"])):
                ctx.notes[self.prefix + "warm_bursts"] = i
                ctx.notes[self.prefix + "warm_last_ms"] = last
                ctx.notes[self.prefix + "warm_ticks"] = (
                    self._tick_until_audited()
                    if int(p.get("warm_audits", 0)) else 0)
                return
            t_send = time.monotonic()
            _, target = self._send(self._plan())
            t_dec = ctx.scraper.wait_count(target, 180.0)
            if t_dec is None:
                raise ctx.failure("a warm-up burst was never decided")
            last = (last + [(t_dec - t_send) * 1e3])[-3:]
            time.sleep(max(0.0, 0.25 - (time.monotonic() - t_send)))
        raise ctx.failure(
            f"the loop never settled: the last warm-up bursts took {last} ms")

    # -- after the window ------------------------------------------------------

    def finish(self) -> None:
        super().finish()
        ctx, pre = self.ctx, self.prefix
        try:
            self._window_notes()
            self._edge_round()
        finally:
            self._stop.set()
            self._standin.join(timeout=30.0)
        if self._standin_error:
            raise ctx.failure(f"the kubelet stand-in: {self._standin_error}")
        ctx.samples[pre + "standin_release_ms"] = self.release_ms

    def _growth(self, series: str, labels: str) -> float:
        before, after = self.ctx.metrics_pages["window"]
        return after.get((series, labels), 0.0) - before.get(
            (series, labels), 0.0)

    def _window_notes(self) -> None:
        """What the window's bursts were, for PERF.md and the acceptance:
        bursts still undecided when the next was due, the claims each
        evict mode committed, and the share of the window's pods that a
        committed claim placed."""
        ctx, pre = self.ctx, self.prefix
        decided = [ctx.scraper.decided_at(target)
                   for _, _, _, target in self.sent]
        nxt = [t_due for t_due, _, _, _ in self.sent[1:]]
        ctx.notes[pre + "bursts_undecided_at_next_due"] = sum(
            1 for d, t in zip(decided, nxt) if d is None or d > t)
        claims = {a: self._growth(
            "volcano_evict_claims_total", f'action="{a}",outcome="committed"')
            for a in ("reclaim", "preempt")}
        pods = sum(len(b[1]) for b in self.window[:len(self.sent)])
        ctx.notes[pre + "window_claims_committed"] = claims
        ctx.notes[pre + "window_evictions"] = {a: self._growth(
            "volcano_evictions_total", f'action="{a}"')
            for a in ("reclaim", "preempt")}
        ctx.notes[pre + "window_repeat_claims"] = {e: self._growth(
            "volcano_evict_repeat_claims_total", f'earlier="{e}"')
            for e in ("in_flight", "released")}
        # every compile JAX reported in the window (compiles_in_window
        # counts the jitstats-tracked solves inside device spans only: the
        # shadow oracle's compile at an audit reads 0 there)
        ctx.notes[pre + "window_jit_compiles"] = self._growth(
            "volcano_jit_compiles_total", "")
        # allocate solves that ran out of rounds while still placing, each
        # followed by a second solve in the same cycle
        ctx.notes[pre + "window_allocate_runs_on"] = self._growth(
            "volcano_allocate_runs_on_total", "")
        ctx.notes[pre + "window_claimed_share"] = (
            sum(claims.values()) / pods if pods else 0.0)

    def _bound(self) -> list:
        return [b for b in self.ctx.server.get("/v1/bindings")
                if b["status"] in reference.BOUND_STATUSES]

    def _released_share(self, binds: list) -> None:
        """The share of the window's pods that sit on capacity an eviction
        released: on each node, those beyond what its free room without
        the run's evictions there would hold (free room now, less what the
        deleted victims gave back, plus what the window's pods took)."""
        ctx, ledger = self.ctx, self.ctx.ledger
        t = ledger.tiers[TIER]
        shape = np.array([t["cpu_milli"][0], t["memory_bytes"][0], 1],
                         np.int64)
        _, used = ledger.check_binds(binds)
        ours = {ledger.key(p) for b in self.window[:len(self.sent)]
                for p in b[1]}
        held = np.zeros(len(ledger.node_names), np.int64)
        for b in binds:
            if b["pod"] in ours:
                held[ledger.node_index[b["node"]]] += 1
        given = np.zeros_like(used)
        for cpu, mem, node in ledger.deleted.values():
            if node >= 0:
                given[node] += (cpu, mem, 1)
        free_without = ledger.alloc - used - given + held[:, None] * shape
        fit = np.clip((free_without // shape).min(axis=1), 0, None)
        placed = int(held.sum())
        ctx.notes[self.prefix + "window_on_released_share"] = (
            float(np.maximum(held - fit, 0).sum()) / placed if placed else 0.0)

    def _edge_round(self) -> None:
        ctx, ledger, p = self.ctx, self.ctx.ledger, self.p
        t0 = time.monotonic()
        binds = self._bound()
        self._released_share(binds)
        queues = [q["name"] for q in ctx.config["queues"]]
        feed_before = len(ledger.feed)
        rounds, sent, skipped = [], 0, 0
        victims0 = ledger.victims_on(binds)
        _, used0 = ledger.check_binds(binds)
        idle0 = (ledger.alloc - used0)[:, 0]
        for j in range(int(p.get("edge_rounds", 12))):
            queue = queues[j % len(queues)]
            _, used = ledger.check_binds(binds)
            idle = (ledger.alloc - used)[:, 0]
            exact, over, _ = reference_tiers.edge_pair(
                idle, ledger.victims_on(binds), queue, queues)
            if exact <= int(idle.max()) + reference.FIT_QUANTUM_MILLI:
                skipped += 1    # some node's idle would hold it: no edge
                continue
            rounds.append((queue, exact, over))
            (o_pgs, o_pods), (e_pgs, e_pods) = (
                ledger.make_tier(TIER, 1, sizes=[1], queues=[queue],
                                 cpus=[cpu], mem=SMALL_MEM)
                for cpu in (over, exact))
            ctx.server.send("POST", "podgroups", o_pgs + e_pgs)
            ctx.server.send("POST", "pods", o_pods + e_pods)
            ledger.add_unfit(o_pods)
            ledger.add(e_pgs, e_pods)
            sent += 1
            if ctx.scraper.wait_count(ctx.posted(1), 30.0) is None:
                break   # it shows as unbound in check_answers
            binds = self._bound()
        # the last over pod has had its chance: two more cycles
        time.sleep(1.0)
        pre = self.prefix
        ctx.notes[pre + "edge_rounds"] = sent
        ctx.notes[pre + "edge_skipped"] = skipped
        ctx.notes[pre + "edge_evictions"] = len(ledger.feed) - feed_before
        ctx.notes[pre + "edge_evictions_for_over_pods"] = sum(
            1 for e in ledger.feed if e["claimant"] in ledger.unfit)
        # an exact pod is given victims once, on one node: more is capacity
        # released twice for one pod
        claimed = {}
        for e in ledger.feed[feed_before:]:
            claimed.setdefault(e["claimant"], set()).add(e["node"])
        ctx.notes[pre + "edge_nodes_per_claimant_max"] = max(
            map(len, claimed.values()), default=0)
        # the reference in the program's place: over the exact victim plane
        # (binds the exact pods, leaves the over pods), over one summed in
        # the control's precision (has to get some wrong), and the
        # sequential reclaim that forgets its own evictions (has to leave a
        # node over)
        control = ctx.config["control"]
        ctx.notes[pre + "edge_reference"] = reference_tiers.edge_control(
            idle0, victims0, rounds, queues, "exact")
        ctx.notes[pre + "control_edge"] = reference_tiers.edge_control(
            idle0, victims0, rounds, queues, control["edge"])
        t = ledger.tiers[TIER]
        claimants = [(int(t["cpu_milli"][0]), int(t["memory_bytes"][0]),
                      queues[g % len(queues)])
                     for g in range(max(12, 4 * int(p["gangs"])))]
        for mode in ("exact", control["placement"]):
            after, _ = reference_tiers.place(
                ledger.alloc, used0, victims0, claimants, mode)
            ctx.notes[f"{pre}control_place_{mode}"] = {
                "nodes_over": int((after > ledger.alloc).any(axis=1).sum())}
        if self.release_ms:
            ctx.notes[pre + "standin_release_p50_ms"] = percentile(
                self.release_ms, 0.5)
        ctx.notes[pre + "edge_s"] = time.monotonic() - t0

"""Open-loop arrivals of high-priority pods on a cluster that low-priority
pods of the same queue fill (upstream scheduler_perf's PreemptionBasic):
``tier_bursts`` over the ledger of ``reference_preempt`` (priority inside one
queue, the eviction feed), which this stream installs in ``ctx.ledger``
before the cluster is loaded.  The kubelet stand-in, the Running report, the
warm-up's ticks and the window's notes are ``tier_bursts``' own; what differs
is here.

At each due time one POST of ``pods`` ``high`` pods with their one-member
PodGroups, whether or not the last burst has been decided.  Nothing is
deleted by the stream: none of the pods fits any node's idle, so every one
is placed by a claim of the preempt action (one queue: reclaim has no
cross-queue victim).  The **stand-in** polls ``GET /v1/evictions?since=N``
every ``standin_period_ms`` and DELETEs each victim AND its one-member
PodGroup (grace 0): a victim's job is gone with it, so the live job and task
counts only fall inside the window and no axis bucket is crossed there.
Victims are bare pods of the source and are never posted again: every pod
binds at most once and the decisions counter reaches exactly the pods
posted.  The pool is finite as in the source (``arrivals.pool`` of the
configuration: a node serves one ``high`` pod): warm-up, window and edge
round together stay under ``arrivals.posted_at_most``, or the run ends
before the window.

``warm()``: the Running report and the ledger's picture of every node
(``note_bound``); no stretch of the job axis as ``tier_bursts`` makes one (a
claim deletes four jobs for the one it adds, so the axes' highest point is
the load plus the first sized burst, inside the load's own 1,024-row
bucket); one burst of each size in ``warm_sizes``
(the pending rungs two or four bursts sharing a cycle would meet); ticks of
small ``low`` pods that fit idle until the guard's shadow oracle has
audited (``warm_audits``); then plain bursts,
``min_warm_bursts`` at least and until three in a row were decided within
``settled_ms``, ``max_warm_bursts`` at most.

``finish()``: ``churn_bursts``' samples and ``tier_bursts``' window notes,
with the victims a claim and the feed's actions; then **the edge round**
(``edge_rounds`` rounds through the same served path).  Twenty thousand
equal victims make every node offer the same 3,600 m and every sum a
multiple of 900, so each round first posts a *filler*: a ``low`` pod of an
irregular size (``FILLERS``), already bound and Running on a node that
still holds its four loaded pods (a static pod's way in; it is no decision
and the counter does not wait for it).  That node then offers more than any
other.  The *exact* pod asks all of it (3,600 m + the filler): the victims
alone have to cover a claimant (preempt.go:262-277), so it binds there
after all five are evicted, and a victim plane that reads one milli-core
short leaves it pending.  The *over* pod asks ``OVER_MILLI`` more than any
node has with every victim gone; it may never bind and may cost nobody a
pod.  Then the controls, into the notes: the same rounds against a victim
plane summed in ``control.edge`` (has to get some wrong: the fillers are
sizes whose running sum bfloat16 reads more than the fit quantum short), and
``reference_preempt.place`` over the end state with ``control.placement``
(has to leave a node over) and ``control.priority`` (has to evict a pod its
claimant does not outrank).

params: rate (bursts/s), pods (per burst), jitter, settled_ms, warm_sizes,
min_warm_bursts (3), max_warm_bursts (8), warm_audits (0),
standin_period_ms (10), edge_rounds (12), prefix ("").
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np

import reference_preempt
from observe import percentile
from streams import churn_bursts, tier_bursts

#: a filler's CPU, by round: 16 k + 11..14, which a bfloat16 running sum of
#: filler + 4 x 900 reads 11-14 m short (reference.plane; the fit quantum
#: forgives 10); all under the 400 m a loaded node has idle
FILLERS = tuple(16 * (7 + j) + 11 + j % 4 for j in range(12))
FILLER_MEM = 100 << 20
#: a tick of the warm-up: one low pod that fits any node's idle
TICK_CPU, TICK_MEM = 100, 100 << 20


class Stream(tier_bursts.Stream):
    def __init__(self, ctx, params: dict, seed: int, seconds: float):
        ledger = ctx.ledger = reference_preempt.Ledger(ctx.config, seed)
        self.cursor = int(ctx.server.get("/v1/evictions?since=0")["next"])
        ctx.server.send("POST", "priorityclasses",
                        ledger.priority_class_dicts())
        pop, arrivals = ctx.config["population"], ctx.config["arrivals"]
        self.low, self.high = pop["class"], arrivals["class"]
        self.release_ms: list = []
        self._stop = threading.Event()
        self._standin = threading.Thread(
            target=self._serve_evictions, name="kubelet-standin", daemon=True)
        self._standin_error = None
        churn_bursts.Stream.__init__(
            self, ctx, dict(params, gaps="near_even"), seed, seconds)
        most = (sum(params.get("warm_sizes", ()))
                + int(params.get("max_warm_bursts", 8)) + self.n) * int(
                    params["pods"]) + int(params.get("edge_rounds", 12))
        if most > int(arrivals["posted_at_most"]):
            raise ctx.failure(
                f"the traffic may post {most} {self.high} pods, the "
                f"configuration allows {arrivals['posted_at_most']} of the "
                f"{arrivals['pool']} the cluster holds")

    # -- the bursts ----------------------------------------------------------

    def _plan(self, times: int = 1):
        pgs, pods = self.ctx.ledger.make_tier(
            self.high, times * int(self.p["pods"]))
        return (pgs, pods, json.dumps(pgs).encode(), json.dumps(pods).encode())

    # -- the kubelet stand-in ------------------------------------------------

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
                    ctx.server.send("DELETE", "podgroups",
                                    ctx.ledger.groups_of(doomed))
                    ctx.ledger.note_released(doomed)
                    self.release_ms.append((time.monotonic() - t0) * 1e3)
                self._stop.wait(max(0.0, period - (time.monotonic() - t0)))
        except Exception as e:  # noqa: BLE001 — carried to finish()
            self._standin_error = f"{type(e).__name__}: {e}"

    # -- set-up ----------------------------------------------------------------

    def _small(self, n: int):
        return self.ctx.ledger.make_tier(
            self.low, n, cpus=[TICK_CPU] * n, mem=TICK_MEM)

    def _tick_until_audited(self) -> int:
        """One small pod a cycle until the shadow oracle has run again."""
        ctx, ledger, sent = self.ctx, self.ctx.ledger, []
        before = self._audits_run()
        while self._audits_run() == before:
            if len(sent) >= 256:
                raise ctx.failure("the guard never audited in the warm-up")
            pgs, pods = self._small(1)
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
        ctx, p, pre = self.ctx, self.p, self.prefix
        t0 = time.monotonic()
        self._report_running()
        ctx.ledger.note_bound(self._bound())
        self._standin.start()
        ctx.notes[pre + "running_report_s"] = time.monotonic() - t0
        for k in p.get("warm_sizes", ()):
            _, target = self._send(self._plan(int(k)))
            if ctx.scraper.wait_count(target, 180.0) is None:
                raise ctx.failure("a warm-up burst was never decided")
        if int(p.get("warm_audits", 0)):
            ctx.notes[pre + "warm_ticks"] = self._tick_until_audited()
        last = []  # ms the last three bursts took
        for i in range(int(p.get("max_warm_bursts", 8)) + 1):
            if (i >= int(p.get("min_warm_bursts", 3)) and len(last) == 3
                    and max(last) <= float(p["settled_ms"])):
                ctx.notes[pre + "warm_bursts"] = i
                ctx.notes[pre + "warm_last_ms"] = last
                return
            if i == int(p.get("max_warm_bursts", 8)):
                break
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

    def _window_notes(self) -> None:
        super()._window_notes()
        ctx, pre, ledger = self.ctx, self.prefix, self.ctx.ledger
        evictions = ctx.notes[pre + "window_evictions"]
        claims = ctx.notes[pre + "window_claims_committed"]
        ctx.notes[pre + "window_victims_per_claim"] = {
            a: evictions[a] / claims[a] for a in claims if claims[a]}
        ctx.notes[pre + "window_statements"] = {o: self._growth(
            "volcano_evict_statements_total",
            f'action="preempt",outcome="{o}"')
            for o in ("opened", "committed", "discarded")}
        with ledger.lock:
            actions: dict = {}
            for e in ledger.feed:
                actions[e["action"]] = actions.get(e["action"], 0) + 1
            ctx.notes[pre + "feed_actions"] = actions
            ctx.notes[pre + "victims_per_claim"] = ledger.victims_per_claim()

    def _edge_round(self) -> None:
        ctx, ledger, p, pre = self.ctx, self.ctx.ledger, self.p, self.prefix
        t0 = time.monotonic()
        binds = self._bound()
        queue = ctx.config["queues"][0]["name"]
        prio = ledger.prio[self.high]
        high = ledger.tiers[self.high]
        mem = int(high["memory_bytes"][0])
        feed_before = len(ledger.feed)
        victims0 = ledger.victims_on(binds)
        _, used0 = ledger.check_binds(binds)
        idle0 = (ledger.alloc - used0)[:, 0]
        # the nodes that still hold their load whole offer most, and alike
        cap0 = reference_preempt.evictable_cpu(
            len(idle0), victims0, queue, prio)
        whole = [int(n) for n in np.flatnonzero(cap0 == cap0.max())]
        idle, victims = idle0.copy(), {n: list(r) for n, r in victims0.items()}
        rounds, skipped = [], 0
        for j in range(int(p.get("edge_rounds", 12))):
            cpu = FILLERS[j % len(FILLERS)]
            if j >= len(whole) or idle[whole[j]] < cpu:
                skipped += 1    # no whole node left, or no idle for a filler
                continue
            at = whole[j]
            f_pgs, f_pods = ledger.make_tier(self.low, 1, cpus=[cpu],
                                             mem=FILLER_MEM)
            f_pods[0].update(node_name=ledger.node_names[at], phase="Running")
            key = ledger.key(f_pods[0])
            filler = (ledger.prio[self.low], -int(f_pods[0]["creation_index"]),
                      key, cpu, FILLER_MEM, queue)
            trial = {**victims, at: sorted(victims.get(at, []) + [filler])}
            trial_idle = idle.copy()
            trial_idle[at] -= cpu
            pair = reference_preempt.edge_pair(trial_idle, trial, queue, prio)
            if pair is None or pair[2] != at:
                skipped += 1    # the filler's node does not stand alone
                continue
            exact, over, _ = pair
            ctx.server.send("POST", "podgroups", f_pgs)
            ctx.server.send("POST", "pods", f_pods)
            ledger.add_bound(f_pgs, f_pods)
            (o_pgs, o_pods), (e_pgs, e_pods) = (
                ledger.make_tier(self.high, 1, cpus=[c], mem=mem)
                for c in (over, exact))
            ctx.server.send("POST", "podgroups", o_pgs + e_pgs)
            ctx.server.send("POST", "pods", o_pods + e_pods)
            ledger.add_unfit(o_pods)
            ledger.add(e_pgs, e_pods)
            rounds.append((at, filler, exact, over))
            taken = [r for r in trial[at]
                     if reference_preempt.may_take(r, queue, prio)]
            victims[at] = [r for r in trial[at] if r not in taken]
            idle[at] = trial_idle[at] + sum(r[3] for r in taken) - exact
            if ctx.scraper.wait_count(ctx.posted(1), 30.0) is None:
                break   # it shows as unbound in check_answers
        # the last over pod has had its chance: two more cycles
        time.sleep(1.0)
        ctx.notes[pre + "edge_rounds"] = len(rounds)
        ctx.notes[pre + "edge_skipped"] = skipped
        ctx.notes[pre + "edge_evictions"] = len(ledger.feed) - feed_before
        ctx.notes[pre + "edge_evictions_for_over_pods"] = sum(
            1 for e in ledger.feed if e["claimant"] in ledger.unfit)
        claimed: dict = {}
        for e in ledger.feed[feed_before:]:
            claimed.setdefault(e["claimant"], set()).add(e["node"])
        ctx.notes[pre + "edge_nodes_per_claimant_max"] = max(
            map(len, claimed.values()), default=0)
        # the reference in the program's place: over the exact victim plane
        # (binds the exact pods, leaves the over pods), over one summed in
        # the control's precision (has to get some wrong), and as the
        # sequential preempt over the end state, which forgets its own
        # evictions (a node over) or the claimant's priority (a victim it
        # does not outrank)
        control = ctx.config["control"]
        for name, precision in (("edge_reference", "exact"),
                                ("control_edge", control["edge"])):
            ctx.notes[pre + name] = reference_preempt.edge_control(
                idle0, victims0, rounds, queue, prio, precision)
        world = ledger.victims_on(binds, running_only=False)
        claimants = [(int(high["cpu_milli"][0]), mem, queue, prio)
                     ] * max(12, int(p["pods"]))
        for mode in ("exact", control["placement"], control["priority"]):
            after, evicted = reference_preempt.place(
                ledger.alloc, used0, world, claimants, mode)
            ctx.notes[f"{pre}control_place_{mode}"] = {
                "nodes_over": int((after > ledger.alloc).any(axis=1).sum()),
                "outranked": reference_preempt.outranked(evicted),
                "evictions": len(evicted)}
        if self.release_ms:
            ctx.notes[pre + "standin_release_p50_ms"] = percentile(
                self.release_ms, 0.5)
        ctx.notes[pre + "edge_s"] = time.monotonic() - t0

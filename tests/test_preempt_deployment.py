"""Priority preemption inside one queue
(``benchmark/configs/schedperf-preempt-20k-5k.json``: upstream
scheduler_perf's PreemptionBasic) at a size the suite holds: 96 nodes of 4
CPU, 384 ``low`` pods of 900m, four a node (``rehearsal-preempt-384-96``),
and bursts of 8 ``high`` pods of 3,000m that fit nowhere until the preempt
action evicts a node's low pods.

The served path (``test_tiers.TiersServed``: cache + ``Scheduler`` + the
shipped five actions, the standalone eviction feed in the evictor's place)
runs over the deployment's plain reference (``benchmark/reference_preempt.py``:
numpy int64, imports nothing of the program), whose stand-in deletes each
victim and its one-member PodGroup.  Every claim of the feed is preempt's;
the reference's counts are all zero; ``place(mode="exact")`` evicts and binds
as many; the three controls come out wrong; a Statement that does not reach
Pipelined leaves nothing behind; phase 2 walks every job and opens no
Statement on a one-member one."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

from kube_batch_tpu.actions.preempt import PreemptAction
from kube_batch_tpu.actions.reclaim import ReplayTally
from kube_batch_tpu.api import serialize
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.framework.session import close_session, open_session
from kube_batch_tpu.metrics import metrics as m
from kube_batch_tpu.obs.trace import tracer_of
from tests.test_envelope import REPO, ZERO, Served, _walk
from tests.test_tiers import TiersServed, grew

BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
try:
    import reference_preempt
finally:
    sys.path.remove(BENCH)

with open(os.path.join(BENCH, "configs",
                       "rehearsal-preempt-384-96.json")) as f:
    CONFIG = json.load(f)
BURST = 8


class PreemptServed(TiersServed):
    """``TiersServed`` over ``reference_preempt.Ledger``; the stand-in's
    poll deletes a victim's one-member PodGroup with it."""

    def __init__(self, seed: int, config: dict = CONFIG):
        self.cursor = 0
        ledger = reference_preempt.Ledger(config, seed)
        self._classes = ledger.priority_class_dicts()
        Served.__init__(self, seed, ledger=ledger)

    def report_running(self) -> None:
        super().report_running()
        self.ledger.note_bound(self.binds())

    def release(self) -> int:
        page = self.cache.eviction_log.since(self.cursor)
        self.cursor = page["next"]
        doomed = self.ledger.note_evictions(page["evictions"])
        for pod in doomed:
            self.cache.delete_pod(serialize.pod_from_dict(pod))
        for pg in self.ledger.groups_of(doomed):
            self.cache.delete_pod_group(
                serialize.pod_group_from_dict(pg).key())
        self.ledger.note_released(doomed)
        return len(doomed)

    def high(self, n: int, size: int = 1, min_member: int = 1) -> list:
        pgs, pods = self.ledger.make_tier("high", n, sizes=[size] * n)
        for pg in pgs:
            pg["min_member"] = min_member
        self.post(pgs, pods)
        return pods

    def loaded(self) -> "PreemptServed":
        assert self.cycles() == ZERO                    # the cold drain
        self.report_running()
        self.cycle()
        return self


def spans_of(served) -> list:
    return [sp for rec in tracer_of(served.cache).recorder.records()
            for root in rec.spans for sp in _walk(root)]


@pytest.fixture(scope="module")
def drive():
    """One drive for the module: two bursts of eight through the loop,
    the first held unreleased for three cycles."""
    old = os.environ.get("KB_SHARD")
    os.environ["KB_SHARD"] = "0"     # one device, as the cell's one chip
    served = PreemptServed(seed=4800000001)
    seen = {"claims_before": dict(m.EVICT_CLAIMS._values),
            "statements_before": dict(m.EVICT_STATEMENTS._values),
            "repeat_before": dict(m.EVICT_REPEAT_CLAIMS._values),
            "commits_before": dict(m.EVICT_COMMITS._values)}
    try:
        served.loaded()
        assert served.release() == 0                    # nothing to evict yet
        pods = served.high(BURST)
        feed = []       # the feed's length after each cycle, none released
        for _ in range(3):
            served.cycle()
            feed.append(served.cache.eviction_log.since(0)["next"])
        seen["feed_unreleased"] = feed
        seen["in_flight_repeats"] = served.ledger.repeat_in_flight
        for _ in range(6):
            served.release()
            served.cycle()
            if not served.counts()["unbound"]:
                break
        pods += served.high(BURST)                      # the second burst
        for _ in range(8):
            served.cycle()
            served.release()
            if not served.counts()["unbound"]:
                break
        seen.update(served=served, posted=[served.ledger.key(p) for p in pods],
                    binds=served.binds(), counts=served.counts(),
                    feed=served.cache.eviction_log.since(0)["evictions"],
                    spans=spans_of(served))
        yield seen
    finally:
        served.close()
        if old is None:
            os.environ.pop("KB_SHARD", None)
        else:
            os.environ["KB_SHARD"] = old


def test_every_feed_entry_is_preempt_s_and_names_its_claimant(drive):
    ledger = drive["served"].ledger
    assert drive["feed"]
    assert {e["action"] for e in drive["feed"]} == {"preempt"}
    assert {e["claimant"] for e in drive["feed"]} == set(drive["posted"])
    assert all(e["node"] in ledger.node_index for e in drive["feed"])
    assert grew(m.EVICT_CLAIMS._values, drive["claims_before"],
                ("reclaim", "committed")) == 0
    assert grew(m.EVICT_CLAIMS._values, drive["claims_before"],
                ("preempt", "committed")) == 2 * BURST


def test_a_claim_takes_its_node_s_low_pods_lowest_task_order_first(drive):
    """kube-batch v0.4.2 counts the victims alone toward the request
    (preempt.go:262-277, :219-237): three 900m pods are 2,700m, so a 3,000m
    claimant takes all four of its node (upstream Kubernetes, which counts
    the node's 400m idle, would take three).  Equal priority: newest first."""
    ledger = drive["served"].ledger
    claims = ledger.claims()
    assert len(claims) == 2 * BURST
    assert ledger.victims_per_claim() == 4.0
    for claimant, node, victims in claims:
        assert ledger.tier[claimant] == "high"
        assert {ledger.tier[v] for v in victims} == {"low"}
        assert {ledger.deleted[v][2] for v in victims} == {
            ledger.node_index[node]}
        orders = [ledger.order[v] for v in victims]
        assert orders == sorted(orders, reverse=True)
    assert len({node for _, node, _ in claims}) == 2 * BURST


def test_every_high_pod_is_bound_and_every_count_is_zero(drive):
    assert drive["counts"] == ZERO
    node_of = {b["pod"]: b["node"] for b in drive["binds"]}
    assert all(key in node_of for key in drive["posted"])
    ledger = drive["served"].ledger
    assert ledger.outranked == ledger.protected_evicted == 0
    assert ledger.uncovered_claims() == ledger.bad_evictions == 0
    # a node serves one high pod
    assert len({node_of[k] for k in drive["posted"]}) == 2 * BURST


def test_the_repeat_claim_gate_holds_with_a_victim_in_flight(drive):
    first, second, third = drive["feed_unreleased"]
    assert first == 4 * BURST and first == second == third
    assert drive["in_flight_repeats"] == 0
    for earlier in ("in_flight", "released"):
        assert grew(m.EVICT_REPEAT_CLAIMS._values, drive["repeat_before"],
                    (earlier,)) == 0
    victims = [e["pod"] for e in drive["feed"]]
    assert len(victims) == len(set(victims))


def test_the_spans_and_the_counter_say_which_statements_there_were(drive):
    replays = [sp for sp in drive["spans"] if sp.name == "preempt_replay"]
    assert replays
    assert sum(sp.attrs["statements"] for sp in replays) == 2 * BURST
    assert sum(sp.attrs["committed"] for sp in replays) == 2 * BURST
    assert all(sp.attrs["discarded"] == 0 for sp in replays)
    # the shared replay span lies inside preempt's own
    for sp in replays:
        assert [c.name for c in sp.children] == ["evict_replay"]
        assert sp.children[0].attrs["claims"] == sp.attrs["claims"]
    for outcome, want in (("opened", 2 * BURST), ("committed", 2 * BURST),
                          ("discarded", 0)):
        assert grew(m.EVICT_STATEMENTS._values, drive["statements_before"],
                    ("preempt", outcome)) == want, outcome
    # every committed Statement told the cache itself, in one call each
    for sp in replays:
        assert sp.children[0].attrs["commits"] == sp.attrs["committed"]
    assert grew(m.EVICT_COMMITS._values, drive["commits_before"],
                ("preempt", "bulk")) == 2 * BURST
    assert grew(m.EVICT_COMMITS._values, drive["commits_before"],
                ("preempt", "single")) == 0
    # phase 2 walked every job of every cycle and opened nothing
    phase2 = [sp for sp in drive["spans"] if sp.name == "preempt_phase2"]
    assert phase2 and all(
        sp.attrs["jobs"] >= 384 - 8 * BURST and sp.attrs["statements"] == 0
        for sp in phase2)


def world(drive):
    ledger = drive["served"].ledger
    binds = drive["binds"]
    _, used = ledger.check_binds(binds)
    return ledger, used, ledger.victims_on(binds, running_only=False)


def test_the_reference_evicts_and_binds_as_many_as_the_program(drive):
    """``place(mode="exact")`` over the loaded cluster, given the drive's
    sixteen claimants: as many evictions and as many nodes taken."""
    ledger = reference_preempt.Ledger(CONFIG, 4800000001)
    pgs, pods = ledger.make_population()
    ledger.add(pgs, pods)
    binds = [{"pod": ledger.key(p), "node": f"n{i // 4}"}
             for i, p in enumerate(pods)]
    ledger.running.update(ledger.pod_dicts)
    _, used = ledger.check_binds(binds)
    claimant = (3000, 500 << 20, "default", 10)
    after, evicted = reference_preempt.place(
        ledger.alloc, used, ledger.victims_on(binds), [claimant] * 2 * BURST)
    assert len(evicted) == len(drive["feed"]) == 8 * BURST
    assert reference_preempt.outranked(evicted) == 0
    assert not (after > ledger.alloc).any()
    taken = {node for *_, node in evicted}
    assert len(taken) == 2 * BURST
    assert (after[sorted(taken), 0] == 3000).all()


@pytest.mark.parametrize("mode, wrong", [
    ("exact", None), ("ignore_priority", "outranked"),
    ("stale", "nodes_over")])
def test_the_placement_controls_come_out_wrong(drive, mode, wrong):
    ledger, used, victims = world(drive)
    claimants = [(3000, 500 << 20, "default", 10)] * 2 * BURST
    after, evicted = reference_preempt.place(
        ledger.alloc, used, victims, claimants, mode)
    got = {"outranked": reference_preempt.outranked(evicted),
           "nodes_over": int((after > ledger.alloc).any(axis=1).sum())}
    for name, value in got.items():
        assert (value > 0) == (name == wrong), (mode, got)


def test_the_lower_precision_edge_plane_comes_out_wrong():
    """The edge round's pairs over a loaded cluster: exact over the exact
    plane, every exact pod left pending over the bfloat16 one (the fillers
    are sizes its running sum reads more than the fit quantum short)."""
    sys.path.insert(0, BENCH)
    try:
        from streams.preempt_bursts import FILLERS
    finally:
        sys.path.remove(BENCH)
    victims = {n: [(0, -(4 * n + i), f"bench/t{4 * n + i}", 900, 500 << 20,
                    "default") for i in range(4)][::-1] for n in range(96)}
    idle = np.full(96, 400, np.int64)
    rounds = [(j, (0, -(1000 + j), f"bench/f{j}", cpu, 100 << 20, "default"),
               3600 + cpu, 4012) for j, cpu in enumerate(FILLERS)]
    assert all(0 < cpu <= 400 for cpu in FILLERS)
    exact = reference_preempt.edge_control(
        idle, victims, rounds, "default", 10, "exact")
    assert exact == {"unbound": 0, "overfit_binds": 0}
    control = reference_preempt.edge_control(
        idle, victims, rounds, "default", 10, CONFIG["control"]["edge"])
    assert control["unbound"] == len(FILLERS)
    # each round's filler makes its node the one that offers most
    trial = dict(victims)
    trial[0] = sorted(trial[0] + [rounds[0][1]])
    trial_idle = idle.copy()
    trial_idle[0] -= FILLERS[0]
    assert reference_preempt.edge_pair(trial_idle, trial, "default", 10) == (
        3600 + FILLERS[0], 4012, 0)
    assert reference_preempt.edge_pair(idle, victims, "default", 10) is None


# -- the ledger counts each planted fault -----------------------------------


def planted(drive, entries=(), release=True):
    """The drive's end state with entries planted in its feed, counted by
    a copy of its ledger."""
    src = drive["served"].ledger
    ledger = reference_preempt.Ledger(CONFIG, 0)
    for name in ("pods", "gangs", "loose", "unfit", "tier", "queue", "order",
                 "pod_dicts", "running", "deleted"):
        setattr(ledger, name, type(getattr(src, name))(getattr(src, name)))
    ledger.gangs = {g: (list(ms), pg, k) for g, (ms, pg, k)
                    in src.gangs.items()}
    ledger.feed = list(src.feed)
    ledger.used0 = src.used0.copy()
    ledger.in_flight = {c: set(w) for c, w in src.in_flight.items()}
    ledger.repeat_in_flight, ledger._claim = src.repeat_in_flight, src._claim
    for e in entries:
        doomed = ledger.note_evictions([e])
        if release:
            ledger.note_released(doomed)
    gone = set(ledger.deleted) - set(src.deleted)   # the stand-in's DELETEs
    return ledger.check_binds(
        [b for b in drive["binds"] if b["pod"] not in gone])[0]


def test_a_planted_eviction_of_a_high_pod_is_a_split_gang(drive):
    victim, claimant = drive["posted"][0], drive["posted"][1]
    got = planted(drive, [{"seq": 999, "pod": victim, "node": "n0",
                           "action": "preempt", "claimant": claimant}])
    assert got["gangs_split"] >= 1      # a high pod named in the feed
    # one its claimant did not outrank, and one never reported Running
    assert got["unknown_pods"] == 2
    assert planted(drive) == ZERO


def test_a_planted_victim_of_another_queue_is_counted(drive):
    ledger = drive["served"].ledger
    low = next(k for k in ledger.pods if ledger.tier[k] == "low")
    ledger.queue[low] = "elsewhere"
    try:
        got = planted(drive, [{
            "seq": 999, "pod": low, "node": "n95", "action": "preempt",
            "claimant": drive["posted"][0]}])
    finally:
        ledger.queue[low] = "default"
    assert got["unknown_pods"] == 1 and got["gangs_split"] == 0


def test_a_planted_claim_that_its_node_cannot_hold_is_a_node_over(drive):
    """One low pod evicted for a high claimant on a node that still holds
    three more: 400m idle + 900m is no room for 3,000m."""
    ledger = drive["served"].ledger
    node_of = {b["pod"]: b["node"] for b in drive["binds"]}
    low = next(k for k in ledger.pods if ledger.tier[k] == "low")
    got = planted(drive, [{
        "seq": 999, "pod": low, "node": node_of[low], "action": "preempt",
        "claimant": drive["posted"][0]}])
    assert got["nodes_over"] == 1 and got["unbound"] == 0


def test_a_planted_second_claim_with_a_victim_in_flight_is_counted(drive):
    ledger = drive["served"].ledger
    node_of = {b["pod"]: b["node"] for b in drive["binds"]}
    lows = [k for k in ledger.pods if ledger.tier[k] == "low"]
    a, b = lows[0], next(k for k in lows if node_of[k] != node_of[lows[0]])
    claimant = drive["posted"][0]
    got = planted(drive, [
        {"seq": 998, "pod": a, "node": node_of[a], "action": "preempt",
         "claimant": claimant},
        {"seq": 999, "pod": b, "node": node_of[b], "action": "preempt",
         "claimant": claimant}], release=False)
    assert got["double_binds"] == 1


# -- the Statement, and phase 2 ---------------------------------------------


def test_a_statement_that_does_not_reach_pipelined_leaves_nothing_behind():
    """A high gang of two with ``minMember`` 2 and a claim for one member
    only: the job does not reach Pipelined, the Statement is discarded, no
    eviction reaches the feed, the victims run on and the session's ledgers
    are as they were."""
    served = PreemptServed(seed=4800000002).loaded()
    try:
        (first, _second) = served.high(1, size=2, min_member=2)
        before = dict(m.EVICT_STATEMENTS._values)
        ssn = open_session(served.cache, served.sched.conf.tiers)
        try:
            job = next(j for j in ssn.jobs.values()
                       if j.pod_group and j.pod_group.min_member == 2)
            task = next(t for t in job.tasks.values()
                        if t.name == first["name"])
            node = ssn.nodes["n0"]
            victims = [t for t in node.tasks.values()
                       if t.status == TaskStatus.RUNNING]
            assert len(victims) == 4
            idle = node.idle.vec.copy()
            claims = [((job.uid, task.key()), "n0",
                       [(v.job, v.key()) for v in victims])]
            with ReplayTally.replaying(ssn, "preempt", claims) as tally:
                got = PreemptAction()._replay(ssn, tally, claims)
            assert got == (1, 0)                    # opened, not committed
            assert (tally.committed, tally.host_rejected) == (0, 1)
            assert task.status == TaskStatus.PENDING
            assert all(v.status == TaskStatus.RUNNING for v in victims)
            assert (node.idle.vec == idle).all()
        finally:
            close_session(ssn)
        assert served.cache.eviction_log.since(0)["next"] == 0
        assert grew(m.EVICT_STATEMENTS._values, before,
                    ("preempt", "opened")) == 0     # counted by _phase1
        assert served.cache.columns.check_consistency(served.cache) == []
    finally:
        served.close()


def test_a_committed_statement_tells_the_cache_in_one_call():
    """A Statement's evictions change the session at the verb and reach the
    feed, all of them, when it commits; a discarded one's never do."""
    served = PreemptServed(seed=4800000006).loaded()
    try:
        (pod,) = served.high(1)
        ssn = open_session(served.cache, served.sched.conf.tiers)
        try:
            job = next(j for j in ssn.jobs.values()
                       if j.name == pod["annotations"][
                           "scheduling.k8s.io/group-name"])
            (task,) = job.tasks.values()
            for node_name, commit in (("n0", False), ("n1", True)):
                victims = [t for t in ssn.nodes[node_name].tasks.values()
                           if t.status == TaskStatus.RUNNING]
                assert len(victims) == 4
                commits = dict(m.EVICT_COMMITS._values)
                stmt = ssn.statement()
                stmt.evict_batch(victims, "preempt", claimant=task)
                assert all(v.status == TaskStatus.RELEASING for v in victims)
                assert served.cache.eviction_log.since(0)["next"] == 0
                if commit:
                    stmt.commit()
                    page = served.cache.eviction_log.since(0)
                    assert page["next"] == 4
                    assert {e["claimant"] for e in page["evictions"]} == {
                        served.ledger.key(pod)}
                    assert grew(m.EVICT_COMMITS._values, commits,
                                ("preempt", "bulk")) == 1
                else:
                    stmt.discard()
                    assert all(v.status == TaskStatus.RUNNING
                               for v in victims)
        finally:
            close_session(ssn)
    finally:
        served.close()


def test_phase_two_opens_no_statement_on_one_member_jobs(monkeypatch):
    served = PreemptServed(seed=4800000003).loaded()
    try:
        served.high(BURST)
        ssn = open_session(served.cache, served.sched.conf.tiers)
        try:
            assert len(ssn.jobs) == 384 + BURST
            opened = []
            monkeypatch.setattr(
                ssn, "statement", lambda: opened.append(1) or None)
            PreemptAction()._phase2(ssn)
            assert opened == []
            span = tracer_of(served.cache).current.spans[-1]
            assert span.name == "preempt_phase2"
            assert span.attrs == {"jobs": 384 + BURST, "statements": 0}
        finally:
            close_session(ssn)
    finally:
        served.close()


def test_phase_two_leaves_a_gang_of_equal_priority_alone(monkeypatch):
    """A job with a Running and a Pending member is the only kind phase 2
    can act for; with the members' priorities equal the task-order gate
    says there is nothing to rebalance, and no Statement is opened."""
    served = PreemptServed(seed=4800000004).loaded()
    try:
        ledger = served.ledger
        pgs, pods = ledger.make_tier("low", 1, sizes=[2], cpus=[100],
                                     mem=100 << 20)
        served.post(pgs, pods)
        served.cycle()                      # both fit idle and bind
        served.cache.update_pod(serialize.pod_from_dict(
            dict(pods[0], phase="Running")))
        ledger.running.add(ledger.key(pods[0]))
        more = ledger._tier_pod("low", 100, 100 << 20, pgs[0]["name"])
        served.post([], [more])             # a third member, Pending
        ssn = open_session(served.cache, served.sched.conf.tiers)
        try:
            both = [
                j for j in ssn.jobs.values()
                if j.task_status_index.get(TaskStatus.PENDING)
                and j.task_status_index.get(TaskStatus.RUNNING)]
            assert [j.name for j in both] == [pgs[0]["name"]]
            before = dict(m.EVICT_STATEMENTS._values)
            assert PreemptAction()._rebalance(ssn) == 0
            monkeypatch.setattr(ssn, "conf_flag", lambda name: True)
            # the reference's ungated phase 2 does open one for it
            assert PreemptAction()._rebalance(ssn) >= 1
            assert grew(m.EVICT_STATEMENTS._values, before,
                        ("preempt", "opened")) == 0     # counted by _phase2
        finally:
            close_session(ssn)
    finally:
        served.close()


def test_equal_priority_is_no_protection_and_the_ledger_counts_it():
    """What the cell does not show (PERF.md section 7): kube-batch v0.4.2's
    priority plugin registers no preemptable function, so once every node
    holds a Running ``high`` pod and no ``low`` one, one more ``high`` pod
    evicts a ``high`` pod of another job.  Upstream Kubernetes never would;
    the deployment's ledger counts each such eviction twice over."""
    served = PreemptServed(seed=4800000005).loaded()
    try:
        served.high(96)                     # a node serves one: all taken
        for _ in range(12):
            served.cycle()
            served.release()
            if not served.counts()["unbound"]:
                break
        assert served.counts() == ZERO
        taken = served.cache.eviction_log.since(0)["next"]
        assert taken == 384                 # every low pod went
        served.report_running()             # a kubelet that reports them all
        (late,) = served.high(1)
        for _ in range(3):
            served.cycle()
            served.release()
        feed = served.cache.eviction_log.since(0)["evictions"][taken:]
        assert [served.ledger.tier[e["pod"]] for e in feed] == ["high"]
        assert feed[0]["claimant"] == served.ledger.key(late)
        got = served.counts()
        assert got["gangs_split"] == 1 and got["unknown_pods"] == 1
        assert served.ledger.outranked == 1
    finally:
        served.close()

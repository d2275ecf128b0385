"""Priority tiers under overcommit (``benchmark/configs/borg-tiers-50k-5k.json``:
production gangs arriving on a full cluster evict best-effort batch) at a
size the suite holds: 192 nodes 96% full (``rehearsal-tiers-1920-192``).

The served path (``test_envelope.Served``: cache + ``Scheduler`` + the
shipped five actions, with the standalone eviction feed of
``cache/evictions.py`` in the evictor's place) is filled, its pods reported
Running, and given production gangs; a kubelet stand-in of three lines
reads the feed and deletes what it names.  The deployment's plain reference
(``benchmark/reference_tiers.py``: numpy int64, imports nothing of the
program) checks the binds to all-zero counts, and counts each planted
fault.  Cycles that run before a victim is released order no eviction
twice; the feed is monotone, bounded and lock-free; the sharded evict
program agrees with the single-device one under both claimant gates."""

from __future__ import annotations

import json
import os
import sys
import threading

import numpy as np
import pytest

from kube_batch_tpu.api import serialize
from kube_batch_tpu.cache.evictions import EvictionLog
from kube_batch_tpu.metrics import metrics as m
from kube_batch_tpu.obs.trace import tracer_of
from tests.test_envelope import REPO, ZERO, Served, _walk

BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
try:
    import reference
    import reference_tiers
finally:
    sys.path.remove(BENCH)

with open(os.path.join(BENCH, "configs",
                       "rehearsal-tiers-1920-192.json")) as f:
    CONFIG = json.load(f)


class TiersServed(Served):
    """``Served`` over ``reference_tiers.Ledger``, the eviction feed in the
    evictor's place, and the kubelet's two acts."""

    def __init__(self, seed: int, config: dict = CONFIG):
        self.cursor = 0
        ledger = reference_tiers.Ledger(config, seed)
        self._classes = ledger.priority_class_dicts()
        super().__init__(seed, ledger=ledger)

    def post(self, pgs, pods) -> None:
        cache = self.cache
        if cache.eviction_log is None:     # the first post: the population
            cache.eviction_log = cache.evictor = EvictionLog()
            for pc in self._classes:
                cache.add_priority_class(
                    serialize.priority_class_from_dict(pc))
        super().post(pgs, pods)

    def report_running(self) -> None:
        for pod in list(self.ledger.pod_dicts.values()):
            self.cache.update_pod(
                serialize.pod_from_dict(dict(pod, phase="Running")))
        self.ledger.running.update(self.ledger.pod_dicts)

    def release(self) -> int:
        """The stand-in's poll: DELETE what the feed names; how many."""
        page = self.cache.eviction_log.since(self.cursor)
        self.cursor = page["next"]
        doomed = self.ledger.note_evictions(page["evictions"])
        for pod in doomed:
            self.cache.delete_pod(serialize.pod_from_dict(pod))
        self.ledger.note_released(doomed)
        return len(doomed)

    def cycle(self) -> None:
        self.sched.run_once_pipelined()
        self.sched.drain_pipeline()

    def production(self, gangs: int) -> list:
        pgs, pods = self.ledger.make_tier("production", gangs)
        self.post(pgs, pods)
        return pods


@pytest.fixture(scope="module")
def drive():
    """One drive for every test of the module: what it saw, by name."""
    old = os.environ.get("KB_SHARD")
    os.environ["KB_SHARD"] = "0"     # one device, as the cell's one chip
    served = TiersServed(seed=3500000001)
    seen = {"claims_before": dict(m.EVICT_CLAIMS._values),
            "evictions_before": dict(m.EVICTIONS._values),
            "repeat_before": dict(m.EVICT_REPEAT_CLAIMS._values),
            "commits_before": dict(m.EVICT_COMMITS._values),
            "released_before": m.EVICTION_RELEASE_LATENCY._count[()]}
    try:
        assert served.cycles() == ZERO                  # the cold drain
        served.report_running()
        served.cycle()
        assert served.release() == 0                    # nothing to evict yet
        pods = served.production(6)                     # 24 pods of 8 cores
        feed = []       # the feed's length after each cycle, none released
        for _ in range(3):
            served.cycle()
            feed.append(served.cache.eviction_log.since(0)["next"])
        seen["feed_unreleased"] = feed
        seen["bound_unreleased"] = served.counts()["unbound"]
        seen["released"] = served.release()
        served.cycle()                                  # the cycle after
        seen["unbound_after_release"] = served.counts()["unbound"]
        for _ in range(6):                              # stragglers, if any
            if not served.counts()["unbound"]:
                break
            served.release()
            served.cycle()
        served.release()
        seen["posted"] = [served.ledger.key(p) for p in pods]
        seen.update(served=served, binds=served.binds(),
                    counts=served.counts(),
                    feed=served.cache.eviction_log.since(0)["evictions"],
                    spans=[sp for rec in tracer_of(served.cache).recorder
                           .records() for root in rec.spans
                           for sp in _walk(root)])
        yield seen
    finally:
        served.close()
        if old is None:
            os.environ.pop("KB_SHARD", None)
        else:
            os.environ["KB_SHARD"] = old


def grew(now: dict, before: dict, key) -> float:
    return now.get(key, 0.0) - before.get(key, 0.0)


def test_every_count_of_the_reference_is_zero(drive):
    assert drive["counts"] == ZERO
    node_of = {b["pod"]: b["node"] for b in drive["binds"]}
    assert all(key in node_of for key in drive["posted"])


def test_cycles_before_a_release_order_no_eviction_twice(drive):
    first, second, third = drive["feed_unreleased"]
    assert first > 0 and first == second == third
    for earlier in ("in_flight", "released"):
        assert grew(m.EVICT_REPEAT_CLAIMS._values, drive["repeat_before"],
                    (earlier,)) == 0
    victims = [e["pod"] for e in drive["feed"]]
    assert len(victims) == len(set(victims))


def test_the_claimants_bind_in_the_cycle_after_the_delete(drive):
    assert drive["bound_unreleased"] > 0        # pipelined is not bound
    assert drive["released"] == drive["feed_unreleased"][0]
    assert drive["unbound_after_release"] == 0


def test_no_protected_pod_is_in_the_feed_and_every_entry_names_its_claim(
        drive):
    ledger = drive["served"].ledger
    assert drive["feed"]
    for e in drive["feed"]:
        assert ledger.tier[e["pod"]] in ("beb", "free")
        assert e["action"] in ("reclaim", "preempt")
        assert e["claimant"] in drive["posted"]
        assert e["node"] in ledger.node_index
    assert [e["seq"] for e in drive["feed"]] == list(range(len(drive["feed"])))


def test_the_counters_and_the_spans_say_what_happened(drive):
    committed = sum(grew(m.EVICT_CLAIMS._values, drive["claims_before"],
                         (a, "committed")) for a in ("reclaim", "preempt"))
    evicted = sum(grew(m.EVICTIONS._values, drive["evictions_before"], (a,))
                  for a in ("reclaim", "preempt"))
    assert evicted == len(drive["feed"]) and 0 < committed <= 24
    assert grew(m.EVICT_CLAIMS._values, drive["claims_before"],
                ("reclaim", "gated_releasing")) > 0
    assert (m.EVICTION_RELEASE_LATENCY._count[()]
            - drive["released_before"]) == len(drive["feed"])
    replays = [sp for sp in drive["spans"] if sp.name == "evict_replay"]
    assert replays and sum(sp.attrs["victims"] for sp in replays) == evicted
    assert sum(sp.attrs["claims"] - sp.attrs["rejected"]
               for sp in replays) == committed
    waits = [sp for sp in drive["spans"] if sp.name == "device_wait"
             and sp.attrs.get("action") in ("reclaim", "preempt")]
    assert waits and all({"rounds", "claims", "victims"} <= set(sp.attrs)
                         for sp in waits)
    assert sum(sp.attrs["claims"] for sp in waits) >= committed


def test_an_action_with_claims_tells_the_cache_once_and_never_singly(drive):
    """``volcano_evict_commits_total``: every replay that committed a claim
    handed its evictions over in ONE ``bulk_evict`` (its span says so too),
    and nothing on the served path evicts a task at a time."""
    replays = [sp for sp in drive["spans"] if sp.name == "evict_replay"]
    with_victims = [sp for sp in replays if sp.attrs["victims"]]
    assert with_victims
    assert all(sp.attrs["commits"] == 1 for sp in with_victims)
    assert all(sp.attrs["commits"] == 0 for sp in replays
               if not sp.attrs["victims"])
    bulk = sum(grew(m.EVICT_COMMITS._values, drive["commits_before"],
                    (a, "bulk")) for a in ("reclaim", "preempt"))
    assert bulk == len(with_victims)
    for action in ("reclaim", "preempt"):
        assert grew(m.EVICT_COMMITS._values, drive["commits_before"],
                    (action, "single")) == 0


# -- the reference counts each planted fault --------------------------------


def planted(drive, entry=None, bind=None, unfit=None, release=False):
    """The drive's end state with one fault under it, counted by a copy of
    its ledger."""
    src = drive["served"].ledger
    ledger = reference_tiers.Ledger(CONFIG, 0)
    for name in ("pods", "gangs", "loose", "unfit", "tier", "queue", "order",
                 "pod_dicts", "running", "deleted"):
        setattr(ledger, name, type(getattr(src, name))(getattr(src, name)))
    ledger.gangs = {g: (list(ms), pg, k) for g, (ms, pg, k)
                    in src.gangs.items()}
    ledger.feed = list(src.feed)
    ledger.in_flight = {c: set(w) for c, w in src.in_flight.items()}
    ledger.repeat_in_flight, ledger._claim = src.repeat_in_flight, src._claim
    binds = list(drive["binds"])
    for e in [entry] if isinstance(entry, dict) else entry or ():
        doomed = ledger.note_evictions([e])
        if release:
            ledger.note_released(doomed)
    if unfit is not None:
        ledger.unfit[unfit] = (40000, 1 << 30)
        binds.append({"pod": unfit, "node": "n0"})
    if bind is not None:
        binds = [dict(b, node=bind[1]) if b["pod"] == bind[0] else b
                 for b in binds]
    return ledger.check_binds(binds)[0]


def test_a_planted_eviction_of_a_protected_pod_is_a_split_gang(drive):
    key = drive["posted"][0]
    got = planted(drive, entry={"seq": 999, "pod": key, "node": "n0",
                                "action": "reclaim", "claimant": "x/y"})
    # counted as ordered, and once more as the gang it leaves short
    assert got["gangs_split"] >= 1
    assert planted(drive) == ZERO


def test_a_planted_eviction_of_a_deleted_pod_is_an_unknown_pod(drive):
    gone = drive["feed"][0]
    got = planted(drive, entry=dict(gone, seq=999))
    assert got["unknown_pods"] == 1 and got["gangs_split"] == 0


def test_a_planted_uncovered_bind_is_a_node_over(drive):
    node_of = {b["pod"]: b["node"] for b in drive["binds"]}
    key = drive["posted"][0]
    other = next(n for n in {node_of[k] for k in drive["posted"]}
                 if n != node_of[key])
    got = planted(drive, bind=(key, other))
    assert got["nodes_over"] >= 1


def test_a_planted_bound_over_pod_is_an_overfit_bind(drive):
    got = planted(drive, unfit="bench/over-0")
    assert got["overfit_binds"] == 1


def test_an_eviction_for_an_over_pod_is_an_overfit_bind(drive):
    ledger = drive["served"].ledger
    victim = next(k for k, t in ledger.tier.items()
                  if t == "free" and k in ledger.pods)
    src_unfit = dict(ledger.unfit)
    try:
        ledger.unfit["bench/over-1"] = (40000, 1 << 30)
        got = planted(drive, entry={
            "seq": 999, "pod": victim, "node": "n1", "action": "reclaim",
            "claimant": "bench/over-1"})
    finally:
        ledger.unfit.clear()
        ledger.unfit.update(src_unfit)
    assert got["overfit_binds"] == 1


def _two_claims_for_one_claimant(drive):
    """Two live low-tier pods, named by two claims of one production
    claimant on two nodes (each covers nothing much: 8 cores are asked)."""
    ledger = drive["served"].ledger
    node_of = {b["pod"]: b["node"] for b in drive["binds"]}
    live = [k for k, t in ledger.tier.items()
            if t in ("beb", "free") and k in ledger.pods
            and k in ledger.running]
    a = live[0]
    b = next(k for k in live if node_of[k] != node_of[a])
    return [{"seq": 900 + i, "pod": k, "node": node_of[k],
             "action": "reclaim", "claimant": drive["posted"][0]}
            for i, k in enumerate((a, b))]


def test_a_planted_second_claim_with_the_first_in_flight_is_a_double_bind(
        drive):
    """The cascade the releasing gate rules out: a claimant given victims
    again before the stand-in's DELETE of the earlier ones was
    acknowledged.  Once it was, a second claim is another matter (the room
    went to another pod) and is not counted here."""
    entries = _two_claims_for_one_claimant(drive)
    assert planted(drive, entry=entries)["double_binds"] == 1
    assert planted(drive, entry=entries, release=True)["double_binds"] == 0
    # one claim in two polls is one claim
    same = [dict(entries[1], node=entries[0]["node"])]
    assert planted(drive, entry=entries[:1] + same)["double_binds"] == 0


def test_a_planted_claim_whose_victims_do_not_cover_it_is_a_node_over(drive):
    """No eviction without a covered placement: one low-tier pod of at most
    4 cores for a claimant of 8 is counted whether or not the claimant ever
    binds there; the drive's own claims all cover."""
    entries = _two_claims_for_one_claimant(drive)
    assert drive["served"].ledger.uncovered_claims() == 0
    got = planted(drive, entry=entries[:1], release=True)
    assert got["nodes_over"] == 1 and got["double_binds"] == 0


# -- the controls ------------------------------------------------------------


def test_the_sequential_reclaim_is_sound_and_stale_leaves_a_node_over(drive):
    served = drive["served"]
    ledger = served.ledger
    victims = ledger.victims_on(drive["binds"])
    _, used = ledger.check_binds(drive["binds"])
    t = ledger.tiers["production"]
    claimants = [(t["cpu_milli"][0], t["memory_bytes"][0], f"q{g % 3}")
                 for g in range(12)]
    exact, gone = reference_tiers.place(ledger.alloc, used, victims,
                                        claimants, "exact")
    assert gone and not (exact > ledger.alloc).any()
    stale, _ = reference_tiers.place(ledger.alloc, used, victims, claimants,
                                     "stale")
    assert (stale > ledger.alloc).any()


def test_the_edge_pair_and_its_bfloat16_control(drive):
    ledger = drive["served"].ledger
    victims = ledger.victims_on(drive["binds"])
    _, used = ledger.check_binds(drive["binds"])
    idle = (ledger.alloc - used)[:, 0]
    rounds, live = [], {n: list(rows) for n, rows in victims.items()}
    queues = ["q0", "q1", "q2"]
    for queue in (*queues, "q0"):
        exact, over, node = reference_tiers.edge_pair(
            idle, live, queue, queues)
        cap = reference_tiers.evictable_cpu(len(idle), live, queue)
        assert exact == cap.max() == cap[node] > idle.max()
        # more than any one claimant's victims and idle make free anywhere
        assert over == reference_tiers.OVER_MILLI + max(
            (idle + reference_tiers.evictable_cpu(len(idle), live, q)).max()
            for q in queues) > (idle + cap).max()
        rounds.append((queue, exact, over))
        # the exact pod takes every cross-queue victim of its node
        live[node] = [r for r in live[node] if r[4] == queue]
    assert reference_tiers.edge_control(idle, victims, rounds, queues,
                                        "exact") == {
        "unbound": 0, "overfit_binds": 0}
    wrong = reference_tiers.edge_control(idle, victims, rounds, queues,
                                         "bfloat16")
    assert wrong["unbound"] + wrong["overfit_binds"] > 0


# -- the feed ----------------------------------------------------------------


def test_the_feed_is_monotone_and_resumes_from_a_cursor():
    log = EvictionLog(capacity=8)
    assert log.since(0) == {"next": 0, "first": 0, "evictions": []}
    for i in range(5):
        assert log.record(f"ns/p{i}", f"n{i}", "reclaim", "ns/c") == i
    page = log.since(0)
    assert [e["seq"] for e in page["evictions"]] == [0, 1, 2, 3, 4]
    assert page["next"] == 5 and page["first"] == 0
    assert log.since(3)["evictions"][0] == {
        "seq": 3, "pod": "ns/p3", "node": "n3", "action": "reclaim",
        "claimant": "ns/c"}
    assert log.since(5) == {"next": 5, "first": 0, "evictions": []}
    assert log.since(99)["next"] == 5       # a cursor from the future


def test_the_feed_is_bounded_and_says_what_a_slow_client_missed():
    log = EvictionLog(capacity=8)
    for i in range(20):
        log.record(f"ns/p{i}", "n0", "preempt", "ns/c")
    page = log.since(0)
    assert page["first"] == 12 and page["next"] == 20
    assert [e["seq"] for e in page["evictions"]] == list(range(12, 20))
    assert len(log._ring) == 8
    paged = log.since(12, page=3)
    assert [e["seq"] for e in paged["evictions"]] == [12, 13, 14]
    assert paged["next"] == 15


def test_a_reader_polling_under_a_batch_sees_whole_gap_free_pages():
    """``record_many`` appends 4,096 entries in one call while a reader
    polls ``since()`` without a lock: every page it gets is whole entries
    with consecutive numbers from its cursor on, and the pages together
    are the batch, in order."""
    log = EvictionLog()
    for i in range(3):                      # the feed is not new
        log.record(f"ns/old{i}", "n0", "preempt", "")
    batch = [(f"ns/p{i}", f"n{i % 7}", "reclaim", f"ns/c{i // 7}")
             for i in range(4096)]
    end = 3 + len(batch)
    pages, started = [], threading.Event()

    def read():
        cursor = 3
        while cursor < end:
            page = log.since(cursor, page=97)
            started.set()
            pages.append((cursor, page))
            cursor = page["next"]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)             # the reader gets in between
    try:
        reader = threading.Thread(target=read, daemon=True)
        reader.start()
        assert started.wait(5)
        assert log.record_many(batch) == 3
        reader.join(30)
        assert not reader.is_alive()
    finally:
        sys.setswitchinterval(old)
    seen = []
    for cursor, page in pages:
        got = page["evictions"]
        assert [e["seq"] for e in got] == list(range(cursor, page["next"]))
        assert page["first"] == 0 and page["next"] <= end
        seen.extend(got)
    assert [(e["pod"], e["node"], e["action"], e["claimant"])
            for e in seen] == batch
    assert [e["seq"] for e in seen] == list(range(3, end))


def test_the_endpoint_answers_while_the_cache_lock_is_held():
    """``GET /v1/evictions`` takes no cache lock: it answers while another
    thread holds it, which ``/v1/bindings`` would wait out."""
    import http.client

    from kube_batch_tpu.cache.cache import SchedulerCache
    from kube_batch_tpu.cmd.server import AdminServer

    cache = SchedulerCache()
    admin = AdminServer(cache)
    admin.start()
    try:
        port = admin.httpd.server_address[1]

        def get(path):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
            try:
                conn.request("GET", path)
                r = conn.getresponse()
                return r.status, json.loads(r.read())
            finally:
                conn.close()

        status, body = get("/v1/evictions")
        assert status == 503 and "no eviction feed" in body["error"]
        cache.eviction_log = EvictionLog()
        cache.eviction_log.record("ns/p", "n0", "reclaim", "ns/c")
        held, release = threading.Event(), threading.Event()

        def hold():
            with cache._lock:
                held.set()
                release.wait(10)

        t = threading.Thread(target=hold, daemon=True)
        t.start()
        assert held.wait(5)
        try:
            status, body = get("/v1/evictions?since=0")
            assert status == 200 and body["next"] == 1
            assert body["evictions"][0]["pod"] == "ns/p"
            assert get("/v1/evictions?since=x")[0] == 400
        finally:
            release.set()
            t.join(5)
    finally:
        admin.stop()


# -- the gates, on the device -------------------------------------------------


def _gated_snapshot():
    """A session snapshot with claimants, victims, idle room for two of the
    claimants and releasing capacity for others."""
    from kube_batch_tpu.framework.session import open_session

    served = TiersServed(seed=3500000002)
    assert served.cycles() == ZERO
    served.report_running()
    served.production(3)
    served.cycle()                  # orders evictions; nobody releases
    # idle room for two claimants: two production pods of the load go
    members = next(ms for ms, pg, _ in served.ledger.gangs.values()
                   if pg["priority_class"] == "production")
    for pod in members[:2]:
        served.cache.delete_pod(serialize.pod_from_dict(pod))
    served.production(2)
    ssn = open_session(served.cache, served.sched.conf.tiers)
    snap, meta = ssn.columns.device_snapshot(ssn)
    return served, ssn, snap, meta


def test_the_sharded_evict_program_agrees_under_both_gates():
    import jax

    from kube_batch_tpu.framework.session import close_session
    from kube_batch_tpu.ops.eviction import EvictConfig, evict_solve
    from kube_batch_tpu.parallel.mesh import make_mesh, program

    served, ssn, snap, meta = _gated_snapshot()
    try:
        assert (np.asarray(snap.node_releasing) > 0).any()
        mesh = make_mesh(8)
        claims = {}
        for mode in ("reclaim", "preempt"):
            for gates in ({}, {"idle_gate": mode == "reclaim",
                               "releasing_gate": True}):
                ec = EvictConfig(mode=mode, **gates)
                ev = jax.device_get(evict_solve(snap, ec))
                with mesh:
                    ev_sm = jax.device_get(
                        program("evict", mesh, "shard_map", ec)(snap))
                    ev_pj = jax.device_get(
                        program("evict", mesh, "pjit", ec)(snap))
                for name in ev._fields:
                    assert np.array_equal(
                        getattr(ev, name), getattr(ev_sm, name)), (mode, name)
                    assert np.array_equal(
                        getattr(ev, name), getattr(ev_pj, name)), (mode, name)
                claims[mode, bool(gates)] = int(
                    (ev.claim_node[: meta.n_tasks] >= 0).sum())
                # the releasing gate's share of the gated, from the device
                assert (int(ev.gated_releasing) > 0) == bool(gates), mode
        # claimants whose evictions are in flight, new ones, two idle
        # slots: the gates keep some of them out of both modes
        assert claims["reclaim", False] > claims["reclaim", True]
        assert claims["preempt", False] >= claims["preempt", True]
    finally:
        close_session(ssn)
        served.close()


def test_idle_room_for_some_gates_as_many_claimants_and_no_more():
    """The idle gate counts: with idle room for k production pods and more
    than k pending, all but k still claim victims.  An existential gate
    would keep every one of them from the victims they need, for ever
    where they are gangs that allocate cannot complete on k slots."""
    import jax

    from kube_batch_tpu.framework.session import close_session, open_session
    from kube_batch_tpu.ops.eviction import EvictConfig, evict_solve

    served = TiersServed(seed=3500000003)
    try:
        assert served.cycles() == ZERO
        served.report_running()
        ledger = served.ledger
        t = ledger.tiers["production"]
        shape = np.array([t["cpu_milli"][0], t["memory_bytes"][0], 1],
                         np.int64)
        _, used = ledger.check_binds(served.binds())
        room = int(reference.slots(ledger.alloc, used, shape).sum())
        gangs = room // 4 + 2               # more claimants than idle room
        served.production(gangs)
        ssn = open_session(served.cache, served.sched.conf.tiers)
        try:
            snap, meta = ssn.columns.device_snapshot(ssn)
            n = meta.n_tasks
            pending = int(np.asarray(snap.task_pending)[:n].sum())
            assert pending == 4 * gangs and 0 < room < pending
            plain = jax.device_get(evict_solve(snap, EvictConfig()))
            gated = jax.device_get(evict_solve(
                snap, EvictConfig(idle_gate=True)))
            assert int((plain.claim_node[:n] >= 0).sum()) == pending
            assert int((gated.claim_node[:n] >= 0).sum()) == pending - room
        finally:
            close_session(ssn)
        # and through the loop every gang binds
        for _ in range(6):
            served.cycle()
            served.release()
        assert served.counts() == ZERO
    finally:
        served.close()


# -- preempt, where reclaim has nothing to take --------------------------------


def test_with_one_queue_preempt_commits_and_the_gangs_bind():
    """kube-batch's own Preemption e2e (job.go:189-221) at the rehearsal
    size: with ONE queue there is no cross-queue victim, so reclaim claims
    nothing, allocate finds no room, and preempt's solve, its Statement
    and its replay evict same-queue best-effort pods for the production
    gangs; every claim of the feed is preempt's and names its claimant,
    and the gangs bind once the stand-in has deleted the victims."""
    config = dict(CONFIG, queues=CONFIG["queues"][:1])
    served = TiersServed(seed=3500000004, config=config)
    try:
        assert served.cycles() == ZERO
        served.report_running()
        served.cycle()
        claims = dict(m.EVICT_CLAIMS._values)
        commits = dict(m.EVICT_COMMITS._values)
        pods = served.production(3)
        served.cycle()
        feed = served.cache.eviction_log.since(0)["evictions"]
        posted = {served.ledger.key(p) for p in pods}
        assert feed and {e["action"] for e in feed} == {"preempt"}
        assert {e["claimant"] for e in feed} <= posted
        committed = grew(m.EVICT_CLAIMS._values, claims,
                         ("preempt", "committed"))
        assert committed == len({e["claimant"] for e in feed}) > 0
        assert grew(m.EVICT_CLAIMS._values, claims,
                    ("reclaim", "committed")) == 0
        replay = [sp for rec in tracer_of(served.cache).recorder.records()
                  for root in rec.spans for sp in _walk(root)
                  if sp.name == "evict_replay" and sp.attrs["victims"]]
        assert sum(sp.attrs["victims"] for sp in replay) == len(feed)
        # a committed Statement's evictions reach the cache in one call
        bulk = grew(m.EVICT_COMMITS._values, commits, ("preempt", "bulk"))
        assert bulk == sum(sp.attrs["commits"] for sp in replay) > 0
        assert bulk <= committed
        assert grew(m.EVICT_COMMITS._values, commits,
                    ("preempt", "single")) == 0
        served.cycle()                  # nobody released: nothing more
        assert served.cache.eviction_log.since(0)["next"] == len(feed)
        for _ in range(6):
            served.release()
            served.cycle()
            if not served.counts()["unbound"]:
                break
        served.release()
        assert served.counts() == ZERO
        assert served.ledger.uncovered_claims() == 0
    finally:
        served.close()

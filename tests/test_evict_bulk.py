"""The batched evict verbs against a plain per-victim reference.

``Session.evict_batch`` / ``Statement.evict_batch`` move a claim's victims
as a group and ``SchedulerCache.bulk_evict`` hears of an action's evictions
in one call.  The reference below is the per-victim path they replaced,
written out here (``ref_*``: nothing of it is imported from the program's
evict verbs): every case builds the same small cluster twice, runs the same
claims through each, and compares the end state field by field: job and
node ledgers, indices and column counts, the plugins' attrs, the pipelined
tasks, the feed with its sequence numbers, the cache's events, the
counters, and the cache's in-flight bookkeeping."""

from __future__ import annotations

import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.actions.preempt import PreemptAction
from kube_batch_tpu.actions.reclaim import ReclaimAction, ReplayTally
from kube_batch_tpu.api.pod import PodGroup, Queue
from kube_batch_tpu.api.types import PodPhase, TaskStatus
from kube_batch_tpu.cache.evictions import EvictionLog
from kube_batch_tpu.framework.conf import parse_scheduler_conf
from kube_batch_tpu.framework.session import (
    Event,
    EventHandler,
    close_session,
    open_session,
)
from kube_batch_tpu.k8s.transport import CircuitOpenError
from kube_batch_tpu.metrics import metrics as m
from kube_batch_tpu.utils import telemetry
from tests import fixtures
from tests.fixtures import GiB, build_cache, build_node, build_pod

#: ONE tier, so that proportion's verdict (which reads the queue ledgers the
#: earlier claims of the action moved) binds beside gang's and conformance's
CONF = """
actions: "reclaim, allocate"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
"""
NS = "c1"


class ScriptedEvictor:
    """Records every call; the calls numbered in ``fail`` (from 1) raise."""

    def __init__(self, fail=(), exc=RuntimeError):
        self.calls, self.fail, self.exc = [], set(fail), exc

    def evict(self, pod) -> None:
        self.calls.append(f"{pod.namespace}/{pod.name}")
        if len(self.calls) in self.fail:
            raise self.exc("scripted")


def cluster(evictor):
    """Two nodes of 8 cores, full of Running 1-core pods of qa (11) and qb
    (5), and four pending claimants of qc asking 10 cores.  Weights 1:1:8
    leave qa and qb 3 cores deserved each: qa may lose 8 pods, qb only 2."""
    fixtures._counter[0] = 0  # the same creation order in every build
    one = {"cpu": 1000, "memory": GiB}

    def running(group, node, n):
        return [build_pod(NS, f"{group}-{i}", node, PodPhase.RUNNING, one,
                          group_name=group) for i in range(n)]

    def claimant(i, cores):
        return build_pod(NS, f"c{i}", None, PodPhase.PENDING,
                         {"cpu": 1000 * cores, "memory": cores * GiB},
                         group_name="jc", priority=100)

    groups = {"a1": "qa", "a2": "qa", "a3": "qa", "a4": "qa",
              "b1": "qb", "b3": "qb", "jc": "qc"}
    cache = build_cache(
        queues=[Queue(name="qa", weight=1), Queue(name="qb", weight=1),
                Queue(name="qc", weight=8)],
        pod_groups=[PodGroup(name=g, namespace=NS, min_member=1, queue=q)
                    for g, q in groups.items()],
        nodes=[build_node(n, cpu=8000, mem=8 * GiB) for n in ("n1", "n2")],
        pods=(running("a1", "n1", 3) + running("a2", "n1", 2)
              + running("b1", "n1", 3) + running("b3", "n2", 2)
              + running("a3", "n2", 4) + running("a4", "n2", 2)
              + [claimant(0, 3), claimant(1, 2), claimant(2, 2),
                 claimant(3, 3)]),
    )
    cache.evictor = evictor
    cache.eviction_log = EvictionLog()
    return cache


def ref(name):
    """(job uid, task key) of a pod of :func:`cluster`, as the decode of a
    solve names it."""
    group = "jc" if name.startswith("c") else name.split("-")[0]
    return (f"{NS}/{group}", f"{NS}/{name}")


def claim(claimant, node, victims):
    return (ref(claimant), node, [ref(v) for v in victims])


def find(ssn, name):
    uid, key = ref(name)
    return ssn.jobs[uid].tasks[key]


# -- the per-victim reference (the verbs as they were before the batch) -------


def ref_cache_evict(cache, task, reason, claimant=None):
    with cache._lock:
        if not cache._session_active:
            own = cache._own_task(task)
            if own is not None:
                job = cache.jobs[task.job]
                job.update_task_status(own, TaskStatus.RELEASING)
                node = cache.nodes.get(own.node_name) if own.node_name else None
                if node is not None:
                    node.update_task(own)
        pod = cache.pods.get(task.key())
    try:
        if pod is not None:
            cache.evictor.evict(pod)
            cache.events.append(("Evict", task.key(), reason))
            m.EVICTIONS.inc(reason)
            whose = claimant.key() if claimant is not None else ""
            with cache._lock:
                if task.key() not in cache._evict_ordered_at:
                    cache._evict_ordered_at[task.key()] = (
                        telemetry.perf_counter(), whose)
                    cache._evict_in_flight[whose] = (
                        cache._evict_in_flight.get(whose, 0) + 1)
            cache.eviction_log.record(
                task.key(), task.node_name or "", reason, whose)
    except CircuitOpenError:
        cache.resync_task(task, reason="breaker-open")
    except Exception:  # noqa: BLE001
        cache.resync_task(task)


def ref_session_half(ssn, task):
    job = ssn.jobs.get(task.job)
    if job is not None:
        job.update_task_status(task, TaskStatus.RELEASING)
    node = ssn.nodes.get(task.node_name)
    if node is not None:
        node.update_task(task)
    for eh in ssn.event_handlers:
        if eh.deallocate_func is not None:
            eh.deallocate_func(Event(task))


def ref_session_evict(ssn, task, reason, claimant=None):
    ref_cache_evict(ssn.cache, task, reason, claimant)
    ref_session_half(ssn, task)


def ref_note_claim(cache, claimant_key, n_victims):
    with cache._lock:
        seen = claimant_key in cache._evict_claimants
        cache._evict_claimants.add(claimant_key)
        earlier = cache._evict_in_flight.get(claimant_key, 0) - n_victims
    if seen:
        m.EVICT_REPEAT_CLAIMS.inc("in_flight" if earlier > 0 else "released")


def ref_reclaim(ssn, claims):
    """ReclaimAction's replay, a victim at a time."""
    outcome = {"committed": 0, "host_rejected": 0, "uncovered": 0}
    for claimant_ref, node_name, victim_refs in claims:
        task = ssn.jobs[claimant_ref[0]].tasks[claimant_ref[1]]
        preemptees = [ssn.jobs[u].tasks[k].clone() for u, k in victim_refs]
        victims = ssn.reclaimable(task, preemptees)
        if not victims:
            outcome["host_rejected"] += 1
            continue
        total = ssn.spec.empty()
        for v in victims:
            total.add_(v.resreq)
        if not task.init_resreq.less_equal(total):
            outcome["uncovered"] += 1
            continue
        reclaimed = ssn.spec.empty()
        evicted = 0
        for victim in victims:
            ref_session_evict(ssn, victim, "reclaim", claimant=task)
            evicted += 1
            reclaimed.add_(victim.resreq)
            if task.init_resreq.less_equal(reclaimed):
                break
        ssn.pipeline(task, node_name)
        ref_note_claim(ssn.cache, task.key(), evicted)
        outcome["committed"] += 1
    for name, n in outcome.items():
        if n:
            m.EVICT_CLAIMS.add(n, "reclaim", name)


def batched_reclaim(ssn, claims):
    """The program's replay of the same claims (no solve: the claims are
    given), as ``ReclaimAction.execute`` runs it."""
    action = ReclaimAction()
    with ReplayTally.replaying(ssn, "reclaim", claims) as tally:
        for c in claims:
            action._replay(ssn, tally, *c)


# -- the end state, as plain values -------------------------------------------


def vec(resource):
    return np.asarray(resource.vec).tolist()


COUNTERS = ("EVICTIONS", "EVICT_CLAIMS", "EVICT_REPEAT_CLAIMS")


def counters():
    return {name: dict(getattr(m, name)._values) for name in COUNTERS}


def model_state(jobs, nodes):
    out = {}
    for uid, job in jobs.items():
        out["job", uid] = {
            "allocated": vec(job.allocated),
            "pending_request": vec(job.pending_request),
            "total_request": vec(job.total_request),
            "index": {s.name: sorted(b) for s, b
                      in job.task_status_index.items() if b},
            "status": {k: t.status.name for k, t in job.tasks.items()},
            "counts": (job._cols.j_counts[job._row].tolist()
                       if job._cols is not None else None),
        }
    for name, node in nodes.items():
        out["node", name] = {
            "idle": vec(node.idle), "used": vec(node.used),
            "releasing": vec(node.releasing),
            "acct": {k: s.name for k, s in node._acct.items()},
            "status": {k: t.status.name for k, t in node.tasks.items()},
            # a task that moved is ONE object, in its job and on its node
            "one_object": all(
                t is jobs[t.job].tasks[k] for k, t in node.tasks.items()
                if t.status == TaskStatus.RELEASING and t.job in jobs),
        }
    return out


def end_state(ssn, cache, before):
    out = model_state(ssn.jobs, ssn.nodes)
    plugin = {p.name: p for p in ssn.plugins}
    drf, prop = plugin["drf"], plugin["proportion"]
    for uid, job in ssn.jobs.items():
        attr = drf.job_attrs.get(uid)
        if attr is not None:
            out["drf", uid] = vec(attr.allocated)
        elif drf._arr is not None and job._row >= 0:
            out["drf", uid] = drf._arr[job._row].tolist()
    for q, attr in prop.queue_attrs.items():
        out["proportion", q] = vec(attr.allocated)
    out["pipelined"] = [t.key() for t in ssn.pipelined_tasks]
    out["feed"] = cache.eviction_log.since(0)
    out["events"] = list(cache.events)
    out["evictor"] = list(cache.evictor.calls)
    now = counters()
    out["counters"] = {
        (name, key): now[name].get(key, 0.0) - before[name].get(key, 0.0)
        for name in COUNTERS
        for key in set(now[name]) | set(before[name])}
    out["in_flight"] = dict(cache._evict_in_flight)
    out["ordered_for"] = {k: v[1] for k, v in cache._evict_ordered_at.items()}
    out["claimants"] = sorted(cache._evict_claimants)
    out["parked"] = sorted(
        (t.key(), cache.resync._entries[t.key()].reason)
        for t in cache.err_tasks)
    return out


def closed_state(cache):
    out = model_state(cache.jobs, cache.nodes)
    out["consistent"] = cache.columns.check_consistency(cache)
    return out


def run(drives, *, evictor=None, isolated=False, handler=False):
    """One build of the cluster under ``drive(ssn, heard)`` (a list of
    drives is a session each, one cycle after another); the end state with
    the last session open, and the cache's own after its close."""
    cache = cluster(evictor() if evictor else ScriptedEvictor())
    before = counters()
    heard = []
    for drive in drives if isinstance(drives, list) else [drives]:
        ssn = open_session(cache, parse_scheduler_conf(CONF).tiers,
                           isolated=isolated)
        if handler:  # a custom plugin's: no batch form, it must miss nothing
            ssn.add_event_handler(EventHandler(
                allocate_func=lambda e: heard.append(
                    ("allocate", e.task.key())),
                deallocate_func=lambda e: heard.append(
                    ("deallocate", e.task.key(), e.task.status.name))))
        try:
            drive(ssn, heard)
            state = end_state(ssn, cache, before)
            state["heard"] = sorted(heard)
            if isolated:
                state["cache"] = model_state(cache.jobs, cache.nodes)
        finally:
            close_session(ssn)
    return state, closed_state(cache)


def same(reference, batched, **how):
    want, want_closed = run(reference, **how)
    got, got_closed = run(batched, **how)
    for key in want:
        assert got[key] == want[key], key
    assert set(got) == set(want)
    assert got_closed == want_closed
    assert got_closed["consistent"] == []
    return got


# -- the cases ----------------------------------------------------------------

SEVEN = [claim("c0", "n1", ["a1-0", "a1-1", "a1-2"]),
         claim("c1", "n1", ["a2-0", "a2-1"]),
         claim("c2", "n2", ["a3-0", "a3-1"])]

CLAIMS = {
    "victims_of_three_jobs_and_two_queues_on_one_node": [
        claim("c0", "n1", ["a1-0", "a2-0", "b1-0"])],
    "the_covering_prefix_is_shorter_than_the_list": [
        claim("c1", "n1", ["a1-0", "a1-1", "a1-2", "a2-0"])],
    "two_claims_on_one_node_in_one_action": [
        claim("c1", "n1", ["a1-0", "a1-1"]),
        claim("c2", "n1", ["a2-0", "b1-0"])],
    # qb may lose two pods: the first claim takes them, so the second is
    # vetoed whole by proportion (on ledgers the first moved), the third
    # commits, and the fourth keeps one victim of two: uncovered
    "a_claim_rejected_by_reclaimable_between_two_committed_ones": [
        claim("c1", "n1", ["b1-0", "b1-1"]),
        claim("c2", "n2", ["b3-0", "b3-1"]),
        claim("c3", "n2", ["a3-0", "a3-1", "a3-2"]),
        claim("c0", "n1", ["a1-0", "b1-2", "b3-0"])],
    "seven_victims_of_three_claims": SEVEN,
}


@pytest.mark.parametrize("name", sorted(CLAIMS))
def test_a_replay_of_claims_ends_where_the_per_victim_replay_does(name):
    claims = CLAIMS[name]
    got = same(lambda ssn, _: ref_reclaim(ssn, claims),
               lambda ssn, _: batched_reclaim(ssn, claims))
    assert got["feed"]["evictions"]  # every case evicts somebody
    assert [e["seq"] for e in got["feed"]["evictions"]] == list(
        range(got["feed"]["next"]))


def test_the_rejected_claim_was_rejected_on_the_ledgers_the_first_moved():
    claims = CLAIMS["a_claim_rejected_by_reclaimable_between_two_committed_ones"]
    got = same(lambda ssn, _: ref_reclaim(ssn, claims),
               lambda ssn, _: batched_reclaim(ssn, claims))
    grew = {k[1]: v for k, v in got["counters"].items()
            if k[0] == "EVICT_CLAIMS" and v}
    assert grew == {("reclaim", "committed"): 2.0,
                    ("reclaim", "host_rejected"): 1.0,
                    ("reclaim", "uncovered"): 1.0}
    # alone, the second claim's victims are reclaimable: what vetoed them
    # is what the first claim took from their queue
    alone = same(lambda ssn, _: ref_reclaim(ssn, claims[1:2]),
                 lambda ssn, _: batched_reclaim(ssn, claims[1:2]))
    assert [e["pod"] for e in alone["feed"]["evictions"]] == [
        f"{NS}/b3-0", f"{NS}/b3-1"]


@pytest.mark.parametrize("exc, reason", [(RuntimeError, "error"),
                                         (CircuitOpenError, "breaker-open")])
def test_an_evictor_that_fails_the_third_pod_of_seven_parks_that_one(
        exc, reason):
    got = same(lambda ssn, _: ref_reclaim(ssn, SEVEN),
               lambda ssn, _: batched_reclaim(ssn, SEVEN),
               evictor=lambda: ScriptedEvictor(fail={3}, exc=exc))
    third = f"{NS}/a1-2"
    assert got["parked"] == [(third, reason)]
    assert len(got["evictor"]) == 7 and got["evictor"][2] == third
    fed = [e["pod"] for e in got["feed"]["evictions"]]
    assert len(fed) == 6 and third not in fed
    assert got["in_flight"] == {f"{NS}/c0": 2, f"{NS}/c1": 2, f"{NS}/c2": 2}
    assert third not in got["ordered_for"]
    # the session went on: the pod is Releasing there all the same
    assert got["job", f"{NS}/a1"]["status"][third] == "RELEASING"


def test_a_breaker_that_stays_open_parks_every_pod_from_there_on():
    got = same(lambda ssn, _: ref_reclaim(ssn, SEVEN),
               lambda ssn, _: batched_reclaim(ssn, SEVEN),
               evictor=lambda: ScriptedEvictor(fail=range(3, 8),
                                               exc=CircuitOpenError))
    assert len(got["parked"]) == 5 and got["feed"]["next"] == 2
    assert {r for _, r in got["parked"]} == {"breaker-open"}
    assert got["in_flight"] == {f"{NS}/c0": 2}


def test_a_session_of_clones_leaves_the_accounting_to_the_cache():
    claims = CLAIMS["two_claims_on_one_node_in_one_action"]
    got = same(lambda ssn, _: ref_reclaim(ssn, claims),
               lambda ssn, _: batched_reclaim(ssn, claims), isolated=True)
    moved = got["cache"]["node", "n1"]
    assert sorted(k for k, s in moved["acct"].items()
                  if s == "RELEASING") == [
        f"{NS}/a1-0", f"{NS}/a1-1", f"{NS}/a2-0", f"{NS}/b1-0"]
    assert moved["releasing"][0] == 4000.0


def test_a_handler_without_the_batch_form_hears_every_task():
    claims = CLAIMS["victims_of_three_jobs_and_two_queues_on_one_node"]
    got = same(lambda ssn, _: ref_reclaim(ssn, claims),
               lambda ssn, _: batched_reclaim(ssn, claims), handler=True)
    assert got["heard"] == sorted(
        [("allocate", f"{NS}/c0")]
        + [("deallocate", f"{NS}/{v}", "RELEASING")
           for v in ("a1-0", "a2-0", "b1-0")])


def _statement(ssn, commit, batched):
    """preempt's shape: a claim's victims and its pipeline in a Statement."""
    task, victims = find(ssn, "c1"), [find(ssn, v).clone()
                                      for v in ("a1-0", "a2-0")]
    stmt = ssn.statement()
    if batched:
        stmt.evict_batch(victims, "preempt", claimant=task)
        stmt.pipeline(task, "n1")
        stmt.commit() if commit else stmt.discard()
        return
    for v in victims:
        ref_session_half(ssn, v)
    stmt.pipeline(task, "n1")
    if commit:
        for v in victims:
            ref_cache_evict(ssn.cache, v, "preempt", task)
        ref_note_claim(ssn.cache, task.key(), len(victims))
        return
    stmt.discard()  # the pipeline, its only operation; then the victims,
    for v in reversed(victims):  # newest first, each as it was evicted
        ssn.jobs[v.job].update_task_status(v, TaskStatus.RUNNING)
        ssn.nodes[v.node_name].update_task(v)
        for eh in ssn.event_handlers:
            if eh.allocate_func is not None:
                eh.allocate_func(Event(v))


@pytest.mark.parametrize("commit", [True, False],
                         ids=["committed", "discarded"])
def test_a_statement_of_preempt(commit):
    got = same(lambda ssn, _: _statement(ssn, commit, batched=False),
               lambda ssn, _: _statement(ssn, commit, batched=True),
               handler=True)
    assert got["feed"]["next"] == (2 if commit else 0)
    assert sorted(k for k, st in got["node", "n1"]["acct"].items()
                  if st == "RELEASING") == (
        [f"{NS}/a1-0", f"{NS}/a2-0"] if commit else [])
    if commit:
        assert {e["action"] for e in got["feed"]["evictions"]} == {"preempt"}
        return
    untouched, _ = run(lambda ssn, _: None, handler=True)
    for key in untouched:  # a discard leaves every ledger where it was
        if key not in ("heard", "pipelined"):
            assert got[key] == untouched[key], key


@pytest.mark.parametrize("copy", [False, True], ids=["resident", "copy"])
def test_the_batch_of_one(copy):
    def pick(ssn):
        task = find(ssn, "a3-1")
        return task.clone() if copy else task

    before = dict(m.EVICT_COMMITS._values)
    got = same(
        lambda ssn, _: ref_session_evict(ssn, pick(ssn), "preempt"),
        lambda ssn, _: ssn.evict(pick(ssn), "preempt"))
    assert got["feed"]["evictions"] == [
        {"seq": 0, "pod": f"{NS}/a3-1", "node": "n2", "action": "preempt",
         "claimant": ""}]
    assert got["in_flight"] == {"": 1} and got["claimants"] == []
    assert (m.EVICT_COMMITS._values[("preempt", "single")]
            - before.get(("preempt", "single"), 0)) == 1
    assert m.EVICT_COMMITS._values.get(("preempt", "bulk"), 0) == before.get(
        ("preempt", "bulk"), 0)


def test_a_task_evicted_twice_moves_no_ledger_the_second_time():
    """Not the common shape (a victim is Running): the second eviction
    meets a Releasing task, which no group sum may count again."""
    def twice(evict):
        def drive(ssn, _):
            evict(ssn, find(ssn, "b1-0").clone(), "preempt")
            evict(ssn, find(ssn, "b1-0").clone(), "preempt")
        return drive

    got = same(twice(ref_session_evict),
               twice(lambda ssn, t, why: ssn.evict_batch([t], why)))
    assert got["node", "n1"]["releasing"][0] == 1000.0
    assert got["feed"]["next"] == 2 and got["in_flight"] == {"": 1}


def test_a_claimant_claimed_again_is_a_repeat_by_what_is_still_in_flight():
    """``volcano_evict_repeat_claims_total`` reads the in-flight count of
    EARLIER cycles: the flush that adds this action's victims must not
    count them against their own claim."""
    def two_cycles(replay):
        return [lambda ssn, _: replay(ssn, [claim("c1", "n1",
                                                  ["a1-0", "a1-1"])]),
                lambda ssn, _: replay(ssn, [claim("c1", "n1",
                                                  ["a2-0", "a2-1"])])]

    got = same(two_cycles(ref_reclaim), two_cycles(batched_reclaim))
    assert got["counters"]["EVICT_REPEAT_CLAIMS", ("in_flight",)] == 1.0
    assert got["counters"]["EVICT_REPEAT_CLAIMS", ("released",)] == 0.0
    assert got["in_flight"] == {f"{NS}/c1": 4}


def test_the_replay_counts_its_commits_on_the_span_and_the_counter():
    from kube_batch_tpu.obs.trace import tracer_of

    cache = cluster(ScriptedEvictor())
    before = dict(m.EVICT_COMMITS._values)
    ssn = open_session(cache, parse_scheduler_conf(CONF).tiers)
    try:
        batched_reclaim(ssn, SEVEN)
        # qb may lose two: the third victim is vetoed, two do not cover c3
        batched_reclaim(ssn, [claim("c3", "n1", ["b1-0", "b1-1", "b1-2"])])
    finally:
        close_session(ssn)
    tracer = tracer_of(cache)
    tracer.end_cycle()
    spans = [sp for rec in tracer.recorder.records() for sp in rec.spans
             if sp.name == "evict_replay"]
    assert [(sp.attrs["claims"], sp.attrs["victims"], sp.attrs["commits"])
            for sp in spans] == [(3, 7, 1), (1, 0, 0)]
    assert (m.EVICT_COMMITS._values[("reclaim", "bulk")]
            - before.get(("reclaim", "bulk"), 0)) == 1
    assert m.EVICT_COMMITS._values.get(("reclaim", "single"), 0) == before.get(
        ("reclaim", "single"), 0)


def test_preempts_replay_commits_once_a_statement():
    """Through ``PreemptAction._replay``: two claimants of one job are one
    Statement, so one ``bulk_evict`` holds both claims' victims."""
    cache = cluster(ScriptedEvictor())
    before = dict(m.EVICT_COMMITS._values)
    ssn = open_session(cache, parse_scheduler_conf(CONF).tiers)
    try:
        claims = [claim("c1", "n1", ["a1-0", "a1-1"]),
                  claim("c2", "n2", ["a3-0", "a3-1"])]
        # drf's verdict is not the matter here: every candidate is a victim
        ssn.preemptable = lambda task, candidates: list(candidates)
        with ReplayTally.replaying(ssn, "preempt", claims) as tally:
            PreemptAction()._replay(ssn, tally, claims)
        assert (tally.committed, tally.victims, tally.commits) == (2, 4, 1)
        assert [e["claimant"] for e
                in cache.eviction_log.since(0)["evictions"]] == (
            [f"{NS}/c1"] * 2 + [f"{NS}/c2"] * 2)
    finally:
        close_session(ssn)
    assert (m.EVICT_COMMITS._values[("preempt", "bulk")]
            - before.get(("preempt", "bulk"), 0)) == 1
    assert cache.columns.check_consistency(cache) == []

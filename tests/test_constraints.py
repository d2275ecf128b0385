"""Inter-pod (anti-)affinity answered from the match-count planes
(api/affinity_planes.py) — the CPU-size twin of the benchmark's
``affinity-10k-5k``: seeded clusters of ~96 nodes in 3 zones and ~600 pods of
upstream scheduler_perf's five templates.

(a) the plane-derived mask and preferred score equal the object-scan oracle
    (``predicates.pod_affinity_ok``, ``nodeorder.preferred_pod_affinity_score``)
    row for row, after each of a sequence of binds, deletes and evictions —
    the incremental update against a rebuild;
(b) what the loop binds passes ``benchmark/reference_constraints.check_binds``
    with every count zero, and planted faults are each counted;
(c) two mutually exclusive pods in one solve land apart without the host
    fallback;
(d) a cluster with no term traces the solve programs it traced before.
"""

import os
import sys
from collections import Counter

import numpy as np
import pytest

from kube_batch_tpu import actions as _actions  # noqa: F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: F401 — registers
from kube_batch_tpu.api.pod import Affinity, PodAffinityTerm
from kube_batch_tpu.api.types import PodPhase
from kube_batch_tpu.framework.interface import get_action
from kube_batch_tpu.plugins.nodeorder import preferred_pod_affinity_score
from kube_batch_tpu.plugins.predicates import pod_affinity_ok

from tests.fixtures import GiB, build_cache, build_node, build_pod
from tests.test_actions import run_actions

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import reference_constraints  # noqa: E402

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
NODES, ZONES = 96, 3


def term(color, key):
    return PodAffinityTerm(match_labels={"color": color}, topology_key=key)


#: upstream scheduler_perf's five pod templates (config/templates/): labels,
#: terms, topology keys; the zone key over 3 zones here
TEMPLATES = {
    "default": dict(labels={}),
    "affinity": dict(labels={"color": "blue"},
                     affinity=lambda: Affinity(
                         pod_affinity=[term("blue", ZONE)])),
    "anti": dict(labels={"color": "green"},
                 affinity=lambda: Affinity(
                     pod_anti_affinity=[term("green", HOST)])),
    "pref": dict(labels={"color": "red"},
                 affinity=lambda: Affinity(
                     preferred_pod_affinity=[(1.0, term("red", HOST))])),
    "pref-anti": dict(labels={"color": "yellow"},
                      affinity=lambda: Affinity(
                          preferred_pod_anti_affinity=[
                              (1.0, term("yellow", HOST))])),
}


def make_pod(name, template, node=None):
    t = TEMPLATES[template]
    kw = {"labels": dict(t["labels"])}
    if "affinity" in t:
        kw["affinity"] = t["affinity"]()
    return build_pod(
        "c1", name, node, PodPhase.RUNNING if node else PodPhase.PENDING,
        {"cpu": 100, "memory": 500 * 2 ** 20}, **kw)


def make_nodes():
    return [build_node(f"n{i:03d}", cpu=4000, mem=32 * GiB,
                       labels={ZONE: f"zone{i % ZONES}", HOST: f"n{i:03d}"})
            for i in range(NODES)]


def seeded_cluster(seed, running=480, pending=120):
    """~600 pods of the five templates: ``running`` spread over the nodes so
    that no required term is violated (green: one a node; blue: one zone),
    ``pending`` for the loop to place."""
    rng = np.random.default_rng(seed)
    names = list(TEMPLATES)
    pods, green_nodes = [], set()
    for i in range(running):
        template = names[i % len(names)]
        if template == "anti":
            free = [n for n in range(NODES) if n not in green_nodes]
            node = int(rng.choice(free))
            green_nodes.add(node)
        elif template == "affinity":
            node = int(rng.integers(NODES // ZONES)) * ZONES  # zone0
        else:
            node = int(rng.integers(NODES))
        pods.append(make_pod(f"run-{i}", template, f"n{node:03d}"))
    for i in range(pending):
        pods.append(make_pod(f"new-{i}", names[i % len(names)]))
    return build_cache(queues=["default"], nodes=make_nodes(), pods=pods)


def assert_planes_equal_oracle(cache):
    """Every termed row's mask and raw score against the object scan, and
    the plane against a rebuild."""
    cols = cache.columns
    planes = cols.affinity
    assert cols.check_consistency(cache) == []
    nodes = [n for n in cols.node_by_row if n is not None]
    checked = 0
    for row in sorted(planes._rows):
        task = cols.task_by_row[row]
        mask, score = planes.mask_row(row), planes.raw_score_row(row)
        for n in nodes:
            assert mask[n._row] == pod_affinity_ok(task, n, nodes), (
                task.key(), n.name)
            assert score[n._row] == preferred_pod_affinity_score(
                task, n, nodes), (task.key(), n.name)
        checked += 1
    return checked


class TestPlanesAgainstTheObjectScan:
    @pytest.mark.parametrize("seed", [7, 2147483659])
    def test_masks_and_scores_after_binds_deletes_and_evictions(self, seed):
        """(a): 96 nodes x 3 zones x 600 pods; the planes are updated at the
        rows each step touches and must equal the object scan after it."""
        rng = np.random.default_rng([seed, 1])
        cache = seeded_cluster(seed, running=300, pending=60)
        # sample of rows: the scan is O(rows x nodes x pods)
        planes = cache.columns.affinity
        keep = set(rng.choice(sorted(planes._rows), 40, replace=False))
        sampled = {r: planes._rows[r] for r in keep}

        def check():
            full, planes._rows = planes._rows, {
                r: t for r, t in planes._rows.items() if r in sampled}
            try:
                return assert_planes_equal_oracle(cache)
            finally:
                planes._rows = full

        assert check() == 40
        # binds: the loop places the pending pods
        run_actions(cache, action_names=["allocate"])
        assert len(cache.binder.binds) == 60
        check()
        # deletes: a fifth of the bound pods go
        victims = [p for p in list(cache.pods.values())
                   if p.node_name][::5]
        for pod in victims:
            cache.delete_pod(pod)
        check()
        # evictions: the pods stay on their nodes, Releasing
        for job in list(cache.jobs.values())[:30]:
            for task in list(job.tasks.values()):
                if task.node_name:
                    cache.evict(task, "test")
        check()
        # a node goes with its residents, and comes back empty
        node = cache.nodes["n003"].node
        cache.delete_node("n003")
        check()
        cache.add_node(node)
        check()

    def test_first_pod_fast_path_and_self_exclusion(self):
        """No blue pod anywhere: every node is open to the first one; a
        bound green pod does not exclude itself from its own node."""
        cache = build_cache(queues=["default"], nodes=make_nodes()[:6], pods=[
            make_pod("b0", "affinity"), make_pod("g0", "anti", "n001"),
            make_pod("g1", "anti")])
        planes = cache.columns.affinity
        rows = {cache.columns.task_by_row[r].name: r for r in planes._rows}
        assert planes.mask_row(rows["b0"])[:6].all()
        assert planes.mask_row(rows["g0"])[:6].all()      # itself apart
        assert not planes.mask_row(rows["g1"])[1]
        assert_planes_equal_oracle(cache)

    def test_signatures_are_released_with_their_last_row(self):
        cache = build_cache(queues=["default"], nodes=make_nodes()[:4],
                            pods=[make_pod("g0", "anti", "n001"),
                                  make_pod("r0", "pref")])
        planes = cache.columns.affinity
        assert planes.live_signatures == 2 and planes._sig_req_refs.any()
        cache.delete_pod(cache.pods["c1/g0"])
        assert planes.live_signatures == 1 and not planes._sig_req_refs.any()
        # a preferred term alone still puts its rows on the in-solve axis
        pending = np.zeros(planes.t_sig.shape[0], bool)
        pending[list(planes._rows)] = True
        idx, _, _, _, _, terms, stats = planes.snapshot_rows(pending)
        assert stats["required"] == 0 and (idx >= 0).sum() == 1
        assert terms is not None and terms.pw.any() and not terms.anti.any()
        assert not planes.cnt.any()
        cache.delete_pod(cache.pods["c1/r0"])
        assert planes.live_signatures == 0


class TestServedBindsAgainstTheReference:
    def test_the_loop_binds_pass_check_binds(self):
        """(b): everything the loop binds, in the order it bound, against
        the plain reference: every count zero."""
        cache = seeded_cluster(11, running=300, pending=120)
        before = {k: p.node_name for k, p in cache.pods.items()
                  if p.node_name}
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert len(binds) == 120
        world = reference_constraints.World.from_pods(
            {n.name: n for n in (c.node for c in cache.nodes.values())},
            cache.pods.values())
        order = [(k, n) for k, n in before.items()] + list(binds.items())
        counts = world.check_binds(order)
        assert counts == dict.fromkeys(counts, 0), counts
        # the allocate replay never left the bulk path
        last = get_action("allocate").last_fallback
        assert last["slow_jobs"] == 0 and last["host_place_tasks"] == 0

    @pytest.mark.parametrize("fault,count", [
        ("second_green", "anti_affinity_violations"),
        ("blue_outside", "affinity_violations"),
        ("node_over", "nodes_over"),
        ("twice", "double_binds"),
    ])
    def test_planted_faults_are_counted(self, fault, count):
        cache = seeded_cluster(13, running=200, pending=0)
        pods = {k: p for k, p in cache.pods.items()}
        order = [(k, p.node_name) for k, p in pods.items()]
        world = reference_constraints.World.from_pods(
            {c.node.name: c.node for c in cache.nodes.values()},
            pods.values())
        clean = world.check_binds(order)
        assert clean == dict.fromkeys(clean, 0)
        green = next(k for k, p in pods.items()
                     if p.labels.get("color") == "green")
        blue = next(k for k, p in pods.items()
                    if p.labels.get("color") == "blue")
        if fault == "second_green":
            other = next(k for k, p in pods.items() if k != green
                         and p.labels.get("color") == "green")
            order = [(k, pods[green].node_name if k == other else n)
                     for k, n in order]
        elif fault == "blue_outside":
            order = [(k, "n001" if k == blue else n) for k, n in order]
        elif fault == "node_over":
            # 41 plain pods of 100 m on one 4,000 m node
            plain = [k for k, p in pods.items() if not p.labels][:41]
            order = [(k, "n000" if k in plain else n) for k, n in order]
        else:
            order = order + [order[0]]
        assert world.check_binds(order)[count] > 0


    @pytest.mark.parametrize("deleted_first,violations", [
        (False, 1), (True, 0)])
    def test_a_deleted_pod_counts_until_its_delete(self, deleted_first,
                                                   violations):
        """The walk takes deletes in order ((pod, None)): a green pod bound
        beside one that is deleted LATER is a violation though the end state
        no longer shows it; beside one deleted BEFORE, none; and the node's
        room comes back with the delete."""
        cache = seeded_cluster(13, running=200, pending=0)
        pods = dict(cache.pods)
        world = reference_constraints.World.from_pods(
            {c.node.name: c.node for c in cache.nodes.values()},
            pods.values())
        order = [(k, p.node_name) for k, p in pods.items()]
        first, second = [k for k, p in pods.items()
                         if p.labels.get("color") == "green"][:2]
        node = pods[first].node_name
        rest = [(k, n) for k, n in order if k != second]
        walk = (rest + [(first, None), (second, node)] if deleted_first
                else rest + [(second, node), (first, None)])
        counts = world.check_binds(walk)
        assert counts["anti_affinity_violations"] == violations
        assert counts["unbound"] == 0 and counts["double_binds"] == 0
        # the end state's plane: the deleted pod's requests are gone
        i = world.node_index[node]
        live = [k for k, n in rest if n == node and k != first] + [second]
        assert world.used[i, 2] == len(live)


class TestSameSolveExclusion:
    def test_exclusive_pods_land_apart_without_the_host_fallback(self):
        """(c): twelve green pods in one solve on twelve free nodes: one a
        node, no slow replay, no host placement."""
        cache = build_cache(
            queues=["default"], nodes=make_nodes()[:12],
            pods=[make_pod(f"g{i}", "anti") for i in range(12)])
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert len(binds) == 12
        assert len(set(binds.values())) == 12
        last = get_action("allocate").last_fallback
        assert last["slow_jobs"] == 0 and last["host_place_tasks"] == 0

    def test_a_thirteenth_stays_pending(self):
        cache = build_cache(
            queues=["default"], nodes=make_nodes()[:12],
            pods=[make_pod(f"g{i}", "anti") for i in range(13)])
        run_actions(cache, action_names=["allocate"])
        assert len(cache.binder.binds) == 12
        assert len(set(cache.binder.binds.values())) == 12

    def test_the_first_pod_pins_the_zone_its_followers_join(self):
        """The first-pod fast path inside one solve: no blue pod anywhere,
        nine arrive together; all nine end up in one zone."""
        cache = build_cache(
            queues=["default"], nodes=make_nodes()[:12],
            pods=[make_pod(f"b{i}", "affinity") for i in range(9)])
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert len(binds) == 9
        assert len({int(n[1:]) % ZONES for n in binds.values()}) == 1
        assert get_action("allocate").last_fallback["slow_jobs"] == 0

    def test_replicas_that_prefer_to_sit_apart_see_each_other(self):
        """Twenty yellow pods (preferred anti-affinity to yellow, hostname)
        in one solve on 24 nodes of which two hold a yellow pod: each sees
        the choices of those before it, as the reference's sequential loop
        would, so the twenty take twenty nodes and none of the two."""
        cache = build_cache(
            queues=["default"], nodes=make_nodes()[:24],
            pods=[make_pod("old0", "pref-anti", "n003"),
                  make_pod("old1", "pref-anti", "n010")]
            + [make_pod(f"y{i}", "pref-anti") for i in range(20)]
            + [make_pod(f"p{i}", "default") for i in range(10)])
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert len(binds) == 30
        yellow = [n for k, n in binds.items() if k.startswith("c1/y")]
        assert len(set(yellow)) == 20
        assert not {"n003", "n010"} & set(yellow)
        last = get_action("allocate").last_fallback
        assert last["slow_jobs"] == 0 and last["host_place_tasks"] == 0

    def test_replicas_that_prefer_company_follow_the_first(self):
        """Six red pods (preferred affinity to red, hostname), no red pod
        anywhere: the first lands by the other scores, the five behind it
        read its choice and join it."""
        cache = build_cache(
            queues=["default"], nodes=make_nodes()[:12],
            pods=[make_pod(f"r{i}", "pref") for i in range(6)])
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert len(binds) == 6 and len(set(binds.values())) == 1

    def test_replicas_that_prefer_company_fill_node_after_node(self):
        """A hundred red pods on empty nodes of 40 slots: the walk charges
        each choice to the node, so the 41st moves on inside the same round
        and the solve does not spend a round a node."""
        cache = build_cache(
            queues=["default"], nodes=make_nodes()[:12],
            pods=[make_pod(f"r{i}", "pref") for i in range(100)])
        run_actions(cache, action_names=["allocate"])
        per_node = sorted(Counter(cache.binder.binds.values()).values())
        assert per_node == [20, 40, 40]
        assert get_action("allocate").last_solve_rounds <= 2

    def test_the_walked_score_is_the_hosts_where_nothing_was_placed(self):
        """One pending pod of each preferred template beside bound ones: the
        walk's own min-max reduce gives the row the host derived, so the
        choice is the object-scan session's."""
        def pods():
            return ([make_pod(f"o{i}", "pref", f"n{i:03d}") for i in (1, 5)]
                    + [make_pod(f"q{i}", "pref-anti", f"n{i:03d}")
                       for i in range(0, 12, 2)]
                    + [make_pod("r", "pref"), make_pod("y", "pref-anti")])
        cache = build_cache(queues=["default"], nodes=make_nodes()[:12],
                            pods=pods())
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert binds["c1/r"] in ("n001", "n005")
        assert int(binds["c1/y"][1:]) % 2 == 1

    def test_a_labelled_pod_counts_inside_the_solve(self):
        """A pod the term's signature selects, carrying no term itself, and
        two pods whose term excludes it: inside one solve the rule is
        symmetric, so the three land on three nodes whichever bids first."""
        cache = build_cache(
            queues=["default"], nodes=make_nodes()[:3],
            pods=[build_pod("c1", "plain", None, PodPhase.PENDING,
                            {"cpu": 100, "memory": GiB},
                            labels={"color": "green"}),
                  make_pod("g0", "anti"), make_pod("g1", "anti")])
        run_actions(cache, action_names=["allocate"])
        binds = cache.binder.binds
        assert len(binds) == 3 and len(set(binds.values())) == 3
        assert get_action("allocate").last_fallback["slow_jobs"] == 0


class TestNoTermNoChange:
    def test_a_cluster_without_terms_traces_what_it_traced_before(self):
        """(d): with no term the snapshot carries no ``aff_terms`` leaf and
        the padding rows every program was compiled with; the full solve's
        jaxpr has no equation of the in-solve rule."""
        import jax

        from kube_batch_tpu.actions.allocate import (
            build_session_snapshot,
            session_allocate_config,
        )
        from kube_batch_tpu.framework.conf import parse_scheduler_conf
        from kube_batch_tpu.framework.session import (
            close_session,
            open_session,
        )
        from kube_batch_tpu.ops.assignment import allocate_solve
        from tests.test_actions import TWO_TIER_CONF

        def traced(pods, drop=()):
            cache = build_cache(queues=["default"], nodes=make_nodes()[:8],
                                pods=pods)
            for key in drop:
                cache.delete_pod(cache.pods[key])
            ssn = open_session(cache, parse_scheduler_conf(TWO_TIER_CONF).tiers)
            try:
                snap, _ = build_session_snapshot(ssn)
                config = session_allocate_config(ssn)
                jaxpr = jax.make_jaxpr(
                    lambda s: allocate_solve(s, config))(snap)
                return snap, jaxpr
            finally:
                close_session(ssn)

        plain_pods = [make_pod(f"p{i}", "default") for i in range(8)]
        plain, jaxpr = traced(plain_pods)
        assert plain.aff_terms is None
        assert plain.task_aff_idx.shape == (1,)
        assert plain.task_pref_idx.shape == (1,)
        # the ten results the solve always had, and no more
        assert len(jaxpr.out_avals) == 10
        termed, with_rule = traced(
            plain_pods[:4] + [make_pod(f"g{i}", "anti") for i in range(4)])
        assert termed.aff_terms is not None
        assert termed.task_aff_idx.shape == (64,)
        assert len(with_rule.out_avals) == 11      # + term_exclusions
        assert len(jax.tree.leaves(plain)) + 8 == len(jax.tree.leaves(termed))
        # a store whose last term has left is back to the same program
        again, jaxpr_again = traced(
            plain_pods[:7] + [make_pod("g", "anti")], drop=["c1/g"])
        assert again.aff_terms is None
        assert str(jaxpr_again) == str(jaxpr)
        assert str(with_rule) != str(jaxpr)

"""The multi-tenant GPU training deployment
(``benchmark/configs/philly-36k-5k.json``: gangs of 1-128 one-GPU pods on
8-GPU nodes, 14 queues of Zipf demand) at a size the suite holds: 96 nodes
90% full, gangs of 1-64 (``rehearsal-gangmix-690-96``).

The served path (``test_envelope.Served``: cache + ``Scheduler`` + the
shipped five actions) is filled, left with one or two GPUs free on the
nodes that have any, given a 64-member gang, and churned for a few bursts;
its binds are checked by the deployment's plain reference
(``benchmark/reference_gangmix.py``: numpy int64, imports nothing of the
program) to all-zero counts.  The same reference counts a planted ninth GPU
on a node, a gang bound one short and the ``stale`` control.  The hot
queue ends above its 1/14 share with nothing pending, the bursts run the
compacted warm program on one device, and the series and span attributes
that observe all this grow."""

from __future__ import annotations

import collections
import json
import os
import sys

import numpy as np
import pytest

from kube_batch_tpu.metrics.metrics import (
    GANG_DECISION_LATENCY,
    TOPK_EXHAUSTED,
    TOPK_REENTRIES,
    gang_size_class,
    render_prometheus,
)
from kube_batch_tpu.obs.trace import tracer_of
from tests.test_envelope import REPO, ZERO, Served, _walk

BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
try:
    import reference_gangmix
finally:
    sys.path.remove(BENCH)

with open(os.path.join(BENCH, "configs",
                       "rehearsal-gangmix-690-96.json")) as f:
    CONFIG = json.load(f)
GPUS = CONFIG["nodes"] * CONFIG["node"]["gpu_milli"]
BURSTS = ([1, 2, 4, 8, 1, 1], [16, 1, 2, 1], [32, 4, 1, 1, 2], [8, 8, 1])


class GangServed(Served):
    def __init__(self, seed: int):
        self.classes = collections.Counter()  # of every gang ever posted
        super().__init__(seed, ledger=reference_gangmix.Ledger(CONFIG, seed))

    def post(self, pgs, pods) -> None:
        super().post(pgs, pods)
        self.classes.update(gang_size_class(pg["min_member"]) for pg in pgs)

    def mix(self, sizes) -> list:
        """Post one fresh gang per size, in the queues that many have."""
        pgs, pods = self.ledger.make_mix(
            sizes, self.ledger.gang_queues(len(sizes)))
        self.post(pgs, pods)
        return pgs

    def free_gpus(self) -> np.ndarray:
        """[N] whole GPUs free on each node, by the reference's count."""
        assert self.counts() == ZERO
        return (self.ledger.gpu_alloc - self.ledger.gpu_used) // 1000

    def node_of(self) -> dict:
        return {b["pod"]: b["node"] for b in self.binds()}


@pytest.fixture(scope="module")
def drive():
    """One drive for every test of the module: what it saw, by name."""
    old = os.environ.get("KB_SHARD")
    os.environ["KB_SHARD"] = "0"     # one device, as the cell's one chip
    served = GangServed(seed=3100000001)
    seen = {"gangs_before": dict(GANG_DECISION_LATENCY._count),
            "topk_before": (TOPK_EXHAUSTED._values[("allocate",)],
                            TOPK_REENTRIES._values[("allocate",)])}
    try:
        assert served.cycles() == ZERO                  # the cold drain
        # fill every GPU with one-pod gangs ...
        served.mix([1] * int(served.free_gpus().sum()))
        assert served.cycles() == ZERO
        assert int(served.free_gpus().sum()) == 0
        # ... then free one or two GPUs on as many nodes as it takes for
        # 64: one-pod gangs only, so no gang is left short
        node_of, taken, pgs, pods = served.node_of(), {}, [], []
        for members, pg, _ in list(served.ledger.gangs.values()):
            node = node_of[served.ledger.key(members[0])]
            if len(members) == 1 and taken.get(node, 0) < 2 and len(pods) < 64:
                taken[node] = taken.get(node, 0) + 1
                pgs.append(pg)
                pods.append(members[0])
        served.delete(pgs, pods)
        free = served.free_gpus()
        assert int(free.sum()) == 64 and int(free.max()) <= 2
        (big,) = served.mix([64])
        assert served.cycles(most=2) == ZERO            # placed whole, at once
        node_of = served.node_of()
        seen["big_nodes"] = {
            node_of[served.ledger.key(p)]
            for p in served.ledger.gangs[big["name"]][0]}
        for sizes in BURSTS:                            # the churn
            served.delete(*served.ledger.oldest_covering(sum(sizes)))
            served.mix(sizes)
            assert served.cycles() == ZERO
        seen.update(served=served, binds=served.binds(),
                    dispatched=served.dispatched(),
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


def test_a_64_member_gang_lands_on_32_or_more_nodes(drive):
    assert len(drive["big_nodes"]) >= 32


def test_the_churned_cluster_passes_the_reference(drive):
    served = drive["served"]
    numbers, used = served.ledger.check_binds(drive["binds"])
    assert numbers == ZERO
    assert used.shape == (CONFIG["nodes"], 3)           # what run.py slices
    assert (served.ledger.gpu_used <= served.ledger.gpu_alloc).all()
    assert int(served.ledger.gpu_used.sum()) == 1000 * len(drive["binds"])


def test_a_ninth_gpu_on_a_node_is_counted(drive):
    ledger, binds = drive["served"].ledger, drive["binds"]
    ledger.check_binds(binds)
    full = ledger.node_names[int(np.argmax(
        ledger.gpu_used == ledger.gpu_alloc))]
    planted = [dict(b) for b in binds]
    next(b for b in planted if b["node"] != full)["node"] = full
    numbers = ledger.check_binds(planted)[0]
    assert numbers["nodes_over"] == 1
    assert numbers == dict(ZERO, nodes_over=1)
    # CPU, memory and pod slots are nowhere near: the GPU alone is over
    used = ledger.check_binds(planted)[1]
    assert (used <= ledger.alloc).all()


def test_a_gang_bound_one_short_is_counted(drive):
    ledger, binds = drive["served"].ledger, drive["binds"]
    gang = next(name for name, (members, _, need) in ledger.gangs.items()
                if need >= 8)
    member = ledger.key(ledger.gangs[gang][0][0])
    numbers = ledger.check_binds([b for b in binds if b["pod"] != member])[0]
    assert numbers == dict(ZERO, gangs_split=1, unbound=1)


@pytest.mark.parametrize("precision,sound", [("exact", True),
                                             ("stale", False)])
def test_the_reference_as_a_scheduler_and_its_stale_control(precision, sound):
    ledger = reference_gangmix.Ledger(CONFIG, 3100000002)
    ledger.add(*ledger.make_population())
    assert len(ledger.pods) == CONFIG["population"]["pods"]
    numbers = ledger.check_binds(
        reference_gangmix.place_first_fit(ledger, precision))[0]
    assert (numbers == ZERO) is sound
    if not sound:
        assert numbers["nodes_over"] >= 1


def test_the_hot_queue_is_lent_what_the_others_leave_idle(drive):
    served = drive["served"]
    ledger = served.ledger
    assert ledger.check_binds(drive["binds"])[0]["unbound"] == 0
    per_queue = dict.fromkeys(ledger.queue_order, 0)
    for b in drive["binds"]:
        gang = ledger.pods[b["pod"]][2]
        per_queue[ledger.gangs[gang][1]["queue"]] += ledger.gpus[b["pod"]]
    hot = per_queue[ledger.queue_order[0]]
    assert hot == max(per_queue.values())
    assert hot / GPUS > 2 / 14          # twice its equal-weight share
    assert sum(per_queue.values()) / GPUS > 0.85


def test_the_bursts_run_the_compacted_warm_program_on_one_device(drive):
    got = drive["dispatched"]
    assert got and {mode for _, mode, _ in got} == {"single"}, got
    assert got.get(("allocate", "single", "warm"), 0) >= len(BURSTS) - 1, got
    assert got.get(("allocate", "single", "cold"), 0) >= 1      # the drain


def test_the_gang_clock_and_the_topk_counters_grow(drive):
    before = drive["gangs_before"]
    grew = {k[0]: v - before.get(k, 0)
            for k, v in GANG_DECISION_LATENCY._count.items()}
    # one sample a gang, in its class: every gang was bound whole, once
    assert grew == dict(drive["served"].classes)
    assert grew["64+"] >= 2 and grew["16-32"] >= 2 and grew["1"] >= 64
    assert [gang_size_class(n) for n in (1, 2, 8, 9, 32, 33, 128)] == [
        "1", "2-8", "2-8", "16-32", "16-32", "64+", "64+"]
    page = render_prometheus()
    assert 'volcano_gang_decision_latency_milliseconds_count{size_class="64+"}' in page
    assert 'volcano_topk_exhausted_total{action="allocate"}' in page
    assert 'volcano_topk_reentries_total{action="allocate"}' in page
    exhausted, reentries = drive["topk_before"]
    assert TOPK_EXHAUSTED._values[("allocate",)] >= exhausted
    assert TOPK_REENTRIES._values[("allocate",)] >= reentries


def test_the_spans_say_what_the_counters_count(drive):
    waits = [sp for sp in drive["spans"] if sp.name == "device_wait"]
    assert waits and all({"exhausted", "reentries", "rounds"} <= set(sp.attrs)
                         for sp in waits)
    replays = [sp.attrs for sp in drive["spans"] if sp.name == "host_replay"]
    assert replays and all({"gangs", "largest_gang"} <= set(a)
                           for a in replays)
    assert max(a["largest_gang"] for a in replays) == 64
    assert any(a["gangs"] == len(BURSTS[0]) for a in replays)


def test_podgroups_with_no_pod_stretch_the_job_axis_and_it_stays():
    """What the cell's warm-up rests on (``gangmix_bursts._stretch``): a
    PodGroup takes a row of the job axis before any pod of it arrives, and
    the axis keeps its capacity when the PodGroups are gone."""
    from kube_batch_tpu.api import serialize
    from kube_batch_tpu.api.snapshot import bucket
    from kube_batch_tpu.cache.cache import SchedulerCache

    cache = SchedulerCache()
    try:
        ledger = reference_gangmix.Ledger(CONFIG, 3100000003)
        before = cache.columns.jobs.cap
        pgs, _ = ledger.make_mix([1] * 300, ledger.gang_queues(300))
        groups = [serialize.pod_group_from_dict(pg) for pg in pgs]
        for pg in groups:
            cache.add_pod_group(pg)
        assert cache.columns.jobs.cap == bucket(300) > before
        for pg in groups:
            cache.delete_pod_group(pg.key())
        assert cache.columns.jobs.cap == bucket(300)
    finally:
        cache.stop()

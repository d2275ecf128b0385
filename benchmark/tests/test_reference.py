"""`correct` can come out false: each fault planted where an answer is
produced is counted by the reference, and the controls fail."""

import copy
import json
import os

import numpy as np
import pytest

import control
import reference
from conftest import BENCH

GiB = 2 ** 30


def config(name="rehearsal-1200-96"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def bound_cluster(seed=3):
    """A ledger with its population bound first-fit by the exact reference."""
    ledger = control.populate(config(), seed)
    return ledger, reference.place_first_fit(ledger, "exact")


def test_sound_binds_pass():
    ledger, binds = bound_cluster()
    numbers, used = ledger.check_binds(binds)
    assert not any(numbers.values())
    assert used[:, 2].sum() == len(ledger.pods)


def test_overcommit_of_one_milli_core_is_counted():
    ledger, binds = bound_cluster()
    _, used = ledger.check_binds(binds)
    # shrink the node that one pod sits on to one milli-core under its use
    node = ledger.node_index[binds[0]["node"]]
    ledger.alloc[node, 0] = used[node, 0] - 1
    numbers, _ = ledger.check_binds(binds)
    assert numbers["nodes_over"] == 1
    ledger.alloc[node, 0] = used[node, 0]
    assert ledger.check_binds(binds)[0]["nodes_over"] == 0


def test_split_gang_is_counted():
    ledger, binds = bound_cluster()
    numbers, _ = ledger.check_binds(binds[1:])  # one member of a gang unbound
    assert numbers["gangs_split"] == 1 and numbers["unbound"] == 1


def test_double_bind_is_counted():
    ledger, binds = bound_cluster()
    other = next(b["node"] for b in binds if b["node"] != binds[0]["node"])
    numbers, _ = ledger.check_binds(binds + [{"pod": binds[0]["pod"],
                                              "node": other}])
    assert numbers["double_binds"] == 1


def test_unknown_pod_and_node_are_counted():
    ledger, binds = bound_cluster()
    numbers, _ = ledger.check_binds(
        binds + [{"pod": "bench/never-sent", "node": "n0"}])
    assert numbers["unknown_pods"] == 1
    moved = copy.deepcopy(binds)
    moved[0]["node"] = "no-such-node"
    assert ledger.check_binds(moved)[0]["unknown_nodes"] == 1


def whatif_state():
    ledger, binds = bound_cluster()
    _, used = ledger.check_binds(binds)
    free = np.sort((ledger.alloc - used)[:, 0])[::-1]
    body = {"queue": "q0", "requests": {"cpu": float(free[2]),
                                        "memory": float(GiB)}}
    room = int(reference.slots(ledger.alloc, used,
                               reference.request_vec(body["requests"])).sum())
    return ledger, used, body, room


def test_wrong_max_fit_is_a_fault():
    ledger, used, body, room = whatif_state()
    body["max_count"] = 64
    right = reference.answer(body, ledger.alloc, used, ledger.node_names, True)
    assert right["max_fit"] == room
    assert reference.check_sweep(right, body, ledger.alloc, used) == []
    wrong = dict(right, max_fit=room + 1)
    assert reference.check_sweep(wrong, body, ledger.alloc, used)


def test_wrong_verdict_and_placement_are_faults():
    ledger, used, body, room = whatif_state()
    body["count"] = room
    right = reference.answer(body, ledger.alloc, used, ledger.node_names, False)
    check = lambda r, b=body: reference.check_probe(  # noqa: E731
        r, b, ledger.alloc, used, ledger.node_index)
    assert right["feasible"] and check(right) == []
    assert check(dict(right, feasible=False))
    crowded = dict(right, nodes=[right["nodes"][0]] * room)
    assert room == 1 or check(crowded)
    too_many = dict(body, count=room + 1)
    assert check(dict(right, nodes=right["nodes"] + ["n0"]), too_many)
    versionless = {k: v for k, v in right.items() if k != "snapshot_version"}
    assert check(versionless)


def test_whatif_control_in_bfloat16_fails_at_the_edge():
    """A capacity plane read back in bfloat16 answers the edge probes
    wrongly: the member that exactly fills a node no longer fits."""
    wrong = 0
    for seed in (1, 2, 3):
        ledger = control.populate(config(), seed)
        # spread, as the program places: every node partly used
        spread = [{"pod": key, "node": ledger.node_names[i % 96]}
                  for i, key in enumerate(list(ledger.pods)[:768])]
        numbers, used = ledger.check_binds(spread)
        assert numbers["nodes_over"] == 0
        stored = reference.plane(used, "bfloat16")
        free = np.sort((ledger.alloc - used)[:, 0])[::-1]
        for k in range(1, 6):
            body = {"queue": "q0", "max_count": 64,
                    "requests": {"cpu": float(free[k]), "memory": float(GiB)}}
            resp = reference.answer(body, ledger.alloc, stored,
                                    ledger.node_names, True)
            wrong += bool(reference.check_sweep(resp, body, ledger.alloc, used))
    assert wrong >= 3


def spread_cluster(cfg, seed, pods):
    """(ledger, rows of (node, request), used) with ``pods`` of the
    population spread over the nodes, as the program places."""
    ledger = control.populate(cfg, seed)
    n = len(ledger.node_names)
    binds = [{"pod": key, "node": ledger.node_names[i % n]}
             for i, key in enumerate(list(ledger.pods)[:pods])]
    numbers, used = ledger.check_binds(binds)
    assert numbers["nodes_over"] == 0
    rows = [(ledger.node_index[b["node"]],
             np.array(ledger.pods[b["pod"]][:2] + (1,), np.int64))
            for b in binds]
    return ledger, rows, used


@pytest.mark.parametrize("name,pods", [("rehearsal-1200-96", 768),
                                       ("rehearsal-kubemark-300-10", 300)])
def test_edge_control_in_bfloat16_fails_and_the_exact_plane_passes(name, pods):
    """Pods that sit on a fit edge see a plane summed in bfloat16: an
    exact pod stays pending where it reads too much in use, an over pod
    binds where it reads too little (30 x 100 m sum to 2,928)."""
    cfg = config(name)
    mem = int(min(cfg["request_mix"]["memory_bytes"]))
    for seed in (1, 2, 3):
        ledger, rows, used = spread_cluster(cfg, seed, pods)
        requests = reference.edge_requests(ledger.alloc, used, mem, 6)
        assert len(requests) == 6
        assert reference.edge_control(
            ledger.alloc, used, requests, "exact") == {
                "unbound": 0, "overfit_binds": 0}
        assert (reference.summed_plane(ledger.alloc, rows, "exact")
                == used).all()
        stored = reference.summed_plane(ledger.alloc, rows, "bfloat16")
        broken = reference.edge_control(
            ledger.alloc, stored, requests, "bfloat16")
        assert broken["unbound"] + broken["overfit_binds"] > 0


def test_a_bound_pod_that_fits_nowhere_is_counted():
    ledger, binds = bound_cluster()
    _, used = ledger.check_binds(binds)
    node = int(np.argmax((ledger.alloc - used)[:, 0]))
    over = ledger.make_pods(
        1, int((ledger.alloc - used)[node, 0]) + 12, GiB)
    ledger.add_unfit(over)
    assert not any(ledger.check_binds(binds)[0].values())  # pending: fine
    numbers, _ = ledger.check_binds(
        binds + [{"pod": ledger.key(over[0]),
                  "node": ledger.node_names[node]}])
    assert numbers["overfit_binds"] == 1 and numbers["nodes_over"] == 1


@pytest.mark.parametrize("name", ["rehearsal-1200-96",
                                  "rehearsal-kubemark-300-10"])
def test_placement_control_fails_and_the_exact_reference_passes(name):
    cfg = config(name)
    for seed in (1, 2, 3):
        assert not any(control.read(cfg, seed, "exact").values())
        broken = control.read(cfg, seed, cfg["control"]["placement"])
        assert broken["nodes_over"] > 0


def test_bfloat16_plane_rounds_to_eight_bits():
    used = np.array([[31750, 3 * GiB, 100], [256, GiB, 7]], np.int64)
    got = reference.plane(used, "bfloat16")
    assert got[0, 0] == 31744 and got[1, 0] == 256
    assert (got[:, 1:] == used[:, 1:]).all()  # GiB multiples and small counts

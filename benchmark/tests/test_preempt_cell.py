"""``preempt-20k-5k`` (``schedperf-preempt-20k-5k`` x ``preempt-open``, 1
chip) rehearsed at a tiny size through run.py's own entry:
``rehearsal-preempt-384-96`` keeps the deployment's node, classes and shapes
on 96 nodes.  The cell is added to the rehearsal's manifest as
``test_tiers_cell.py`` adds one, from files alone, together with the twelve
per-layer metrics ``preempt_manifest.py`` lists (it says why
``BENCHMARK.json`` cannot list them yet).  The whole cell runs: the stream,
the kubelet stand-in that deletes a victim's PodGroup with it, the edge
round with its fillers, the three controls, every new metric file read.  A
feed that names a ``high`` pod comes out ``correct: false``; a program
without the new spans and counter reports nothing and does not raise; the
window's plan is the same for every seed and stays inside the pool."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import preempt_manifest
import reference_preempt
import run as harness
import server as server_mod
from conftest import BENCH, REPO
from rehearsal_manifest import derive
from streams import preempt_bursts

CELL, STANDS_FOR = "rehearsal-preempt", preempt_manifest.CELL
CONFIG = "rehearsal-preempt-384-96"
SOURCE = "schedperf-preempt-20k-5k"


def load(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest() -> dict:
    # end to end what BENCHMARK.json lets the cell report; per layer what a
    # traced run reads through the derived copy
    full = dict(preempt_manifest.derive(),
                end_to_end=load(REPO, "BENCHMARK.json")["end_to_end"])
    out = derive()
    out["configs"].append({
        "name": CONFIG, "file": f"benchmark/configs/{CONFIG}.json"})
    out["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "rehearsal-preempt",
        "chips": 1})
    for section in ("end_to_end", "per_layer"):
        for tiny, accepted in zip(out[section], full[section]):
            # a metric with no list is reported in every cell
            if STANDS_FOR in accepted.get("workloads", [STANDS_FOR]):
                tiny["workloads"].append(CELL)
    out["per_layer"] += (preempt_manifest.tiers_manifest.entries([CELL])
                         + preempt_manifest.entries([CELL]))
    return out


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("preempt") / "manifest.json"
    path.write_text(json.dumps(manifest()))
    return str(path)


@pytest.fixture(scope="module")
def lines(tmp_path_factory, manifest_path):
    tmp = tmp_path_factory.mktemp("preempt-out")
    out = {}
    for trace in ("0", "1"):
        got = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"),
             "--manifest", manifest_path, "--workload", CELL,
             "--seed", "2147484035", "--seconds", "6", "--trace", trace,
             "--platform", "cpu", "--out", str(tmp / "out")],
            cwd=REPO, capture_output=True, text=True, timeout=900,
            env=dict(os.environ, XLA_FLAGS=(
                "--xla_force_host_platform_device_count=1")))
        assert got.returncode == 0, got.stderr[-2000:]
        out[trace] = (json.loads(got.stdout.strip().splitlines()[-1]),
                      got.stdout)
    return out


def test_the_cell_and_its_configuration_are_in_the_manifest():
    full = load(REPO, "BENCHMARK.json")
    cell = next(w for w in full["workloads"] if w["name"] == STANDS_FOR)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        SOURCE, "preempt-open", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in full["configs"] if c["name"] == SOURCE)
    assert len(entry["source"]) <= 200
    assert "PreemptionBasic" in entry["source"]
    config = load(REPO, entry["file"])
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == []
    assert config["architecture"] is None and config["chips"] == 1
    mixed = load(BENCH, "configs", "schedperf-mixed-10k-5k.json")
    for key in ("nodes", "node", "queues"):     # node-default.yaml, one queue
        assert config[key] == mixed[key], key
    classes = {c["name"]: c for c in config["priority_classes"]}
    mi = 1 << 20
    assert (classes["low"]["priority"], classes["low"]["cpu_milli"],
            classes["low"]["memory_bytes"]) == (0, [900], [500 * mi])
    assert (classes["high"]["priority"], classes["high"]["cpu_milli"],
            classes["high"]["memory_bytes"]) == (10, [3000], [500 * mi])
    for c in classes.values():                  # a bare pod, a job of one
        assert (c["size"], c["min_member"]) == (1, 1)
    assert config["population"] == {
        "kind": "priority_classes", "class": "low", "pods": 20000}
    assert config["arrivals"]["class"] == "high"
    assert config["arrivals"]["posted_at_most"] <= config["arrivals"][
        "pool"] == config["nodes"] == 5000
    assert config["assumed"] and config["from_source"]
    assert all(config["guarantees"].values()) and len(
        config["guarantees"]) == 7
    # what the program gives, not what Kubernetes would: PriorityClasses
    # decide no eviction in kube-batch v0.4.2, and the configuration says so
    assert not any("priority" in g for g in config["guarantees"])
    assert any(a.startswith("NOT EXERCISED") and "equal or higher priority"
               in a for a in config["assumed"])
    assert config["control"] == {"placement": "stale",
                                 "priority": "ignore_priority",
                                 "edge": "bfloat16"}
    # every quantity of the deployment, and a node's full sum, is exact in
    # float32
    for v in (*config["node"].values(), 3600, 4 * 500 * mi,
              *(x for c in classes.values()
                for x in (*c["cpu_milli"], *c["memory_bytes"]))):
        assert int(np.float32(v)) == v
    mix = load(BENCH, "traffic", "preempt-open.json")
    (stream,) = mix["streams"]
    assert (stream["kind"], stream["rate"], stream["pods"],
            stream["jitter"], stream["standin_period_ms"],
            stream["edge_rounds"], stream["warm_sizes"],
            stream["warm_audits"], stream["max_warm_bursts"]) == (
        "preempt_bursts", 0.5, 100, 0.2, 10, 12, [2, 4], 1, 8)
    # what ISSUE 48 fixed and no more: tiers-open's keys, at its values
    tiers = load(BENCH, "traffic", "tiers-open.json")
    assert mix.keys() == tiers.keys() and "edge_check" not in mix
    assert mix["scrape_period_ms"] == tiers["scrape_period_ms"] == 10
    assert stream.keys() == tiers["streams"][0].keys() - {
        "gangs", "stretch_gangs"} | {"pods"}
    # the cell reports what overcommit-50k-5k reports, end to end and per
    # layer, but for decision_p90_ms and the four per-layer metrics that
    # move it: every set of six on the chip spread by more than half its
    # bound (25 bursts, of which the 22nd and 23rd decide; PERF.md section
    # 6), so
    # its name went into thirteen of ISSUE 48's eighteen lists
    def reports(name):
        return {m["name"] for m in full["end_to_end"] + full["per_layer"]
                if name in m.get("workloads", [name])}
    tail = {m["name"] for m in full["per_layer"]
            if m["moves"] == "decision_p90_ms"} | {"decision_p90_ms"}
    assert reports(STANDS_FOR) == reports("overcommit-50k-5k") - tail
    assert len(reports("overcommit-50k-5k") & tail) == 5
    assert sum(1 for m in full["end_to_end"] + full["per_layer"]
               if STANDS_FOR in m.get("workloads", [])) == 13
    # a traced run through the derived copy still reads all of them
    derived = preempt_manifest.derive()
    assert all(STANDS_FOR in m["workloads"] for m in
               derived["end_to_end"] + derived["per_layer"]
               if m["name"] in tail)


def test_the_manifest_copy_lists_the_twelve_for_the_cell():
    full = preempt_manifest.derive()
    names = [m["name"] for m in full["per_layer"]]
    assert len(names) == len(set(names))
    mine = {m["name"]: m for m in full["per_layer"][-12:]}
    assert set(mine) == set(preempt_manifest.tiers_manifest.EVICT_PATH) | set(
        preempt_manifest.PREEMPT)
    layers = {m["layer"] for m in load(REPO, "BENCHMARK.json")["per_layer"]}
    for m in mine.values():
        assert m["workloads"] == [STANDS_FOR] and m["layer"] in layers
        spec = load(BENCH, "layer_metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
    # the new files read on the readers there are
    assert {load(BENCH, "layer_metrics", n + ".json")["reader"]
            for n in preempt_manifest.PREEMPT} == {
        "span_totals", "metrics_delta"}


def test_the_population_and_the_pool_are_the_source_s_at_full_size():
    config = load(BENCH, "configs", SOURCE + ".json")
    ledger = reference_preempt.Ledger(config, 2**31 + 5)
    pgs, pods = ledger.make_population()
    ledger.add(pgs, pods)
    assert len(pods) == len(pgs) == 20000
    assert {pg["min_member"] for pg in pgs} == {1}
    assert {pg["priority_class"] for pg in pgs} == {"low"}
    assert {pg["queue"] for pg in pgs} == {"default"}
    assert {(c, m) for c, m, _ in ledger.pods.values()} == {
        (900, 500 << 20)}
    assert ledger.protected == {"high"}
    # four to a node is 3.6 of 4 CPU: a high pod fits nowhere, and the
    # victims alone cover it only when all four go
    assert 4 * 900 + 3000 > 4000 >= 4 * 900
    assert 3 * 900 < 3000 <= 4 * 900
    (params,) = load(BENCH, "traffic", "preempt-open.json")["streams"]
    most = (sum(params["warm_sizes"]) + params["max_warm_bursts"] + 25
            ) * params["pods"] + params["edge_rounds"]
    assert most == 3912 <= config["arrivals"]["posted_at_most"] == 4500
    assert 4 * most <= 20000            # victims for every one of them


def test_the_rehearsal_is_correct_and_reads_the_twelve(lines):
    for trace in ("0", "1"):
        line, stdout = lines[trace]
        assert line["correct"] is True and line["failed"] == 0, stdout[-3000:]
        assert line["attempted"] == 6
        assert line["metrics"] == {}     # no CPU number under a device name
        notes = json.loads(next(
            ln for ln in stdout.splitlines() if ln.startswith("notes: "))[7:])
        # the window was decided by preempt alone, four victims a claim
        assert notes["feed_actions"].keys() == {"preempt"}
        assert notes["window_claims_committed"] == {
            "reclaim": 0.0, "preempt": 24.0}
        assert notes["window_evictions"] == {"reclaim": 0.0, "preempt": 96.0}
        assert notes["window_victims_per_claim"] == {"preempt": 4.0}
        assert notes["window_claimed_share"] == 1.0
        assert notes["window_repeat_claims"]["in_flight"] == 0
        assert notes["window_statements"] == {
            "opened": 24.0, "committed": 24.0, "discarded": 0.0}
        assert notes["backlog_at_close"] == 0 and notes["drained"] is True
        # the edge round: every exact pod bound after five evictions, no
        # over pod, no eviction for one; the three controls wrong
        assert notes["edge_rounds"] == 4 and notes["edge_skipped"] == 0
        assert notes["edge_evictions"] == 20
        assert notes["edge_evictions_for_over_pods"] == 0
        assert notes["edge_nodes_per_claimant_max"] == 1
        assert notes["edge_reference"] == {"unbound": 0, "overfit_binds": 0}
        assert notes["control_edge"]["unbound"] == 4
        exact = notes["control_place_exact"]
        assert (exact["nodes_over"], exact["outranked"]) == (0, 0)
        assert notes["control_place_stale"]["nodes_over"] >= 1
        assert notes["control_place_ignore_priority"]["outranked"] >= 1
    got = lines["0"][0]["rehearsal"]
    for name in ("cpu_decision_p50_ms", "cpu_setup_s"):
        assert got[name]["value"] > 0, name
    assert "cpu_decision_p90_ms" not in got
    got = lines["1"][0]["rehearsal"]
    assert got["cpu_preempt_replay_ms"]["value"] > 0
    assert got["cpu_preempt_phase2_ms"]["value"] > 0
    assert 0 < got["cpu_preempt_statements_per_cycle"]["value"] <= 4.0
    assert got["cpu_preempt_claims_rejected_share"]["value"] == 0.0
    assert got["cpu_preempt_victims_per_claim"]["value"] == 4.0
    assert got["cpu_evictions_per_claim"]["value"] == 4.0
    assert got["cpu_evict_replay_ms"]["value"] > 0
    assert got["cpu_evict_solves_per_cycle"]["value"] > 0
    assert got["cpu_evict_repeat_claims"]["value"] == 0.0
    assert got["cpu_eviction_release_ms"]["value"] > 0
    # the replay of both actions lies inside preempt's own span here
    assert (got["cpu_evict_replay_ms"]["value"]
            <= got["cpu_preempt_replay_ms"]["value"])
    for name in ("cpu_host_replay_ms", "cpu_solve_dispatches_per_cycle",
                 "cpu_compiles_in_window", "cpu_device_wait_ms",
                 "cpu_park_floor_ms", "cpu_generator_late_ms"):
        assert name in got, name


def test_a_program_without_the_spans_reports_nothing_and_does_not_raise():
    """The parent has neither span and no Statements counter: each reader
    finds nothing and the line leaves the metric out; the two that read
    series the parent has give their numbers."""
    from readers import metrics_delta, span_totals

    claims = ("volcano_evict_claims_total",
              'action="preempt",outcome="committed"')
    evictions = ("volcano_evictions_total", 'action="preempt"')
    run = types.SimpleNamespace(
        metrics_pages={"window": ({claims: 10.0, evictions: 40.0},
                                  {claims: 35.0, evictions: 140.0})},
        span_seconds={"window": 50.0},
        trace_states=({"span_ms": {}, "span_counts": {}},
                      {"span_ms": {"evict_replay": 5.0},
                       "span_counts": {"evict_replay": 1}}))
    for name in ("preempt_replay_ms", "preempt_phase2_ms"):
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert span_totals.read(spec, run) is None, name
    spec = load(BENCH, "layer_metrics", "preempt_statements_per_cycle.json")
    assert metrics_delta.read(spec, run) is None
    spec = load(BENCH, "layer_metrics", "preempt_victims_per_claim.json")
    assert metrics_delta.read(spec, run) == 4.0
    spec = load(BENCH, "layer_metrics", "preempt_claims_rejected_share.json")
    assert metrics_delta.read(spec, run) == 0.0
    run.metrics_pages = {"window": ({}, {})}    # preempt committed nothing
    for name in ("preempt_victims_per_claim",
                 "preempt_claims_rejected_share"):
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert metrics_delta.read(spec, run) is None, name


class HighInFeed(server_mod.Server):
    """The served path with one ``high`` pod named in the eviction feed: a
    scheduler that orders a pod evicted that nobody outranks."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.high = None
        self.planted = False

    def raw(self, method, path, data=None, timeout=120.0):
        if (method, path) == ("POST", "/v1/pods") and self.high is None:
            self.high = next(
                (f"{p['namespace']}/{p['name']}" for p in json.loads(data)
                 if p.get("priority_class") == "high"), None)
        return super().raw(method, path, data, timeout)

    def request(self, method, path, body=None, timeout=120.0):
        resp = super().request(method, path, body, timeout)
        if (path.startswith("/v1/evictions") and resp["evictions"]
                and not self.planted and self.high is not None):
            self.planted = True
            resp["evictions"].append(dict(
                resp["evictions"][-1], pod=self.high))
        return resp


def test_a_high_pod_in_the_feed_is_not_correct(tmp_path, manifest_path,
                                               monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    args = harness.argparse.Namespace(
        workload=CELL, seed=7, seconds=3.0, trace=0, platform="cpu")
    out = tmp_path / "out"
    out.mkdir()
    line = harness.run_cell(args, harness.load_json(manifest_path), str(out),
                            server_factory=HighInFeed)
    printed = capsys.readouterr().out
    assert line["correct"] is False
    assert "gangs_split: 1 <= 0" in printed and "NOT correct" in printed
    # never reported Running, and not outranked by its claimant
    assert "unknown_pods: 2 <= 0" in printed
    for name in ("nodes_over", "double_binds", "overfit_binds", "unbound"):
        assert f"{name}: 0 <= 0" in printed, name


@pytest.fixture(scope="module")
def plans():
    """The window's plan at the cell's own size, for three seeds."""
    config = load(BENCH, "configs", SOURCE + ".json")
    (params,) = load(BENCH, "traffic", "preempt-open.json")["streams"]
    server = types.SimpleNamespace(
        get=lambda path: {"next": 0, "first": 0, "evictions": []},
        send=lambda *a, **k: None)
    out = []
    for seed in (1, 2147484033, 2**31 + 77):
        ctx = types.SimpleNamespace(config=config, ledger=None, server=server,
                                    failure=RuntimeError)
        out.append((ctx, preempt_bursts.Stream(ctx, params, seed, 50.0)))
    return out


def test_the_window_is_the_same_work_for_every_seed(plans):
    orders = set()
    for ctx, stream in plans:
        assert isinstance(ctx.ledger, reference_preempt.Ledger)
        assert len(stream.window) == stream.n == 25
        for pgs, pods, _, _ in stream.window:
            assert len(pgs) == len(pods) == 100
            assert {pg["min_member"] for pg in pgs} == {1}
            assert {pg["queue"] for pg in pgs} == {"default"}
            assert {pg["priority_class"] for pg in pgs} == {"high"}
            assert all(p["requests"] == {"cpu": 3000.0,
                                         "memory": float(500 << 20)}
                       and p["priority_class"] == "high" for p in pods)
        gaps = np.diff(stream.due)
        allowed = (1.0 + 0.2 * np.linspace(-1.0, 1.0, 25)) / 0.5
        assert np.abs(gaps[:, None] - allowed[None, :]).min(axis=1).max() < 1e-9
        assert 1.6 - 1e-9 <= gaps.min() and gaps.max() <= 2.4 + 1e-9
        orders.add(tuple(np.round(gaps, 9)))
    assert len(orders) == 3


def test_traffic_past_the_pool_ends_the_run_before_the_load():
    config = load(BENCH, "configs", SOURCE + ".json")
    (params,) = load(BENCH, "traffic", "preempt-open.json")["streams"]
    server = types.SimpleNamespace(
        get=lambda path: {"next": 0, "first": 0, "evictions": []},
        send=lambda *a, **k: None)
    ctx = types.SimpleNamespace(config=config, ledger=None, server=server,
                                failure=RuntimeError)
    with pytest.raises(RuntimeError, match="4500 of the 5000"):
        preempt_bursts.Stream(ctx, dict(params, pods=120), 1, 50.0)

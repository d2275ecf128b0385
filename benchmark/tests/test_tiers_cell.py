"""``overcommit-50k-5k`` (``borg-tiers-50k-5k`` x ``tiers-open``, 1 chip)
rehearsed at a tiny size through run.py's own entry:
``rehearsal-tiers-1920-192`` keeps the deployment's tiers, shapes and shares
on 192 nodes.  The cell is added to the rehearsal's manifest as
``test_gangmix_cell.py`` adds one, from files alone, together with the seven
per-layer metrics of the evict path (``tiers_manifest.py`` says why
``BENCHMARK.json`` cannot list them yet).  The whole cell runs: the stream,
the kubelet stand-in, the eviction edge round, both controls, every new
metric file read.  A feed that names a production pod under a whole run
comes out ``correct: false``; a program without the feed ends the run before
anything is loaded; the window's plan is the same for every seed."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import reference_tiers
import run as harness
import server as server_mod
import tiers_manifest
from conftest import BENCH, REPO
from rehearsal_manifest import derive
from streams import tier_bursts

CELL, STANDS_FOR = "rehearsal-tiers", tiers_manifest.CELL
CONFIG = "rehearsal-tiers-1920-192"


def load(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest() -> dict:
    full = tiers_manifest.derive()
    out = derive()
    out["configs"].append({
        "name": CONFIG, "file": f"benchmark/configs/{CONFIG}.json"})
    out["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "rehearsal-tiers",
        "chips": 1})
    for section in ("end_to_end", "per_layer"):
        for tiny, accepted in zip(out[section], full[section]):
            # a metric with no list is reported in every cell
            if STANDS_FOR in accepted.get("workloads", [STANDS_FOR]):
                tiny["workloads"].append(CELL)
    out["per_layer"] += tiers_manifest.entries([CELL])
    return out


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("tiers") / "manifest.json"
    path.write_text(json.dumps(manifest()))
    return str(path)


@pytest.fixture(scope="module")
def lines(tmp_path_factory, manifest_path):
    tmp = tmp_path_factory.mktemp("tiers-out")
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
        "borg-tiers-50k-5k", "tiers-open", 1)
    entry = next(c for c in full["configs"]
                 if c["name"] == "borg-tiers-50k-5k")
    config = load(REPO, entry["file"])
    assert entry["reduced"] == config["reduced"] == []
    assert config["architecture"] is None
    baseline = load(BENCH, "configs", "baseline-50k-5k.json")
    for key in ("nodes", "node", "queues"):     # the baseline's cluster
        assert config[key] == baseline[key], key
    assert config["population"] == {"kind": "tiers", "pods": 50000}
    tiers = {t["name"]: t for t in config["tiers"]}
    assert sorted(tiers, key=lambda n: -tiers[n]["priority"]) == [
        "production", "mid", "beb", "free"]
    gib = 1 << 30
    assert (tiers["production"]["cpu_milli"],
            tiers["production"]["memory_bytes"]) == ([8000], [32 * gib])
    assert (tiers["mid"]["cpu_milli"],
            tiers["mid"]["memory_bytes"]) == ([4000], [16 * gib])
    for name in ("production", "mid"):          # the gang plugin's veto
        assert (tiers[name]["size"], tiers[name]["min_member"]) == (4, 4)
    for name in ("beb", "free"):                # evictable task by task
        t = tiers[name]
        assert (t["size_min"], t["size_max"], t["min_member"]) == (8, 32, 1)
        assert t["cpu_milli"] == [1000, 2000, 4000]
        assert t["memory_bytes"] == [2 * gib, 4 * gib, 8 * gib]
    assert config["assumed"] and config["from_source"]
    for promise in ("no production or mid pod ever evicted",
                    "no eviction without a covered placement",
                    "every pod bound at most once"):
        assert config["guarantees"][promise] is True
    assert config["control"] == {"placement": "stale", "edge": "bfloat16"}
    # every quantity of the deployment is exact in float32
    for v in (*config["node"].values(),
              *(x for t in tiers.values()
                for x in (*t["cpu_milli"], *t["memory_bytes"]))):
        assert int(np.float32(v)) == v
    (stream,) = load(BENCH, "traffic", "tiers-open.json")["streams"]
    assert (stream["kind"], stream["rate"], stream["gangs"],
            stream["jitter"], stream["standin_period_ms"],
            stream["edge_rounds"]) == ("tier_bursts", 0.5, 25, 0.2, 10, 12)
    # the sized bursts (the pending rungs), the plain ones and the window
    # stay inside the ~51 bursts' worth that the evictable pods can make
    # room for (PERF.md section 4), and past the cluster's first ~20
    assert stream["warm_sizes"] == [2, 4]
    assert 20 <= sum(stream["warm_sizes"]) + stream["min_warm_bursts"]
    assert stream["min_warm_bursts"] <= stream["max_warm_bursts"]
    assert sum(stream["warm_sizes"]) + stream["max_warm_bursts"] + 25 <= 48
    assert "edge_check" not in load(BENCH, "traffic", "tiers-open.json")
    # the cell reports what skew-36k-5k reports, end to end and per layer
    def reports(name):
        return {m["name"] for m in full["end_to_end"] + full["per_layer"]
                if name in m.get("workloads", [name])}
    assert reports(STANDS_FOR) == reports("skew-36k-5k")
    assert {"decision_p50_ms", "decision_p90_ms", "setup_s"} <= reports(
        STANDS_FOR)


def test_the_population_is_the_configuration_s_at_full_size():
    config = load(BENCH, "configs", "borg-tiers-50k-5k.json")
    tiers: dict = {}
    cpu: dict = {}
    ledger = reference_tiers.Ledger(config, 2**31 + 5)
    pgs, pods = ledger.make_population()
    ledger.add(pgs, pods)
    for key, (c, _, _) in ledger.pods.items():
        tiers[ledger.tier[key]] = tiers.get(ledger.tier[key], 0) + 1
        cpu[ledger.tier[key]] = cpu.get(ledger.tier[key], 0) + c
    assert len(pods) == 50000
    assert (tiers["production"], tiers["mid"]) == (6000, 2000)
    total = int(ledger.alloc[:, 0].sum())
    assert cpu["production"] / total == 0.30 and cpu["mid"] / total == 0.05
    assert 0.90 <= sum(cpu.values()) / total <= 0.98
    assert abs(tiers["beb"] - tiers["free"]) < 2000
    # 40 warm-up bursts, the window's 25 and the edge round find victims:
    # the low tiers hold more memory than 65 bursts of claims ask to cover
    low_mem = sum(m for k, (_, m, _) in ledger.pods.items()
                  if ledger.tier[k] in ("beb", "free"))
    assert low_mem > 65 * 100 * (32 << 30) // 2
    sizes = [len(ms) for ms, pg, _ in ledger.gangs.values()
             if pg["priority_class"] in ("beb", "free")]
    assert min(sizes) >= 1 and max(sizes) <= 32
    assert {pg["min_member"] for pg in pgs
            if pg["priority_class"] in ("beb", "free")} == {1}


def test_the_rehearsal_is_correct_and_reads_the_seven(lines):
    for trace in ("0", "1"):
        line, stdout = lines[trace]
        assert line["correct"] is True and line["failed"] == 0, stdout[-3000:]
        assert line["attempted"] == 6
        assert line["metrics"] == {}     # no CPU number under a device name
        notes = json.loads(next(
            ln for ln in stdout.splitlines() if ln.startswith("notes: "))[7:])
        # the edge round: every exact pod bound, no over pod, no eviction
        # for one; both controls wrong
        assert notes["edge_rounds"] + notes["edge_skipped"] == 4
        assert notes["edge_rounds"] >= 2
        assert notes["edge_evictions"] > 0
        assert notes["edge_evictions_for_over_pods"] == 0
        assert notes["edge_reference"] == {"unbound": 0, "overfit_binds": 0}
        control = notes["control_edge"]
        assert control["unbound"] + control["overfit_binds"] > 0
        assert notes["control_place_exact"] == {"nodes_over": 0}
        assert notes["control_place_stale"]["nodes_over"] >= 1
        # the window was decided by the evict path, and never twice
        assert notes["window_claims_committed"]["reclaim"] > 0
        assert notes["window_repeat_claims"]["in_flight"] == 0
        assert notes["window_jit_compiles"] >= 0    # every compile JAX saw
        assert notes["window_on_released_share"] >= 0.75
        assert notes["drained"] is True
    got = lines["0"][0]["rehearsal"]
    for name in ("cpu_decision_p50_ms", "cpu_decision_p90_ms", "cpu_setup_s"):
        assert got[name]["value"] > 0, name
    got = lines["1"][0]["rehearsal"]
    assert got["cpu_evict_solves_per_cycle"]["value"] > 0
    assert got["cpu_evict_replay_ms"]["value"] > 0
    assert got["cpu_evictions_per_claim"]["value"] >= 1.0
    assert got["cpu_evict_repeat_claims"]["value"] == 0.0
    assert got["cpu_eviction_release_ms"]["value"] > 0
    assert 0.0 <= got["cpu_evict_claims_rejected_share"]["value"] <= 1.0
    for name in ("cpu_host_replay_ms", "cpu_solve_dispatches_per_cycle",
                 "cpu_compiles_in_window", "cpu_device_wait_ms",
                 "cpu_park_floor_ms", "cpu_generator_late_ms"):
        assert name in got, name


def test_a_program_without_the_series_reports_nothing_and_does_not_raise():
    """The parent has none of the evict series and no ``evict_replay`` span:
    each reader finds nothing and the line leaves the metric out."""
    from readers import metrics_delta, profile_programs, span_totals

    run = types.SimpleNamespace(
        metrics_pages={"window": ({}, {}), "profile": ({}, {})},
        span_seconds={"window": 50.0, "profile": 4.0},
        trace_states=({"span_ms": {}, "span_counts": {}},
                      {"span_ms": {"device_wait": 5.0},
                       "span_counts": {"device_wait": 1}}),
        profile={"device_planes": ["/device:TPU:0"], "programs": [
            ["jit_fused", 0.2], ["jit_scatter", 0.01]]})
    for name in ("evict_solves_per_cycle", "evictions_per_claim",
                 "eviction_release_ms", "evict_claims_rejected_share"):
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert metrics_delta.read(spec, run) is None, name
    assert span_totals.read(
        load(BENCH, "layer_metrics", "evict_replay_ms.json"), run) is None
    spec = load(BENCH, "layer_metrics", "evict_device_ms_per_solve.json")
    assert profile_programs.read(spec, run) is None     # no evict dispatch
    run.profile = None
    assert profile_programs.read(spec, run) is None     # no trace


def test_the_evict_programs_are_read_by_name_per_dispatch():
    from readers import profile_programs

    spec = load(BENCH, "layer_metrics", "evict_device_ms_per_solve.json")
    key = ("volcano_solve_dispatches_total",
           'action="reclaim",mode="single",program="evict"')
    run = types.SimpleNamespace(
        metrics_pages={"profile": ({key: 10.0}, {key: 14.0})},
        span_seconds={"profile": 4.0},
        profile={"device_planes": ["/device:TPU:0"], "programs": [
            ["jit_evict_sentinel_solve", 0.6], ["jit_fused", 0.2]]})
    assert profile_programs.read(spec, run) == pytest.approx(150.0)


class ProtectedInFeed(server_mod.Server):
    """The served path with one production pod of the load named in the
    eviction feed: a scheduler that orders a protected pod evicted."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.production = None
        self.planted = False

    def raw(self, method, path, data=None, timeout=120.0):
        if (method, path) == ("POST", "/v1/pods") and self.production is None:
            self.production = next(
                f"{p['namespace']}/{p['name']}" for p in json.loads(data)
                if p.get("priority_class") == "production")
        return super().raw(method, path, data, timeout)

    def request(self, method, path, body=None, timeout=120.0):
        resp = super().request(method, path, body, timeout)
        if (path.startswith("/v1/evictions") and resp["evictions"]
                and not self.planted):
            self.planted = True
            resp["evictions"].append(dict(
                resp["evictions"][-1], pod=self.production))
        return resp


def test_a_protected_pod_in_the_feed_is_not_correct(tmp_path, manifest_path,
                                                    monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    args = harness.argparse.Namespace(
        workload=CELL, seed=7, seconds=3.0, trace=0, platform="cpu")
    out = tmp_path / "out"
    out.mkdir()
    line = harness.run_cell(args, harness.load_json(manifest_path), str(out),
                            server_factory=ProtectedInFeed)
    printed = capsys.readouterr().out
    assert line["correct"] is False
    # ordered evicted, and (the stand-in deleted it) its gang bound short
    assert "gangs_split: 2 <= 0" in printed and "NOT correct" in printed
    for name in ("nodes_over", "double_binds", "overfit_binds",
                 "unknown_pods"):
        assert f"{name}: 0 <= 0" in printed, name


class NoFeed(server_mod.Server):
    """The parent: ``/v1/evictions`` is not a path it knows."""

    def raw(self, method, path, data=None, timeout=120.0):
        if path.startswith("/v1/evictions"):
            return 404, b'{"error": "not found"}'
        return super().raw(method, path, data, timeout)


def test_a_program_without_the_feed_ends_the_run_before_the_load(
        tmp_path, manifest_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    args = harness.argparse.Namespace(
        workload=CELL, seed=7, seconds=3.0, trace=0, platform="cpu")
    out = tmp_path / "out"
    out.mkdir()
    posted = []

    class Watched(NoFeed):
        def raw(self, method, path, data=None, timeout=120.0):
            posted.append((method, path))
            return super().raw(method, path, data, timeout)

    with pytest.raises(server_mod.RunFailure, match="/v1/evictions"):
        harness.run_cell(args, harness.load_json(manifest_path), str(out),
                         server_factory=Watched)
    assert not any(method == "POST" for method, _ in posted)


@pytest.fixture(scope="module")
def plans():
    """The window's plan at the cell's own size, for three seeds."""
    config = load(BENCH, "configs", "borg-tiers-50k-5k.json")
    (params,) = load(BENCH, "traffic", "tiers-open.json")["streams"]
    server = types.SimpleNamespace(
        get=lambda path: {"next": 0, "first": 0, "evictions": []},
        send=lambda *a, **k: None)
    out = []
    for seed in (1, 2147484033, 2**31 + 77):
        ctx = types.SimpleNamespace(config=config, ledger=None, server=server,
                                    failure=RuntimeError)
        out.append((ctx, tier_bursts.Stream(ctx, params, seed, 50.0)))
    return out


def test_the_window_is_the_same_work_for_every_seed(plans):
    orders = set()
    for ctx, stream in plans:
        assert isinstance(ctx.ledger, reference_tiers.Ledger)
        assert len(stream.window) == stream.n == 25
        for pgs, pods, _, _ in stream.window:
            assert len(pgs) == 25 and len(pods) == 100
            assert {pg["min_member"] for pg in pgs} == {4}
            assert [pg["queue"] for pg in pgs] == [
                f"q{g % 3}" for g in range(25)]
            assert all(p["requests"] == {"cpu": 8000.0,
                                         "memory": float(32 << 30)}
                       and p["priority_class"] == "production" for p in pods)
        # gaps from the one set of 25 (1.6-2.4 s), in seeded order
        gaps = np.diff(stream.due)
        allowed = (1.0 + 0.2 * np.linspace(-1.0, 1.0, 25)) / 0.5
        assert np.abs(gaps[:, None] - allowed[None, :]).min(axis=1).max() < 1e-9
        assert 1.6 - 1e-9 <= gaps.min() and gaps.max() <= 2.4 + 1e-9
        orders.add(tuple(np.round(gaps, 9)))
    assert len(orders) == 3

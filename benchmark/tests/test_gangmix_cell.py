"""``skew-36k-5k`` (``philly-36k-5k`` x ``gangmix-open``, 1 chip) rehearsed
at a tiny size through run.py's own entry: ``rehearsal-gangmix-690-96``
keeps the deployment's 8-GPU nodes, 90% occupancy and 14 Zipf queues on 96
nodes, with gangs of 1-64.  The cell is added to the rehearsal's manifest
as ``test_envelope_cell.py`` adds one, from files alone, together with the
four per-layer metrics of the gang mix (``gangmix_manifest.py`` says why
``BENCHMARK.json`` cannot list them yet).  A GPU overcommit planted under a
whole run comes out ``correct: false``; the window's plan is the same
multiset for every seed, dealt so that no burst holds two large gangs."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import gangmix_manifest
import reference_gangmix
import run as harness
import server as server_mod
from conftest import BENCH, REPO
from rehearsal_manifest import derive
from streams import gangmix_bursts

CELL, STANDS_FOR = "rehearsal-gangmix", gangmix_manifest.CELL
CONFIG = "rehearsal-gangmix-690-96"


def load(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest() -> dict:
    full = gangmix_manifest.derive()
    out = derive()
    out["configs"].append({
        "name": CONFIG, "file": f"benchmark/configs/{CONFIG}.json"})
    out["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "rehearsal-gangmix",
        "chips": 1})
    for section in ("end_to_end", "per_layer"):
        for tiny, accepted in zip(out[section], full[section]):
            # a metric with no list is reported in every cell
            if STANDS_FOR in accepted.get("workloads", [STANDS_FOR]):
                tiny["workloads"].append(CELL)
    out["per_layer"] += gangmix_manifest.entries([CELL])
    return out


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("gangmix") / "manifest.json"
    path.write_text(json.dumps(manifest()))
    return str(path)


@pytest.fixture(scope="module")
def lines(tmp_path_factory, manifest_path):
    tmp = tmp_path_factory.mktemp("gangmix-out")
    out = {}
    for trace in ("0", "1"):
        got = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"),
             "--manifest", manifest_path, "--workload", CELL,
             "--seed", "2147484031", "--seconds", "5", "--trace", trace,
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
        "philly-36k-5k", "gangmix-open", 1)
    entry = next(c for c in full["configs"] if c["name"] == "philly-36k-5k")
    config = load(REPO, entry["file"])
    assert entry["reduced"] == config["reduced"] == []
    assert (config["nodes"], config["node"]["gpu_milli"],
            config["population"]["pods"], config["chips"]) == (
                5000, 8000, 36000, 1)
    assert len(config["queues"]) == 14 and config["queue_skew"] == 1.0
    assert {q["weight"] for q in config["queues"]} == {1}
    assert config["gang"]["sizes"] == [1, 2, 4, 8, 16, 32, 64, 128]
    assert config["request_mix"]["gpu_milli"] == [1000]
    assert any("scale" in line for line in config["assumed"])
    assert config["from_source"] and config["guarantees"]
    assert config["control"] == {"placement": "stale", "whatif": "bfloat16",
                                 "edge": "bfloat16"}
    # every quantity of the deployment is exact in float32
    node, mix = config["node"], config["request_mix"]
    for v in (*node.values(), *mix["cpu_milli"], *mix["memory_bytes"]):
        assert int(np.float32(v)) == v
    # eight pods never exceed a node's CPU or memory: the GPU binds
    assert 8 * max(mix["cpu_milli"]) <= node["cpu_milli"]
    assert 8 * max(mix["memory_bytes"]) <= node["memory_bytes"]
    (stream,) = load(BENCH, "traffic", "gangmix-open.json")["streams"]
    assert (stream["kind"], stream["rate"], stream["gangs"],
            stream["jitter"], stream["warm_audits"]) == (
                "gangmix_bursts", 1, 25, 0.2, 1)
    # one step of the program's job axis, grown in set-up (api/snapshot.py)
    assert stream["stretch_gangs"] == 1024
    # the cell reports what steady-150k-5k's lists let it, and both modes
    reports = [m["name"] for m in full["end_to_end"] + full["per_layer"]
               if STANDS_FOR in m.get("workloads", [STANDS_FOR])]
    assert {"decision_p50_ms", "decision_p90_ms", "setup_s",
            "host_replay_ms", "compiles_in_window"} <= set(reports)
    assert len(reports) == 19


def test_the_rehearsal_is_correct_and_reads_the_four(lines):
    for trace in ("0", "1"):
        line, stdout = lines[trace]
        assert line["correct"] is True and line["failed"] == 0, stdout[-3000:]
        assert line["attempted"] == 10
        assert line["metrics"] == {}     # no CPU number under a device name
        notes = json.loads(next(
            ln for ln in stdout.splitlines() if ln.startswith("notes: "))[7:])
        assert notes["compiles_in_window"] == 0
        assert notes["control_edge"]["unbound"] > 0
        assert notes["control_edge"]["overfit_binds"] > 0
        assert set(notes["solve_dispatches"]) <= {
            "single", "single+topk", "single+topk+warm"}
        assert notes["solve_dispatches"]["single+topk+warm"] >= 10
        assert notes["large_bursts"]["n"] == 1
        assert notes["large_bursts"]["pods_max"] >= 64
    got = lines["0"][0]["rehearsal"]
    for name in ("cpu_decision_p50_ms", "cpu_decision_p90_ms", "cpu_setup_s"):
        assert got[name]["value"] > 0, name
    got = lines["1"][0]["rehearsal"]
    assert 1.0 <= got["cpu_solve_rounds_per_solve"]["value"] <= 18.0
    assert 0.0 <= got["cpu_solve_over_budget_share"]["value"] <= 1.0
    assert got["cpu_topk_exhausted_per_solve"]["value"] >= 0.0
    assert got["cpu_gang_decision_ms.large"]["value"] > 0
    for name in ("cpu_host_replay_ms", "cpu_solve_dispatches_per_cycle",
                 "cpu_compiles_in_window", "cpu_device_wait_ms",
                 "cpu_park_floor_ms"):
        assert name in got, name


def test_a_program_without_the_series_reports_nothing_and_does_not_raise():
    """The parent has no ``volcano_topk_*`` and no gang clock: the reader
    finds no growth in the denominator and the line leaves the metric out."""
    from readers import metrics_delta

    spec = load(BENCH, "layer_metrics", "gang_decision_ms.large.json")
    run = types.SimpleNamespace(
        metrics_pages={"window": ({}, {})}, span_seconds={"window": 50.0})
    assert metrics_delta.read(spec, run) is None
    # and where the program has them but no gang of 64 was bound
    key = ("volcano_gang_decision_latency_milliseconds_count",
           'size_class="64+"')
    run.metrics_pages["window"] = ({key: 3.0}, {key: 3.0})
    assert metrics_delta.read(spec, run) is None


class NinthGpu(server_mod.Server):
    """The served path with one one-GPU pod reported on a node whose eight
    GPUs are taken: a capacity plane that reads a GPU too few in use."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.gpu_pods: set = set()

    def raw(self, method, path, data=None, timeout=120.0):
        if (method, path) == ("POST", "/v1/pods"):
            self.gpu_pods.update(
                f"{p['namespace']}/{p['name']}" for p in json.loads(data)
                if p["requests"].get(reference_gangmix.GPU))
        return super().raw(method, path, data, timeout)

    def request(self, method, path, body=None, timeout=120.0):
        resp = super().request(method, path, body, timeout)
        if path == "/v1/bindings":
            per_node: dict = {}
            for row in resp:
                if row["pod"] in self.gpu_pods:
                    per_node[row["node"]] = per_node.get(row["node"], 0) + 1
            full = max(per_node, key=per_node.get)
            assert per_node[full] == 8
            next(row for row in resp if row["pod"] in self.gpu_pods
                 and row["node"] != full)["node"] = full
        return resp


def test_a_gpu_overcommit_is_not_correct(tmp_path, manifest_path,
                                         monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    args = harness.argparse.Namespace(
        workload=CELL, seed=7, seconds=3.0, trace=0, platform="cpu")
    out = tmp_path / "out"
    out.mkdir()
    line = harness.run_cell(args, harness.load_json(manifest_path), str(out),
                            server_factory=NinthGpu)
    printed = capsys.readouterr().out
    assert line["correct"] is False
    assert "nodes_over: 1 <= 0" in printed and "NOT correct" in printed
    # nothing else is over: the GPU column alone decided it
    for name in ("unbound", "gangs_split", "double_binds", "overfit_binds"):
        assert f"{name}: 0 <= 0" in printed, name


@pytest.fixture(scope="module")
def plans():
    """The window's plan at the cell's own size, for three seeds."""
    config = load(BENCH, "configs", "philly-36k-5k.json")
    (params,) = load(BENCH, "traffic", "gangmix-open.json")["streams"]
    out = []
    for seed in (1, 2147484033, 2**31 + 77):
        ctx = types.SimpleNamespace(config=config, ledger=None,
                                    failure=RuntimeError)
        stream = gangmix_bursts.Stream(ctx, params, seed, 50.0)
        out.append((ctx, stream))
    return out


def test_the_window_is_one_multiset_for_every_seed(plans):
    sizes = [1, 2, 4, 8, 16, 32, 64, 128]
    zipf = reference_gangmix.counts(
        1250, reference_gangmix.zipf_shares(14, 1.0))
    assert zipf[0] == 384 and zipf.sum() == 1250    # the hottest asks 31%
    orders = set()
    for ctx, stream in plans:
        assert isinstance(ctx.ledger, reference_gangmix.Ledger)
        assert len(stream.plan) == stream.n == 50
        dealt = [s for burst, _ in stream.plan for s in burst]
        assert [dealt.count(s) for s in sizes] == [
            750, 150, 150, 125, 38, 25, 8, 4]
        assert sum(dealt) == 5082
        queues = [q for _, qs in stream.plan for q in qs]
        assert [queues.count(q) for q in ctx.ledger.queue_order] == list(zipf)
        # rendered as dealt: 25 gangs a burst, every member one GPU
        for (burst, qs), (pgs, pods, _, _) in zip(stream.plan, stream.window):
            assert len(burst) == len(pgs) == 25 and len(pods) == sum(burst)
            assert [pg["min_member"] for pg in pgs] == burst
            assert [pg["queue"] for pg in pgs] == qs
            assert all(p["requests"][reference_gangmix.GPU] == 1000.0
                       for p in pods)
        # gaps from the one set of 50 (0.8-1.2 s), in seeded order
        gaps = np.diff(stream.due)
        allowed = 1.0 + 0.2 * np.linspace(-1.0, 1.0, 50)
        assert np.abs(gaps[:, None] - allowed[None, :]).min(axis=1).max() < 1e-9
        assert len(set(np.round(gaps, 9))) == 49
        orders.add(tuple(dealt))
    assert len(orders) == 3


def test_no_burst_holds_two_gangs_of_64_or_more(plans):
    for _, stream in plans:
        large = [sum(1 for s in burst if s >= 64) for burst, _ in stream.plan]
        assert max(large) == 1 and sum(large) == 12
        pods = [sum(burst) for burst, _ in stream.plan]
        assert min(pods) >= 25 and max(pods) <= 128 + 24 * 32


def test_deletes_cover_posts_and_occupancy_does_not_drift(plans):
    ctx, stream = plans[0]
    ledger = ctx.ledger
    ledger.add(*ledger.make_population())
    live = len(ledger.pods)
    assert live == 36000
    for pgs, pods, _, _ in stream.window:
        old_pgs, old_pods = ledger.oldest_covering(len(pods))
        ledger.retire(old_pgs, old_pods)
        ledger.add(pgs, pods)
        # at least what was posted has been freed, at most one gang more
        assert live - 128 < len(ledger.pods) <= live

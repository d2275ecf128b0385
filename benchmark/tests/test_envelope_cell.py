"""``steady-150k-5k`` (``k8s-envelope-150k-5k`` x ``churn-open``, 4 chips)
rehearsed at a tiny size on four virtual CPU devices, through run.py's own
entry: ``rehearsal-envelope-4800-160`` keeps the deployment's node size, 30
pods a node and gangs of 4 on 160 nodes, which pad to the 256 at which the
program turns its mesh on.  The cell is added to the rehearsal's manifest as
``test_extend.py`` adds one, from files alone, together with the five
per-layer metrics of the sharded path whose files this directory holds
(``BENCHMARK.json`` cannot list them yet: ``test_span_plane.py`` pins PR
24's fourteen entries as the manifest's last; PERF.md section 7)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO
from rehearsal_manifest import derive

CELL, STANDS_FOR = "rehearsal-envelope", "steady-150k-5k"
LAYER = "resident cache + device solves"
#: name -> (unit, better, source, layer, moves)
SHARDED_PATH = {
    "sharded_dispatch_share": (
        "share", "higher", "program_counter", LAYER, "decision_p50_ms"),
    "cold_solves_in_window": (
        "count", "lower", "program_counter", LAYER, "decision_p90_ms"),
    "solve_dispatch_ms.sharded": (
        "ms", "lower", "program_span", LAYER, "decision_p50_ms"),
    "device_wait_ms.sharded": (
        "ms", "lower", "program_span", LAYER, "decision_p50_ms"),
    "collective_ms_per_cycle": (
        "ms", "lower", "device_trace", "device", "decision_p50_ms"),
}


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        full = json.load(f)
    out = derive()
    out["configs"].append({
        "name": "rehearsal-envelope-4800-160",
        "file": "benchmark/configs/rehearsal-envelope-4800-160.json"})
    out["workloads"].append({
        "name": CELL, "config": "rehearsal-envelope-4800-160",
        "traffic": "rehearsal-churn", "chips": 4})
    for section in ("end_to_end", "per_layer"):
        for tiny, accepted in zip(out[section], full[section]):
            # a metric with no list is reported in every cell
            if STANDS_FOR in accepted.get("workloads", [STANDS_FOR]):
                tiny["workloads"].append(CELL)
    out["per_layer"] += [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": moves, "workloads": [CELL]}
        for name, (unit, better, source, layer, moves) in SHARDED_PATH.items()]
    return out


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("envelope")
    path = tmp / "manifest.json"
    path.write_text(json.dumps(manifest()))
    out = {}
    for trace in ("0", "1"):
        got = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"),
             "--manifest", str(path), "--workload", CELL,
             "--seed", "2147484027", "--seconds", "6", "--trace", trace,
             "--platform", "cpu", "--out", str(tmp / "out")],
            cwd=REPO, capture_output=True, text=True, timeout=900,
            env=dict(os.environ, XLA_FLAGS=(
                "--xla_force_host_platform_device_count=4")))
        assert got.returncode == 0, got.stderr[-2000:]
        out[trace] = (json.loads(got.stdout.strip().splitlines()[-1]),
                      got.stdout)
    return out


def test_the_cell_and_its_configuration_are_in_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        full = json.load(f)
    cell = next(w for w in full["workloads"] if w["name"] == STANDS_FOR)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "k8s-envelope-150k-5k", "churn-open", 4)
    with open(os.path.join(BENCH, "configs",
                           "k8s-envelope-150k-5k.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "configs", "baseline-50k-5k.json")) as f:
        baseline = json.load(f)
    assert (config["nodes"], config["population"]["pods"],
            config["node"]["pods"], config["chips"]) == (5000, 150000, 110, 4)
    assert config["reduced"] == []
    # the baseline's request mix, gangs, queues, guarantees and controls
    for key in ("request_mix", "gang", "queues", "guarantees", "control"):
        assert config[key] == baseline[key], key
    # a node three times the baseline's, for three times the pods
    assert config["node"]["cpu_milli"] == 3 * baseline["node"]["cpu_milli"]
    assert (config["node"]["memory_bytes"]
            == 3 * baseline["node"]["memory_bytes"])


def test_the_rehearsal_is_correct_on_four_devices(lines):
    for trace in ("0", "1"):
        line, stdout = lines[trace]
        assert line["correct"] is True and line["failed"] == 0, stdout[-3000:]
        assert line["device"] == dict(line["device"], platform="cpu", count=4)
        assert line["metrics"] == {}     # no CPU number under a device name
    line, stdout = lines["0"]
    for name in ("cpu_decision_p50_ms", "cpu_decision_p90_ms", "cpu_setup_s"):
        assert line["rehearsal"][name]["value"] > 0, name
    notes = json.loads(next(
        ln for ln in stdout.splitlines() if ln.startswith("notes: "))[7:])
    assert notes["solve_dispatches"]
    assert all(key.startswith("sharded+shard_map")
               for key in notes["solve_dispatches"]), notes["solve_dispatches"]


def test_the_sharded_path_metrics_are_reported(lines):
    got = lines["1"][0]["rehearsal"]
    assert got["cpu_sharded_dispatch_share"]["value"] == 1.0
    assert got["cpu_cold_solves_in_window"]["value"] == 0.0
    assert got["cpu_solve_dispatch_ms.sharded"]["value"] > 0
    assert got["cpu_device_wait_ms.sharded"]["value"] > 0
    # the CPU's trace shows no device plane: the reader gives nothing there
    assert "cpu_collective_ms_per_cycle" not in got
    for name in ("cpu_cycles_rate", "cpu_cold_drain_s", "cpu_allocate_ms",
                 "cpu_solve_dispatches_per_cycle", "cpu_compiles_in_window"):
        assert name in got, name


def test_profile_ops_sums_the_collectives_of_the_top_ten():
    import types

    from readers import profile_ops

    spec = {"match": ["all-reduce", "all-gather", "collective-permute"],
            "scale": 1000.0}
    profile = {"device_planes": ["/device:TPU:0"], "device_ops": [
        ["%fusion.3", 0.5], ["%all-gather.16", 0.002],
        ["%all-reduce-start.1", 0.001], ["%copy.2", 0.1]]}
    run = types.SimpleNamespace(profile=profile, metrics_pages={},
                                span_seconds={})
    assert profile_ops.read(spec, run) == pytest.approx(3.0)
    count = ("volcano_cycle_stage_latency_milliseconds_count",
             'stage="session_open"')
    run.metrics_pages["profile"] = ({count: 10.0}, {count: 16.0})
    assert profile_ops.read(dict(spec, per=[list(count)]), run) == (
        pytest.approx(0.5))
    # no collective among the ten: 0, not nothing
    profile["device_ops"] = [["%fusion.3", 0.5]]
    assert profile_ops.read(spec, run) == 0.0
    # no device plane (a CPU trace), or no trace: nothing
    profile["device_planes"] = []
    assert profile_ops.read(spec, run) is None
    run.profile = None
    assert profile_ops.read(spec, run) is None

"""BENCHMARK.json against the contract it is checked by, and against the
files the harness resolves its names to."""

import json
import os
import re

import pytest

from conftest import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_lengths(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m["name"]
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_name_resolves_to_its_file(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        with open(os.path.join(REPO, configs[w["config"]]["file"])) as f:
            config = json.load(f)
        assert config["chips"] == w["chips"]
        assert config["reduced"] == configs[w["config"]]["reduced"]
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        for stream in mix["streams"]:
            assert os.path.exists(os.path.join(
                BENCH, "streams", stream["kind"] + ".py"))
    for section, folder in (("end_to_end", "end_to_end"),
                            ("per_layer", "layer_metrics")):
        for m in manifest[section]:
            with open(os.path.join(BENCH, folder, m["name"] + ".json")) as f:
                spec = json.load(f)
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py")), m["name"]


def test_each_cell_reports_what_the_contract_asks(manifest):
    cells = [w["name"] for w in manifest["workloads"]]

    def reported(metric):
        return metric.get("workloads", cells)

    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for cell in cells:
        assert cell in reported(e2e["setup_s"])
        assert any(cell in reported(m) for n, m in e2e.items()
                   if n != "setup_s")
        assert any(cell in reported(m) for m in manifest["per_layer"])
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for cell in reported(m):
            assert cell in cells
            assert cell in reported(e2e[m["moves"]]), (m["name"], cell)
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)

"""The per-layer metrics of the interruption ledger (PR 37): the twelve
entries ``interruptions_manifest.py`` derives and their files, the
``cycle_table`` reader on a recorded pair of ``/v1/trace`` states, and the
CPU rehearsal through run.py's own entry, in which every one of them
reports a number and a full garbage collection planted in the server shows
in ``gc_full_collections_in_window``."""

import json
import os
import subprocess
import sys
import types

import pytest

import interruptions_manifest
from conftest import BENCH, HERE, REPO
from readers import cycle_table
from rehearsal_manifest import CELLS, derive

NINE = list(interruptions_manifest.INTERRUPTIONS)
TWINS = [n + ".read" for n in interruptions_manifest.FOR_THE_READ_PLANE]

#: the server child collects everything once a second from its start
#: (site imports this before the program's entry point runs)
PLANT = '''
import gc, sys, threading, time
def _collect():
    while True:
        time.sleep(1.0)
        gc.collect(2)
if any(a.endswith("serve.py") for a in sys.orig_argv):
    threading.Thread(target=_collect, name="planted-gc", daemon=True).start()
'''


def manifest() -> dict:
    """The rehearsal's manifest with the twelve, each over the tiny cells
    that stand for the cells it is reported in."""
    out = derive()
    for e in interruptions_manifest.derive()["per_layer"][-12:]:
        out["per_layer"].append(dict(e, workloads=[
            tiny for tiny, (cell, _, _) in CELLS.items()
            if cell in e["workloads"]]))
    return out


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("interruptions") / "manifest.json"
    path.write_text(json.dumps(manifest()))
    return str(path)


def rehearse(tmp_path, command, manifest_path, workload, trace):
    plant = tmp_path / "plant"
    plant.mkdir()
    (plant / "sitecustomize.py").write_text(PLANT)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=os.pathsep.join(
                   [str(plant), os.environ.get("PYTHONPATH", "")]))
    got = subprocess.run(
        [sys.executable, *command, "--manifest", manifest_path,
         "--workload", workload, "--seed", "2147484037", "--seconds", "6",
         "--trace", trace, "--platform", "cpu",
         "--out", str(tmp_path / "out")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert got.returncode == 0, got.stderr[-2000:]
    lines = got.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_the_twelve_entries_and_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        accepted = json.load(f)
    derived = interruptions_manifest.derive()
    # nothing the benchmark has is touched; the twelve come after it
    assert derived["per_layer"][:-12] == accepted["per_layer"]
    for key in accepted:
        if key != "per_layer":
            assert derived[key] == accepted[key], key
    added = derived["per_layer"][-12:]
    assert [e["name"] for e in added] == NINE + TWINS
    deciding = ["steady-50k-5k", "kubemark-3k-100", "steady-150k-5k",
                "skew-36k-5k", "overcommit-50k-5k"]
    end_to_end = {m["name"]: m for m in accepted["end_to_end"]}
    layers = {m["layer"] for m in accepted["per_layer"]}
    for e in added:
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["layer"] == "loop" and e["layer"] in layers
        assert e["workloads"] == (
            ["whatif-50k-5k"] if e["name"].endswith(".read") else deciding)
        # every cell that reports it reports the metric it moves
        assert set(e["workloads"]) <= set(end_to_end[e["moves"]]["workloads"])
        assert e["better"] == ("higher" if e["name"] ==
                               "slowest_decision_named_share" else "lower")
        with open(os.path.join(BENCH, "layer_metrics",
                               e["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
        assert e["source"] == ("program_span" if spec["reader"] in (
            "span_totals", "cycle_table") else "program_counter")
    # a twin reads what its original reads
    for name in TWINS:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            twin = f.read()
        with open(os.path.join(BENCH, "layer_metrics",
                               name[:-len(".read")] + ".json")) as f:
            assert f.read() == twin


def row(cycle, worst_ms=None, named_ms=None, **more):
    return dict({"cycle": cycle, "worst_ms": worst_ms,
                 "worst_named_ms": named_ms, "decided": 100 if worst_ms else 0},
                **more)


#: a window of cycles 42-400 read from a ring of 256: the slow burst of
#: cycle 120 left the ring and is in the kept list alone; cycle 30 was kept
#: before the window opened; a stall still waits for its cycle
#: (cycle 41 was running when the window opened: a warm-up burst's)
BEFORE = {"cycles": [row(c, 60.0, 60.0) for c in range(1, 41)],
          "kept": [row(30, 2000.0, 100.0, why=["slow"])], "next_cycle": 42}
RING = {c: row(c, 200.0 + c % 7, 190.0) if c % 3 else row(c)
        for c in range(145, 401)}
RING[310] = row(310, 206.5, 250.0, compile_ms=31.0)
AFTER = {
    "cycles": list(RING.values()),
    "kept": [row(30, 2000.0, 100.0, why=["slow"]),
             row(41, 1500.0, 1500.0, why=["slow"]),
             row(120, 900.0, 630.0, why=["slow", "gc_full"], gc_full=1),
             dict(RING[310], why=["compile"]),
             {"cycle": None, "why": ["stall"], "stall_ms": None,
              "phase": "parked"}],
}


def observed(before, after):
    return types.SimpleNamespace(trace_states=(before, after))


def test_cycle_table_reads_the_slowest_row_the_window_added():
    run = observed(BEFORE, AFTER)
    assert cycle_table.read({"stat": "worst_ms"}, run) == 900.0
    assert cycle_table.read({"stat": "named_share"}, run) == pytest.approx(
        0.7)
    # a kept row that is still in the ring is one row, and a share is at
    # most 1 (the named time is rounded apart from the interval)
    only = observed(BEFORE, dict(AFTER, kept=AFTER["kept"][3:]))
    assert cycle_table.read({"stat": "worst_ms"}, only) == 206.5
    assert cycle_table.read({"stat": "named_share"}, only) == 1.0
    # a window from the program's start: nothing was there before
    assert cycle_table.read({"stat": "worst_ms"},
                            observed({"cycles": [], "kept": [],
                                      "next_cycle": 0}, AFTER)) == 2000.0


@pytest.mark.parametrize("before,after", [
    # a program from before PR 37: /v1/trace has neither key
    ({"span_ms": {}, "last_cycle": None}, {"span_ms": {}, "last_cycle": None}),
    # no cycle of the window decided anything
    (BEFORE, {"cycles": [row(c) for c in range(30, 60)], "kept": [],
              "next_cycle": 60}),
    # no cycle ran in the window
    (BEFORE, BEFORE),
])
def test_cycle_table_finds_nothing_and_does_not_raise(before, after):
    for stat in ("worst_ms", "named_share"):
        assert cycle_table.read({"stat": stat},
                                observed(before, after)) is None
    assert cycle_table.read({"stat": "worst_ms"},
                            types.SimpleNamespace(trace_states=None)) is None


def test_rehearsal_reports_the_nine_and_the_planted_collections(
        tmp_path, manifest_path):
    line, _ = rehearse(tmp_path, [os.path.join(BENCH, "run.py")],
                       manifest_path, "rehearsal-steady", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {}    # a CPU run names no device metric
    reported = line["rehearsal"]
    for name in NINE:
        assert "cpu_" + name in reported, name
    for name in TWINS:
        assert "cpu_" + name not in reported, name
    # one planted collection a second of a 6 s window (the program's own
    # churn of 1,200 pods starts none of its own that often)
    assert reported["cpu_gc_full_collections_in_window"]["value"] >= 4
    assert reported["cpu_gc_full_pause_s"]["value"] > 0
    assert 0 < reported["cpu_gc_pause_share"]["value"] < 1
    assert reported["cpu_loop_stalls_in_window"]["value"] == 0
    assert reported["cpu_loop_stall_s_in_window"]["value"] == 0
    assert reported["cpu_slow_decisions_in_window"]["value"] >= 0
    assert reported["cpu_loop_between_ms_per_s"]["value"] > 0
    assert reported["cpu_slowest_decision_ms"]["value"] > 0
    assert 0.5 < reported["cpu_slowest_decision_named_share"]["value"] <= 1
    # the loop's second is still covered by its stages: `between` is what
    # is left of it, and is no stage
    assert (reported["cpu_loop_accounted_ms_per_s"]["value"]
            + reported["cpu_loop_between_ms_per_s"]["value"]) > 900


def test_a_trace_0_run_through_the_manifests_own_entry_prints_the_ledger(
        tmp_path, manifest_path):
    line, lines = rehearse(
        tmp_path, [os.path.join(HERE, "interruptions_manifest.py"), "--run"],
        manifest_path, "rehearsal-steady", "0")
    assert line["correct"] is True
    assert set(line["rehearsal"]) == {
        "cpu_decision_p50_ms", "cpu_decision_p90_ms", "cpu_setup_s"}
    (printed,) = [l for l in lines if l.startswith("interruptions: ")]
    ledger = json.loads(printed[len("interruptions: "):])
    for name in NINE:
        assert isinstance(ledger[name], float), name
    assert ledger["gc_full_collections_in_window"] >= 4
    rows = ledger["rows"]
    assert rows and all(r["worst_ms"] > 0 for r in rows)
    assert ledger["slowest_decision_ms"] == max(r["worst_ms"] for r in rows)
    # both clocks are the machine's monotonic clock: the binds fall inside
    # the window, and each is followed by a rise of the scraped counter
    assert all(0 <= r["at_s"] <= 6.5 for r in rows)
    assert sum(n for _, n in ledger["steps"]) >= sum(
        r["decided"] for r in rows)
    assert len(ledger["client"]["burst_latency_ms"]) >= 1
    (notes,) = [l for l in lines if l.startswith("notes: ")]
    tree = json.loads(notes[len("notes: "):])["slowest_tree"]
    assert tree["decisions"]["worst_ms"] == ledger["slowest_decision_ms"]
    assert tree["cycle"] in [r["cycle"] for r in rows]


def test_rehearsal_of_the_read_plane_reports_the_three_twins(
        tmp_path, manifest_path):
    line, _ = rehearse(tmp_path, [os.path.join(BENCH, "run.py")],
                       manifest_path, "rehearsal-whatif", "1")
    assert line["correct"] is True and line["failed"] == 0
    reported = line["rehearsal"]
    for name in TWINS:
        assert "cpu_" + name in reported, name
    for name in NINE:
        assert "cpu_" + name not in reported, name
    assert reported["cpu_gc_full_collections_in_window.read"]["value"] >= 4

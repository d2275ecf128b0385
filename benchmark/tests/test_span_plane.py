"""The per-layer metrics that read the program's span plane (PR 24): the
fourteen entries and their files, the ``span_totals`` reader, each of them
reported by the CPU rehearsal of its cell (as ``cpu_*``), and
``trace_reduce`` naming the longest idle gap of a recorded steady-cell
chip trace by the program's own parked-time span."""

import gzip
import json
import os
import types

import pytest

import trace_reduce
from conftest import BENCH, HERE, REPO
from readers import span_totals
from test_rehearsal import run_cli

STEADY, KUBEMARK, WHATIF = "steady-50k-5k", "kubemark-3k-100", "whatif-50k-5k"
#: metric -> (reader, the cells it is reported in)
NEW = {
    "park_floor_ms": ("metrics_delta", [STEADY, KUBEMARK]),
    "park_event_ms_per_s": ("metrics_delta", [STEADY, KUBEMARK]),
    "loop_accounted_ms_per_s": ("metrics_delta", [STEADY, KUBEMARK]),
    "decision_queue_wait_ms": ("metrics_delta", [STEADY, KUBEMARK]),
    "leftover_decisions": ("metrics_delta", [STEADY]),
    "solve_dispatch_ms": ("span_totals", [STEADY]),
    "device_wait_ms": ("span_totals", [STEADY]),
    "compile_s_in_window": ("metrics_delta", [STEADY, KUBEMARK]),
    "jit_compiles_in_window": ("metrics_delta", [STEADY, KUBEMARK]),
    "compile_s_in_window.read": ("metrics_delta", [WHATIF]),
    "whatif_queue_ms": ("metrics_delta", [WHATIF]),
    "whatif_probe_ms": ("span_totals", [WHATIF]),
    "whatif_lease_ms_per_s": ("span_totals", [WHATIF]),
    "whatif_flush_ms": ("span_totals", [WHATIF]),
}
RECORDED = os.path.join(HERE, "data", "steady-50k-5k.pr24.xplane.pb.gz")


def test_the_fourteen_entries_and_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, (reader, cells) in NEW.items():
        assert per_layer[name]["workloads"] == cells, name
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            assert json.load(f)["reader"] == reader, name
    # the one whose growth is good news
    assert [n for n in NEW if per_layer[n]["better"] == "higher"] == [
        "loop_accounted_ms_per_s"]
    # they come after everything the benchmark had
    assert list(per_layer)[-len(NEW):] == list(NEW)


def observed(before, after, seconds=50.0):
    return types.SimpleNamespace(trace_states=(before, after),
                                 span_seconds={"window": seconds})


def test_span_totals_reads_growth_per_span_and_per_second():
    before = {"span_ms": {"whatif:probe": 100.0, "whatif:lease": 10.0},
              "span_counts": {"whatif:probe": 10, "whatif:lease": 10}}
    after = {"span_ms": {"whatif:probe": 700.0, "whatif:lease": 260.0},
             "span_counts": {"whatif:probe": 40, "whatif:lease": 35}}
    run = observed(before, after)
    assert span_totals.read(
        {"span": "whatif:probe", "stat": "mean"}, run) == 20.0
    assert span_totals.read(
        {"span": "whatif:lease", "stat": "per_second"}, run) == 5.0
    # a span that first closed inside the window
    first = observed({"span_ms": {}, "span_counts": {}}, after)
    assert span_totals.read(
        {"span": "whatif:probe", "stat": "mean"}, first) == 17.5


@pytest.mark.parametrize("before,after", [
    # a program from before PR 24: /v1/trace has no span_ms
    ({"span_counts": {"solve_dispatch": 3}},
     {"span_counts": {"solve_dispatch": 9}}),
    # no span of the name closed in the window
    ({"span_ms": {"solve_dispatch": 5.0}, "span_counts": {"solve_dispatch": 1}},
     {"span_ms": {"solve_dispatch": 5.0}, "span_counts": {"solve_dispatch": 1}}),
    # the name was never seen
    ({"span_ms": {}, "span_counts": {}}, {"span_ms": {}, "span_counts": {}}),
])
def test_span_totals_finds_nothing_and_does_not_raise(before, after):
    run = observed(before, after)
    assert span_totals.read(
        {"span": "solve_dispatch", "stat": "mean"}, run) is None
    assert span_totals.read({"span": "solve_dispatch", "stat": "mean"},
                            types.SimpleNamespace(trace_states=None)) is None


@pytest.mark.parametrize("workload,cell", [
    ("rehearsal-steady", STEADY),
    ("rehearsal-kubemark", KUBEMARK),
    ("rehearsal-whatif", WHATIF),
])
def test_rehearsal_reports_every_new_metric_of_its_cell(
        tmp_path, rehearsal_path, workload, cell):
    got = run_cli(tmp_path, rehearsal_path, workload, "--trace", "1",
                  "--platform", "cpu")
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {}
    reported = line["rehearsal"]
    for name, (_, cells) in NEW.items():
        assert (("cpu_" + name) in reported) == (cell in cells), name
    if cell == WHATIF:
        assert reported["cpu_whatif_flush_ms"]["value"] > 0
        assert reported["cpu_whatif_probe_ms"]["value"] > 0
        assert reported["cpu_whatif_queue_ms"]["value"] > 0
    else:
        # the loop thread's root spans cover the loop's second (a count of
        # milliseconds on this host's clock, never a device time)
        assert reported["cpu_loop_accounted_ms_per_s"]["value"] > 800
        assert reported["cpu_park_event_ms_per_s"]["value"] > 0
        assert reported["cpu_decision_queue_wait_ms"]["value"] > 0
        assert reported["cpu_jit_compiles_in_window"]["value"] >= 0


def test_recorded_steady_trace_names_its_longest_gap_by_a_park_span():
    """The traced run of ``steady-50k-5k`` that PR 24 made on the chip (TPU
    v5 lite, seed 2800000101; every event's name and interval as recorded,
    the per-event stats dropped and the file gzipped to keep the checkout
    small: the reduction reads the same from both).  The program's spans
    are on the host plane, so the idle gaps carry their names and not
    whatever XLA event touched the gap."""
    from jax.profiler import ProfileData

    with gzip.open(RECORDED) as f:
        got = trace_reduce.reduce_data(
            ProfileData.from_serialized_xspace(f.read()))
    assert got["device_planes"] == ["/device:TPU:0"]
    assert got["busy_s"] == pytest.approx(0.191191514, rel=1e-6)
    assert got["window_s"] == pytest.approx(3.902439289, rel=1e-6)
    assert got["programs"][0] == ["jit__warm_sentinel_body",
                                  pytest.approx(0.189051783, rel=1e-6)]
    longest, seconds = got["idle_gaps"][0]
    assert longest == "park:event -> jit_scatter"
    assert seconds == pytest.approx(3.012114085, rel=1e-6)
    # at least nine tenths of the idle seconds listed carry the name of a
    # span of the program on the host side
    spans = ("park:", "action:", "solve_dispatch", "session_open",
             "ingest_drain", "status_derive")
    named = sum(t for name, t in got["idle_gaps"] if name.startswith(spans))
    assert named >= 0.9 * sum(t for _, t in got["idle_gaps"])

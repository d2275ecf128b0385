"""``BENCHMARK.json`` with what it cannot list yet of ``overcommit-50k-5k``,
for the cell's traced runs (``run.py --manifest``): the seven per-layer
metrics of the evict path whose files ``layer_metrics/`` holds, and the
cell's name in the lists of PR 24's fourteen that read something there.
``test_span_plane.py`` pins those fourteen as the manifest's last entries,
each list equal to its cells (PERF.md section 7).

    python benchmark/tests/tiers_manifest.py > chiprun_out/tiers.json
    python benchmark/run.py --manifest chiprun_out/tiers.json \\
        --workload overcommit-50k-5k --seed 1 --seconds 50 --trace 1
"""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CELL, SHARES_CODE_WITH = "overcommit-50k-5k", "steady-50k-5k"
SOLVES, ACTIONS = "resident cache + device solves", "actions"
#: name -> (unit, better, source, layer, moves)
EVICT_PATH = {
    "evict_solves_per_cycle": (
        "count", "lower", "program_counter", SOLVES, "decision_p50_ms"),
    "evict_device_ms_per_solve": (
        "ms", "lower", "device_trace", "device", "decision_p50_ms"),
    "evict_replay_ms": (
        "ms", "lower", "program_span", ACTIONS, "decision_p50_ms"),
    "evictions_per_claim": (
        "count", "lower", "program_counter", ACTIONS, "decision_p50_ms"),
    "evict_repeat_claims": (
        "count", "lower", "program_counter", ACTIONS, "decision_p90_ms"),
    "eviction_release_ms": (
        "ms", "lower", "program_span", "cache + columnar model",
        "decision_p50_ms"),
    "evict_claims_rejected_share": (
        "share", "lower", "program_counter", ACTIONS, "decision_p90_ms"),
}


def entries(cells: list) -> list:
    """The seven as ``per_layer`` entries reported in ``cells``."""
    return [{"name": name, "unit": unit, "better": better, "source": source,
             "layer": layer, "moves": moves, "workloads": list(cells)}
            for name, (unit, better, source, layer, moves)
            in EVICT_PATH.items()]


def derive() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for m in manifest["per_layer"]:
        cells = m.get("workloads", [])
        if SHARES_CODE_WITH in cells and CELL not in cells:
            cells.append(CELL)
    manifest["per_layer"] += entries([CELL])
    return manifest


if __name__ == "__main__":
    print(json.dumps(derive(), indent=1))

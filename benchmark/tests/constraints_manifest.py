"""``BENCHMARK.json`` with what it cannot list yet of ``affinity-10k-5k``,
for the cell's traced runs (``run.py --manifest``): the six per-layer metrics
of the inter-pod terms whose files ``layer_metrics/`` holds, and the cell's
name in the lists of PR 24's fourteen that read something there.
``test_span_plane.py`` pins those fourteen as the manifest's last entries,
each list equal to its cells (PERF.md section 7).

    python benchmark/tests/constraints_manifest.py > chiprun_out/constraints.json
    python benchmark/run.py --manifest chiprun_out/constraints.json \\
        --workload affinity-10k-5k --seed 1 --seconds 50 --trace 1
"""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CELL, SHARES_CODE_WITH = "affinity-10k-5k", "steady-50k-5k"
MODEL, SOLVES, ACTIONS = ("cache + columnar model",
                          "resident cache + device solves", "actions")
#: name -> (unit, better, source, layer, moves)
TERMS = {
    "affinity_mask_ms": (
        "ms", "lower", "program_span", MODEL, "decision_p50_ms"),
    "affinity_plane_update_ms": (
        "ms", "lower", "program_span", MODEL, "decision_p50_ms"),
    "affinity_rows_per_cycle": (
        "rows", "lower", "program_counter", MODEL, "decision_p50_ms"),
    "inter_pod_exclusions_per_cycle": (
        "count", "lower", "program_counter", SOLVES, "decision_p50_ms"),
    "host_fallback_share": (
        "share", "lower", "program_counter", ACTIONS, "decision_p50_ms"),
    "slow_replay_jobs_per_cycle": (
        "count", "lower", "program_counter", ACTIONS, "decision_p50_ms"),
}


def entries(cells: list) -> list:
    """The six as ``per_layer`` entries reported in ``cells``."""
    return [{"name": name, "unit": unit, "better": better, "source": source,
             "layer": layer, "moves": moves, "workloads": list(cells)}
            for name, (unit, better, source, layer, moves) in TERMS.items()]


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

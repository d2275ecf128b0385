"""``BENCHMARK.json`` with what it cannot list yet of ``skew-36k-5k``, for
the cell's traced runs (``run.py --manifest``): the four per-layer metrics
of the gang mix whose files ``layer_metrics/`` holds, and the cell's name in
the lists of PR 24's fourteen that read something there.
``test_span_plane.py`` pins those fourteen as the manifest's last entries,
each list equal to its cells (PERF.md section 7).

    python benchmark/tests/gangmix_manifest.py > chiprun_out/gangmix.json
    python benchmark/run.py --manifest chiprun_out/gangmix.json \\
        --workload skew-36k-5k --seed 1 --seconds 50 --trace 1
"""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CELL, SHARES_CODE_WITH = "skew-36k-5k", "steady-50k-5k"
SOLVES = "resident cache + device solves"
#: name -> (unit, better, source, layer, moves)
GANG_MIX = {
    "solve_rounds_per_solve": (
        "rounds", "lower", "program_counter", SOLVES, "decision_p50_ms"),
    "solve_over_budget_share": (
        "share", "lower", "program_counter", SOLVES, "decision_p90_ms"),
    "topk_exhausted_per_solve": (
        "count", "lower", "program_counter", SOLVES, "decision_p90_ms"),
    "gang_decision_ms.large": (
        "ms", "lower", "program_span", "cache + columnar model",
        "decision_p90_ms"),
}


def entries(cells: list) -> list:
    """The four as ``per_layer`` entries reported in ``cells``."""
    return [{"name": name, "unit": unit, "better": better, "source": source,
             "layer": layer, "moves": moves, "workloads": list(cells)}
            for name, (unit, better, source, layer, moves) in GANG_MIX.items()]


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

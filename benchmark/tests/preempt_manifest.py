"""``BENCHMARK.json`` with what it cannot list yet of ``preempt-20k-5k``,
for the cell's traced runs (``run.py --manifest``): the five per-layer
metrics of the preempt action whose files ``layer_metrics/`` holds, the
cell's readings of the seven evict-path metrics of ``tiers_manifest.py``,
and the cell's name in the lists of PR 24's fourteen that read something
there.  ``test_span_plane.py`` pins those fourteen as the manifest's last
entries, each list equal to its cells (PERF.md section 7).

    python benchmark/tests/preempt_manifest.py > chiprun_out/preempt.json
    python benchmark/run.py --manifest chiprun_out/preempt.json \\
        --workload preempt-20k-5k --seed 1 --seconds 50 --trace 1
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiers_manifest  # noqa: E402

REPO = tiers_manifest.REPO

CELL, SHARES_CODE_WITH = "preempt-20k-5k", "steady-50k-5k"
ACTIONS = tiers_manifest.ACTIONS
#: name -> (unit, better, source, layer, moves)
PREEMPT = {
    "preempt_replay_ms": (
        "ms", "lower", "program_span", ACTIONS, "decision_p50_ms"),
    "preempt_phase2_ms": (
        "ms", "lower", "program_span", ACTIONS, "decision_p50_ms"),
    "preempt_statements_per_cycle": (
        "count", "lower", "program_counter", ACTIONS, "decision_p50_ms"),
    "preempt_claims_rejected_share": (
        "share", "lower", "program_counter", ACTIONS, "decision_p90_ms"),
    "preempt_victims_per_claim": (
        "count", "lower", "program_counter", ACTIONS, "decision_p50_ms"),
}


def entries(cells: list) -> list:
    """The five as ``per_layer`` entries reported in ``cells``."""
    return [{"name": name, "unit": unit, "better": better, "source": source,
             "layer": layer, "moves": moves, "workloads": list(cells)}
            for name, (unit, better, source, layer, moves) in PREEMPT.items()]


def derive() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    # a traced run also reads what the cell may not report to the driver:
    # decision_p90_ms spreads by more than half its bound there (PERF.md
    # section 6), so BENCHMARK.json leaves the cell out of it and of the
    # four per-layer metrics that move it (compiles_in_window among them)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        cells = m.get("workloads", [])
        if SHARES_CODE_WITH in cells and CELL not in cells:
            cells.append(CELL)
    manifest["per_layer"] += tiers_manifest.entries([CELL]) + entries([CELL])
    return manifest


if __name__ == "__main__":
    print(json.dumps(derive(), indent=1))

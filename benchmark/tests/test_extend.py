"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as new files plus entries in the manifest, and edits no file that is
there.  This does exactly that with throw-away files, and runs the cell."""

import json
import os
import subprocess
import sys

from conftest import BENCH, REPO
from rehearsal_manifest import derive

NEW_FILES = {
    "configs/throwaway-48.json": {
        "name": "throwaway-48", "source": "a test", "chips": 1,
        "namespace": "bench",
        "queues": [{"name": "only", "weight": 1}],
        "nodes": 48,
        "node": {"cpu_milli": 16000, "memory_bytes": 64 * 2 ** 30, "pods": 110},
        "population": {"kind": "gangs", "pods": 240},
        "gang": {"size": 2, "min_member": 2},
        "request_mix": {"cpu_milli": [500, 1000],
                        "memory_bytes": [2 ** 30]},
        "control": {"placement": "stale"}, "reduced": [], "assumed": []},
    "traffic/throwaway-churn.json": {
        "why": "a test", "loop": "open", "scrape_period_ms": 20,
        "streams": [{"kind": "churn_bursts", "rate": 2, "gangs": 3,
                     "jitter": 0.2, "warm_sizes": [1, 2],
                     "settled_ms": 500}]},
    "layer_metrics/throwaway_enqueue_ms.json": {
        "reader": "metrics_delta",
        "numerator": [["volcano_cycle_stage_latency_milliseconds_sum",
                       "stage=\"action:enqueue\""]],
        "denominator": [["volcano_cycle_stage_latency_milliseconds_count",
                         "stage=\"action:enqueue\""]]},
}


def test_new_cell_and_metric_from_new_files_only(tmp_path):
    manifest = derive()
    manifest["configs"].append({"name": "throwaway-48",
                                "file": "benchmark/configs/throwaway-48.json"})
    manifest["workloads"].append({"name": "throwaway", "chips": 1,
                                  "config": "throwaway-48",
                                  "traffic": "throwaway-churn"})
    for m in manifest["end_to_end"]:
        if m["name"].startswith("decision_"):
            m["workloads"].append("throwaway")
    manifest["per_layer"].append({
        "name": "throwaway_enqueue_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "actions",
        "moves": "decision_p50_ms", "workloads": ["throwaway"]})
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    written = []
    try:
        for rel, content in NEW_FILES.items():
            full = os.path.join(BENCH, rel)
            assert not os.path.exists(full)
            with open(full, "w") as f:
                json.dump(content, f)
            written.append(full)
        lines = {}
        for trace in ("0", "1"):
            got = subprocess.run(
                [sys.executable, os.path.join(BENCH, "run.py"),
                 "--manifest", str(path), "--workload", "throwaway",
                 "--seed", "9", "--seconds", "4", "--trace", trace,
                 "--platform", "cpu", "--out", str(tmp_path / "out")],
                cwd=REPO, capture_output=True, text=True, timeout=600,
                env=dict(os.environ, XLA_FLAGS=(
                    "--xla_force_host_platform_device_count=1")))
            assert got.returncode == 0, got.stderr[-2000:]
            lines[trace] = json.loads(got.stdout.strip().splitlines()[-1])
    finally:
        for full in written:
            os.remove(full)
    assert lines["0"]["correct"] and lines["1"]["correct"]
    assert lines["0"]["rehearsal"]["cpu_decision_p50_ms"]["value"] > 0
    assert lines["1"]["rehearsal"]["cpu_throwaway_enqueue_ms"]["value"] > 0

"""The CPU rehearsal's manifest, derived from BENCHMARK.json: its metrics,
each over the tiny cell that stands for the cell it is reported in.  The
tiny cells are never cells of the benchmark.

    python benchmark/tests/rehearsal_manifest.py > chiprun_out/rehearsal.json
    python benchmark/run.py --platform cpu --manifest chiprun_out/rehearsal.json \\
        --workload rehearsal-steady --seed 1 --seconds 5 --trace 0
"""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: tiny cell -> (the cell it stands for, its configuration, its traffic)
CELLS = {
    "rehearsal-steady": ("steady-50k-5k", "rehearsal-1200-96",
                         "rehearsal-churn"),
    "rehearsal-kubemark": ("kubemark-3k-100", "rehearsal-kubemark-300-10",
                           "rehearsal-density"),
    "rehearsal-whatif": ("whatif-50k-5k", "rehearsal-1200-96",
                         "rehearsal-whatif"),
}


def derive() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    every = [w["name"] for w in manifest["workloads"]]
    configs = sorted({c for _, c, _ in CELLS.values()})
    out = {
        "configs": [{"name": c, "file": f"benchmark/configs/{c}.json"}
                    for c in configs],
        "workloads": [{"name": tiny, "config": c, "traffic": t, "chips": 1}
                      for tiny, (_, c, t) in CELLS.items()],
    }
    for section in ("end_to_end", "per_layer"):
        out[section] = [
            dict(m, workloads=[tiny for tiny, (cell, _, _) in CELLS.items()
                               if cell in m.get("workloads", every)])
            for m in manifest[section]]
    return out


if __name__ == "__main__":
    print(json.dumps(derive(), indent=1))

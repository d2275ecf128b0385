"""Mean time per cycle of named spans of the program's own span tree,
over the cycles that ``GET /v1/trace`` showed as ``last_cycle`` while the
window ran (sampled a few times a second in the ``--trace 1`` run only:
the stage histogram on /metrics sees root spans alone, and the spans under
``action:allocate`` are what tell the solve from the replay).

spec: {"spans": [name, ...]}: a cycle counts if it holds one of them.
"""


def _total(spans: list, names: set) -> tuple:
    found, total = False, 0.0
    for sp in spans:
        if sp["name"] in names:
            found, total = True, total + sp["dur_ms"]
        f, t = _total(sp.get("children", ()), names)
        found, total = found or f, total + t
    return found, total


def read(spec: dict, run):
    names = set(spec["spans"])
    per_cycle = [t for f, t in (_total(c["spans"], names)
                                for c in run.cycle_samples.values()) if f]
    return sum(per_cycle) / len(per_cycle) if per_cycle else None

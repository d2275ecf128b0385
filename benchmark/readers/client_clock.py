"""A statistic of samples the streams took on the client's clock, or one of
the run's scalars (``setup_s``, ``load_s``, ...).

spec: {"samples": <name>, "stat": "p50"|"p90"|"p95"|"mean"|"sum"|
"per_second"} or
{"scalar": <name>}.
"""

from observe import percentile


def read(spec: dict, run):
    if "scalar" in spec:
        return run.scalars.get(spec["scalar"])
    values = run.samples.get(spec["samples"])
    if not values:
        return None
    stat = spec["stat"]
    if stat == "per_second":
        return len(values) / run.window_s
    if stat == "sum":
        return sum(values)
    if stat == "mean":
        return sum(values) / len(values)
    return percentile(values, {"p50": 0.5, "p90": 0.9, "p95": 0.95}[stat])

"""A number that trace_reduce.py took from the profiler's trace.

spec: {"key": <key of trace_reduce.reduce()'s result>, "scale": 1.0,
       "per": [[series, labels], ...] (growth over the profiled span)}
"""

from readers import metrics_delta


def read(spec: dict, run):
    if (run.profile is None or not run.profile["device_planes"]
            or spec["key"] not in run.profile):
        return None  # no trace, or a trace in which no device shows
    value = run.profile[spec["key"]] * spec.get("scale", 1.0)
    if "per" in spec:
        per = metrics_delta.read(
            {"numerator": spec["per"], "span": "profile"}, run)
        return value / per if per else None
    return value

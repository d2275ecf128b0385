"""Device time of the operations of a kind, from trace_reduce.py's list of
the operations that took the most time (``device_ops``: the ten largest,
seconds averaged over the device planes), so a lower bound: an operation
of the kind that is not among the ten is not counted.

spec: {"match": [<substring of an operation's name>, ...], "scale": 1.0,
       "per": [[series, labels], ...] (growth over the profiled span)}
An operation counts when its name holds one of ``match`` (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute`` name XLA's
collectives, fused or not).  A trace in which no device shows, or no
trace, gives nothing; a trace with no such operation gives 0.
"""

from readers import metrics_delta


def read(spec: dict, run):
    if run.profile is None or not run.profile["device_planes"]:
        return None
    seconds = sum(t for name, t in run.profile["device_ops"]
                  if any(m in name for m in spec["match"]))
    value = seconds * spec.get("scale", 1.0)
    if "per" in spec:
        per = metrics_delta.read(
            {"numerator": spec["per"], "span": "profile"}, run)
        return value / per if per else None
    return value

"""Device time of the programs of a kind, from trace_reduce.py's list of
the programs that took the most time (``programs``: the ten largest XLA
modules, seconds averaged over the device planes), so a lower bound: a
program of the kind that is not among the ten is not counted.

spec: {"match": [<substring of a program's name>, ...], "scale": 1.0,
       "per": [[series, labels], ...] (growth over the profiled span)}
A program counts when its name holds one of ``match`` (``evict`` names the
reclaim and preempt solves: ``jit_evict_sentinel_solve``, ``jit_evict_solve``
and their sharded twins).  A trace in which no device shows, no trace, or a
profiled span in which ``per`` did not grow gives nothing; a trace with no
such program gives 0.
"""

from readers import metrics_delta


def read(spec: dict, run):
    if run.profile is None or not run.profile["device_planes"]:
        return None
    seconds = sum(t for name, t in run.profile["programs"]
                  if any(m in name for m in spec["match"]))
    value = seconds * spec.get("scale", 1.0)
    if "per" in spec:
        per = metrics_delta.read(
            {"numerator": spec["per"], "span": "profile"}, run)
        return value / per if per else None
    return value

"""Growth of the program's per-name span totals over the window: every
span of a name is counted and its time added up by the tracer itself
(``GET /v1/trace``: ``span_ms`` beside ``span_counts``; children and roots,
every cycle and every flush, not the ``last_cycle`` a sampler happened to
see).  A program whose ``/v1/trace`` has no ``span_ms`` (before PR 24), or
a window in which no span of the name closed, gives nothing.

spec: {"span": <span name>, "stat": "mean" | "per_second"}
``mean``: ms per span (growth of ``span_ms[name]`` over growth of
``span_counts[name]``); ``per_second``: ms per second of the window.
"""


def read(spec: dict, run):
    if run.trace_states is None:
        return None
    name = spec["span"]
    before, after = ((s.get("span_ms"), s.get("span_counts") or {})
                     for s in run.trace_states)
    if after[0] is None or name not in after[0]:
        return None
    ms = after[0][name] - (before[0] or {}).get(name, 0.0)
    if spec["stat"] == "per_second":
        seconds = run.span_seconds.get("window")
        return ms / seconds if seconds else None
    count = after[1].get(name, 0) - before[1].get(name, 0)
    return ms / count if count else None

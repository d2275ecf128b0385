"""Growth of a number in ``GET /v1/trace`` over the window.

spec: {"path": ["span_counts", "solve_dispatch"]} (keys from the top of the
state down to a number), optionally {"per": [[series, labels], ...]} to
divide by the growth of /metrics series.
"""

from readers import metrics_delta


def _number(state: dict, spec: dict) -> float:
    for key in spec["path"]:
        state = state.get(key, 0) if isinstance(state, dict) else 0
    return float(state)


def read(spec: dict, run):
    if run.trace_states is None:
        return None
    before, after = run.trace_states
    grown = _number(after, spec) - _number(before, spec)
    if "per" not in spec:
        return grown
    per = metrics_delta.read({"numerator": spec["per"]}, run)
    return grown / per if per else None

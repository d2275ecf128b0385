"""The slowest decision of the window, from the program's table of cycles.

``GET /v1/trace`` lists one row a cycle record the flight recorder's ring
still holds (``cycles``) and the rows of the records it kept because they
were slow or interrupted (``kept``, which outlive the ring's rollover).
The rows of the cycles that began after the state fetched before the
window (its ``next_cycle``; a cycle that was running then is a warm-up
burst's) are the window's; among them the one with the largest
``worst_ms`` (the worst arrival-to-decision latency that cycle closed) is
the slowest decision.  A program without the table (before PR 37), or a
window in which no row decided anything, gives nothing.

spec: {"stat": "worst_ms" | "named_share"}
``worst_ms``: that row's ``worst_ms``; ``named_share``: the share, at most
1, of that decision's interval (earliest arrival to the bind) that the
program can name: time under a root span of the cycle thread, parked time,
a garbage collection in a gap between two roots, or a declared stall
(``worst_named_ms`` over ``worst_ms``).
"""


def rows_added(before: dict, after: dict) -> list:
    """The deciding rows of ``after`` whose cycles began after ``before``
    was fetched, by cycle number, each once (a kept row may still be in
    the ring)."""
    first = before.get("next_cycle", 0)
    rows = {row["cycle"]: row
            for row in after.get("cycles", []) + (after.get("kept") or [])
            if row.get("cycle") is not None and row["cycle"] >= first
            and row.get("worst_ms")}
    return [rows[cycle] for cycle in sorted(rows)]


def read(spec: dict, run):
    if run.trace_states is None or "cycles" not in run.trace_states[1]:
        return None
    rows = rows_added(*run.trace_states)
    if not rows:
        return None
    slowest = max(rows, key=lambda row: row["worst_ms"])
    if spec["stat"] == "worst_ms":
        return slowest["worst_ms"]
    return min(slowest["worst_named_ms"] / slowest["worst_ms"], 1.0)

"""Growth of ``/metrics`` series over the window (or, with ``"span":
"profile"``, over the profiled part of it): the sum of the numerator's
growth over the sum of the denominator's, the span's seconds, or nothing.

spec: {"numerator": [[series, labels], ...],
       "denominator": [[series, labels], ...] | "seconds" | null,
       "span": "window" | "profile"}
``labels`` is the text between the braces as /metrics prints it.  Sums and
counters print six significant digits, so this is for per-layer means,
never for an end-to-end metric.
"""


def _growth(pages, series) -> float:
    before, after = pages
    return sum(after.get((name, labels), 0.0) - before.get((name, labels), 0.0)
               for name, labels in series)


def read(spec: dict, run):
    span = spec.get("span", "window")
    pages = run.metrics_pages.get(span)
    if pages is None:
        return None
    num = _growth(pages, spec["numerator"])
    den = spec.get("denominator")
    if den is None:
        return num
    den = run.span_seconds[span] if den == "seconds" else _growth(pages, den)
    return num / den if den else None

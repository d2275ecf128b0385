"""Jit compile/retrace accounting for the solver programs.

The cycle-time budget assumes the compiled solves are cache hits after
warmup: the snapshot axes are padded to capacity buckets precisely so a
±10% pod-count wobble maps to the SAME shapes cycle after cycle.  A silent
retrace (shape drift, a fresh lambda in a jit cache key, an axis growing
mid-flight) costs hundreds of ms and hides inside p50s — so the bench and
the tests read these counters instead of guessing.

Every jitted entry point registers itself here; ``total_compiles()`` sums
``_cache_size()`` (the per-function count of distinct traced/compiled
specializations) across them.  A delta of zero between two points proves no
retrace happened in the interval.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

_TRACKED: List[Tuple[str, object]] = []


def register(name: str, fn) -> object:
    """Track a jitted callable (idempotent per (name, fn)); returns fn so it
    can wrap a definition site."""
    for n, f in _TRACKED:
        if n == name and f is fn:
            return fn
    _TRACKED.append((name, fn))
    return fn


def _size(fn) -> int:
    return int(fn._cache_size())


def compile_counts() -> Dict[str, int]:
    """{name: compiled-specialization count} for every tracked function."""
    out: Dict[str, int] = {}
    for name, fn in _TRACKED:
        out[name] = out.get(name, 0) + _size(fn)
    return out


def total_compiles() -> int:
    return sum(_size(fn) for _, fn in _TRACKED)


# --------------------------------------------------------------------------
# collective-bytes inventory (the shard_map comms counter)
# --------------------------------------------------------------------------

#: cross-device communication primitives as they appear in jaxprs
COLLECTIVE_PRIMS = (
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "psum_scatter",
)
_LOOP_PRIMS = ("while", "scan")


def _aval_bytes(var) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def collective_inventory(closed_jaxpr, *, detail: bool = False) -> Dict:
    """Walk a traced program (a ClosedJaxpr, e.g. ``fn.trace(...).jaxpr``)
    and account every collective primitive's result bytes, split into
    per-ROUND (inside a while/scan body — paid every bidding round) and
    per-SOLVE (outside the loops — e.g. the one-time node-ledger gather).

    This is the evidence behind the "O(tasks) cross-host bytes per round"
    claim: the numbers come from the program XLA compiles, so a regression
    that smuggles an O(nodes) or O(tasks × nodes) collective into the
    round loop shows up as a bytes jump, not a silent slowdown.  Bytes are
    the collective RESULT sizes — a uniform proxy for payload (an
    all-reduce moves ~result-size per hop; an all_gather's result already
    includes the axis-size factor).

    Nested loops: a collective inside a scan/fori nested WITHIN the round
    loop (the warm refresh's inner merge loops) runs inner-trip-count times
    per round.  ``per_round_bytes`` keeps the historical once-per-site
    count; ``per_round_bytes_expanded`` multiplies each per-round site by
    the product of the scan lengths of the loops strictly inside the
    outermost one.  An inner ``while`` has no static trip count — its sites
    count ×1 in the expanded total and set
    ``per_round_has_unbounded_inner_loop`` so the consumer (KBT204) knows
    the formula is a floor, not a bound.

    With ``detail=True``, each result also carries ``sites``: one record
    per collective equation with its result shape/dtype/bytes, loop depth,
    and inner trip multiplier — the raw material for byte-formula
    extraction."""
    per: Dict[str, Dict[str, Dict[str, int]]] = {
        "per_round": {}, "per_solve": {},
    }
    sites: List[Dict] = []
    expanded = {"per_round": 0}
    unbounded_seen = [False]

    def walk(jaxpr, depth: int, inner_trips: int, unbounded: bool) -> None:
        # depth = enclosing while/scan count; inner_trips = product of the
        # known scan lengths of the enclosing loops EXCLUDING the outermost
        # (per-round means "per iteration of the outermost loop").
        for eqn in jaxpr.eqns:
            prim = str(eqn.primitive)
            if prim in COLLECTIVE_PRIMS:
                in_loop = depth > 0
                bucket = per["per_round" if in_loop else "per_solve"]
                rec = bucket.setdefault(prim, {"count": 0, "bytes": 0})
                rec["count"] += 1
                b = sum(_aval_bytes(v) for v in eqn.outvars)
                rec["bytes"] += b
                if in_loop:
                    expanded["per_round"] += b * inner_trips
                    if unbounded:
                        unbounded_seen[0] = True
                if detail:
                    aval = getattr(eqn.outvars[0], "aval", None)
                    sites.append({
                        "prim": prim,
                        "bytes": b,
                        "shape": tuple(getattr(aval, "shape", ()) or ()),
                        "dtype": str(getattr(aval, "dtype", "?")),
                        "depth": depth,
                        "inner_trips": inner_trips,
                        "unbounded_trips": unbounded,
                    })
            is_loop = prim in _LOOP_PRIMS
            if is_loop and depth >= 1:
                # entering a loop nested inside the round loop: fold its
                # trip count into the per-round multiplier
                length = eqn.params.get("length")
                sub_trips = inner_trips * int(length) if length else inner_trips
                sub_unbounded = unbounded or length is None
            else:
                sub_trips, sub_unbounded = inner_trips, unbounded
            inner_depth = depth + 1 if is_loop else depth
            for param in eqn.params.values():
                vals = param if isinstance(param, (list, tuple)) else [param]
                for sub in vals:
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        walk(inner, inner_depth, sub_trips, sub_unbounded)
                    elif hasattr(sub, "eqns"):
                        walk(sub, inner_depth, sub_trips, sub_unbounded)

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    walk(jaxpr, 0, 1, False)
    out = {
        "per_round_bytes": sum(
            r["bytes"] for r in per["per_round"].values()
        ),
        "per_solve_bytes": sum(
            r["bytes"] for r in per["per_solve"].values()
        ),
        "per_round_bytes_expanded": expanded["per_round"],
        "per_round_has_unbounded_inner_loop": unbounded_seen[0],
        "ops": per,
    }
    if detail:
        out["sites"] = sites
    return out

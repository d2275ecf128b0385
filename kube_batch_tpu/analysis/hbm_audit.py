"""Tier C: jaxpr liveness / HBM budget audit of the registered entry points.

Tier B answers "does the traced program contain a hazard primitive"; tier C
answers the question that actually caps the rebuild's scale ceiling: **does
each compiled program FIT** — peak live bytes under a per-device HBM budget
at the shapes production will run, long before any hardware sees the
program.  ROADMAP item 1's contract is that at 1M pods × 100k nodes any
materialized [T, N] plane (~400 GB at f32) is unaffordable, so the steady
dispatch path must stay on the compacted [P, K] candidate geometry; this
tier makes that a CI-enforced invariant instead of a code-review argument.

Mechanism: every tier-B registry entry is re-traced (abstract — no device
work) at a LADDER of shape points (current bench shapes, the 50k×5k
headline, the 1M×100k north star), and each closed jaxpr is walked with a
linear-scan liveness analysis:

- values live from the equation that produces them to their last read
  (or program exit for outputs); constvars and non-donated inputs are
  live throughout; a DONATED input's buffer is free once its last read
  passes (the aliasing credit the budget model claims — KBT203 checks
  it's real);
- ``while``/``scan``/``cond``/``pjit`` sub-jaxprs recurse: a loop body's
  internal peak is transient extra on top of the carry (counted at the
  call site), ``cond`` takes the max over branches, ``scan`` stacked
  outputs are charged at the call site;
- ``shard_map`` bodies are walked at their per-shard LOCAL avals (that's
  what each device holds), and the call-site operands/results are charged
  at global-bytes ÷ (mesh-axis extent) per the in/out specs — so an
  ``all_gather`` result inside the body is charged at its gathered
  (global) size on every device, exactly the collective-materialization
  cost the budget must absorb.

Rules (suppressions are per-(entry, rule, shape-point) allowlist entries
with mandatory reasons — see HBM_ALLOWLIST; stale entries fail the audit):

- **KBT201 over budget** — peak live bytes exceed the backend profile
  (v5e 16 GiB default; ``KB_HBM_BUDGET`` accepts a GiB number or a
  profile name) at a declared shape point.
- **KBT202 full-matrix temporary** — a program declared steady-path
  (EntryPoint.steady) materializes a task-axis × node-axis plane.  This
  is the rule that permanently pins ROADMAP 1.(1) (evict's bid planes:
  [P, N] on the pending bucket, [T, N] in the full-axis fallback) and
  1.(2) (shard_map exhaustion fallback): those corners live in the
  allowlist with ROADMAP cross-references until fixed — the allowlist IS
  the burn-down list.
- **KBT203 unrealized donation** — the registry declares a donated
  argument but no output of the traced jaxpr can alias it (shape+dtype
  match): the savings the budget model credits would not materialize,
  and XLA would warn-and-ignore the donation at runtime.
- **KBT204 node-scaled per-round collective** — a collective inside the
  bidding round loop whose payload carries a node-axis dimension
  (extending utils.jitstats.collective_inventory's per-round/per-solve
  bucketing, nested-loop trip counts included).  The cross-host byte
  contract is O(tasks)/round; an O(nodes)/round collective breaks the
  scaling story even when it fits HBM.

Known slack vs XLA's real allocator (documented, deliberate):

- fusion: XLA fuses elementwise chains so intermediate values never
  materialize; this walk charges each equation output.  Overestimate.
- scheduling: XLA may reorder to shrink live ranges; the walk takes the
  traced order.  Overestimate.
- sub-jaxpr outputs are charged both inside the body (at its internal
  peak) and at the call site.  Small overestimate (~carry size).
- the PJIT-ORACLE sharded entries carry no specs on their intermediates
  (jitted-with-in_shardings functions expose none), so their per-device
  bytes are MODELLED: a value with the global node axis is charged at
  bytes ÷ node shards (``EntryPoint.spmd_shards``), which is what XLA's
  partitioner does with node-sharded inputs; tests/test_tpu_compile.py
  holds the model above the TPU compiler's own allocation at the envelope
  point.  The shard_map production path is discounted exactly, via the
  eqn's in/out specs.

All slack overestimates: a clean tier-C verdict is conservative-safe.

Run via ``python -m kube_batch_tpu.analysis --hbm`` (``--hbm-only`` for
just this tier), the check.sh gate, or the tier-1 self-enforcement test.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from kube_batch_tpu.analysis.engine import Finding
from kube_batch_tpu.analysis.jaxpr_audit import (
    EntryPoint,
    ShapePoint,
    full_registry,
    shape_point,
)

HBM_RULES = {
    "KBT201": "peak live bytes over the HBM budget at a declared shape point",
    "KBT202": "task-axis × node-axis temporary in a steady-path program",
    "KBT203": "declared donation the traced jaxpr never aliases to an output",
    "KBT204": "per-round collective payload scaling with the node axis",
}

GIB = 2**30

#: per-backend HBM budgets, GiB per device.  v5e is the deployment target
#: (ROADMAP: "assert peak live bytes fit a v5e").
BUDGET_PROFILES: Dict[str, float] = {"v5e": 16.0, "v6e": 32.0, "v5p": 95.0}
DEFAULT_PROFILE = "v5e"


def budget_bytes() -> Tuple[int, str]:
    """(budget in bytes, label).  ``KB_HBM_BUDGET`` overrides: a profile
    name ("v6e") or a GiB number ("24"); anything unparsable falls back to
    the default profile (the audit must never silently relax)."""
    raw = os.environ.get("KB_HBM_BUDGET", "").strip()
    if raw:
        if raw in BUDGET_PROFILES:
            return int(BUDGET_PROFILES[raw] * GIB), raw
        try:
            return int(float(raw) * GIB), f"{raw} GiB (KB_HBM_BUDGET)"
        except ValueError:
            pass
    return int(BUDGET_PROFILES[DEFAULT_PROFILE] * GIB), DEFAULT_PROFILE


_POINTS: Optional[Tuple[ShapePoint, ...]] = None


def shape_points() -> Tuple[ShapePoint, ...]:
    """The audit ladder: the bench's current scale, the <1s/50k-pod
    headline, Kubernetes' published envelope, and ROADMAP item 1's
    1M×100k north star."""
    global _POINTS
    if _POINTS is None:
        _POINTS = (
            shape_point("bench-20k", 20_000, 2_000),
            shape_point("headline-50k", 50_000, 5_000),
            # Kubernetes' documented limit (benchmark/configs/
            # k8s-envelope-150k-5k.json): the deployment that lives
            # node-sharded on the four chips of a v5e-4 host
            shape_point("envelope-150k", 150_000, 5_000),
            shape_point("northstar-1m", 1_000_000, 100_000),
        )
    return _POINTS


# --------------------------------------------------------------------------
# axis classification: which integer extents mean "task-scale" and
# "node-scale" at a given shape point (sharded locals included)
# --------------------------------------------------------------------------

#: node/task axis shard counts the audit meshes can produce
_SHARD_DIVS = (2, 4, 8)


def _axis_dims(sp: ShapePoint) -> Tuple[Set[int], Set[int]]:
    task = {sp.T, sp.P}
    task |= {sp.T // d for d in _SHARD_DIVS if sp.T % d == 0}
    node = {sp.N}
    node |= {sp.N // d for d in _SHARD_DIVS if sp.N % d == 0}
    # extents that are NOT evidence of a task/node axis at this point:
    # other snapshot axes that may numerically collide (e.g. warm_c=512
    # vs N/4=512 at the bench point), and anything below the noise floor.
    # warm_pi is deliberately absent — the top rerank rung IS P.
    ambiguous = {sp.J, sp.Q, sp.R, sp.W, sp.K_aff, sp.topk, sp.warm_w,
                 sp.warm_c, sp.probe_b, sp.probe_g}
    task = {d for d in task if d >= 256} - ambiguous - node
    node = {d for d in node if d >= 256} - ambiguous - {sp.T, sp.P}
    return task, node


def _dim_label(d: int, sp: ShapePoint) -> str:
    names = {sp.T: "T", sp.N: "N", sp.P: "P", sp.J: "J"}
    if d in names:
        return f"{names[d]}={d}"
    for base, tag in ((sp.T, "T"), (sp.N, "N"), (sp.P, "P")):
        for s in _SHARD_DIVS:
            if base % s == 0 and d == base // s:
                return f"{tag}/{s}={d}"
    return str(d)


def _fmt_aval(aval, sp: ShapePoint) -> str:
    shape = tuple(getattr(aval, "shape", ()) or ())
    dtype = str(getattr(aval, "dtype", "?"))
    dims = ", ".join(_dim_label(int(d), sp) for d in shape)
    return f"{dtype}[{dims}]"


# --------------------------------------------------------------------------
# liveness walk
# --------------------------------------------------------------------------


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def _var_bytes(v) -> int:
    return _aval_bytes(getattr(v, "aval", None))


def _sub_jaxprs(eqn) -> List:
    subs = []
    for param in eqn.params.values():
        vals = param if isinstance(param, (list, tuple)) else [param]
        for sub in vals:
            inner = getattr(sub, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                subs.append(inner)
            elif hasattr(sub, "eqns"):
                subs.append(sub)
    return subs


def _mesh_extent(mesh, axes) -> int:
    shape = dict(mesh.shape)
    n = 1
    for ax in axes:
        n *= int(shape.get(ax, 1))
    return n


def _shard_divisors(eqn, specs_key: str) -> List[int]:
    """Per-operand (``in_specs``) or per-result (``out_specs``) sharding
    divisor of a shard_map eqn: the product of mesh-axis extents the
    PartitionSpec maps onto the value's dims — global bytes ÷ divisor is
    what one device holds."""
    mesh = eqn.params["mesh"]
    divs = []
    for spec in eqn.params[specs_key]:
        axes: List = []
        for dim_axes in spec:
            if dim_axes is None:
                continue
            axes.extend(dim_axes if isinstance(dim_axes, tuple)
                        else (dim_axes,))
        divs.append(_mesh_extent(mesh, axes))
    return divs


@dataclasses.dataclass
class LivenessStats:
    """What one entry-point trace yields at one shape point."""

    peak_bytes: int = 0
    #: rendered task×node planes materialized anywhere in the program
    tn_temps: List[str] = dataclasses.field(default_factory=list)


class _Liveness:
    """Linear-scan liveness over a closed jaxpr, recursing into control-flow
    sub-jaxprs.  ``_scan_program`` returns the peak bytes of values a
    (sub-)program allocates itself — operands are charged by the caller."""

    #: record at most this many [T,N] planes per entry (messages stay short)
    MAX_TN_SAMPLES = 8

    def __init__(self, sp: ShapePoint, spmd_shards: int = 1):
        self.sp = sp
        self.task_dims, self.node_dims = _axis_dims(sp)
        self.tn_temps: List[str] = []
        self.tn_count = 0
        # a program jitted with node-axis in/out shardings over this many
        # devices (the pjit oracle): XLA's partitioner keeps a value that
        # carries the GLOBAL node axis sharded on it, so one device holds
        # bytes ÷ shards of it.  A model of the compiler, not a reading of
        # the jaxpr (a pjit's intermediates carry no specs); it is held to
        # the TPU compiler's own allocation at the envelope point by
        # tests/test_tpu_compile.py.  Off (1) where the node extent
        # collides with another axis: no discount is the safe side.
        collides = sp.N in {sp.T, sp.P, sp.J}
        self.spmd_shards = 1 if collides else max(1, int(spmd_shards))

    def _vb(self, v) -> int:
        """Bytes of ``v`` on one device."""
        b = _var_bytes(v)
        if self.spmd_shards > 1 and b:
            shape = getattr(getattr(v, "aval", None), "shape", ()) or ()
            if any(int(d) == self.sp.N for d in shape):
                return b // self.spmd_shards
        return b

    # -- task×node plane detection --------------------------------------

    def _note_tn(self, eqn, v) -> None:
        if not self.task_dims or not self.node_dims:
            return
        aval = getattr(v, "aval", None)
        shape = tuple(getattr(aval, "shape", ()) or ())
        if len(shape) < 2:
            return
        has_t = any(int(d) in self.task_dims for d in shape)
        has_n = any(int(d) in self.node_dims for d in shape)
        if has_t and has_n:
            self.tn_count += 1
            if len(self.tn_temps) < self.MAX_TN_SAMPLES:
                self.tn_temps.append(
                    f"{eqn.primitive} -> {_fmt_aval(aval, self.sp)}"
                    f" ({self._vb(v):,} B)")

    # -- sub-jaxpr transient extra ---------------------------------------

    def _eqn_extra(self, eqn) -> int:
        prim = str(eqn.primitive)
        if prim == "cond":
            branches = eqn.params.get("branches", ())
            return max(
                (self._scan_program(getattr(b, "jaxpr", b))
                 for b in branches), default=0)
        if prim == "while":
            cond = eqn.params.get("cond_jaxpr")
            body = eqn.params.get("body_jaxpr")
            return max(
                self._scan_program(getattr(cond, "jaxpr", cond)) if cond else 0,
                self._scan_program(getattr(body, "jaxpr", body)) if body else 0,
            )
        if prim == "scan":
            body = eqn.params.get("jaxpr")
            return (self._scan_program(getattr(body, "jaxpr", body))
                    if body is not None else 0)
        # pjit / closed_call / custom_* / remat / shard_map:
        # walk every reachable sub-jaxpr; shard_map bodies carry per-shard
        # LOCAL avals, so their internal peak is already per-device
        return sum(self._scan_program(s) for s in _sub_jaxprs(eqn))

    # -- the linear scan -------------------------------------------------

    def _scan_program(self, jaxpr) -> int:
        live = sum(self._vb(v) for v in jaxpr.constvars)
        peak = live
        n_eqns = len(jaxpr.eqns)
        last: Dict = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if hasattr(v, "aval") and not _is_literal(v):
                    last[v] = i
        for v in jaxpr.outvars:
            if hasattr(v, "aval") and not _is_literal(v):
                last[v] = n_eqns  # outputs survive the program
        owned: Dict = {}
        for i, eqn in enumerate(jaxpr.eqns):
            out_divs = (_shard_divisors(eqn, "out_specs")
                        if str(eqn.primitive) == "shard_map"
                        else [1] * len(eqn.outvars))
            out_b = 0
            for v, d in zip(eqn.outvars, out_divs):
                b = self._vb(v) // max(1, d)
                out_b += b
                self._note_tn(eqn, v)
                if last.get(v, -1) > i:
                    owned[v] = b
            extra = self._eqn_extra(eqn)
            live += out_b
            peak = max(peak, live + extra)
            # dead-on-arrival results (DropVars, unused outputs) and
            # operands at their last read free right after the eqn
            for v, d in zip(eqn.outvars, out_divs):
                if last.get(v, -1) <= i:
                    live -= self._vb(v) // max(1, d)
            for v in eqn.invars:
                if _is_literal(v):
                    continue
                if v in owned and last.get(v) == i:
                    live -= owned.pop(v)
        return peak

    # -- entry point: the top-level program ------------------------------

    def run(self, closed_jaxpr, donated_flat: Set[int]) -> LivenessStats:
        jaxpr = closed_jaxpr.jaxpr
        n_eqns = len(jaxpr.eqns)
        last: Dict = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if hasattr(v, "aval") and not _is_literal(v):
                    last[v] = i
        outset = set()
        for v in jaxpr.outvars:
            if hasattr(v, "aval") and not _is_literal(v):
                last[v] = n_eqns
                outset.add(v)

        # a top-level invar consumed ONLY by shard_map eqns is resident
        # per-device at its sharded size; everything else at global bytes
        consumers: Dict = {}
        shard_div: Dict = {}
        for eqn in jaxpr.eqns:
            is_sm = str(eqn.primitive) == "shard_map"
            divs = (_shard_divisors(eqn, "in_specs")
                    if is_sm else [1] * len(eqn.invars))
            for v, d in zip(eqn.invars, divs):
                if hasattr(v, "aval") and not _is_literal(v):
                    consumers.setdefault(v, set()).add(d if is_sm else 1)
        for v, divs in consumers.items():
            if len(divs) == 1:
                shard_div[v] = next(iter(divs))

        def in_bytes(v) -> int:
            return self._vb(v) // max(1, shard_div.get(v, 1))

        live = sum(self._vb(v) for v in jaxpr.constvars)
        live += sum(in_bytes(v) for v in jaxpr.invars)
        peak = live
        owned: Dict = {}
        for idx, v in enumerate(jaxpr.invars):
            if idx in donated_flat and v not in outset:
                if v in last and last[v] < n_eqns:
                    owned[v] = in_bytes(v)
                else:
                    live -= in_bytes(v)  # donated and never read: free now

        for i, eqn in enumerate(jaxpr.eqns):
            out_divs = (_shard_divisors(eqn, "out_specs")
                        if str(eqn.primitive) == "shard_map"
                        else [1] * len(eqn.outvars))
            out_b = 0
            for v, d in zip(eqn.outvars, out_divs):
                b = self._vb(v) // max(1, d)
                out_b += b
                self._note_tn(eqn, v)
                if last.get(v, -1) > i and v not in shard_div:
                    owned[v] = b
                    shard_div[v] = d  # results keep their sharded residency
                elif last.get(v, -1) > i:
                    owned[v] = b
            extra = self._eqn_extra(eqn)
            live += out_b
            peak = max(peak, live + extra)
            for v, d in zip(eqn.outvars, out_divs):
                if last.get(v, -1) <= i:
                    live -= self._vb(v) // max(1, d)
            for v in eqn.invars:
                if _is_literal(v):
                    continue
                if v in owned and last.get(v) == i:
                    live -= owned.pop(v)
        return LivenessStats(peak_bytes=peak, tn_temps=list(self.tn_temps))


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def peak_live_bytes(closed_jaxpr, donated_flat: Iterable[int] = (),
                    sp: Optional[ShapePoint] = None,
                    spmd_shards: int = 1) -> int:
    """Peak live bytes of one closed jaxpr (donated flat-invar indices get
    the free-after-last-read credit).  The raw engine behind KBT201,
    exposed for tests and what-fits probes (guard/fit.py asks it whether a
    demotion's target holds the cluster).  ``spmd_shards``: see
    :class:`_Liveness`."""
    from kube_batch_tpu.analysis.jaxpr_audit import _AUDIT_POINT

    lv = _Liveness(sp or _AUDIT_POINT, spmd_shards)
    return lv.run(closed_jaxpr, set(donated_flat)).peak_bytes


# --------------------------------------------------------------------------
# donation mapping + realization (KBT203)
# --------------------------------------------------------------------------


def _flat_ranges(args, n_flat: int) -> Optional[List[Tuple[int, int]]]:
    """Per-argument (start, stop) ranges into the traced flat invars, by
    counting array-typed pytree leaves (static config objects and python
    scalars contribute none).  None when the count disagrees with the
    trace — the caller then skips donation modeling rather than guess."""
    import jax

    ranges: List[Tuple[int, int]] = []
    i = 0
    for a in args:
        leaves = jax.tree_util.tree_leaves(a)
        c = sum(1 for leaf in leaves
                if hasattr(leaf, "shape") and hasattr(leaf, "dtype"))
        ranges.append((i, i + c))
        i += c
    return ranges if i == n_flat else None


def _donated_flat(entry: EntryPoint, args, n_flat: int) -> Optional[Set[int]]:
    """Flat invar indices of the entry's DECLARED accelerator donation
    (donate["*"] — CPU wrappers gate donation off, but the budget models
    the accelerator).  None when the argnum→flat mapping is ambiguous."""
    declared = entry.donate.get("*", ())
    if not declared:
        return set()
    ranges = _flat_ranges(args, n_flat)
    if ranges is None:
        return None
    flat: Set[int] = set()
    for argnum in declared:
        if argnum >= len(ranges):
            return None
        lo, hi = ranges[argnum]
        flat.update(range(lo, hi))
    return flat


def _unrealized_donations(entry: EntryPoint, args,
                          closed_jaxpr) -> List[Tuple[int, List[str]]]:
    """[(argnum, descriptions)] for declared donated args where NO flat
    component can alias any output (shape+dtype match, each output slot
    consumed once — mirroring XLA's buffer-donation matching)."""
    declared = entry.donate.get("*", ())
    if not declared:
        return []
    jaxpr = closed_jaxpr.jaxpr
    ranges = _flat_ranges(args, len(jaxpr.invars))
    if ranges is None:
        return []
    pool: List = []
    for v in jaxpr.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            pool.append((tuple(aval.shape), str(aval.dtype)))
    out: List[Tuple[int, List[str]]] = []
    for argnum in sorted(declared):
        if argnum >= len(ranges):
            continue
        lo, hi = ranges[argnum]
        avals = [getattr(jaxpr.invars[i], "aval", None) for i in range(lo, hi)]
        matched_any = False
        for aval in avals:
            key = (tuple(aval.shape), str(aval.dtype))
            if key in pool:
                pool.remove(key)  # each output aliases at most one input
                matched_any = True
        if avals and not matched_any:
            out.append((argnum, [
                f"{str(a.dtype)}{list(a.shape)}" for a in avals]))
    return out


# --------------------------------------------------------------------------
# per-entry, per-point audit
# --------------------------------------------------------------------------


@dataclasses.dataclass
class EntryReport:
    """One (entry, shape point) audit result — stats plus raw findings
    (allowlist not yet applied)."""

    entry: str
    point: str
    steady: bool
    traced: bool
    peak_bytes: int = 0
    budget: int = 0
    findings: List[Tuple[str, str]] = dataclasses.field(default_factory=list)


def _fmt_bytes(b: int) -> str:
    if b >= GIB:
        return f"{b / GIB:.2f} GiB"
    return f"{b / 2**20:.1f} MiB"


def audit_entry_at(entry: EntryPoint, sp: ShapePoint,
                   budget: Optional[int] = None,
                   label: Optional[str] = None) -> EntryReport:
    """Trace one entry at one shape point and run KBT201-204 over the
    closed jaxpr.  A build/trace failure is a KBT000 finding naming the
    shape point (a broken entry must not read as clean OR kill the tier —
    a shape-derived python branch blowing up at 1M×100k is exactly the
    regression class this audit exists to surface)."""
    from kube_batch_tpu.utils.jitstats import collective_inventory

    if budget is None:
        budget, label = budget_bytes()
    rep = EntryReport(entry=entry.name, point=sp.name, steady=entry.steady,
                      traced=False, budget=budget)
    try:
        fn, args = entry.build(sp)
        traced = fn.trace(*args)
        closed = traced.jaxpr
    except Exception as e:  # noqa: BLE001 — report, don't crash the tier
        rep.findings.append((
            "KBT000",
            f"entry point failed to trace at shape point {sp.name} "
            f"(T={sp.T}, N={sp.N}): {type(e).__name__}: {e}"))
        return rep
    rep.traced = True

    donated = _donated_flat(entry, args, len(closed.jaxpr.invars))
    lv = _Liveness(sp, entry.spmd_shards)
    stats = lv.run(closed, donated or set())
    rep.peak_bytes = stats.peak_bytes

    # KBT201: fit the per-device budget
    if stats.peak_bytes > budget:
        rep.findings.append((
            "KBT201",
            f"peak live bytes {_fmt_bytes(stats.peak_bytes)} exceed the "
            f"{label or 'v5e'} budget {_fmt_bytes(budget)} at shape point "
            f"{sp.name} (T={sp.T}, N={sp.N}) — "
            f"{stats.peak_bytes / budget:.1f}x over"))

    # KBT202: steady-path programs must stay off task×node planes
    if entry.steady and lv.tn_count:
        sample = "; ".join(stats.tn_temps[:3])
        rep.findings.append((
            "KBT202",
            f"{lv.tn_count} task-axis × node-axis temporar"
            f"{'y' if lv.tn_count == 1 else 'ies'} in a steady-path "
            f"program at {sp.name} (e.g. {sample}) — the steady dispatch "
            "contract is the compacted [P, K] candidate geometry "
            "(ROADMAP 1)"))

    # KBT203: declared donations must be aliasable into outputs
    for argnum, avals in _unrealized_donations(entry, args, closed):
        rep.findings.append((
            "KBT203",
            f"declared donation of arg {argnum} ({', '.join(avals)}) has "
            "no shape/dtype-matching output to alias — XLA would ignore "
            "it and the budget's free-after-last-read credit is fiction"))

    # KBT204: per-round collectives must not scale with the node axis
    _, node_dims = _axis_dims(sp)
    inv = collective_inventory(closed, detail=True)
    node_sites = [
        s for s in inv.get("sites", ())
        if s["depth"] >= 1 and any(int(d) in node_dims for d in s["shape"])
    ]
    if node_sites:
        parts = []
        for s in node_sites[:4]:
            dims = ", ".join(_dim_label(int(d), sp) for d in s["shape"])
            trip = (f" ×{s['inner_trips']}/round" if s["inner_trips"] > 1
                    else "")
            trip += " ×unbounded-inner-loop" if s["unbounded_trips"] else ""
            parts.append(f"{s['prim']}[{s['dtype']}[{dims}]] = "
                         f"{s['bytes']:,} B{trip}")
        rep.findings.append((
            "KBT204",
            f"{len(node_sites)} per-round collective(s) with node-axis "
            f"payloads at {sp.name}: {'; '.join(parts)} — the cross-host "
            "contract is O(tasks) bytes per bidding round"))
    return rep


# --------------------------------------------------------------------------
# allowlist: (entry glob, rule, point glob) → mandatory reason
# --------------------------------------------------------------------------

#: The tier-C suppression registry — and deliberately ALSO the burn-down
#: list for ROADMAP item 1 (sparse-first scale jump): every entry names the
#: ROADMAP sub-item that deletes it.  Stale entries (nothing matched) fail
#: the audit, so a fix can't leave its waiver behind.
HBM_ALLOWLIST: Dict[Tuple[str, str, str], str] = {
    # -- ROADMAP 1.(1): the evict bids are [claimant, node] planes --------
    # (PR 36: the single-device programs bid on the pending bucket, [P, N],
    # wherever the pending set fits it — the ``[*,compact]`` entries — and
    # on [T, N] in the full-axis fallback; the sharded bodies keep the task
    # axis and hold [T, N/shards] per device.  One glob covers a program's
    # both shapes: same rule, same burn-down item)
    ("ops.eviction.evict_solve[*]", "KBT202", "*"):
        "ROADMAP 1.(1): eviction scores [P, N] bid planes on the pending "
        "bucket (as allocate's table build does, 1.(2)) and full [T, N] "
        "planes in the full-axis fallback (a pending set past the bucket, "
        "a task axis too small to have one); the candidate-table + "
        "warm-carry rebuild over per-(queue, node) capacity keys is the "
        "planned fix",
    ("ops.eviction.evict_solve[*]", "KBT201", "northstar-1m"):
        "ROADMAP 1.(1): the bid planes blow the v5e budget at "
        "1M\u00d7100k, [P, N] at P=65536 (~26 GiB a plane) as well as the "
        "fallback's [T, N]; evict is gated to \u2264headline scale until "
        "sparse eviction lands",
    ("ops.invariants.evict_sentinel_solve[*]", "KBT202", "*"):
        "ROADMAP 1.(1): sentinel-fused evict inherits the bare solve's "
        "bid planes, [P, N] on the bucket and [T, N] in the fallback",
    ("ops.invariants.evict_sentinel_solve[*]", "KBT201", "northstar-1m"):
        "ROADMAP 1.(1): sentinel-fused evict inherits the bare solve's "
        "over-budget planes at 1M\u00d7100k, both shapes",
    ("parallel.mesh.*sharded_evict_solve[*]", "KBT202", "*"):
        "ROADMAP 1.(1): sharded evict (both impls, sentinel-fused "
        "included) bids on the whole task axis: it shards the bid planes "
        "over nodes but still holds [T, N/shards] per device",
    ("parallel.mesh.*sharded_evict_solve[*]", "KBT201", "northstar-1m"):
        "ROADMAP 1.(1): [T, N/8] per device is ~200 GiB at 1M\u00d7100k "
        "\u2014 sharding alone cannot absorb a full-matrix plane",
    # -- ROADMAP 1.(2): the compacted topk path's table build + shard_map
    #    exhaustion fallback keep [P, N] score/hash planes ----------------
    ("ops.assignment.allocate_topk_solve", "KBT202", "*"):
        "ROADMAP 1.(2): the candidate-table build scores [P, N] planes "
        "(and the exhaustion fallback re-enters them); a blocked "
        "table rebuild is the planned fix",
    ("ops.assignment.allocate_topk_solve", "KBT201", "northstar-1m"):
        "ROADMAP 1.(2): the [P, N] build planes are ~26 GiB each at "
        "P=65536, N=100k \u2014 over v5e budget until the blocked rebuild",
    ("ops.invariants.allocate_topk_sentinel_solve", "KBT202", "*"):
        "ROADMAP 1.(2): sentinel-fused topk inherits the table build's "
        "[P, N] planes",
    ("ops.invariants.allocate_topk_sentinel_solve", "KBT201",
     "northstar-1m"):
        "ROADMAP 1.(2): sentinel-fused topk inherits the over-budget "
        "build planes at 1M\u00d7100k",
    ("parallel.mesh.*sharded_allocate_topk_solve[*]", "KBT202", "*"):
        "ROADMAP 1.(2): the sharded topk build/fallback holds "
        "[P, N/shards] score/hash planes per device (pjit oracle: "
        "unsharded [P, N] \u2014 charged at global bytes, documented "
        "slack)",
    ("parallel.mesh.*sharded_allocate_topk_solve[*]", "KBT201",
     "northstar-1m"):
        "ROADMAP 1.(2): the sharded build planes still exceed v5e at "
        "1M\u00d7100k; re-enter via blocked table REBUILD instead",
    ("ops.assignment.warm_allocate_solve", "KBT202", "*"):
        "ROADMAP 1.(2): the warm refresh escalates to the cold table "
        "build ([P, N] planes) when the carry is invalid; same fix",
    ("ops.assignment.warm_allocate_solve", "KBT201", "northstar-1m"):
        "ROADMAP 1.(2): warm's cold-escalation branch carries the build "
        "planes past v5e at 1M\u00d7100k",
    ("ops.invariants.warm_allocate_sentinel_solve", "KBT202", "*"):
        "ROADMAP 1.(2): sentinel-fused warm inherits the cold-escalation "
        "[P, N] planes",
    ("ops.invariants.warm_allocate_sentinel_solve", "KBT201",
     "northstar-1m"):
        "ROADMAP 1.(2): sentinel-fused warm inherits the over-budget "
        "escalation planes at 1M\u00d7100k",
    ("parallel.mesh.*sharded_warm_allocate_solve[*]", "KBT202", "*"):
        "ROADMAP 1.(2): sharded warm (both impls, sentinel-fused "
        "included) inherits the build/fallback planes per device",
    ("parallel.mesh.*sharded_warm_allocate_solve[*]", "KBT201",
     "northstar-1m"):
        "ROADMAP 1.(2): sharded warm's escalation planes still exceed "
        "v5e at 1M\u00d7100k",
    # -- ROADMAP R2: Kubernetes' published envelope (150k pods x 5k nodes)
    #    is the deployment that lives on the 4 chips of a v5e-4 host.  The
    #    single-device full-matrix programs are over one chip's budget by
    #    this tier's (conservative) count; their node-sharded twins hold
    #    4.3-7.0 GiB a device on a 4-device mesh (tests/test_hbm_audit.py
    #    TestEnvelope), and that is where this deployment runs them.  (The
    #    TPU compiler allocates 11.5 GiB for the cold program on one chip,
    #    and one chip has run the deployment: PERF.md section 7.  The count
    #    here overestimates by design; the waivers say where it runs.) -----
    ("ops.assignment.allocate_solve", "KBT201", "envelope-150k"):
        "ROADMAP R2: this deployment lives on 4 chips; the cold "
        "full-matrix drain runs as sharded_allocate_solve there",
    ("ops.invariants.allocate_sentinel_solve", "KBT201", "envelope-150k"):
        "ROADMAP R2: this deployment lives on 4 chips; sentinel-fused "
        "cold drain, same verdict as the bare program",
    ("ops.assignment.failure_histogram_solve", "KBT201", "envelope-150k"):
        "ROADMAP R2: this deployment lives on 4 chips; the full-walk "
        "histogram runs as sharded_failure_histogram there",
    ("ops.eviction.evict_solve[*]", "KBT201", "envelope-150k"):
        "ROADMAP R2: this deployment lives on 4 chips; reclaim/preempt "
        "run as sharded_evict_solve there (this covers the full-axis "
        "fallback alone: on the pending bucket the program fits one chip)",
    ("ops.invariants.evict_sentinel_solve[*]", "KBT201", "envelope-150k"):
        "ROADMAP R2: this deployment lives on 4 chips; sentinel-fused "
        "evict, same verdict as the bare program (the full-axis fallback "
        "alone)",
    # -- cold oracles + diagnostics: not steady-path (no KBT202 claim),
    #    but their full-matrix peaks are on the same ROADMAP 1 burn-down --
    ("ops.assignment.allocate_solve", "KBT201", "northstar-1m"):
        "ROADMAP 1: the full-matrix allocate is the COLD bit-exactness "
        "oracle; at 1M\u00d7100k only the compacted path dispatches \u2014 "
        "the oracle runs at \u2264headline scale",
    ("ops.invariants.allocate_sentinel_solve", "KBT201", "northstar-1m"):
        "ROADMAP 1: sentinel-fused full-matrix oracle, same scale gate as "
        "the bare oracle",
    ("parallel.mesh.sharded_allocate_solve[*]", "KBT201", "northstar-1m"):
        "ROADMAP 1: sharded full-matrix oracle (incl. the 2-D mesh "
        "variant): [T, N/shards] per device cannot fit at 1M\u00d7100k; "
        "cross-check runs at \u2264headline scale",
    ("parallel.mesh.sentinel_sharded_allocate_solve[*]", "KBT201",
     "northstar-1m"):
        "ROADMAP 1: sentinel-fused sharded oracle, same scale gate",
    ("ops.assignment.failure_histogram_solve", "KBT201", "northstar-1m"):
        "ROADMAP 1: the full-walk failure histogram is an on-demand "
        "diagnostic (not dispatched per cycle); the bucket variant is the "
        "at-scale surface and the node axis still wants compaction",
    ("parallel.mesh.sharded_failure_histogram[*]", "KBT201",
     "northstar-1m"):
        "ROADMAP 1: sharded full-walk histogram, same on-demand diagnostic "
        "verdict",
    ("ops.assignment.failure_histogram_bucket_solve", "KBT201",
     "northstar-1m"):
        "ROADMAP 1: the bucket histogram still walks [P, N] reason "
        "planes; per-(reason, node-shard) partials are the planned "
        "compaction",
    ("parallel.mesh.sharded_failure_histogram_bucket[*]", "KBT201",
     "northstar-1m"):
        "ROADMAP 1: sharded bucket histogram holds [P, N/shards] reason "
        "planes per device \u2014 1.2\u00d7 over v5e at 1M\u00d7100k, "
        "closest corner to done",
}


def _glob_match(name: str, pat: str) -> bool:
    """fnmatch-style ``*`` wildcards with NO character classes — entry
    names contain literal brackets (``evict_solve[reclaim]``), so the
    pattern language is: ``*`` matches anything, all else is literal."""
    rx = re.escape(pat).replace(r"\*", ".*")
    return re.fullmatch(rx, name) is not None


def _allowlist_reason(allowlist, entry_name: str, rule: str,
                      point: str) -> Optional[Tuple[Tuple, str]]:
    for key, reason in allowlist.items():
        e_pat, a_rule, p_pat = key
        if (a_rule == rule and _glob_match(entry_name, e_pat)
                and _glob_match(point, p_pat)):
            return key, reason
    return None


# --------------------------------------------------------------------------
# the tier driver
# --------------------------------------------------------------------------


def run_hbm_audit(
    registry: Optional[Sequence[EntryPoint]] = None,
    points: Optional[Sequence[ShapePoint]] = None,
    select: Optional[Sequence[str]] = None,
    allowlist: Optional[Dict[Tuple[str, str, str], str]] = None,
) -> List[Finding]:
    """Audit every registered entry point at every ladder point.  Returns
    engine Findings at paths ``<hbm:entry@point>`` — allowlisted ones
    dropped, empty-reason and STALE allowlist entries surfaced as KBT000
    (same contract as tier A/B suppressions: a waiver that no longer
    waives anything must be deleted, not accumulate)."""
    if registry is None:
        registry = full_registry()
    if points is None:
        points = shape_points()
    if allowlist is None:
        allowlist = HBM_ALLOWLIST

    findings: List[Finding] = []
    used: Set[Tuple] = set()
    for entry in registry:
        for sp in points:
            rep = audit_entry_at(entry, sp)
            path = f"<hbm:{entry.name}@{sp.name}>"
            for rule, message in rep.findings:
                hit = (None if rule == "KBT000" else
                       _allowlist_reason(allowlist, entry.name, rule, sp.name))
                if hit is not None:
                    key, reason = hit
                    used.add(key)
                    if not reason.strip():
                        findings.append(Finding(
                            "KBT000", path, 0, 0,
                            f"allowlist[{key}] has no reason — "
                            "suppression ignored"))
                    continue
                findings.append(Finding(rule, path, 0, 0, message))

    # stale allowlist entries: only judged when the corresponding entries
    # and points were actually in this run (a single-device run must not
    # flag sharded-namespace waivers, nor a one-point run the rest of the
    # ladder)
    entry_names = [e.name for e in registry]
    point_names = [sp.name for sp in points]
    for key, reason in allowlist.items():
        if key in used:
            continue
        e_pat, _rule, p_pat = key
        covered = (
            any(_glob_match(n, e_pat) for n in entry_names)
            and any(_glob_match(n, p_pat) for n in point_names)
        )
        if covered:
            findings.append(Finding(
                "KBT000", "<hbm:allowlist>", 0, 0,
                f"stale allowlist entry {key}: matched no finding — the "
                "corner it waived is fixed; delete the entry "
                f"(reason was: {reason})"))

    if select is not None:
        wanted = set(select) | {"KBT000"}
        findings = [f for f in findings if f.rule in wanted]
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def headroom_report(
    registry: Optional[Sequence[EntryPoint]] = None,
    points: Optional[Sequence[ShapePoint]] = None,
) -> Dict:
    """bytes-vs-budget per entry per shape point."""
    if registry is None:
        registry = full_registry()
    if points is None:
        points = shape_points()
    budget, label = budget_bytes()
    entries: Dict[str, Dict[str, Dict]] = {}
    for entry in registry:
        per_point: Dict[str, Dict] = {}
        for sp in points:
            rep = audit_entry_at(entry, sp, budget=budget, label=label)
            per_point[sp.name] = {
                "traced": rep.traced,
                "peak_bytes": rep.peak_bytes,
                "headroom_bytes": budget - rep.peak_bytes,
                "over_budget": rep.peak_bytes > budget,
                "findings": [r for r, _ in rep.findings],
            }
        entries[entry.name] = per_point
    return {
        "budget_bytes": budget,
        "budget_profile": label,
        "points": [
            {"name": sp.name, "tasks": sp.tasks, "nodes": sp.nodes,
             "T": sp.T, "N": sp.N, "P": sp.P, "topk": sp.topk}
            for sp in points
        ],
        "entries": entries,
    }

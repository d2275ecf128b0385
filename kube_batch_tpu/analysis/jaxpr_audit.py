"""Tier B: jaxpr-level audit of the jitted entry points.

Tier A (the AST rules) sees source text; XLA sees the traced computation —
and the gap between them is where the PR 3 hot path's silent bugs live: a
float64 upcast that doubles every buffer, a `device_put` smuggled into the
middle of a compiled program, a host callback stalling the pipeline, a
donation that quietly stopped happening.  None of those fail a test on CPU;
all of them cost the <1s/50k-pod target on a real accelerator.  This
module is the JaxPruner-style answer (PAPERS.md): audit what actually gets
compiled, not what the source looks like.

Mechanism: a REGISTRY of the package's jitted entry points, read off the
program table of parallel/mesh.py (plus the resident swaps, which the
table does not own).  Each entry is traced with
ABSTRACT inputs (jax.ShapeDtypeStruct — no device work, no compile) under
``jax.enable_x64`` so dtype promotion is visible instead of
silently canonicalized away, then the closed jaxpr is walked recursively
(while/cond/scan/pjit sub-jaxprs included) and linted:

- **KBT101 float64 upcast** — any f64 aval anywhere in the jaxpr when the
  declared inputs are f32/i32.  Integer widening under the x64 probe is
  canonicalization noise and ignored.
- **KBT102 in-graph transfer** — a `device_put` targeting a concrete
  device or performing a real copy (alias placements with device=None are
  how jnp constants materialize and are benign).
- **KBT103 host callback** — `pure_callback`/`io_callback`/`debug_callback`
  inside a hot-path program: a host round-trip per invocation.
- **KBT104 donation mismatch** — the wrapper's traced donate_argnums
  differ from what the registry entry declares for the current backend
  (e.g. someone drops donate_argnums from the resident swap: CPU tests
  stay green, every TPU cycle silently double-allocates).

Suppression: registry entries carry ``allow={"KBT10x": "reason"}`` — the
reason is mandatory, mirroring the `# kbt: allow` contract.

Run via ``python -m kube_batch_tpu.analysis --jaxpr`` (adds this tier to
the static run; ``--jaxpr-only`` skips tier A) or the tier-1
self-enforcement test.  Tracing is abstract, so the whole audit is
sub-second after the jax import.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from kube_batch_tpu.analysis.engine import Finding

AUDIT_RULES = {
    "KBT101": "float64 upcast in a traced entry point",
    "KBT102": "in-graph device transfer in a traced entry point",
    "KBT103": "host callback in a traced entry point",
    "KBT104": "donation mismatch between wrapper and registry declaration",
}

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "callback", "debug_callback"}


@dataclasses.dataclass
class EntryPoint:
    """One jitted entry point the audit traces.

    ``build`` returns ``(jitted_fn, args)`` with abstract (ShapeDtypeStruct)
    array arguments — static arguments go in baked into ``args`` as real
    values.  ``build`` accepts an optional ShapePoint: ``build()`` traces at
    the tier-B audit extents, ``build(sp)`` at a tier-C shape-ladder point.
    ``donate`` maps backend name → expected donate_argnums, with ``"*"`` as
    the fallback (the resident swap donates everywhere except CPU).
    ``allow`` suppresses one audit rule for this entry, reason mandatory.
    ``steady`` declares the program steady-path/sparse: dispatched every
    cycle at scale, so tier C's KBT202 asserts it materializes no
    task-axis × node-axis plane (the full-matrix oracle is NOT steady: it is
    the cold reference)."""

    name: str
    build: Callable[..., Tuple[Callable, Tuple]]
    donate: Dict[str, Tuple[int, ...]] = dataclasses.field(
        default_factory=lambda: {"*": ()})
    allow: Dict[str, str] = dataclasses.field(default_factory=dict)
    steady: bool = False
    #: node shards of a program jitted with node-axis in/out shardings and
    #: no shard_map (the pjit oracles): tier C charges a value that carries
    #: the global node axis at bytes ÷ this (hbm_audit._Liveness)
    spmd_shards: int = 1


# --------------------------------------------------------------------------
# abstract input builders
# --------------------------------------------------------------------------

# small-but-representative axis sizes: which primitives appear in the trace
# does not depend on extents, and small shapes keep tracing fast.  W/Wt=1
# matches a fresh ColumnStore; K/Kp=1 is the padded sparse-row floor.
_T, _N, _J, _Q, _R, _W, _K = 16, 8, 4, 2, 3, 1, 1


@dataclasses.dataclass(frozen=True)
class ShapePoint:
    """One rung of the tier-C shape ladder: the abstract axis extents every
    entry point is traced at when the HBM audit asks "does this program fit
    at THIS scale".  Tier B traces at `_AUDIT_POINT` (the tiny historical
    extents — primitive coverage only); tier C re-traces the same builders
    at the bench shapes, the 50k×5k headline, and the 1M×100k north star,
    where peak live bytes are the production numbers.

    ``T``/``N``/``J`` are the padded capacity buckets (api.snapshot.bucket)
    for ``tasks``/``nodes`` pods/nodes; ``P``/``topk`` are the compacted
    [P, K] dispatch extents the production sizing would pick at this scale,
    and ``warm_*`` mirror api.resident's warm-carry plan for the same."""

    name: str
    tasks: int           # nominal pod count (pre-bucketing)
    nodes: int           # nominal node count (pre-bucketing)
    T: int               # task capacity bucket
    N: int               # node capacity bucket
    J: int               # job capacity bucket
    Q: int               # queue count
    R: int               # resource kinds
    W: int               # label/selector bitset words
    K_aff: int           # padded affinity rows
    P: int               # compacted pending bucket
    topk: int            # candidate width K of the [P, K] table
    warm_w: int          # warm carried-table stored width
    warm_c: int          # warm changed-node slots
    warm_pi: int         # warm rerank rung (re-ranked rows per refresh)
    probe_b: int = 2     # what-if probe batch
    probe_g: int = 4     # what-if gang width


#: tier B's extents as a ShapePoint — `build()` with no argument traces here
_AUDIT_POINT = ShapePoint(
    name="audit", tasks=_T, nodes=_N, T=_T, N=_N, J=_J, Q=_Q, R=_R, W=_W,
    K_aff=_K, P=8, topk=2, warm_w=4, warm_c=4, warm_pi=4,
    probe_b=2, probe_g=4,
)


def shape_point(name: str, tasks: int, nodes: int, R: int = 8,
                W: int = 4) -> ShapePoint:
    """Derive a ladder point from nominal pod/node counts using the SAME
    sizing the production path uses: capacity buckets from
    api.snapshot.bucket, the pending bucket from actions.allocate's
    ``fit ≤ T // 4`` rule (largest fitting bucket = the worst case the
    audit must cover), and the warm plan's width/changed/rung arithmetic
    from api.resident.  Keeping these derivations shared — not copied —
    is the point: if the sizing rules move, the audit moves with them."""
    from kube_batch_tpu.actions.allocate import TOPK_DEFAULT, TOPK_PEND_BUCKETS
    from kube_batch_tpu.api.resident import (
        WARM_CHANGED_BUCKETS,
        WARM_WIDTH_MARGIN,
        warm_rerank_rungs,
    )
    from kube_batch_tpu.api.snapshot import bucket

    T, N = bucket(tasks), bucket(nodes)
    J = bucket(max(8, tasks // 4))
    fit = [b for b in TOPK_PEND_BUCKETS if b <= T // 4]
    P = fit[-1] if fit else TOPK_PEND_BUCKETS[0]
    k = TOPK_DEFAULT
    changed = [c for c in WARM_CHANGED_BUCKETS if c < N]
    warm_c = changed[-1] if changed else WARM_CHANGED_BUCKETS[0]
    return ShapePoint(
        name=name, tasks=tasks, nodes=nodes, T=T, N=N, J=J, Q=8, R=R, W=W,
        K_aff=4, P=P, topk=k, warm_w=k + WARM_WIDTH_MARGIN, warm_c=warm_c,
        warm_pi=warm_rerank_rungs(P)[-1], probe_b=2, probe_g=4,
    )


def abstract_snapshot(T=_T, N=_N, J=_J, Q=_Q, R=_R, W=_W, K=_K):
    """A DeviceSnapshot of ShapeDtypeStructs — the audit's default small
    shapes, or caller-supplied bucket sizes (the bench traces the
    collective inventory at its REAL padded shapes so the byte counts are
    the production program's)."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S

    from kube_batch_tpu.api.snapshot import DeviceSnapshot

    f32, i32, b, u32 = jnp.float32, jnp.int32, jnp.bool_, jnp.uint32
    return DeviceSnapshot(
        task_req=S((T, R), f32), task_resreq=S((T, R), f32),
        task_job=S((T,), i32), task_prio=S((T,), i32),
        task_creation=S((T,), i32), task_status=S((T,), i32),
        task_valid=S((T,), b), task_pending=S((T,), b),
        task_best_effort=S((T,), b), task_sel_bits=S((T, W), u32),
        task_sel_impossible=S((T,), b), task_tol_bits=S((T, W), u32),
        task_node=S((T,), i32), task_critical=S((T,), b),
        task_needs_host=S((T,), b), task_aff_idx=S((K,), i32),
        task_aff_mask=S((K, N), b), task_pref_idx=S((K,), i32),
        task_pref_node=S((K, N), f32), task_pref_pod=S((K, N), f32),
        node_idle=S((N, R), f32), node_releasing=S((N, R), f32),
        node_used=S((N, R), f32), node_alloc=S((N, R), f32),
        node_valid=S((N,), b), node_sched=S((N,), b),
        node_label_bits=S((N, W), u32), node_taint_bits=S((N, W), u32),
        job_min_avail=S((J,), i32), job_ready=S((J,), i32),
        job_queue=S((J,), i32), job_prio=S((J,), i32),
        job_creation=S((J,), i32), job_valid=S((J,), b),
        job_schedulable=S((J,), b), job_allocated=S((J, R), f32),
        queue_weight=S((Q,), f32), queue_capability=S((Q, R), f32),
        queue_alloc=S((Q, R), f32), queue_request=S((Q, R), f32),
        queue_valid=S((Q,), b), total=S((R,), f32), quanta=S((R,), f32),
    )


def _snap(ax: ShapePoint):
    return abstract_snapshot(
        T=ax.T, N=ax.N, J=ax.J, Q=ax.Q, R=ax.R, W=ax.W, K=ax.K_aff)


#: audit-scale pending bucket + candidate width for the compacted solve
_P, _TOPK = 8, 2


def _abstract_pend_rows(P=_P):
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S

    return S((P,), jnp.int32)


#: warm-carry audit shapes: stored width W, changed-node slots, rerank
#: rung — small audit extents like _T/_N, NOT the dispatch's real sizing
#: (W = K + WARM_WIDTH_MARGIN there); the traced primitives don't depend
#: on the extents
_WARM_W, _WARM_C, _WARM_PI = 2 * _TOPK, 4, 4


def _abstract_warm_args(P=_P, W=_WARM_W, C=_WARM_C, Pi=_WARM_PI):
    """(pend_rows, table×4, plan×4) ShapeDtypeStructs of the warm solve."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S

    return (
        S((P,), jnp.int32),
        S((P, W), jnp.int32), S((P, W), jnp.int32), S((P, W), jnp.int32),
        S((P,), jnp.bool_),
        S((P,), jnp.int32), S((C,), jnp.int32),
        S((Pi,), jnp.int32), S((Pi,), jnp.int32),
    )


def _warm_donation() -> Dict[str, Tuple[int, ...]]:
    # the warm solve donates the stale carried-table buffers into the
    # refresh everywhere donation is supported; CPU skips it.  Literal
    # positions: must match ops.assignment.WARM_TABLE_ARGNUMS, which the
    # warm entry's KBT104 check pins per backend.
    return {"cpu": (), "*": (2, 3, 4, 5)}


def _abstract_probe_batch(B=2, G=4, R=_R, W=_W):
    """A ProbeBatch of ShapeDtypeStructs + the [G] row oracle — the query
    plane's serving shapes at audit scale."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S

    from kube_batch_tpu.ops.probe import ProbeBatch

    f32, i32, b, u32 = jnp.float32, jnp.int32, jnp.bool_, jnp.uint32
    batch = ProbeBatch(
        req=S((B, G, R), f32), valid=S((B, G), b),
        min_avail=S((B,), i32), queue=S((B,), i32), prio=S((B,), i32),
        sel_bits=S((B, W), u32), sel_impossible=S((B,), b),
        tol_bits=S((B, W), u32), min_res=S((B, R), f32),
        has_min_res=S((B,), b),
    )
    return batch, S((G,), i32)


# ---- one abstract-arguments maker per kind of parallel/mesh.py's program
# table: (config, statics, arrays) at a shape point — the only thing an
# entry adds to the table's row


def _args_full(ax: ShapePoint):
    from kube_batch_tpu.ops.assignment import AllocateConfig

    return AllocateConfig(), {}, (_snap(ax),)


def _args_topk(ax: ShapePoint):
    from kube_batch_tpu.ops.assignment import AllocateConfig

    return AllocateConfig(topk=ax.topk), {}, (
        _snap(ax), _abstract_pend_rows(ax.P))


def _args_warm(ax: ShapePoint):
    from kube_batch_tpu.ops.assignment import AllocateConfig

    return AllocateConfig(topk=ax.warm_w), {"k_min": ax.topk}, (
        _snap(ax),
        *_abstract_warm_args(P=ax.P, W=ax.warm_w, C=ax.warm_c, Pi=ax.warm_pi))


def _args_evict(ax: ShapePoint, mode, compact=False):
    from kube_batch_tpu.ops.eviction import EvictConfig

    rows = (_abstract_pend_rows(ax.P),) if compact else ()
    return EvictConfig(mode=mode), {}, (_snap(ax),) + rows


def _args_fail_hist(ax: ShapePoint):
    return None, {}, (_snap(ax),)


def _args_fail_hist_bucket(ax: ShapePoint):
    return None, {}, (_snap(ax), _abstract_pend_rows(ax.P))


def _args_probe(ax: ShapePoint, topk=False):
    """``topk``: the probe traced with a topk>0 config.  The query plane
    reuses the session's AllocateConfig, and the probe's [G, N] head
    ignores the compaction knob by design (a gang's task axis is already
    tiny) — that entry pins that the knob stays inert on the program."""
    from kube_batch_tpu.ops.assignment import AllocateConfig
    from kube_batch_tpu.ops.eviction import EvictConfig

    batch, rows = _abstract_probe_batch(
        B=ax.probe_b, G=ax.probe_g, R=ax.R, W=ax.W)
    # with_evictions=True traces the superset program (head + admission +
    # histogram + the eviction probe's while_loop)
    return AllocateConfig(topk=ax.topk if topk else 0), {
        "evict_config": EvictConfig(mode="preempt"), "with_evictions": True,
    }, (_snap(ax), batch, rows)


def _args_gate(ax: ShapePoint):
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S

    return None, {}, (
        S((ax.J, ax.R), jnp.float32), S((ax.J,), jnp.bool_),
        S((ax.R,), jnp.float32), S((ax.R,), jnp.float32),
    )


_ARGS = {
    "full": _args_full, "topk": _args_topk, "warm": _args_warm,
    "evict": _args_evict, "fail_hist": _args_fail_hist,
    "fail_hist_bucket": _args_fail_hist_bucket, "probe": _args_probe,
    "gate": _args_gate,
}

#: the kinds dispatched every cycle at scale (EntryPoint.steady).  The
#: full-matrix allocate is the COLD oracle — not steady by design; the
#: compacted topk/warm programs are what dispatches at scale.  Eviction
#: runs inside production cycles, so KBT202 pins the known bid planes
#: (ROADMAP 1.(1)) via the allowlist: [P, N] on the pending bucket (what
#: actions/reclaim.py dispatches wherever the pending set fits it), [T, N]
#: in the full-axis fallback
_STEADY = frozenset({"topk", "warm", "evict", "probe", "gate"})


def _variants(kind: str, one_device: bool):
    """What is traced of a kind, as (name tags, maker arguments): one
    program, but for the evict kind one per mode — and on one device one
    per claimant axis (the sharded bodies bid on the task axis only)."""
    if kind != "evict":
        return [((), {})]
    return [
        ((mode,) + (("compact",) if compact else ()),
         {"mode": mode, "compact": compact})
        for mode in ("reclaim", "preempt")
        for compact in ((False, True) if one_device else (False,))
    ]


def _build(kind, variant, mesh, impl, sentinel,
           sp: Optional[ShapePoint] = None):
    """``(program, abstract arguments)`` of one cell of the table: the very
    object the dispatch looks up, and the arguments in the shape the
    dispatch calls it with."""
    from kube_batch_tpu.parallel.mesh import KINDS, program

    config, statics, arrays = _ARGS[kind](sp or _AUDIT_POINT, **variant)
    fn = program(kind, mesh, impl, config, sentinel, **statics)
    if mesh is None:
        # the one-device program takes config and statics at the call;
        # keyword statics trace in their positions (the trailing ones)
        arrays = KINDS[kind].one_device(
            lambda *a, **kw: a + tuple(kw.values()), arrays, config, statics)
    return fn, arrays


def _abstract_swap_args(ax: ShapePoint, fields, slots: int, lead=()):
    """(buffers, row-index block, value blocks, layout) of a resident swap
    program over `fields` at its widest slot bucket — every column of the
    snapshot at `ax` rides, whatever its size (the production layout
    leaves out only columns smaller than their own payload)."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S

    from kube_batch_tpu.api.resident import swap_layout

    snap = _snap(ax)
    devs = {f: getattr(snap, f) for f in fields}
    layout = swap_layout(devs)
    return (
        devs,
        S(lead + (len(layout.fields), slots), jnp.int32),
        tuple(S(lead + (slots, width), jnp.dtype(dtype))
              for dtype, width in layout.groups),
        layout,
    )


def _build_resident_swap(sp: Optional[ShapePoint] = None):
    from kube_batch_tpu.api.resident import (
        SCATTER_SLOTS,
        SWAP_FIELDS,
        _swap_scatter_fn,
    )

    return _swap_scatter_fn(), _abstract_swap_args(
        sp or _AUDIT_POINT, SWAP_FIELDS, SCATTER_SLOTS)


def _build_shard_swap(mesh, sp: Optional[ShapePoint] = None):
    from kube_batch_tpu.api.resident import (
        NODE_SWAP_FIELDS,
        SHARD_SCATTER_SLOTS,
        _mesh_shard_scatter_fn,
    )
    from kube_batch_tpu.parallel.mesh import NODE_AXIS

    d = int(dict(mesh.shape)[NODE_AXIS])  # node-axis extent, not device count
    return _mesh_shard_scatter_fn(mesh), _abstract_swap_args(
        sp or _AUDIT_POINT, NODE_SWAP_FIELDS, SHARD_SCATTER_SLOTS, lead=(d,))


def _build_repl_swap(mesh, sp: Optional[ShapePoint] = None):
    from kube_batch_tpu.api.resident import (
        REPL_SWAP_FIELDS,
        SCATTER_SLOTS,
        _mesh_repl_scatter_fn,
    )

    return _mesh_repl_scatter_fn(mesh), _abstract_swap_args(
        sp or _AUDIT_POINT, REPL_SWAP_FIELDS, SCATTER_SLOTS)


def _swap_donation() -> Dict[str, Tuple[int, ...]]:
    # a resident swap program donates the dict of stale device buffers it
    # refreshes (argument 0, every leaf) everywhere donation is supported;
    # CPU skips it (api/resident.py's own gate)
    return {"cpu": (), "*": (0,)}


@functools.lru_cache(maxsize=None)
def _one_device_registry() -> Tuple[EntryPoint, ...]:
    """REGISTRY: the table's rows on one device, bare and sentinel-fused —
    the dispatch-facing sentinel programs are solve body + ops/invariants
    tail in ONE jaxpr and must pass KBT101-104 like the bare solves (a
    sentinel that smuggled an f64 upcast or a host callback into every
    production dispatch would tax exactly the path it guards) — and, by
    hand, what the table does not own."""
    from kube_batch_tpu.parallel.mesh import KINDS, tagged

    p = functools.partial
    entries = [
        EntryPoint(
            tagged(row.ops_names[sentinel], tags),
            p(_build, kind, variant, None, None, sentinel),
            donate=_warm_donation() if kind == "warm" else {"*": ()},
            steady=kind in _STEADY)
        for sentinel in (False, True)
        for kind, row in KINDS.items() if row.fused or not sentinel
        for tags, variant in _variants(kind, one_device=True)
    ]
    entries += [
        EntryPoint("api.resident.swap", _build_resident_swap,
                   donate=_swap_donation(), steady=True),
        EntryPoint("ops.probe.probe_solve[topk-inert]",
                   p(_build, "probe", {"topk": True}, None, None, False),
                   steady=True),
    ]
    return tuple(entries)


def __getattr__(name: str):
    # REGISTRY is read off parallel/mesh.py's table, which imports jax: on
    # first use, so that this module (the audit-rule ids, ShapePoint)
    # stays importable without it
    if name == "REGISTRY":
        return _one_device_registry()
    raise AttributeError(name)


def sharded_registry(n_devices: Optional[int] = None
                     ) -> Tuple[EntryPoint, ...]:
    """The table's rows on a mesh — traced whenever the backend exposes ≥2
    devices.  On CPU a forced host-platform device count
    (XLA_FLAGS=--xla_force_host_platform_device_count=N; tier-1's conftest
    forces 8) stands in for a multi-device CI mesh, so KBT101-104 cover the
    sharded entry points without real hardware.  Single-device runs skip
    them (the registry is empty there, never silently "clean" — the CLI
    exit code reflects only what was actually traced).  Over every device
    by default, over the first ``n_devices`` where a caller asks what ONE
    host's chips hold (the v5e-4 envelope tests).  BOTH implementations
    are traced: the shard_map bodies (the production path — KBT101-104
    must cover the authored-collective programs) and the pjit oracle
    (KB_SHARD_MAP=0), so neither can silently regress.  By hand: the two
    mesh swap programs, and on ≥4-device backends a 2-D (tasks × nodes)
    mesh variant of the shard_map allocate body — the task-axis-sharded
    program is a distinct jaxpr (block slicing + task-axis all_gathers)
    and needs its own audit."""
    import jax

    if len(jax.devices()) < 2:
        return ()
    from kube_batch_tpu.parallel.mesh import KINDS, make_mesh

    # _N (8) must divide the mesh for the per-shard scatter's local indexing
    n_dev = min(n_devices or len(jax.devices()), len(jax.devices()))
    while n_dev > 1 and _N % n_dev:
        n_dev -= 1
    mesh = make_mesh(n_dev)
    p = functools.partial
    entries = [
        EntryPoint(
            "parallel.mesh." + ("sentinel_" if sentinel else "")
            + row.mesh_name + "".join(
                f"[{t}]" for t in tags + ((impl,) if row.pjit else ())),
            p(_build, kind, variant, mesh, impl, sentinel),
            steady=kind in _STEADY,
            # a pjit's intermediates carry no specs: tier C models them
            spmd_shards=n_dev if impl == "pjit" else 1)
        for kind, row in KINDS.items()
        for impl in (("shard_map", "pjit") if row.pjit else ("shard_map",))
        for sentinel in ((False, True) if row.invariants else (False,))
        for tags, variant in _variants(kind, one_device=False)
    ]
    entries += [
        EntryPoint("api.resident.swap_sharded",
                   p(_build_shard_swap, mesh),
                   donate=_swap_donation(), steady=True),
        EntryPoint("api.resident.swap_repl",
                   p(_build_repl_swap, mesh),
                   donate=_swap_donation(), steady=True),
    ]
    if n_dev >= 4 and n_dev % 2 == 0 and _T % 2 == 0:
        entries.append(EntryPoint(
            "parallel.mesh.sharded_allocate_solve[shard_map,2d]",
            p(_build, "full", {}, make_mesh(n_dev, task_shards=2),
              "shard_map", False),
        ))
    return tuple(entries)


def full_registry() -> Tuple[EntryPoint, ...]:
    """Every audited entry point: one device plus, on multi-device
    backends, the mesh."""
    return _one_device_registry() + sharded_registry()


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------


def _iter_jaxprs(jaxpr) -> Iterable:
    """The jaxpr and every sub-jaxpr reachable through eqn params
    (pjit/while/cond/scan bodies)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for param in eqn.params.values():
            vals = param if isinstance(param, (list, tuple)) else [param]
            for sub in vals:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_jaxprs(inner)
                elif hasattr(sub, "eqns"):
                    yield from _iter_jaxprs(sub)


def _eqn_dtypes(eqn) -> Iterable[str]:
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            yield str(dtype)


def _real_transfer(eqn) -> bool:
    """True when a device_put eqn moves data for real: a concrete target
    device/src, or copy semantics beyond the benign alias placement that
    jnp constant materialization emits."""
    devices = eqn.params.get("devices", [])
    srcs = eqn.params.get("srcs", [])
    if any(d is not None for d in devices) or any(s is not None for s in srcs):
        return True
    semantics = eqn.params.get("copy_semantics", [])
    return any(getattr(s, "name", str(s)) not in ("ALIAS",) for s in semantics)


def audit_entry(entry: EntryPoint) -> List[Finding]:
    """Trace one entry point and lint its closed jaxpr.  Returns findings
    (suppressed ones dropped; an allow with no reason is itself a KBT000,
    mirroring the static tier's contract)."""
    import jax

    path = f"<jaxpr:{entry.name}>"
    findings: List[Finding] = []
    raw: List[Tuple[str, str]] = []  # (rule, message)

    try:
        fn, args = entry.build()
        with jax.enable_x64():
            traced = fn.trace(*args)
        closed = traced.jaxpr
    except Exception as e:  # noqa: BLE001 — a broken entry must not read as clean
        return [Finding("KBT000", path, 0, 0,
                        f"entry point failed to trace: {type(e).__name__}: {e}")]

    f64_prims: List[str] = []
    transfers: List[str] = []
    callbacks: List[str] = []
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            prim = str(eqn.primitive)
            if prim == "device_put":
                if _real_transfer(eqn):
                    transfers.append(prim)
                continue
            if prim in _CALLBACK_PRIMS:
                callbacks.append(prim)
                continue
            if any(dt == "float64" for dt in _eqn_dtypes(eqn)):
                f64_prims.append(prim)
    if f64_prims:
        uniq = sorted(set(f64_prims))
        raw.append((
            "KBT101",
            f"float64 values produced by {', '.join(uniq)} "
            f"({len(f64_prims)} eqn(s)) — the snapshot contract is f32; an "
            "f64 upcast doubles buffer traffic and flips TPU matmuls to "
            "the slow path",
        ))
    if transfers:
        raw.append((
            "KBT102",
            f"{len(transfers)} in-graph device transfer(s) — a device_put "
            "with a concrete placement inside a compiled program is a "
            "mid-solve copy; inputs should arrive placed (resident cache)",
        ))
    if callbacks:
        raw.append((
            "KBT103",
            f"host callback(s) {sorted(set(callbacks))} inside a compiled "
            "hot-path program — one host round-trip per invocation",
        ))

    expected = entry.donate.get(
        jax.default_backend(), entry.donate.get("*", ()))
    # positional argnums, as the registry declares them: the trace reports
    # donation per flat LEAF, and a swap program's argument 0 is a dict
    actual = tuple(
        i for i, arg in enumerate(traced.args_info[0])
        if any(leaf.donated for leaf in jax.tree_util.tree_leaves(arg))
    )
    if tuple(sorted(expected)) != actual:
        raw.append((
            "KBT104",
            f"wrapper donates argnums {actual}, registry declares "
            f"{tuple(sorted(expected))} for backend "
            f"'{jax.default_backend()}' — donation silently changed "
            "(double-allocation on device, or a read of a buffer the "
            "caller thinks it still owns)",
        ))

    for rule, message in raw:
        reason = entry.allow.get(rule)
        if reason is not None:
            if not reason.strip():
                findings.append(Finding(
                    "KBT000", path, 0, 0,
                    f"allow[{rule}] has no reason — suppression ignored",
                ))
            continue
        findings.append(Finding(rule, path, 0, 0, message))
    return findings


def run_audit(
    registry: Optional[Sequence[EntryPoint]] = None,
    select: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Audit every registered entry point — the single-device REGISTRY plus,
    on multi-device backends, the mesh-sharded variants.  ``select``
    restricts to a rule subset (CLI --select parity with the static
    tier)."""
    if registry is None:
        registry = full_registry()
    findings: List[Finding] = []
    for entry in registry:
        findings.extend(audit_entry(entry))
    if select is not None:
        wanted = set(select) | {"KBT000"}
        findings = [f for f in findings if f.rule in wanted]
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings

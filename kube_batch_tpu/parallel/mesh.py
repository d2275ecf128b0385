"""Device-mesh sharding of the allocate solve over ICI.

SURVEY.md §5.7/§5.8: the reference scales its per-cycle problem with
16-worker goroutine fan-outs; the TPU-native analog partitions the **node
axis** across a `jax.sharding.Mesh` (the way a sequence axis is partitioned
in sequence parallelism). Every [N, R] budget tensor and the [T, N]
feasibility/score intermediates shard over the 'nodes' axis; task-axis
tensors replicate. XLA/GSPMD then inserts the collectives: the per-task
argmax over nodes becomes a sharded argmax + all-reduce of (value, index)
pairs, and the post-conflict budget updates stay node-local — the only
cross-chip traffic per round is O(T) "who won", never O(T × N) — riding ICI,
with DCN reserved for host↔cluster-API traffic.

Two implementations share the mesh and the snapshot shardings:

- **shard_map (default)** — parallel/shard_solve.py: the solves run as
  ``shard_map`` bodies with AUTHORED collectives; per-round cross-host
  traffic is the explicit O(tasks) pmax/pmin/psum reductions of the
  winner vectors, auditable via ``collective_stats``.
- **pjit (KB_SHARD_MAP=0)** — the original declarative path: NamedSharding
  on the snapshot pytree and jit's in_shardings/out_shardings, collectives
  compiler-inserted by GSPMD.  Kept as the bit-exactness oracle.

Which jitted program a dispatch runs — mesh or one device, which impl, bare
or sentinel-fused, of which kind — is looked up in the program table below
(``KINDS``, :func:`program`, :func:`call`).

A second mesh dim shards the TASK axis too (KB_TASK_SHARDS=k or
``make_mesh(task_shards=k)``) for when node-axis sharding alone no longer
fits the [T, N] round intermediates in HBM (shard_map path only)."""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kube_batch_tpu.api.snapshot import DeviceSnapshot
from kube_batch_tpu.ops import admission, assignment, invariants, probe
from kube_batch_tpu.ops.assignment import AllocateConfig, AllocateResult, allocate_solve
from kube_batch_tpu.ops.eviction import EvictConfig, EvictResult, evict_solve
from kube_batch_tpu.utils import jitstats

NODE_AXIS = "nodes"
TASK_AXIS = "tasks"

# below this padded node-axis size a single chip wins: the per-round
# cross-chip argmax reduction costs more than the sharded [T, N] work saves
SHARD_MIN_NODES = 256

_default_mesh: dict = {}
_bad_task_shards: set = set()  # warn once per bad KB_TASK_SHARDS value


def _env_off(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() in (
        "0", "false", "off", "no"
    )


def shard_map_enabled() -> bool:
    """KB_SHARD_MAP=0 selects the pjit oracle path; default is the
    explicit-collective shard_map path."""
    return not _env_off("KB_SHARD_MAP")


def task_shards() -> int:
    """KB_TASK_SHARDS=k splits the mesh into a (tasks=k, nodes=d/k) grid —
    the HBM escape hatch for cycles whose [T, N] round intermediates no
    longer fit when only the node axis shards.  Default 1 (node-only)."""
    try:
        return max(1, int(os.environ.get("KB_TASK_SHARDS", "1")))
    except ValueError:
        return 1


def default_mesh() -> Optional[Mesh]:
    """The production mesh over every visible device — None on single-chip
    parts.  Cached per task-shard count: the device list is fixed for the
    process lifetime, but KB_TASK_SHARDS may select a different grid.  A
    KB_TASK_SHARDS that does not divide the device count falls back to the
    1-D node mesh WITH a warning — it must degrade the grid, never
    silently disable sharding wholesale."""
    ts = task_shards()
    n_dev = len(jax.devices())
    if n_dev <= 1:
        return None
    if ts > 1 and n_dev % ts:
        if ts not in _bad_task_shards:
            _bad_task_shards.add(ts)
            import logging

            logging.getLogger("kube_batch_tpu").warning(
                "KB_TASK_SHARDS=%d does not divide the %d-device count; "
                "falling back to the 1-D node mesh", ts, n_dev,
            )
        ts = 1
    mesh = _default_mesh.get(ts)
    if mesh is None:
        mesh = _default_mesh[ts] = make_mesh(task_shards=ts)
    return mesh


def should_shard(n_nodes_padded: int) -> bool:
    """The production actions' auto-selection gate: a mesh exists and the
    node axis is big enough that sharding beats one chip (the reference's
    16-worker fan-out is always on, scheduler_helper.go:34-64; here the
    analog turns on with the hardware).  KB_SHARD=0 forces the single-chip
    path (the sharded-vs-single equivalence tests' knob)."""
    if _env_off("KB_SHARD"):
        return False
    return n_nodes_padded >= SHARD_MIN_NODES and default_mesh() is not None


def make_mesh(n_devices: Optional[int] = None, task_shards: int = 1) -> Mesh:
    """Mesh over the node axis — 1-D by default; ``task_shards`` > 1 folds
    the device list into a (tasks, nodes) grid whose node axis carries the
    ICI-contiguous fast dim.  Multi-host: pass the global device list
    order; ICI rings form along the axes automatically."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    if task_shards > 1:
        arr = np.asarray(devices).reshape(
            task_shards, len(devices) // task_shards
        )
        return Mesh(arr, (TASK_AXIS, NODE_AXIS))
    return Mesh(np.asarray(devices), (NODE_AXIS,))


@lru_cache(maxsize=8)
def snapshot_shardings(mesh: Mesh) -> DeviceSnapshot:
    """A DeviceSnapshot-shaped pytree of NamedShardings: node-axis arrays
    sharded, everything else replicated. Memoized per mesh — the resident
    feature cache consults it every sharded cycle."""
    node1 = NamedSharding(mesh, P(NODE_AXIS))        # [N]
    node2 = NamedSharding(mesh, P(NODE_AXIS, None))  # [N, R] / [N, W]
    repl = NamedSharding(mesh, P())

    return DeviceSnapshot(
        task_req=repl,
        task_resreq=repl,
        task_job=repl,
        task_prio=repl,
        task_creation=repl,
        task_status=repl,
        task_valid=repl,
        task_pending=repl,
        task_best_effort=repl,
        task_sel_bits=repl,
        task_sel_impossible=repl,
        task_tol_bits=repl,
        task_node=repl,
        task_critical=repl,
        task_needs_host=repl,
        task_aff_idx=repl,
        task_aff_mask=NamedSharding(mesh, P(None, NODE_AXIS)),
        task_pref_idx=repl,
        task_pref_node=NamedSharding(mesh, P(None, NODE_AXIS)),
        task_pref_pod=NamedSharding(mesh, P(None, NODE_AXIS)),
        node_idle=node2,
        node_releasing=node2,
        node_used=node2,
        node_alloc=node2,
        node_valid=node1,
        node_sched=node1,
        node_label_bits=node2,
        node_taint_bits=node2,
        job_min_avail=repl,
        job_ready=repl,
        job_queue=repl,
        job_prio=repl,
        job_creation=repl,
        job_valid=repl,
        job_schedulable=repl,
        job_allocated=repl,
        queue_weight=repl,
        queue_capability=repl,
        queue_alloc=repl,
        queue_request=repl,
        queue_valid=repl,
        total=repl,
        quanta=repl,
    )


def resolve_impl(impl: Optional[str]) -> str:
    """Resolve the sharded-solve implementation: explicit override, else
    the KB_SHARD_MAP knob (shard_map by default, pjit as the oracle)."""
    if impl is not None:
        return impl
    return "shard_map" if shard_map_enabled() else "pjit"


# --------------------------------------------------------------------------
# THE program table.  Every device dispatch is plan -> program -> call: the
# site decides on the host what to run (mesh or one device, which impl,
# sentinel or bare), looks the jitted program up HERE, and calls it.  The
# table is the one place that can enumerate the programs a process may
# dispatch: the jaxpr audit (analysis/jaxpr_audit.py) derives its registry
# by walking it.
# --------------------------------------------------------------------------


def _solve(snap: DeviceSnapshot, config: AllocateConfig) -> AllocateResult:
    # the pjit body: the guard's shadow oracle and shard_map's demotion
    # target; named so that a device trace tells its ops from the fast path's
    with jax.named_scope("pjit_oracle"):
        return allocate_solve(snap, config)


def _evict(snap: DeviceSnapshot, config: EvictConfig) -> EvictResult:
    return evict_solve(snap, config)


#: the result shardings of a pjit program, as specs, once per result type:
#: an allocate result's node ledgers are node-sharded, its task-axis
#: vectors replicated; every field of the other two is task- or gang-axis
_ALLOCATE_OUT = AllocateResult(
    assigned=P(),
    pipelined=P(),
    committed=P(),
    node_idle=P(NODE_AXIS, None),
    node_releasing=P(NODE_AXIS, None),
    node_used=P(NODE_AXIS, None),
    deserved=P(),
    rounds_run=P(),
    topk_exhausted=P(),
    topk_reentries=P(),
)
_EVICT_OUT = EvictResult(*[P()] * len(EvictResult._fields))
_PROBE_OUT = probe.ProbeResult(*[P()] * len(probe.ProbeResult._fields))


class Kind(NamedTuple):
    """One row of the program table: what differs between the kinds of
    device program, and nothing else."""

    #: getters of the one-device program of ops/, as it is, and of the same
    #: with its invariant tail fused behind it (None: no such program)
    bare: Callable
    fused: Optional[Callable]
    #: the names those two are audited under
    ops_names: Tuple[str, ...]
    #: what the mesh programs register under, before the [mode,impl] tag
    mesh_name: str
    #: parallel/shard_solve.py's builder of the shard_map program
    shard_map: str
    #: the pjit program (None: shard_map is the kind's one mesh program):
    #: the single-device body re-jitted with mesh shardings, collectives
    #: compiler-inserted — shard_map's bit-exactness oracle and the guard's
    #: demotion target.  (body, specs of the arguments behind the snapshot,
    #: specs of the result); config and statics reach the body by keyword
    pjit: Optional[tuple] = None
    #: ops/invariants.py's function that the mesh sentinel fuses behind the
    #: program (None: the kind has no mesh sentinel)
    invariants: Optional[str] = None
    #: the program returns (result, table', eroded): a warm program's
    #: sentinel passes the refreshed table and the erosion flag through
    carries_table: bool = False
    #: how the one-device program takes its arrays, config and statics.
    #: The mesh programs have config and statics baked in; ops/ takes them
    #: at the call, each in the argument order its other callers (bundle
    #: replay, the fit check, tests) use — a call of another shape would be
    #: another entry of the jit cache, compiled again
    one_device: Callable = lambda fn, arrays, config, statics: fn(
        *arrays, config)


def _arrays_only(fn, arrays, config, statics):
    return fn(*arrays)


KINDS = {
    "full": Kind(
        bare=lambda: assignment.allocate_solve,
        fused=lambda: invariants.allocate_sentinel_solve,
        ops_names=("ops.assignment.allocate_solve",
                   "ops.invariants.allocate_sentinel_solve"),
        mesh_name="sharded_allocate_solve",
        shard_map="allocate_shard_map",
        pjit=(_solve, (), _ALLOCATE_OUT),
        invariants="allocate_invariants",
    ),
    # config.topk > 0: the [P, K] candidate-table program.  shard_map
    # builds per-shard candidate lists and merges them with one per-solve
    # gather — zero per-round collectives
    "topk": Kind(
        bare=lambda: assignment.allocate_topk_solve,
        fused=lambda: invariants.allocate_topk_sentinel_solve,
        ops_names=("ops.assignment.allocate_topk_solve",
                   "ops.invariants.allocate_topk_sentinel_solve"),
        mesh_name="sharded_allocate_topk_solve",
        shard_map="allocate_topk_shard_map",
        pjit=(assignment.allocate_topk_solve.__wrapped__, (P(),),
              _ALLOCATE_OUT),
        invariants="allocate_invariants",
    ),
    # the cross-cycle candidate-table carry (static ``k_min``).  shard_map
    # contributes delta-sized per-shard work (fresh changed-node keys via
    # one psum, the invalidated sub-bucket via one all_gather + replicated
    # merge) and keeps the round loop collective-free; under pjit the
    # pending bucket, the table (4) and the plan (4) replicate
    "warm": Kind(
        bare=assignment.warm_solve_fn,
        fused=invariants.warm_sentinel_solve_fn,
        ops_names=("ops.assignment.warm_allocate_solve",
                   "ops.invariants.warm_allocate_sentinel_solve"),
        mesh_name="sharded_warm_allocate_solve",
        shard_map="warm_allocate_shard_map",
        pjit=(assignment._warm_allocate_solve, (P(),) * 9,
              (_ALLOCATE_OUT, (P(),) * 4, P())),
        invariants="allocate_invariants", carries_table=True,
        one_device=lambda fn, arrays, config, statics: fn(
            *arrays, config=config, **statics),
    ),
    # reclaim / preempt (mode and gates in the EvictConfig).  One device
    # bids on the pending bucket where one is passed behind the snapshot;
    # the sharded bodies bid on the task axis
    "evict": Kind(
        bare=lambda: evict_solve,
        fused=lambda: invariants.evict_sentinel_solve,
        ops_names=("ops.eviction.evict_solve",
                   "ops.invariants.evict_sentinel_solve"),
        mesh_name="sharded_evict_solve",
        shard_map="evict_shard_map",
        pjit=(_evict, (), _EVICT_OUT),
        invariants="evict_invariants",
        one_device=lambda fn, arrays, config, statics: fn(
            arrays[0], config, *(arrays[1:] or (None,))),
    ),
    # the lazy fit-error histogram: [T, N]-scale predicate masks shard
    # along the node axis, the per-reason counts reduce (one psum on the
    # shard_map path) into the replicated [T, N_REASONS] result
    "fail_hist": Kind(
        bare=lambda: assignment.failure_histogram_solve, fused=None,
        ops_names=("ops.assignment.failure_histogram_solve",),
        mesh_name="sharded_failure_histogram",
        shard_map="failure_histogram_shard_map",
        pjit=(assignment.failure_histogram_solve.__wrapped__, (), P()),
        one_device=_arrays_only,
    ),
    # the same restricted to the [P] pending bucket (1-D node meshes only)
    "fail_hist_bucket": Kind(
        bare=lambda: assignment.failure_histogram_bucket_solve, fused=None,
        ops_names=("ops.assignment.failure_histogram_bucket_solve",),
        mesh_name="sharded_failure_histogram_bucket",
        shard_map="failure_histogram_bucket_shard_map",
        pjit=(assignment.failure_histogram_bucket_solve.__wrapped__, (P(),),
              P()),
        one_device=_arrays_only,
    ),
    # the batched what-if probe (statics ``evict_config``,
    # ``with_evictions``), the query plane's dispatch: node-axis snapshot
    # columns stay sharded (the lease's resident placement), the B-gang
    # batch and the row oracle replicate
    "probe": Kind(
        bare=lambda: probe.probe_solve, fused=None,
        ops_names=("ops.probe.probe_solve",),
        mesh_name="sharded_probe_solve",
        shard_map="probe_shard_map",
        pjit=(probe.probe_body,
              (probe.ProbeBatch(*[P()] * len(probe.ProbeBatch._fields)), P()),
              _PROBE_OUT),
        one_device=lambda fn, arrays, config, statics: fn(
            *arrays, config, statics["evict_config"],
            statics["with_evictions"]),
    ),
    # the enqueue admission scan.  On the mesh it is one replicated
    # shard_map body around ops.admission.gate_scan — zero cross-shard
    # bytes (shard_solve.enqueue_gate_shard_map says why it exists) — with
    # no pjit twin and no fused sentinel: the caller checks on the host
    "gate": Kind(
        bare=admission.enqueue_gate_fn,
        fused=invariants.enqueue_gate_sentinel_fn,
        ops_names=("ops.admission.enqueue_gate",
                   "ops.invariants.enqueue_gate_sentinel"),
        mesh_name="sharded_enqueue_gate",
        shard_map="enqueue_gate_shard_map",
        one_device=_arrays_only,
    ),
}

# one jitted program per (kind, mesh, impl, sentinel, config, statics) — a
# fresh jax.jit wrapper per call would retrace and recompile the whole
# solve every scheduling cycle.  The dispatch, the guard's audit, the read
# plane's prewarm and the jaxpr audit all get THIS object, so nothing
# compiles twice
_jit_cache: dict = {}


def mesh_tags(kind: str, impl: str, config) -> Tuple[str, ...]:
    """What tells one mesh program of ``kind`` from the next in a name:
    the eviction mode, and the impl where the kind has two."""
    return ((config.mode,) if kind == "evict" else ()) + (
        (impl,) if KINDS[kind].pjit else ())


def tagged(name: str, tags) -> str:
    return name + (f"[{','.join(tags)}]" if tags else "")


def _shardings(mesh: Mesh, specs):
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec), specs)


def _mesh_program(kind: str, mesh: Mesh, impl: str, sentinel: bool, config,
                  statics: dict):
    """THE factory: builds, registers and memoizes the jitted mesh program
    of one cell of the table."""
    key = (kind, mesh, impl, sentinel, config, tuple(sorted(statics.items())))
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    row = KINDS[kind]
    if sentinel:
        # the memoized bare program plus the ops/invariants tail in ONE
        # jitted program: the invariant reductions run on the replicated
        # result vectors and the node-sharded ledgers (GSPMD partitions the
        # O(N) cross-checks), and the verdict/histogram ride the action's
        # single readback exactly like the single-device sentinel programs
        inner = _mesh_program(kind, mesh, impl, False, config, statics)
        check = getattr(invariants, row.invariants)

        def fused(snap, *rest):
            out = inner(snap, *rest)
            res, *carry = out if row.carries_table else (out,)
            verdict, hist = check(snap, res, config)
            return (res, verdict, hist,
                    invariants.eligibility_checksum(snap), *carry)

        fn = jax.jit(fused)
    elif impl == "shard_map":
        from kube_batch_tpu.parallel import shard_solve

        fn = getattr(shard_solve, row.shard_map)(
            mesh, *(() if config is None else (config,)), **statics)
    else:
        body, in_specs, out_specs = row.pjit
        if config is not None:
            body = partial(body, config=config, **statics)
        fn = jax.jit(
            body,
            in_shardings=(snapshot_shardings(mesh),
                          *_shardings(mesh, in_specs)),
            out_shardings=_shardings(mesh, out_specs))
    jitstats.register(
        tagged(("sentinel_" if sentinel else "") + row.mesh_name,
               mesh_tags(kind, impl, config)), fn)
    _jit_cache[key] = fn
    return fn


def program(kind: str, mesh: Optional[Mesh], impl: Optional[str], config,
            sentinel: bool = False, **statics):
    """THE lookup of a device dispatch's program: the memoized jitted
    callable of ``kind`` (a key of :data:`KINDS`), bare or with the
    invariant tail fused behind it (``sentinel``).  On a ``mesh`` it is the
    program of (mesh, impl, config, statics), all of them baked in;
    ``impl`` None follows KB_SHARD_MAP, and ``"pjit"`` is what the guard's
    demotion and its shadow audit pass.  ``mesh=None`` is the one-device
    program of ops/, which takes config and statics at the call
    (:func:`call`)."""
    row = KINDS[kind]
    if mesh is None:
        return (row.fused if sentinel else row.bare)()
    return _mesh_program(
        kind, mesh, resolve_impl(impl) if row.pjit else "shard_map",
        sentinel, config, statics)


def call(fn, mesh: Optional[Mesh], *arrays, config=None, **statics):
    """THE call of a looked-up program (:func:`program`) on its arrays:
    under its mesh, where config and statics are baked in; on one device
    as ``one_device`` of the row that ``fn`` is the program of passes
    them."""
    if mesh is not None:
        with mesh:
            return fn(*arrays)
    for row in KINDS.values():
        if fn is row.bare() or (row.fused and fn is row.fused()):
            return row.one_device(fn, arrays, config, statics)
    raise KeyError(f"{fn} is no one-device program of the table")


def collective_stats(mesh: Mesh, snap, config: Optional[AllocateConfig] = None,
                     pend_bucket: Optional[int] = None) -> dict:
    """Traced collective inventory of the shard_map allocate solve on
    `mesh` — the per-round / per-solve cross-shard byte accounting
    (utils/jitstats.collective_inventory) of the program XLA actually
    compiles, at the abstract shapes of ``snap`` (a snapshot of
    ShapeDtypeStructs: analysis.jaxpr_audit.abstract_snapshot).  The bench
    and the sim report this next to the measured
    round counts, so the O(tasks) comms claim is checked against the real
    traced program, not asserted in a comment.

    With ``config.topk > 0`` and a ``pend_bucket`` size, the COMPACTED
    program is traced instead — its contract is per_round_bytes == 0
    (the candidate merge and the fallback's node-column gathers are all
    per-solve), which the bench and tests assert from these numbers.

    The inventory's nested-loop fields pass through:
    ``per_round_bytes_expanded`` multiplies each per-round site by the
    trip count of any scan nested inside the round loop, and
    ``per_round_has_unbounded_inner_loop`` marks an inner ``while``
    (no static trip count — the expanded total is then a floor).  The
    HBM audit's KBT204 reads the same fields for its byte formulas."""
    import jax.numpy as jnp

    config = config or AllocateConfig()
    if config.topk and pend_bucket:
        traced = program("topk", mesh, "shard_map", config).trace(
            snap, jax.ShapeDtypeStruct((pend_bucket,), jnp.int32)
        )
    else:
        traced = program("full", mesh, "shard_map", config).trace(snap)
    stats = jitstats.collective_inventory(traced.jaxpr)
    stats["mesh"] = {k: int(v) for k, v in dict(mesh.shape).items()}
    stats["task_bucket"] = int(snap.task_req.shape[0])
    stats["node_bucket"] = int(snap.node_idle.shape[0])
    if config.topk and pend_bucket:
        stats["topk"] = int(config.topk)
        stats["pend_bucket"] = int(pend_bucket)
    return stats

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

A second mesh dim shards the TASK axis too (KB_TASK_SHARDS=k or
``make_mesh(task_shards=k)``) for when node-axis sharding alone no longer
fits the [T, N] round intermediates in HBM (shard_map path only)."""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kube_batch_tpu.api.snapshot import DeviceSnapshot
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


# jitted solve per (mesh, config, impl) — a fresh jax.jit wrapper per call
# would retrace and recompile the whole solve every scheduling cycle
_jit_cache: dict = {}


def _impl(impl: Optional[str]) -> str:
    """Resolve the sharded-solve implementation: explicit override, else
    the KB_SHARD_MAP knob (shard_map by default, pjit as the oracle)."""
    if impl is not None:
        return impl
    return "shard_map" if shard_map_enabled() else "pjit"


def allocate_solve_fn(mesh: Mesh, config: AllocateConfig,
                      impl: Optional[str] = None):
    """The memoized jitted allocate solve for (mesh, config, impl) — the
    dispatch below calls it; the jaxpr audit (analysis/jaxpr_audit.py)
    traces BOTH impls abstractly so KBT101-104 cover the sharded variants
    in tier-1."""
    impl = _impl(impl)
    key = (mesh, config, impl)
    fn = _jit_cache.get(key)
    if fn is None:
        if impl == "shard_map":
            from kube_batch_tpu.parallel import shard_solve

            fn = shard_solve.allocate_shard_map(mesh, config)
        else:
            in_shardings = snapshot_shardings(mesh)
            node2 = NamedSharding(mesh, P(NODE_AXIS, None))
            repl = NamedSharding(mesh, P())
            out_shardings = AllocateResult(
                assigned=repl,
                pipelined=repl,
                committed=repl,
                node_idle=node2,
                node_releasing=node2,
                node_used=node2,
                deserved=repl,
                rounds_run=repl,
                topk_exhausted=repl,
                topk_reentries=repl,
            )
            fn = jax.jit(
                partial(_solve, config=config),
                in_shardings=(in_shardings,),
                out_shardings=out_shardings,
            )
        jitstats.register(f"sharded_allocate_solve[{impl}]", fn)
        _jit_cache[key] = fn
    return fn


def sharded_allocate_solve(
    snap: DeviceSnapshot, config: AllocateConfig, mesh: Mesh,
    impl: Optional[str] = None,
) -> AllocateResult:
    """The allocate solve jitted over the mesh. Node-axis inputs/outputs are
    sharded; the assignment vector comes back replicated.  ``impl``
    overrides the KB_SHARD_MAP selection — the guard plane's demotion
    passes ``"pjit"`` here to pin a tripped shard_map path to its oracle."""
    fn = allocate_solve_fn(mesh, config, impl=impl)
    with mesh:
        return fn(snap)


def _solve(snap: DeviceSnapshot, config: AllocateConfig) -> AllocateResult:
    # the pjit body: the guard's shadow oracle and shard_map's demotion
    # target; named so that a device trace tells its ops from the fast path's
    with jax.named_scope("pjit_oracle"):
        return allocate_solve(snap, config)


def allocate_topk_solve_fn(mesh: Mesh, config: AllocateConfig,
                           impl: Optional[str] = None):
    """The memoized jitted COMPACTED allocate solve for (mesh, config,
    impl) — config.topk > 0 selects the [P, K] candidate-table program
    (ops.assignment.allocate_topk_solve).  The shard_map impl builds
    per-shard candidate lists and merges them with one per-solve gather
    (parallel/shard_solve.allocate_topk_shard_map — zero per-round
    collectives); the pjit impl re-jits the single-device compacted body
    with mesh shardings as the sharded bit-exactness oracle, mirroring the
    full solve's impl split."""
    from kube_batch_tpu.ops.assignment import allocate_topk_solve

    impl = _impl(impl)
    key = (mesh, config, "topk", impl)
    fn = _jit_cache.get(key)
    if fn is None:
        if impl == "shard_map":
            from kube_batch_tpu.parallel import shard_solve

            fn = shard_solve.allocate_topk_shard_map(mesh, config)
        else:
            in_shardings = snapshot_shardings(mesh)
            node2 = NamedSharding(mesh, P(NODE_AXIS, None))
            repl = NamedSharding(mesh, P())
            out_shardings = AllocateResult(
                assigned=repl, pipelined=repl, committed=repl,
                node_idle=node2, node_releasing=node2, node_used=node2,
                deserved=repl, rounds_run=repl,
                topk_exhausted=repl, topk_reentries=repl,
            )
            fn = jax.jit(
                partial(allocate_topk_solve.__wrapped__, config=config),
                in_shardings=(in_shardings, repl),
                out_shardings=out_shardings,
            )
        jitstats.register(f"sharded_allocate_topk_solve[{impl}]", fn)
        _jit_cache[key] = fn
    return fn


def warm_allocate_solve_fn(mesh: Mesh, config: AllocateConfig, k_min: int,
                           impl: Optional[str] = None):
    """The memoized jitted WARM-STARTED compacted solve for (mesh, config,
    k_min, impl) — the cross-cycle candidate-table carry
    (ops.assignment._warm_allocate_solve).  The shard_map impl contributes
    delta-sized per-shard work (fresh changed-node keys via one psum, the
    invalidated sub-bucket via one all_gather + replicated merge) and
    keeps the round loop collective-free; the pjit impl re-jits the
    single-device warm body with mesh shardings (table + plan replicated)
    as the sharded bit-exactness oracle — the same split as every solve."""
    from kube_batch_tpu.ops.assignment import _warm_allocate_solve

    impl = _impl(impl)
    key = (mesh, config, "warm", k_min, impl)
    fn = _jit_cache.get(key)
    if fn is None:
        if impl == "shard_map":
            from kube_batch_tpu.parallel import shard_solve

            fn = shard_solve.warm_allocate_shard_map(mesh, config, k_min)
        else:
            in_shardings = snapshot_shardings(mesh)
            node2 = NamedSharding(mesh, P(NODE_AXIS, None))
            repl = NamedSharding(mesh, P())
            res_shardings = AllocateResult(
                assigned=repl, pipelined=repl, committed=repl,
                node_idle=node2, node_releasing=node2, node_used=node2,
                deserved=repl, rounds_run=repl,
                topk_exhausted=repl, topk_reentries=repl,
            )
            fn = jax.jit(
                partial(_warm_allocate_solve, config=config, k_min=k_min),
                in_shardings=(in_shardings,) + (repl,) * 9,
                out_shardings=(res_shardings, (repl,) * 4, repl),
            )
        jitstats.register(f"sharded_warm_allocate_solve[{impl}]", fn)
        _jit_cache[key] = fn
    return fn


def failure_histogram_bucket_fn(mesh: Mesh, impl: Optional[str] = None):
    """Memoized jitted sharded BUCKETED fit-error histogram for `mesh`
    (dispatch + jaxpr-audit entry point) — the [P] pending-bucket variant
    of failure_histogram_fn."""
    from kube_batch_tpu.ops.assignment import failure_histogram_bucket_solve

    impl = _impl(impl)
    key = (mesh, "fail_hist_bucket", impl)
    fn = _jit_cache.get(key)
    if fn is None:
        if impl == "shard_map":
            from kube_batch_tpu.parallel import shard_solve

            fn = shard_solve.failure_histogram_bucket_shard_map(mesh)
        else:
            repl = NamedSharding(mesh, P())
            fn = jax.jit(
                failure_histogram_bucket_solve.__wrapped__,
                in_shardings=(snapshot_shardings(mesh), repl),
                out_shardings=repl,
            )
        jitstats.register(f"sharded_failure_histogram_bucket[{impl}]", fn)
        _jit_cache[key] = fn
    return fn


def sharded_failure_histogram_bucket(snap: DeviceSnapshot, pend_rows,
                                     mesh: Mesh):
    """The lazy fit-error histogram over the mesh, restricted to the [P]
    pending bucket — per-shard [P, N_loc] partials, one psum, scattered
    back to the replicated [T, N_REASONS] result."""
    fn = failure_histogram_bucket_fn(mesh)
    with mesh:
        return fn(snap, pend_rows)


def failure_histogram_fn(mesh: Mesh, impl: Optional[str] = None):
    """Memoized jitted sharded fit-error histogram for `mesh` (dispatch +
    jaxpr-audit entry point)."""
    from kube_batch_tpu.ops.assignment import failure_histogram_solve

    impl = _impl(impl)
    key = (mesh, "fail_hist", impl)
    fn = _jit_cache.get(key)
    if fn is None:
        if impl == "shard_map":
            from kube_batch_tpu.parallel import shard_solve

            fn = shard_solve.failure_histogram_shard_map(mesh)
        else:
            fn = jax.jit(
                failure_histogram_solve.__wrapped__,
                in_shardings=(snapshot_shardings(mesh),),
                out_shardings=NamedSharding(mesh, P()),
            )
        jitstats.register(f"sharded_failure_histogram[{impl}]", fn)
        _jit_cache[key] = fn
    return fn


def sharded_failure_histogram(snap: DeviceSnapshot, mesh: Mesh):
    """The lazy fit-error histogram over the mesh: [T, N]-scale predicate
    masks shard along the node axis, the per-reason node counts reduce
    (an explicit psum on the shard_map path) into the replicated
    [T, N_REASONS] result."""
    fn = failure_histogram_fn(mesh)
    with mesh:
        return fn(snap)


def evict_solve_fn(mesh: Mesh, config: EvictConfig,
                   impl: Optional[str] = None):
    """Memoized jitted sharded eviction solve for (mesh, config, impl)
    (dispatch + jaxpr-audit entry point)."""
    impl = _impl(impl)
    key = (mesh, config, "evict", impl)
    fn = _jit_cache.get(key)
    if fn is None:
        if impl == "shard_map":
            from kube_batch_tpu.parallel import shard_solve

            fn = shard_solve.evict_shard_map(mesh, config)
        else:
            in_shardings = snapshot_shardings(mesh)
            repl = NamedSharding(mesh, P())
            out_shardings = EvictResult(
                claim_node=repl, evicted=repl, victim_claimant=repl,
                rounds_run=repl, gated_releasing=repl,
            )
            fn = jax.jit(
                partial(_evict, config=config),
                in_shardings=(in_shardings,),
                out_shardings=out_shardings,
            )
        jitstats.register(f"sharded_evict_solve[{config.mode},{impl}]", fn)
        _jit_cache[key] = fn
    return fn


def sharded_evict_solve(
    snap: DeviceSnapshot, config: EvictConfig, mesh: Mesh,
    impl: Optional[str] = None,
) -> EvictResult:
    """The eviction solve (preempt/reclaim) jitted over the mesh: node-axis
    inputs shard exactly like the allocate solve's; every EvictResult field
    is task-axis, so outputs replicate.  ``impl`` is the guard plane's
    demotion override (``"pjit"`` = the oracle)."""
    fn = evict_solve_fn(mesh, config, impl=impl)
    with mesh:
        return fn(snap)


def _evict(snap: DeviceSnapshot, config: EvictConfig) -> EvictResult:
    return evict_solve(snap, config)


# --------------------------------------------------------------------------
# sentinel-fused sharded solves (guard plane tier 1): the memoized sharded
# solve body plus the ops/invariants tail in ONE jitted program — the
# invariant reductions run on the replicated result vectors and the
# node-sharded ledgers (GSPMD partitions the O(N) cross-checks), and the
# verdict/histogram ride the action's single readback exactly like the
# single-device sentinel programs.
# --------------------------------------------------------------------------


def _sentinel_fn(key, name: str, inner_fn, invariants, config,
                 carries_table: bool = False):
    """The memoized jitted ``inner`` program with ``invariants`` and the
    eligibility checksum fused behind it: ``(result, verdict, hist,
    checksum)``, and behind those the refreshed table and erosion flag of
    a warm program (``carries_table``), which returns ``(result, table',
    eroded)``.  ``inner_fn`` builds the inner program on first use."""
    fn = _jit_cache.get(key)
    if fn is None:
        from kube_batch_tpu.ops.invariants import eligibility_checksum

        inner = inner_fn()

        def fused(snap, *rest):
            out = inner(snap, *rest)
            res, *carry = out if carries_table else (out,)
            verdict, hist = invariants(snap, res, config)
            return (res, verdict, hist, eligibility_checksum(snap), *carry)

        fn = jax.jit(fused)
        jitstats.register(name, fn)
        _jit_cache[key] = fn
    return fn


def sentinel_allocate_solve_fn(mesh: Mesh, config: AllocateConfig,
                               impl: Optional[str] = None):
    from kube_batch_tpu.ops.invariants import allocate_invariants

    impl = _impl(impl)
    return _sentinel_fn(
        (mesh, config, "sentinel_alloc", impl),
        f"sentinel_sharded_allocate_solve[{impl}]",
        lambda: allocate_solve_fn(mesh, config, impl=impl),
        allocate_invariants, config)


def sentinel_allocate_topk_solve_fn(mesh: Mesh, config: AllocateConfig,
                                    impl: Optional[str] = None):
    from kube_batch_tpu.ops.invariants import allocate_invariants

    impl = _impl(impl)
    return _sentinel_fn(
        (mesh, config, "sentinel_topk", impl),
        f"sentinel_sharded_allocate_topk_solve[{impl}]",
        lambda: allocate_topk_solve_fn(mesh, config, impl=impl),
        allocate_invariants, config)


def sentinel_warm_allocate_solve_fn(mesh: Mesh, config: AllocateConfig,
                                    k_min: int,
                                    impl: Optional[str] = None):
    from kube_batch_tpu.ops.invariants import allocate_invariants

    impl = _impl(impl)
    return _sentinel_fn(
        (mesh, config, "sentinel_warm", k_min, impl),
        f"sentinel_sharded_warm_allocate_solve[{impl}]",
        lambda: warm_allocate_solve_fn(mesh, config, k_min, impl=impl),
        allocate_invariants, config, carries_table=True)


def sentinel_evict_solve_fn(mesh: Mesh, config: EvictConfig,
                            impl: Optional[str] = None):
    from kube_batch_tpu.ops.invariants import evict_invariants

    impl = _impl(impl)
    return _sentinel_fn(
        (mesh, config, "sentinel_evict", impl),
        f"sentinel_sharded_evict_solve[{config.mode},{impl}]",
        lambda: evict_solve_fn(mesh, config, impl=impl),
        evict_invariants, config)


def sentinel_sharded_evict_solve(snap, config, mesh, impl=None):
    fn = sentinel_evict_solve_fn(mesh, config, impl=impl)
    with mesh:
        return fn(snap)


#: (kind, sentinel) -> the getter that memoizes the program on a mesh
_MESH_ALLOCATE_GETTERS = {
    ("full", False): allocate_solve_fn,
    ("full", True): sentinel_allocate_solve_fn,
    ("topk", False): allocate_topk_solve_fn,
    ("topk", True): sentinel_allocate_topk_solve_fn,
    ("warm", False): warm_allocate_solve_fn,
    ("warm", True): sentinel_warm_allocate_solve_fn,
}


def allocate_program(kind: str, mesh: Optional[Mesh], impl: Optional[str],
                     config: AllocateConfig, sentinel: bool, k_min: int = 0):
    """THE lookup of an allocate dispatch's program: the memoized jitted
    callable for ``kind`` ("full" | "topk" | "warm"), bare or with the
    invariant tail fused behind it (``sentinel``).  On a ``mesh`` it is
    what the ``*_solve_fn`` getter memoizes for (mesh, config, impl), with
    ``config`` (and a warm program's ``k_min``) baked in; ``mesh=None`` is
    the single-device program of ops/assignment.py or ops/invariants.py,
    which takes them as static arguments at the call."""
    if mesh is not None:
        getter = _MESH_ALLOCATE_GETTERS[kind, sentinel]
        if kind == "warm":
            return getter(mesh, config, k_min, impl=impl)
        return getter(mesh, config, impl=impl)
    from kube_batch_tpu.ops import assignment, invariants

    if kind == "warm":
        return (invariants.warm_sentinel_solve_fn() if sentinel
                else assignment.warm_solve_fn())
    return {
        ("full", False): assignment.allocate_solve,
        ("full", True): invariants.allocate_sentinel_solve,
        ("topk", False): assignment.allocate_topk_solve,
        ("topk", True): invariants.allocate_topk_sentinel_solve,
    }[kind, sentinel]


def probe_solve_fn(mesh: Mesh, config: AllocateConfig,
                   evict_config: EvictConfig, with_evictions: bool,
                   impl: Optional[str] = None):
    """Memoized jitted sharded what-if probe (ops/probe.py) for (mesh,
    config, evict_config, with_evictions, impl) — the query plane's
    dispatch on multi-device leases, and a jaxpr-audit entry point.  The
    shard_map impl authors its collectives (parallel/shard_solve.py);
    the pjit impl re-jits the single-device :func:`ops.probe.probe_body`
    with mesh shardings — the bit-exactness oracle, same split as the
    solves."""
    impl = _impl(impl)
    key = (mesh, config, evict_config, with_evictions, "probe", impl)
    fn = _jit_cache.get(key)
    if fn is None:
        if impl == "shard_map":
            from kube_batch_tpu.parallel import shard_solve

            fn = shard_solve.probe_shard_map(
                mesh, config, evict_config, with_evictions
            )
        else:
            from kube_batch_tpu.ops.probe import (
                ProbeBatch,
                ProbeResult,
                probe_body,
            )

            repl = NamedSharding(mesh, P())
            batch_shardings = ProbeBatch(
                *([repl] * len(ProbeBatch._fields)))
            out_shardings = ProbeResult(
                *([repl] * len(ProbeResult._fields)))
            fn = jax.jit(
                partial(probe_body, config=config,
                        evict_config=evict_config,
                        with_evictions=with_evictions),
                in_shardings=(snapshot_shardings(mesh), batch_shardings,
                              repl),
                out_shardings=out_shardings,
            )
        jitstats.register(f"sharded_probe_solve[{impl}]", fn)
        _jit_cache[key] = fn
    return fn


def sharded_probe_solve(snap: DeviceSnapshot, batch, probe_rows, mesh: Mesh,
                        config: AllocateConfig, evict_config: EvictConfig,
                        with_evictions: bool = False):
    """The batched what-if probe over the mesh: node-axis snapshot columns
    stay sharded (the lease's resident placement), the B-gang batch and
    row oracle replicate, every ProbeResult field comes back replicated."""
    fn = probe_solve_fn(mesh, config, evict_config, with_evictions)
    with mesh:
        return fn(snap, batch, probe_rows)


def enqueue_gate_solve_fn(mesh: Mesh):
    """Memoized mesh-replicated enqueue admission scan (the shard_map
    wrapper around ops.admission.gate_scan — zero cross-shard bytes; see
    shard_solve.enqueue_gate_shard_map for why it exists)."""
    key = (mesh, "enqueue_gate")
    fn = _jit_cache.get(key)
    if fn is None:
        from kube_batch_tpu.parallel import shard_solve

        fn = shard_solve.enqueue_gate_shard_map(mesh)
        jitstats.register("sharded_enqueue_gate", fn)
        _jit_cache[key] = fn
    return fn


def dispatch_enqueue_gate(min_res, cand, idle0, quanta, n_nodes_padded: int):
    """The enqueue action's gate dispatch: ride the mesh (replicated
    shard_map) when the cycle's solves shard and the shard_map path is on,
    else the single-device jitted scan.  Verdicts are bit-equal either way
    (both trace ops.admission.gate_scan)."""
    if should_shard(n_nodes_padded) and shard_map_enabled():
        mesh = default_mesh()
        with mesh:
            return enqueue_gate_solve_fn(mesh)(min_res, cand, idle0, quanta)
    from kube_batch_tpu.ops.admission import enqueue_gate_solve

    return enqueue_gate_solve(min_res, cand, idle0, quanta)


def collective_stats(mesh: Mesh, config: Optional[AllocateConfig] = None,
                     snap=None, pend_bucket: Optional[int] = None) -> dict:
    """Traced collective inventory of the shard_map allocate solve on
    `mesh` — the per-round / per-solve cross-shard byte accounting
    (utils/jitstats.collective_inventory) of the program XLA actually
    compiles, at the abstract shapes of ``snap`` (defaults to the audit's
    small shapes).  The bench and the sim report this next to the measured
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

    if snap is None:
        from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot

        snap = abstract_snapshot()
    config = config or AllocateConfig()
    if config.topk and pend_bucket:
        fn = allocate_topk_solve_fn(mesh, config, impl="shard_map")
        traced = fn.trace(
            snap, jax.ShapeDtypeStruct((pend_bucket,), jnp.int32)
        )
    else:
        fn = allocate_solve_fn(mesh, config, impl="shard_map")
        traced = fn.trace(snap)
    stats = jitstats.collective_inventory(traced.jaxpr)
    stats["mesh"] = {k: int(v) for k, v in dict(mesh.shape).items()}
    stats["task_bucket"] = int(snap.task_req.shape[0])
    stats["node_bucket"] = int(snap.node_idle.shape[0])
    if config.topk and pend_bucket:
        stats["topk"] = int(config.topk)
        stats["pend_bucket"] = int(pend_bucket)
    return stats

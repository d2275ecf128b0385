"""What the TPU's own compiler says of the programs the envelope deployment
(150,000 pods x 5,000 nodes on a v5e-4 host) rests on, compiled here for a
chip that is described and not attached (nothing runs: a compile gives
bytes, never a time).

The liveness audit (analysis/hbm_audit.py) cannot read a pjit program's
intermediate shardings and MODELS them (``EntryPoint.spmd_shards``: a value
with the global node axis is held at bytes / node shards).  This file holds
that model to the compiler: what XLA allocates on one of four devices for
the guard's pjit oracle at the envelope point is under the audit's count,
and both are under a v5e's 16 GiB.

One file, one fixture, the compile inside the test: only the worker that is
given this file loads the TPU's library."""

import os

import jax
import numpy as np
import pytest

from kube_batch_tpu.analysis.hbm_audit import (
    GIB,
    audit_entry_at,
    shape_points,
)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_the_pjit_oracle_at_the_envelope_fits_as_the_audit_models_it(topo):
    from jax.sharding import Mesh

    from kube_batch_tpu.analysis.jaxpr_audit import (
        EntryPoint,
        _build_sharded_allocate,
        _snap,
    )
    from kube_batch_tpu.ops.assignment import AllocateConfig
    from kube_batch_tpu.parallel import mesh as pm

    point = next(sp for sp in shape_points() if sp.name == "envelope-150k")
    mesh = Mesh(np.asarray(topo.devices), (pm.NODE_AXIS,))
    assert mesh.size == 4
    snap = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        _snap(point), pm.snapshot_shardings(mesh))
    fn = pm.allocate_solve_fn(mesh, AllocateConfig(), impl="pjit")
    with mesh:
        memory = fn.lower(snap).compile().memory_analysis()
    compiled = (memory.temp_size_in_bytes + memory.argument_size_in_bytes
                + memory.output_size_in_bytes)
    modelled = audit_entry_at(EntryPoint(
        "parallel.mesh.sharded_allocate_solve[pjit]",
        lambda sp: _build_sharded_allocate(mesh, "pjit", sp),
        spmd_shards=4), point).peak_bytes
    # XLA keeps the [150528, 5120] planes node-sharded: about one quarter
    # plane set a device, where a replicated plane alone is 2.9 GiB
    assert GIB < compiled <= modelled <= 16 * GIB, (compiled, modelled)


def test_on_the_pending_bucket_the_evict_solve_holds_a_sixth_of_the_planes(
        topo):
    """``overcommit-50k-5k``'s evict program (reclaim, both claimant gates,
    the guard's sentinel fused) at 50,176 x 5,120 on one chip: bidding on
    the pending bucket of 8,192 rows the compiler allocates under a GiB of
    temporaries, where the full-axis fallback takes over four (the cell's
    whole ``peak_bytes_reserved`` before PR 36)."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from kube_batch_tpu.actions.allocate import topk_bucket_for
    from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot
    from kube_batch_tpu.ops.eviction import EvictConfig
    from kube_batch_tpu.ops.invariants import evict_sentinel_solve

    one_chip = SingleDeviceSharding(topo.devices[0])
    T, N = 50_176, 5_120
    assert topk_bucket_for(T) == 8_192
    snap = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract_snapshot(T=T, N=N, J=13_312, Q=8, R=4, W=4, K=4))
    rows = jax.ShapeDtypeStruct((8_192,), jnp.int32, sharding=one_chip)
    ec = EvictConfig(mode="reclaim", idle_gate=True, releasing_gate=True)
    temp = {}
    for name, args in (("bucket", (snap, ec, rows)), ("full", (snap, ec))):
        memory = evict_sentinel_solve.lower(*args).compile().memory_analysis()
        temp[name] = memory.temp_size_in_bytes
    assert 0 < temp["bucket"] < GIB < 4 * GIB < temp["full"], temp
    assert temp["full"] > 5 * temp["bucket"]

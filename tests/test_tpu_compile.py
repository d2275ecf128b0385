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
import re

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
        _build,
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
    fn = pm.program("full", mesh, "pjit", AllocateConfig())
    with mesh:
        memory = fn.lower(snap).compile().memory_analysis()
    compiled = (memory.temp_size_in_bytes + memory.argument_size_in_bytes
                + memory.output_size_in_bytes)
    modelled = audit_entry_at(EntryPoint(
        "parallel.mesh.sharded_allocate_solve[pjit]",
        lambda sp: _build("full", {}, mesh, "pjit", False, sp),
        spmd_shards=4), point).peak_bytes
    # XLA keeps the [150528, 5120] planes node-sharded: about one quarter
    # plane set a device, where a replicated plane alone is 2.9 GiB
    assert GIB < compiled <= modelled <= 16 * GIB, (compiled, modelled)


T_ROWS, N_NODES, BUCKET = 50_176, 5_120, 8_192


@pytest.fixture(scope="module")
def evict_compiled(topo):
    """``overcommit-50k-5k``'s evict program (reclaim, both claimant gates,
    the guard's sentinel fused) at 50,176 x 5,120 on one chip, compiled on
    the pending bucket of 8,192 rows and on the full axis."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from kube_batch_tpu.actions.allocate import topk_bucket_for
    from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot
    from kube_batch_tpu.ops.eviction import EvictConfig
    from kube_batch_tpu.ops.invariants import evict_sentinel_solve

    one_chip = SingleDeviceSharding(topo.devices[0])
    assert topk_bucket_for(T_ROWS) == BUCKET
    snap = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract_snapshot(T=T_ROWS, N=N_NODES, J=13_312, Q=8, R=4, W=4, K=4))
    rows = jax.ShapeDtypeStruct((BUCKET,), jnp.int32, sharding=one_chip)
    ec = EvictConfig(mode="reclaim", idle_gate=True, releasing_gate=True)
    return {name: evict_sentinel_solve.lower(*args).compile()
            for name, args in (("bucket", (snap, ec, rows)),
                               ("full", (snap, ec)))}


def test_on_the_pending_bucket_the_evict_solve_holds_a_sixth_of_the_planes(
        evict_compiled):
    """Bidding on the pending bucket the compiler allocates under a GiB of
    temporaries, where the full-axis fallback takes nearly four (3.84 GiB;
    4.26 before the rounds became a conditional's branch, the cell's whole
    ``peak_bytes_reserved`` before PR 36)."""
    temp = {name: compiled.memory_analysis().temp_size_in_bytes
            for name, compiled in evict_compiled.items()}
    assert 0 < temp["bucket"] < GIB < 3 * GIB < temp["full"], temp
    assert temp["full"] > 5 * temp["bucket"]


def _computations(hlo: str) -> dict:
    """name -> the lines of each computation of a compiled module's text."""
    comps, lines = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", line)
        if head:
            lines = comps.setdefault(
                "ENTRY" if line.startswith("ENTRY") else head.group(1), [])
        elif line.startswith("}"):
            lines = None
        elif lines is not None:
            lines.append(line)
    return comps


def _reach(comps: dict, root_lines: list) -> list:
    """Every line of the computations ``root_lines`` call, however deep."""
    seen, todo, out = set(), list(root_lines), []
    while todo:
        line = todo.pop()
        out.append(line)
        called = re.findall(
            r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        for name in called:
            if name not in seen and name in comps:
                seen.add(name)
                todo.extend(comps[name])
    return out


def _task_axis_argsorts(lines) -> int:
    return sum(bool(re.search(rf"= \(s32\[{T_ROWS}\]\S*, \S+\) sort\(", ln))
               and "argsort" in ln for ln in lines)


@pytest.mark.parametrize("shape", ("bucket", "full"))
def test_a_solve_that_ends_at_its_gates_skips_the_task_axis_sorts(
        evict_compiled, shape):
    """The TPU compiler keeps what only the rounds read INSIDE the branch a
    solve with no claimant left does not take: the round loop and the
    victims' [T] rankings are in the conditional's one branch, the other is
    a handful of constants, and above the conditional only the gates' own
    two rankings (two keys each) sort the task axis."""
    comps = _computations(evict_compiled[shape].as_text())
    entry = comps["ENTRY"]
    (cond,) = [ln for ln in entry if " conditional(" in ln]
    (names,) = re.findall(r"branch_computations=\{([^}]*)\}", cond)
    nobody, rounds = (_reach(comps, comps[n.strip().lstrip("%")])
                      for n in names.split(","))
    assert sum(" while(" in ln for ln in rounds) >= 1
    assert _task_axis_argsorts(rounds) >= 2           # the victims' rank
    assert not any(" while(" in ln or " sort(" in ln for ln in nobody)
    assert len(nobody) < 16
    above = _reach(comps, [ln for ln in entry if ln is not cond])
    assert not any(" while(" in ln for ln in above)
    assert _task_axis_argsorts(above) == 4            # the gates' rankings


def _donating(fn):
    """`fn`'s traced body under a jit that donates argument 0 — what the
    resident swap programs are on an accelerator (their own wrappers gate
    donation off on this sandbox's CPU backend)."""
    return jax.jit(fn.__wrapped__, static_argnums=(3,), donate_argnums=(0,))


@pytest.mark.parametrize("where", ["one_chip_50k", "mesh_150k"])
def test_the_resident_swap_programs_compile_for_the_chip_and_alias(
        topo, where):
    """The packed swap programs (api/resident.py) at the benchmark's axes:
    the TPU compiler takes them at the widest slot bucket, and every
    donated resident buffer is aliased to its refreshed successor — the
    update is in place, so a swap allocates nothing the size of a column."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from kube_batch_tpu.analysis.jaxpr_audit import (
        _build_repl_swap,
        _build_resident_swap,
        _build_shard_swap,
    )
    from kube_batch_tpu.api import resident as res
    from kube_batch_tpu.parallel import mesh as pm

    def placed(args, sharding):
        devs, rows, vals, layout = args
        put = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=sharding)
        return jax.tree.map(put, (devs, rows, vals)) + (layout,)

    if where == "one_chip_50k":
        point = next(sp for sp in shape_points() if sp.name == "headline-50k")
        _fn, args = _build_resident_swap(point)
        programs = [(res._swap_scatter_fn(),
                     placed(args, SingleDeviceSharding(topo.devices[0])))]
    else:
        point = next(sp for sp in shape_points()
                     if sp.name == "envelope-150k")
        mesh = Mesh(np.asarray(topo.devices), (pm.NODE_AXIS,))
        programs = [
            (res._mesh_repl_scatter_fn(mesh),
             placed(_build_repl_swap(mesh, point)[1],
                    NamedSharding(mesh, P()))),
            (res._mesh_shard_scatter_fn(mesh),
             placed(_build_shard_swap(mesh, point)[1],
                    NamedSharding(mesh, P(pm.NODE_AXIS)))),
        ]
    for fn, args in programs:
        memory = _donating(fn).lower(*args).compile().memory_analysis()
        held = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in jax.tree.leaves(args[0]))
        per_device = held // (4 if "sharded" in fn.__name__ else 1)
        assert memory.alias_size_in_bytes >= per_device, (
            where, fn.__name__, memory.alias_size_in_bytes, per_device)
        # nothing column-sized beyond the aliased buffers and the payload
        assert memory.temp_size_in_bytes < per_device, (
            where, fn.__name__, memory.temp_size_in_bytes)

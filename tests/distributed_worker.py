"""Worker process for the two-process distributed smoke test
(tests/test_distributed.py). NOT a pytest module.

Each of the two ranks: joins the jax.distributed cluster over the given
coordinator, builds the IDENTICAL deterministic snapshot, distributes it
over the global 8-device mesh with the production shardings, runs the
sharded allocate solve, and (every rank — the outputs are replicated)
compares the assignment against the purely-local single-process solve.
Prints "MATCH placed=<n>" on success.
"""

import os
import sys


def main() -> None:
    coordinator, rank = sys.argv[1], int(sys.argv[2])
    # the CPU env must be in place BEFORE the first jax import
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from kube_batch_tpu.envutil import apply_cpu_env

    apply_cpu_env(n_devices=4)
    import jax

    from kube_batch_tpu.parallel.distributed import global_mesh, initialize

    initialize(coordinator=coordinator, num_processes=2, process_id=rank)
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert len(jax.local_devices()) == 4

    import numpy as np

    from kube_batch_tpu import plugins as _p  # noqa: F401 — registers
    from kube_batch_tpu.actions.allocate import (
        build_session_snapshot,
        session_allocate_config,
    )
    from kube_batch_tpu.framework.conf import load_scheduler_conf
    from kube_batch_tpu.framework.session import close_session, open_session
    from kube_batch_tpu.ops.assignment import allocate_solve
    from kube_batch_tpu.parallel.mesh import call, program, snapshot_shardings
    from kube_batch_tpu.testing.synthetic import synthetic_cluster

    # deterministic: both ranks build the same cluster (seed=0) — the
    # multi-controller contract: every process runs the same program
    cache = synthetic_cluster(n_tasks=128, n_nodes=300, gang_size=4,
                              n_queues=2, seed=0)
    conf = load_scheduler_conf(None)
    ssn = open_session(cache, conf.tiers)
    try:
        snap, meta = build_session_snapshot(ssn)
        config = session_allocate_config(ssn)

        # local single-process reference solve (local 4-device jit, no mesh)
        local = jax.device_get(allocate_solve(snap, config).assigned)

        mesh = global_mesh()
        assert mesh.devices.size == 8
        shardings = snapshot_shardings(mesh)

        def distribute(x, sharding):
            arr = np.asarray(x)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx]
            )

        gsnap = jax.tree.map(distribute, snap, shardings)
        result = call(program("full", mesh, None, config), mesh, gsnap)
        dist = jax.device_get(result.assigned)  # replicated output

        # BOTH sharded implementations, explicitly: the shard_map body's
        # authored collectives must cross the real two-process boundary
        # (ICI within a rank, DCN between) and still match the pjit oracle
        # and the local solve bit-for-bit
        with mesh:
            sm = jax.device_get(
                program("full", mesh, "shard_map", config)(gsnap).assigned
            )
            pj = jax.device_get(
                program("full", mesh, "pjit", config)(gsnap).assigned
            )

        # per-host sharded residency: each process diffs the full host
        # column but SHIPS only its own shards' rows (the
        # make_array_from_callback path) — the scatter-refreshed device
        # columns must round-trip bit-exact on every host's local shards
        from kube_batch_tpu.api.resident import ShardedPerCycleDeviceCache

        rc = ShardedPerCycleDeviceCache(mesh)
        with mesh:
            rc.swap(snap)
            host = np.asarray(snap.node_idle).copy()
            host[5] += 1.0
            host[257] += 2.0  # a row on the other process's shard
            snap2 = snap._replace(node_idle=host)
            sw2 = rc.swap(snap2)
        resident_ok = rc.scatter_updates > 0
        for s in sw2.node_idle.addressable_shards:
            if not np.array_equal(np.asarray(s.data), host[s.index]):
                resident_ok = False
    finally:
        close_session(ssn)

    if not np.array_equal(local, dist):
        diff = int((local != dist).sum())
        print(f"MISMATCH rank={rank} differing={diff}", flush=True)
        sys.exit(1)
    if not (np.array_equal(local, sm) and np.array_equal(local, pj)):
        print(f"IMPL MISMATCH rank={rank}"
              f" shard_map={np.array_equal(local, sm)}"
              f" pjit={np.array_equal(local, pj)}", flush=True)
        sys.exit(1)
    if not resident_ok:
        print(f"RESIDENT MISMATCH rank={rank}", flush=True)
        sys.exit(1)
    print("RESIDENT OK", flush=True)
    placed = int((dist >= 0).sum())
    assert placed > 0
    print(f"MATCH placed={placed}", flush=True)


if __name__ == "__main__":
    main()

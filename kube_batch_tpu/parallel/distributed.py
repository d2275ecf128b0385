"""Multi-host distributed setup — the DCN/ICI scaling story.

The reference scales out with active/passive HA replicas (leader election,
server.go:106-151); scheduling itself is single-process. Here the *solve*
scales across chips and hosts: the node axis shards over a global
`jax.sharding.Mesh` whose devices may span hosts — XLA/GSPMD inserts the
collectives, which ride ICI within a host slice and DCN across hosts. The
host-side cache/ingest stays on one leader process (elected via
cmd/leader_election.py); follower hosts only contribute devices through
`jax.distributed`.

Per-cycle cross-host traffic is the same O(tasks) per round as the
single-host sharded solve (parallel/mesh.py): budgets and score columns are
node-local, only the per-task winner (value, index) pairs all-reduce.

Usage on each host of the cluster:

    from kube_batch_tpu.parallel.distributed import initialize, global_mesh
    initialize(coordinator="host0:9000", num_processes=4, process_id=rank)
    mesh = global_mesh()          # 1-D 'nodes' mesh over ALL devices
    # leader: call(program("full", mesh, None, config), mesh, snap)
"""

from __future__ import annotations

from typing import Optional

import jax

# NOTE: no top-level kube_batch_tpu.parallel.mesh import — its import chain
# (ops.assignment's module-level jnp constants) initialises the XLA backend,
# which must not happen before jax.distributed.initialize runs


def initialize(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """jax.distributed.initialize wrapper. With no arguments, relies on the
    environment (TPU pod auto-configuration); no-op when already
    initialized or single-process.

    The already-initialized probe must NOT touch the backend:
    jax.process_count() would initialise XLA and make a subsequent
    jax.distributed.initialize impossible (the bug the two-process smoke
    test pinned, tests/test_distributed.py).  jax.distributed.is_initialized
    checks only the coordination-service client — backend-safe, and a
    failed earlier attempt (which leaves coordinator_address residue but no
    client) stays retryable."""
    if jax.distributed.is_initialized():
        return
    if coordinator is None and num_processes is None:
        try:
            jax.distributed.initialize()
        except (RuntimeError, ValueError):
            pass  # single-process / no cluster env — stay local
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def global_mesh():
    """1-D 'nodes' mesh over every device in the (possibly multi-host)
    cluster. Device order follows jax.devices(), so the mesh axis is
    contiguous per host — node shards stay host-local and the all-reduces
    are hierarchical (ICI within a host, DCN across)."""
    from kube_batch_tpu.parallel.mesh import make_mesh

    return make_mesh(None)

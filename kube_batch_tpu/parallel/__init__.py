"""Device-mesh parallelism. Exports resolve lazily (PEP 562): importing
this package must not pull in ops.assignment's module-level jnp constants,
which would initialise the XLA backend before a multi-host deployment's
jax.distributed.initialize (parallel/distributed.py) gets to run."""

__all__ = ["call", "make_mesh", "program", "snapshot_shardings"]


def __getattr__(name):
    if name in __all__:
        from kube_batch_tpu.parallel import mesh

        return getattr(mesh, name)
    raise AttributeError(name)

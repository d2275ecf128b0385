"""Enqueue admission gate — the jitted prefix-scan over job rows.

The reference's enqueue action (enqueue.go:102-117) walks Pending-phase
podgroups in (queue, job) priority order, admitting each whose MinResources
fit the remaining overcommitted idle and deducting on admission.  The walk
was the last O(jobs) Python loop in the 5-action pipeline; here the
sequential dependence (each admission shrinks the idle the next candidate
sees) becomes one ``lax.scan`` over the PRE-ORDERED candidate rows:

- the host supplies candidates already permuted into admission order
  (queues drained in tiered queue order — exact, because the session's
  queue_order_fn is a strict total order, so the reference's heap pop/push
  degenerates to drain-by-queue — jobs within a queue in tiered job order,
  both derived from columns; actions/enqueue.py);
- per step: ``ok = cand & (min ≤ idle tolerating sub-quantum excess)``
  (Resource.less_equal's exact comparison), then
  ``idle -= min`` clamped at zero (Resource.sub_'s clamp) when admitted;
- the admitted mask comes back in ONE readback; only promoted rows touch
  Python objects.

Precision: the device scan runs in float32 (the snapshot dtype contract —
f64 would trip KBT101 and be silently downcast off-x64 anyway), while the
retained object walk deducts in float64.  A naive f32 running difference
would drift by one ulp PER admission — at the 5k-node scale (idle memory
~5e13 bytes, f32 ulp ~4e6) a few thousand admissions could push the drift
past the 10 MiB comparison quantum.  The scan therefore carries the idle
budget as a Kahan-compensated (value, compensation) pair: the low bits
each subtraction would lose are carried forward, bounding the TOTAL
accumulation error to ~1 ulp regardless of admission count, which keeps
the worst-case divergence vs the f64 walk inside the input-cast rounding
(±½ ulp on idle0 and each MinResources row) — below the comparison quanta
for every real resource magnitude, so a verdict can differ from the walk
only for a job sitting within ~1 ulp of the tolerance band's edge.

Shapes are the padded job-axis capacity, so the scan compiles once per
(capJ, R) bucket and steady-state cycles are jit cache hits (the bench's
retrace counters include it).  Registered in the jaxpr audit
(analysis/jaxpr_audit.py) so KBT101-104 cover it in tier-1.
"""

from __future__ import annotations

from kube_batch_tpu.utils import jitstats

_GATE = None


def gate_scan(min_res, cand, idle0, quanta):
    """The raw (untraced) admission scan — shared by the single-device jit
    wrapper below AND the mesh-replicated shard_map wrapper
    (parallel/mesh.py's "gate" row), so both paths trace the
    identical program and the verdicts are bit-equal by construction."""
    import jax
    import jax.numpy as jnp

    def step(carry, inp):
        idle, comp = carry
        m, c = inp
        eff = idle + comp  # compensated view of the budget
        fits = jnp.all((m <= eff) | (m - eff < quanta))
        ok = c & fits
        # Kahan/Neumaier-compensated deduction: carry the low bits
        # `idle - m` would round away (module docstring)
        y = jnp.where(ok, comp - m, comp)
        t = idle + y
        comp = (idle - t) + y
        idle = jnp.maximum(t, 0.0)  # Resource.sub_'s clamp
        comp = jnp.where(idle > 0.0, comp, 0.0)
        return (idle, comp), ok

    init = (idle0, jnp.zeros_like(idle0))
    _, admitted = jax.lax.scan(step, init, (min_res, cand))
    return admitted


def enqueue_gate_fn():
    """The shared jitted admission scan (module-level memo — one compile
    cache for every cache/scheduler instance in the process)."""
    global _GATE
    if _GATE is None:
        import jax

        _GATE = jitstats.register("enqueue_gate", jax.jit(gate_scan))
    return _GATE


def enqueue_gate_solve(min_res, cand, idle0, quanta):
    """Admitted mask for candidates in scan order: ``min_res`` [capJ, R]
    f32 (MinResources rows, zeros on padding), ``cand`` [capJ] bool
    (candidate AND statically enqueueable), ``idle0`` [R] f32 the
    overcommitted idle, ``quanta`` [R] f32 the comparison quanta."""
    return enqueue_gate_fn()(min_res, cand, idle0, quanta)

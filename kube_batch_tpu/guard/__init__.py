"""Result-integrity guard plane — runtime verification of the fast paths.

Three tiers, wired through every dispatching action:

1. **Cycle invariant sentinel** (ops/invariants.py): a fused device-side
   check appended to each solve program; a nonzero verdict makes the
   action FAIL CLOSED — no binds/evictions from a condemned solve.
2. **Sampled shadow-oracle audit**: every KB_AUDIT_EVERY-th dispatch the
   committed solve re-runs through its oracle path (KB_TOPK=0 / pjit /
   full-matrix) against the same snapshot, bit-compared off the critical
   path (overlapped with the host replay).
3. **Self-healing demotion** (:class:`GuardPlane`): a per-fast-path health
   breaker — a trip demotes the engaged fast paths to their oracles,
   drops the resident device cache (an HBM corruption heals on the next
   full upload), and dumps a self-contained diagnostics bundle
   (guard/bundle.py) that ``python -m kube_batch_tpu.sim --replay-bundle``
   reloads for deterministic offline triage; half-open probes re-promote
   after KB_GUARD_COOLDOWN clean cycles.  A demotion whose target does not
   fit the device (guard/fit.py: the full [T, N] matrix of a cluster sized
   for the compacted path) fails closed instead of landing on it.

Knobs: ``KB_GUARD=0`` (escape hatch — no sentinel, no audits, no
demotion), ``KB_AUDIT_EVERY`` (default 64; 0 = audits off),
``KB_GUARD_COOLDOWN`` (clean cycles before a half-open probe; default 8),
``KB_GUARD_DIR`` (diagnostics bundle directory).
"""

from kube_batch_tpu.guard.fit import OracleUnfit
from kube_batch_tpu.guard.plane import (
    FAST_PATHS,
    GuardPlane,
    consume_assignment_sentinel,
    consume_sentinel,
    guard_of,
    make_heal,
    sentinel_bundle_thunk,
)

__all__ = [
    "FAST_PATHS", "GuardPlane", "OracleUnfit", "consume_assignment_sentinel",
    "consume_sentinel", "guard_of", "make_heal", "sentinel_bundle_thunk",
]

"""Pipelined-cycle smoke (wired into scripts/check.sh): seed-deterministic
virtual-time evidence for the event-driven loop.

Two checks, one JSON summary line:

1. Trigger policy (smoke preset, trigger-bound): the pipelined loop's
   pod-arrival→bind-decision p99 must beat the fixed 1 s tick by ≥ 2×
   (it is bounded by the min-period floor, not the period), with the same
   jobs completed and clean invariants.
2. Chaos integrity (bind-storm preset, capacity-bound): the pipelined loop
   under the binder-flap storm must produce ZERO duplicate/lost binds,
   drain the whole workload, and report a p99 no worse than the serial
   tick's (the tail there is queueing, not the tick — the ratio is
   reported, the ≥2× bar belongs to the trigger-bound cases above and to
   the CPU bench's live-arrival section).

Exit 0 = all invariants hold; 1 = any violated.
"""

from __future__ import annotations

import json
import os
import sys

# runnable as `python scripts/pipeline_smoke.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kube_batch_tpu.envutil import apply_cpu_env  # noqa: E402

apply_cpu_env()

from kube_batch_tpu.sim.runner import run_preset  # noqa: E402


def main() -> int:
    errors = []

    serial = run_preset("smoke", seed=3)
    pipe = run_preset("smoke", seed=3, pipelined=True)
    p99_serial = serial["pod_bind_latency_vt"]["p99"]
    p99_pipe = pipe["pod_bind_latency_vt"]["p99"]
    if pipe["bind_integrity"]["duplicate_binds"]:
        errors.append("smoke/pipelined: duplicate binds")
    if pipe["invariants"]["errors"]:
        errors.append(f"smoke/pipelined: {pipe['invariants']['errors']}")
    if pipe["jobs"] != serial["jobs"]:
        errors.append(
            f"smoke: job outcomes diverged {pipe['jobs']} vs {serial['jobs']}")
    if not (p99_pipe * 2 <= p99_serial):
        errors.append(
            f"smoke: pipelined p99 {p99_pipe} not ≥2× better than the "
            f"fixed tick's {p99_serial}")

    storm = run_preset("bind-storm", seed=0, pipelined=True)
    bi = storm["bind_integrity"]
    if bi["duplicate_binds"]:
        errors.append("bind-storm/pipelined: duplicate binds")
    if storm["invariants"]["errors"]:
        errors.append(f"bind-storm/pipelined: {storm['invariants']['errors']}")
    jobs = storm["jobs"]
    if jobs["completed"] != jobs["submitted"]:
        errors.append(
            f"bind-storm/pipelined: {jobs['completed']}/{jobs['submitted']} "
            "jobs completed — storm did not drain")

    print(json.dumps({
        "smoke_p99_vt": {"serial": p99_serial, "pipelined": p99_pipe,
                         "improvement": round(p99_serial / p99_pipe, 1)
                         if p99_pipe else None},
        "bind_storm_pipelined": {
            "p99_vt": storm["pod_bind_latency_vt"]["p99"],
            "mean_vt": storm["pod_bind_latency_vt"]["mean"],
            "cycles": storm["cycles_run"],
            "duplicate_binds": bi["duplicate_binds"],
            "acked_binds": bi["acked_binds"],
        },
        "errors": errors,
    }, sort_keys=True))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())

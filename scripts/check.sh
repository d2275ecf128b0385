#!/usr/bin/env bash
# kbt-check, all four tiers: the static AST/flow rules over the package
# tree, the jaxpr-level audit of the registered jitted entry points, the
# tier-C liveness/HBM-budget audit (every entry point traced at the
# abstract shape ladder up to 1M×100k — CPU-pinned, traces only, no device
# memory is ever allocated), AND the tier-D thread/lock-domain race rules
# (KBT301-304 over the inferred per-class lock domains) — then the seeded
# chaos smoke (bind-storm + leader-failover sim presets), so
# fault-hardening invariants run on every PR alongside the lint tiers.
# Exit 0 = clean, 1 = findings / violated chaos invariants, 2 = usage error.
#
# CI usage:  scripts/check.sh [--jsonl]
# The jaxpr tier imports jax; pin it to CPU so the check never takes an
# accelerator another process may hold — tracing is abstract, the backend
# only matters for the donation table, and CPU is the declared-() baseline.
# A forced host-platform device count gives the audit a virtual mesh so the
# SHARDED solve variants trace too — both the shard_map bodies (incl. the
# 2-D tasks×nodes mesh variant and the mesh enqueue gate) and the pjit
# oracle (KBT101-104 over every sharded path, without a multi-device CI
# mesh); an explicit count in XLA_FLAGS wins.
set -euo pipefail
cd "$(dirname "$0")/.."
if [[ "${XLA_FLAGS:-}" != *xla_force_host_platform_device_count* ]]; then
  export XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}"
fi
env JAX_PLATFORMS=cpu python -m kube_batch_tpu.analysis --jaxpr --hbm --races "$@"

# chaos smoke: each preset's CLI exits nonzero on a violated recovery
# invariant (lost/duplicate binds, accounting drift, failed fault
# recovery) — deterministic per seed, CPU-only, ~1 min combined
echo "kbt-check: chaos smoke (bind-storm, leader-failover)"
env JAX_PLATFORMS=cpu python -m kube_batch_tpu.sim \
  --preset bind-storm --seed 0 --no-fairness-series >/dev/null
env JAX_PLATFORMS=cpu python -m kube_batch_tpu.sim \
  --preset leader-failover --seed 5 --no-fairness-series >/dev/null
echo "kbt-check: chaos smoke clean"

# guard smoke: the result-integrity corruption preset — three resident
# device-column corruptions must each trip the sentinel with ZERO bad
# binds dispatched (no duplicate acks, no accounting drift), demotion
# must engage and re-promote, and every trip's diagnostics bundle must
# --replay-bundle deterministically (exit nonzero on any violation)
echo "kbt-check: guard smoke (corruption preset + bundle replay)"
GUARD_TMP="$(mktemp -d)"
trap 'rm -rf "$GUARD_TMP"' EXIT
env JAX_PLATFORMS=cpu KB_GUARD_DIR="$GUARD_TMP" python -m kube_batch_tpu.sim \
  --preset corruption --seed 0 --no-fairness-series >/dev/null
for bundle in "$GUARD_TMP"/trip-*; do
  env JAX_PLATFORMS=cpu python -m kube_batch_tpu.sim \
    --replay-bundle "$bundle" >/dev/null
done
echo "kbt-check: guard smoke clean"

# whatif smoke: the serve/ query plane end to end — loopback AdminServer,
# mixed feasible/infeasible gangs via the kb-ctl whatif CLI, verdict +
# Prometheus-counter + amortization assertions (scripts/whatif_smoke.py)
echo "kbt-check: whatif smoke (query plane)"
env JAX_PLATFORMS=cpu python scripts/whatif_smoke.py

# pipeline smoke: the event-driven pipelined loop's virtual-time evidence —
# trigger-bound p99 ≥2× better than the fixed 1 s tick, and the bind-storm
# chaos preset pipelined with zero duplicate binds and a full drain
echo "kbt-check: pipeline smoke (event-driven cycles)"
env JAX_PLATFORMS=cpu python scripts/pipeline_smoke.py

# trace smoke: the cycle tracing plane — traced sim run with a validating
# Chrome trace-event export, corruption-trip flight-recorder dumps that
# validate, and the pipelined overlap rendered as overlapping spans
# (scripts/trace_smoke.py; KBT014 keeps span bodies clock-free statically)
echo "kbt-check: trace smoke (spans + flight recorder)"
env JAX_PLATFORMS=cpu python scripts/trace_smoke.py

# warm smoke: the KB_WARM A/B leg (ISSUE 14) — the warm-churn preset run
# twice, carried candidate table vs the cold per-solve build; every acked
# bind must be bit-identical and the carry must actually engage (the CLI
# exits nonzero on either failure)
echo "kbt-check: warm smoke (KB_WARM A/B, warm-churn preset)"
env JAX_PLATFORMS=cpu python -m kube_batch_tpu.sim \
  --preset warm-churn --seed 3 --warm-ab --no-fairness-series >/dev/null
echo "kbt-check: warm smoke clean"

# replication smoke: the replicate/ follower read plane over real loopback
# HTTP — a leader + two pull-loop followers under randomized churn, with
# bit-matched /v1/whatif(+/sweep) verdicts once caught up, staleness p99
# ≤ 1 cycle on live followers, and serving continuity + warm re-adoption
# through one follower kill/restart (scripts/replication_smoke.py)
echo "kbt-check: replication smoke (leader + 2 followers)"
env JAX_PLATFORMS=cpu python scripts/replication_smoke.py

"""Benchmark — the BASELINE.json north star on real hardware.

Times the FULL scheduling cycle at 50k pods × 5k nodes (heterogeneous
gangs, 3 weighted queues, minMember=4): open_session (cache deep-clone +
plugin open) → allocate.execute (device snapshot build + compiled solve +
host replay + bulk bind) → close_session (status writeback), through the
real cache handlers and fake binder — the end-to-end path the reference's
1 s schedule-period covers (scheduler.go:88-102, options.go:28).

Prints ONE JSON line: {"metric", "value", "unit", "device", "vs_baseline",
"phases", ...}.  value is the e2e p50 over the timed cycles; phases is the
p50 per-phase breakdown in ms. vs_baseline is measured against the
driver-provided target of a 1000 ms cycle — >1 means faster than target.

It measures the accelerator or nothing: where JAX finds only the CPU it
exits non-zero without a number, and a section that raises ends the run
non-zero.  This process holds the chip, so it starts no child that needs
one (chip_smoke.py is the pattern for that: a parent off JAX, one child).
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import sys
import time

import numpy as np

from kube_batch_tpu.envutil import enable_persistent_compilation_cache

enable_persistent_compilation_cache()  # compiles survive across invocations


from kube_batch_tpu import actions as _actions  # noqa: E402,F401 — registers
from kube_batch_tpu import plugins as _plugins  # noqa: E402,F401 — registers
from kube_batch_tpu.api.resident import (  # noqa: E402
    scatter_summary as _resident_scatter_summary,
)
from kube_batch_tpu.framework.conf import load_scheduler_conf  # noqa: E402
from kube_batch_tpu.framework.session import close_session, open_session  # noqa: E402
from kube_batch_tpu.framework.interface import get_action  # noqa: E402
from kube_batch_tpu.testing.synthetic import synthetic_cluster  # noqa: E402

TARGET_MS = 1000.0  # <1s per cycle on TPU v5e (BASELINE.md north star)

N_TASKS = 50_000
N_NODES = 5_000
CYCLES = 6  # p50 over more cycles — host-load noise at this scale is ±10%


def one_cycle(conf, cache):
    """One full scheduling cycle; returns (phase_ms, binds)."""
    phases = {}
    t0 = time.perf_counter()
    ssn = open_session(cache, conf.tiers)
    phases["open_session"] = (time.perf_counter() - t0) * 1e3
    for name in conf.actions:
        t0 = time.perf_counter()
        get_action(name).execute(ssn)
        phases[f"action_{name}"] = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    close_session(ssn)
    phases["close_session"] = (time.perf_counter() - t0) * 1e3
    # fold the allocate-internal breakdown in (snapshot build / device solve /
    # host replay) — recorded by the action itself
    for k, v in get_action("allocate").last_phase_ms.items():
        phases[f"allocate_{k}"] = v
    t0 = time.perf_counter()
    cache.flush_binds()
    phases["async_bind_drain"] = (time.perf_counter() - t0) * 1e3
    return phases


def _pct(values, p):
    """Nearest-rank percentile (the shared sim/metrics definition)."""
    from kube_batch_tpu.sim.metrics import nearest_rank

    return nearest_rank(values, p)


def measure(conf, make_cache, cycles):
    """Warm once (compile), then time `cycles` fresh-cache runs under the
    shared gc discipline. Returns (p50_ms, phase_p50, phase_p90, warmup_ms,
    placed_on_warmup) — the warmup/compile cycle is timed and labeled
    separately so compile cost never leaks into the steady percentiles."""
    warm = make_cache()
    t0 = time.perf_counter()
    one_cycle(conf, warm)
    warmup_ms = (time.perf_counter() - t0) * 1e3
    placed = len(warm.binder.binds)
    del warm
    e2e, per_phase = [], []
    for _ in range(cycles):
        cache = make_cache()
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        phases = one_cycle(conf, cache)
        e2e.append((time.perf_counter() - t0) * 1e3)
        gc.enable()
        per_phase.append(phases)
        del cache
    phase_p50 = {
        k: round(statistics.median(p[k] for p in per_phase), 1)
        for k in per_phase[0]
    }
    phase_p90 = {
        k: round(_pct([p[k] for p in per_phase], 0.90), 1)
        for k in per_phase[0]
    }
    return statistics.median(e2e), phase_p50, phase_p90, warmup_ms, placed


def multicycle_bench(conf, n_tasks, n_nodes, cycles=8, warmup_cycles=2,
                     churn_frac=0.02, seed=0, delta=True, wobble=0.1):
    """The steady-state multi-cycle regime the 1 s schedule period actually
    runs in: ONE persistent cache, per-cycle churn (bound gangs complete,
    new gangs arrive) with a ±10% pod-count wobble, back-to-back cycles.

    This is where the cross-cycle resident snapshot earns its keep — and
    where a shape-bucket regression would show as retraces.  Per cycle it
    records the phase breakdown, the open/snapshot path taken (delta vs
    full), and the jit compile delta; the summary separates the labeled
    warmup cycles from the steady percentiles.  `delta=False` forces the
    full-rebuild path for the same workload, giving the reduction
    denominator on the same host."""
    import itertools

    import numpy as np

    from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION, Pod, PodGroup
    from kube_batch_tpu.api.types import PodPhase
    from kube_batch_tpu.testing.synthetic import CPU_CHOICES, MEM_CHOICES
    from kube_batch_tpu.utils import jitstats

    cache = synthetic_cluster(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=3
    )
    cache.delta_enabled = delta
    # pre-reserve the wobble ceiling so axis growth (a one-off recompile)
    # happens at warmup, never mid-steady-state
    cache.columns.reserve(
        n_tasks=int(n_tasks * 1.15), n_jobs=int(n_tasks / 4 * 1.15) + 8
    )
    rng = np.random.default_rng(seed)
    serial = itertools.count(1_000_000)
    gang = 4

    def churn_step():
        k = max(1, int(len(cache.jobs) * churn_frac))
        done = 0
        for uid, job in list(cache.jobs.items()):
            if done >= k:
                break
            pods = [cache.pods.get(key) for key in job.tasks]
            if not pods or any(p is None or p.node_name is None for p in pods):
                continue
            for p in sorted(pods, key=lambda p: p.name):
                cache.delete_pod(p)
            cache.delete_pod_group(uid)
            done += 1
        want = int(n_tasks * (1.0 + wobble * float(rng.uniform(-1, 1))))
        while len(cache.pods) + gang <= want:
            j = next(serial)
            cache.add_pod_group(PodGroup(
                name=f"mc{j}", namespace="bench", min_member=gang,
                queue=f"q{j % 3}", creation_index=j,
            ))
            for t in range(gang):
                cache.add_pod(Pod(
                    name=f"mc{j}-{t}", namespace="bench",
                    requests={
                        "cpu": float(rng.choice(CPU_CHOICES)),
                        "memory": float(rng.choice(MEM_CHOICES)),
                    },
                    annotations={GROUP_NAME_ANNOTATION: f"mc{j}"},
                    phase=PodPhase.PENDING,
                    creation_index=j * 10 + t,
                ))

    def warm_failure_histogram():
        """The fit-error histogram only dispatches on cycles with unplaced
        pending tasks, which may first occur mid-steady-state — compile it
        during warmup so the zero-retrace claim covers failure cycles too.
        Warms the variant the allocate dispatch would actually pick, so a
        sharded run doesn't warm (and hold resident copies for) the wrong
        path."""
        from kube_batch_tpu.actions.allocate import build_session_snapshot
        from kube_batch_tpu.api.columns import resident_snap
        from kube_batch_tpu.ops.assignment import failure_histogram_solve
        from kube_batch_tpu.framework.session import (
            close_session as _close, open_session as _open,
        )
        from kube_batch_tpu.parallel.mesh import (
            default_mesh, sharded_failure_histogram, should_shard,
        )

        ssn = _open(cache, conf.tiers)
        try:
            snap, _ = build_session_snapshot(ssn)
            if should_shard(snap.node_alloc.shape[0]):
                mesh = default_mesh()
                sharded_failure_histogram(
                    resident_snap(cache.columns, snap, mesh), mesh
                ).block_until_ready()
            else:
                failure_histogram_solve(
                    resident_snap(cache.columns, snap)
                ).block_until_ready()
        finally:
            _close(ssn)

    records = []
    pod_counts = []
    for c in range(warmup_cycles + cycles):
        if c:
            churn_step()
        if c == warmup_cycles:
            warm_failure_histogram()
        pod_counts.append(len(cache.pods))
        compiles0 = jitstats.total_compiles()
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        rec = one_cycle(conf, cache)
        rec["e2e"] = (time.perf_counter() - t0) * 1e3
        gc.enable()
        rec["compiles"] = jitstats.total_compiles() - compiles0
        rec["open_path"] = cache.last_open_path
        rec["snapshot_path"] = cache.columns.last_snapshot_path
        rec["topk"] = get_action("allocate").last_topk
        rec["solve_rounds"] = get_action("allocate").last_solve_rounds
        records.append(rec)
    # span-recorder stats for the trace_overhead section: spans per cycle
    # and per-stage counts, straight off the per-cache tracer (obs/trace)
    tracer = getattr(cache, "tracer", None)
    trace_stats = (
        tracer.stage_attribution()
        if tracer is not None and tracer.enabled else None
    )
    cache.stop()

    warm, steady = records[:warmup_cycles], records[warmup_cycles:]
    phase_keys = sorted(set().union(*(set(r) for r in steady))
                        - {"compiles", "open_path", "snapshot_path",
                           "topk", "solve_rounds"})
    summary = {
        k: {
            "p50": round(_pct([r.get(k, 0.0) for r in steady], 0.50), 2),
            "p90": round(_pct([r.get(k, 0.0) for r in steady], 0.90), 2),
        }
        for k in phase_keys
    }
    open_plus_snap = [
        r.get("open_session", 0.0) + r.get("allocate_snapshot_build", 0.0)
        for r in steady
    ]
    paths = {}
    for r in steady:
        key = f"{r['open_path']}/{r['snapshot_path']}"
        paths[key] = paths.get(key, 0) + 1
    # candidate-compaction evidence (ISSUE 10): which steady cycles ran the
    # compacted program, the K/bucket they ran at, and the exhaustion /
    # full-head-re-entry counters that prove K is sized right (an
    # exhaustion rate near 0 means the table almost never falls back)
    topk_cycles = [r for r in steady if r.get("topk")]
    rounds_steady = [r.get("solve_rounds", 0) for r in steady]
    topk_summary = {
        "compacted_cycles": len(topk_cycles),
        "steady_cycles": len(steady),
        "rounds_run_p50": _pct(rounds_steady, 0.50) if rounds_steady else 0,
    }
    if topk_cycles:
        exh = sum(r["topk"]["exhausted"] for r in topk_cycles)
        reent = sum(r["topk"]["reentries"] for r in topk_cycles)
        rounds_c = sum(max(r.get("solve_rounds", 0), 1) for r in topk_cycles)
        topk_summary.update({
            "k": topk_cycles[-1]["topk"]["k"],
            "bucket": max(r["topk"]["bucket"] for r in topk_cycles),
            "exhausted_total": exh,
            "reentries_total": reent,
            "exhaustion_rate_per_round": round(exh / rounds_c, 4),
            "reentries_per_solve": round(reent / len(topk_cycles), 3),
        })
    # warm-carry evidence (ISSUE 14): which steady cycles ran the carried
    # table, how many cold-rebuilt, and the invalidated-row fraction —
    # re-ranked rows over the live bucket, the delta-work claim
    warm_cycles = [r["topk"]["warm"] for r in topk_cycles
                   if r["topk"].get("warm")]
    warm_summary = {"warm_cycles": len(warm_cycles)}
    if warm_cycles:
        merged = [w for w in warm_cycles if not w["cold"]]
        fracs = [
            w["reranked"] / max(w["bucket_live"], 1) for w in merged
        ]
        warm_summary.update({
            "cold_builds": len(warm_cycles) - len(merged),
            "invalidated_row_fraction_mean": (
                round(float(np.mean(fracs)), 4) if fracs else None
            ),
            "changed_nodes_mean": (
                round(float(np.mean([w["changed"] for w in merged])), 1)
                if merged else None
            ),
        })
    topk_summary["warm"] = warm_summary
    return {
        "delta_enabled": delta,
        "pods_target": n_tasks,
        "nodes": n_nodes,
        "churn_frac": churn_frac,
        "pod_count_range": [min(pod_counts), max(pod_counts)],
        "warmup_cycles": warmup_cycles,
        "warmup_e2e_ms": [round(r["e2e"], 1) for r in warm],
        "warmup_compiles": sum(r["compiles"] for r in warm),
        "steady_cycles": len(steady),
        "steady": summary,
        "open_plus_snapshot_build_ms": {
            "p50": round(_pct(open_plus_snap, 0.50), 2),
            "p90": round(_pct(open_plus_snap, 0.90), 2),
        },
        # the acceptance counters: which path each steady cycle took, and
        # whether ANY steady cycle retraced (must be 0 across the wobble)
        "snapshot_paths": paths,
        "retraces_steady": sum(r["compiles"] for r in steady),
        "topk": topk_summary,
        "jit_compile_counts": jitstats.compile_counts(),
        # which solve the cycles dispatched ("single" | "sharded") and the
        # per-cycle device-resident cache's delta-vs-full bytes-moved
        # evidence, per path (api/resident.py counters)
        "solve_mode": get_action("allocate").last_solve_mode,
        "shard_impl": _shard_impl(),
        "resident_scatter": _resident_scatter_summary(
            cache.columns.resident_counters()
        ),
        # per-slot warm-carry lifetime counters (plans / cold builds /
        # re-ranked and changed totals) — the ColumnStore-side view of
        # the per-cycle "warm" records above
        "warm_tables": cache.columns.warm_counters(),
        "trace": trace_stats,
    }


def _shard_impl() -> str:
    from kube_batch_tpu.parallel.mesh import shard_map_enabled, task_shards

    impl = "shard_map" if shard_map_enabled() else "pjit"
    ts = task_shards()
    return f"{impl},tasks={ts}" if ts > 1 else impl


def run_multicycle_pair(conf, n_tasks, n_nodes, cycles=8):
    """Delta vs forced-full-rebuild on the same host/workload; returns
    (delta_report, full_report, open+snapshot p50 reduction)."""
    mc_delta = multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles,
                                delta=True)
    mc_full = multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles,
                               delta=False)
    d = mc_delta["open_plus_snapshot_build_ms"]["p50"]
    f = mc_full["open_plus_snapshot_build_ms"]["p50"]
    reduction = round(1.0 - d / f, 3) if f > 0 else 0.0
    return mc_delta, mc_full, reduction


def _oracle_ab_pair(env_key, on_fn, off_fn):
    """The shared scaffolding of every fast-path-vs-oracle comparison:
    run ``on_fn`` with ``env_key`` unset (the fast path's default), then
    ``off_fn`` with it pinned to "0" (the oracle), restoring the caller's
    environment either way."""
    saved = os.environ.get(env_key)
    try:
        os.environ.pop(env_key, None)
        on = on_fn()
        os.environ[env_key] = "0"
        off = off_fn()
    finally:
        if saved is None:
            os.environ.pop(env_key, None)
        else:
            os.environ[env_key] = saved
    return on, off


def run_topk_pair(conf, n_tasks, n_nodes, cycles=6):
    """Compacted-vs-full solve-phase comparison on the same host/workload
    (ISSUE 10 acceptance): the multicycle regime with KB_TOPK at its
    default vs KB_TOPK=0 (the full-matrix oracle).  Returns a dict with
    both solve p50s, the speedup, and the compacted run's candidate-table
    stats — the compacted run must also show zero steady retraces."""
    on, off = _oracle_ab_pair(
        "KB_TOPK",
        lambda: multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles),
        lambda: multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles),
    )
    s_on = on["steady"].get("allocate_solve", {}).get("p50", 0.0)
    s_off = off["steady"].get("allocate_solve", {}).get("p50", 0.0)
    return {
        "pods": n_tasks, "nodes": n_nodes,
        "solve_p50_ms_topk": s_on,
        "solve_p50_ms_full": s_off,
        "solve_speedup": round(s_off / s_on, 2) if s_on > 0 else 0.0,
        "e2e_p50_ms_topk": on["steady"].get("e2e", {}).get("p50"),
        "e2e_p50_ms_full": off["steady"].get("e2e", {}).get("p50"),
        "retraces_steady_topk": on.get("retraces_steady"),
        "topk": on.get("topk"),
    }


def run_warm_pair(conf, n_tasks, n_nodes, cycles=6):
    """Warm-vs-cold solve-phase comparison on the same host/workload
    (ISSUE 14 acceptance): the multicycle regime with KB_WARM at its
    default (carried candidate table + in-program repair) vs KB_WARM=0
    (the cold per-solve build oracle), both with compaction on.  Returns
    both solve p50s, the speedup, the warm run's invalidated-row fraction
    (re-ranked rows over the live bucket — the delta-work evidence), and
    the warm run's steady retrace count (must be 0)."""
    # the acceptance regime is ≤2% GANG churn and nothing else: the
    # pod-count wobble is OFF for both legs (fair A/B) — the default
    # ±10% wobble is the retrace-hunting workload, whose random
    # multi-hundred-pod bursts legitimately visit new sub-bucket rungs
    # (a one-time compile each, like any shape-bucket growth).  The
    # shared warmup is long enough both for the workload to reach its
    # standing-backlog equilibrium (the regime the carry serves) and for
    # the rung ratchets to settle off the cold-start burst
    # (WARM_RUNG_DECAY plans) before the steady window.
    def leg():
        return multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles,
                                warmup_cycles=14, wobble=0.0)

    on, off = _oracle_ab_pair("KB_WARM", leg, leg)
    s_on = on["steady"].get("allocate_solve", {}).get("p50", 0.0)
    s_off = off["steady"].get("allocate_solve", {}).get("p50", 0.0)
    return {
        "pods": n_tasks, "nodes": n_nodes,
        "solve_p50_ms_warm": s_on,
        "solve_p50_ms_cold": s_off,
        "solve_speedup": round(s_off / s_on, 2) if s_on > 0 else 0.0,
        "e2e_p50_ms_warm": on["steady"].get("e2e", {}).get("p50"),
        "e2e_p50_ms_cold": off["steady"].get("e2e", {}).get("p50"),
        "retraces_steady_warm": on.get("retraces_steady"),
        "warm": (on.get("topk") or {}).get("warm"),
        "topk": on.get("topk"),
    }


def guard_overhead_bench(conf, n_tasks=20_000, n_nodes=2_000, reps=13,
                         steady_cycles=6):
    """Sentinel-on vs sentinel-off cost (guard-plane acceptance): the
    fused invariant tail must cost <5% of steady-cycle p50.

    Methodology: the sentinel is a FUSED tail on each solve program, and
    a full-program A/B pair is unmeasurable on a loaded 2-core CPU box —
    a ~1-3ms tail hides under the solve's ±10% run-to-run wobble (an
    A-then-B multicycle pair even flips sign between runs).  So the tail
    programs THEMSELVES are timed — ``allocate_invariants`` /
    ``evict_invariants`` + the eligibility checksum, jitted standalone on
    the real snapshot and a real solve result: exactly the operations the
    fusion appends, with none of the solve's noise.  The per-cycle cost
    sums one allocate tail and both eviction tails (every sentinel-fused
    dispatch of the shipped 5-action steady cycle); the denominator is
    the steady-cycle e2e p50 from a multicycle run under the production
    default (guard on).  Audit cycles are excluded by design: they
    re-run the oracle as OVERLAPPED work."""
    import functools
    import time as _time

    import jax

    def _timed(fn, *args):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(reps):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(_time.perf_counter() - t0)
        return statistics.median(ts) * 1e3

    cache = synthetic_cluster(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=3
    )
    ssn = open_session(cache, conf.tiers)
    try:
        from kube_batch_tpu.actions.allocate import session_allocate_config
        from kube_batch_tpu.api.columns import resident_snap
        from kube_batch_tpu.ops.assignment import allocate_solve
        from kube_batch_tpu.ops.eviction import EvictConfig, evict_solve
        from kube_batch_tpu.ops.invariants import (
            allocate_invariants,
            eligibility_checksum,
            evict_invariants,
        )

        cols = cache.columns
        snap, _meta = cols.device_snapshot(ssn)
        config = session_allocate_config(ssn)._replace(topk=0)
        dev = resident_snap(cols, snap)
        res = allocate_solve(dev, config)
        jax.block_until_ready(res)
        atail = jax.jit(functools.partial(allocate_invariants, config=config))
        ck = jax.jit(eligibility_checksum)
        t_alloc = _timed(lambda: (atail(dev, res), ck(dev)))
        ecfg = EvictConfig(mode="preempt")
        eres = evict_solve(dev, ecfg)
        jax.block_until_ready(eres)
        etail = jax.jit(functools.partial(evict_invariants, config=ecfg))
        t_evict = _timed(lambda: (etail(dev, eres), ck(dev)))
    finally:
        close_session(ssn)
    del cache
    # per steady cycle: one allocate tail + reclaim & preempt tails
    deltas = t_alloc + 2.0 * t_evict
    # denominator: the steady-cycle e2e p50 under the production default
    # (guard on) — overhead_pct is the whole cycle's sentinel tax
    mc = multicycle_bench(conf, n_tasks, n_nodes, cycles=steady_cycles)
    e2e = mc["steady"].get("e2e", {}).get("p50", 0.0)
    return {
        "pods": n_tasks, "nodes": n_nodes, "reps": reps,
        "target": "overhead_pct < 5",
        "allocate_sentinel_tail_ms": round(t_alloc, 2),
        "evict_sentinel_tail_ms": round(t_evict, 2),
        "sentinel_delta_ms_per_cycle": round(deltas, 2),
        "steady_cycle_e2e_p50_ms": e2e,
        "overhead_pct": round(100.0 * deltas / e2e, 2) if e2e > 0 else 0.0,
        "retraces_steady": mc.get("retraces_steady"),
    }


def trace_overhead_bench(conf, n_tasks=20_000, n_nodes=2_000, cycles=6,
                         reps=20_000):
    """Span-recorder cost vs the steady e2e p50 (<2% acceptance target),
    with zero new steady retraces.

    Methodology (the guard_overhead precedent): a full A/B multicycle
    pair on the loaded 2-core box is noise-dominated — a sub-ms per-cycle
    tracing cost hides under the solve's ±10% wobble — so the span
    machinery ITSELF is micro-timed (context-manager enter/exit with ring
    retention, plus the device-span counter probes: the jit compile-count
    read and the resident-counter read, paid twice per device span) and
    multiplied by the spans-per-cycle the traced multicycle run actually
    created; the denominator is that run's steady e2e p50.  The A/B pair
    still runs and is reported as corroboration, and the traced run's
    retrace counter is the zero-new-retraces acceptance."""
    import tempfile

    from kube_batch_tpu.obs.recorder import FlightRecorder
    from kube_batch_tpu.obs.trace import Tracer
    from kube_batch_tpu.utils import jitstats

    saved = os.environ.get("KB_TRACE")
    try:
        os.environ.pop("KB_TRACE", None)        # default = tracing on
        on = multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles)
        os.environ["KB_TRACE"] = "0"
        off = multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles)
    finally:
        if saved is None:
            os.environ.pop("KB_TRACE", None)
        else:
            os.environ["KB_TRACE"] = saved
    e2e_on = on["steady"].get("e2e", {}).get("p50", 0.0)
    e2e_off = off["steady"].get("e2e", {}).get("p50", 0.0)
    trace = on.get("trace") or {}
    n_cycles = 2 + cycles  # multicycle_bench's warmup + steady cycles
    spans_per_cycle = trace.get("spans_total", 0) / n_cycles
    device_span_names = {"solve_dispatch", "device_wait", "gate_dispatch",
                         "fit_histogram_dispatch", "fit_errors",
                         "audit_dispatch"}
    dev_spans_per_cycle = sum(
        c for name, c in (trace.get("stages") or {}).items()
        if name in device_span_names
    ) / n_cycles

    # micro: span enter/exit with full retention (ring + stage counters)
    tr = Tracer(
        recorder=FlightRecorder(
            ring=256, directory=tempfile.mkdtemp(prefix="kb-flight-bench-")
        ),
        enabled=True,
    )
    t0 = time.perf_counter()
    for _ in range(reps):
        with tr.span("bench"):
            pass
    span_ms = (time.perf_counter() - t0) / reps * 1e3
    # micro: the device-span counter probes (sampled at enter AND exit)
    t0 = time.perf_counter()
    for _ in range(1000):
        jitstats.total_compiles()
    jit_probe_ms = (time.perf_counter() - t0) / 1000 * 1e3
    probe_cache = synthetic_cluster(n_tasks=256, n_nodes=32, gang_size=4,
                                    n_queues=1)
    cols = probe_cache.columns
    t0 = time.perf_counter()
    for _ in range(1000):
        cols.resident_counters()
    scat_probe_ms = (time.perf_counter() - t0) / 1000 * 1e3
    probe_cache.stop()

    modeled_ms = (
        spans_per_cycle * span_ms
        + dev_spans_per_cycle * 2.0 * (jit_probe_ms + scat_probe_ms)
    )
    return {
        "pods": n_tasks, "nodes": n_nodes,
        "target": "overhead_pct < 2",
        "spans_per_cycle": round(spans_per_cycle, 1),
        "device_spans_per_cycle": round(dev_spans_per_cycle, 1),
        "span_cost_us": round(span_ms * 1e3, 3),
        "device_probe_cost_us": round(
            (jit_probe_ms + scat_probe_ms) * 1e3, 3),
        "trace_delta_ms_per_cycle": round(modeled_ms, 3),
        "steady_cycle_e2e_p50_ms": e2e_on,
        "overhead_pct": round(100.0 * modeled_ms / e2e_on, 3)
        if e2e_on > 0 else 0.0,
        # corroborating A/B pair (noise-dominated on a loaded CPU box —
        # the modeled number above is the acceptance figure)
        "e2e_p50_ms_trace_on": e2e_on,
        "e2e_p50_ms_trace_off": e2e_off,
        "ab_delta_pct": round(100.0 * (e2e_on - e2e_off) / e2e_off, 2)
        if e2e_off > 0 else 0.0,
        # zero NEW steady retraces with tracing on (the inertness half)
        "retraces_steady_trace_on": on.get("retraces_steady"),
        "retraces_attributed": trace.get("retraces_attributed"),
    }


def lock_profile_bench(conf, n_tasks=2_000, n_nodes=200, cycles=8,
                       feeders=2):
    """Lock-hold / acquire-wait profile over the pipelined cycle under
    concurrent staged ingest — the measurement the ROADMAP's 'striped
    per-kind ingest locks (profile first)' item asks for.  lockdep's
    TrackedLock accumulates per-lock-class wait/hold (per-thread, merged
    at report time); feeder threads stage gang arrivals through the real
    ingest surface while the pipelined loop cycles, so the profile shows
    whether the single staging buffer (or the cache big lock) actually
    contends before anyone pays for striping."""
    import threading

    from kube_batch_tpu.analysis import lockdep
    from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION, Pod, PodGroup
    from kube_batch_tpu.api.types import PodPhase
    from kube_batch_tpu.scheduler import Scheduler

    was_installed = lockdep.current_state() is not None
    state = lockdep.install()
    try:
        # the cache is built AFTER install so its locks are tracked
        cache = synthetic_cluster(
            n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=2
        )
        cache.columns.reserve(
            n_tasks=n_tasks + 4 * feeders * cycles * 4,
            n_jobs=n_tasks // 4 + feeders * cycles * 4 + 8,
        )
        sched = Scheduler(cache, conf=conf)
        sched.run_once()  # warm the compiles outside the profiled window
        cache.enable_ingest_staging()
        stop_evt = threading.Event()

        def feeder(fid: int):
            i = 0
            while not stop_evt.is_set():
                name = f"lf{fid}-{i}"
                cache.add_pod_group(PodGroup(
                    name=name, namespace="lp", min_member=1, queue="q0",
                    creation_index=9_000_000 + fid * 100_000 + i,
                ))
                cache.add_pod(Pod(
                    name=f"{name}-0", namespace="lp",
                    requests={"cpu": 100.0, "memory": float(2 ** 28)},
                    annotations={GROUP_NAME_ANNOTATION: name},
                    phase=PodPhase.PENDING,
                    creation_index=90_000_000 + fid * 100_000 + i,
                ))
                i += 1
                time.sleep(0.002)

        threads = [threading.Thread(target=feeder, args=(f,), daemon=True)
                   for f in range(feeders)]
        for t in threads:
            t.start()
        for _ in range(cycles):
            sched.run_once_pipelined()
        stop_evt.set()
        for t in threads:
            t.join(timeout=10)
        sched.drain_pipeline()
        cache.disable_ingest_staging()
        if sched._wb_pool is not None:
            sched._wb_pool.shutdown(wait=True)
            sched._wb_pool = None
        cache.stop()
        prof = state.profile_report()
    finally:
        if not was_installed:
            lockdep.uninstall()
    # rank by total acquire-wait: the contention signal striping would fix
    top = dict(list(prof.items())[:10])
    cache_sites = {
        site: rec for site, rec in prof.items()
        if "cache.cache" in site
    }
    total_wait = sum(r["wait_ms_total"] for r in prof.values())
    ingest_wait = sum(r["wait_ms_total"] for r in cache_sites.values())
    return {
        "pods": n_tasks, "nodes": n_nodes, "cycles": cycles,
        "feeder_threads": feeders,
        "total_wait_ms": round(total_wait, 3),
        "cache_lock_wait_ms": round(ingest_wait, 3),
        "top_sites_by_wait": top,
    }


def collective_evidence(n_tasks, n_nodes):
    """Per-round cross-shard byte accounting of the shard_map allocate
    solve, TRACED at the bench's real padded shapes (utils/jitstats.
    collective_inventory over the program XLA compiles — measured from the
    jaxpr, not asserted).  The scaling proof: re-trace with the node count
    doubled at fixed tasks (per-round bytes must not move — the round
    collectives are the O(tasks) winner-vector reductions) and with the
    task count doubled (bytes must ~double)."""
    from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot
    from kube_batch_tpu.api.snapshot import bucket
    from kube_batch_tpu.parallel.mesh import (
        collective_stats,
        default_mesh,
        shard_map_enabled,
    )

    mesh = default_mesh()
    if mesh is None:
        return {"skipped": "single-device backend"}
    if not shard_map_enabled():
        return {"skipped": "KB_SHARD_MAP=0 (pjit oracle path)"}
    J, Q = bucket(max(1, n_tasks // 4)), 8

    def stats(t, n):
        return collective_stats(
            mesh, snap=abstract_snapshot(T=bucket(t), N=bucket(n), J=J, Q=Q)
        )

    base = stats(n_tasks, n_nodes)
    nodes2 = stats(n_tasks, 2 * n_nodes)
    tasks2 = stats(2 * n_tasks, n_nodes)
    rounds = get_action("allocate").last_solve_rounds
    return {
        "mesh": base["mesh"],
        "task_bucket": base["task_bucket"],
        "node_bucket": base["node_bucket"],
        "per_round_bytes": base["per_round_bytes"],
        # the one-time node-ledger all_gather (O(N·R) per SOLVE, not round)
        "per_solve_bytes": base["per_solve_bytes"],
        "ops": base["ops"],
        # measured rounds of the last cycle × traced per-round bytes = the
        # cycle's cross-shard budget
        "rounds_last_cycle": rounds,
        "bytes_last_cycle": (
            base["per_solve_bytes"]
            + base["per_round_bytes"] * max(rounds, 1)
        ),
        "per_round_bytes_nodes_x2": nodes2["per_round_bytes"],
        "per_round_bytes_tasks_x2": tasks2["per_round_bytes"],
        "per_round_scales_with_tasks": bool(
            nodes2["per_round_bytes"] == base["per_round_bytes"]
            and tasks2["per_round_bytes"] > base["per_round_bytes"]
        ),
        # the compacted program's contract: after the ONE per-solve
        # candidate merge + node-column gathers, rounds cross zero bytes
        "topk": _topk_collective_evidence(n_tasks, n_nodes, J, Q),
    }


def _topk_collective_evidence(n_tasks, n_nodes, J, Q):
    from kube_batch_tpu.actions.allocate import TOPK_PEND_BUCKETS, resolve_topk
    from kube_batch_tpu.analysis.jaxpr_audit import abstract_snapshot
    from kube_batch_tpu.api.snapshot import bucket
    from kube_batch_tpu.ops.assignment import AllocateConfig
    from kube_batch_tpu.parallel.mesh import collective_stats, default_mesh

    k = resolve_topk()
    if not k:
        # KB_TOPK=0: the measured cycles dispatched the full program —
        # emitting compacted-path evidence here would attribute it to a
        # run that never executed the compacted solve
        return {"disabled": "KB_TOPK=0 (full-matrix oracle run)"}
    st = collective_stats(
        default_mesh(), config=AllocateConfig(topk=k),
        snap=abstract_snapshot(T=bucket(n_tasks), N=bucket(n_nodes), J=J, Q=Q),
        pend_bucket=TOPK_PEND_BUCKETS[0],
    )
    return {
        "k": k,
        "pend_bucket": st["pend_bucket"],
        "per_round_bytes": st["per_round_bytes"],
        "per_solve_bytes": st["per_solve_bytes"],
        "zero_round_collectives": st["per_round_bytes"] == 0,
    }


def hbm_round_head_model(T=500_000, N=50_000, R=8, node_ring=8,
                         hbm_gb=16.0):
    """Per-device residency model of the [T, N]-scale round-head
    intermediates at the 500k×50k north star: ~14 live bytes per
    (task, node) block element at the round peak (masked+score_static f32,
    tie-hash i32, fit/static bools).  The node axis shards along one
    fixed-width ICI ring (``node_ring``); extra devices can only join the
    TASK axis — which is exactly when 2-D sharding is the difference
    between fitting the 16 GB v5e HBM and not.  The task-axis bench probe
    pairs this model with an actually-completed 2-D-mesh cycle."""
    BYTES_PER_ELT = 14
    budget = hbm_gb * 2**30
    rows = []
    for ts in (1, 2, 4, 8):
        per_dev = (T / ts) * (N / node_ring) * BYTES_PER_ELT
        rows.append({
            "task_shards": ts,
            "devices": ts * node_ring,
            "round_head_gb": round(per_dev / 2**30, 1),
            "fits_hbm": bool(per_dev < budget),
        })
    return {
        "tasks": T, "nodes": N, "node_ring": node_ring,
        "hbm_gb": hbm_gb, "bytes_per_elt": BYTES_PER_ELT,
        "configs": rows,
    }


def hbm_headroom_bench():
    """The tier-C audit's bytes-vs-budget numbers as a bench section, so
    the headroom trajectory is tracked across PRs like any other perf
    number.  Tracing is abstract (no device memory, backend-independent):
    the peaks are the liveness model's per-device bytes at each ladder
    point — see analysis/hbm_audit.py for the model and its documented
    overestimate-direction slack.  Entries that fail to trace at a point
    record ``traced: false`` (the audit's KBT000 covers the alarm)."""
    from kube_batch_tpu.analysis.hbm_audit import GIB, headroom_report

    rep = headroom_report()
    entries = {}
    worst = None
    for name, per_point in rep["entries"].items():
        compact = {}
        for pt, d in per_point.items():
            if not d["traced"]:
                compact[pt] = {"traced": False}
                continue
            compact[pt] = {
                "peak_gib": round(d["peak_bytes"] / GIB, 3),
                "headroom_gib": round(d["headroom_bytes"] / GIB, 3),
                "over_budget": d["over_budget"],
            }
            if worst is None or d["peak_bytes"] > worst[2]:
                worst = (name, pt, d["peak_bytes"])
        entries[name] = compact
    out = {
        "budget_gib": round(rep["budget_bytes"] / GIB, 1),
        "budget_profile": rep["budget_profile"],
        "points": {
            p["name"]: {"tasks": p["tasks"], "nodes": p["nodes"],
                        "T": p["T"], "N": p["N"], "P": p["P"]}
            for p in rep["points"]
        },
        "entries": entries,
    }
    if worst is not None:
        out["worst"] = {
            "entry": worst[0], "point": worst[1],
            "peak_gib": round(worst[2] / GIB, 3),
        }
    return out


def task_axis_probe(conf, n_tasks, n_nodes, cycles=3):
    """The task-axis-sharded cycle: rerun the steady-state regime on a 2-D
    (tasks=2 × nodes) mesh (KB_TASK_SHARDS=2) and report that the cycle
    completes sharded with zero steady retraces, next to the HBM model
    showing the node×task sizes only the 2-D mesh can hold resident."""
    import jax

    n_dev = len(jax.devices())
    if n_dev < 4 or n_dev % 2:
        return {"skipped": f"{n_dev} devices (need an even count >= 4)",
                "hbm_model": hbm_round_head_model()}
    saved = os.environ.get("KB_TASK_SHARDS")
    os.environ["KB_TASK_SHARDS"] = "2"
    try:
        rep = multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles)
    finally:
        if saved is None:
            os.environ.pop("KB_TASK_SHARDS", None)
        else:
            os.environ["KB_TASK_SHARDS"] = saved
    return {
        "task_shards": 2,
        "solve_mode": rep.get("solve_mode"),
        "steady_e2e_ms": rep.get("steady", {}).get("e2e"),
        "retraces_steady": rep.get("retraces_steady"),
        "resident_scatter": rep.get("resident_scatter"),
        "hbm_model": hbm_round_head_model(),
    }


def sharded_multicycle(conf, n_tasks, n_nodes, cycles=6):
    """The sharded steady-state section: the multicycle regime (persistent
    cache, 2% churn, ±10% wobble) dispatched over the device mesh — reports
    the per-shard delta-vs-full upload reduction, the retrace counters,
    the traced per-round collective-bytes evidence, and the task-axis
    (2-D mesh) probe.  Requires ≥2 devices and a node axis past the shard
    gate."""
    import jax

    from kube_batch_tpu.parallel.mesh import SHARD_MIN_NODES

    if len(jax.devices()) < 2:
        return {"skipped": "single-device backend"}
    if n_nodes < SHARD_MIN_NODES:
        return {"skipped": f"node axis below shard gate ({SHARD_MIN_NODES})"}
    rep = multicycle_bench(conf, n_tasks, n_nodes, cycles=cycles)
    if rep.get("solve_mode") != "sharded":
        rep["warning"] = "solve did not dispatch sharded"
    try:
        rep["collectives"] = collective_evidence(n_tasks, n_nodes)
    except Exception as e:  # noqa: BLE001 — evidence must not sink the bench
        rep["collectives_error"] = f"{type(e).__name__}: {e}"
    try:
        # probe at a bounded size: the 2-D mesh's point is the HBM model +
        # a completed sharded cycle, not a second full-scale run
        rep["task_axis"] = task_axis_probe(
            conf, min(n_tasks, 2000), min(n_nodes, 600)
        )
    except Exception as e:  # noqa: BLE001
        rep["task_axis_error"] = f"{type(e).__name__}: {e}"
    return rep


def whatif_serving_bench(conf, n_tasks=20_000, n_nodes=2_000,
                         n_clients=16, requests_per_client=25):
    """The serve/ query-plane bench (ISSUE 8): N concurrent what-if
    clients against a 20k×2k snapshot, driven straight at
    ``QueryPlane.submit`` (the HTTP hop is constant per request and
    covered by the check.sh smoke — this section measures the batcher +
    probe dispatch).  Reports p50/p99 request latency, achieved QPS, mean
    batch size, and dispatches per 100 requests; the amortization claim is
    dispatch counter < requests (many requests per device dispatch) with
    ZERO probe retraces after warmup across varying batch fill."""
    import threading

    import numpy as np

    from kube_batch_tpu.serve.plane import QueryPlane
    from kube_batch_tpu.utils import jitstats

    cache = synthetic_cluster(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=3
    )
    qp = QueryPlane(cache, max_batch=32, window_s=0.002, start_thread=True)
    try:
        one_cycle(conf, cache)  # the cycle publishes the snapshot lease
        gib = float(2 ** 30)

        def ask(count, cpu):
            return {"queue": "q0", "count": count,
                    "requests": {"cpu": cpu, "memory": gib}}

        def probe_compiles():
            # every probe path — single-device "probe_solve" AND the
            # per-mesh "sharded_probe_solve[impl]" registrations — so the
            # zero-retrace claim measures whichever path serving took
            return sum(v for k, v in jitstats.compile_counts().items()
                       if "probe_solve" in k)

        # warmup: compile the probe at the serving (B, G) buckets
        for count in (1, 3, 8):
            qp.submit(ask(count, 500.0)).result(timeout=300)
        compiles0 = probe_compiles()
        req0, disp0 = qp.requests_served, qp.dispatches

        lat: list = []
        errors: list = []
        lock = threading.Lock()

        def client(k):
            rng = np.random.default_rng(k)
            mine = []
            try:
                for _ in range(requests_per_client):
                    body = ask(int(rng.integers(1, 9)),
                               float(rng.choice([250.0, 1000.0, 4000.0])))
                    t0 = time.perf_counter()
                    resp = qp.submit(body).result(timeout=300)
                    mine.append((time.perf_counter() - t0) * 1e3)
                    assert "feasible" in resp
            except Exception as e:  # noqa: BLE001 — surface, don't hang
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                return
            with lock:
                lat.extend(mine)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        elapsed = time.perf_counter() - t0
        total = qp.requests_served - req0
        dispatches = qp.dispatches - disp0
        retraces = probe_compiles() - compiles0
        out = {
            "n_tasks": n_tasks,
            "n_nodes": n_nodes,
            "clients": n_clients,
            "requests": total,
            "whatif_p50_ms": round(_pct(lat, 0.50), 2) if lat else None,
            "whatif_p99_ms": round(_pct(lat, 0.99), 2) if lat else None,
            "qps": round(total / elapsed, 1) if elapsed > 0 else None,
            "device_dispatches": dispatches,
            "mean_batch_size": round(total / dispatches, 2) if dispatches else None,
            "dispatches_per_100_requests": (
                round(100.0 * dispatches / total, 1) if total else None
            ),
            # the acceptance pair: amortized (≫1 request per dispatch) and
            # no steady-state retraces across varying batch fill
            "amortized": bool(total > dispatches > 0),
            "retraces_after_warmup": retraces,
        }
        if errors:
            out["client_errors"] = errors[:3]
        return out
    finally:
        qp.close()


def pipelined_bench(conf, n_tasks=400, n_nodes=48, arrivals=10,
                    period=1.0, seed=0):
    """Event-driven pipelined cycles (ISSUE 9): the arrival→decision
    latency a user actually observes, measured live — a feeder thread
    posts single-pod gangs at random offsets while the L1 loop runs in
    (a) the reference's serial wait.Until(1 s) shape and (b) the
    event-driven pipelined mode (ingest staging + trigger wake + staged
    close with the writeback worker).  Same arrival stream, same warmed
    cache shape; the serial loop's latency is dominated by the tick (mean
    ~period/2, p99 → period), the pipelined loop's by its min-period
    floor.  Also reports the overlap gain: the writeback ms each pipelined
    cycle hides behind the next cycle's compute, and zero steady retraces
    on both paths."""
    import threading

    import numpy as np

    from kube_batch_tpu import metrics as prom_metrics
    from kube_batch_tpu.api.pod import GROUP_NAME_ANNOTATION, Pod, PodGroup
    from kube_batch_tpu.api.types import PodPhase
    from kube_batch_tpu.metrics.metrics import PIPELINE_OVERLAP
    from kube_batch_tpu.scheduler import Scheduler
    from kube_batch_tpu.utils import jitstats

    def one_mode(pipelined: bool) -> dict:
        cache = synthetic_cluster(
            n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=3
        )
        sched = Scheduler(cache, conf=conf, schedule_period=period)
        sched.pipelined = pipelined
        sched.min_period = 0.02
        sched.max_period = period
        # pre-reserve the feed's axis growth so it lands in a pre-warmed
        # bucket — the zero-retrace claim must hold through the arrivals
        cache.columns.reserve(
            n_tasks=n_tasks + 4 * arrivals,
            n_jobs=n_tasks // 4 + 4 * arrivals,
        )
        # warmup: compile + place the synthetic backlog before the feed
        for _ in range(2):
            sched.run_once()
        sink: list = []
        prom_metrics.set_decision_latency_sink(sink)
        compiles0 = jitstats.total_compiles()
        overlap0 = (PIPELINE_OVERLAP._sum.get((), 0.0),
                    PIPELINE_OVERLAP._count.get((), 0))
        rng = np.random.default_rng(seed)
        offsets = rng.uniform(0.15, 0.45, size=arrivals)
        fed: list = []

        def feeder():
            for i, dt in enumerate(offsets):
                time.sleep(float(dt))
                name = f"arr{i}"
                cache.add_pod_group(PodGroup(
                    name=name, namespace="feed", min_member=1, queue="q0",
                    creation_index=5_000_000 + i,
                ))
                cache.add_pod(Pod(
                    name=f"{name}-0", namespace="feed",
                    requests={"cpu": 250.0, "memory": float(2 ** 30)},
                    annotations={GROUP_NAME_ANNOTATION: name},
                    phase=PodPhase.PENDING,
                    creation_index=50_000_000 + i,
                ))
                fed.append(f"feed/{name}-0")

        loop = threading.Thread(target=sched.run_forever, daemon=True)
        feed = threading.Thread(target=feeder, daemon=True)
        try:
            loop.start()
            feed.start()
            feed.join(timeout=60)
            deadline = time.perf_counter() + 6 * period + 10
            while time.perf_counter() < deadline:
                if len(sink) >= arrivals:
                    break
                time.sleep(0.05)
        finally:
            sched.stop()
            loop.join(timeout=30)
            prom_metrics.set_decision_latency_sink(None)
        retraces = jitstats.total_compiles() - compiles0
        out = {
            "mode": "pipelined" if pipelined else "serial",
            "arrivals": arrivals,
            "decided": len(sink),
            "p50_ms": round(_pct(sink, 0.50), 1) if sink else None,
            "p99_ms": round(_pct(sink, 0.99), 1) if sink else None,
            "mean_ms": round(sum(sink) / len(sink), 1) if sink else None,
            "retraces_steady": retraces,
        }
        if pipelined:
            ov_sum = PIPELINE_OVERLAP._sum.get((), 0.0) - overlap0[0]
            ov_n = PIPELINE_OVERLAP._count.get((), 0) - overlap0[1]
            out["writeback_overlapped_ms_mean"] = (
                round(ov_sum / ov_n, 2) if ov_n else None
            )
            out["writeback_stages"] = ov_n
        return out

    serial = one_mode(False)
    pipe = one_mode(True)
    ratio = None
    if serial["p99_ms"] and pipe["p99_ms"]:
        ratio = round(serial["p99_ms"] / pipe["p99_ms"], 2)
    return {
        "n_tasks": n_tasks,
        "n_nodes": n_nodes,
        "period_s": period,
        "serial": serial,
        "pipelined": pipe,
        # the acceptance pair: arrival→decision p99 ≥2× better than the
        # fixed tick, with zero steady retraces on BOTH paths
        "p99_improvement": ratio,
        "acceptance_2x": bool(ratio is not None and ratio >= 2.0
                              and serial["retraces_steady"] == 0
                              and pipe["retraces_steady"] == 0),
    }


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        # a measurement path that finds no chip fails: no chip, no number
        sys.exit("bench.py measures the accelerator and JAX found only "
                 "the CPU; nothing measured")

    start = time.perf_counter()
    # soft deadline for the optional sections: a section whose worst-case
    # runtime no longer fits is skipped and listed in `sections_skipped`
    deadline_s = float(os.environ.get("KB_BENCH_DEADLINE", "420"))

    conf = load_scheduler_conf(None)  # default: allocate, backfill

    def make_cache():
        return synthetic_cluster(
            n_tasks=N_TASKS, n_nodes=N_NODES, gang_size=4, n_queues=3
        )

    p50, phase_p50, phase_p90, warmup_ms, placed = measure(
        conf, make_cache, CYCLES
    )
    solve_rounds = get_action("allocate").last_solve_rounds
    result = {
        "metric": (
            f"full_cycle_ms_{N_TASKS // 1000}k_pods_"
            f"{N_NODES // 1000}k_nodes_placed_{placed}"
        ),
        "value": round(p50, 2),
        "unit": "ms",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "vs_baseline": round(TARGET_MS / p50, 2),
        "phases": phase_p50,
        "phases_p90": phase_p90,
        # the compile cycle, labeled apart from the steady percentiles —
        # a retrace regression shows up HERE, not smeared into the p50
        "warmup_cycle_ms": round(warmup_ms, 1),
        # measured convergence of the final timed cycle's solve (the
        # while_loops early-exit well inside the 6x3 round budget)
        "solve_rounds": solve_rounds,
    }

    skipped = []

    def section(name, margin_s=0.0):
        """Deadline gate: a skipped section is recorded in
        `sections_skipped`.  `margin_s` is the section's worst-case runtime
        — checked up front, because the deadline can't interrupt a section
        mid-flight and a case started just under the wire would blow the
        driver timeout.  A section that RAISES ends the run non-zero."""
        if time.perf_counter() - start + margin_s > deadline_s:
            skipped.append(name)
            return False
        return True

    # ---- steady-state multi-cycle regime (cross-cycle resident snapshot):
    # delta vs forced-full-rebuild on the same host, plus the zero-retrace
    # proof across the ±10% pod-count wobble — the PR's acceptance evidence
    if section("multicycle", margin_s=150):
        mc_d, mc_f, red = run_multicycle_pair(
            conf, N_TASKS, N_NODES, cycles=8
        )
        result["multicycle"] = mc_d
        result["multicycle_full_rebuild"] = mc_f
        result["multicycle_open_snapshot_reduction"] = red

    # ---- compacted-vs-full solve comparison (ISSUE 10): the top-K
    # candidate table's ≥2× solve-phase p50 claim at the 20k×2k regime,
    # with the compacted run's exhaustion/retrace counters
    if section("topk_compare", margin_s=150):
        result["topk_compare"] = run_topk_pair(
            conf, 20_000, 2_000, cycles=6
        )

    # ---- warm-vs-cold solve comparison (ISSUE 14): the carried candidate
    # table's ≥3× solve-phase p50 claim at ≤2% gang churn (20k×2k, CPU),
    # with the per-cycle invalidated-row fraction and zero steady retraces
    if section("incremental_solve", margin_s=320):
        result["incremental_solve"] = run_warm_pair(
            conf, 20_000, 2_000, cycles=6
        )

    # ---- result-integrity guard overhead: the fused sentinel's cost on
    # the steady cycle must stay under 5% of p50 (the verdict rides the
    # existing per-action readback; audit cycles are overlapped work)
    if section("guard_overhead", margin_s=150):
        result["guard_overhead"] = guard_overhead_bench(conf)

    # ---- cycle tracing plane (ISSUE 13): the span recorder's cost vs the
    # steady p50 must stay under 2% with zero new steady retraces, and the
    # lockdep contention profile answers the striped-ingest-lock question
    if section("trace_overhead", margin_s=200):
        result["trace_overhead"] = trace_overhead_bench(conf)
    if section("lock_profile", margin_s=60):
        result["lock_profile"] = lock_profile_bench(conf)

    # ---- tier-C HBM headroom: the liveness audit's peak-live-bytes vs the
    # v5e budget per entry per ladder point — abstract traces only, so the
    # numbers are identical on any backend and regress visibly in the JSON
    if section("hbm_headroom", margin_s=90):
        result["hbm_headroom"] = hbm_headroom_bench()

    # ---- the SHARDED steady-state regime: same persistent-cache churn
    # cycle over the device mesh — the per-shard scatter-delta residency's
    # bytes-moved reduction and zero-retrace proof (this PR's acceptance)
    if section("multicycle_sharded", margin_s=150):
        result["multicycle_sharded"] = sharded_multicycle(
            conf, N_TASKS, N_NODES
        )

    # ---- the serve/ query plane: concurrent what-if clients against a
    # 20k×2k snapshot — request latency, QPS, and the amortization proof
    # (dispatches ≪ requests, zero retraces across varying batch fill)
    if section("whatif_serving", margin_s=120):
        result["whatif_serving"] = whatif_serving_bench(conf)

    # ---- event-driven pipelined cycles: live arrival→decision latency,
    # serial 1 s tick vs trigger-driven loop, + the writeback overlap gain
    if section("pipelined", margin_s=60):
        result["pipelined"] = pipelined_bench(conf)

    # ---- ≥10×-vs-Go-loop target (BASELINE.md): time the faithful
    # sequential re-creation of the reference's allocate loop over the same
    # workload.  Three denominators bracket the reference (measured, not
    # argued — go_baseline module docstring): the numpy re-creation, the
    # whole loop in compiled C single-threaded (maximally generous), and
    # the C loop with the reference's 16-worker chunked pass.
    if section("go_loop", margin_s=45):
        from kube_batch_tpu.testing.go_baseline import run_go_baseline

        go_stats = run_go_baseline(N_TASKS, N_NODES, gang_size=4, n_queues=3)
        result["go_loop_ms"] = round(go_stats["elapsed_ms"], 1)
        result["speedup_vs_go_loop"] = round(go_stats["elapsed_ms"] / p50, 1)
        if "native_single_ms" in go_stats:
            result["go_loop_native_single_ms"] = go_stats["native_single_ms"]
            result["speedup_vs_go_loop_native_single"] = round(
                go_stats["native_single_ms"] / p50, 2
            )
        if "native_pooled_ms" in go_stats:
            result["go_loop_native_pooled_ms"] = go_stats["native_pooled_ms"]
            result["speedup_vs_go_loop_native_pooled"] = round(
                go_stats["native_pooled_ms"] / p50, 2
            )
        # a diverging C run reports a divergence count INSTEAD of a time —
        # surface it so the invalid-denominator state is visible in the
        # artifact rather than reading like a missing toolchain
        for k in ("native_single_divergence", "native_pooled_divergence"):
            if k in go_stats:
                result[f"go_loop_{k}"] = go_stats[k]

    # ---- Pallas round-head vs XLA on the real backend (VERDICT r3 #2):
    # the hardware number that decides the kernel's fate
    if section("pallas_roundhead", margin_s=90):
        from kube_batch_tpu.testing.pallas_bench import compare_roundhead

        result["pallas_roundhead"] = compare_roundhead(N_TASKS, N_NODES)

    # ---- the SHIPPED 5-action pipeline (enqueue, reclaim, allocate,
    # backfill, preempt — config/kube-batch-tpu-conf.yaml) at the same
    # 50k×5k scale; podgroups start Pending so enqueue has real work
    from kube_batch_tpu.api.types import PodGroupPhase

    if section("pipeline5", margin_s=180):
        from kube_batch_tpu.framework.conf import shipped_conf_path

        conf5 = load_scheduler_conf(shipped_conf_path())

        def pending_cluster():
            cache = synthetic_cluster(
                n_tasks=N_TASKS, n_nodes=N_NODES, gang_size=4, n_queues=3
            )
            for job in cache.jobs.values():
                if job.pod_group is not None:
                    job.pod_group.phase = PodGroupPhase.PENDING
            return cache

        p50_5, phases5_p50, _phases5_p90, _w5, placed5 = measure(
            conf5, pending_cluster, 3
        )
        result["pipeline5_ms"] = round(p50_5, 2)
        result["pipeline5_placed"] = placed5
        result["pipeline5_vs_headline"] = round(p50_5 / p50, 2)
        result["pipeline5_phases"] = phases5_p50

    # ---- heterogeneous-constraints case (BASELINE config #5 / VERDICT r2
    # weak #6): 30% of tasks carry hostPorts, routing their jobs through the
    # fallback machinery — must stay within ~2× the homogeneous cycle
    if section("het30", margin_s=120):

        def het_cluster():
            return synthetic_cluster(
                n_tasks=N_TASKS, n_nodes=N_NODES, gang_size=4, n_queues=3,
                host_ports_frac=0.3,
            )

        p50_het, _, _, _, placed_het = measure(conf, het_cluster, 3)
        result["het30_ms"] = round(p50_het, 2)
        result["het30_placed"] = placed_het
        result["het30_vs_headline"] = round(p50_het / p50, 2)
        result["het30_fallback"] = get_action("allocate").last_fallback

    # ---- the full BASELINE.json config matrix (testing/benchmark.py — the
    # kubemark successor, VERDICT r3 #1): per-config latency percentiles,
    # each case individually deadline-gated
    from kube_batch_tpu.testing.benchmark import build_cases

    matrix = {}
    for case in build_cases():
        # worst-case runtime per case: the 50k/60k-task cases pay fresh
        # compiles + host replay; the kubemark density case sleeps through
        # its batch feed and drain
        margin = 300 if "50k" in case.name else (
            150 if "latency" in case.name else 90
        )
        if not section(f"matrix.{case.name}", margin_s=margin):
            continue
        matrix[case.name] = case.run(2)
    if matrix:
        result["matrix"] = matrix

    if skipped:
        result["sections_skipped"] = ",".join(skipped) + " (deadline)"
    print(json.dumps(result))


if __name__ == "__main__":
    main()

"""allocate action — the hot placement pass, device-solved.

The reference's allocate (actions/allocate/allocate.go) is the
O(tasks × nodes) host loop; here it becomes: build the device snapshot, run
ops/assignment.allocate_solve (one compiled program: predicates, scoring,
fairness, ordering, gang commit/discard), then apply the resulting
assignment to host state.

The apply is *vectorized*: jobs whose readiness gate is the gang arithmetic
(JobReady ⊆ {gang}) and whose tasks carry no host-only constraints take a
bulk path — readiness decided up front from the snapshot's ready counts
(so discards never mutate anything), then per-job index moves and presummed
per-node accounting (job_info/node_info bulk methods), batched event
handlers, and one bulk_bind for every committed placement.  Jobs needing
host-side predicate re-validation (ports, rich affinity, pressure gates) or
nonstandard JobReady vetoes replay through the per-task Statement path with
exactly the sequential semantics (statement.go:29-337).
"""

from __future__ import annotations

import logging
import os
from functools import partial
from kube_batch_tpu.utils import telemetry
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from kube_batch_tpu.api.cluster_info import ClusterInfo
from kube_batch_tpu.api.columns import resident_snap
from kube_batch_tpu.api.snapshot import build_snapshot
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.framework.interface import Action
from kube_batch_tpu.framework.session import FitFailure, JOB_READY
from kube_batch_tpu import metrics
from kube_batch_tpu.ops.assignment import AllocateConfig

logger = logging.getLogger("kube_batch_tpu")

# --------------------------------------------------------------------------
# top-K candidate compaction (KB_TOPK) — dispatch-side planning
# --------------------------------------------------------------------------

#: the pending-row bucket ladder.  The compacted solve's task axis is ONE
#: FIXED bucket per task-capacity shape: the largest ladder value at or
#: below capT/4 (compaction only runs where it wins — pending well under
#: the task bucket).  Deriving the bucket from capT instead of the
#: instantaneous pending count makes steady-state retraces structurally
#: impossible: the bucket cannot move while the cache's shape buckets
#: don't, no matter how the pending count wobbles (an instantaneous-count
#: ladder flapped a boundary mid-steady and retraced — measured, rejected).
#: Cycles whose pending exceeds the bucket (cold starts) run the full
#: program, which is the right shape there anyway.
TOPK_PEND_BUCKETS = (256, 512, 1024, 2048, 4096, 8192, 16384, 32768, 65536)

#: default candidate-list width — the measured knee at bench scales; the
#: exhaustion re-entry keeps ANY width bit-exact, so K tunes cost, never
#: correctness
TOPK_DEFAULT = 32


def resolve_topk() -> int:
    """KB_TOPK: candidate-list width K (default 32); 0 disables compaction
    and keeps the full-matrix program as the oracle — same contract as
    KB_SHARD_MAP=0 / KB_PIPELINE=0.  An unparsable value DISABLES
    compaction (a typo'd attempt to turn the knob off must not silently
    re-enable it and invalidate an oracle comparison)."""
    raw = os.environ.get("KB_TOPK", "").strip()
    if not raw:
        return TOPK_DEFAULT
    try:
        return max(0, int(raw))
    except ValueError:
        logger.warning("unparsable KB_TOPK=%r; compaction disabled", raw)
        return 0


def resolve_warm() -> bool:
    """KB_WARM: carry the candidate table across cycles and repair it from
    the resident-scatter deltas (default ON whenever compaction runs);
    KB_WARM=0 rebuilds the table cold every solve — the bit-exactness
    oracle, same contract as KB_TOPK=0 / KB_SHARD_MAP=0 / KB_PIPELINE=0.
    Any value other than an explicit enable counts as OFF (the KB_TOPK
    garbage-disables discipline: a typo'd disable attempt must not
    silently re-enable the fast path under an oracle comparison)."""
    raw = os.environ.get("KB_WARM", "").strip().lower()
    if not raw:
        return True
    return raw in ("1", "true", "on", "yes")


def _warm_state(cols, mesh, impl, config, guard, warm: bool, k: int):
    """The carried-table state for this dispatch slot, or None when the
    warm path must not run: opt-out (KB_WARM=0), guard demotion, no
    ColumnStore, or an explicitly cold caller (the backfill real-request
    pass solves a mid-cycle snapshot and must not consume the allocate
    carry's deltas).

    Called BEFORE the resident swap so a fresh state still absorbs this
    cycle's delta record and cold-builds the same dispatch."""
    if (
        not warm or cols is None or k <= 0
        or not resolve_warm()
        # a custom score row may read ANY snapshot field (the seam's
        # contract) — including per-cycle state the carry's invalidation
        # sources don't track (queue_alloc, job rows, statuses), which
        # would silently stale the carried keys.  Same policy as the
        # columnar host fast path: custom scoring defers to the general
        # machinery (here: the cold per-solve build).
        or config.weights.extra_rows
        or (guard is not None and not guard.allow("warm"))
    ):
        return None
    return cols.warm_table_state(mesh=mesh, impl=impl)


def _warm_commit(wstate, call):
    """Run one warm solve thunk and adopt its refreshed table (the last
    two outputs of every warm program).  ANY failure drops the carried
    state wholesale — plan() already consumed the invalidation
    accumulators, and off-CPU the solve donated the stale table buffers,
    so a carried-on state would pair stale (or deleted) entries with the
    new bucket order."""
    try:
        out = call()
    except BaseException:
        wstate.drop()
        raise
    wstate.commit(out[-2], out[-1])
    return out


def warm_k_min(k: int) -> int:
    """The erosion floor of the carried table: a row re-ranks when its
    valid prefix thins below (a per-row staggered threshold above) this.
    K/4, not K: a thin table still answers EXACTLY — the head's argmax
    over an exact prefix equals the full argmax while any entry fits, and
    exhaustion re-enters the full-matrix head the same round — so the
    floor trades re-rank traffic against fallback probability, and
    ``topk_exhausted`` (read back every cycle) monitors the latter."""
    return max(4, k // 4)


def _warm_plan(state, cols, pend_rows, k: int, config, tracer):
    """The post-swap invalidation plan (api/resident.WarmTableState.plan),
    span-attributed as table maintenance under the owning solve_dispatch
    span.  None = the delta chain is broken this cycle (no per-cycle
    resident cache, or a swap the state did not absorb) — the dispatch
    falls back to the cold per-solve build."""
    if state is None:
        return None
    if tracer is None:
        return state.plan(cols, pend_rows, k, config)
    with tracer.span("table_invalidate") as sp:
        plan = state.plan(cols, pend_rows, k, config)
        if plan is not None:
            sp.set(cold=bool(plan["cold"]),
                   reranked=int(state.last.get("reranked", 0)),
                   changed=int(state.last.get("changed", 0)))
    return plan


def topk_bucket_for(capT: int):
    """The ONE pending bucket a task capacity of ``capT`` compacts into —
    the largest ladder value at or below capT/4, or None below the
    smallest rung (tiny clusters: the full program is already cheap)."""
    fit = [b for b in TOPK_PEND_BUCKETS if b <= capT // 4]
    return fit[-1] if fit else None


def plan_pend_bucket(snap):
    """The pending-axis compaction every bidding program shares (allocate's
    top-K and warm solves, the evict solves): ``(pend_rows, pending,
    bucket)`` — the pending task rows of the host-backed ``snap`` in
    ascending order, padded with -1 to the ONE bucket its task axis
    compacts into (:func:`topk_bucket_for`), or None where the full-axis
    program should run: a task bucket too small to carry a compaction
    rung (``bucket`` None), no pending row, or a pending set past the
    bucket (the cold-start regime — the full program IS the right shape
    there).  The bucket is a pure function of the task-capacity shape, so
    a compacted program's shapes can only change when the cache's own
    shape buckets do — zero steady-state retraces by construction."""
    bucket = topk_bucket_for(int(snap.task_req.shape[0]))
    rows = np.flatnonzero(np.asarray(snap.task_pending))
    if bucket is None or rows.size == 0 or rows.size > bucket:
        return None, int(rows.size), bucket
    pend_rows = np.full(bucket, -1, np.int32)
    pend_rows[: rows.size] = rows.astype(np.int32)
    return pend_rows, int(rows.size), bucket


def plan_topk_bucket(snap, cols, k: int):
    """The dispatch's compaction plan: (pend_rows [P] np.int32, K) or
    (None, 0) when the full-matrix program should run.

    Compaction is declined when it cannot win: K no smaller than the node
    bucket, or no bucket for the pending set (:func:`plan_pend_bucket`:
    idle cycles are skipped upstream anyway)."""
    del cols  # the bucket is shape-derived; no per-cache state
    capN = int(snap.node_idle.shape[0])
    if k <= 0 or k >= capN:
        return None, 0
    pend_rows, _, _ = plan_pend_bucket(snap)
    if pend_rows is None:
        return None, 0
    return pend_rows, k


def _run_bounds(sorted_arr) -> list:
    """[lo..hi) run boundaries of equal values in a sorted array — the
    segmentation idiom shared by the per-job and per-node replay groupings."""
    return np.concatenate(
        ([0], np.flatnonzero(np.diff(sorted_arr)) + 1, [sorted_arr.size])
    ).tolist()


def build_session_snapshot(ssn):
    """(DeviceSnapshot, meta) for the session — columnar row space when the
    session is exclusive, object rebuild for isolated sessions.  Shared by
    execute() and the backfill real-request pass so both solve the
    identically-constructed problem."""
    cols = ssn.columns
    if cols is not None:
        return cols.device_snapshot(ssn)
    cluster = ClusterInfo(ssn.spec)
    cluster.nodes = ssn.nodes
    cluster.queues = ssn.queues
    cluster.jobs = ssn.jobs
    return build_snapshot(cluster, excluded_nodes=ssn.session_excluded_nodes)


def session_allocate_config(ssn) -> AllocateConfig:
    """The solve configuration a session implies (plugin enables + opt-ins);
    `weights` is the session's ScoreWeights (ops/scoring.py)."""
    return AllocateConfig(
        gang=ssn.plugin_enabled("gang"),
        drf=ssn.plugin_enabled("drf"),
        proportion=ssn.plugin_enabled("proportion"),
        weights=ssn.score_weights,
    )


class AllocateDispatchPlan(NamedTuple):
    """What one allocate-shaped dispatch will run, decided on the host
    before anything touches the device (:func:`plan_allocate_dispatch`)."""

    mesh: Optional[object]   # the mesh the solve shards over; None = one device
    impl: Optional[str]      # "pjit" where shard_map is demoted, else None
    #                          (KB_SHARD_MAP selects, mesh.resolve_impl)
    kind: str                # "full" | "topk"; the dispatch turns "topk"
    #                          into "warm" once a carried-table plan exists
    k: int                   # candidate-list width K (0 in the full program)
    pend_rows: Optional[np.ndarray]  # the [P] pending bucket as planned
    demoted: bool            # a guard demotion picked this program
    sentinel: bool           # the invariant tail is fused behind the solve
    engaged: Tuple[str, ...]  # the guard fast paths the program engages
    wstate: Optional[object]  # carried-table state, None = cold build


def plan_allocate_dispatch(snap, config, cols, guard, warm
                           ) -> AllocateDispatchPlan:
    """Choose the program of one allocate-shaped dispatch from what the
    host can observe: the guard's demotions first (shard_map to its pjit
    oracle, compaction to the full matrix), then the compaction plan, then
    whether a mesh exists and the cluster is wide enough to shard, then
    the carried-table state.  Touches no device, and runs BEFORE the
    resident swap (:func:`_warm_state` says why)."""
    from kube_batch_tpu.parallel.mesh import (
        TASK_AXIS,
        default_mesh,
        resolve_impl,
        should_shard,
    )

    impl = None
    demoted = False
    if guard is not None and not guard.allow("shard_map"):
        impl = "pjit"  # shard_map demoted → the pjit oracle
        demoted = True
    k = resolve_topk()
    if guard is not None and not guard.allow("topk"):
        k = 0  # compaction demoted → the full-matrix oracle
        demoted = True
    pend_rows, k = plan_topk_bucket(snap, cols, k)
    mesh = default_mesh() if should_shard(snap.node_alloc.shape[0]) else None
    engaged: Tuple[str, ...] = ()
    if mesh is not None and resolve_impl(impl) == "shard_map":
        engaged = ("shard_map",)
    kind, wstate = "full", None
    # the compacted body requires a 1-D node mesh — the 2-D task-axis
    # grid is the cold-start HBM escape, where compaction can't apply
    if pend_rows is not None and (
        mesh is None or dict(mesh.shape).get(TASK_AXIS, 1) == 1
    ):
        kind = "topk"
        engaged += ("topk",)
        wstate = _warm_state(
            cols, mesh, None if mesh is None else resolve_impl(impl),
            config, guard, warm, k)
    return AllocateDispatchPlan(
        mesh=mesh, impl=impl, kind=kind, k=k, pend_rows=pend_rows,
        demoted=demoted, sentinel=guard is not None and guard.enabled,
        engaged=engaged, wstate=wstate,
    )


def dispatch_allocate_solve(snap, config, cols=None, guard=None,
                            warm=False, tracer=None):
    """Shard-or-local solve dispatch; returns (result, mode, topk_info,
    ginfo): plan (:func:`plan_allocate_dispatch`), resident swap, program
    lookup (parallel.mesh.program), one call (parallel.mesh.call).

    ``warm=True`` (the allocate action's steady path) lets the compacted
    program run WARM-STARTED: the [P, K] candidate table carries across
    cycles on device, invalidated from the resident-scatter delta records
    and repaired in-program (ops.assignment.warm_allocate_solve) instead
    of re-ranked from scratch — ``topk_info["warm"]`` records the plan
    (cold / re-ranked rows / changed nodes).  ``tracer`` attributes the
    table maintenance as children of the caller's solve_dispatch span.

    With a ColumnStore, the ingest-static feature columns ride the
    device-resident cache (columns.resident_features) so per-cycle
    host→device traffic is only the truly per-cycle arrays; the caller's
    `snap` stays host-backed for its numpy reads.

    ``topk_info`` records the compaction decision ({"k", "bucket"} when
    the KB_TOPK compacted program ran, None otherwise) — the action folds
    the solve's exhaustion counters into it for the sim.

    ``guard`` (a :class:`kube_batch_tpu.guard.GuardPlane`) makes the
    dispatch GUARDED: demoted fast paths fall back to their oracles
    (KB_TOPK=0 / pjit / the cold table build) and the sentinel-fused
    program variants run, returning the invariant verdict + histogram in
    ``ginfo`` ("sentinel") alongside the engaged fast-path names
    ("engaged") and the compaction plan ("pend_rows", for the diagnostics
    bundle).  The caller MUST feed the verdict through
    ``guard.consume_verdict`` before acting on the result (rule KBT013
    enforces this at every dispatch site)."""
    # kbt: allow[KBT013] the dispatch RETURNS the sentinel verdict to its
    # caller — consume_verdict happens at the action's readback, the one
    # place the verdict exists on host
    from kube_batch_tpu.parallel.mesh import call, program

    plan = plan_allocate_dispatch(snap, config, cols, guard, warm)
    mesh, kind, pend_rows = plan.mesh, plan.kind, plan.pend_rows
    dev = resident_snap(cols, snap, mesh)
    # cfg is the EFFECTIVE config the program runs with (demotions applied,
    # topk as dispatched: K, or the carried table's width W) — a trip
    # bundle must replay the condemned program, not the session's nominal
    # one (the carry itself is not replayable: the table is cross-cycle
    # state, so a warm trip replays the cold compacted program at W)
    cfg, args, statics, info = config, (dev,), {}, None
    if kind == "topk":
        info = {"k": plan.k, "bucket": int(pend_rows.shape[0])}
        cfg = config._replace(topk=plan.k)
        args = (dev, pend_rows)
        wplan = _warm_plan(plan.wstate, cols, pend_rows, plan.k, config,
                           tracer)
        if wplan is not None:
            kind = "warm"
            info["warm"] = dict(plan.wstate.last)
            cfg = config._replace(topk=wplan["w"])
            statics = {"k_min": warm_k_min(plan.k)}
            args += (*wplan["table"], wplan["row_map"], wplan["changed"],
                     wplan["rerank_rows"], wplan["rerank_slots"])
    elif plan.demoted:
        # the full [T, N] matrix as a demotion's target: only where it
        # holds the cluster (a cold start runs it undemoted, and the
        # deployment is sized for that)
        _require_full_matrix_fit(
            program("full", mesh, plan.impl, config), dev, config, mesh,
            plan.impl)
    run = partial(
        call, program(kind, mesh, plan.impl, cfg, plan.sentinel, **statics),
        mesh, *args, config=cfg, **statics)
    if kind == "warm":
        # the last two outputs are the refreshed table the commit adopts
        out = _warm_commit(plan.wstate, run)[:-2]
    else:
        out = run() if plan.sentinel else (run(),)
    ginfo = {
        "engaged": list(plan.engaged) + (["warm"] if kind == "warm" else []),
        "sentinel": tuple(out[1:]) or None,  # (verdict, hist, checksum)
        "pend_rows": pend_rows, "impl": plan.impl,
        # the exact (post-resident-swap) snapshot the solve consumed —
        # what a trip's diagnostics bundle must capture
        "dev": dev,
        "config": cfg,
    }
    return out[0], "single" if mesh is None else "sharded", info, ginfo


def _require_full_matrix_fit(fn, dev, config, mesh, impl):
    """Raise :class:`guard.OracleUnfit` unless ``fn``, the bare full [T, N]
    allocate program, holds ``dev`` on one device (guard/fit.py): across
    ``mesh`` with ``impl``, or on a single device."""
    from kube_batch_tpu.guard.fit import require_fit
    from kube_batch_tpu.parallel.mesh import NODE_AXIS, resolve_impl

    if mesh is None:
        require_fit("the demotion's target, the full-matrix solve,",
                    fn, dev, config)
        return
    require_fit(
        "the demotion's target, the sharded full-matrix solve,", fn, dev,
        mesh=mesh,
        spmd_shards=(dict(mesh.shape)[NODE_AXIS]
                     if resolve_impl(impl) == "pjit" else 1),
    )


def dispatch_allocate_oracle(snap, config, cols, mode):
    """The shadow-oracle dispatch for an allocate-shaped audit: the same
    snapshot through the all-oracle program (KB_TOPK=0; pjit impl when the
    committed solve ran sharded).  ``resident_snap`` is memoized on the
    snap object, so this re-dispatch is device work only — no re-upload."""
    from kube_batch_tpu.parallel.mesh import call, default_mesh, program

    oracle_cfg = config._replace(topk=0)
    mesh = default_mesh() if mode == "sharded" else None
    return call(
        program("full", mesh, "pjit", oracle_cfg), mesh,
        resident_snap(cols, snap, mesh), config=oracle_cfg)


def republish_query_lease(ssn, snap=None, meta=None, build=None,
                          version=None) -> bool:
    """THE guarded what-if lease publish — every publish path (allocate's
    solve and idle/empty cycles, reclaim/backfill/preempt's post-swap
    re-arms, the cycle's re-arm at its commit) goes through here, so the
    gate, the version-token source, and the failure policy live once.
    Returns whether a lease was published.

    On donating backends EVERY resident swap retires the published lease
    (serve/lease.py) — and reclaim, backfill, and preempt all swap after
    allocate's publish, so without the post-dispatch re-arms the query
    plane would sit leaseless from the last swap until the NEXT cycle's
    allocate: the whole schedule period, exactly on the hardware serving
    targets.  ``resident_snap`` is memoized on the exact ``snap`` object
    the caller's dispatch used, so a re-arm is bookkeeping, not device
    work.  ``build`` is the lazy (snap, meta) builder for the paths with no
    snapshot in hand: the rebuild runs only when the publish is actually
    owed (no plane attached, an isolated/object session, or a live lease
    already covering the version — CPU: swaps never retire — all skip it).

    ``version`` is the dirty-tracker token the snapshot holds: the open's
    (the default) for a snapshot taken before the cycle's own binds, the
    tracker's own for one built after them, at the end of the session
    (:meth:`Scheduler._rearm_lease`).  A publish failure degrades serving,
    never the cycle."""
    qp = getattr(ssn.cache, "query_plane", None)
    if qp is None or ssn.columns is None:
        return False
    if version is None:
        version = int(getattr(ssn.cache, "last_open_version", 0))
    try:
        if not qp.needs_publish(version):
            return False
        if build is not None:
            snap, meta = build()
        qp.publish_session(ssn, snap, meta, version)
        return True
    except Exception:  # noqa: BLE001 — the write path outranks serving
        logger.exception("whatif lease publication failed")
        return False


class AllocateAction(Action):
    name = "allocate"

    def __init__(self):
        # "single" | "sharded" — which solve the last execute() dispatched
        self.last_solve_mode = "single"
        # bidding rounds the last solve executed (early exits make this
        # the measured convergence, not the 6x3 cap)
        self.last_solve_rounds = 0
        # candidate-compaction record of the most recent execute():
        # {"k", "bucket", "exhausted", "reentries"} when the KB_TOPK
        # compacted program ran, None otherwise (sim evidence)
        self.last_topk = None
        # warm-carry record ({"cold", "reranked", "changed", ...}) when
        # the KB_WARM carried-table program ran, None otherwise
        self.last_warm = None
        # fallback pressure of the most recent execute() (VERDICT r2 #6)
        self.last_fallback: Dict[str, int] = {}
        # jobs whose placements were DISCARDED host-side this execute()
        # (slow-replay JobReady failures, volume demotion dead-ends): their
        # freed capacity is stranded for the rest of the cycle unless the
        # backfill action's real-request pass re-offers it
        self.last_host_discards = 0
        self._host_place_count = 0
        self._n_applied = 0
        self._ports_by_node: Optional[Dict[int, set]] = None
        # (mode, bucket, T, N) of the bucketed fit-error histograms this
        # action has dispatched: each is compiled with its first solve
        self._fit_histograms_seen: set = set()

    def execute(self, ssn) -> None:
        # a solve that spent its whole rounds x outer budget while it was
        # still placing, and left pods unplaced, runs on once: on a packed
        # cluster a node offers one slot, equal pods bid for the same
        # best-scored nodes and a node has one winner a round, so 18 rounds
        # place 50-80 pods of a hundred with room left for the rest.  The
        # reference's sequential loop has no round budget: it would have
        # reached that room in this cycle.  What a second solve leaves
        # waits for the next cycle, as before (the loop wakes itself)
        if self._solve_and_replay(ssn):
            metrics.register_allocate_runs_on()
            self._solve_and_replay(ssn)

    def _solve_and_replay(self, ssn) -> bool:
        """One dispatch, its readback and its replay; True when the solve
        ran out of rounds while still placing and left pods unplaced."""
        self.last_fallback = {}
        self.last_host_discards = 0
        self.last_solve_rounds = 0
        self.last_topk = None
        self.last_warm = None
        self._host_place_count = 0
        self._n_applied = 0
        self._ports_by_node = None
        # session → ClusterInfo view (the session's jobs/nodes/queues ARE the
        # snapshot clone; invalid jobs were already dropped at open). ALL jobs
        # are included so fairness state (queue_alloc/job_allocated) counts
        # Pending-phase jobs' allocations; the Pending-phase gate
        # (allocate.go:50-52) is the snapshot's job_schedulable flag
        cols = ssn.columns
        if not ssn.jobs or not ssn.nodes:
            # an empty (or node-less) cluster still serves what-ifs:
            # publish the lease so probes answer against the real — if
            # vacuous — state instead of 503ing until first ingest
            republish_query_lease(
                ssn, build=lambda: build_session_snapshot(ssn)
            )
            return False

        from kube_batch_tpu.obs.trace import solve_program, tracer_of

        tracer = tracer_of(ssn.cache)
        t0 = telemetry.perf_counter()
        if cols is not None and not cols.has_schedulable_pending():
            # steady-state idle cycle: nothing schedulable anywhere — skip
            # the snapshot/solve/replay entirely (the reference's loop with
            # an empty pending set is ~free; ours must be too at a 1 s
            # schedule period)
            # serving deployments still need a lease for this state: an
            # idle cluster is exactly when capacity-planning what-ifs
            # arrive.  The snapshot build + resident swap run only when a
            # query plane is attached AND ingest moved the version since
            # the last publish — a steadily idle cluster pays for the
            # rebuild once, not every schedule period.
            republish_query_lease(
                ssn, build=lambda: build_session_snapshot(ssn)
            )
            return False
        with tracer.span("snapshot_build"):
            snap, meta = build_session_snapshot(ssn)
            if cols is not None and cols.affinity.live_signatures:
                # the mask and score rows derived from the match-count
                # planes, timed where it ran (api/affinity_planes.py)
                with tracer.tallied_span(
                        "affinity_mask",
                        cols.last_affinity.get("derive_s", 0.0)) as sp_aff:
                    tracer.note_affinity_rows(sp_aff, cols.last_affinity)
        # multi-chip parts shard the node axis over the ICI mesh — the
        # production analog of the reference's always-on 16-worker fan-out
        # (scheduler_helper.go:34-64); single-chip or small-N stays local
        from kube_batch_tpu.guard import OracleUnfit, guard_of

        gp = guard_of(ssn.cache)
        config = session_allocate_config(ssn)
        # device-attributed span: a retrace or an unexpected full resident
        # upload is annotated onto THIS dispatch, not smeared into a p50
        try:
            with tracer.device_span("solve_dispatch", cols=cols) as sp_solve:
                result, self.last_solve_mode, topk_info, ginfo = (
                    dispatch_allocate_solve(snap, config, cols=cols,
                                            guard=gp, warm=True,
                                            tracer=tracer)
                )
        except OracleUnfit as e:
            # a demotion whose target cannot hold the cluster: no program
            # ran, nothing below runs (no replay, no binds, no fit errors)
            gp.fail_closed("allocate", str(e))
            return False
        warm_plan = (topk_info or {}).get("warm") or {}
        tracer.note_solve_dispatch(
            sp_solve, "allocate", self.last_solve_mode, ginfo["engaged"],
            program=solve_program(
                ginfo["engaged"], rebuilt=bool(warm_plan.get("cold"))),
            bucket=(topk_info or {}).get("bucket"),
            rungs=warm_plan.get("rungs"),
        )
        # shadow-oracle audit (guard tier 2): every KB_AUDIT_EVERY-th
        # dispatch re-runs the committed solve through its oracle path,
        # DISPATCHED here so the oracle re-solve overlaps the readback +
        # host replay (the fit-histogram idiom) and COMPARED after the
        # replay — audit cycles pay device time, never critical-path time
        audit_dev = None
        if ginfo["engaged"] and gp.audit_due("allocate"):
            with tracer.device_span("audit_dispatch",
                                    mode=self.last_solve_mode):
                audit_dev = dispatch_allocate_oracle(
                    snap, config, cols, self.last_solve_mode
                )
        # the lease shares this dispatch's resident swap (memoized on the
        # same snap object), so publication is bookkeeping-only
        republish_query_lease(ssn, snap, meta)
        sentinel = ginfo["sentinel"]
        # kbt: allow[KBT010] THE sanctioned choke point: one blocking
        # transfer for everything the host replay reads — the sentinel
        # verdict + violation histogram ride it (the AllocateResult-
        # counters idiom), so the guard adds zero extra transfers
        with tracer.device_span("device_wait") as sp_wait:
            (assigned, pipelined, rounds_run, topk_exh, topk_reent,
             verdict, vhist, echeck, turned_away) = jax.device_get(  # kbt: allow[KBT010] ^
                (result.assigned, result.pipelined, result.rounds_run,
                 result.topk_exhausted, result.topk_reentries,
                 sentinel[0] if sentinel is not None else np.int32(0),
                 sentinel[1] if sentinel is not None else None,
                 sentinel[2] if sentinel is not None else np.int32(0),
                 result.term_exclusions)
            )
        # convergence diagnostic: how far into rounds x outer the solve went
        self.last_solve_rounds = int(rounds_run)
        tracer.note_solve_rounds(
            sp_wait, "allocate", self.last_solve_rounds, config.rounds)
        tracer.note_topk_fallbacks(
            sp_wait, "allocate", int(topk_exh), int(topk_reent))
        if turned_away is not None:
            tracer.note_term_exclusions(sp_wait, "allocate", int(turned_away))
        # the solve carried the in-solve rule of the required inter-pod
        # terms (the snapshot it consumed had them: one device): its termed
        # placements need no host predicate at replay
        meta.terms_exact = ginfo["dev"].aff_terms is not None
        if topk_info is not None:
            topk_info = dict(
                topk_info, exhausted=int(topk_exh), reentries=int(topk_reent)
            )
        self.last_topk = topk_info
        # warm-carry record of this execute ({"cold", "reranked",
        # "changed", "bucket_live", "w"} when the carried-table program
        # ran, None otherwise) — sim evidence
        self.last_warm = (topk_info or {}).get("warm")
        assigned = assigned[: meta.n_tasks]
        pipelined = pipelined[: meta.n_tasks]
        if sentinel is not None and not self._consume_sentinel(
            ssn, gp, snap, config, ginfo, int(verdict), vhist,
            assigned, meta, int(echeck),
        ):
            # guard tier 1: the solve is CONDEMNED — fail closed.  Nothing
            # below this line runs: no replay, no binds, no fit errors.
            # The guard has already demoted the engaged fast paths, healed
            # the resident cache, and dumped the diagnostics bundle.
            return False
        task_job = np.asarray(snap.task_job)[: meta.n_tasks]
        # fit errors only for tasks of jobs that are IN this session (the
        # columnar row space also carries rows of jobs the session dropped —
        # gang-invalid or unknown-queue — which the object path never saw);
        # Pending-phase jobs stay included: their histogram rows carry the
        # real per-node reasons, keeping the condition dedup stable across
        # cycles
        job_in_session = np.asarray(snap.job_valid)
        pending = (
            np.asarray(snap.task_pending)[: meta.n_tasks]
            & job_in_session[task_job]
        )
        # the fit-error histogram is a SEPARATE lazy dispatch: only cycles
        # with unplaced pending tasks pay its [T, N] predicate re-walk
        # (allocate.go:151-155 builds FitErrors only for failing tasks).
        # It is DISPATCHED here but read back only after the host replay:
        # jax dispatch is async, so the device grinds the histogram while
        # the host replays the assignment.  This is the IN-CYCLE instance
        # of the cycle pipeline's general stage-overlap mechanism (the
        # scheduler module's staged loop overlaps the close-time status
        # flush and the binder drain with the NEXT cycle the same way) —
        # the async-binder seam extended one stage earlier into the cycle.
        fail_hist_dev = None
        p_rows = ginfo.get("pend_rows")
        unplaced = bool(np.any(pending & (assigned < 0)))
        # the steady path's histogram program is compiled with the first
        # compacted solve of its shapes, not by the first cycle that leaves
        # a pod unplaced: that cycle may come minutes into serving, and the
        # compile (8.5 s at 150k x 5k on four chips) would stop decisions
        # there.  Such a prewarm is dispatched and never read.
        first = p_rows is not None and self._first_fit_histogram(snap, p_rows)
        if unplaced or first:
            with tracer.device_span("fit_histogram_dispatch",
                                    prewarm=not unplaced):
                hist_dev = self._dispatch_fit_histogram(cols, snap, p_rows)
            if unplaced:
                fail_hist_dev = hist_dev
        with tracer.span("host_replay") as sp_replay:
            self._replay(ssn, snap, meta, assigned, pipelined, task_job)
            placed_per_job = np.bincount(task_job[assigned >= 0])
            sp_replay.set(gangs=int(np.count_nonzero(placed_per_job)),
                          largest_gang=int(placed_per_job.max(initial=0)))
        if fail_hist_dev is not None:
            # blocks only on whatever the device hasn't finished during the
            # replay; fit-error recording touches job diagnostic dicts the
            # replay never reads, so the reordering is invisible to it
            with tracer.device_span("fit_errors"):
                self._record_fit_errors(
                    # kbt: allow[KBT010] sanctioned post-replay readback: the
                    # histogram was dispatched before the replay precisely so
                    # this read overlaps host work instead of stalling
                    ssn, meta, np.asarray(fail_hist_dev), assigned, task_job,
                    pending,
                )
        if self._n_applied:
            # amortized per-task latency over placements actually APPLIED
            # (bulk-committed + statement-committed), so the histogram count
            # matches real placements (metrics.go:66-72 analog)
            metrics.observe_task_latencies(
                (telemetry.perf_counter() - t0) * 1e6 / self._n_applied,
                self._n_applied,
            )
        if audit_dev is not None:
            self._compare_audit(
                ssn, gp, snap, config, ginfo, audit_dev, assigned, pipelined,
                meta,
            )
        return (unplaced and bool((assigned >= 0).any())
                and self.last_solve_rounds >= config.rounds * config.outer)

    # ------------------------------------------------------------------
    # fit-error histogram (the lazy [P, N] / [T, N] predicate re-walk)
    # ------------------------------------------------------------------
    def _first_fit_histogram(self, snap, p_rows) -> bool:
        """True once per (mode, bucket, task and node capacity), and marks
        it: this action has not dispatched the bucketed histogram program
        of these shapes before."""
        key = (self.last_solve_mode, int(p_rows.shape[0]),
               int(snap.task_req.shape[0]), int(snap.node_alloc.shape[0]))
        if key in self._fit_histograms_seen:
            return False
        self._fit_histograms_seen.add(key)
        return True

    def _dispatch_fit_histogram(self, cols, snap, p_rows):
        """Dispatch the failure histogram of this cycle's solve; returns
        the device array ([P, N_REASONS] over the bucket, else [T, ...]).

        The compacted dispatch's [P] pending bucket covers every
        schedulable-pending row, and the histogram is only ever read at
        unplaced pending rows — so failure cycles walk [P, N] instead of
        [T, N] whenever a bucket exists (ROADMAP standing item: the PR 10
        bucket applies to the histogram verbatim)."""
        from kube_batch_tpu.parallel.mesh import (
            TASK_AXIS,
            call,
            default_mesh,
            program,
        )

        mesh = default_mesh() if self.last_solve_mode == "sharded" else None
        # the bucketed body requires a 1-D node mesh, exactly like the
        # compacted solve (which also declined on a 2-D grid even
        # though the bucket was planned)
        if mesh is not None and dict(mesh.shape).get(TASK_AXIS, 1) != 1:
            p_rows = None
        dev = resident_snap(cols, snap, mesh)
        if p_rows is None:
            return call(program("fail_hist", mesh, None, None), mesh, dev)
        return call(
            program("fail_hist_bucket", mesh, None, None), mesh, dev, p_rows)

    # ------------------------------------------------------------------
    # guard plane wiring (tiers 1 + 2)
    # ------------------------------------------------------------------
    def _consume_sentinel(self, ssn, gp, snap, config, ginfo, verdict, vhist,
                 assigned, meta, echeck) -> bool:
        """The SHARED assignment-shaped consumer (guard/plane: host
        pending cross-check + checksum compare + histogram folding +
        bundle + resident/lease heal) — one copy with backfill's
        real-request pass."""
        from kube_batch_tpu.guard import consume_assignment_sentinel

        return consume_assignment_sentinel(
            gp, "allocate", ssn, snap, meta, ginfo, verdict, vhist,
            echeck, assigned, extra_report={"mode": self.last_solve_mode},
        )

    def _compare_audit(self, ssn, gp, snap, config, ginfo, audit_dev,
                       assigned, pipelined, meta) -> None:
        """Bit-compare the committed fast-path result against the shadow
        oracle (read back AFTER the host replay — the oracle re-solve ran
        overlapped with it)."""
        from kube_batch_tpu.guard import make_heal, sentinel_bundle_thunk
        from kube_batch_tpu.obs.trace import tracer_of

        # the oracle was dispatched before the replay precisely so that
        # this read overlaps host work instead of stalling the cycle; what
        # of the oracle's device time the replay did not cover is this span
        with tracer_of(ssn.cache).device_span(
                "audit_wait", mode=self.last_solve_mode):
            # kbt: allow[KBT010] sanctioned post-replay audit readback
            a_assigned, a_pipelined = jax.device_get(
                (audit_dev.assigned, audit_dev.pipelined)
            )
        n = meta.n_tasks
        mism = int(
            np.sum(a_assigned[:n] != assigned)
            + np.sum(a_pipelined[:n] != pipelined)
        )
        report = {
            "audit_mismatches": mism, "engaged": ginfo["engaged"],
            "mode": self.last_solve_mode,
        }
        gp.note_audit(
            "allocate", ginfo["engaged"], mism == 0,
            detail=f"fast-vs-oracle mismatch at {mism} task rows",
            dump=sentinel_bundle_thunk(
                gp, "allocate", ginfo["dev"], ginfo["config"],
                report, pend_rows=ginfo.get("pend_rows"),
            ),
            heal=make_heal(ssn),
        )

    # ------------------------------------------------------------------
    def _replay(self, ssn, snap, meta, assigned, pipelined, task_job) -> None:
        placed = np.flatnonzero(assigned >= 0)
        if placed.size == 0:
            return
        # group placements by job, preserving device task order within a job;
        # groups are (job_idx, lo, hi) ranges over the sorted flat arrays
        order = np.argsort(task_job[placed], kind="stable")
        placed = placed[order]
        pjobs = task_job[placed]
        bounds = _run_bounds(pjobs)

        # the bulk path is sound only when the gang arithmetic is the whole
        # JobReady gate (gang.go:122-129 delegates to job.ready(), which is
        # exactly snapshot ready count + new allocations vs min_available)
        gang_only_ready = ssn.enabled_plugin_names(JOB_READY) <= {"gang"}
        # inter-pod terms were exact in the solve (the in-solve rule ran:
        # one device, a columnar snapshot): tasks whose only host-side
        # constraint they are keep the bulk path
        terms_exact = meta.terms_exact and meta.task_terms_only is not None
        nJ, nN = len(meta.job_objs), len(meta.node_names)
        resreq64 = meta.task_resreq64
        spec = ssn.spec
        R = resreq64.shape[1] if resreq64.ndim == 2 else spec.n
        pipe_flags = pipelined[placed].astype(bool)
        n_alloc_per_job = np.bincount(pjobs[~pipe_flags], minlength=nJ)
        if ssn.plugin_enabled("gang"):
            committed = (
                np.asarray(snap.job_ready)[:nJ] + n_alloc_per_job
            ) >= np.asarray(snap.job_min_avail)[:nJ]
        else:
            # no gang plugin ⇒ JobReady is vacuously true (veto dispatch over
            # zero fns, session_plugins.go:202-220): every placement commits
            committed = np.ones(nJ, bool)
        job_slow = np.zeros(nJ, bool)
        if not gang_only_ready or ssn.host_only_predicates:
            job_slow[:] = True
        else:
            needs_host = meta.task_needs_host[placed]
            if terms_exact:
                needs_host = needs_host & ~meta.task_terms_only[placed]
            np.logical_or.at(job_slow, pjobs, needs_host)

        # ---- bulk path FIRST ------------------------------------------
        # Bulk placements need no host state (the solve guarantee covers
        # their fit, and readiness is snapshot arithmetic), while the slow
        # path's host predicates must observe them live — an inter-pod
        # affinity follower co-locates with an anchor this cycle only if the
        # anchor is on the node when the follower is validated.  Host
        # fallbacks, the one mutation the solve can't account for, then
        # happen strictly after every bulk placement has landed.
        #
        # All resreq sums are computed globally up front (segment sums over
        # the float64 resreq matrix) and the apply loop runs over plain
        # python lists — gangs are small, so per-group numpy would pay call
        # overhead 10k+ times for 4-row reductions.
        placed_l = placed.tolist()
        pjobs_l = pjobs.tolist()
        pipe_l = pipe_flags.tolist()
        node_l = assigned[placed].tolist()
        task_objs = meta.task_objs
        node_names = meta.node_names
        n_groups = len(bounds) - 1

        # ---- promote host-ports-only jobs back to the bulk path --------
        # A job is "slow" when any task carries host-only constraints, but
        # the dominant such constraint (hostPorts) is checkable in one batch
        # pass: a placement conflicts iff its (node, port) is already held
        # by a resident task or claimed earlier this cycle.  Conflict-free
        # jobs keep the solve's guarantees and bulk-apply; only conflicted
        # or affinity-carrying jobs pay the sequential Statement replay
        # (VERDICT r2 weak #6 — 30% ported tasks degraded the cycle ~5×).
        promoted_jobs = 0
        cols0 = ssn.columns
        if (
            job_slow.any() and gang_only_ready
            and not ssn.host_only_predicates and cols0 is not None
        ):
            # resident occupancy snapshot, O(ported tasks) once — exact
            # here because nothing has been applied yet this cycle; the
            # slow phase later uses the live per-query view instead
            # (_port_held_nodes) so Statement discards roll claims back
            occupied = set()
            t_node_col = cols0.t_node
            task_by_row = cols0.task_by_row
            for r in cols0._ported_rows:
                ni = int(t_node_col[r])
                if ni < 0:
                    continue
                rt = task_by_row[r]
                if rt is not None:
                    for p in rt.pod.host_ports:
                        occupied.add((ni, p))
            # claims of jobs promoted earlier in this pass — their t_node
            # rows are only written when the bulk apply runs below
            for g in range(n_groups):
                lo, hi = bounds[g], bounds[g + 1]
                ji = pjobs_l[lo]
                # uncommitted jobs never apply — promoting them would only
                # plant phantom port claims that demote real jobs
                if not job_slow[ji] or not committed[ji]:
                    continue
                claims: Optional[set] = set()
                for i in range(lo, hi):
                    t = task_objs[placed_l[i]]
                    if not t.needs_host_predicate or (
                            terms_exact and t.inter_pod_terms_only):
                        continue
                    if t.pod.affinity is not None:
                        claims = None  # rich constraints → sequential path
                        break
                    ni = node_l[i]
                    for p in t.pod.host_ports:
                        key = (ni, p)
                        if key in occupied or key in claims:
                            claims = None
                            break
                        claims.add(key)
                    if claims is None:
                        break
                if claims is None:
                    continue  # conflict → sequential replay re-decides
                occupied.update(claims)
                job_slow[ji] = False
                promoted_jobs += 1

        slow_l = job_slow.tolist()
        committed_l = committed.tolist()

        # volume pre-check (AllocateVolumes, session.go:252-257): a rejected
        # group demotes to the sequential path BEFORE anything is mutated or
        # summed, so the bulk apply below has no failure path.  Skipped
        # wholesale when the volume binder declares itself a no-op.
        demoted_jobs: set = set()
        volume_noop = getattr(ssn.cache.volume_binder, "noop", False)
        if not volume_noop:
            allocate_volumes = ssn.cache.allocate_volumes
            for g in range(n_groups):
                lo = bounds[g]
                ji = pjobs_l[lo]
                if slow_l[ji] or not committed_l[ji]:
                    continue
                try:
                    for i in range(lo, bounds[g + 1]):
                        if not pipe_l[i]:
                            allocate_volumes(
                                task_objs[placed_l[i]], node_names[node_l[i]]
                            )
                except FitFailure:
                    demoted_jobs.add(ji)
                    # free this group's pre-check reservations: the slow
                    # replay re-reserves per task, and tasks it fails to
                    # place must not hold PVs across cycles
                    release = getattr(
                        ssn.cache.volume_binder, "release_task", None
                    )
                    if release is not None:
                        for i in range(lo, bounds[g + 1]):
                            release(task_objs[placed_l[i]].uid)

        apply_job = np.asarray(
            [committed[j] and not job_slow[j] and j not in demoted_jobs
             for j in range(nJ)], bool,
        ) if demoted_jobs else (committed & ~job_slow)
        apply_mask = apply_job[pjobs]          # placements to bulk-apply
        alloc_sel = apply_mask & ~pipe_flags
        pipe_sel = apply_mask & pipe_flags
        self._n_applied += int(apply_mask.sum())
        placed_rows = resreq64[placed]
        node_of = assigned[placed]
        job_alloc_sum = np.zeros((nJ, R))
        np.add.at(job_alloc_sum, pjobs[alloc_sel], placed_rows[alloc_sel])
        job_total_sum = np.zeros((nJ, R))
        np.add.at(job_total_sum, pjobs[apply_mask], placed_rows[apply_mask])
        node_alloc_sum = np.zeros((nN, R))
        np.add.at(node_alloc_sum, node_of[alloc_sel], placed_rows[alloc_sel])
        node_pipe_sum = np.zeros((nN, R))
        np.add.at(node_pipe_sum, node_of[pipe_sel], placed_rows[pipe_sel])

        EMPTY = spec.empty()
        apply_l = apply_job.tolist()
        wrap_vec = spec.wrap_vec
        binds: List[Tuple[object, str]] = []
        by_node: Dict[int, Tuple[list, list]] = {}
        # shared by the columnar count update and the bulk_bind job sums
        n_alloc_applied = np.bincount(pjobs[alloc_sel], minlength=nJ)

        cols = ssn.columns
        columnar = (
            cols is not None
            and meta.task_objs is cols.task_by_row  # snapshot IS the row space
            and ssn.all_handlers_columnar()
        )
        # the no-pipeline columnar cycle (every placement allocates — the
        # steady-state headline shape) takes a flat-array residue path below
        # instead of the per-task branching group loop
        fast_residue = columnar and not bool(pipe_sel.any())
        if columnar:
            # ---- columnar apply: every ledger/count/status column updated
            # by whole-matrix ops; the Python loop below only does what MUST
            # touch objects (status-index buckets, node task dicts, the
            # binds list).  The ledger matrices are the same buffers the
            # JobInfo/NodeInfo Resource views wrap, so the object model
            # observes every update with zero double bookkeeping.
            BINDING_I = int(TaskStatus.BINDING)
            PIPELINED_I = int(TaskStatus.PIPELINED)
            PENDING_I = int(TaskStatus.PENDING)
            alloc_rows = placed[alloc_sel]
            pipe_rows = placed[pipe_sel]
            cols.t_status[alloc_rows] = BINDING_I
            cols.t_status[pipe_rows] = PIPELINED_I
            apply_rows = placed[apply_mask]
            cols.set_task_nodes(apply_rows, node_of[apply_mask])
            cols.j_alloc += job_alloc_sum
            # alloc-twin choke: the f32 j_alloc32 refresh visits exactly
            # the rows this vectorized update moved
            cols.note_job_alloc_rows(np.any(job_alloc_sum != 0.0, axis=1))
            cols.j_pend -= job_total_sum
            np.maximum(cols.j_pend, 0.0, out=cols.j_pend)
            n_pipe_applied = np.bincount(pjobs[pipe_sel], minlength=nJ)
            jc = cols.j_counts
            jc[:, PENDING_I] -= n_alloc_applied + n_pipe_applied
            jc[:, BINDING_I] += n_alloc_applied
            jc[:, PIPELINED_I] += n_pipe_applied
            # count choke point: the delta close-session pass visits exactly
            # the rows this vectorized update moved
            cols.j_touched[(n_alloc_applied + n_pipe_applied) > 0] = True
            cols.n_idle -= node_alloc_sum
            np.maximum(cols.n_idle, 0.0, out=cols.n_idle)
            cols.n_used += node_alloc_sum + node_pipe_sum
            cols.n_rel -= node_pipe_sum
            np.maximum(cols.n_rel, 0.0, out=cols.n_rel)
            # ledger choke point: the f32 snapshot twins refresh these rows
            cols.note_node_ledger_rows(
                np.any(node_alloc_sum != 0.0, axis=1)
                | np.any(node_pipe_sum != 0.0, axis=1)
            )
            ssn.fire_columnar_allocations(cols, job_total_sum)

        if fast_residue:
            # ---- flat residue: binds / bucket moves / node registration
            # from whole arrays.  Per task this costs one object gather and
            # one dict insert (inside bulk_register_tasks) instead of the
            # general loop's slot lookups, branches, and appends.
            ptasks_l = [task_objs[r] for r in placed_l]
            apply_pos = np.flatnonzero(apply_mask)
            app_tasks = (
                ptasks_l if apply_pos.size == len(ptasks_l)
                else [ptasks_l[i] for i in apply_pos.tolist()]
            )
            app_nodes = node_of[apply_mask]
            binds = list(zip(app_tasks, (node_names[n] for n in app_nodes.tolist())))
            # job bucket moves: applied groups are contiguous runs of placed
            job_objs = meta.job_objs
            for g in range(n_groups):
                lo = bounds[g]
                ji = pjobs_l[lo]
                if apply_l[ji]:
                    job_objs[ji].rebucket_moved(
                        ptasks_l[lo:bounds[g + 1]], TaskStatus.BINDING
                    )
            # node registration grouped by one argsort over the node column
            if app_nodes.size:
                nsort = np.argsort(app_nodes, kind="stable")
                nodes_sorted = app_nodes[nsort]
                run_bounds = _run_bounds(nodes_sorted)
                nsort_l = nsort.tolist()
                get_node = ssn.nodes.get
                for k in range(len(run_bounds) - 1):
                    lo, hi = run_bounds[k], run_bounds[k + 1]
                    node = get_node(node_names[nodes_sorted[lo]])
                    if node is not None:
                        node.bulk_register_tasks(
                            [app_tasks[i] for i in nsort_l[lo:hi]], ()
                        )
            by_node = {}  # residue fully handled; skip the general pass

        for g in range(0 if fast_residue else n_groups):
            lo, hi = bounds[g], bounds[g + 1]
            ji = pjobs_l[lo]
            if not apply_l[ji]:
                continue
            job = meta.job_objs[ji]
            alloc_tasks: list = []
            pipe_tasks: list = []
            if columnar:
                # object residue only: bucket moves, node dicts, binds.
                # _status/_node_name are written as raw attrs — the columns
                # were already updated vectorized above, and going through
                # the property setters would redo 50k scalar column writes
                for i in range(lo, hi):
                    t = task_objs[placed_l[i]]
                    ni = node_l[i]
                    name = node_names[ni]
                    t._node_name = name
                    slot = by_node.get(ni)
                    if slot is None:
                        slot = by_node[ni] = ([], [])
                    if pipe_l[i]:
                        pnode = ssn.nodes.get(name)
                        if pnode is not None:
                            job.nodes_fit_delta[name] = (
                                t.init_resreq.fit_delta(pnode.idle)
                            )
                            ssn.note_fit_state(job)
                        pipe_tasks.append(t)
                        slot[1].append(t)
                    else:
                        alloc_tasks.append(t)
                        slot[0].append(t)
                        binds.append((t, name))
                job.rebucket_moved(alloc_tasks, TaskStatus.BINDING)
                if pipe_tasks:
                    job.rebucket_moved(pipe_tasks, TaskStatus.PIPELINED)
                    ssn.pipelined_tasks.extend(pipe_tasks)
                continue
            for i in range(lo, hi):
                t = task_objs[placed_l[i]]
                ni = node_l[i]
                t.node_name = node_names[ni]
                slot = by_node.get(ni)
                if slot is None:
                    slot = by_node[ni] = ([], [])
                if pipe_l[i]:
                    # pipeline-on-releasing ⇒ the task did NOT fit Idle:
                    # record the shortfall diagnostic (allocate.go:170-175)
                    pnode = ssn.nodes.get(t.node_name)
                    if pnode is not None:
                        job.nodes_fit_delta[t.node_name] = (
                            t.init_resreq.fit_delta(pnode.idle)
                        )
                        ssn.note_fit_state(job)
                    pipe_tasks.append(t)
                    slot[1].append(t)
                else:
                    alloc_tasks.append(t)
                    slot[0].append(t)
                    binds.append((t, t.node_name))
            # committed & ready → every new allocation dispatches immediately
            # (session.go:286-294); BINDING directly, skipping the
            # ALLOCATED→BINDING index churn
            asum = wrap_vec(job_alloc_sum[ji])
            job.bulk_transition(alloc_tasks, TaskStatus.BINDING, asum,
                                pending_sum=asum)
            if pipe_tasks:
                job.bulk_transition(
                    pipe_tasks, TaskStatus.PIPELINED, EMPTY,
                    pending_sum=wrap_vec(job_total_sum[ji] - job_alloc_sum[ji]),
                )
                ssn.pipelined_tasks.extend(pipe_tasks)
            ssn.fire_batch_allocations(job, alloc_tasks + pipe_tasks,
                                       wrap_vec(job_total_sum[ji]))

        # per-node accounting with the presummed rows (node_info.go:165-222
        # algebra); columnar path already applied the resource algebra via
        # the column matrices — only the task dict / acct residue remains
        for ni, (allocs, pipes) in by_node.items():
            node = ssn.nodes.get(node_names[ni])
            if node is None:
                continue
            if columnar:
                node.bulk_register_tasks(allocs, pipes)
            else:
                node.bulk_add_tasks(
                    allocs, pipes,
                    spec.wrap_vec(node_alloc_sum[ni]), spec.wrap_vec(node_pipe_sum[ni]),
                )

        if binds:
            # BindVolumes precedes every dispatch (statement.go:253-277)
            if not volume_noop:
                bind_volumes = ssn.cache.bind_volumes
                for t, _ in binds:
                    bind_volumes(t)
            # hand the cache the segment sums this replay already computed
            # ({key: (count, vec)}; bulk_bind falls back to accumulating any
            # group whose applied count differs)
            job_sums = {
                meta.job_objs[ji].uid: (int(n_alloc_applied[ji]), job_alloc_sum[ji])
                for ji in np.flatnonzero(n_alloc_applied).tolist()
            }
            node_alloc_cnt = np.bincount(node_of[alloc_sel], minlength=nN)
            node_sums = {
                node_names[ni]: (int(node_alloc_cnt[ni]), node_alloc_sum[ni])
                for ni in np.flatnonzero(node_alloc_cnt).tolist()
            }
            ssn.cache.bulk_bind(binds, job_sums=job_sums, node_sums=node_sums)

        # slow path after every bulk placement has landed: host predicates
        # observe them; jobs the bulk path demoted replay sequentially too
        n_slow = 0
        for g in range(n_groups):
            ji = pjobs_l[bounds[g]]
            if slow_l[ji] or ji in demoted_jobs:
                n_slow += 1
                self._slow_replay_job(
                    ssn, meta, assigned, pipelined, ji, placed[bounds[g]:bounds[g + 1]]
                )
        self.last_fallback = {
            "slow_jobs": n_slow,
            "promoted_ports_jobs": promoted_jobs,
            "host_place_tasks": self._host_place_count,
        }
        metrics.register_slow_replay_jobs(n_slow)
        metrics.register_host_fallback_tasks(self._host_place_count)

    # ------------------------------------------------------------------
    def _slow_replay_job(self, ssn, meta, assigned, pipelined, ji, idxs) -> None:
        """Per-task Statement replay — host is authoritative for the commit
        gate (JobReady, allocate.go:192-196) and for every predicate."""
        job = meta.job_objs[ji]
        stmt = ssn.statement()
        for ti in idxs:
            task = meta.task_objs[int(ti)]
            node_name = meta.node_names[int(assigned[ti])]
            pipe = bool(pipelined[ti])
            node = ssn.nodes.get(node_name)
            try:
                if node is not None and (
                    task.needs_host_predicate or ssn.host_only_predicates
                ):
                    ssn.predicate(task, node)
                # live fit re-check: a host-fallback placement may have
                # consumed capacity the device solve promised to this
                # placement; node.add_task does not re-verify fit
                if node is not None and not (
                    (not pipe and task.init_resreq.less_equal(node.idle))
                    or (pipe and task.init_resreq.less_equal(node.releasing))
                ):
                    raise FitFailure("node resources taken by host fallback")
                if pipe:
                    if node is not None:
                        job.nodes_fit_delta[node_name] = (
                            task.init_resreq.fit_delta(node.idle)
                        )
                        ssn.note_fit_state(job)
                    stmt.pipeline(task, node_name)
                else:
                    # raises FitFailure before mutating when a volume claim
                    # can't be satisfied from this node (cache.go:189-209)
                    stmt.allocate(task, node_name)
            except FitFailure as e:
                logger.info("device placement %s→%s rejected by host predicate: %s",
                            task.key(), node_name, e.reason)
                # the device would re-propose the same node next cycle
                # (the solve is deterministic), so fall back to the
                # reference's own sequential path for this task
                self._host_place(ssn, stmt, task)
        if ssn.job_ready(job):
            self._n_applied += len(stmt.operations)
            stmt.commit()
        else:
            logger.info(
                "job %s not ready after device solve (%d placements), discarding",
                job.uid, int(idxs.size),
            )
            # the session carries the control signal (backfill's real-request
            # gate reads ssn.host_discards — round-5 ADVICE #5: the registry
            # singleton's counter crossed wires between scheduler instances);
            # the instance counter stays as a diagnostics record
            self.last_host_discards += 1
            ssn.host_discards += 1
            stmt.discard()

    def _record_fit_errors(self, ssn, meta, fail_hist, assigned, task_job, pending) -> None:
        """FitErrors for unplaced pending tasks (allocate.go:151-155). The
        reason histogram comes from the lazy failure_histogram_solve dispatch
        the caller ran — only failure cycles pay it."""
        from kube_batch_tpu.api.job_info import FitErrors
        from kube_batch_tpu.ops.feasibility import REASON_MESSAGES

        unplaced = np.flatnonzero(pending & (assigned < 0))
        if unplaced.size == 0:
            return
        hist = fail_hist[: meta.n_tasks]
        n_nodes = getattr(meta, "live_nodes", meta.n_nodes)
        for ti in unplaced:
            job = meta.job_objs[int(task_job[ti])]
            task = meta.task_objs[int(ti)]
            if job is None or task is None:
                continue
            counts = dict(zip(REASON_MESSAGES, hist[ti].tolist()))
            if not any(counts.values()):
                # task was feasible at cycle start but lost the contention —
                # capacity went to other tasks this cycle
                counts = {
                    "node(s) resources were consumed by other tasks this cycle":
                        n_nodes
                }
            fe = FitErrors()
            fe.set_histogram(counts, n_nodes)
            job.nodes_fit_errors[task.uid] = fe
            ssn.note_fit_state(job)

    def _port_rows(self, cols) -> Dict[int, list]:
        """Lazily built per-execute: port → [task rows] of EVERY ported task
        (resident, pending, placed).  Occupancy is derived LIVE from the
        t_node column at query time — placements, discards, and object-scan
        fallbacks all flow through the node_name property that keeps t_node
        current, so there is exactly one source of truth and nothing to roll
        back."""
        idx = self._ports_by_node
        if idx is None:
            idx = self._ports_by_node = {}
            for row in cols._ported_rows:
                t = cols.task_by_row[row]
                if t is None:
                    continue
                for p in t.pod.host_ports:
                    idx.setdefault(p, []).append(row)
        return idx

    def _port_held_nodes(self, cols, port: int, exclude_row: int) -> set:
        """Node rows currently holding `port` (live t_node view)."""
        rows = self._port_rows(cols).get(port)
        if not rows:
            return set()
        t_node = cols.t_node
        return {
            int(t_node[r]) for r in rows
            if r != exclude_row and t_node[r] >= 0
        }

    def _host_place_columns(self, ssn, stmt, task) -> Optional[bool]:
        """Vectorized residual placement over the column matrices for tasks
        whose host-side constraints are hostPorts and inter-pod terms: fit +
        static predicates + port exclusion + the match-count planes' mask
        and preferred rows (live: this session's placements count) as array
        ops, device-weight scoring, then the same Idle-vs-Releasing
        decision.  Returns None when the task needs the full object scan
        (node-affinity terms the label bits cannot encode, host-only
        predicate plugins, no columns)."""
        cols = ssn.columns
        from kube_batch_tpu.framework.session import NODE_ORDER

        if (
            cols is None
            or ssn.host_only_predicates
            or task.rich_node_affinity
            or getattr(task, "_row", -1) < 0
            # a custom scoring policy (an extension score row or a NODE_ORDER
            # scorer beyond the built-in nodeorder plugin) isn't encoded in
            # the vectorized score below — the object scan consults
            # ssn.node_order, so policy stays consistent with the device solve
            or ssn.score_weights.extra_rows
            or set(ssn._fns.get(NODE_ORDER, {})) - {"nodeorder"}
        ):
            return None
        req = task.init_resreq.vec
        quanta = cols.spec.quanta
        fit_idle = np.all(req <= cols.n_idle + quanta, axis=1)
        fit_rel = np.all(req <= cols.n_rel + quanta, axis=1)
        cand = (fit_idle | fit_rel) & cols.n_valid & cols.n_sched
        excluded_rows = cols.excluded_node_rows(ssn)
        if excluded_rows:
            cand[excluded_rows] = False
        row = task._row
        # selector / taint bitsets (same encoding the device predicate uses)
        if cols.t_sel_impossible[row]:
            return False
        sel = cols.t_sel_bits[row]
        if sel.any():
            cand &= ~np.any(sel[None, :] & ~cols.n_label_bits, axis=1)
        cand &= ~np.any(cols.n_taint_bits & ~cols.t_tol_bits[row][None, :], axis=1)
        for p in task.pod.host_ports:
            held = self._port_held_nodes(cols, p, exclude_row=task._row)
            if held:
                cand[list(held)] = False
        planes = cols.affinity
        if planes.t_req[row]:
            cand &= planes.mask_row(row)
        if not cand.any():
            return False
        # device-weight scoring rows (ops/scoring.py's host twin)
        w = ssn.score_weights
        alloc = cols.n_alloc
        with np.errstate(divide="ignore", invalid="ignore"):
            used_after = cols.n_used + req
            frac = np.where(alloc > 0, np.minimum(used_after / np.maximum(alloc, 1e-9), 1.0), 1.0)
        free_cpu, free_mem = 1.0 - frac[:, 0], 1.0 - frac[:, 1]
        score = (
            w.least_requested * (free_cpu + free_mem) * 5.0
            + w.balanced_resource * (10.0 - np.abs(free_cpu - free_mem) * 10.0)
            + w.binpack * (frac[:, 0] + frac[:, 1]) * 5.0
        )
        if planes.t_pref[row]:
            score = score + w.pod_affinity * planes.scaled_score_row(row)
            if task.pod.affinity.preferred_node_terms:
                score = score + w.node_affinity * planes.node_score_row(row)
        score = np.where(cand, score, -np.inf)
        volume_ok = getattr(ssn.cache.volume_binder, "noop", False)
        for _ in range(8):  # volume-infeasible nodes retire and we re-pick
            ni = int(np.argmax(score))
            if score[ni] == -np.inf:
                return False
            name = cols.node_names[ni]
            if volume_ok or ssn.cache.volume_feasible(task, name):
                break
            score[ni] = -np.inf
        else:
            # more than 8 volume-infeasible picks: defer to the full object
            # scan, which probes volume feasibility on every node — a 9th
            # node may fit and must not be missed forever
            return None
        try:
            if fit_idle[ni]:
                stmt.allocate(task, name)
            else:
                job = ssn.jobs.get(task.job)
                node = ssn.nodes.get(name)
                if job is not None and node is not None:
                    job.nodes_fit_delta[name] = task.init_resreq.fit_delta(node.idle)
                    ssn.note_fit_state(job)
                stmt.pipeline(task, name)
        except FitFailure as e:
            logger.info("columns host placement %s→%s failed: %s",
                        task.key(), name, e.reason)
            return False
        # no port-ledger update needed: the placement just wrote t_node via
        # the node_name property, which is exactly what _port_held_nodes
        # reads — discards roll it back the same way
        return True

    def _host_place(self, ssn, stmt, task) -> bool:
        """Sequential placement for a task the device model couldn't encode:
        predicate every node, pick the best-scoring fit — exactly
        allocate.go:151-184 (PredicateNodes → PrioritizeNodes →
        SelectBestNode → Allocate on Idle / Pipeline on Releasing).  Tasks
        whose only host constraint is hostPorts take the vectorized column
        path instead of the O(nodes) object scan (VERDICT r2 weak #6)."""
        self._host_place_count += 1
        fast = self._host_place_columns(ssn, stmt, task)
        if fast is not None:
            return fast
        best, best_score = None, None
        for node in ssn.nodes.values():
            try:
                ssn.predicate(task, node)
            except FitFailure:
                continue
            if not (task.init_resreq.less_equal(node.idle)
                    or task.init_resreq.less_equal(node.releasing)):
                continue
            # volume reachability is part of host placement (AllocateVolumes
            # failing a node, cache.go:189-209)
            if not ssn.cache.volume_feasible(task, node.name):
                continue
            score = ssn.node_order(task, node)
            if best is None or score > best_score:
                best, best_score = node, score
        if best is None:
            return False
        # allocate-vs-pipeline is decided on the already-selected node
        # (allocate.go:161-184), not folded into the selection
        try:
            if task.init_resreq.less_equal(best.idle):
                stmt.allocate(task, best.name)
            else:
                job = ssn.jobs.get(task.job)
                if job is not None:
                    job.nodes_fit_delta[best.name] = (
                        task.init_resreq.fit_delta(best.idle)
                    )
                    ssn.note_fit_state(job)
                stmt.pipeline(task, best.name)
        except FitFailure as e:
            # e.g. a same-cycle reservation raced the feasibility probe;
            # the task stays Pending and the next cycle self-corrects
            # (allocate.go logs and moves on the same way)
            logger.info("host placement %s→%s failed: %s",
                        task.key(), best.name, e.reason)
            return False
        return True

"""QueryPlane — request encoding, probe dispatch, decode, publication.

One instance rides a SchedulerCache (``cache.query_plane``).  The
scheduling cycle publishes a :class:`serve.lease.SnapshotLease` after its
resident swap (actions/allocate.py calls :meth:`publish_session` on both
the solve path and the idle-cycle path, so an idle cluster still serves);
HTTP handler threads :meth:`submit` requests; the micro-batcher flushes
them together: a flush plans its probe points (a plain request is one, a
sweep its count grid) and answers them in as few
:func:`ops.probe.probe_solve` dispatches as hold them — usually one —
against the lease's device-resident columns; the shard_map variant when
the lease's solve ran sharded.

Probe answers are oracle-exact on a frozen snapshot (ops/probe.py module
docstring); the lease's ``snapshot_version`` tells clients which cache
state answered them, and every verdict carries a ``staleness`` block
(lease seq/version vs the publisher head — replicate/) bounding how far
behind the leader the serving state is.  The same plane serves follower
processes: replicate/follower.py publishes wire-rebuilt leases into this
broker and points :attr:`QueryPlane.head_fn` at the stream head.
"""

from __future__ import annotations

import contextlib
import logging
import threading
from concurrent.futures import Future
from typing import Dict, List, Optional

import numpy as np

from kube_batch_tpu import metrics
from kube_batch_tpu.obs.trace import tracer_of
from kube_batch_tpu.serve.batcher import MicroBatcher, _env_float
from kube_batch_tpu.serve.lease import LeaseBroker, SnapshotLease
from kube_batch_tpu.utils import telemetry

logger = logging.getLogger("kube_batch_tpu")

#: hard cap on speculative gang size (the G bucket ceiling); larger gangs
#: are rejected 400 — a capacity-planning sweep should batch smaller asks
MAX_GANG = 64

#: the probe batch's integer columns are i32 — out-of-range values must
#: 400 their own request at parse time, never overflow inside the flush
_I32_MAX = 2**31 - 1

#: /v1/whatif/sweep: the geometric count grid a sweep's first points
#: probe to bracket the feasibility boundary before binary search
_SWEEP_GRID = (1, 2, 4, 8, 16, 32, 64)


class WhatifError(Exception):
    """Request-level failure with an HTTP status (the handler maps it)."""

    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status


def _parse_request(body: dict, spec) -> dict:
    """Validate and normalize one /v1/whatif body.  Schema (README "Query
    plane"): queue, count, requests{cpu,memory,...}, and optional
    min_available / priority / node_selector / tolerations /
    min_resources / evictions."""
    if not isinstance(body, dict):
        raise WhatifError(400, "body must be a JSON object")
    queue = body.get("queue", "default")
    try:
        count = int(body.get("count", 1))
    except (TypeError, ValueError):
        raise WhatifError(400, "count must be an integer")
    if count < 1:
        raise WhatifError(400, "count must be >= 1")
    if count > MAX_GANG:
        raise WhatifError(400, f"count {count} exceeds the gang cap {MAX_GANG}")
    requests = body.get("requests") or {}
    if not isinstance(requests, dict):
        raise WhatifError(400, "requests must be a resource map")
    try:
        min_avail = int(body.get("min_available", count))
    except (TypeError, ValueError):
        raise WhatifError(400, "min_available must be an integer")
    # NO upper clamp to count: min_available > count is a gang that can
    # never reach readiness, and the real scheduler's gang discard reverts
    # exactly such placements — clamping would fabricate committed=true
    # where submission binds nothing (the commit gate must see the real
    # value).  The int32 bound IS enforced: the batch arrays are i32, and
    # an overflow there would 500 the whole flush window instead of
    # 400-ing this request
    min_avail = max(1, min_avail)
    if min_avail > _I32_MAX:
        raise WhatifError(400, "min_available out of range")
    selector = body.get("node_selector") or {}
    if not isinstance(selector, dict):
        raise WhatifError(400, "node_selector must be a label map")
    # tolerations/min_resources/priority are validated HERE, per request —
    # a malformed field must 400 its own request at submit time, never
    # surface inside the batch flush where it would 500 the whole window
    raw_tol = body.get("tolerations") or []
    if not isinstance(raw_tol, list):
        raise WhatifError(400, "tolerations must be a list")
    from kube_batch_tpu.api.pod import Toleration

    try:
        tolerations = [Toleration(**d) for d in raw_tol]
    except TypeError:
        raise WhatifError(400, "malformed toleration")
    min_resources = body.get("min_resources")
    if min_resources is not None:
        if not isinstance(min_resources, dict):
            raise WhatifError(400, "min_resources must be a resource map")
        try:
            min_resources = {str(k): float(v) for k, v in min_resources.items()}
        except (TypeError, ValueError):
            raise WhatifError(400, "min_resources values must be numeric")
    try:
        priority = int(body.get("priority", 0) or 0)
    except (TypeError, ValueError):
        raise WhatifError(400, "priority must be an integer")
    if not -_I32_MAX - 1 <= priority <= _I32_MAX:
        raise WhatifError(400, "priority out of range")
    # per-member resource vector — the SAME conversion an ingested pod's
    # TaskInfo applies (pods dim included), so the probe's rows carry
    # exactly what submission would
    from kube_batch_tpu.api.task_info import _requests_to_resource

    try:
        res = _requests_to_resource(
            {k: float(v) for k, v in requests.items()}, spec
        )
        req_vec = res.vec.astype(np.float32)
        # BestEffort member (empty InitResreq, the backfill path's pods):
        # the probe never models backfill binds, so the verdict carries an
        # explicit `unmodeled` entry instead of a silently-wrong verdict
        best_effort = bool(res.is_empty())
    except (TypeError, ValueError):
        raise WhatifError(400, "requests values must be numeric")
    return {
        "queue": str(queue),
        "count": count,
        "min_avail": min_avail,
        "priority": priority,
        "selector": {str(k): str(v) for k, v in selector.items()},
        "tolerations": tolerations,  # parsed Toleration objects
        "min_resources": min_resources,
        "req_vec": req_vec,
        "best_effort": best_effort,
        "evictions": bool(body.get("evictions", False)),
        "_t0": telemetry.perf_counter(),
    }


class _SweepPlan:
    """One /v1/whatif/sweep as a planner of probe points: the largest
    replica count whose gang fits, against ONE lease.  A geometric grid
    brackets the feasibility boundary, then classic binary search refines
    it — the server does the log(N) probes the client would otherwise issue
    as round-trips (feasibility is monotone in count on a frozen snapshot:
    a (c+1)-gang placement contains a c-gang placement).  The plan only
    NAMES the counts it needs next; the flush answers them, every sweep's
    together with the window's other points, and writes the verdicts into
    ``feasible``."""

    def __init__(self, req: dict):
        self.req = req
        self.feasible: Dict[int, bool] = {}
        self.max_fit: Optional[int] = None  # known once the walk has ended
        self._walk = self._plan()

    def _plan(self):
        max_count = self.req["max_count"]
        feasible = self.feasible
        grid = sorted({c for c in _SWEEP_GRID if c < max_count}
                      | {max_count})
        yield grid
        if not feasible[grid[0]]:
            return 0
        if feasible[max_count]:
            return max_count
        lo = max(c for c in grid if feasible[c])
        hi = min(c for c in grid if not feasible[c])
        while hi - lo > 1:
            mid = (lo + hi) // 2
            yield [mid]
            if feasible[mid]:
                lo = mid
            else:
                hi = mid
        return lo

    def next_points(self) -> List[int]:
        """The counts to probe next (each answered in ``feasible`` before
        the next call); empty once ``max_fit`` is known."""
        if self.max_fit is not None:
            return []
        try:
            return next(self._walk)
        except StopIteration as ended:
            self.max_fit = ended.value
            return []

    def response(self, lease: SnapshotLease, staleness: dict) -> dict:
        return {
            "snapshot_version": lease.version,
            "max_fit": self.max_fit,
            "feasible": self.max_fit >= 1,
            "max_count": self.req["max_count"],
            "probes": len(self.feasible),
            "staleness": staleness,
        }


class QueryPlane:
    def __init__(self, cache, max_batch: Optional[int] = None,
                 window_s: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 dispatch_timeout: Optional[float] = None,
                 start_thread: bool = True, prewarm: bool = False):
        cols = getattr(cache, "columns", None)
        if cols is None:
            raise ValueError("QueryPlane requires a columnar SchedulerCache")
        self.cache = cache
        self.broker = LeaseBroker()
        # KB_WHATIF_TIMEOUT_S bounds the wait for a lease inside a flush;
        # the HTTP handler derives its request timeout from it, so raising
        # the knob also buys a cold probe compile more headroom
        self.dispatch_timeout = (
            dispatch_timeout if dispatch_timeout is not None
            else _env_float("KB_WHATIF_TIMEOUT_S", 2.0)
        )
        self.max_gang = MAX_GANG
        # prewarm=True (the production server path) compiles the serving
        # floor bucket off the request path at each new lease shape, so the
        # first real window hits a warm jit cache instead of timing out
        # behind a cold compile
        self._prewarm = prewarm
        self._warm_lock = threading.Lock()
        self._warmed: set = set()
        self._warm_threads: List[threading.Thread] = []
        self._gate_gap_warned = False  # one-shot victim-gate divergence log
        # probe dispatches and the cycle's donating resident swaps exclude
        # each other through the broker (serve/lease.py module docstring).
        # The bound method is captured ONCE: attribute access creates a
        # fresh bound-method object each time, so close()'s identity check
        # needs this exact object to detach cleanly.
        self._swap_guard = self.broker.swap_guard
        cols.resident_swap_guard = self._swap_guard
        cache.query_plane = self
        # replication head source for the staleness block: None (the
        # leader — the lease IS the head) or a () -> (head_seq,
        # head_version) callable (followers point it at their applier)
        self.head_fn = None
        self.batcher = MicroBatcher(
            self._flush, max_batch=max_batch, window_s=window_s,
            max_queue=max_queue, start_thread=start_thread,
        )
        # two batcher workers flush at once: the totals below move under
        # this lock, each flush adding what it tallied for itself
        self._mu = threading.Lock()
        self.dispatches = 0
        self.points = 0  # live lanes put into those dispatches
        self.requests_served = 0
        self.flushes = 0  # the sequence number a flush's span carries
        self._in_flight = 0  # flushes between entry and their last answer
        # the cache's one span plane: a flush is a tree of its own there
        # (whatif:*), never part of a cycle's record
        self.tracer = tracer_of(cache)

    def close(self) -> None:
        self.batcher.stop()
        # bounded join on the prewarm workers: they are daemon threads, but
        # a closed plane must be quiescent (no compile racing teardown) —
        # each warm is one probe, so a short timeout covers the honest case
        # and a wedged compile can't hang close()
        for t in self._warm_threads:
            t.join(timeout=5.0)
        self._warm_threads = []
        cols = getattr(self.cache, "columns", None)
        if cols is not None and cols.resident_swap_guard is self._swap_guard:
            cols.resident_swap_guard = None
        if getattr(self.cache, "query_plane", None) is self:
            self.cache.query_plane = None

    # ------------------------------------------------------------------
    # publication (called from the cycle — actions/allocate.py)
    # ------------------------------------------------------------------
    def needs_publish(self, version: int) -> bool:
        """False when the live lease already carries ``version`` — an idle
        cycle with no ingest since the last publish can skip the snapshot
        build + resident swap entirely (the existing lease describes the
        exact same cache state)."""
        lease = self.broker.current()
        return lease is None or lease.version < version

    def publish_session(self, ssn, snap, meta, version: int) -> None:
        """Publish the lease for this cycle: the device-resident snapshot
        the solve consumed (memoized — the swap already ran for the solve
        dispatch), the session's solve configs, the dirty-tracker version
        token ``version`` that ``snap`` holds, and the row-allocator peek
        that keys the tie-hash oracle."""
        cols = ssn.columns
        if cols is None:
            return  # isolated/object session — nothing resident to lease
        from kube_batch_tpu.actions.allocate import session_allocate_config
        from kube_batch_tpu.actions.reclaim import victim_gates
        from kube_batch_tpu.api.columns import resident_snap
        from kube_batch_tpu.ops.eviction import EvictConfig
        from kube_batch_tpu.parallel.mesh import default_mesh, should_shard

        mesh = (
            default_mesh() if should_shard(snap.node_alloc.shape[0]) else None
        )
        dev = resident_snap(cols, snap, mesh=mesh)
        config = session_allocate_config(ssn)
        gates = victim_gates(ssn, "preempt")
        if not self._gate_gap_warned and gates & {"drf", "proportion"}:
            # a conf whose first voting preempt tier includes drf or
            # proportion victim gates is outside the eviction probe's
            # model (README "Query plane" modeled scope) — its victim
            # answers can diverge from the committed preempt solve.  Say
            # so once instead of silently serving wrong eviction sets.
            self._gate_gap_warned = True
            logger.warning(
                "whatif eviction probe does not model the conf's %s victim "
                "gate(s): /v1/whatif evictions answers may diverge from "
                "the committed preempt solve under this conf",
                sorted(gates & {"drf", "proportion"}),
            )
        evict_config = EvictConfig(
            mode="preempt",
            gang=ssn.plugin_enabled("gang"),
            drf=ssn.plugin_enabled("drf"),
            proportion=ssn.plugin_enabled("proportion"),
            victim_gang="gang" in gates,
            victim_conformance="conformance" in gates,
            # victim_drf/victim_proportion are not modeled by the eviction
            # probe (they never bind under the shipped two-tier conf, whose
            # first voting tier is gang+conformance; non-default confs get
            # the one-shot divergence warning above — README modeled scope)
            victim_drf=False,
            victim_proportion=False,
            weights=ssn.score_weights,
        )
        queue_rows = {
            name: i for i, name in enumerate(meta.queue_names) if name
        }
        if dev.aff_terms is not None or dev.task_aff_idx.shape[0] != 1:
            # a what-if gang carries no inter-pod term and the probe replaces
            # the sparse rows in its view: lease their padding, so that the
            # probe programs keep one shape whatever rung the cycle's termed
            # rows stood on
            capN = dev.node_alloc.shape[0]
            dev = dev._replace(
                aff_terms=None,
                task_aff_idx=np.full(1, -1, np.int32),
                task_aff_mask=np.ones((1, capN), bool),
                task_pref_idx=np.full(1, -1, np.int32),
                task_pref_node=np.zeros((1, capN), np.float32),
                task_pref_pod=np.zeros((1, capN), np.float32),
            )
        lease = SnapshotLease(
            snap=dev,
            meta=meta,
            version=int(version),
            config=config,
            evict_config=evict_config,
            mesh=mesh,
            probe_rows=tuple(cols.peek_task_rows(self.max_gang)),
            queue_rows=queue_rows,
            unmodeled_gates=tuple(sorted(gates & {"drf", "proportion"})),
        )
        pub = getattr(self.cache, "replication", None)
        if pub is not None:
            # publish the cycle onto the replication stream BEFORE the
            # broker install, so the lease carries the record's seq and
            # leader verdicts report the same staleness coordinates a
            # caught-up follower's do.  The resident swap's own delta
            # record rides along as the diff fast path.
            try:
                hint, hint_version = cols.export_delta_record(mesh)
                seq = pub.publish_cycle(
                    snap, meta, lease, delta_hint=hint,
                    cache_version=hint_version,
                )
                lease = lease._replace(seq=seq)
            except Exception:  # noqa: BLE001 — replication must never stall the cycle
                logger.exception(
                    "replication publish failed; followers will resync")
        self.broker.publish(lease)
        metrics.set_whatif_snapshot_version(lease.version)
        if self._prewarm:
            self._maybe_prewarm(lease)

    def _maybe_prewarm(self, lease: SnapshotLease) -> None:
        """Compile the serving floor bucket — (B, G=8, no evictions) — in a
        background thread the first time a lease with this (mesh, config,
        snapshot-shape) signature is published.  A cold probe compile at
        real serving scale outlasts the request timeout, so without this
        the first window after startup (and after every shape-bucket
        growth) would 503 through a healthy system.  The eviction variant
        stays lazily compiled: it runs in its own dispatch (see
        _flush_traced), so only its first requester waits on it.

        The warm dispatch probes a ZEROS TWIN of the lease snapshot, not
        the lease itself: the jit cache keys on shapes/dtypes/shardings,
        never values, and a warm thread registered as a broker reader for
        the compile's duration would block a donating resident swap — and
        with it the scheduling cycle — for that whole time, inverting
        "the write path outranks serving"."""
        key = (
            lease.mesh, lease.config, lease.evict_config,
            tuple(tuple(getattr(a, "shape", ())) for a in lease.snap),
        )
        with self._warm_lock:
            if key in self._warmed:
                return
            self._warmed.add(key)

        def warm():
            import jax
            import jax.numpy as jnp

            req = {
                "queue": "", "count": 1, "min_avail": 1, "priority": 0,
                "selector": {}, "tolerations": [], "min_resources": None,
                "req_vec": np.zeros(
                    int(lease.snap.task_req.shape[1]), np.float32),
                "evictions": False, "_t0": telemetry.perf_counter(),
            }
            try:
                # the twin's columns are task/node VECTORS (a few MB), not
                # the solve's [T, N] intermediates, so the clone is cheap;
                # the lease's own buffers are never read, so a concurrent
                # swap can donate them mid-warm without consequence (shape
                # and sharding are metadata — readable even off a donated
                # array); a leaf still on the host has no sharding and
                # takes the default placement the probe would give it
                twin = jax.tree_util.tree_map(
                    lambda a: jax.device_put(
                        jnp.zeros(a.shape, a.dtype),
                        getattr(a, "sharding", None)),
                    lease.snap,
                )
                # a tree of its own: the compile lands on its whatif:probe
                # child (/v1/trace last_detached), outside every cycle record
                with self.tracer.detached_span("whatif:prewarm"):
                    self._probe(lease._replace(snap=twin), [req])
            except Exception:  # noqa: BLE001 — warm-up only; serving still works cold
                logger.exception("whatif probe pre-warm failed")

        t = threading.Thread(target=warm, daemon=True, name="whatif-prewarm")
        # prune finished warms: a long-lived server crosses shape buckets
        # repeatedly, and an append-only list would retain every dead
        # thread (and its closure) for the process lifetime
        self._warm_threads = [w for w in self._warm_threads if w.is_alive()]
        self._warm_threads.append(t)
        t.start()

    # ------------------------------------------------------------------
    # request intake (HTTP handler threads)
    # ------------------------------------------------------------------
    def submit(self, body: dict) -> Future:
        """Validate and enqueue one request; the future resolves to the
        response dict (WhatifError for request-level failures)."""
        req = _parse_request(body, self.cache.spec)  # raises WhatifError(400)
        # overflow/stopped comes back as a QueueFull already set ON the
        # future (batcher.submit never raises)
        return self.batcher.submit(req)

    def submit_sweep(self, body: dict) -> Future:
        """Validate and enqueue one /v1/whatif/sweep request — the
        server-side "how many replicas of this gang fit" binary search.
        The body is a normal whatif body plus ``max_count`` (default the
        gang cap); ``count``/``min_available`` are ignored — each probed
        point c asks for a gang of c members, all required
        (min_available=c).  The future resolves to the sweep response."""
        req = _parse_request(body, self.cache.spec)
        if req["evictions"]:
            raise WhatifError(400, "sweep does not support evictions")
        try:
            max_count = int(body.get("max_count", MAX_GANG))
        except (TypeError, ValueError):
            raise WhatifError(400, "max_count must be an integer")
        if not 1 <= max_count <= MAX_GANG:
            raise WhatifError(
                400, f"max_count must be in [1, {MAX_GANG}]")
        req["max_count"] = max_count
        req["_sweep"] = True
        return self.batcher.submit(req)

    # ------------------------------------------------------------------
    # batch flush — plan the window's probe points, dispatch them together
    # ------------------------------------------------------------------
    def _flush(self, batch) -> None:
        # a client that timed out already 503'd and CANCELLED its future
        # (cmd/server.py) — don't spend device time on abandoned probes,
        # and don't let them into the verdict/latency metrics: a stalled
        # window would otherwise record N "successes" nobody received,
        # masking the outage in exactly the serving SLO series
        batch = [(r, f) for r, f in batch if not f.cancelled()]
        if not batch:
            return
        with self._mu:
            self.flushes += 1
            seq = self.flushes
            self._in_flight += 1
            in_flight = self._in_flight
        metrics.observe_whatif_batch(len(batch), self.batcher.depth(),
                                     in_flight)
        # the flush's own span tree (this is one of the batcher's worker
        # threads, or a test's tick; the open-span stacks are the
        # thread's own, so two flushes' trees do not mix): timed, on the
        # profiler's clock and totalled by name, never on a cycle's
        # record — ~28 flushes a second would push the solving cycles out
        # of the 256-cycle ring within seconds.  in_flight: the flushes in
        # flight as this one began, itself included (2 = it ran beside
        # another)
        try:
            with self.tracer.detached_span(
                    "whatif:flush", seq=seq, batch=len(batch),
                    in_flight=in_flight) as sp:
                self._flush_traced(batch, sp)
        finally:
            with self._mu:
                self._in_flight -= 1

    def _flush_traced(self, batch, sp_flush) -> None:
        tracer = self.tracer
        # a point is (index into the batch, count): one lane of a dispatch.
        # A plain request is one point (count None: as it was asked); a
        # sweep names the counts its plan needs next.  Every lane of the
        # probe program is an independent hypothetical against the frozen
        # snapshot, so a sweep's grid rides the plain requests' dispatch
        # and all sweeps take a refinement step in ONE dispatch: a window
        # costs one dispatch, plus one a step while a boundary lies inside
        # a grid gap, plus one where the points pass the batch bucket.
        # Requests with evictions keep a dispatch of their own:
        # with_evictions is a static jit arg selecting a superset program,
        # so one of them must not make every co-batched point pay the
        # eviction pass's device time (a sweep never carries evictions).
        sweeps = {i: _SweepPlan(req) for i, (req, _) in enumerate(batch)
                  if req.get("_sweep")}
        answers: list = [None] * len(batch)  # a response or a WhatifError
        first: List[tuple] = []
        for i, (req, _) in enumerate(batch):
            if i in sweeps:
                first += [(i, c) for c in sweeps[i].next_points()]
            elif not req["evictions"]:
                first.append((i, None))
        evicting = [(i, None) for i, (req, _) in enumerate(batch)
                    if req["evictions"]]
        tally = [0, 0]  # this flush's own dispatches and live points
        # the flush's place in the order of answers (serve/lease.py): taken
        # with the lease, given up when the block ends, whatever ends it
        with self.broker.delivery() as turn:
            with contextlib.ExitStack() as held:
                # from entering the broker until the lease is held or
                # refused: a resident swap in flight, or no lease since the
                # last one
                with tracer.span("whatif:lease"):
                    lease = held.enter_context(self.broker.dispatch(
                        timeout=self.dispatch_timeout, turn=turn))
                if lease is None:
                    err = WhatifError(
                        503,
                        "no snapshot lease published yet (scheduler warming)")
                    for _req, fut in batch:
                        if self._deliver(fut, error=err):
                            metrics.register_whatif_request("error")
                    return
                sp_flush.set(lease_version=lease.version)
                # every dispatch inside the single held region: each point
                # of the window answers against one snapshot
                self._dispatch_points(lease, batch, sweeps, answers, first,
                                      tally)
                self._dispatch_points(lease, batch, sweeps, answers,
                                      evicting, tally)
                while True:
                    step = [(i, c) for i, plan in sweeps.items()
                            if answers[i] is None
                            for c in plan.next_points()]
                    if not step:
                        break
                    self._dispatch_points(lease, batch, sweeps, answers,
                                          step, tally)
                staleness = self._staleness(lease)
                for i, plan in sweeps.items():
                    if answers[i] is None:
                        answers[i] = plan.response(lease, staleness)
                sp_flush.set(dispatches=tally[0], points=tally[1])
            # delivered once the lease is released, so a waiting swap goes
            # first, and once no flush that holds an older version is still
            # to deliver, so versions never decrease across responses
            with tracer.span("whatif:deliver"):
                turn.wait()
                self._deliver_all(batch, answers)

    def _dispatch_points(self, lease: SnapshotLease, batch, sweeps, answers,
                         points, tally) -> None:
        """Answer ``points`` in chunks of at most the batch bucket's lanes,
        in the order given, one dispatch a chunk, and route each lane back:
        a plain request's response into ``answers``, a count's verdict
        into the plan of the sweep that asked it.  A chunk that fails fails
        the requests with a point in it (500) and no others."""
        lanes = self.batcher.max_batch
        for k in range(0, len(points), lanes):
            chunk = points[k:k + lanes]
            reqs = [
                batch[i][0] if c is None
                else dict(batch[i][0], count=c, min_avail=c)
                for i, c in chunk
            ]
            try:
                host = self._probe(lease, reqs, tally=tally)
                with self.tracer.span("whatif:decode"):
                    for b, (i, c) in enumerate(chunk):
                        if answers[i] is not None:
                            continue  # failed with an earlier chunk
                        if c is None:
                            answers[i] = self._decode(lease, reqs[b], host, b)
                        else:
                            sweeps[i].feasible[c] = bool(host.feasible[b])
            except Exception as e:  # noqa: BLE001 — fail THIS chunk, keep serving
                logger.exception("whatif probe dispatch failed")
                for i, c in chunk:
                    what = "probe" if c is None else "sweep"
                    answers[i] = WhatifError(500, f"{what} failed: {e}")

    def _deliver_all(self, batch, answers) -> None:
        served = 0
        for (req, fut), answer in zip(batch, answers):
            if isinstance(answer, WhatifError):
                if self._deliver(fut, error=answer):
                    metrics.register_whatif_request("error")
                continue
            if not self._deliver(fut, result=answer):
                continue  # client gave up mid-dispatch
            if req.get("_sweep"):
                metrics.register_whatif_sweep()
            else:
                metrics.register_whatif_request(
                    "feasible" if answer["feasible"] else "infeasible")
            metrics.observe_whatif_latency(
                (telemetry.perf_counter() - req["_t0"]) * 1e3
            )
            served += 1
        with self._mu:
            self.requests_served += served

    @staticmethod
    def _deliver(fut: Future, result=None, error=None) -> bool:
        """Resolve a request future, tolerating a concurrent client
        cancellation (the handler cancels on its timeout) — returns
        whether the answer was actually delivered, so abandoned requests
        stay out of the serving counters."""
        try:
            if error is not None:
                fut.set_exception(error)
            else:
                fut.set_result(result)
            return True
        except Exception:  # noqa: BLE001 — cancelled between check and set
            return False

    # ---- encoding ----------------------------------------------------
    def _encode(self, lease: SnapshotLease, reqs: List[dict]):
        from kube_batch_tpu.api.snapshot import _TaintView, _pack_bits, bucket
        from kube_batch_tpu.ops.probe import ProbeBatch

        snap, meta = lease.snap, lease.meta
        R = int(snap.task_req.shape[1])
        W = int(snap.task_sel_bits.shape[1])
        Wt = int(snap.task_tol_bits.shape[1])
        B = self.batcher.max_batch      # FIXED bucket — no retrace on fill
        G = min(self.max_gang,
                bucket(max(r["count"] for r in reqs), floor=8))
        spec = self.cache.spec

        req_arr = np.zeros((B, G, R), np.float32)
        valid = np.zeros((B, G), bool)
        min_avail = np.ones(B, np.int32)
        queue = np.full(B, -1, np.int32)
        prio = np.zeros(B, np.int32)
        sel_bits = np.zeros((B, W), np.uint32)
        sel_imp = np.zeros(B, bool)
        tol_bits = np.zeros((B, Wt), np.uint32)
        min_res = np.zeros((B, R), np.float32)
        has_min_res = np.zeros(B, bool)
        taint_list = list(meta.taint_bit.items())
        for b, r in enumerate(reqs):
            n = r["count"]
            req_arr[b, :n] = r["req_vec"]
            valid[b, :n] = True
            min_avail[b] = r["min_avail"]
            queue[b] = lease.queue_rows.get(r["queue"], -1)
            prio[b] = r["priority"]
            # selector pairs → required label bits (build_snapshot's exact
            # encoding: a pair no node carries makes the selector impossible)
            bits: List[int] = []
            for k, v in r["selector"].items():
                bit = meta.label_pair_bit.get((k, v))
                if bit is None:
                    sel_imp[b] = True
                else:
                    bits.append(bit)
            if bits:
                sel_bits[b] = _pack_bits(bits, W)
            if r["tolerations"] and taint_list:
                # already-parsed Toleration objects (_parse_request)
                tb = [
                    bit for (tk, tv, te), bit in taint_list
                    if any(t.tolerates(_TaintView(tk, tv, te))
                           for t in r["tolerations"])
                ]
                if tb:
                    tol_bits[b] = _pack_bits(tb, Wt)
            mr = r["min_resources"]
            if mr is not None:
                has_min_res[b] = True
                for name, v in mr.items():
                    if name in spec:
                        min_res[b, spec.index(name)] = v
        pbatch = ProbeBatch(
            req=req_arr, valid=valid, min_avail=min_avail, queue=queue,
            prio=prio, sel_bits=sel_bits, sel_impossible=sel_imp,
            tol_bits=tol_bits, min_res=min_res, has_min_res=has_min_res,
        )
        rows = np.asarray(lease.probe_rows[:G], np.int32)
        return pbatch, rows

    # ---- dispatch + decode -------------------------------------------
    def _probe(self, lease: SnapshotLease, reqs: List[dict],
               tally: Optional[list] = None):
        """One ``(B, G)`` program over ``reqs``, a lane each: the device's
        answers on the host, for :meth:`_decode` to read by lane.  ``tally``
        is the calling flush's own ``[dispatches, points]``; a pre-warm has
        none and stays out of the serving counters."""
        import jax

        from kube_batch_tpu.parallel.mesh import call, program

        # children of the caller's root (a flush, a pre-warm): one of each
        # per dispatch
        tracer = self.tracer
        with tracer.span("whatif:encode"):
            pbatch, rows = self._encode(lease, reqs)
        with_evictions = any(r["evictions"] for r in reqs)
        # program dispatch + device_get: the device's share of a flush
        with tracer.span("whatif:probe", batch=len(reqs), gang=len(rows)):
            statics = dict(evict_config=lease.evict_config,
                           with_evictions=with_evictions)
            res = call(
                program("probe", lease.mesh, None, lease.config, **statics),
                lease.mesh, lease.snap, pbatch, rows, config=lease.config,
                **statics)
            if tally is not None:
                tally[0] += 1
                tally[1] += len(reqs)
                with self._mu:
                    self.dispatches += 1
                    self.points += len(reqs)
                metrics.register_whatif_dispatch(len(reqs))
            if not with_evictions:
                # the eviction fields are all-zeros placeholders on this
                # program, and victims is [B, T]-sized — at big snapshots
                # that dead transfer would rival the batch window itself.
                # None is an empty pytree: device_get skips it, and _decode
                # only reads these fields for evictions requests (the flush
                # keeps those in dispatches of their own, so a dispatch is
                # uniform)
                res = res._replace(
                    claim_node=None, victims=None, evict_covered=None
                )
            # kbt: allow[KBT010] THE sanctioned serving choke point: one
            # blocking transfer per dispatch — the whole point of the
            # micro-batcher is that every queued point shares it
            return jax.device_get(res)

    def _staleness(self, lease: SnapshotLease) -> dict:
        """The version-token-bounded staleness block every verdict
        carries: this lease's replication coordinates vs the stream head.
        On the leader (``head_fn`` unset) the lease IS the head — lag 0
        by construction; a follower reports the head of its last fetched
        frame, so ``lag_cycles`` bounds how many cycles behind the
        answering state is."""
        head_seq, head_version = (
            self.head_fn() if self.head_fn is not None
            else (lease.seq, lease.version)
        )
        return {
            "seq": lease.seq,
            "version": lease.version,
            "head_seq": head_seq,
            "head_version": head_version,
            "lag_cycles": max(0, head_seq - lease.seq),
        }

    def _decode(self, lease: SnapshotLease, req: dict, host, b: int) -> dict:
        from kube_batch_tpu.ops.feasibility import REASON_MESSAGES

        meta = lease.meta
        n = req["count"]
        assigned = np.asarray(host.assigned[b][:n])
        pipelined = np.asarray(host.pipelined[b][:n])
        node_names = meta.node_names
        nodes = [
            node_names[i] if 0 <= i < len(node_names) else None
            for i in assigned.tolist()
        ]
        feasible = bool(host.feasible[b])
        unplaced = int(np.sum(assigned < 0))
        # verdict honesty: every gap between this probe's model and the
        # committed pipeline that APPLIES to this request is surfaced per
        # response — a client must never silently over-trust a verdict
        # (these were one-shot process logs before; a log line is invisible
        # to the caller who needs it)
        unmodeled = []
        if req["evictions"]:
            unmodeled += [
                f"preempt victim gate '{g}' (conf tier) is not modeled by "
                "the eviction probe — victim sets may diverge from the "
                "committed preempt solve"
                for g in lease.unmodeled_gates
            ]
        if req.get("best_effort"):
            unmodeled.append(
                "all members are BestEffort (sub-quanta requests): the "
                "committed pipeline binds them via backfill, which this "
                "probe does not model — 'infeasible' here is expected"
            )
        out = {
            "snapshot_version": lease.version,
            "feasible": feasible,
            "committed": bool(host.committed[b]),
            "enqueue_admitted": bool(host.enqueue_ok[b]),
            "nodes": nodes,
            "pipelined": [bool(p) for p in pipelined.tolist()],
            "unplaced": unplaced,
            "unmodeled": unmodeled,
            "staleness": self._staleness(lease),
        }
        if unplaced:
            # fit-error reasons summed over the unplaced members — the same
            # histogram rows the committed cycle would record as FitErrors
            hist = np.asarray(host.reasons[b][:n])[assigned < 0].sum(axis=0)
            out["fit_errors"] = {
                msg: int(c) for msg, c in zip(REASON_MESSAGES, hist.tolist())
                if c
            }
        if req["evictions"]:
            claim = np.asarray(host.claim_node[b][:n])
            victims = np.flatnonzero(np.asarray(host.victims[b]))
            task_keys = meta.task_keys
            out["evictions"] = {
                "claim_nodes": [
                    node_names[i] if 0 <= i < len(node_names) else None
                    for i in claim.tolist()
                ],
                "victims": sorted(
                    task_keys[t] for t in victims.tolist()
                    if t < len(task_keys) and task_keys[t]
                ),
                "covered": bool(host.evict_covered[b]),
            }
        return out

"""Prometheus-compatible metrics (pkg/scheduler/metrics/metrics.go:27-121).

Same metric names and label sets under the `volcano` subsystem, with the
reference's 5·2^k exponential buckets, rendered in the Prometheus text
exposition format. Implemented standalone (no prometheus_client dependency);
serve render_prometheus() from any HTTP endpoint to match the reference's
`/metrics` (server.go:96-99)."""

from __future__ import annotations

import bisect
import threading
from collections import defaultdict
from typing import Dict, List, Tuple

# 5·2^k, k=0..9 (metrics.go:38-72)
EXP_BUCKETS = [5.0 * (2**k) for k in range(10)]


class Histogram:
    def __init__(self, name: str, help_text: str, labels: Tuple[str, ...] = ()):
        self.name = name
        self.help = help_text
        self.label_names = labels
        self._lock = threading.Lock()
        self._buckets: Dict[Tuple[str, ...], List[int]] = defaultdict(
            lambda: [0] * (len(EXP_BUCKETS) + 1)
        )
        self._sum: Dict[Tuple[str, ...], float] = defaultdict(float)
        self._count: Dict[Tuple[str, ...], int] = defaultdict(int)

    def observe(self, value: float, *label_values: str) -> None:
        self.observe_many(value, 1, *label_values)

    def observe_many(self, value: float, count: int, *label_values: str) -> None:
        """Record `count` samples of `value` in one update — the vectorized
        cycle's amortized per-task observations (50k individual observe()
        calls per cycle would be pure lock churn)."""
        if count <= 0:
            return
        with self._lock:
            b = self._buckets[label_values]
            b[bisect.bisect_left(EXP_BUCKETS, value)] += count
            self._sum[label_values] += value * count
            self._count[label_values] += count

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        with self._lock:
            for labels, buckets in self._buckets.items():
                base = ",".join(
                    f'{n}="{v}"' for n, v in zip(self.label_names, labels)
                )
                cum = 0
                for le, cnt in zip(EXP_BUCKETS, buckets):
                    cum += cnt
                    sep = "," if base else ""
                    lines.append(f'{self.name}_bucket{{{base}{sep}le="{le:g}"}} {cum}')
                cum += buckets[-1]
                sep = "," if base else ""
                lines.append(f'{self.name}_bucket{{{base}{sep}le="+Inf"}} {cum}')
                lines.append(f"{self.name}_sum{{{base}}} {self._sum[labels]:g}")
                lines.append(f"{self.name}_count{{{base}}} {self._count[labels]}")
        return "\n".join(lines)


class Summary:
    """A sum and a count, no buckets: two lines a series, for quantities
    read as a mean over a window (growth of the sum over growth of the
    count) on a page that is rendered every few milliseconds.  The sum
    prints ten significant digits: a mean of a few ms taken from the growth
    of a sum that has reached 1e7 ms needs them."""

    def __init__(self, name: str, help_text: str):
        self.name = name
        self.help = help_text
        self._lock = threading.Lock()
        self._sum = 0.0
        self._count = 0

    def observe_many(self, total: float, count: int) -> None:
        """Record ``count`` samples that add up to ``total``."""
        if count <= 0:
            return
        with self._lock:
            self._sum += total
            self._count += count

    def render(self) -> str:
        with self._lock:
            total, count = self._sum, self._count
        return "\n".join((
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} summary",
            f"{self.name}_sum{{}} {total:.10g}",
            f"{self.name}_count{{}} {count}",
        ))


class Counter:
    #: Prometheus exposition type — Gauge overrides (a counter that goes
    #: down reads as a reset to Prometheus clients)
    prom_type = "counter"

    def __init__(self, name: str, help_text: str, labels: Tuple[str, ...] = ()):
        self.name = name
        self.help = help_text
        self.label_names = labels
        self._lock = threading.Lock()
        self._values: Dict[Tuple[str, ...], float] = defaultdict(float)

    def add(self, value: float, *label_values: str) -> None:
        with self._lock:
            self._values[label_values] += value

    def inc(self, *label_values: str) -> None:
        self.add(1.0, *label_values)

    def set(self, value: float, *label_values: str) -> None:
        with self._lock:
            self._values[label_values] = value

    def remove(self, *label_values: str) -> None:
        """Drop a labeled series — per-job series are pruned when the job
        is collected, or long-running servers grow /metrics unboundedly."""
        with self._lock:
            self._values.pop(label_values, None)

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {self.prom_type}"]
        with self._lock:
            for labels, v in self._values.items():
                base = ",".join(f'{n}="{val}"' for n, val in zip(self.label_names, labels))
                lines.append(f"{self.name}{{{base}}} {v:g}")
        return "\n".join(lines)


class PolledCounter(Counter):
    """A counter whose values are kept elsewhere and read when the page is
    rendered.  The garbage collector's callback can run inside any
    allocation, one made while a Counter's lock is held included, so it
    adds into plain lists and never takes a lock
    (``obs/interruptions.py::GCLedger``); ``poll`` returns those totals as
    ``{label values: value}``."""

    poll = None

    def _refresh(self) -> None:
        if self.poll is not None:
            polled = self.poll()
            with self._lock:
                self._values.update(polled)

    def values(self) -> Dict[Tuple[str, ...], float]:
        """The series as they stand now (what :meth:`render` prints)."""
        self._refresh()
        with self._lock:
            return dict(self._values)

    def render(self) -> str:
        self._refresh()
        return super().render()


class Gauge(Counter):
    """A settable series rendered with TYPE gauge (Counter already carries
    set(); only the exposition type differs — Prometheus clients treat a
    counter that goes down as a reset, so shares/versions must not render
    as counters)."""

    prom_type = "gauge"


_SUBSYSTEM = "volcano"

E2E_LATENCY = Histogram(
    f"{_SUBSYSTEM}_e2e_scheduling_latency_milliseconds",
    "E2E scheduling latency in milliseconds",
)
PLUGIN_LATENCY = Histogram(
    f"{_SUBSYSTEM}_plugin_scheduling_latency_microseconds",
    "Plugin scheduling latency in microseconds",
    ("plugin", "OnSession"),
)
ACTION_LATENCY = Histogram(
    f"{_SUBSYSTEM}_action_scheduling_latency_microseconds",
    "Action scheduling latency in microseconds",
    ("action",),
)
TASK_LATENCY = Histogram(
    f"{_SUBSYSTEM}_task_scheduling_latency_microseconds",
    "Task scheduling latency in microseconds",
)
SCHEDULE_ATTEMPTS = Counter(
    f"{_SUBSYSTEM}_schedule_attempts_total",
    "Number of attempts to schedule pods, by the result",
    ("result",),
)
POD_PREEMPTION_VICTIMS = Counter(
    f"{_SUBSYSTEM}_pod_preemption_victims",
    "Number of selected preemption victims",
)
PREEMPTION_ATTEMPTS = Counter(
    f"{_SUBSYSTEM}_total_preemption_attempts",
    "Total preemption attempts in the cluster till now",
)
UNSCHEDULE_TASK_COUNT = Counter(
    f"{_SUBSYSTEM}_unschedule_task_count",
    "Number of tasks could not be scheduled",
    ("job_id",),
)
UNSCHEDULE_JOB_COUNT = Counter(
    f"{_SUBSYSTEM}_unschedule_job_count",
    "Number of jobs could not be scheduled",
)
# metrics.go:113-121 — declared by the reference (never incremented there);
# here it counts jobs re-entering a cycle still unschedulable
JOB_RETRY_COUNTS = Counter(
    f"{_SUBSYSTEM}_job_retry_counts",
    "Number of retry attempts per job",
    ("job_id",),
)
# fallback-pressure counters (round-3): how much of the allocate replay ran
# outside the vectorized bulk path
SLOW_REPLAY_JOBS = Counter(
    f"{_SUBSYSTEM}_slow_replay_jobs_total",
    "Jobs replayed through the sequential Statement path",
)
HOST_FALLBACK_TASKS = Counter(
    f"{_SUBSYSTEM}_host_fallback_tasks_total",
    "Tasks placed by the O(nodes) host fallback scan",
)
# fault-hardening counters (robustness PR): classified transport retries,
# per-host circuit-breaker state, degraded-cycle parking/shedding, failover
TRANSPORT_RETRIES = Counter(
    f"{_SUBSYSTEM}_transport_retries_total",
    "Apiserver transport retries by endpoint class and error kind",
    ("endpoint_class", "kind"),
)
BREAKER_TRANSITIONS = Counter(
    f"{_SUBSYSTEM}_circuit_breaker_transitions_total",
    "Circuit breaker state transitions",
    ("host", "state"),
)
BREAKER_OPEN = Counter(
    f"{_SUBSYSTEM}_circuit_breaker_open",
    "1 while the named host's circuit breaker is open",
    ("host",),
)
RESYNC_PARKED = Counter(
    f"{_SUBSYSTEM}_resync_parked_total",
    "Failed bind/evict decisions parked in the resync queue, by reason",
    ("reason",),
)
RESYNC_DEPTH = Counter(
    f"{_SUBSYSTEM}_resync_queue_depth",
    "Tasks currently awaiting resync repair",
)
RESYNC_QUARANTINED = Counter(
    f"{_SUBSYSTEM}_resync_quarantined",
    "Tasks shelved after exhausting their resync budget",
)
STATUS_WRITES_SHED = Counter(
    f"{_SUBSYSTEM}_status_writes_shed_total",
    "Status writebacks skipped or made async by a degraded cycle",
)
CYCLE_BUDGET_EXCEEDED = Counter(
    f"{_SUBSYSTEM}_cycle_budget_exceeded_total",
    "Cycles whose soft time budget elapsed before close",
)
LEADER_FAILOVER = Counter(
    f"{_SUBSYSTEM}_leader_failover_total",
    "Leadership takeovers, by resident-cache outcome (warm|cold)",
    ("mode",),
)
# query-plane counters (serve/): the amortization story is readable straight
# off /metrics — requests_total vs device_dispatches_total is the
# requests-per-dispatch ratio the serving bench asserts; dispatches over
# batch_size_count is the dispatches a flush, dispatch_points over
# dispatches x the batch bucket the share of the lanes paid for that were live
WHATIF_REQUESTS = Counter(
    f"{_SUBSYSTEM}_whatif_requests_total",
    "What-if probe requests, by verdict (feasible|infeasible|error)",
    ("verdict",),
)
WHATIF_DISPATCHES = Counter(
    f"{_SUBSYSTEM}_whatif_device_dispatches_total",
    "Batched probe device dispatches (a flush window's points in as few "
    "as hold them)",
)
WHATIF_DISPATCH_POINTS = Counter(
    f"{_SUBSYSTEM}_whatif_dispatch_points_total",
    "Live lanes put into probe dispatches (a plain request is one point, "
    "a sweep one per count it probes)",
)
WHATIF_BATCH_SIZE = Histogram(
    f"{_SUBSYSTEM}_whatif_batch_size",
    "Requests one flush window answered together",
)
WHATIF_FLUSHES_OVERLAPPED = Counter(
    f"{_SUBSYSTEM}_whatif_flushes_overlapped_total",
    "Flush windows that began while another flush was in flight (over "
    "whatif_batch_size_count: the share the second worker engaged for)",
)
WHATIF_QUEUE_DEPTH = Histogram(
    f"{_SUBSYSTEM}_whatif_queue_depth",
    "Whatif requests still queued at flush time",
)
WHATIF_LATENCY = Histogram(
    f"{_SUBSYSTEM}_whatif_request_latency_milliseconds",
    "Whatif request latency (enqueue to verdict) in milliseconds",
)
WHATIF_SNAPSHOT_VERSION = Gauge(
    f"{_SUBSYSTEM}_whatif_snapshot_version",
    "Dirty-tracker version token of the published snapshot lease",
)
# pipelined-cycle metrics (the event-driven loop): the latency the pipeline
# exists to optimize (pod ARRIVAL → bind DECISION, not just cycle ms), what
# woke each cycle, and how much egress the writeback stage hid behind the
# next cycle's compute
DECISION_LATENCY = Histogram(
    f"{_SUBSYSTEM}_arrival_to_decision_latency_milliseconds",
    "Pod-arrival to bind-decision latency in milliseconds",
)
TRIGGER_WAKES = Counter(
    f"{_SUBSYSTEM}_cycle_trigger_wakes_total",
    "Scheduling-cycle wakeups, by trigger (ingest: a raised signal woke "
    "the loop, arrival churn or the loop's own for pods its last cycle "
    "left pending | floor: the idle tick); the two sum to every wake",
    ("trigger",),
)
SELF_WAKES = Counter(
    f"{_SUBSYSTEM}_cycle_self_wakes_total",
    "Cycles woken by the loop's own signal alone: its last cycle bound "
    "pods and left schedulable ones pending (each also counted on "
    "cycle_trigger_wakes_total under trigger=ingest)",
)
# the trigger's settle hold (CycleTrigger._settle): how often an ingest
# wake waited for the rest of its burst, what ended the wait, how many
# signals one cycle then took (signals / holds = the coalescing factor),
# and what the waiting cost
SETTLE_HOLDS = Counter(
    f"{_SUBSYSTEM}_cycle_settle_holds_total",
    "Ingest wakes held for the rest of their burst before the cycle "
    "started, by what ended the hold (quiet: no further signal for the "
    "quiet gap | cap: the bound from the first signal | stop: shutdown)",
    ("ended_by",),
)
SETTLE_SIGNALS = Counter(
    f"{_SUBSYSTEM}_cycle_settle_signals_total",
    "Ingest signals folded into held wakes (over settle_holds_total: "
    "signals a deciding cycle took at once)",
)
SETTLE_HELD = Summary(
    f"{_SUBSYSTEM}_cycle_settle_milliseconds",
    "Time ingest wakes were held for the rest of their burst (ms)",
)
# the idle tick (Scheduler._idle_tick) and the lease re-arm that lets it
# find nothing owed (Scheduler._rearm_lease)
QUIESCENT_TICKS = Counter(
    f"{_SUBSYSTEM}_cycle_quiescent_ticks_total",
    "Floor wakes that found nothing owed and opened no session (each also "
    "counted on cycle_trigger_wakes_total under trigger=floor; over those: "
    "the share of idle ticks that cost no cycle)",
)
LEASE_REARMS = Counter(
    f"{_SUBSYSTEM}_whatif_lease_rearms_total",
    "What-if lease re-arms at a cycle's commit, by outcome (published: "
    "the cycle moved the cache past the lease, which was "
    "published again on the state the cycle left | ingest_pending: "
    "skipped, the next cycle starts at once and publishes from its open | "
    "not_owed: the lease already covered that state | failed)",
    ("outcome",),
)
PIPELINE_OVERLAP = Histogram(
    f"{_SUBSYSTEM}_pipeline_writeback_overlap_milliseconds",
    "Writeback-stage time overlapped behind the next cycle (ms)",
)
STAGED_INGEST = Counter(
    f"{_SUBSYSTEM}_staged_ingest_events_total",
    "Ingest events applied through the staged (one-lock) drain",
)
# longitudinal fairness surfaced live (sim runner + any caller with
# per-queue share samples): dominant share vs weight entitlement per queue
QUEUE_SHARE = Gauge(
    f"{_SUBSYSTEM}_queue_dominant_share",
    "Per-queue dominant share of cluster capacity (0..1)",
    ("queue",),
)
QUEUE_ENTITLEMENT = Gauge(
    f"{_SUBSYSTEM}_queue_share_entitlement",
    "Per-queue weight entitlement (weight / Σ weights)",
    ("queue",),
)
# result-integrity guard plane (kube_batch_tpu/guard): sentinel trips /
# fail-closed solves, shadow-oracle audit outcomes, and per-fast-path
# demotion state — the runtime twin of the KB_* oracle knobs
GUARD_TRIPS = Counter(
    f"{_SUBSYSTEM}_guard_trips_total",
    "Result-integrity trips (condemned solves), by action and reason "
    "(invariant|audit|unfit: a demotion's target does not fit the device)",
    ("action", "reason"),
)
GUARD_AUDITS = Counter(
    f"{_SUBSYSTEM}_guard_audits_total",
    "Shadow-oracle audit comparisons, by result (match|mismatch)",
    ("result",),
)
GUARD_PATH_DEMOTED = Gauge(
    f"{_SUBSYSTEM}_guard_path_demoted",
    "1 while a fast path is demoted to its oracle (topk|shard_map|warm)",
    ("path",),
)
# cycle tracing plane (kube_batch_tpu/obs): per-stage latency straight off
# the span recorder (the histogram twin of the trace tree), flight-recorder
# dumps by trigger reason, and the guard trip-rate SLO alerts
STAGE_LATENCY = Histogram(
    f"{_SUBSYSTEM}_cycle_stage_latency_milliseconds",
    "Per-stage scheduling-cycle latency (span recorder) in milliseconds",
    ("stage",),
)
FLIGHT_DUMPS = Counter(
    f"{_SUBSYSTEM}_flight_recorder_dumps_total",
    "Flight-recorder trace dumps, by trigger reason",
    ("reason",),
)
ALERTS_FIRING = Gauge(
    f"{_SUBSYSTEM}_alerts_firing",
    "1 while the named SLO alert fires (guard trip-rate thresholds)",
    ("alert",),
)
# replicated follower read plane (kube_batch_tpu/replicate): the leader's
# published stream (records/bytes by kind), the follower's apply/resync
# outcomes, and its live lag behind the stream head in cycles
REPLICATION_RECORDS = Counter(
    f"{_SUBSYSTEM}_replication_records_total",
    "Replication records published, by kind (full|delta|heartbeat)",
    ("kind",),
)
REPLICATION_BYTES = Counter(
    f"{_SUBSYSTEM}_replication_bytes_total",
    "Replication wire bytes published (encoded frames)",
)
REPLICATION_APPLIED = Counter(
    f"{_SUBSYSTEM}_replication_applied_total",
    "Replication records applied by this follower, by kind (full|delta)",
    ("kind",),
)
REPLICATION_RESYNCS = Counter(
    f"{_SUBSYSTEM}_replication_resyncs_total",
    "Delta-chain gaps that escalated this follower to a full resync",
)
REPLICATION_LAG = Gauge(
    f"{_SUBSYSTEM}_replication_lag_cycles",
    "Cycles this follower's applied state trails the stream head",
)
WHATIF_SWEEPS = Counter(
    f"{_SUBSYSTEM}_whatif_sweeps_total",
    "Capacity sweeps (/v1/whatif/sweep) served",
)

# the span plane's counters (obs/trace.py): where a decision's latency went
# (the wait for the deciding cycle to start; pods a cycle passed over), how
# long a what-if sat in the batcher, and every compile JAX reports with its
# seconds (jax.monitoring duration events — programs jitstats never tracked
# and compiles outside a device span included)
DECISION_QUEUE_WAIT = Summary(
    f"{_SUBSYSTEM}_decision_queue_wait_milliseconds",
    "Pod arrival to the start of the cycle that decided it (ms); the rest "
    "of arrival_to_decision_latency was spent inside that cycle",
)
DECISIONS_LEFTOVER = Counter(
    f"{_SUBSYSTEM}_decisions_leftover_total",
    "Pods bound after two or more cycles had drained ingest since they "
    "arrived (passed over by at least one cycle)",
)
WHATIF_QUEUE_WAIT = Summary(
    f"{_SUBSYSTEM}_whatif_queue_wait_milliseconds",
    "Whatif requests: enqueue to the start of the flush that took them (ms)",
)
JIT_COMPILE_SECONDS = Counter(
    f"{_SUBSYSTEM}_jit_compile_seconds_total",
    "Seconds JAX spent compiling, by phase (trace|lower|backend)",
    ("phase",),
)
JIT_COMPILES = Counter(
    f"{_SUBSYSTEM}_jit_compiles_total",
    "Backend compiles JAX reported (persistent-cache hits included)",
)
# which solve program a dispatch ran and where: the counter the benchmark
# reads where GET /v1/trace only tallies the flight recorder's ring
SOLVE_DISPATCHES = Counter(
    f"{_SUBSYSTEM}_solve_dispatches_total",
    "Device solve dispatches, by action, mode (single|sharded) and program "
    "(cold: full matrix | topk: compacted, table built this solve | "
    "warm: compacted, table carried | evict)",
    ("action", "mode", "program"),
)
# how far the allocate solve went into its rounds x outer budget: the sum
# over solves, and the solves that went past their first pass
SOLVE_ROUNDS = Counter(
    f"{_SUBSYSTEM}_solve_rounds_total",
    "Bidding rounds the device solves ran, by action",
    ("action",),
)
SOLVE_OVER_BUDGET = Counter(
    f"{_SUBSYSTEM}_solve_over_budget_total",
    "Device solves that ran more bidding rounds than one pass has "
    "(AllocateConfig.rounds): a pass ended with work left and another "
    "carried on from what it had placed, by action",
    ("action",),
)
ALLOCATE_RUNS_ON = Counter(
    f"{_SUBSYSTEM}_allocate_runs_on_total",
    "Allocate solves that spent their whole rounds x outer budget while "
    "still placing and were followed by a second solve in the same cycle",
)
# how often the compacted solve's candidate lists ran dry: the read-back
# allocate already makes of AllocateResult.topk_exhausted / topk_reentries
TOPK_EXHAUSTED = Counter(
    f"{_SUBSYSTEM}_topk_exhausted_total",
    "Task-rounds of the compacted solves in which a pending task's "
    "candidate list held no node that still fit, by action",
    ("action",),
)
# inter-pod (anti-)affinity answered from the match-count planes
# (api/affinity_planes.py): what a cycle derived from them, what keeping
# them cost, and what the in-solve rule turned away
AFFINITY_ROWS = Counter(
    f"{_SUBSYSTEM}_affinity_rows_total",
    "Pending rows whose required mask (kind=required) or preferred score "
    "row (kind=preferred) a device snapshot derived from the planes",
    ("kind",),
)
AFFINITY_PLANE_UPDATES = Counter(
    f"{_SUBSYSTEM}_affinity_plane_updates_total",
    "Cells of the match-count planes that a bind, a delete or a status "
    "change moved",
)
AFFINITY_SIGNATURES = Gauge(
    f"{_SUBSYSTEM}_affinity_signatures",
    "Distinct pod selectors of live inter-pod terms (rows of the planes)",
)
AFFINITY_DOMAINS = Gauge(
    f"{_SUBSYSTEM}_affinity_domains",
    "Distinct topology domains over the topology keys live terms use",
)
INTER_POD_EXCLUSIONS = Counter(
    f"{_SUBSYSTEM}_inter_pod_exclusions_total",
    "Bidders of the allocate solves that a placement of the same solve "
    "turned away from the node they would have chosen, by action",
    ("action",),
)
TOPK_REENTRIES = Counter(
    f"{_SUBSYSTEM}_topk_reentries_total",
    "Bidding rounds of the compacted solves that re-entered the full "
    "[T, N] matrix because a candidate list had run dry, by action",
    ("action",),
)
# a gang's own clock beside the pods': from its first member's arrival to
# the bind that left it no pending member, by the gang's size
GANG_DECISION_LATENCY = Histogram(
    f"{_SUBSYSTEM}_gang_decision_latency_milliseconds",
    "A gang's first arrival to its last member's bind decision in "
    "milliseconds, by size class (1 | 2-8 | 16-32 | 64+, named for the "
    "powers of two in them: 9-32 tasks read 16-32, 33 and more read 64+)",
    ("size_class",),
)
# the evict path (reclaim, preempt), end to end: what the solves' claims
# came to on the host, what was evicted, how long a victim took to go, and
# whether an eviction in flight was ever ordered again
EVICTIONS = Counter(
    f"{_SUBSYSTEM}_evictions_total",
    "Evictions ordered (the victim went Releasing and the order went out), "
    "by the action that ordered them",
    ("action",),
)
EVICT_CLAIMS = Counter(
    f"{_SUBSYSTEM}_evict_claims_total",
    "Claimants of the evict solves, by action and outcome (committed: "
    "victims evicted and the claimant pipelined | host_rejected: a host "
    "predicate, the tiered victim verdict or preempt's Statement gate said "
    "no | uncovered: the validated victims no longer cover it | "
    "gated_releasing: kept out of the solve because it fits a node's idle "
    "plus what that node's releasing victims have promised)",
    ("action", "outcome"),
)
EVICT_COMMITS = Counter(
    f"{_SUBSYSTEM}_evict_commits_total",
    "Batches of evictions handed to the cache, by the action that ordered "
    "them and the verb (bulk: bulk_evict, once an action of reclaim's "
    "replay and once a committed Statement of preempt's | single: evict, "
    "one task from outside a replay)",
    ("action", "path"),
)
EVICT_STATEMENTS = Counter(
    f"{_SUBSYSTEM}_evict_statements_total",
    "Statements of an evict action's replay, by action and outcome (opened: "
    "one a claimant job of preempt's phase 1, one a preemptor of its phase "
    "2; reclaim holds none | committed: the job reached Pipelined and its "
    "evictions went to the cache | discarded: it did not, and the session "
    "was put back as it was)",
    ("action", "outcome"),
)
EVICT_SOLVE_COMPACTED = Counter(
    f"{_SUBSYSTEM}_evict_solve_compacted_total",
    "Evict solve dispatches, by action and whether the bids ran on the "
    "pending bucket (true) or on the whole task axis (false: a pending set "
    "past the bucket, a task axis too small to have one, the sharded path)",
    ("action", "compacted"),
)
EVICTION_RELEASE_LATENCY = Histogram(
    f"{_SUBSYSTEM}_eviction_release_latency_milliseconds",
    "An eviction's order to the drain of its victim's DELETE in milliseconds",
)
EVICT_REPEAT_CLAIMS = Counter(
    f"{_SUBSYSTEM}_evict_repeat_claims_total",
    "Committed claims of a claimant that was given victims in an earlier "
    "cycle already, by what had become of those (in_flight: one is still to "
    "be deleted, so an eviction was ordered again while the first was in "
    "flight | released: all were deleted and the room they left went to "
    "another pod)",
    ("earlier",),
)
# the interruption ledger (obs/interruptions.py): what stopped the loop
# thread for a reason no span names.  The collector's pauses by generation
# (CPython's collection is stop-the-world under the GIL; the reference
# exports the same as go_gc_duration_seconds), the stalls the loop's
# watchdog declared, and the deciding cycles the flight recorder pinned
# because their worst decision stood out
GC_COLLECTIONS = PolledCounter(
    f"{_SUBSYSTEM}_gc_collections_total",
    "Garbage collections of the process, by generation",
    ("generation",),
)
GC_PAUSE_SECONDS = PolledCounter(
    f"{_SUBSYSTEM}_gc_pause_seconds_total",
    "Seconds every thread stood still for a garbage collection, by "
    "generation",
    ("generation",),
)
LOOP_STALLS = Counter(
    f"{_SUBSYSTEM}_loop_stalls_total",
    "Stalls of the scheduling loop the watchdog declared (parked: an "
    "ingest signal left unconsumed | cycle: a root span held open), each "
    "past four times what the loop expects and 250 ms",
    ("phase",),
)
LOOP_STALL_SECONDS = Counter(
    f"{_SUBSYSTEM}_loop_stall_seconds_total",
    "Seconds the declared stalls lasted, from their start, by phase",
    ("phase",),
)
SLOW_DECISIONS = Counter(
    f"{_SUBSYSTEM}_slow_decisions_total",
    "Cycles kept because the worst arrival-to-decision latency they "
    "closed was 100 ms and twice above the median of the last 32 "
    "deciding cycles",
)
DEVICE_PEAK_BYTES = Gauge(
    f"{_SUBSYSTEM}_device_peak_bytes",
    "peak_bytes_in_use of each local device, refreshed at most once a cycle",
    ("device",),
)
# a sound window reads 0 from these, not "no such series"
SELF_WAKES.add(0.0)
SETTLE_SIGNALS.add(0.0)
for _ended_by in ("quiet", "cap"):
    SETTLE_HOLDS.add(0.0, _ended_by)
QUIESCENT_TICKS.add(0.0)
for _outcome in ("published", "ingest_pending", "not_owed"):
    LEASE_REARMS.add(0.0, _outcome)
DECISIONS_LEFTOVER.add(0.0)
WHATIF_FLUSHES_OVERLAPPED.add(0.0)
SOLVE_ROUNDS.add(0.0, "allocate")
SOLVE_OVER_BUDGET.add(0.0, "allocate")
ALLOCATE_RUNS_ON.add(0.0)
TOPK_EXHAUSTED.add(0.0, "allocate")
TOPK_REENTRIES.add(0.0, "allocate")
for _kind in ("required", "preferred"):
    AFFINITY_ROWS.add(0.0, _kind)
AFFINITY_PLANE_UPDATES.add(0.0)
AFFINITY_SIGNATURES.set(0.0)
AFFINITY_DOMAINS.set(0.0)
INTER_POD_EXCLUSIONS.add(0.0, "allocate")
SLOW_REPLAY_JOBS.add(0.0)
HOST_FALLBACK_TASKS.add(0.0)
for _earlier in ("in_flight", "released"):
    EVICT_REPEAT_CLAIMS.add(0.0, _earlier)
for _action in ("reclaim", "preempt"):
    EVICTIONS.add(0.0, _action)
    for _outcome in ("committed", "host_rejected", "uncovered",
                     "gated_releasing"):
        EVICT_CLAIMS.add(0.0, _action, _outcome)
    for _compacted in ("true", "false"):
        EVICT_SOLVE_COMPACTED.add(0.0, _action, _compacted)
    for _path in ("bulk", "single"):
        EVICT_COMMITS.add(0.0, _action, _path)
for _outcome in ("opened", "committed", "discarded"):
    EVICT_STATEMENTS.add(0.0, "preempt", _outcome)
JIT_COMPILES.add(0.0)
for _phase in ("trace", "lower", "backend"):
    JIT_COMPILE_SECONDS.add(0.0, _phase)
for _generation in ("0", "1", "2"):
    GC_COLLECTIONS.add(0.0, _generation)
    GC_PAUSE_SECONDS.add(0.0, _generation)
for _phase in ("parked", "cycle"):
    LOOP_STALLS.add(0.0, _phase)
    LOOP_STALL_SECONDS.add(0.0, _phase)
SLOW_DECISIONS.add(0.0)

METRICS = [
    E2E_LATENCY,
    PLUGIN_LATENCY,
    ACTION_LATENCY,
    TASK_LATENCY,
    SCHEDULE_ATTEMPTS,
    POD_PREEMPTION_VICTIMS,
    PREEMPTION_ATTEMPTS,
    UNSCHEDULE_TASK_COUNT,
    UNSCHEDULE_JOB_COUNT,
    JOB_RETRY_COUNTS,
    SLOW_REPLAY_JOBS,
    HOST_FALLBACK_TASKS,
    TRANSPORT_RETRIES,
    BREAKER_TRANSITIONS,
    BREAKER_OPEN,
    RESYNC_PARKED,
    RESYNC_DEPTH,
    RESYNC_QUARANTINED,
    STATUS_WRITES_SHED,
    CYCLE_BUDGET_EXCEEDED,
    LEADER_FAILOVER,
    WHATIF_REQUESTS,
    WHATIF_DISPATCHES,
    WHATIF_DISPATCH_POINTS,
    WHATIF_BATCH_SIZE,
    WHATIF_FLUSHES_OVERLAPPED,
    WHATIF_QUEUE_DEPTH,
    WHATIF_LATENCY,
    WHATIF_SNAPSHOT_VERSION,
    DECISION_LATENCY,
    TRIGGER_WAKES,
    SELF_WAKES,
    SETTLE_HOLDS,
    SETTLE_SIGNALS,
    SETTLE_HELD,
    QUIESCENT_TICKS,
    LEASE_REARMS,
    PIPELINE_OVERLAP,
    STAGED_INGEST,
    QUEUE_SHARE,
    QUEUE_ENTITLEMENT,
    GUARD_TRIPS,
    GUARD_AUDITS,
    GUARD_PATH_DEMOTED,
    STAGE_LATENCY,
    FLIGHT_DUMPS,
    ALERTS_FIRING,
    REPLICATION_RECORDS,
    REPLICATION_BYTES,
    REPLICATION_APPLIED,
    REPLICATION_RESYNCS,
    REPLICATION_LAG,
    WHATIF_SWEEPS,
    DECISION_QUEUE_WAIT,
    DECISIONS_LEFTOVER,
    WHATIF_QUEUE_WAIT,
    JIT_COMPILE_SECONDS,
    JIT_COMPILES,
    SOLVE_DISPATCHES,
    SOLVE_ROUNDS,
    SOLVE_OVER_BUDGET,
    ALLOCATE_RUNS_ON,
    TOPK_EXHAUSTED,
    TOPK_REENTRIES,
    AFFINITY_ROWS,
    AFFINITY_PLANE_UPDATES,
    AFFINITY_SIGNATURES,
    AFFINITY_DOMAINS,
    INTER_POD_EXCLUSIONS,
    GANG_DECISION_LATENCY,
    DEVICE_PEAK_BYTES,
    EVICTIONS,
    EVICT_CLAIMS,
    EVICT_COMMITS,
    EVICT_STATEMENTS,
    EVICT_SOLVE_COMPACTED,
    EVICTION_RELEASE_LATENCY,
    EVICT_REPEAT_CLAIMS,
    GC_COLLECTIONS,
    GC_PAUSE_SECONDS,
    LOOP_STALLS,
    LOOP_STALL_SECONDS,
    SLOW_DECISIONS,
]


def observe_e2e_latency(ms: float) -> None:
    E2E_LATENCY.observe(ms)


def observe_action_latency(action: str, us: float) -> None:
    ACTION_LATENCY.observe(us, action)


def observe_plugin_latency(plugin: str, on_session: str, us: float) -> None:
    PLUGIN_LATENCY.observe(us, plugin, on_session)


def observe_task_latency(us: float) -> None:
    TASK_LATENCY.observe(us)


def observe_task_latencies(us_each: float, count: int) -> None:
    """Amortized per-task latency for `count` placements of one cycle —
    the vectorized analog of the reference's per-task observation
    (metrics.go:66-72, session.go:321)."""
    TASK_LATENCY.observe_many(us_each, count)


def register_schedule_attempt(result: str) -> None:
    SCHEDULE_ATTEMPTS.inc(result)


def update_preemption_victims(count: int) -> None:
    POD_PREEMPTION_VICTIMS.add(count)


def register_preemption_attempt() -> None:
    PREEMPTION_ATTEMPTS.inc()


def update_unschedule_task_count(job_id: str, count: int) -> None:
    UNSCHEDULE_TASK_COUNT.set(count, job_id)


def update_unschedule_job_count(count: int) -> None:
    UNSCHEDULE_JOB_COUNT.set(count)


def register_job_retry(job_id: str) -> None:
    JOB_RETRY_COUNTS.inc(job_id)


def prune_job_series(job_id: str) -> None:
    """Forget a collected job's labeled series (job_retry_counts,
    unschedule_task_count) — the cardinality bound for per-job labels."""
    JOB_RETRY_COUNTS.remove(job_id)
    UNSCHEDULE_TASK_COUNT.remove(job_id)


def register_slow_replay_jobs(count: int) -> None:
    if count:
        SLOW_REPLAY_JOBS.add(count)


def register_host_fallback_tasks(count: int) -> None:
    if count:
        HOST_FALLBACK_TASKS.add(count)


def register_transport_retry(endpoint_class: str, kind: str) -> None:
    TRANSPORT_RETRIES.inc(endpoint_class, kind)


def register_breaker_transition(host: str, state: str) -> None:
    BREAKER_TRANSITIONS.inc(host, state)


def set_breaker_open(host: str, is_open: int) -> None:
    BREAKER_OPEN.set(float(is_open), host)


def register_resync_parked(reason: str) -> None:
    RESYNC_PARKED.inc(reason)


def set_resync_depth(depth: int, quarantined: int) -> None:
    RESYNC_DEPTH.set(float(depth))
    RESYNC_QUARANTINED.set(float(quarantined))


def register_status_writes_shed(count: int) -> None:
    if count:
        STATUS_WRITES_SHED.add(count)


def register_cycle_budget_exceeded() -> None:
    CYCLE_BUDGET_EXCEEDED.inc()


def register_leader_failover(mode: str) -> None:
    LEADER_FAILOVER.inc(mode)


def register_guard_trip(action: str, reason: str) -> None:
    GUARD_TRIPS.inc(action, reason)


def register_guard_audit(result: str) -> None:
    GUARD_AUDITS.inc(result)


def set_guard_path_demoted(path: str, demoted: int) -> None:
    GUARD_PATH_DEMOTED.set(demoted, path)


def observe_stage_latency(stage: str, ms: float) -> None:
    STAGE_LATENCY.observe(ms, stage)


def register_flight_dump(reason: str) -> None:
    FLIGHT_DUMPS.inc(reason)


def set_alert_firing(alert: str, firing: int) -> None:
    ALERTS_FIRING.set(float(firing), alert)


def register_whatif_request(verdict: str) -> None:
    WHATIF_REQUESTS.inc(verdict)


def register_whatif_dispatch(points: int) -> None:
    WHATIF_DISPATCHES.inc()
    WHATIF_DISPATCH_POINTS.add(float(points))


def observe_whatif_batch(size: int, queue_depth: int,
                         in_flight: int) -> None:
    """One flush window: its requests, what it left queued, and how many
    flushes were in flight once it began (itself included)."""
    WHATIF_BATCH_SIZE.observe(float(size))
    WHATIF_QUEUE_DEPTH.observe(float(queue_depth))
    if in_flight > 1:
        WHATIF_FLUSHES_OVERLAPPED.inc()


def observe_whatif_latency(ms: float) -> None:
    WHATIF_LATENCY.observe(ms)


def set_whatif_snapshot_version(version: int) -> None:
    WHATIF_SNAPSHOT_VERSION.set(float(version))


def register_replication_record(kind: str, nbytes: int) -> None:
    REPLICATION_RECORDS.inc(kind)
    if nbytes:
        REPLICATION_BYTES.add(float(nbytes))


def register_replication_applied(kind: str) -> None:
    REPLICATION_APPLIED.inc(kind)


def register_replication_resync() -> None:
    REPLICATION_RESYNCS.inc()


def set_replication_lag(lag: int) -> None:
    REPLICATION_LAG.set(float(lag))


def register_whatif_sweep() -> None:
    WHATIF_SWEEPS.inc()


# optional exact-sample sink for the decision-latency stream: the bench
# needs true p50/p99 over the raw samples, which the 5·2^k histogram
# buckets are far too coarse for — a registered list receives every ms
# value alongside the histogram observation
_decision_sink = None


def set_decision_latency_sink(sink) -> None:
    """Register (or clear, sink=None) a list that receives every raw
    arrival→decision latency sample in ms."""
    global _decision_sink
    _decision_sink = sink


def observe_decision_latencies(ms_values) -> None:
    """Record arrival→decision latencies for one cycle's bind decisions."""
    for ms in ms_values:
        DECISION_LATENCY.observe(ms)
    sink = _decision_sink
    if sink is not None:
        sink.extend(ms_values)


def observe_decision_queue_wait(total_ms: float, count: int) -> None:
    DECISION_QUEUE_WAIT.observe_many(total_ms, count)


def register_decisions_leftover(count: int) -> None:
    if count:
        DECISIONS_LEFTOVER.add(count)


def observe_whatif_queue_wait(total_ms: float, count: int) -> None:
    WHATIF_QUEUE_WAIT.observe_many(total_ms, count)


def register_jit_compile(phase: str, seconds: float) -> None:
    JIT_COMPILE_SECONDS.add(seconds, phase)
    if phase == "backend":
        JIT_COMPILES.inc()


def register_loop_stall(phase: str) -> None:
    LOOP_STALLS.inc(phase)


def observe_loop_stall_seconds(phase: str, seconds: float) -> None:
    LOOP_STALL_SECONDS.add(seconds, phase)


def register_slow_decision() -> None:
    SLOW_DECISIONS.inc()


def register_solve_dispatch(action: str, mode: str, program: str) -> None:
    SOLVE_DISPATCHES.inc(action, mode, program)


def register_solve_rounds(action: str, rounds: int, over_budget: bool) -> None:
    SOLVE_ROUNDS.add(rounds, action)
    if over_budget:
        SOLVE_OVER_BUDGET.inc(action)


def register_allocate_runs_on() -> None:
    ALLOCATE_RUNS_ON.inc()


def register_topk_fallbacks(action: str, exhausted: int,
                            reentries: int) -> None:
    TOPK_EXHAUSTED.add(exhausted, action)
    TOPK_REENTRIES.add(reentries, action)


def register_affinity_rows(required: int, preferred: int) -> None:
    AFFINITY_ROWS.add(required, "required")
    AFFINITY_ROWS.add(preferred, "preferred")


def register_affinity_planes(updates: int, signatures: int,
                             domains: int) -> None:
    AFFINITY_PLANE_UPDATES.add(updates)
    AFFINITY_SIGNATURES.set(signatures)
    AFFINITY_DOMAINS.set(domains)


def register_inter_pod_exclusions(action: str, n: int) -> None:
    INTER_POD_EXCLUSIONS.add(n, action)


def register_eviction(action: str, n: int = 1) -> None:
    EVICTIONS.add(n, action)


def register_evict_commit(action: str, path: str) -> None:
    EVICT_COMMITS.inc(action, path)


def register_evict_claims(action: str, outcome: str, n: int) -> None:
    if n:
        EVICT_CLAIMS.add(n, action, outcome)


def register_evict_statements(action: str, outcome: str, n: int) -> None:
    if n:
        EVICT_STATEMENTS.add(n, action, outcome)


def register_evict_solve_compacted(action: str, compacted: bool) -> None:
    EVICT_SOLVE_COMPACTED.inc(action, "true" if compacted else "false")


def register_evict_repeat_claim(earlier: str) -> None:
    EVICT_REPEAT_CLAIMS.inc(earlier)


def observe_eviction_release_latency(ms: float) -> None:
    EVICTION_RELEASE_LATENCY.observe(ms)


def gang_size_class(size: int) -> str:
    """The ``size_class`` label of a gang of ``size`` tasks."""
    if size <= 1:
        return "1"
    if size <= 8:
        return "2-8"
    return "16-32" if size <= 32 else "64+"


def observe_gang_decision_latencies(gangs) -> None:
    """Record ``(ms, size)`` for each gang whose last member was decided."""
    for ms, size in gangs:
        GANG_DECISION_LATENCY.observe(ms, gang_size_class(size))


def refresh_device_peak_bytes() -> None:
    """Read every local device's ``peak_bytes_in_use`` into the gauge.  The
    scheduling loop calls this once a cycle; nothing does per scrape (the
    benchmark's decision channel reads /metrics every few milliseconds).
    A backend that reports no memory statistics (the CPU's) leaves no
    series."""
    import jax

    for device in jax.local_devices():
        stats = device.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            DEVICE_PEAK_BYTES.set(
                float(stats["peak_bytes_in_use"]), str(device.id))


def register_trigger_wake(trigger: str) -> None:
    """One loop wake, by :meth:`CycleTrigger.wait_for_work`'s reason.  The
    loop's own wake (``"leftover"``) has a counter of its own and is a
    raised signal like any other on the labelled series, so that series'
    two labels keep summing to the cycles run."""
    if trigger == "leftover":
        SELF_WAKES.inc()
        trigger = "ingest"
    TRIGGER_WAKES.inc(trigger)


def register_settle_hold(ended_by: str, signals: int, held_ms: float) -> None:
    """One settle hold of the trigger: what ended it, the ingest signals
    its wake had gathered by then, and how long the loop was held."""
    SETTLE_HOLDS.inc(ended_by)
    SETTLE_SIGNALS.add(signals)
    SETTLE_HELD.observe_many(held_ms, 1)


def register_quiescent_tick() -> None:
    """One floor wake that opened no session."""
    QUIESCENT_TICKS.inc()


def register_lease_rearm(outcome: str) -> None:
    """One look at the what-if lease at a cycle's commit, by what came of
    it."""
    LEASE_REARMS.inc(outcome)


def observe_pipeline_overlap(ms: float) -> None:
    PIPELINE_OVERLAP.observe(ms)


def register_staged_ingest(count: int) -> None:
    if count:
        STAGED_INGEST.add(count)


def set_queue_shares(shares: dict) -> None:
    """Export per-queue {share, entitlement} samples as live gauges — the
    sim runner's longitudinal fairness series surfaced through /metrics
    (and usable by any caller with the same sample shape).  Queues absent
    from the sample are pruned: a deleted queue must not export a phantom
    share forever."""
    live = {(q,) for q in shares}
    for gauge in (QUEUE_SHARE, QUEUE_ENTITLEMENT):
        for stale in [k for k in list(gauge._values) if k not in live]:
            gauge.remove(*stale)
    for queue, s in shares.items():
        QUEUE_SHARE.set(float(s.get("share", 0.0)), queue)
        QUEUE_ENTITLEMENT.set(float(s.get("entitlement", 0.0)), queue)


def render_prometheus() -> str:
    return "\n".join(m.render() for m in METRICS) + "\n"

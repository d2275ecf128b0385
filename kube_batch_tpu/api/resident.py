"""Device-resident snapshot columns, refreshed by ONE packed delta a swap.

Every column a solve reads from the device lives here between cycles: the
*per-cycle* columns (statuses, node ledgers, job/queue rows) and the task
*feature* columns (requests, priorities, selector/toleration bitsets) that
pod churn rewrites a few rows of on every burst.  Only the three node
feature columns stay on ``ColumnStore.resident_features``' version gate
(nodes do not churn).

Mechanism: for each cached field the host keeps a mirror of what the device
holds.  Each swap the freshly built host column is diffed against the
mirror (one vectorized compare — cheaper than the upload it replaces; the
task feature columns skip even that while the store's task feature version
has not moved):

- no rows changed  → the cached device array is handed to the solve as-is;
- a small delta    → the field's (rows, values) join the swap's ONE packed
  payload: an ``int32 [F, slots]`` row-index block plus one ``[slots,
  width]`` value block per dtype, padded to a FIXED slot count with
  out-of-range indices (each index row sorted and unique, declared so).  One jitted program takes the dict of resident
  buffers and the payload and returns the dict refreshed
  (``.at[rows].set(vals, mode="drop")`` per field; a field with nothing to
  write this swap carries all-padding rows), with every stale buffer
  DONATED so XLA writes in place;
- a large delta, a shape change (axis growth), or a column smaller than
  its own scatter payload → whole re-upload (``device_put``) of that field.

So a swap is one program dispatch plus one ``device_put`` per whole upload,
whatever number of fields moved (``counters()["dispatches"]``).  The slot
width is one bucket for the whole swap — the smallest of
``SCATTER_SLOT_BUCKETS`` that holds the widest field's delta — so the
program has three specializations per set of column shapes.  All three are
compiled at the cold upload (and again whenever a column's shape changes),
in two passes so that both upload-placed and program-output buffers have
been seen: steady-state cycles compile nothing (the bench's retrace
counters prove it).  Values are bit-identical to a whole upload by
construction — the scatter writes exactly the host rows — and
tests/test_snapshot_delta.py checks the round-trip.

Donation is skipped on the CPU backend (unsupported there; jax would warn
every cycle).

Mesh-sharded residency (:class:`ShardedPerCycleDeviceCache`): the sharded
solve keeps the same columns alive as ``NamedSharding``-placed buffers —
node-axis columns sharded over the mesh, everything else replicated — and
refreshes them with at most TWO programs a swap: the replicated columns
ride the same packed program under explicit replicated shardings, and the
node-axis columns ride a per-shard twin of it.  The changed node rows are
partitioned by owning shard on the host and shipped as ``[n_shards, F,
slots]`` LOCAL indices + ``[n_shards, slots, width]`` values whose leading
axis carries the mesh sharding, so the jitted update (a vmapped per-shard
scatter with explicit ``in_shardings``/``out_shardings``) routes each
delta slice straight to its owning chip — no gather, no reshard, no
cross-chip traffic.  Fallbacks to a whole (sharded) re-upload: cold cache,
axis growth, a delta wider than the per-shard slot budget (high churn), or
a mesh change (the ColumnStore drops the old mesh's cache wholesale — see
``per_cycle_resident``; the shape buckets are divisible by any
power-of-two mesh axis, and jax itself rejects an indivisible placement
before any solve could run).

Donation audit (PR 4, PR 42): the one donating call site in this module,
``PerCycleDeviceCache._dispatch``, rebinds the donated name to the call's
result (``devs = _swap_scatter_fn()(devs, rows, vals, layout)``, and its
mesh twins in the sharded cache's override) before anything reads it
— the shape KBT006 (analysis/flowrules.py) verifies package-wide, so a
post-donation read introduced later fails the tier-1 self-enforcement
test.  The swap programs (single-device AND per-mesh) are registered in
the jaxpr audit (analysis/jaxpr_audit.py), which asserts their donation
wiring per backend (KBT104) and that no f64/transfer/callback sneaks into
the traced update.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

from kube_batch_tpu.utils import jitstats

# snapshot fields rebuilt every cycle (minus the variable-K sparse affinity
# rows, which ship with each solve)
PER_CYCLE_FIELDS: Tuple[str, ...] = (
    "task_status", "task_node", "task_valid", "task_pending",
    "node_idle", "node_releasing", "node_used", "node_valid", "node_sched",
    "job_min_avail", "job_ready", "job_queue", "job_prio", "job_creation",
    "job_valid", "job_schedulable", "job_allocated",
    "queue_weight", "queue_capability", "queue_alloc", "queue_request",
    "queue_valid",
    "total",
)

#: the task feature columns: written at ingest (alloc_task / free_task /
#: refresh_task_bits, each of which bumps
#: ``ColumnStore.task_feature_version``), a few rows a burst — they ride the
#: swap's delta like the per-cycle fields, gated on that version
TASK_FEATURE_FIELDS: Tuple[str, ...] = (
    "task_req", "task_resreq", "task_job", "task_prio", "task_creation",
    "task_best_effort", "task_critical", "task_needs_host",
    "task_sel_bits", "task_sel_impossible", "task_tol_bits",
)
_TASK_FEATURES = frozenset(TASK_FEATURE_FIELDS)

#: everything one swap refreshes, in the order it stages them
SWAP_FIELDS: Tuple[str, ...] = PER_CYCLE_FIELDS + TASK_FEATURE_FIELDS

#: the subset whose leading axis is the node axis — sharded over the mesh
#: on the sharded solve path (parallel/mesh.snapshot_shardings); everything
#: else replicates
NODE_AXIS_FIELDS = frozenset((
    "node_idle", "node_releasing", "node_used", "node_valid", "node_sched",
))
#: the sharded cache's two programs' fields: the per-shard one's, and the
#: replicated one's
NODE_SWAP_FIELDS = tuple(f for f in SWAP_FIELDS if f in NODE_AXIS_FIELDS)
REPL_SWAP_FIELDS = tuple(f for f in SWAP_FIELDS if f not in NODE_AXIS_FIELDS)

#: fixed scatter width buckets — a swap ships at the smallest bucket that
#: holds its widest field's delta, so tiny steady-state deltas don't pay
#: the worst-case payload; every bucket is pre-warmed at the cold upload,
#: so the bounded set of specializations per set of column shapes never
#: retraces mid-steady-state.  A field whose delta is wider than the
#: largest bucket takes the whole-upload path (at which point the transfer
#: is no longer the bottleneck anyway).
SCATTER_SLOT_BUCKETS: Tuple[int, ...] = (64, 512, 4096)
SCATTER_SLOTS = SCATTER_SLOT_BUCKETS[-1]

#: per-shard slot-width buckets of the mesh scatter: the [n_shards, F,
#: slots] delta is sharded on its leading axis, so each chip receives
#: exactly its own slice.  This static ladder is the DEFAULT (zero observed
#: churn); the sharded cache retargets its live ladder from the churn EWMA
#: (:func:`adaptive_ladder`), capped by SHARD_SCATTER_SLOTS.
SHARD_SCATTER_SLOT_BUCKETS: Tuple[int, ...] = (16, 128, 1024)
SHARD_SCATTER_SLOTS = SHARD_SCATTER_SLOT_BUCKETS[-1]

#: churn EWMA smoothing for the adaptive per-shard ladder
CHURN_EWMA_DECAY = 0.8

#: where a payload's padding row indices start: slot k of a field's index
#: row holds either a changed row or PAD_ROW + k — out of range for every
#: column, so mode="drop" discards the write and the program's shape
#: depends only on the (pre-warmed) slot bucket, never on a delta's size;
#: and, the changed rows ascending below it, every index row is SORTED and
#: UNIQUE, which the scatter declares so XLA need not sort or combine
PAD_ROW = 1 << 30


def _slot_bucket(n: int, buckets: Tuple[int, ...]) -> int:
    """Smallest slot bucket holding an n-row delta (caller guarantees
    n ≤ buckets[-1])."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def all_shard_buckets(max_slots: int) -> Tuple[int, ...]:
    """Every per-shard bucket width the adaptive ladder can ever select
    (powers of two from 16 up to the hard cap).  The cold-upload prewarm
    compiles ALL of them, so a later ladder retarget is pure payload-
    sizing bookkeeping — no compile can ever land in a steady-state
    cycle, no matter where the churn EWMA moves."""
    out = []
    v = min(16, max_slots)
    while True:
        out.append(v)
        if v >= max_slots:
            return tuple(out)
        v = min(v * 2, max_slots)


def adaptive_ladder(ewma: float, max_slots: int) -> Tuple[int, ...]:
    """Per-shard slot-bucket ladder sized from the observed churn EWMA
    (replacing the static 16/128/1024 cap): the base bucket is the
    smallest power of two ≥ max(16, 2×ewma) — 2× headroom so the typical
    steady-state delta lands in the FIRST bucket instead of climbing the
    ladder — then ×8 steps up to the hard cap.  Zero churn reproduces the
    static default exactly; a steady high-churn regime drops the
    too-small buckets (their payloads would never be used) and starts at
    a bucket the observed deltas actually fit."""
    base = 16
    target = max(16.0, 2.0 * ewma)
    while base < target and base < max_slots:
        base *= 2
    base = min(base, max_slots)
    ladder = [base]
    while ladder[-1] < max_slots:
        ladder.append(min(ladder[-1] * 8, max_slots))
    return tuple(ladder)


# --------------------------------------------------------------------------
# the packed payload and the programs that apply it
# --------------------------------------------------------------------------


class SwapLayout(NamedTuple):
    """Where each field of one swap program sits in the packed payload —
    hashable, so it is the program's STATIC argument: one specialization
    per (layout, slot bucket).  ``fields[i]`` is ``(name, group, offset,
    width)``: row ``i`` of the index block holds the field's row indices,
    and columns ``offset : offset + width`` of value block ``group`` hold
    its rows, flattened.  ``groups[g]`` is ``(dtype name, total width)``."""

    fields: Tuple[Tuple[str, int, int, int], ...]
    groups: Tuple[Tuple[str, int], ...]


def _row_width(host) -> int:
    """Elements in one row of a column (1 for a 1-D column)."""
    width = 1
    for extent in host.shape[1:]:
        width *= int(extent)
    return width


def swap_layout(columns: Dict[str, np.ndarray]) -> SwapLayout:
    """The payload layout for `columns` (field → host column, in staging
    order): value blocks grouped by the dtype the DEVICE holds (the
    canonical one — x64 is off, so a 64-bit host column lives 32-bit)."""
    import jax

    widths: Dict[str, int] = {}
    placed = []
    for name, host in columns.items():
        dtype = np.dtype(jax.dtypes.canonicalize_dtype(host.dtype)).name
        width = _row_width(host)
        placed.append((name, dtype, widths.get(dtype, 0), width))
        widths[dtype] = widths.get(dtype, 0) + width
    order = sorted(widths)
    return SwapLayout(
        fields=tuple(
            (name, order.index(dtype), off, width)
            for name, dtype, off, width in placed
        ),
        groups=tuple((dtype, widths[dtype]) for dtype in order),
    )


def _apply_payload(devs, rows, vals, layout: SwapLayout):
    """The traced body of every swap program: scatter each field's slice
    of the payload into its buffer.  `rows` is [F, slots], `vals[g]` is
    [slots, width_g]; padding rows (PAD_ROW + slot) drop."""
    slots = rows.shape[1]
    out = {}
    for i, (name, group, off, width) in enumerate(layout.fields):
        dev = devs[name]
        v = vals[group][:, off:off + width].reshape((slots,) + dev.shape[1:])
        out[name] = dev.at[rows[i]].set(
            v, mode="drop", indices_are_sorted=True, unique_indices=True)
    return out


_SWAP_SCATTER = None


def _swap_scatter_fn():
    """The shared jitted swap program — ONE module-level function so every
    cache instance (simulator multi-scheduler runs, bench pairs, the test
    suite) reuses the same compiled specializations and jitstats tracks a
    single entry instead of retaining one wrapper per dead instance."""
    global _SWAP_SCATTER
    if _SWAP_SCATTER is None:
        import jax

        def swap_scatter(devs, rows, vals, layout):
            return _apply_payload(devs, rows, vals, layout)

        # donate the stale device buffers on real accelerators so the
        # update writes in place; CPU ignores donation (and warns), so
        # skip it there
        donate = () if jax.default_backend() == "cpu" else (0,)
        _SWAP_SCATTER = jitstats.register(
            "resident_swap",
            jax.jit(swap_scatter, static_argnums=(3,), donate_argnums=donate),
        )
    return _SWAP_SCATTER


# per-(mesh, sharded?) jitted swap programs — memoized so steady-state
# sharded cycles reuse one compiled specialization per (layout, bucket),
# same contract as the single-device _swap_scatter_fn
_MESH_SCATTER: dict = {}


def _mesh_repl_scatter_fn(mesh):
    """The replicated-placement swap program for `mesh`: same update as the
    single-device one, with explicit replicated in/out shardings so the
    results stay committed mesh arrays the sharded solve accepts as-is."""
    fn = _MESH_SCATTER.get((mesh, "repl"))
    if fn is None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(mesh, P())

        def swap_scatter_repl(devs, rows, vals, layout):
            return _apply_payload(devs, rows, vals, layout)

        donate = () if jax.default_backend() == "cpu" else (0,)
        fn = jitstats.register(
            "resident_swap_repl",
            jax.jit(swap_scatter_repl, static_argnums=(3,),
                    donate_argnums=donate,
                    in_shardings=(repl, repl, repl), out_shardings=repl),
        )
        _MESH_SCATTER[(mesh, "repl")] = fn
    return fn


def _mesh_shard_scatter_fn(mesh):
    """The per-shard swap program for the node-axis columns: each buffer is
    [N, ...] sharded over the node axis, `rows` is [n_shards, F, slots]
    and `vals[g]` [n_shards, slots, width_g], sharded on their LEADING
    axis with shard-LOCAL row indices — the vmap over the shard axis makes
    each chip scatter only its own delta slice (padding rows drop), and the
    explicit shardings keep GSPMD from inserting any gather/reshard around
    the update."""
    fn = _MESH_SCATTER.get((mesh, "shard"))
    if fn is None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from kube_batch_tpu.parallel.mesh import NODE_AXIS

        shard = NamedSharding(mesh, P(NODE_AXIS))

        def swap_scatter_sharded(devs, rows, vals, layout):
            n_shards = rows.shape[0]
            devs3 = {
                name: dev.reshape(
                    (n_shards, dev.shape[0] // n_shards) + dev.shape[1:])
                for name, dev in devs.items()
            }
            out = jax.vmap(
                lambda d, r, v: _apply_payload(d, r, v, layout)
            )(devs3, rows, vals)
            return {name: out[name].reshape(devs[name].shape)
                    for name in devs}

        donate = () if jax.default_backend() == "cpu" else (0,)
        fn = jitstats.register(
            "resident_swap_sharded",
            jax.jit(swap_scatter_sharded, static_argnums=(3,),
                    donate_argnums=donate,
                    in_shardings=(shard, shard, shard), out_shardings=shard),
        )
        _MESH_SCATTER[(mesh, "shard")] = fn
    return fn


def changed_rows(mirror: np.ndarray, host: np.ndarray) -> np.ndarray:
    """Ascending row indices where ``host`` differs from ``mirror`` — the
    ONE vectorized diff behind both device-cache scatter refreshes AND the
    replication publisher's wire deltas (replicate/publisher.py), so a
    follower's scatter payload is row-for-row the leader's."""
    if host.ndim == 1:
        return np.flatnonzero(mirror != host)
    # the differing ELEMENTS, folded to their rows: a sparse delta touches
    # few of them, and a per-row any() over a short inner axis costs ten
    # times the compare itself on a [50k, R] column
    hits = np.flatnonzero(mirror != host)
    if hits.size == 0:
        return hits
    rows = hits // _row_width(host)
    keep = np.empty(rows.size, bool)
    keep[0] = True
    np.not_equal(rows[1:], rows[:-1], out=keep[1:])
    return rows[keep]


def scatter_summary(per_path_counters: Dict[str, Dict[str, int]]
                    ) -> Dict[str, Dict]:
    """Per-path counter summary with the delta-vs-full bytes-moved
    reduction — the ONE derivation behind the bench artifact and the sim's
    longitudinal report (`ColumnStore.resident_counters()` feeds it)."""
    out: Dict[str, Dict] = {}
    for path, c in per_path_counters.items():
        moved = c["bytes_full"] + c["bytes_scatter"]
        rec = dict(c)
        rec["bytes_moved"] = moved
        if c["bytes_if_full"]:
            rec["upload_reduction"] = round(
                1.0 - moved / c["bytes_if_full"], 3
            )
        out[path] = rec
    return out


class _Staged(NamedTuple):
    """One field's share of a swap's payload, decided on the host."""

    host: np.ndarray
    changed: np.ndarray   # ascending row indices
    slots: int            # the smallest bucket holding this field's delta


class PerCycleDeviceCache:
    def __init__(self) -> None:
        self._mirror: Dict[str, np.ndarray] = {}
        self._dev: Dict[str, object] = {}
        # the packed-payload layout of the fields that can ever take the
        # scatter path at their current shapes (a column smaller than its
        # smallest payload always re-uploads whole and stays out); rebuilt,
        # and its program specializations pre-warmed, whenever a column is
        # uploaded cold or at a new shape
        self._layout = SwapLayout((), ())
        # per-swap delta record: field → changed row indices (np.ndarray)
        # for a scatter refresh, None for a full upload; clean fields are
        # absent.  The warm-started allocate's table invalidation
        # (WarmTableState.absorb) consumes this — the scatter diff already
        # knows exactly where state moved, so the candidate-table carry
        # rides the same knowledge instead of re-deriving it.
        self.delta_record: Dict[str, object] = {}
        # last (input snap, swapped result): a repeat swap of the SAME snap
        # object (api.columns.resident_snap memoizes one level up; the
        # follower and direct callers land here) is a guaranteed all-clean
        # diff over every field, skipped by identity
        self._last_in = None
        self._last_out = None
        # the store's task feature version at the last swap: while it has
        # not moved no task feature row was written, and their diff is
        # skipped (callers with no such token pass None and always diff)
        self._feature_token = None
        # monotonic swap version — the warm-standby revalidation's token:
        # a cache that has synced at least one snapshot (version > 0) and
        # passes the store's consistency check after a failover rebuild is
        # kept (buffers + compiled specializations survive; the next swap's
        # mirror diff absorbs any residual divergence as ordinary deltas)
        self.version = 0
        # diagnostics for the bench / tests: per-FIELD outcomes ...
        self.full_uploads = 0
        self.scatter_updates = 0
        self.clean_hits = 0
        # ... task feature columns among the whole uploads ...
        self.feature_uploads = 0
        # ... and device calls made: swap programs + whole device_puts
        self.dispatches = 0
        # bytes actually shipped host→device vs what full per-cycle uploads
        # would have shipped — the bench's delta-vs-full reduction evidence
        self.bytes_full = 0
        self.bytes_scatter = 0
        self.bytes_if_full = 0

    def counters(self) -> Dict[str, int]:
        return {
            "version": self.version,
            "full_uploads": self.full_uploads,
            "scatter_updates": self.scatter_updates,
            "clean_hits": self.clean_hits,
            "feature_uploads": self.feature_uploads,
            "dispatches": self.dispatches,
            "bytes_full": self.bytes_full,
            "bytes_scatter": self.bytes_scatter,
            "bytes_if_full": self.bytes_if_full,
        }

    @staticmethod
    def _payload_bytes(slots: int, host: np.ndarray) -> int:
        """Scatter payload size for a `slots`-wide delta of `host`'s row
        shape (int32 index + one value row per slot)."""
        return slots * (4 + host.dtype.itemsize * _row_width(host))

    # ---- placement hooks (the sharded cache overrides these) -------------
    def _put(self, field: str, host: np.ndarray):
        import jax

        return jax.device_put(host)

    def _share(self, field: str) -> float:
        """The share of `field`'s bytes THIS process ships (1 unless the
        field is sharded over a multi-host mesh)."""
        return 1.0

    def _scatter_slots(self, field: str, host: np.ndarray,
                       changed: np.ndarray) -> Optional[int]:
        """The slot bucket `field`'s delta needs, or None when it must
        re-upload whole: a delta past the widest bucket, or a column no
        larger than the payload that would patch it."""
        if changed.size > SCATTER_SLOTS:
            return None
        slots = _slot_bucket(changed.size, SCATTER_SLOT_BUCKETS)
        if self._payload_bytes(slots, host) >= host.nbytes:
            return None
        return slots

    def _scatterable(self, field: str, host: np.ndarray) -> bool:
        """Whether `field` can take the scatter path at ANY delta width —
        a shape-only fact, so it decides program membership."""
        return self._payload_bytes(SCATTER_SLOT_BUCKETS[0], host) < host.nbytes

    # ---- host side: decide each field's path -----------------------------
    def _upload(self, field: str, host: np.ndarray) -> None:
        """Whole upload of one field (cold, reshaped, or a wide delta)."""
        self.full_uploads += 1
        self.dispatches += 1
        if field in _TASK_FEATURES:
            self.feature_uploads += 1
        self.bytes_full += int(host.nbytes * self._share(field))
        self._dev[field] = self._put(field, host)
        self._mirror[field] = host.copy()

    def swap(self, snap, feature_token=None):
        """`snap` with every field of SWAP_FIELDS replaced by its
        device-resident copy (refreshed by delta).  The caller keeps using
        the ORIGINAL host-backed snap for numpy reads — only the returned
        copy feeds the solve.  A repeat call with the identical snap object
        (the same cycle's second dispatch) returns the memoized result
        without re-diffing.  ``feature_token`` is the store's task feature
        version: equal to the last swap's, the task feature columns are
        clean without a diff."""
        if snap is self._last_in:
            return self._last_out
        self.version += 1
        self.delta_record = {}
        features_clean = (
            feature_token is not None
            and feature_token == self._feature_token
        )
        staged: Dict[str, _Staged] = {}
        reshaped = False
        for field in SWAP_FIELDS:
            host = np.asarray(getattr(snap, field))
            self.bytes_if_full += int(host.nbytes * self._share(field))
            mirror = self._mirror.get(field)
            if (
                mirror is None
                or mirror.shape != host.shape
                or mirror.dtype != host.dtype
            ):
                self.delta_record[field] = None
                self._upload(field, host)
                reshaped = True
                continue
            if features_clean and field in _TASK_FEATURES:
                self.clean_hits += 1
                continue
            changed = changed_rows(mirror, host)
            if changed.size == 0:
                self.clean_hits += 1
                continue
            # the delta is known row-exactly from here down — either path
            # moves exactly `changed`, which is what the warm-table carry's
            # invalidation consumes
            self.delta_record[field] = changed
            slots = self._scatter_slots(field, host, changed)
            if slots is None:
                # specializations are already warm — no prewarm on this path
                self._upload(field, host)
            else:
                staged[field] = _Staged(host, changed, slots)
        if reshaped:
            self._prewarm()
        if staged:
            self._scatter(staged)
        self._feature_token = feature_token
        out = snap._replace(**self._dev)
        self._last_in, self._last_out = snap, out
        return out

    # ---- device side: one program for everything staged ------------------
    #: the fields the packed program covers (the sharded cache keeps the
    #: node-axis fields out of it, for their per-shard program)
    _packed_fields: Tuple[str, ...] = SWAP_FIELDS

    def _layout_of(self, fields) -> SwapLayout:
        """The payload layout over those of `fields` that can ever take
        the scatter path at their current shapes."""
        return swap_layout({
            field: self._mirror[field] for field in fields
            if self._scatterable(field, self._mirror[field])
        })

    def _dispatch(self, layout: SwapLayout, rows, vals) -> None:
        """THE donating call: the program takes the resident buffers of
        `layout`'s fields and hands back their refreshed successors,
        rebound before anything can read the donated ones."""
        devs = {name: self._dev[name] for name, _, _, _ in layout.fields}
        devs = _swap_scatter_fn()(devs, rows, vals, layout)
        self._dev.update(devs)
        self.dispatches += 1

    @staticmethod
    def _empty_payload(layout: SwapLayout, lead: Tuple[int, ...], slots: int):
        """An all-padding payload for `layout` ([*lead, F, slots] indices,
        [*lead, slots, width] values per dtype group)."""
        rows = np.empty(lead + (len(layout.fields), slots), np.int32)
        rows[...] = PAD_ROW + np.arange(slots, dtype=np.int32)
        vals = tuple(
            np.zeros(lead + (slots, width), dtype)
            for dtype, width in layout.groups
        )
        return rows, vals

    def _prewarm(self) -> None:
        """Rebuild the layout for the columns' current shapes and compile
        EVERY slot-bucket specialization of its program NOW — an all-
        padding payload writes nothing, so the values are untouched, but
        any real delta width in a later steady-state cycle becomes a cache
        hit, never a retrace.  TWO passes: the first bucket's first call
        sees upload-placed buffers, while real deltas mostly see program
        OUTPUT buffers — whose layout can key a fresh specialization; the
        second pass compiles every bucket against those too."""
        self._layout = layout = self._layout_of(self._packed_fields)
        if not layout.fields:
            return
        for _ in range(2):
            for slots in SCATTER_SLOT_BUCKETS:
                self._dispatch(layout,
                               *self._empty_payload(layout, (), slots))

    def _scatter(self, staged: Dict[str, _Staged]) -> None:
        """Pack every staged field's delta into one payload at the widest
        field's bucket and apply it in one dispatch."""
        layout = self._layout
        slots = max(s.slots for s in staged.values())
        rows, vals = self._empty_payload(layout, (), slots)
        for i, (name, group, off, width) in enumerate(layout.fields):
            s = staged.get(name)
            if s is None:
                continue
            n = s.changed.size
            fresh = s.host[s.changed]
            rows[i, :n] = s.changed
            vals[group][:n, off:off + width] = fresh.reshape(n, width)
            self._mirror[name][s.changed] = fresh
        self._dispatch(layout, rows, vals)
        self.scatter_updates += len(staged)
        self.bytes_scatter += rows.nbytes + sum(v.nbytes for v in vals)


class ShardedPerCycleDeviceCache(PerCycleDeviceCache):
    """Residency for the mesh-sharded solve path (module docstring):
    node-axis columns live sharded over `mesh`, everything else replicated
    across it; a swap refreshes the replicated columns with one packed
    program and the node-axis columns with its per-shard twin.

    Multi-host meshes: each process materializes and ships only its own
    ADDRESSABLE shards — uploads and per-shard payloads go through
    ``jax.make_array_from_callback`` (the callback is invoked per local
    shard only), so a host's cross-DCN upstream per cycle is its own
    shard's delta rows, never the full column.  The byte counters record
    the per-HOST share on sharded fields.

    The per-shard slot ladder is ADAPTIVE (:func:`adaptive_ladder`): a
    churn EWMA over the per-cycle max per-shard delta width retargets the
    bucket set, replacing the static 16/128/1024 sizing.  The cold-upload
    prewarm compiles the FULL reachable bucket set up front
    (:func:`all_shard_buckets`, all-padding payloads), so a retarget is
    pure payload-sizing bookkeeping and a real delta of any admissible
    width is a jit cache hit — steady state never retraces regardless of
    where the ladder moves."""

    def __init__(self, mesh) -> None:
        super().__init__()
        self.mesh = mesh
        from kube_batch_tpu.parallel.mesh import NODE_AXIS

        # the SCATTER shard count is the node-axis extent — on a 2-D
        # (tasks, nodes) mesh the node columns replicate across the task
        # axis, so the [n_shards, F, slots] payload splits by node shard
        # only
        self.n_shards = int(dict(mesh.shape)[NODE_AXIS])
        self.churn_ewma = 0.0
        self._ladder: Tuple[int, ...] = adaptive_ladder(
            0.0, SHARD_SCATTER_SLOTS
        )
        self._shard_layout = SwapLayout((), ())
        self._cycle_max = 0
        self.ladder_retargets = 0

    def counters(self) -> Dict[str, int]:
        out = super().counters()
        out["churn_ewma"] = round(self.churn_ewma, 2)
        out["slot_ladder"] = list(self._ladder)
        out["ladder_retargets"] = self.ladder_retargets
        return out

    def _host_fraction(self) -> float:
        """This process's addressable share of the mesh — the per-host
        byte-counter scale for sharded payloads."""
        import jax

        return 1.0 / jax.process_count()

    def _share(self, field: str) -> float:
        """Per-host accounting: a sharded field ships this process's share
        (numerator AND the bytes_if_full denominator, or upload_reduction
        would read inflated on multi-host meshes)."""
        return self._host_fraction() if field in NODE_AXIS_FIELDS else 1.0

    def _put(self, field: str, host: np.ndarray):
        """Placed upload: single-process goes through device_put; on a
        multi-host mesh each process materializes only its addressable
        shards via make_array_from_callback (the per-host scatter/upload
        contract above).  A node axis the mesh cannot divide would make
        per-shard indexing undefined — but jax itself rejects such a
        placement (NamedSharding divisibility), so the sharded solve path
        never reaches here with one; the shape buckets (snapshot.bucket)
        are divisible by any power-of-two mesh."""
        import jax

        from kube_batch_tpu.parallel.mesh import snapshot_shardings

        sharding = getattr(snapshot_shardings(self.mesh), field)
        if jax.process_count() > 1:
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx]
            )
        return jax.device_put(host, sharding)

    def _put_payload(self, arr: np.ndarray):
        """Per-shard payload block (leading axis sharded over the node
        axis): pre-placed per host on multi-process meshes so only the
        local shards' slices upload; single-process passes the numpy array
        straight to the jitted program (whose in_shardings place it)."""
        import jax

        if jax.process_count() == 1:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P

        from kube_batch_tpu.parallel.mesh import NODE_AXIS

        return jax.make_array_from_callback(
            arr.shape, NamedSharding(self.mesh, P(NODE_AXIS)),
            lambda idx: arr[idx],
        )

    def _shard_counts(self, host: np.ndarray, changed: np.ndarray):
        """(owning shard of each changed row, rows per shard)."""
        shard_ids = changed // (host.shape[0] // self.n_shards)
        return shard_ids, np.bincount(shard_ids, minlength=self.n_shards)

    def _scatter_slots(self, field, host, changed):
        if field not in NODE_AXIS_FIELDS:
            return super()._scatter_slots(field, host, changed)
        widest = int(self._shard_counts(host, changed)[1].max())
        self._cycle_max = max(self._cycle_max, widest)
        if widest > min(self._ladder[-1], SHARD_SCATTER_SLOTS):
            # over the LIVE ladder's cap — whole re-upload; the churn note
            # above grows the EWMA so a sustained regime retargets a wider
            # ladder instead of thrashing
            return None
        slots = _slot_bucket(widest, self._ladder)
        if self._payload_bytes(slots, host) * self.n_shards >= host.nbytes:
            # tiny sharded column: the whole upload is cheaper than the
            # smallest per-shard scatter payload
            return None
        return slots

    def _scatterable(self, field, host):
        if field not in NODE_AXIS_FIELDS:
            return super()._scatterable(field, host)
        return (self._payload_bytes(all_shard_buckets(SHARD_SCATTER_SLOTS)[0],
                                    host) * self.n_shards < host.nbytes)

    _packed_fields = REPL_SWAP_FIELDS

    def _dispatch(self, layout: SwapLayout, rows, vals) -> None:
        """The mesh twins of the base class's donating call, same shape:
        the per-shard program for the node-axis layout, the replicated one
        for the rest."""
        devs = {name: self._dev[name] for name, _, _, _ in layout.fields}
        if layout is self._shard_layout:
            devs = _mesh_shard_scatter_fn(self.mesh)(devs, rows, vals, layout)
        else:
            devs = _mesh_repl_scatter_fn(self.mesh)(devs, rows, vals, layout)
        self._dev.update(devs)
        self.dispatches += 1

    def _scatter_sharded(self, slots: int, staged: Dict[str, _Staged]) -> int:
        """One per-shard program over the node-axis layout: `staged`'s
        deltas partitioned by owning shard, shard-LOCAL indices.  Returns
        the payload bytes."""
        layout = self._shard_layout
        rows, vals = self._empty_payload(layout, (self.n_shards,), slots)
        for i, (name, group, off, width) in enumerate(layout.fields):
            s = staged.get(name)
            if s is None:
                continue
            per = s.host.shape[0] // self.n_shards
            shard_ids, counts = self._shard_counts(s.host, s.changed)
            # position of each changed row inside its shard's slots
            # (`changed` ascends, so each shard's rows are contiguous)
            offs = np.concatenate(([0], np.cumsum(counts)[:-1]))
            pos = np.arange(s.changed.size) - np.repeat(offs, counts)
            fresh = s.host[s.changed]
            rows[shard_ids, i, pos] = s.changed % per
            vals[group][shard_ids, pos, off:off + width] = fresh.reshape(
                s.changed.size, width)
            self._mirror[name][s.changed] = fresh
        self._dispatch(layout, self._put_payload(rows),
                       tuple(self._put_payload(v) for v in vals))
        return rows.nbytes + sum(v.nbytes for v in vals)

    def _prewarm(self) -> None:
        """The replicated program's buckets (base class), then the per-
        shard program's FULL reachable set (:func:`all_shard_buckets`),
        not just the live ladder."""
        super()._prewarm()
        self._shard_layout = self._layout_of(NODE_SWAP_FIELDS)
        if not self._shard_layout.fields:
            return
        for _ in range(2):
            for slots in all_shard_buckets(SHARD_SCATTER_SLOTS):
                self._scatter_sharded(slots, {})

    def _scatter(self, staged: Dict[str, _Staged]) -> None:
        sharded = {f: s for f, s in staged.items() if f in NODE_AXIS_FIELDS}
        repl = {f: s for f, s in staged.items() if f not in NODE_AXIS_FIELDS}
        if repl:
            super()._scatter(repl)
        if sharded:
            shipped = self._scatter_sharded(
                max(s.slots for s in sharded.values()), sharded)
            self.scatter_updates += len(sharded)
            self.bytes_scatter += int(shipped * self._host_fraction())

    def swap(self, snap, feature_token=None):
        if snap is self._last_in:
            return self._last_out
        out = super().swap(snap, feature_token)
        # EWMA update + ladder retarget at swap end.  Retargeting only
        # changes which payload widths later deltas ship — every reachable
        # bucket was compiled at the cold-upload prewarm, so this costs
        # nothing and can never retrace a steady-state cycle.
        self.churn_ewma = (
            CHURN_EWMA_DECAY * self.churn_ewma
            + (1.0 - CHURN_EWMA_DECAY) * self._cycle_max
        )
        self._cycle_max = 0
        new = adaptive_ladder(self.churn_ewma, SHARD_SCATTER_SLOTS)
        if new != self._ladder:
            self._ladder = new
            self.ladder_retargets += 1
        return out


# ==========================================================================
# Warm-started allocate: the cross-cycle candidate-table planner (KB_WARM)
# ==========================================================================
#
# The device side (ops/assignment.py warm_allocate_solve) carries the
# [P, W] candidate table between solves; this is the HOST side — the
# per-row invalidation bookkeeping that turns "what moved since the last
# solve" into the (row_map, changed_nodes, rerank_rows, rerank_slots)
# plan the warm program consumes.  The invalidation sources:
#
#   per-cycle node columns (ledgers, valid, sched) — the resident scatter
#     delta records above (``delta_record``): the diff that sizes the
#     scatter IS the row-exact "these nodes moved" set, absorbed into the
#     state between solves (multiple swaps per cycle accumulate);
#   task features (requests, selector/toleration bitsets) — they ride
#     the same swap, so the same records name the task rows that moved;
#   node features (allocatable / label / taint bits) — version-keyed
#     uploads carry no row deltas, so the state keeps its own mirrors and
#     diffs them at plan time;
#   a row's own bucket churn — membership/position handled by row_map;
#   sparse affinity/preference rows — derived per cycle from the
#     match-count planes (api/affinity_planes.py), which report where a
#     derived row moved since the last snapshot (``note_term_rows``): at
#     few nodes, those nodes join the changed set (a bind or a delete
#     moves a hostname-keyed term at the node whose ledger it moved
#     anyway); at many (a zone's count crossing zero, a min-max range
#     moving), the rows that read it re-rank;
#   erosion — the solve's per-row ``eroded`` output (θ-cut rows whose
#     valid prefix fell below the nominal K) re-ranks next cycle.
#
# Any wholesale movement (full upload, version gap, shape change, config
# change) escalates to a COLD plan: every live bucket row re-ranks, which
# is the carry's self-rebuild — bit-exact like every other path.

#: changed-node slot rungs of the warm merge's fresh [P, C] block —
#: coarse ×8 steps (the scatter-slot discipline) so steady churn cannot
#: flap a shape boundary; churn past the top rung escalates to cold
WARM_CHANGED_BUCKETS: Tuple[int, ...] = (64, 512, 4096)

#: stored-width margin: the carried table keeps W = K + margin entries so
#: θ/φ-cut erosion rarely reaches the nominal K before the re-rank
#: catches up.  Additive, not multiplicative: every extraction step of
#: the re-rank build costs ~the same regardless of M, so doubling W would
#: double the one genuinely extraction-bound piece of a warm cycle
WARM_WIDTH_MARGIN = 16


def warm_rerank_rungs(P: int) -> Tuple[int, ...]:
    """The sub-bucket rungs for a [P] pending bucket — ×2 steps from 128
    up to P (always ending at P).  Shared by the invalidated-row re-rank
    rung and the merge rung (the [M] live prefix the table refresh
    operates on — padding rows past the live count pay nothing).  The
    ratchets make each rung a one-time compile, so the finer ladder buys
    tighter compute without steady-state retrace risk."""
    out = []
    v = min(128, P)
    while v < P:
        out.append(v)
        v = min(v * 2, P)
    out.append(P)
    return tuple(out)


def _rung(n: int, rungs: Tuple[int, ...]) -> int:
    for r in rungs:
        if n <= r:
            return r
    return rungs[-1]


#: consecutive under-rung plans before a ratcheted rung drops back to fit
WARM_RUNG_DECAY = 3


def _ratchet(current: int, needed: int, low_streak: int, floor: int = 0):
    """Sticky rung with hysteresis decay: grow immediately, drop straight
    to the needed rung after WARM_RUNG_DECAY consecutive plans that
    needed less — a burst pins its rung only until the regime provably
    ended, so steady cycles stop paying burst-sized compute.  Every rung
    ever visited stays in the jit cache, so later oscillation between
    known rungs compiles nothing; the hysteresis only bounds how many
    DISTINCT rungs a noisy workload visits.  Returns (rung, streak')."""
    if needed >= current:
        return needed, 0
    low_streak += 1
    if low_streak < WARM_RUNG_DECAY:
        return current, low_streak
    return max(needed, floor), 0


class WarmTableState:
    """One solve path's carried candidate table + invalidation planner.

    Owned by the ColumnStore (one per (mesh, impl) dispatch slot — see
    ``ColumnStore.warm_table_state``); dropped wholesale on axis growth,
    resident-cache drops (guard heals), and mesh changes, so a carried
    table can never outlive the coordinate system its indices live in."""

    #: per-cycle snapshot fields whose row deltas invalidate node keys
    NODE_DELTA_FIELDS = (
        "node_idle", "node_releasing", "node_used", "node_valid",
        "node_sched",
    )
    #: task feature fields a carried row's ranking read: a row whose own
    #: request or bitsets moved re-ranks
    TASK_DELTA_FIELDS = (
        "task_req", "task_sel_bits", "task_sel_impossible", "task_tol_bits",
    )

    def __init__(self, mesh=None, impl=None):
        self.mesh = mesh
        self.impl = impl
        self._reset()
        # lifetime counters (bench incremental_solve / sim evidence)
        self.plans = 0
        self.cold_builds = 0
        self.reranked_total = 0
        self.changed_total = 0

    def _reset(self) -> None:
        self.shape_key = None       # (P, W, capN, capT, config)
        self.rows: Optional[np.ndarray] = None
        self.table = None           # (idx, skey, hash, trunc) device
        self.eroded_dev = None
        self._changed: Optional[np.ndarray] = None  # np bool [capN]
        self._node_full = True
        self._task_dirty: Optional[np.ndarray] = None  # np bool [capT]
        self._task_full = True
        self._absorbed_version = -1
        self._consumed_version = -1
        self._n_mirror: Optional[Dict[str, np.ndarray]] = None
        self._term_rerank: set = set()  # task rows a moved term re-ranks
        self._n_feat_ver = -1   # mirror-diff short circuit (see plan)
        # sticky rung ratchets (the TOPK bucket-ratchet discipline): a
        # rung, once visited, stays — churn oscillating across a rung
        # boundary must not retrace every other steady cycle.  The
        # rerank ratchet excludes the top (=P, cold-plan) rung: pinning
        # it would make every later merge cycle pay a cold-sized build.
        # The merge rung additionally may only decay down to the PREVIOUS
        # bucket's live count: carried row_map values index old live
        # slots, which must stay inside the sliced prefix.
        self._c_rung = 0
        self._r_rung = 0
        self._m_rung = 0
        self._c_low = 0   # consecutive plans under the current rung
        self._r_low = 0
        self._m_low = 0
        self.last: Dict = {}

    # ------------------------------------------------------------------
    def absorb(self, record: Dict, version: int) -> None:
        """Fold one resident swap's delta record into the pending
        invalidation (called from ColumnStore.per_cycle_resident after
        every swap of this state's mesh path).  A version the planner has
        already CONSUMED is skipped — the same cycle's later dispatches
        (the failure-histogram re-swap is memoized at the same version)
        re-notify the same record, and re-marking it after plan() cleared
        the accumulators would double every delta into the next merge."""
        if version <= self._consumed_version:
            return
        if not self._mark(record, self.NODE_DELTA_FIELDS, self._changed):
            self._node_full = True
        if not self._mark(record, self.TASK_DELTA_FIELDS, self._task_dirty):
            self._task_full = True
        self._absorbed_version = version

    @staticmethod
    def _mark(record: Dict, fields, mask: Optional[np.ndarray]) -> bool:
        """Set `mask` at the rows `record` names under `fields`; False when
        some of it moved wholesale (a full upload) or past the mask's axis
        (shape drift).  With no mask yet (before the first plan, which
        starts from all-moved anyway) there is nothing to mark."""
        for field in fields:
            if field not in record:
                continue
            rows = record[field]
            if rows is None:
                return False
            if mask is not None:
                if rows.size and rows[-1] >= mask.shape[0]:
                    return False
                mask[rows] = True
        return True

    def note_term_rows(self, changed_nodes: np.ndarray, rerank_rows) -> None:
        """Fold one snapshot's moved inter-pod/preferred rows into the
        pending invalidation (ColumnStore.device_snapshot, every build)."""
        if self._changed is not None:
            if changed_nodes.shape == self._changed.shape:
                self._changed |= changed_nodes
            else:
                self._node_full = True
        self._term_rerank.update(rerank_rows)

    # ------------------------------------------------------------------
    def _ensure(self, key, cols) -> None:
        if key != self.shape_key:
            self._reset()
            self.shape_key = key

    def _node_feature_dirty(self, cols) -> np.ndarray:
        """Bool mask of the node rows whose feature columns (allocatable,
        label / taint bits) moved since the last plan.  The node features
        carry no scatter deltas, so the state keeps its own mirrors; a
        shape change (bitset width growth, axis growth) reads as
        all-changed.  Short-circuits on the ColumnStore's node feature
        VERSION (the resident_features upload-cache key): an unmoved
        version means no node feature column changed, so the copy+compare
        is skipped on every steady cycle."""
        sources = {name: getattr(cols, name)
                   for name in ("n_alloc32", "n_label_bits", "n_taint_bits")}
        out = np.zeros(cols.n_alloc32.shape[0], bool)
        mirror = self._n_mirror
        if mirror is not None and self._n_feat_ver == cols.node_feature_version:
            return out
        for name, arr in sources.items():
            old = None if mirror is None else mirror.get(name)
            if old is None or old.shape != arr.shape:
                out[:] = True
            else:
                out |= np.any(old != arr, axis=1)
        self._n_mirror = {name: arr.copy() for name, arr in sources.items()}
        self._n_feat_ver = cols.node_feature_version
        return out

    # ------------------------------------------------------------------
    def plan(self, cols, pend_rows: np.ndarray, k: int,
             config) -> Optional[Dict]:
        """The per-solve invalidation plan, or None when warm cannot run
        this cycle (no per-cycle resident cache, or a swap this state did
        not absorb — both mean the delta chain is broken).

        Returns {"row_map", "changed", "rerank_rows", "rerank_slots",
        "table", "w", "cold"} — numpy plan arrays, the carried (or
        freshly zeroed) table, and the stored width."""
        cache = cols._per_cycle_dev.get(self.mesh)
        if cache is None or cache.version != self._absorbed_version:
            return None
        P = int(pend_rows.shape[0])
        capN = cols.nodes.cap
        capT = cols.tasks.cap
        W = k + WARM_WIDTH_MARGIN
        key = (P, W, capN, capT, config)
        self._ensure(key, cols)
        self.plans += 1
        if self._changed is None:
            self._changed = np.zeros(capN, bool)
        if self._task_dirty is None:
            self._task_dirty = np.zeros(capT, bool)

        new_live = pend_rows[pend_rows >= 0]
        # ---- task features: the rows the absorbed swaps recorded -------
        task_dirty = (
            np.ones(capT, bool) if self._task_full else self._task_dirty
        )
        # ---- node feature diffs (no scatter deltas to ride) ------------
        node_feat_dirty = self._node_feature_dirty(cols)

        # C rungs past the node capacity would make the fresh block wider
        # than the cold build it replaces — they escalate to cold instead
        c_rungs = tuple(
            r for r in WARM_CHANGED_BUCKETS if r < capN
        ) or (WARM_CHANGED_BUCKETS[0],)
        cold = (
            self.table is None
            or self.rows is None
            or self._node_full
            or bool(node_feat_dirty.all())
        )
        changed_mask = self._changed
        if not cold:
            changed_mask = changed_mask | node_feat_dirty
            n_changed = int(changed_mask.sum())
            if n_changed > min(c_rungs[-1], capN - 1):
                cold = True

        rerank_mask = np.zeros(P, bool)
        n_live = int(new_live.size)
        rungs = warm_rerank_rungs(P)
        # the merge rung: the [M] live prefix the device-side refresh
        # slices to (row_map's length IS the rung) — ratcheted with decay;
        # the decay floor covers the PREVIOUS bucket's live count so
        # carried old-slot indices always stay inside the prefix
        old_live = (
            int((self.rows >= 0).sum()) if self.rows is not None else 0
        )
        m_need = _rung(max(n_live, old_live, 1), rungs)
        self._m_rung, self._m_low = _ratchet(
            self._m_rung, m_need, self._m_low
        )
        m_rung = self._m_rung
        n_new = n_dirty = n_eroded = 0
        if cold:
            self.cold_builds += 1
            row_map = np.full(m_rung, -1, np.int32)
            rerank_mask[:n_live] = True
            changed = np.full(max(self._c_rung, c_rungs[0]), -1, np.int32)
            n_changed = 0
        else:
            # ---- bucket permutation (old slot per new slot) ----------
            old_live = self.rows[self.rows >= 0]
            pos = np.searchsorted(old_live, new_live)
            safe = np.minimum(pos, max(old_live.size - 1, 0))
            carried = (
                (pos < old_live.size) & (old_live[safe] == new_live)
                if old_live.size else np.zeros(n_live, bool)
            )
            row_map = np.full(m_rung, -1, np.int32)
            row_map[:n_live][carried] = pos[carried].astype(np.int32)
            # ---- the re-rank set -------------------------------------
            rerank_mask[:n_live] = ~carried                 # new rows
            n_new = int(np.sum(~carried))
            rerank_mask[:n_live] |= task_dirty[new_live]    # own features
            n_dirty = int(np.sum(task_dirty[new_live]))
            if self._term_rerank:                           # a moved term
                rerank_mask[:n_live] |= np.isin(
                    new_live, np.fromiter(self._term_rerank, np.int64)
                )
            if self.eroded_dev is not None:
                # kbt: allow[KBT010] tiny [P]-bool readback of LAST cycle's
                # erosion flags at plan time — long since computed, so the
                # sync is free; riding the action readback would thread
                # warm state through every consumer for no transfer win
                eroded = np.asarray(self.eroded_dev)
                er_rows = self.rows[np.flatnonzero(eroded)]
                er_rows = er_rows[er_rows >= 0]
                n_eroded = int(er_rows.size)
                if er_rows.size:
                    # SPARE-FILL budget: erosion refresh only occupies the
                    # re-rank rung's padding slots, never grows the rung —
                    # the mandatory set (new/dirty rows) prices the rung,
                    # and refreshing eroded rows inside it is free compute.
                    # Deferred rows stay EXACT (a thin table answers via
                    # the prefix/exhaustion contract) and retry next cycle.
                    base = int(rerank_mask.sum())
                    spare = _rung(max(base, 1), warm_rerank_rungs(P)) - base
                    if spare > 0:
                        admit = np.isin(new_live, er_rows)
                        admit &= ~rerank_mask[:n_live]
                        extra = np.flatnonzero(admit)[:spare]
                        rerank_mask[extra] = True
            # changed-node list at its (ratcheted, decaying) rung
            ch_rows = np.flatnonzero(changed_mask)
            n_changed = int(ch_rows.size)
            self._c_rung, self._c_low = _ratchet(
                self._c_rung, _rung(max(n_changed, 1), c_rungs),
                self._c_low, floor=c_rungs[0],
            )
            changed = np.full(self._c_rung, -1, np.int32)
            changed[:n_changed] = ch_rows.astype(np.int32)

        n_rerank = int(rerank_mask.sum())
        rrung = _rung(max(n_rerank, 1), rungs)
        if rrung < P:
            # sub-P rungs ratchet with decay; a cold-sized rung (=P)
            # never pins the ratchet
            self._r_rung, self._r_low = _ratchet(
                self._r_rung, rrung, self._r_low, floor=rungs[0]
            )
            rrung = min(self._r_rung, m_rung)
        rerank_slots = np.full(rrung, -1, np.int32)
        slots = np.flatnonzero(rerank_mask)
        rerank_slots[:n_rerank] = slots.astype(np.int32)
        rerank_rows = np.full(rrung, -1, np.int32)
        rerank_rows[:n_rerank] = pend_rows[slots]

        table = self.table
        if table is None:
            table = self._init_table(P, W)
        # plan consumed: clear the accumulators (and mark the consumed
        # swap version so same-version re-notifies can't re-mark them);
        # the next swaps rebuild
        self._changed = np.zeros(capN, bool)
        self._task_dirty = np.zeros(capT, bool)
        self._term_rerank = set()
        self._node_full = False
        self._task_full = False
        self._consumed_version = self._absorbed_version
        self.rows = pend_rows.copy()
        self.reranked_total += n_rerank
        self.changed_total += n_changed
        self.last = {
            "cold": cold, "reranked": n_rerank, "changed": n_changed,
            "bucket_live": n_live, "w": W,
            # re-rank attribution (bench/sim evidence): fresh bucket rows,
            # rows whose own features moved, θ/φ-eroded rows
            "new": n_new, "dirty": n_dirty, "eroded": n_eroded,
            # the shapes this plan's program is compiled for: the merge,
            # re-rank and changed-node rungs (a compile in the serving loop
            # is a rung no warm-up visited: the compile log names it)
            "rungs": [int(m_rung), int(rrung), int(changed.shape[0])],
        }
        return {
            "row_map": row_map, "changed": changed,
            "rerank_rows": rerank_rows, "rerank_slots": rerank_slots,
            "table": table, "w": W, "cold": cold,
        }

    def _init_table(self, P: int, W: int):
        import jax
        import jax.numpy as jnp

        idx = np.zeros((P, W), np.int32)
        skey = np.full((P, W), -(2 ** 31), np.int32)
        hsh = np.full((P, W), -1, np.int32)
        trunc = np.zeros(P, bool)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P_

            repl = NamedSharding(self.mesh, P_())
            return tuple(
                jax.device_put(a, repl) for a in (idx, skey, hsh, trunc)
            )
        return tuple(map(jnp.asarray, (idx, skey, hsh, trunc)))

    def commit(self, table, eroded) -> None:
        """Adopt the refreshed table + erosion flags the solve returned
        (the stale buffers were donated into the refresh off-CPU)."""
        self.table = table
        self.eroded_dev = eroded

    def drop(self) -> None:
        """Abandon the carry (next plan cold-builds).  The dispatch calls
        this when a warm solve raises between plan() and commit():
        plan() already consumed the invalidation accumulators and — off
        CPU — the solve donated the table buffers, so carrying on would
        pair a stale (or deleted) table with the new bucket order."""
        self._reset()

    def counters(self) -> Dict:
        return {
            "plans": self.plans,
            "cold_builds": self.cold_builds,
            "reranked_total": self.reranked_total,
            "changed_total": self.changed_total,
            "last": dict(self.last),
        }

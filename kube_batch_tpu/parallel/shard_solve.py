"""Explicit-collective shard_map solve bodies over the device mesh.

The pjit path (parallel/mesh.py) shards the node axis declaratively and
lets XLA's SPMD partitioner insert collectives — correct, but the
cross-host traffic is whatever GSPMD decides, and nothing bounds it as the
mesh grows to multi-host ICI+DCN.  This module rewrites the sharded solves
as ``shard_map`` bodies in which every cross-shard byte is AUTHORED:

- each shard computes its local block of the [T, N]-scale round head
  (feasibility, score, masked two-key argmax) over its node shard (and,
  on a 2-D ``(tasks, nodes)`` mesh, its task block);
- per round the shards reduce the TASK-SIZED winner vectors with explicit
  ``pmax``/``pmin``/``psum`` collectives (the two-key argmax decomposes
  into three O(T) reductions) — only O(tasks) crosses hosts per round,
  never O(tasks × nodes) or O(nodes);
- the node ledgers are all-gathered ONCE per solve (O(N·R) per cycle, not
  per round) so the conflict-resolution / gang-commit tail runs as
  replicated compute — literally the same :func:`ops.assignment.
  allocate_rounds` / :func:`ops.eviction.evict_rounds` machinery the
  single-device solve runs, which is what makes the shard_map path
  bit-exact against the pjit path by construction.

Collective inventory per allocate round (see utils/jitstats.
collective_inventory, which derives this from the traced program rather
than trusting this comment):

  pmax [T] f32   — global max score per task
  pmax [T] i32   — max tie-hash among max-score shards
  pmin [T] i32   — lowest global node index among (score, hash) ties
  psum [T] i32   — the winning shard contributes chose_idle
  (+ all_gather [T_blk] → [T] ×3 over the task axis when it is sharded)

Task-axis sharding (the second mesh dim): the [T, N] intermediates are
the HBM hogs at the 500k×50k north star (~2.5e10 elements); sharding the
task axis too divides them by the task-shard count.  The body slices its
task block out of the replicated task columns (no extra inputs), computes
[T_blk, N_loc] matrices, and reassembles the O(T) winner vectors with one
tiled ``all_gather`` per round over the task axis.  The replicated tail
is unchanged — its arrays are O(T) and O(N), never O(T × N).

Exactness notes (why bit-equal, not just equivalent):
- every [T_blk, N_loc] matrix element is computed by the same scalar
  expression as the corresponding element of the full matrix (the block
  view slices inputs; the tie-hash takes global offsets);
- the two-key argmax decomposition (max value → max hash among value
  ties → min global index among (value, hash) ties) reproduces
  ``jnp.argmax``'s first-max-index semantics exactly — integer and exact
  f32 comparisons only, no arithmetic on the reduced values;
- per-node accumulations (victim capacity) sum the same values in the
  same task order per node as the global program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kube_batch_tpu.ops import assignment as asg
from kube_batch_tpu.ops import eviction as evi
from kube_batch_tpu.ops.admission import gate_scan
from kube_batch_tpu.ops.feasibility import (
    FeasibilityMasks,
    failure_histogram,
    fits,
    static_predicates,
)
from kube_batch_tpu.ops.scoring import score_matrix

NEG = asg.NEG
BIG = jnp.int32(1 << 30)

# axis names live in parallel.mesh (shard_solve is imported lazily from
# there, so this import is acyclic at module load)
from kube_batch_tpu.parallel.mesh import NODE_AXIS, TASK_AXIS  # noqa: E402


def _axis_sizes(mesh):
    shape = dict(mesh.shape)
    return shape.get(TASK_AXIS, 1), shape[NODE_AXIS]


def _gather_tasks(x, task_shards):
    """Reassemble a [T_blk, ...] per-task-shard vector into the full [T]
    vector (tiled all_gather over the task axis; identity when the task
    axis is unsharded)."""
    if task_shards == 1:
        return x
    with jax.named_scope("xchip_gather_tasks"):
        return jax.lax.all_gather(x, TASK_AXIS, axis=0, tiled=True)


def _gather_nodes(x, node_shards):
    """One-per-solve reassembly of a node-sharded [N_loc, ...] column into
    the replicated global [N, ...] array the solve tail consumes."""
    if node_shards == 1:
        return x
    with jax.named_scope("xchip_gather_nodes"):
        return jax.lax.all_gather(x, NODE_AXIS, axis=0, tiled=True)


def _block_view(snap, t0, T_blk, task_shards):
    """``snap`` restricted to this shard's task block.  Node-axis arrays
    arrive shard-local under shard_map and pass through; task-axis arrays
    are sliced to [t0, t0+T_blk); the sparse affinity/preference row
    indices are remapped into block coordinates (out-of-block rows park at
    -1, which their consumers treat as padding).  Per-element math over
    the view equals the same elements of the global matrices — the
    bit-exactness contract of the SPMD round head."""
    if task_shards == 1:
        return snap
    ts = partial(jax.lax.dynamic_slice_in_dim, start_index=t0,
                 slice_size=T_blk, axis=0)
    aff = snap.task_aff_idx
    aff_l = jnp.where((aff >= t0) & (aff < t0 + T_blk), aff - t0, -1)
    pref = snap.task_pref_idx
    pref_l = jnp.where((pref >= t0) & (pref < t0 + T_blk), pref - t0, -1)
    return snap._replace(
        task_req=ts(snap.task_req),
        task_resreq=ts(snap.task_resreq),
        task_job=ts(snap.task_job),
        task_prio=ts(snap.task_prio),
        task_creation=ts(snap.task_creation),
        task_status=ts(snap.task_status),
        task_valid=ts(snap.task_valid),
        task_pending=ts(snap.task_pending),
        task_best_effort=ts(snap.task_best_effort),
        task_sel_bits=ts(snap.task_sel_bits),
        task_sel_impossible=ts(snap.task_sel_impossible),
        task_tol_bits=ts(snap.task_tol_bits),
        task_node=ts(snap.task_node),
        task_critical=ts(snap.task_critical),
        task_needs_host=ts(snap.task_needs_host),
        task_aff_idx=aff_l,
        task_pref_idx=pref_l,
    )


def _local_best(masked, tie_blk, n0):
    """Per-shard two-key winner triple: (lval, lkey, lidx_global) with the
    EXACT semantics of ops.assignment._best_node restricted to this block
    — max score, then max tie-hash among score ties, first index among
    (score, hash) ties (jnp.argmax first-max semantics)."""
    lval = jnp.max(masked, axis=1)
    cand = jnp.where(masked >= lval[:, None], tie_blk, -1)
    pick = jnp.argmax(cand, axis=1).astype(jnp.int32)
    lkey = jnp.max(cand, axis=1)
    return lval, lkey, pick, pick + n0


def _combine_best(lval, lkey, lidx, lextra=None):
    """The cross-shard two-key argmax as ONE stacked-payload collective.

    The first cut ran four DEPENDENT O(T) reductions per round — pmax
    value → pmax key among value ties → pmin global index among (value,
    key) ties → one-hot psum of the winner's extra — four cross-host
    latency hops on DCN.  Since the per-shard triple is tiny (3-4 i32
    rows of T), a single ``all_gather`` of the stacked payload followed by
    a replicated lexicographic reduce over the shard axis computes the
    same winner with ONE collective: the f32 value rides as its
    order-preserving i32 sort key (ops.assignment.f32_sort_key — integer
    compare ≡ float compare), so max-by-(value, key, −index) over the
    gathered [S, ·, T] block is exact.  Equivalent to jnp.argmax over the
    concatenated node axis, bit-for-bit (the pjit oracle and the
    equivalence tests hold it to that)."""
    from kube_batch_tpu.ops.assignment import f32_sort_key

    vkey = f32_sort_key(lval)
    parts = [vkey, lkey, lidx]
    if lextra is not None:
        parts.append(lextra)
    with jax.named_scope("xchip_argmax"):
        g = jax.lax.all_gather(
            jnp.stack(parts, axis=0), NODE_AXIS, axis=0, tiled=False
        )                                              # [S, 3|4, T]
    gv, gk, gi = g[:, 0], g[:, 1], g[:, 2]
    vmax_k = jnp.max(gv, axis=0)
    # the key map is a bijection, so the max key's preimage IS the max value
    vmax = _inv_sort_key(vmax_k)
    eq = gv == vmax_k
    kmax = jnp.max(jnp.where(eq, gk, jnp.asarray(-1, gk.dtype)), axis=0)
    eqk = eq & (gk == kmax)
    imin = jnp.min(jnp.where(eqk, gi, BIG), axis=0)
    if lextra is None:
        return vmax, imin
    win = eqk & (gi == imin)
    shard = jnp.argmax(win, axis=0)[None]              # [1, T]
    extra = jnp.take_along_axis(g[:, 3], shard, axis=0)[0]
    return vmax, imin, extra


def _inv_sort_key(k):
    """Inverse of ops.assignment.f32_sort_key (exact bijection)."""
    b = jnp.where(k < 0, k ^ jnp.int32(0x7FFFFFFF), k)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


# --------------------------------------------------------------------------
# allocate
# --------------------------------------------------------------------------


def _allocate_body(snap, *, config, node_shards, task_shards):
    N_loc = snap.node_idle.shape[0]
    T = snap.task_req.shape[0]
    T_blk = T // task_shards
    n0 = jax.lax.axis_index(NODE_AXIS) * N_loc
    t0 = (
        jax.lax.axis_index(TASK_AXIS) * T_blk if task_shards > 1
        else 0
    )
    view = _block_view(snap, t0, T_blk, task_shards)
    # the loop-invariant [T_blk, N_loc] blocks, computed once per solve
    static_ok = static_predicates(view)
    score = score_matrix(view, config.weights)
    score_static = jnp.where(static_ok, score, NEG)
    tie_blk = asg._tie_break_hash(T_blk, N_loc, t0=t0, n0=n0)
    req_blk = view.task_req
    quanta = snap.quanta

    def head(idle_g, releasing_g, pending):
        idle_b = jax.lax.dynamic_slice_in_dim(idle_g, n0, N_loc, axis=0)
        rel_b = jax.lax.dynamic_slice_in_dim(releasing_g, n0, N_loc, axis=0)
        pending_b = (
            pending if task_shards == 1
            else jax.lax.dynamic_slice_in_dim(pending, t0, T_blk, axis=0)
        )
        fit_idle = fits(req_blk, idle_b, quanta)
        # per-shard zero-releasing skip: exact for solver outputs (see
        # local_round_head), and finer-grained than the global test —
        # a shard with no releasing budget skips its block fit alone
        fit_rel = jax.lax.cond(
            jnp.any(rel_b > 0.0),
            lambda rel: fits(req_blk, rel, quanta),
            lambda rel: jnp.zeros_like(fit_idle),
            rel_b,
        )
        masked = jnp.where(
            (fit_idle | fit_rel) & pending_b[:, None], score_static, NEG
        )
        lval, lkey, pick, lidx = _local_best(masked, tie_blk, n0)
        lchose = jnp.take_along_axis(fit_idle, pick[:, None], axis=1)[:, 0]
        vmax, best_b, chose_b = _combine_best(
            lval, lkey, lidx, lchose.astype(jnp.int32)
        )
        best = _gather_tasks(best_b, task_shards)
        has = _gather_tasks(vmax > NEG, task_shards)
        chose = _gather_tasks(chose_b > 0, task_shards)
        return best, has, chose

    # the conflict/gang tail runs replicated on the explicitly gathered
    # ledgers — one O(N·R) all_gather per solve, zero per-round node bytes
    idle0 = _gather_nodes(snap.node_idle, node_shards)
    rel0 = _gather_nodes(snap.node_releasing, node_shards)
    used0 = _gather_nodes(snap.node_used, node_shards)
    res = asg.allocate_rounds(snap, config, head, idle0, rel0, used0)
    # emit the node ledgers as this shard's local blocks (out_specs
    # reassemble the node-sharded placement the pjit path produces)
    sl = partial(jax.lax.dynamic_slice_in_dim, start_index=n0,
                 slice_size=N_loc, axis=0)
    return res._replace(
        node_idle=sl(res.node_idle),
        node_releasing=sl(res.node_releasing),
        node_used=sl(res.node_used),
    )


# --------------------------------------------------------------------------
# compacted allocate (KB_TOPK) — zero per-round cross-shard collectives
# --------------------------------------------------------------------------


def _allocate_topk_body(snap, pend_rows, *, config, node_shards):
    """The compacted sharded solve: each shard ranks its local [P, N_loc]
    block into a [P, K] candidate list (exact lex order, global node
    indices, offset tie hash), the lists merge via ONE per-solve
    ``all_gather`` + replicated top-K merge, and the bidding rounds then
    run fully replicated on the merged table + the gathered ledgers — ZERO
    per-round cross-shard collectives (``collective_stats`` proves it from
    the traced program).  The exhaustion re-entry computes the full-matrix
    head over the bucket from per-solve-gathered node columns, so even the
    rare fallback rounds stay collective-free."""
    from kube_batch_tpu.ops import assignment as _asg

    N_loc = snap.node_idle.shape[0]
    N = N_loc * node_shards
    T = snap.task_req.shape[0]
    K = config.topk
    n0 = jax.lax.axis_index(NODE_AXIS) * N_loc
    quanta = snap.quanta
    P_rows = pend_rows.shape[0]

    # ---- local block build + single-gather merge ------------------------
    view_l = _asg.pend_view(snap, pend_rows)
    ki, ks, kh, n_feas_l, _ss, _tie = _asg.compact_candidates(
        view_l, pend_rows, snap.node_idle, snap.node_releasing, quanta,
        config, n0=n0,
    )
    payload = jnp.concatenate(
        [ks, kh, ki, n_feas_l[:, None]], axis=1
    )                                                  # [P, 3K+1] i32
    with jax.named_scope("xchip_topk_merge"):
        g = jax.lax.all_gather(payload, NODE_AXIS, axis=0, tiled=False)
    # shard-major concat: positions ascend with the global node index, so
    # the merge's first-position tie rule keeps jnp.argmax semantics
    skeys = jnp.transpose(g[:, :, 0:K], (1, 0, 2)).reshape(P_rows, -1)
    hashes = jnp.transpose(g[:, :, K:2 * K], (1, 0, 2)).reshape(P_rows, -1)
    idxs = jnp.transpose(g[:, :, 2 * K:3 * K], (1, 0, 2)).reshape(P_rows, -1)
    n_feas = jnp.sum(g[:, :, 3 * K], axis=0)
    mi, ms, mh = _asg.lex_topk(skeys, hashes, idxs, K, block=max(K, 8))
    truncated = n_feas > K

    # ---- per-solve gathers: ledgers + the fallback's node columns -------
    idle0 = _gather_nodes(snap.node_idle, node_shards)
    rel0 = _gather_nodes(snap.node_releasing, node_shards)
    used0 = _gather_nodes(snap.node_used, node_shards)

    def _gn(x):
        return _gather_nodes(x, node_shards)

    def _gn1(x):  # [K?, N_loc] sharded along axis 1
        if node_shards == 1:
            return x
        with jax.named_scope("xchip_gather_nodes"):
            return jax.lax.all_gather(x, NODE_AXIS, axis=1, tiled=True)

    snap_repl = snap._replace(
        node_idle=idle0, node_releasing=rel0, node_used=used0,
        node_alloc=_gn(snap.node_alloc), node_valid=_gn(snap.node_valid),
        node_sched=_gn(snap.node_sched),
        node_label_bits=_gn(snap.node_label_bits),
        node_taint_bits=_gn(snap.node_taint_bits),
        task_aff_mask=_gn1(snap.task_aff_mask),
        task_pref_node=_gn1(snap.task_pref_node),
        task_pref_pod=_gn1(snap.task_pref_pod),
    )
    view_repl = _asg.pend_view(snap_repl, pend_rows)
    safe_rows = jnp.maximum(pend_rows, 0)

    def fallback(idle, releasing, pending_exh):
        # traced inside the exhaustion cond — the [P, N] planes are only
        # computed in rounds that actually re-enter the full-matrix head
        static_ok = static_predicates(view_repl)
        score = score_matrix(view_repl, config.weights)
        ss = jnp.where(static_ok, score, NEG)
        tie = _asg.tie_break_hash_rows(
            safe_rows, jnp.arange(N, dtype=jnp.int32)
        )
        return _asg.make_bucket_fallback(view_repl, ss, tie, quanta)(
            idle, releasing, pending_exh
        )

    head = _asg.make_compact_head(
        mi, ms, mh, truncated, view_repl.task_req, quanta, N, fallback,
    )
    # rounds run replicated AND bucket-native: the rank/gate/conflict
    # machinery shrinks from [T] to [P] exactly like the single-device
    # compacted solve (scatter_bucket_result documents the exactness)
    res = _asg.allocate_rounds(
        view_repl, config, None, idle0, rel0, used0, compact_head=head
    )
    res = _asg.scatter_bucket_result(res, pend_rows, T)
    sl = partial(jax.lax.dynamic_slice_in_dim, start_index=n0,
                 slice_size=N_loc, axis=0)
    return res._replace(
        node_idle=sl(res.node_idle),
        node_releasing=sl(res.node_releasing),
        node_used=sl(res.node_used),
    )


def allocate_topk_shard_map(mesh, config):
    """jitted shard_map compacted allocate solve for (mesh, config) — the
    pending-row bucket rides replicated; node-axis inputs shard-local like
    the full solve.  Task-axis (2-D) meshes are not compacted — the
    dispatch routes them to the full path (their regime is the cold-start
    HBM escape, where the whole task axis is pending anyway)."""
    from kube_batch_tpu.ops.assignment import AllocateResult

    task_shards, node_shards = _axis_sizes(mesh)
    if task_shards != 1:
        raise ValueError("KB_TOPK compaction requires a 1-D node mesh")
    node2 = P(NODE_AXIS, None)
    out_specs = AllocateResult(
        assigned=P(), pipelined=P(), committed=P(),
        node_idle=node2, node_releasing=node2, node_used=node2,
        deserved=P(), rounds_run=P(),
        topk_exhausted=P(), topk_reentries=P(),
    )
    body = partial(_allocate_topk_body, config=config,
                   node_shards=node_shards)
    return _shard_map(body, mesh, (_snapshot_specs(mesh), P()), out_specs)


# --------------------------------------------------------------------------
# warm-started compacted allocate (KB_WARM) — cross-cycle table carry
# --------------------------------------------------------------------------


def _warm_allocate_body(snap, pend_rows, t_idx, t_skey, t_hash, t_trunc,
                        row_map, changed_nodes, rerank_rows, rerank_slots,
                        *, config, node_shards, k_min):
    """The sharded warm solve: the carried [P, W] table rides REPLICATED
    across cycles; per solve each shard contributes only delta-sized work —

    - the fresh keys of ITS OWN changed nodes (a [P, C] partial over the
      local node columns, merged with ONE psum: each changed node is owned
      by exactly one shard, so the masked-sum is the exact stacked value);
    - its local [Pi, W] candidate lists for the INVALIDATED sub-bucket,
      merged with one all_gather + replicated lex merge — the PR 10
      per-solve merge, now shipped only for the invalidated rows.

    Table refresh (permute / remove / θ-cut merge / re-rank scatter) and
    the bidding rounds run replicated on the merged state + the per-solve
    gathered ledgers, so the round loop keeps the compacted path's ZERO
    per-round cross-shard collectives."""
    from kube_batch_tpu.ops import assignment as _asg

    N_loc = snap.node_idle.shape[0]
    N = N_loc * node_shards
    T = snap.task_req.shape[0]
    W = config.topk
    n0 = jax.lax.axis_index(NODE_AXIS) * N_loc
    quanta = snap.quanta

    # ---- fresh changed-node keys over the [M] live prefix: per-shard
    # partial + one psum (each changed node is owned by exactly one shard)
    M = row_map.shape[0]
    rows_m = pend_rows[:M]
    view_lm = _asg.pend_view(snap, rows_m)
    loc = changed_nodes - n0
    own = (changed_nodes >= 0) & (loc >= 0) & (loc < N_loc)
    view_lc = _asg.node_view(view_lm, jnp.where(own, loc, -1))
    skey_part = _asg.fresh_block_skey(view_lc, quanta, config)
    with jax.named_scope("xchip_changed_keys"):
        skey_c = jax.lax.psum(
            jnp.where(own[None, :], skey_part, 0), NODE_AXIS
        )
    skey_c = jnp.where(
        (changed_nodes >= 0)[None, :], skey_c, _asg._I32_MIN
    )
    hash_c = _asg.tie_break_hash_rows(
        jnp.maximum(rows_m, 0), jnp.maximum(changed_nodes, 0)
    )

    # ---- invalidated sub-bucket: local build, gather, replicated merge --
    view_i = _asg.pend_view(snap, rerank_rows)
    ki, ks, kh, nf_l, _ss, _tie = _asg.compact_candidates(
        view_i, rerank_rows, snap.node_idle, snap.node_releasing, quanta,
        config, n0=n0,
    )
    Pi = rerank_rows.shape[0]
    payload = jnp.concatenate([ks, kh, ki, nf_l[:, None]], axis=1)
    with jax.named_scope("xchip_topk_merge"):
        g = jax.lax.all_gather(payload, NODE_AXIS, axis=0, tiled=False)
    skeys = jnp.transpose(g[:, :, 0:W], (1, 0, 2)).reshape(Pi, -1)
    hashes = jnp.transpose(g[:, :, W:2 * W], (1, 0, 2)).reshape(Pi, -1)
    idxs = jnp.transpose(g[:, :, 2 * W:3 * W], (1, 0, 2)).reshape(Pi, -1)
    n_feas = jnp.sum(g[:, :, 3 * W], axis=0)
    ri, rs, rh = _asg.lex_topk(skeys, hashes, idxs, W, block=max(W, 8))

    # ---- replicated table refresh + rounds ------------------------------
    ni, ns, nh, trunc, eroded = _asg.warm_refresh_table(
        t_idx, t_skey, t_hash, t_trunc, row_map, rows_m, changed_nodes,
        skey_c, hash_c, ri, rs, rh, n_feas > W, rerank_slots, N, k_min,
    )
    idle0 = _gather_nodes(snap.node_idle, node_shards)
    rel0 = _gather_nodes(snap.node_releasing, node_shards)
    used0 = _gather_nodes(snap.node_used, node_shards)

    def _gn(x):
        return _gather_nodes(x, node_shards)

    def _gn1(x):
        if node_shards == 1:
            return x
        with jax.named_scope("xchip_gather_nodes"):
            return jax.lax.all_gather(x, NODE_AXIS, axis=1, tiled=True)

    snap_repl = snap._replace(
        node_idle=idle0, node_releasing=rel0, node_used=used0,
        node_alloc=_gn(snap.node_alloc), node_valid=_gn(snap.node_valid),
        node_sched=_gn(snap.node_sched),
        node_label_bits=_gn(snap.node_label_bits),
        node_taint_bits=_gn(snap.node_taint_bits),
        task_aff_mask=_gn1(snap.task_aff_mask),
        task_pref_node=_gn1(snap.task_pref_node),
        task_pref_pod=_gn1(snap.task_pref_pod),
    )
    view_repl = _asg.pend_view(snap_repl, pend_rows)
    fallback = _asg.make_lazy_bucket_fallback(
        view_repl, pend_rows, quanta, config
    )
    head = _asg.make_compact_head(
        ni, ns, nh, trunc, view_repl.task_req, quanta, N, fallback,
    )
    res = _asg.allocate_rounds(
        view_repl, config, None, idle0, rel0, used0, compact_head=head
    )
    res = _asg.scatter_bucket_result(res, pend_rows, T)
    sl = partial(jax.lax.dynamic_slice_in_dim, start_index=n0,
                 slice_size=N_loc, axis=0)
    res = res._replace(
        node_idle=sl(res.node_idle),
        node_releasing=sl(res.node_releasing),
        node_used=sl(res.node_used),
    )
    return res, (ni, ns, nh, trunc), eroded


def warm_allocate_shard_map(mesh, config, k_min: int):
    """jitted shard_map warm-started compacted solve for (mesh, config,
    k_min) — the carried table and every plan array ride replicated; only
    the node-axis snapshot columns are shard-local.  Like the cold
    compacted path, a 2-D task-sharded mesh declines (the dispatch never
    routes it here)."""
    from kube_batch_tpu.ops.assignment import AllocateResult

    task_shards, node_shards = _axis_sizes(mesh)
    if task_shards != 1:
        raise ValueError("KB_WARM carry requires a 1-D node mesh")
    node2 = P(NODE_AXIS, None)
    res_specs = AllocateResult(
        assigned=P(), pipelined=P(), committed=P(),
        node_idle=node2, node_releasing=node2, node_used=node2,
        deserved=P(), rounds_run=P(),
        topk_exhausted=P(), topk_reentries=P(),
    )
    out_specs = (res_specs, (P(), P(), P(), P()), P())
    body = partial(_warm_allocate_body, config=config,
                   node_shards=node_shards, k_min=k_min)
    in_specs = (_snapshot_specs(mesh),) + (P(),) * 9
    return _shard_map(body, mesh, in_specs, out_specs)


# --------------------------------------------------------------------------
# evict (reclaim / preempt)
# --------------------------------------------------------------------------


def _evict_body(snap, *, config, node_shards, task_shards):
    N_loc = snap.node_alloc.shape[0]
    N = N_loc * node_shards
    T = snap.task_req.shape[0]
    T_blk = T // task_shards
    R = snap.task_req.shape[1]
    Q = snap.queue_weight.shape[0]
    preempt = config.mode == "preempt"
    n0 = jax.lax.axis_index(NODE_AXIS) * N_loc
    t0 = (
        jax.lax.axis_index(TASK_AXIS) * T_blk if task_shards > 1
        else 0
    )
    view = _block_view(snap, t0, T_blk, task_shards)

    def make_bids():
        # the block head, built where evict_rounds calls for it: inside the
        # branch a solve that ends at its gates does not take (the
        # predicate is replicated, so every shard takes the same one)
        static_ok = static_predicates(view)
        score = score_matrix(view, config.weights)
        tie_blk = asg._tie_break_hash(T_blk, N_loc, t0=t0, n0=n0)
        task_queue = snap.job_queue[snap.task_job]          # [T] replicated
        tq_blk = view.job_queue[view.task_job]              # [T_blk]

        def tslice(x):
            if task_shards == 1:
                return x
            return jax.lax.dynamic_slice_in_dim(x, t0, T_blk, axis=0)

        def bids(victim_ok, claimant_ok):
            # ---- per-(queue, local-node) evictable capacity --------------
            # built from the REPLICATED task vectors, restricted to victims
            # resident on this shard's nodes: same values in the same task
            # order per (queue, node) cell as the global scatter
            vreq = jnp.where(victim_ok[:, None], snap.task_resreq, 0.0)
            vnode_l = snap.task_node - n0
            in_shard = (vnode_l >= 0) & (vnode_l < N_loc)
            vreq_l = jnp.where(in_shard[:, None], vreq, 0.0)
            tot_v = jax.ops.segment_sum(
                vreq_l,
                jnp.where(victim_ok & in_shard, vnode_l, N_loc),
                num_segments=N_loc + 1,
            )[:N_loc]                                        # [N_loc, R]
            per_qn = jnp.zeros((Q, N_loc, R), jnp.float32).at[
                task_queue, jnp.clip(vnode_l, 0, N_loc - 1)
            ].add(vreq_l)
            if preempt:
                cap = per_qn                  # same-queue victims
            else:
                cap = tot_v[None] - per_qn    # cross-queue victims

            # ---- block bids (one-hot queue gather, exact f32 matmul) -----
            co_b = tslice(claimant_ok)
            onehot_q = (tq_blk[:, None] == jnp.arange(Q)[None, :]).astype(
                jnp.float32
            )
            feas = static_ok & co_b[:, None]
            feas &= ((tq_blk >= 0) & (tq_blk < Q))[:, None]
            for r in range(R):
                # kbt: allow[KBT005] trace-time unroll over the small static
                # resource dim R inside jit (same rationale as the single path)
                cap_tr = jnp.matmul(
                    onehot_q, cap[:, :, r], precision=jax.lax.Precision.HIGHEST
                )                                            # [T_blk, N_loc]
                feas &= view.task_req[:, r, None] <= cap_tr + snap.quanta[r]
            masked = jnp.where(feas, score, NEG)
            lval, lkey, _pick, lidx = _local_best(masked, tie_blk, n0)
            vmax, best_b = _combine_best(lval, lkey, lidx)
            best = _gather_tasks(best_b, task_shards)
            has = _gather_tasks(vmax > NEG, task_shards)
            return best, has

        return bids

    room = None
    if evi.gates_on(config):
        room_l = evi.gate_room_local(
            view.task_req, static_predicates(view), snap, config)
        with jax.named_scope("xchip_any_bid"):
            room_g = jax.lax.psum(room_l, NODE_AXIS)
        room = _gather_tasks(room_g, task_shards)
    return evi.evict_rounds(snap, config, make_bids, room, n_nodes=N)


# --------------------------------------------------------------------------
# fit-error histogram
# --------------------------------------------------------------------------


def _histogram_body(snap, *, node_shards, task_shards):
    T = snap.task_req.shape[0]
    T_blk = T // task_shards
    t0 = (
        jax.lax.axis_index(TASK_AXIS) * T_blk if task_shards > 1
        else 0
    )
    view = _block_view(snap, t0, T_blk, task_shards)
    static_ok = static_predicates(view)
    fit_i = fits(view.task_req, snap.node_idle, snap.quanta)
    fit_r = fits(view.task_req, snap.node_releasing, snap.quanta)
    h = failure_histogram(
        view,
        FeasibilityMasks(static_ok, fit_i, fit_r,
                         static_ok & (fit_i | fit_r)),
    )
    # every histogram column is an integer count over nodes — one exact
    # O(T × N_REASONS) psum reduces the per-shard partial counts
    with jax.named_scope("xchip_histogram"):
        h = jax.lax.psum(h, NODE_AXIS)
    return _gather_tasks(h, task_shards)


def _histogram_bucket_body(snap, pend_rows, *, node_shards):
    """The fit-error histogram on the [P] pending bucket: per-shard
    [P, N_loc] partial counts, one psum, scattered back to the [T] task
    axis (the compacted-allocate bucket idiom applied to the histogram —
    every consumer reads rows only for unplaced PENDING tasks, all of
    which the bucket covers)."""
    from kube_batch_tpu.ops import assignment as _asg
    from kube_batch_tpu.ops.feasibility import N_REASONS

    T = snap.task_req.shape[0]
    view = _asg.pend_view(snap, pend_rows)
    static_ok = static_predicates(view)
    fit_i = fits(view.task_req, snap.node_idle, snap.quanta)
    fit_r = fits(view.task_req, snap.node_releasing, snap.quanta)
    h = failure_histogram(
        view,
        FeasibilityMasks(static_ok, fit_i, fit_r,
                         static_ok & (fit_i | fit_r)),
    )
    with jax.named_scope("xchip_histogram"):
        h = jax.lax.psum(h, NODE_AXIS)
    scat = jnp.where(pend_rows >= 0, pend_rows, T)
    return jnp.zeros((T + 1, N_REASONS), jnp.int32).at[scat].set(h)[:T]


# --------------------------------------------------------------------------
# builders — jitted shard_map wrappers (memoized by parallel.mesh)
# --------------------------------------------------------------------------


def _shard_map(body, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _snapshot_specs(mesh):
    from kube_batch_tpu.parallel.mesh import snapshot_shardings

    return jax.tree.map(lambda s: s.spec, snapshot_shardings(mesh))


def allocate_shard_map(mesh, config):
    """jitted shard_map allocate solve for (mesh, config) — node-axis
    inputs consumed shard-local, task/job/queue inputs replicated, node
    ledgers emitted node-sharded, task vectors replicated."""
    from kube_batch_tpu.ops.assignment import AllocateResult

    task_shards, node_shards = _axis_sizes(mesh)
    node2 = P(NODE_AXIS, None)
    out_specs = AllocateResult(
        assigned=P(), pipelined=P(), committed=P(),
        node_idle=node2, node_releasing=node2, node_used=node2,
        deserved=P(), rounds_run=P(),
        topk_exhausted=P(), topk_reentries=P(),
    )
    body = partial(_allocate_body, config=config,
                   node_shards=node_shards, task_shards=task_shards)
    return _shard_map(body, mesh, (_snapshot_specs(mesh),), out_specs)


def evict_shard_map(mesh, config):
    """jitted shard_map eviction solve — every EvictResult field is
    task-axis, so all outputs replicate."""
    from kube_batch_tpu.ops.eviction import EvictResult

    task_shards, node_shards = _axis_sizes(mesh)
    out_specs = EvictResult(
        claim_node=P(), evicted=P(), victim_claimant=P(), rounds_run=P(),
        gated_releasing=P(),
    )
    body = partial(_evict_body, config=config,
                   node_shards=node_shards, task_shards=task_shards)
    return _shard_map(body, mesh, (_snapshot_specs(mesh),), out_specs)


def failure_histogram_shard_map(mesh):
    """jitted shard_map fit-error histogram: per-shard partial counts, one
    psum over the node shards, replicated [T, N_REASONS] out."""
    task_shards, node_shards = _axis_sizes(mesh)
    body = partial(_histogram_body,
                   node_shards=node_shards, task_shards=task_shards)
    return _shard_map(body, mesh, (_snapshot_specs(mesh),), P())


def failure_histogram_bucket_shard_map(mesh):
    """jitted shard_map BUCKETED fit-error histogram (the [P] pending
    bucket instead of [T, N] — dispatched whenever the compacted allocate
    planned a bucket this cycle; 1-D node meshes only, like the compacted
    solve itself)."""
    task_shards, node_shards = _axis_sizes(mesh)
    if task_shards != 1:
        raise ValueError("bucketed histogram requires a 1-D node mesh")
    body = partial(_histogram_bucket_body, node_shards=node_shards)
    return _shard_map(body, mesh, (_snapshot_specs(mesh), P()), P())


def _probe_body(snap, batch, probe_rows, *, config, evict_config,
                with_evictions, node_shards):
    """The shard_map what-if probe (ops/probe.py): each shard computes the
    [G, N_loc] blocks — gang-view static predicates, scores, per-round
    fits, eviction bids, fit-error histogram partials — and the gang-sized
    winner vectors reduce with the SAME two-key pargmax decomposition the
    sharded allocate solve uses.  Everything downstream of the blocks is
    :func:`ops.probe.probe_gang_core`, verbatim — the bit-exactness story
    is the one the solves already proved.

    The task axis of a 2-D mesh is untouched (a gang's G rows are tiny);
    on such meshes every task-shard row computes identical replicated
    results with zero task-axis collectives."""
    from kube_batch_tpu.ops import probe as prb

    N_loc = snap.node_idle.shape[0]
    N = N_loc * node_shards
    n0 = jax.lax.axis_index(NODE_AXIS) * N_loc
    # the replicated ledgers for the allocate-rounds tail: one O(N·R)
    # all_gather per DISPATCH (not per gang — hoisted out of the vmap),
    # mirroring the sharded allocate body's once-per-solve gather
    idle0 = _gather_nodes(snap.node_idle, node_shards)
    rel0 = _gather_nodes(snap.node_releasing, node_shards)
    used0 = _gather_nodes(snap.node_used, node_shards)
    # admission budget: local used-sum + one O(R) psum
    used_l = jnp.sum(
        jnp.where(snap.node_valid[:, None], snap.node_used, 0.0), axis=0
    )
    with jax.named_scope("xchip_used_sum"):
        used = jax.lax.psum(used_l, NODE_AXIS)
    oc_idle = jnp.maximum(snap.total * prb.OVERCOMMIT_FACTOR - used, 0.0)

    def one(g):
        view = prb._gang_view(
            snap, g.req, g.valid, g.min_avail, g.queue, g.prio,
            g.sel_bits, g.sel_impossible, g.tol_bits,
        )
        static_ok = static_predicates(view)            # [G, N_loc]
        score = score_matrix(view, config.weights)
        score_static = jnp.where(static_ok, score, NEG)
        tie_blk = asg.tie_break_hash_rows(
            probe_rows, jnp.arange(N_loc, dtype=jnp.int32) + n0
        )

        def head(idle_g, releasing_g, pending):
            idle_b = jax.lax.dynamic_slice_in_dim(idle_g, n0, N_loc, axis=0)
            rel_b = jax.lax.dynamic_slice_in_dim(
                releasing_g, n0, N_loc, axis=0
            )
            fit_idle = fits(view.task_req, idle_b, snap.quanta)
            fit_rel = jax.lax.cond(
                jnp.any(rel_b > 0.0),
                lambda rel: fits(view.task_req, rel, snap.quanta),
                lambda rel: jnp.zeros_like(fit_idle),
                rel_b,
            )
            masked = jnp.where(
                (fit_idle | fit_rel) & pending[:, None], score_static, NEG
            )
            lval, lkey, pick, lidx = _local_best(masked, tie_blk, n0)
            lchose = jnp.take_along_axis(fit_idle, pick[:, None], axis=1)[:, 0]
            vmax, best, chose = _combine_best(
                lval, lkey, lidx, lchose.astype(jnp.int32)
            )
            return best, vmax > NEG, chose > 0

        def bid_fn(claimant_ok, cap):
            cap_b = jax.lax.dynamic_slice_in_dim(cap, n0, N_loc, axis=0)
            feas = static_ok & claimant_ok[:, None]
            feas &= jnp.all(
                g.req[:, None, :] <= cap_b[None, :, :] + snap.quanta, axis=-1
            )
            masked = jnp.where(feas, score, NEG)
            lval, lkey, _pick, lidx = _local_best(masked, tie_blk, n0)
            vmax, best = _combine_best(lval, lkey, lidx)
            return best, vmax > NEG

        def hist_fn():
            fit_idle0 = fits(view.task_req, snap.node_idle, snap.quanta)
            fit_rel0 = fits(view.task_req, snap.node_releasing, snap.quanta)
            h = failure_histogram(
                view,
                FeasibilityMasks(
                    static_ok, fit_idle0, fit_rel0,
                    static_ok & (fit_idle0 | fit_rel0),
                ),
            )
            # every histogram column is an integer count over nodes — one
            # exact psum reduces the per-shard partials (same argument as
            # the sharded failure-histogram solve)
            with jax.named_scope("xchip_histogram"):
                return jax.lax.psum(h, NODE_AXIS)

        return prb.probe_gang_core(
            snap, view, g, config, evict_config, with_evictions,
            head=head, bid_fn=bid_fn, hist_fn=hist_fn, oc_idle=oc_idle,
            idle0=idle0, rel0=rel0, used0=used0, n_nodes=N,
        )

    return jax.vmap(one)(batch)


def probe_shard_map(mesh, config, evict_config, with_evictions):
    """jitted shard_map what-if probe for (mesh, config, evict_config,
    with_evictions) — node-axis snapshot columns consumed shard-local, the
    probe batch and row oracle replicated, every ProbeResult field
    replicated (all are B/G/T-axis)."""
    from kube_batch_tpu.ops.probe import ProbeBatch, ProbeResult

    _task_shards, node_shards = _axis_sizes(mesh)
    repl = P()
    batch_specs = ProbeBatch(*([repl] * len(ProbeBatch._fields)))
    out_specs = ProbeResult(*([repl] * len(ProbeResult._fields)))
    body = partial(_probe_body, config=config, evict_config=evict_config,
                   with_evictions=with_evictions, node_shards=node_shards)
    return _shard_map(
        body, mesh, (_snapshot_specs(mesh), batch_specs, repl), out_specs
    )


def enqueue_gate_shard_map(mesh):
    """jitted mesh-replicated enqueue admission scan: the scan is
    sequentially dependent (each admission shrinks the idle the next
    candidate sees), so it cannot decompose across shards — instead every
    device runs the identical ``gate_scan`` program on replicated inputs
    and ZERO bytes cross shards.  The point on a multi-host mesh is
    placement consistency: every process computes the same admitted mask
    from the same replicated operands, so the multi-controller cycle never
    diverges on admission."""
    repl = P()
    return _shard_map(
        gate_scan, mesh,
        (repl, repl, repl, repl), repl,
    )

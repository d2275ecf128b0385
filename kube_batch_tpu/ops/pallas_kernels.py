"""Pallas TPU kernels for the auction round's hot op.

`masked_best_node` fuses the per-round feasibility test + score masking +
two-key tie-broken argmax (ops/assignment.py round_body's first half) into
VMEM-tiled passes: the [T, N] fit matrices are never materialized in HBM —
req/idle/releasing live in VMEM and the fit predicate is computed on the fly
per (task, node) tile; only the score and static-predicate matrices stream
in, and three [T]-shaped vectors stream out.

Round-3 change: the node axis is TILED too (grid (T/TM, N/TN)) with the
argmax carried across node tiles through revisited output blocks — the
round-2 kernel put the whole node axis (5 120 wide at the bench shape) in
one block, and that single-block layout was what pushed the Mosaic compile
past 10 minutes; with both axes tiled the kernel compiles in seconds at
50k×5k.  The cross-tile merge is the exact two-key order: strictly greater
score wins, equal score resolves by the tie hash, equal (score, hash) keeps
the earlier tile — reproducing jnp.argmax's first-max-index semantics.

The XLA path computes the same values with fused broadcasts; this kernel
exists to cut the intermediate [T, N] bool traffic on real TPU. It is
opt-in (AllocateConfig.use_pallas, wired to env KB_PALLAS=1 / the
`allocate.pallas` conf argument by the allocate action) and runs in
interpret mode on the CPU backend only, so the parity tests run there
(``interpret_mode``).

TPU lowering constraints shape the kernel: everything is float32 or int32
(no uint32, no bool refs — the Mosaic lowering in this jax version supports
neither), and every ref is ≥2-D (1-D refs mis-tile). Masks travel as f32
0/1 and outputs are (T, 1) columns squeezed by the wrapper.

Reference semantics carried over: epsilon-tolerant fit (resource_info.go:
269-284 LessEqual), SelectBestNode's uniform tie-break among max-score nodes
(scheduler_helper.go:147-158) via the same per-(task, node) int32 hash as
ops/assignment._tie_break_hash.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# plain Python float — a jnp scalar would be a captured constant, which
# pallas_call rejects
NEG = -3.0e38

TASK_TILE = 256
NODE_TILE = 512


def _kernel(score_ref, static_ref, req_ref, idle_ref, rel_ref, pending_ref,
            quanta_ref, offs_ref, best_ref, val_ref, hash_ref,
            chose_idle_ref):
    TM = score_ref.shape[0]
    TN = score_ref.shape[1]
    R = req_ref.shape[1]
    j = pl.program_id(1)

    req = req_ref[:]                      # [TM, R]
    quanta = quanta_ref[:]                # [1, R]

    # fit[t, n] = all_r req[t, r] <= budget[n, r] + quanta[r]  (tolerant
    # LessEqual); R is tiny and static — unrolled, no [TM, TN, R] tensor
    def fit_matrix(budget_ref):
        fit = None
        for r in range(R):
            f = req[:, r][:, None] <= budget_ref[:, r][None, :] + quanta[0, r]
            fit = f if fit is None else (fit & f)
        return fit

    fit_idle = fit_matrix(idle_ref)
    fit_rel = fit_matrix(rel_ref)
    pending = pending_ref[:] > 0.0        # [TM, 1] f32 0/1 → bool
    feas = (static_ref[:] > 0.0) & (fit_idle | fit_rel) & pending
    masked = jnp.where(feas, score_ref[:], NEG)

    # two-key argmax within this node tile: exact max score, then the
    # per-(task, node) hash among ties (ops/assignment._tie_break_hash —
    # same constants, same int32 wrapping arithmetic).  offs_ref carries
    # the (task, node) GLOBAL offsets of this invocation's matrix block —
    # zero on the single-program path; the shard_map round head passes its
    # shard's origin so the hash (and therefore every tie-break) matches
    # the full-matrix program bit-for-bit
    from kube_batch_tpu.ops.assignment import _H1, _H2, _H3

    ti = (
        jax.lax.broadcasted_iota(jnp.int32, (TM, TN), 0)
        + pl.program_id(0) * TM + offs_ref[0, 0]
    )
    ni = (
        jax.lax.broadcasted_iota(jnp.int32, (TM, TN), 1)
        + j * TN + offs_ref[0, 1]
    )
    h = ti * jnp.int32(_H1) + ni * jnp.int32(_H2)
    h = (h ^ jax.lax.shift_right_logical(h, jnp.int32(15))) * jnp.int32(_H3)
    # the hash rides f32 like the score key it is merged with; its 16 bits
    # are exactly representable, so the cast preserves the ordering
    tie_hash = jax.lax.shift_right_logical(h, jnp.int32(16)).astype(jnp.float32)

    lval = jnp.max(masked, axis=1)                            # [TM]
    tie = masked >= lval[:, None]
    hash_masked = jnp.where(tie, tie_hash, -1.0)
    lhash = jnp.max(hash_masked, axis=1)                      # [TM]
    # the first column among full (score, hash) ties, spelled as a min
    # over the tied columns and NOT as jnp.argmax: compiled by Mosaic,
    # argmax returns a later tied column (measured on a v5e, ~0.2% of rows
    # at 50k×5k), and first-max-index is the order the XLA path and the
    # cross-tile merge below both keep
    col = jax.lax.broadcasted_iota(jnp.int32, (TM, TN), 1)
    pick = jnp.min(
        jnp.where(hash_masked >= lhash[:, None], col, TN), axis=1
    )                                                         # local col
    lbest = pick + j * TN
    lchose = jnp.any(fit_idle & (col == pick[:, None]), axis=1)
    lval_c = lval[:, None]
    lhash_c = lhash[:, None]
    lbest_c = lbest[:, None]
    # bool→f32 cast, not jnp.where(_, 1.0, 0.0): two weak Python floats
    # promote to the DEFAULT float dtype — an f64 upcast the moment x64 is
    # on (caught by the jaxpr audit, KBT101)
    lchose_c = lchose.astype(jnp.float32)[:, None]

    # cross-tile merge through the revisited output blocks (the node-tile
    # grid axis iterates sequentially on TPU): strictly-better (val, hash)
    # replaces; ties keep the earlier tile = first-max-index semantics
    @pl.when(j == 0)
    def _init():
        best_ref[:] = lbest_c
        val_ref[:] = lval_c
        hash_ref[:] = lhash_c
        chose_idle_ref[:] = lchose_c

    @pl.when(j > 0)
    def _merge():
        pval = val_ref[:]
        phash = hash_ref[:]
        better = (lval_c > pval) | ((lval_c == pval) & (lhash_c > phash))
        best_ref[:] = jnp.where(better, lbest_c, best_ref[:])
        val_ref[:] = jnp.where(better, lval_c, pval)
        hash_ref[:] = jnp.where(better, lhash_c, phash)
        chose_idle_ref[:] = jnp.where(better, lchose_c, chose_idle_ref[:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_best_node_raw(
    score: jnp.ndarray,       # [T, N] f32
    static_ok: jnp.ndarray,   # [T, N] bool
    task_req: jnp.ndarray,    # [T, R] f32 — InitResreq
    idle: jnp.ndarray,        # [N, R] f32
    releasing: jnp.ndarray,   # [N, R] f32
    pending: jnp.ndarray,     # [T] bool
    quanta: jnp.ndarray,      # [R] f32
    t0=0,                     # global task offset of this block (i32)
    n0=0,                     # global node offset of this block (i32)
    interpret: bool = False,
):
    """(best [T] i32, val [T] f32, hash [T] f32, chose_idle [T] bool) — the
    fused round head with the winner's (score, tie-hash) key exposed.  The
    shard_map head needs the raw key to run the cross-shard two-key argmax
    reduction; ``t0``/``n0`` are the block's global matrix origin (the
    tie-hash is a function of GLOBAL coordinates).  T must be a multiple of
    the task tile and N of the node tile (snapshot buckets guarantee both
    at scale; callers pad otherwise).  ``best`` stays block-local (callers
    add their node offset)."""
    T, N = score.shape
    R = task_req.shape[1]
    tile_t = min(TASK_TILE, T)
    tile_n = min(NODE_TILE, N)
    grid = (T // tile_t, N // tile_n)
    q2 = quanta.reshape(1, R).astype(jnp.float32)
    offs = jnp.asarray([t0, n0], jnp.int32).reshape(1, 2)

    best, val, hsh, chose = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_t, tile_n), lambda i, j: (i, j)),  # score
            pl.BlockSpec((tile_t, tile_n), lambda i, j: (i, j)),  # static_ok
            pl.BlockSpec((tile_t, R), lambda i, j: (i, 0)),       # req
            pl.BlockSpec((tile_n, R), lambda i, j: (j, 0)),       # idle
            pl.BlockSpec((tile_n, R), lambda i, j: (j, 0)),       # releasing
            pl.BlockSpec((tile_t, 1), lambda i, j: (i, 0)),       # pending
            pl.BlockSpec((1, R), lambda i, j: (0, 0)),            # quanta
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),            # offsets
        ],
        out_specs=[
            pl.BlockSpec((tile_t, 1), lambda i, j: (i, 0)),       # best
            pl.BlockSpec((tile_t, 1), lambda i, j: (i, 0)),       # val
            pl.BlockSpec((tile_t, 1), lambda i, j: (i, 0)),       # hash
            pl.BlockSpec((tile_t, 1), lambda i, j: (i, 0)),       # chose_idle
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        score.astype(jnp.float32),
        static_ok.astype(jnp.float32),
        task_req.astype(jnp.float32),
        idle.astype(jnp.float32),
        releasing.astype(jnp.float32),
        pending.astype(jnp.float32)[:, None],
        q2,
        offs,
    )
    return best[:, 0], val[:, 0], hsh[:, 0], chose[:, 0] > 0.0


# --------------------------------------------------------------------------
# top-K candidate build (ops/assignment.py's KB_TOPK compaction)
# --------------------------------------------------------------------------

#: sub-block width of the emitted per-block winner triples — must divide
#: NODE_TILE; the XLA-side extraction (ops.assignment.lex_topk) defaults to
#: the same block width, so the kernel's partials line up with its grid
TOPK_BLOCK = 64


def _topk_kernel(score_ref, req_ref, idle_ref, rel_ref, rows_ref,
                 quanta_ref, offs_ref, skey_ref, bval_ref, bhash_ref,
                 bcol_ref):
    TM = score_ref.shape[0]
    TN = score_ref.shape[1]
    R = req_ref.shape[1]
    C = TOPK_BLOCK
    NB = TN // C
    j = pl.program_id(1)

    req = req_ref[:]
    quanta = quanta_ref[:]

    def fit_matrix(budget_ref):
        fit = None
        for r in range(R):
            f = req[:, r][:, None] <= budget_ref[:, r][None, :] + quanta[0, r]
            fit = f if fit is None else (fit & f)
        return fit

    # the build-time masked key plane: score_static where the node fits the
    # CYCLE-START budgets, NEG otherwise, as the order-preserving i32 sort
    # key (ops.assignment.f32_sort_key — same bit trick, Mosaic-safe)
    feas = fit_matrix(idle_ref) | fit_matrix(rel_ref)
    masked = jnp.where(feas, score_ref[:], NEG)
    # + 0.0 canonicalizes -0.0 (exact identity otherwise) — must match
    # ops.assignment.f32_sort_key bit-for-bit
    bits = jax.lax.bitcast_convert_type(masked + 0.0, jnp.int32)
    skey = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    skey_ref[:] = skey

    # the tie hash at GLOBAL (task-row, node) coordinates: task rows come
    # from an explicit per-row index ref (the pending bucket's rows are
    # scattered, not an arange block), node columns from the tile offset
    from kube_batch_tpu.ops.assignment import _H1, _H2, _H3

    ti = jnp.broadcast_to(rows_ref[:], (TM, TN))
    ni = (
        jax.lax.broadcasted_iota(jnp.int32, (TM, TN), 1)
        + j * TN + offs_ref[0, 0]
    )
    h = ti * jnp.int32(_H1) + ni * jnp.int32(_H2)
    h = (h ^ jax.lax.shift_right_logical(h, jnp.int32(15))) * jnp.int32(_H3)
    tie_hash = jax.lax.shift_right_logical(h, jnp.int32(16))

    # per-C-block two-key winner triples (the extraction's phase-1 input):
    # max key, max hash among key ties, first column among full ties
    # trace-time unroll over the static sub-block count (NODE_TILE /
    # TOPK_BLOCK = 8) inside the kernel body — no per-iteration dispatch;
    # the first column among full ties is a min over the tied columns, not
    # jnp.argmax (see the round head: compiled argmax picks a later one)
    cb = jax.lax.broadcasted_iota(jnp.int32, (TM, C), 1)
    for b in range(NB):
        sb = skey[:, b * C:(b + 1) * C]
        hb = tie_hash[:, b * C:(b + 1) * C]
        # kbt: allow[KBT005] static in-kernel unroll (see loop comment)
        bval = jnp.max(sb, axis=1)
        tie = sb >= bval[:, None]
        # kbt: allow[KBT005] static in-kernel unroll (see loop comment)
        hmask = jnp.where(tie, hb, -2)
        # kbt: allow[KBT005] static in-kernel unroll (see loop comment)
        bhash = jnp.max(hmask, axis=1)
        # kbt: allow[KBT005] static in-kernel unroll (see loop comment)
        bcol = jnp.min(jnp.where(hmask >= bhash[:, None], cb, C), axis=1)
        bval_ref[:, b:b + 1] = bval[:, None]
        bhash_ref[:, b:b + 1] = bhash[:, None]
        bcol_ref[:, b:b + 1] = bcol[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_topk_blocks(
    score_static: jnp.ndarray,  # [P, N] f32 — statics already folded (NEG)
    task_req: jnp.ndarray,      # [P, R] f32 — InitResreq of the bucket rows
    idle: jnp.ndarray,          # [N, R] f32 — cycle-start budgets
    releasing: jnp.ndarray,     # [N, R] f32
    rows: jnp.ndarray,          # [P] i32 — GLOBAL task row per bucket slot
    quanta: jnp.ndarray,        # [R] f32
    n0=0,                       # global node offset of this block (i32)
    interpret: bool = False,
):
    """The fused candidate-build head for the KB_TOPK compaction: one VMEM
    pass emits the masked sort-key plane ``skey`` [P, N] i32 plus the
    per-``TOPK_BLOCK`` two-key winner triples (``bval``/``bhash``/``bcol``
    [P, N/TOPK_BLOCK]) without materializing the fit matrices in HBM.  The
    XLA extraction loop (ops.assignment.lex_topk) consumes ``skey``; the
    triples prove the kernel computes the exact phase-1 reduction (the
    parity test cross-checks them).  P must be a multiple of the task tile
    and N of the node tile, like the round-head kernel."""
    P, N = score_static.shape
    R = task_req.shape[1]
    tile_t = min(TASK_TILE, P)
    tile_n = min(NODE_TILE, N)
    grid = (P // tile_t, N // tile_n)
    NB = tile_n // TOPK_BLOCK
    q2 = quanta.reshape(1, R).astype(jnp.float32)
    offs = jnp.asarray([n0], jnp.int32).reshape(1, 1)

    # the triples leave as [node tile, P, NB] and are laid out [P, N/C]
    # below: an (tile_t, NB) block of a [P, N/C] array is one Mosaic
    # refuses (NB = 8 lanes is neither 128-divisible nor the full width),
    # while here NB is the array's whole last dimension
    triple = pl.BlockSpec((None, tile_t, NB), lambda i, j: (j, i, 0))
    triple_shape = jax.ShapeDtypeStruct((N // tile_n, P, NB), jnp.int32)
    skey, bval, bhash, bcol = pl.pallas_call(
        _topk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_t, tile_n), lambda i, j: (i, j)),  # score
            pl.BlockSpec((tile_t, R), lambda i, j: (i, 0)),       # req
            pl.BlockSpec((tile_n, R), lambda i, j: (j, 0)),       # idle
            pl.BlockSpec((tile_n, R), lambda i, j: (j, 0)),       # releasing
            pl.BlockSpec((tile_t, 1), lambda i, j: (i, 0)),       # rows
            pl.BlockSpec((1, R), lambda i, j: (0, 0)),            # quanta
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),            # offsets
        ],
        out_specs=[
            pl.BlockSpec((tile_t, tile_n), lambda i, j: (i, j)),  # skey
            triple,                                               # bval
            triple,                                               # bhash
            triple,                                               # bcol
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, N), jnp.int32),
            triple_shape, triple_shape, triple_shape,
        ],
        interpret=interpret,
    )(
        score_static.astype(jnp.float32),
        task_req.astype(jnp.float32),
        idle.astype(jnp.float32),
        releasing.astype(jnp.float32),
        rows.astype(jnp.int32)[:, None],
        q2,
        offs,
    )
    bval, bhash, bcol = (
        x.transpose(1, 0, 2).reshape(P, N // TOPK_BLOCK)
        for x in (bval, bhash, bcol)
    )
    return skey, bval, bhash, bcol


def interpret_mode() -> bool:
    """The ``interpret`` argument every production call site passes: the
    kernels compile on a TPU — or fail there with the compiler's message —
    and are interpreted only on the CPU backend, where the parity tests
    run.  Any other platform is refused rather than quietly interpreted."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels are written for TPU (and interpreted on "
            f"cpu for tests); platform {backend!r} is not supported")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_best_node(
    score: jnp.ndarray,       # [T, N] f32
    static_ok: jnp.ndarray,   # [T, N] bool
    task_req: jnp.ndarray,    # [T, R] f32 — InitResreq
    idle: jnp.ndarray,        # [N, R] f32
    releasing: jnp.ndarray,   # [N, R] f32
    pending: jnp.ndarray,     # [T] bool
    quanta: jnp.ndarray,      # [R] f32
    interpret: bool = False,
):
    """(best [T] i32, has [T] bool, chose_idle [T] bool) — the fused round
    head. T must be a multiple of the task tile and N of the node tile
    (snapshot buckets guarantee both at scale; callers pad otherwise)."""
    best, val, _, chose = masked_best_node_raw(
        score, static_ok, task_req, idle, releasing, pending, quanta,
        interpret=interpret,
    )
    return best, val > NEG, chose

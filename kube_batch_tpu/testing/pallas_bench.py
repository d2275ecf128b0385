"""Pallas round-head vs XLA round-head — the hardware decider (VERDICT r3 #2).

`ops/pallas_kernels.masked_best_node` fuses the auction round's first half
(fit + mask + two-key argmax) into VMEM tiles; the XLA path computes the same
values through fused broadcasts (`ops/assignment.round_body`). Both are timed
here on the SAME inputs at the same shapes the solve uses, so the number
decides whether the kernel earns its place as the default (flip
`AllocateConfig.use_pallas`) or gets deleted with the measurement recorded in
PARITY.md.

Each side is timed as the jitted round-head alone — score/static mask/tie
hash precomputed outside the timed region, exactly how `allocate_solve`
hoists them out of the rounds.

`compare_topk_build` does the same for the third kernel,
`masked_topk_blocks` (the compacted solve's candidate-build head), at the
pending-bucket shape the 50k×5k steady cycle uses.

Run: python -m kube_batch_tpu.testing.pallas_bench [--tasks 50000] [--nodes 5000]
Prints one JSON line.  On a TPU the kernels compile for real (a compiler
refusal raises, with Mosaic's message); on the CPU backend they are
interpreted, which checks the values and times nothing meaningful.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time


def _timed(fn, args, kwargs, reps):
    """(outputs, first-call seconds incl. compile, p50 step ms).  On the
    CPU backend only the outputs: the kernels are interpreted there, and a
    CPU time is not written under a device metric's name."""
    import jax

    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    if jax.default_backend() == "cpu":
        return out, None, None
    steps = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        steps.append((time.perf_counter() - t0) * 1e3)
    return out, round(compile_s, 1), round(statistics.median(steps), 3)


def _mismatches(names, xs, ys) -> dict:
    """{output name: differing elements} — all zeros is a match.  The count
    per output is what told a tie-order difference (only ``best`` differs,
    on full ties) from a wrong value."""
    import numpy as np

    return {
        n: int(np.sum(np.asarray(a) != np.asarray(b)))
        for n, a, b in zip(names, xs, ys)
    }


def compare_roundhead(
    n_tasks: int = 50_000,
    n_nodes: int = 5_000,
    reps: int = 20,
    seed: int = 0,
) -> dict:
    """Time one auction round head (fit + mask + lexicographic argmax +
    chose-idle gather) via XLA broadcasts vs the fused Pallas kernel.

    Returns p50 step ms, compile seconds, and bit-equality of the outputs
    (the kernel must match the XLA path exactly — same tie-hash constants,
    same epsilon fit — or its number is meaningless)."""
    import jax
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import NEG, _best_node, _tie_break_hash
    from kube_batch_tpu.ops.feasibility import fits, static_predicates
    from kube_batch_tpu.ops.pallas_kernels import (
        interpret_mode,
        masked_best_node,
    )
    from kube_batch_tpu.ops.scoring import ScoreWeights, score_matrix
    from kube_batch_tpu.testing.synthetic import synthetic_device_snapshot

    snap_np, _meta = synthetic_device_snapshot(
        n_tasks=n_tasks, n_nodes=n_nodes, gang_size=4, n_queues=3, seed=seed
    )
    snap = jax.device_put(snap_np)

    # hoisted round invariants (assignment.py:195-225)
    static_ok = static_predicates(snap)
    score = score_matrix(snap, ScoreWeights())
    score_static = jnp.where(static_ok, score, NEG)
    T, N = score.shape
    tie_hash = _tie_break_hash(T, N)
    pending = snap.task_pending & snap.task_valid

    @jax.jit
    def xla_head(score_static, tie_hash, task_req, idle, releasing, pending, quanta):
        fit_idle = fits(task_req, idle, quanta)
        fit_rel = fits(task_req, releasing, quanta)
        masked = jnp.where(
            (fit_idle | fit_rel) & pending[:, None], score_static, NEG
        )
        best, has = _best_node(masked, tie_hash)
        chose_idle = jnp.take_along_axis(fit_idle, best[:, None], axis=1)[:, 0]
        return best, has, chose_idle

    xla_args = (score_static, tie_hash, snap.task_req, snap.node_idle,
                snap.node_releasing, pending, snap.quanta)
    pallas_args = (score, static_ok, snap.task_req, snap.node_idle,
                   snap.node_releasing, pending, snap.quanta)

    xla_out, xla_compile_s, xla_ms = _timed(xla_head, xla_args, {}, reps)
    pallas_out, pallas_compile_s, pallas_ms = _timed(
        masked_best_node, pallas_args, {"interpret": interpret_mode()}, reps
    )
    diff = _mismatches(("best", "has", "chose_idle"), xla_out, pallas_out)
    return {
        "tasks": n_tasks, "nodes": n_nodes, "backend": jax.default_backend(),
        "xla_ms": xla_ms, "pallas_ms": pallas_ms,
        "xla_compile_s": xla_compile_s, "pallas_compile_s": pallas_compile_s,
        "outputs_match": not any(diff.values()), "mismatches": diff,
        "pallas_speedup": round(xla_ms / pallas_ms, 2) if pallas_ms else None,
    }


def compare_topk_build(
    n_pend: int = 8_192,
    n_nodes: int = 5_000,
    reps: int = 20,
    seed: int = 0,
) -> dict:
    """The candidate-build head of the compacted solve: masked sort-key
    plane + per-``TOPK_BLOCK`` winner triples, fused Pallas kernel vs the
    same values from XLA ops (``compact_candidates``' non-Pallas arm plus
    the phase-1 block reduction the triples stand for).  ``n_pend`` is the
    pending bucket (8,192 rows at the 50,176-task capacity)."""
    import jax
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import (
        NEG,
        f32_sort_key,
        tie_break_hash_rows,
    )
    from kube_batch_tpu.ops.feasibility import fits, static_predicates
    from kube_batch_tpu.ops.pallas_kernels import (
        TOPK_BLOCK,
        interpret_mode,
        masked_topk_blocks,
    )
    from kube_batch_tpu.ops.scoring import ScoreWeights, score_matrix
    from kube_batch_tpu.testing.synthetic import synthetic_device_snapshot

    snap_np, _meta = synthetic_device_snapshot(
        n_tasks=n_pend, n_nodes=n_nodes, gang_size=4, n_queues=3, seed=seed
    )
    snap = jax.device_put(snap_np)
    score_static = jnp.where(
        static_predicates(snap), score_matrix(snap, ScoreWeights()), NEG
    )
    P, N = score_static.shape
    # scattered global rows, as a pending bucket has them
    rows = (jnp.arange(P, dtype=jnp.int32) * 5 + 3)
    args = (score_static, snap.task_req, snap.node_idle,
            snap.node_releasing, rows, snap.quanta)

    @jax.jit
    def xla_build(score_static, task_req, idle, releasing, rows, quanta):
        fit = fits(task_req, idle, quanta) | fits(task_req, releasing, quanta)
        skey = f32_sort_key(jnp.where(fit, score_static, NEG))
        tie = tie_break_hash_rows(rows, jnp.arange(N, dtype=jnp.int32))
        sb = skey.reshape(P, N // TOPK_BLOCK, TOPK_BLOCK)
        hb = tie.reshape(P, N // TOPK_BLOCK, TOPK_BLOCK)
        bval = sb.max(axis=2)
        hmask = jnp.where(sb >= bval[:, :, None], hb, -2)
        return (skey, bval, hmask.max(axis=2),
                jnp.argmax(hmask, axis=2).astype(jnp.int32))

    xla_out, xla_compile_s, xla_ms = _timed(xla_build, args, {}, reps)
    pallas_out, pallas_compile_s, pallas_ms = _timed(
        masked_topk_blocks, args, {"interpret": interpret_mode()}, reps
    )
    diff = _mismatches(("skey", "bval", "bhash", "bcol"), xla_out, pallas_out)
    return {
        "pend_rows": P, "nodes": n_nodes, "backend": jax.default_backend(),
        "xla_ms": xla_ms, "pallas_ms": pallas_ms,
        "xla_compile_s": xla_compile_s, "pallas_compile_s": pallas_compile_s,
        "outputs_match": not any(diff.values()), "mismatches": diff,
    }


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--tasks", type=int, default=50_000)
    parser.add_argument("--nodes", type=int, default=5_000)
    parser.add_argument("--pend", type=int, default=8_192,
                        help="pending-bucket rows for the top-K build")
    parser.add_argument("--reps", type=int, default=20)
    args = parser.parse_args(argv)
    out = {
        "roundhead": compare_roundhead(args.tasks, args.nodes, args.reps),
        "topk_build": compare_topk_build(args.pend, args.nodes, args.reps),
    }
    print(json.dumps(out))
    if not all(r["outputs_match"] for r in out.values()):
        raise SystemExit(1)


if __name__ == "__main__":
    main()

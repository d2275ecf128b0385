"""Pallas round-head kernel parity vs the XLA path (interpret mode on CPU)."""

from __future__ import annotations

import numpy as np
import pytest

from kube_batch_tpu.ops.assignment import AllocateConfig, allocate_solve
from kube_batch_tpu.testing.synthetic import synthetic_device_snapshot


def test_masked_best_node_matches_xla():
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import NEG, _best_node, _tie_break_hash
    from kube_batch_tpu.ops.feasibility import fits, static_predicates
    from kube_batch_tpu.ops.pallas_kernels import masked_best_node
    from kube_batch_tpu.ops.scoring import ScoreWeights, score_matrix

    snap, meta = synthetic_device_snapshot(
        n_tasks=256, n_nodes=64, gang_size=4, n_queues=2, gpu_task_frac=0.3
    )
    score = score_matrix(snap, ScoreWeights())
    static_ok = static_predicates(snap)
    pending = jnp.asarray(snap.task_pending)

    best_k, has_k, chose_idle_k = masked_best_node(
        score, static_ok, snap.task_req, snap.node_idle, snap.node_releasing,
        pending, snap.quanta, interpret=True,
    )

    fit_idle = fits(snap.task_req, snap.node_idle, snap.quanta)
    fit_rel = fits(snap.task_req, snap.node_releasing, snap.quanta)
    feas = static_ok & (fit_idle | fit_rel) & pending[:, None]
    masked = jnp.where(feas, score, NEG)
    T, N = masked.shape
    best_x, has_x = _best_node(masked, _tie_break_hash(T, N))
    chose_idle_x = jnp.take_along_axis(fit_idle, best_x[:, None], axis=1)[:, 0]

    np.testing.assert_array_equal(np.asarray(has_k), np.asarray(has_x))
    np.testing.assert_array_equal(
        np.asarray(best_k)[np.asarray(has_x)], np.asarray(best_x)[np.asarray(has_x)]
    )
    np.testing.assert_array_equal(
        np.asarray(chose_idle_k)[np.asarray(has_x)],
        np.asarray(chose_idle_x)[np.asarray(has_x)],
    )


@pytest.mark.parametrize("gpu_frac", [0.0, 0.25])
def test_full_solve_parity(gpu_frac):
    """The whole allocate solve must produce identical placements with the
    pallas round head enabled."""
    snap, meta = synthetic_device_snapshot(
        n_tasks=512, n_nodes=64, gang_size=4, n_queues=3, gpu_task_frac=gpu_frac
    )
    r_xla = allocate_solve(snap, AllocateConfig())
    r_pls = allocate_solve(snap, AllocateConfig(use_pallas=True))
    np.testing.assert_array_equal(np.asarray(r_xla.assigned), np.asarray(r_pls.assigned))
    np.testing.assert_array_equal(np.asarray(r_xla.pipelined), np.asarray(r_pls.pipelined))


def test_raw_kernel_block_offsets_match_global_slice():
    """The (t0, n0) offsets make a block invocation agree with the global
    matrix: running the kernel on a [T_blk, N_blk] sub-block with its
    global origin must reproduce the winner value/hash/pick of the XLA
    two-key argmax over that exact slice of the FULL tie-hash matrix —
    the contract the shard_map round head relies on."""
    import jax.numpy as jnp

    from kube_batch_tpu.ops.assignment import NEG, _tie_break_hash
    from kube_batch_tpu.ops.feasibility import fits, static_predicates
    from kube_batch_tpu.ops.pallas_kernels import masked_best_node_raw
    from kube_batch_tpu.ops.scoring import ScoreWeights, score_matrix

    snap, _meta = synthetic_device_snapshot(
        n_tasks=512, n_nodes=128, gang_size=4, n_queues=2, gpu_task_frac=0.2
    )
    score = score_matrix(snap, ScoreWeights())
    static_ok = static_predicates(snap)
    pending = jnp.asarray(snap.task_pending)
    T, N = score.shape
    t0, n0, T_blk, N_blk = 256, 64, 256, 64

    best_k, val_k, hash_k, chose_k = masked_best_node_raw(
        score[t0:t0 + T_blk, n0:n0 + N_blk],
        static_ok[t0:t0 + T_blk, n0:n0 + N_blk],
        snap.task_req[t0:t0 + T_blk],
        snap.node_idle[n0:n0 + N_blk],
        snap.node_releasing[n0:n0 + N_blk],
        pending[t0:t0 + T_blk],
        snap.quanta, t0=t0, n0=n0, interpret=True,
    )

    # XLA reference over the same block with the GLOBAL tie-hash slice
    fit_idle = fits(snap.task_req[t0:t0 + T_blk],
                    snap.node_idle[n0:n0 + N_blk], snap.quanta)
    fit_rel = fits(snap.task_req[t0:t0 + T_blk],
                   snap.node_releasing[n0:n0 + N_blk], snap.quanta)
    feas = (
        static_ok[t0:t0 + T_blk, n0:n0 + N_blk]
        & (fit_idle | fit_rel) & pending[t0:t0 + T_blk, None]
    )
    masked = jnp.where(feas, score[t0:t0 + T_blk, n0:n0 + N_blk], NEG)
    tie = _tie_break_hash(T, N)[t0:t0 + T_blk, n0:n0 + N_blk]
    lval = jnp.max(masked, axis=1)
    cand = jnp.where(masked >= lval[:, None], tie, -1)
    pick = jnp.argmax(cand, axis=1).astype(jnp.int32)
    lkey = jnp.max(cand, axis=1)

    has = np.asarray(lval > NEG)
    np.testing.assert_array_equal(np.asarray(val_k)[has], np.asarray(lval)[has])
    np.testing.assert_array_equal(
        np.asarray(hash_k)[has], np.asarray(lkey).astype(np.float32)[has]
    )
    np.testing.assert_array_equal(np.asarray(best_k)[has], np.asarray(pick)[has])


def test_compiled_vs_interpret_agree_on_tpu():
    """The ROADMAP straggler: on a real TPU backend the kernel compiles
    for real (interpret=False) and must agree with interpret mode; other
    backends keep interpret=True as the fallback and skip here."""
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("compiled Pallas path requires the TPU backend")
    import jax.numpy as jnp

    from kube_batch_tpu.ops.pallas_kernels import masked_best_node
    from kube_batch_tpu.ops.feasibility import static_predicates
    from kube_batch_tpu.ops.scoring import ScoreWeights, score_matrix

    snap, _meta = synthetic_device_snapshot(
        n_tasks=512, n_nodes=512, gang_size=4, n_queues=2, gpu_task_frac=0.2
    )
    score = score_matrix(snap, ScoreWeights())
    static_ok = static_predicates(snap)
    pending = jnp.asarray(snap.task_pending)
    args = (score, static_ok, snap.task_req, snap.node_idle,
            snap.node_releasing, pending, snap.quanta)
    compiled = masked_best_node(*args, interpret=False)
    interp = masked_best_node(*args, interpret=True)
    for c, i in zip(compiled, interp):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(i))


def test_topk_build_kernel_matches_xla():
    """The third kernel: masked sort-key plane + per-block winner triples of
    the compacted solve's candidate build equal the same values from XLA
    ops (the comparison the on-chip kernel check runs at 8,192×5,000)."""
    from kube_batch_tpu.testing.pallas_bench import compare_topk_build

    out = compare_topk_build(n_pend=512, n_nodes=600, reps=1)
    assert out["pend_rows"] == 512 and out["outputs_match"], out

"""Quality parity: the TPU ALS (ops/als.py) must match an independent
MLlib-faithful CPU reference (quality/mllib_als.py) on held-out metrics
over identical data (VERDICT r1 #1; the north star's "at matching MAP@10"
half). Full-scale runs live in quality.py; these tests prove
the harness and the agreement at CI-sized scale."""

import numpy as np
import pytest

from predictionio_tpu.quality import datasets
from predictionio_tpu.quality.mllib_als import mllib_als_train, solve_one_row
from predictionio_tpu.quality.parity import (
    map_at_k_heldout, rmse_heldout, run_parity,
)


def test_solve_one_row_matches_batched_explicit():
    """The standalone scipy-Cholesky row solve and the batched _solve_side
    path must agree (two independent factorizations of the same system)."""
    rng = np.random.default_rng(0)
    n_items, k = 50, 8
    Y = rng.standard_normal((n_items, k)).astype(np.float32)
    cols = rng.choice(n_items, 12, replace=False).astype(np.int32)
    vals = rng.uniform(1, 5, 12).astype(np.float32)
    x1 = solve_one_row(Y, cols, vals, reg=0.1)
    res = mllib_als_train(np.zeros(12, np.int32), cols, vals, 1, n_items,
                          rank=k, iterations=1, reg=0.1, seed=0)
    # after one iteration the user row was solved against the *updated*
    # item factors, so recompute the expected row against those
    expect = solve_one_row(res.item_factors, cols, vals, reg=0.1)
    np.testing.assert_allclose(res.user_factors[0], expect, rtol=1e-5)
    assert x1.shape == (k,)


def test_weighted_reg_scales_with_count():
    """ALS-WR: doubling a row's ratings (duplicated) must yield the same
    solution as solving with the duplicates — i.e. λ scales with n."""
    rng = np.random.default_rng(1)
    Y = rng.standard_normal((20, 4)).astype(np.float32)
    cols = np.array([1, 5, 9], np.int32)
    vals = np.array([4.0, 2.0, 5.0], np.float32)
    x1 = solve_one_row(Y, cols, vals, reg=0.3)
    x2 = solve_one_row(Y, np.tile(cols, 2), np.tile(vals, 2), reg=0.3)
    # duplicating every rating doubles A, b, and λn uniformly → same x
    np.testing.assert_allclose(x1, x2, rtol=1e-6)


def test_implicit_row_matches_hkv_formula():
    rng = np.random.default_rng(2)
    Y = rng.standard_normal((30, 6)).astype(np.float32)
    cols = np.array([0, 7, 19], np.int32)
    vals = np.array([3.0, 1.0, 2.0], np.float32)
    alpha, reg = 2.0, 0.5
    x = solve_one_row(Y, cols, vals, reg, implicit=True, alpha=alpha)
    Y64 = Y.astype(np.float64)
    C = np.ones(len(Y64))
    C[cols] += alpha * vals  # c = 1 + αr on observed, 1 elsewhere
    p = np.zeros(len(Y64))
    p[cols] = 1.0
    A = Y64.T @ (C[:, None] * Y64) + reg * len(cols) * np.eye(6)
    b = Y64.T @ (C * p)
    np.testing.assert_allclose(x, np.linalg.solve(A, b), rtol=1e-5)


def test_explicit_parity_small():
    """Both implementations reach the same held-out RMSE (±0.01) on a
    20k-rating planted dataset — agreement through completely disjoint
    code paths (numpy/scipy loop vs bucketed jitted scan)."""
    split = datasets.synth_explicit("100k", seed=3)

    from predictionio_tpu.ops.als import ALSConfig, als_train

    rank, iters, reg = 16, 8, 0.1
    ours = als_train(split.train_u, split.train_i, split.train_r,
                     split.n_users, split.n_items,
                     ALSConfig(rank=rank, iterations=iters, reg=reg, seed=3))
    ref = mllib_als_train(split.train_u, split.train_i, split.train_r,
                          split.n_users, split.n_items, rank=rank,
                          iterations=iters, reg=reg, seed=3)
    r_ours = rmse_heldout(ours.user_factors, ours.item_factors, split)
    r_ref = rmse_heldout(ref.user_factors, ref.item_factors, split)
    assert abs(r_ours - r_ref) < 0.01, (r_ours, r_ref)
    # sanity: both actually learned (global-mean predictor RMSE ≈ 1.1 here)
    assert r_ours < 1.0 and r_ref < 1.0


def test_implicit_parity_small():
    split = datasets.synth_implicit("100k", seed=4)
    n_tr, n_te = 30_000, 3_000
    split = datasets.RatingSplit(
        split.train_u[:n_tr], split.train_i[:n_tr], split.train_r[:n_tr],
        split.test_u[:n_te], split.test_i[:n_te], split.test_r[:n_te],
        split.n_users, split.n_items)

    from predictionio_tpu.ops.als import ALSConfig, als_train

    rank, iters, reg, alpha = 16, 8, 0.05, 40.0
    ours = als_train(split.train_u, split.train_i, split.train_r,
                     split.n_users, split.n_items,
                     ALSConfig(rank=rank, iterations=iters, reg=reg,
                               implicit=True, alpha=alpha, seed=4))
    ref = mllib_als_train(split.train_u, split.train_i, split.train_r,
                          split.n_users, split.n_items, rank=rank,
                          iterations=iters, reg=reg, implicit=True,
                          alpha=alpha, seed=4)
    m_ours = map_at_k_heldout(ours.user_factors, ours.item_factors, split,
                              10, max_users=3000)
    m_ref = map_at_k_heldout(ref.user_factors, ref.item_factors, split,
                             10, max_users=3000)
    # MAP is noisier than RMSE at this scale; relative agreement
    assert m_ours > 0.5 * m_ref and m_ref > 0.5 * m_ours, (m_ours, m_ref)
    assert m_ours > 0.01 and m_ref > 0.01  # both learned real ranking signal


def test_run_parity_smoke():
    out = run_parity(mode="explicit", scale="100k", rank=8, iterations=3,
                     reg=0.1, seed=5)
    assert out["metric"] == "rmse"
    assert "rmse" in out["ours"] and "rmse" in out["ref"]
    assert abs(out["delta"]) < 0.1

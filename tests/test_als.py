"""ALS op correctness: bucketing, normal-equation solves vs a dense numpy
reference, low-rank recovery, implicit mode, and ranking metrics."""

import dataclasses

import numpy as np
import pytest

from predictionio_tpu.ops.als import ALSConfig, Bucket, als_train, bucket_ragged
from predictionio_tpu.ops.ranking import (
    average_precision_at_k,
    map_at_k,
    recommend_topk,
)


def synth_ratings(n_users=60, n_items=40, rank=3, density=0.3, seed=0, noise=0.0):
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    v = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    full = u @ v.T
    mask = rng.random((n_users, n_items)) < density
    ui, ii = np.nonzero(mask)
    r = full[ui, ii] + noise * rng.normal(size=len(ui))
    return ui.astype(np.int32), ii.astype(np.int32), r.astype(np.float32), full


class TestSolvers:
    def test_chol_converges(self):
        ui, ii, r, _ = synth_ratings(n_users=50, n_items=35, seed=2)
        cfg = ALSConfig(rank=6, iterations=15, reg=0.01, seed=3,
                        solver="chol")
        out = als_train(ui, ii, r, 50, 35, cfg, compute_rmse=True)
        assert out.rmse_history[-1] < 0.05  # near-noiseless synth recovers

    @pytest.mark.parametrize("solver", ["lu", "cg", "chl"])
    def test_unknown_solver_is_refused(self, solver):
        """A solver that was deleted, or a typo, never trains: until
        PR 30 any unknown string fell through to `jnp.linalg.solve`."""
        from predictionio_tpu.ops.als import resolve_solver

        with pytest.raises(ValueError, match="auto / gj / chol"):
            resolve_solver(ALSConfig(rank=6, solver=solver))


class TestBucketing:
    def test_buckets_cover_all_entries(self):
        rng = np.random.default_rng(1)
        rows = rng.integers(0, 50, 500).astype(np.int32)
        cols = rng.integers(0, 30, 500).astype(np.int32)
        vals = rng.random(500).astype(np.float32)
        buckets = bucket_ragged(rows, cols, vals, n_rows=50, row_multiple=8)
        # every real entry appears exactly once
        total = sum(int(b.mask.sum()) for b in buckets)
        assert total == 500
        # row counts padded to multiple of 8, rows unique across buckets
        seen_rows = []
        for b in buckets:
            assert b.rows.shape[0] % 8 == 0
            assert b.cols.shape == b.vals.shape == b.mask.shape
            real = b.rows[b.rows < 50]
            seen_rows.extend(real.tolist())
            # capacity fits the largest row in the bucket
            assert int(b.mask.sum(1).max()) <= b.cap
        assert sorted(seen_rows) == sorted(np.unique(rows).tolist())
        # sentinel rows are fully masked out
        for b in buckets:
            pad = b.rows >= 50
            assert b.mask[pad].sum() == 0

    def test_cap_ladder(self):
        rows = np.asarray([0] * 3 + [1] * 9 + [2] * 17, dtype=np.int32)
        cols = np.arange(29, dtype=np.int32)
        vals = np.ones(29, dtype=np.float32)
        # growth 2.0 = round-1 power-of-two caps
        buckets = bucket_ragged(rows, cols, vals, n_rows=3, cap_growth=2.0)
        assert sorted(b.cap for b in buckets) == [8, 16, 32]
        # default 1.5 ladder: 8, 16, 24, ... (each ceil(prev*1.5/8)*8)
        buckets = bucket_ragged(rows, cols, vals, n_rows=3)
        assert sorted(b.cap for b in buckets) == [8, 16, 24]

    def test_max_cap_truncates(self):
        rows = np.zeros(100, dtype=np.int32)
        cols = np.arange(100, dtype=np.int32)
        vals = np.ones(100, dtype=np.float32)
        (b,) = bucket_ragged(rows, cols, vals, n_rows=1, max_cap=32)
        assert b.cap == 32
        assert int(b.mask.sum()) == 32


def dense_als_reference(ui, ii, r, n_users, n_items, rank, reg, iters, seed,
                        weighted=True):
    """Straightforward numpy ALS with identical init for comparison."""
    import jax

    key = jax.random.key(seed)
    v = np.asarray(jax.random.normal(key, (n_items, rank), dtype=np.float32)
                   ) / np.sqrt(rank)
    u = np.zeros((n_users, rank), dtype=np.float32)
    R = np.zeros((n_users, n_items), dtype=np.float32)
    M = np.zeros((n_users, n_items), dtype=bool)
    R[ui, ii] = r
    M[ui, ii] = True
    for _ in range(iters):
        for X, Y, Rm, Mm in ((u, v, R, M), (v, u, R.T, M.T)):
            for row in range(X.shape[0]):
                sel = Mm[row]
                n = sel.sum()
                if n == 0:
                    continue
                Ys = Y[sel]
                lam = reg * (n if weighted else 1.0)
                A = Ys.T @ Ys + lam * np.eye(rank)
                X[row] = np.linalg.solve(A, Ys.T @ Rm[row, sel])
    return u, v


class TestALSCorrectness:
    def test_matches_dense_reference(self):
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, density=0.4)
        cfg = ALSConfig(rank=4, iterations=3, reg=0.1, seed=7)
        res = als_train(ui, ii, r, 30, 20, cfg)
        u_ref, v_ref = dense_als_reference(ui, ii, r, 30, 20, 4, 0.1, 3, 7)
        # f32 einsum vs numpy-loop accumulation order → ~1e-3 noise
        np.testing.assert_allclose(res.user_factors, u_ref, rtol=2e-2, atol=5e-3)
        np.testing.assert_allclose(res.item_factors, v_ref, rtol=2e-2, atol=5e-3)

    def test_low_rank_recovery_rmse(self):
        ui, ii, r, _ = synth_ratings(n_users=80, n_items=50, rank=3, density=0.4)
        cfg = ALSConfig(rank=3, iterations=12, reg=1e-3, seed=0)
        res = als_train(ui, ii, r, 80, 50, cfg, compute_rmse=True)
        assert res.rmse_history[-1] < 0.05  # exact low-rank data → tiny residual
        assert res.rmse_history[-1] <= res.rmse_history[0]

    def test_users_with_no_ratings_stay_zero(self):
        ui = np.asarray([0, 0, 2], dtype=np.int32)  # user 1 has nothing
        ii = np.asarray([0, 1, 1], dtype=np.int32)
        r = np.ones(3, dtype=np.float32)
        res = als_train(ui, ii, r, 3, 2, ALSConfig(rank=2, iterations=2))
        assert np.all(res.user_factors[1] == 0)
        assert np.any(res.user_factors[0] != 0)

    def test_implicit_mode_ranks_observed_higher(self):
        # two user groups with disjoint item preferences
        rng = np.random.default_rng(0)
        ui, ii, r = [], [], []
        for u in range(20):
            prefer = range(0, 10) if u < 10 else range(10, 20)
            for i in rng.choice(list(prefer), 6, replace=False):
                ui.append(u); ii.append(int(i)); r.append(1.0)
        ui = np.asarray(ui, np.int32); ii = np.asarray(ii, np.int32)
        r = np.asarray(r, np.float32)
        cfg = ALSConfig(rank=8, iterations=8, reg=0.1, implicit=True, alpha=10.0)
        res = als_train(ui, ii, r, 20, 20, cfg)
        scores = res.user_factors @ res.item_factors.T
        # user 0 (likes items 0-9) should score in-group items higher on average
        assert scores[0, :10].mean() > scores[0, 10:].mean() + 0.1


class TestRanking:
    def test_average_precision(self):
        assert average_precision_at_k(np.asarray([1, 2, 3]), {1, 2, 3}, 3) == 1.0
        assert average_precision_at_k(np.asarray([9, 1]), {1}, 2) == pytest.approx(0.5)
        assert average_precision_at_k(np.asarray([1]), set(), 1) == 0.0

    def test_recommend_topk_excludes(self):
        u = np.asarray([[1.0, 0.0]])
        v = np.asarray([[2.0, 0], [1.5, 0], [1.0, 0]])
        _, idx = recommend_topk(u, v, np.asarray([0]), 2)
        assert idx[0].tolist() == [0, 1]
        _, idx = recommend_topk(u, v, np.asarray([0]), 2,
                                exclude={0: np.asarray([0])})
        assert idx[0].tolist() == [1, 2]

    def test_map_at_k_end_to_end(self):
        ui, ii, r, full = synth_ratings(n_users=50, n_items=40, rank=3,
                                        density=0.35, seed=2)
        cfg = ALSConfig(rank=3, iterations=10, reg=1e-3)
        res = als_train(ui, ii, r, 50, 40, cfg)
        # test set: for each user, the top unrated item by true score
        rated = {u: set() for u in range(50)}
        for u_, i_ in zip(ui, ii):
            rated[int(u_)].add(int(i_))
        test = {}
        exclude = {}
        for u in range(50):
            unrated = [i for i in range(40) if i not in rated[u]]
            if unrated:
                test[u] = {max(unrated, key=lambda i: full[u, i])}
                exclude[u] = np.asarray(sorted(rated[u]), dtype=np.int32)
        score = map_at_k(res.user_factors, res.item_factors, test, k=10,
                         exclude=exclude)
        assert score > 0.3  # exact low-rank data → should rank well


class TestReviewRegressions:
    def test_engine_requires_algorithm_map(self):
        from predictionio_tpu.controller import Engine
        import pytest as _pytest

        with _pytest.raises(ValueError, match="algorithm_class_map"):
            Engine(data_source_class_map=dict, algorithm_class_map=None)

    def test_resolve_component_strict_on_typo(self):
        from predictionio_tpu.controller.engine import resolve_component
        import pytest as _pytest

        class A: pass
        assert resolve_component({"als": A}, "", "algorithm") is A
        assert resolve_component({"als": A}, "als", "algorithm") is A
        with _pytest.raises(KeyError, match="alss"):
            resolve_component({"als": A}, "alss", "algorithm")

    def test_recommend_topk_no_exclude_no_mask_path(self):
        u = np.asarray([[1.0, 0.0]])
        v = np.asarray([[2.0, 0], [1.5, 0], [1.0, 0]])
        s, idx = recommend_topk(u, v, np.asarray([0]), 2, exclude=None)
        assert idx[0].tolist() == [0, 1]
        # empty-dict exclude also takes the unmasked path
        s, idx = recommend_topk(u, v, np.asarray([0]), 2, exclude={})
        assert idx[0].tolist() == [0, 1]


class TestHotRowSplitting:
    """bucket_ragged_split + segment accumulation: hot rows are split into
    bounded segments whose partial normal equations are summed pre-solve,
    so results match the unsplit math (SURVEY.md §7.3 padding-waste risk)."""

    def _skewed(self, seed=0, n_users=40, n_items=25):
        # user 0 rates every item 4x epochs... make user 0 and item 0 hot
        rng = np.random.default_rng(seed)
        ui, ii, r, _ = synth_ratings(n_users=n_users, n_items=n_items,
                                     seed=seed, density=0.4)
        return ui, ii, r

    def test_split_table_and_coverage(self):
        from predictionio_tpu.ops.als import bucket_ragged_split

        ui, ii, r = self._skewed()
        n_entries = len(r)
        buckets, split = bucket_ragged_split(ui, ii, r, 40, 8, split_cap=8)
        # every real entry appears exactly once across buckets
        assert sum(int(b.mask.sum()) for b in buckets) == n_entries
        counts = np.bincount(ui, minlength=40)
        assert set(split) == set(np.nonzero(counts > 8)[0])
        # no bucket is wider than the split cap (pow2 of it)
        assert max(b.cap for b in buckets) <= 8
        # segment rows carry real row ids and valid segmap slots
        for b in buckets:
            if b.segmap is None:
                continue
            seg = b.segmap < len(split)
            assert np.all(np.isin(b.rows[seg], split))
        # reconstruct per-row entry multisets
        got = {}
        for b in buckets:
            for rr, cc, vv, mm in zip(b.rows, b.cols, b.vals, b.mask):
                for c, v, m in zip(cc, vv, mm):
                    if m:
                        got.setdefault(int(rr), []).append((int(c), float(v)))
        want = {}
        for u, i, v in zip(ui, ii, r):
            want.setdefault(int(u), []).append((int(i), float(v)))
        assert {k: sorted(vs) for k, vs in got.items()} == \
               {k: sorted(vs) for k, vs in want.items()}

    def test_split_nothing_when_under_cap(self):
        from predictionio_tpu.ops.als import bucket_ragged_split

        ui, ii, r = self._skewed()
        buckets, split = bucket_ragged_split(ui, ii, r, 40, 8,
                                             split_cap=1 << 20)
        assert len(split) == 0
        assert all(b.segmap is None for b in buckets)

    @pytest.mark.parametrize("implicit", [False, True])
    def test_split_factors_match_unsplit(self, implicit):
        ui, ii, r = self._skewed(seed=3)
        base = ALSConfig(rank=5, iterations=4, reg=0.05, seed=1,
                         implicit=implicit, split_cap=0)
        split = dataclasses.replace(base, split_cap=8)
        out_u = als_train(ui, ii, r, 40, 25, base, compute_rmse=True)
        out_s = als_train(ui, ii, r, 40, 25, split, compute_rmse=True)
        np.testing.assert_allclose(out_s.user_factors, out_u.user_factors,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(out_s.item_factors, out_u.item_factors,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(out_s.rmse_history, out_u.rmse_history,
                                   rtol=1e-4)

    def test_chunked_bucket_walk_matches(self, monkeypatch):
        from predictionio_tpu.ops import als as als_mod

        ui, ii, r = self._skewed(seed=5)
        cfg = ALSConfig(rank=5, iterations=3, reg=0.05, seed=2)
        out_full = als_train(ui, ii, r, 40, 25, cfg, compute_rmse=True)
        # force the fori_loop row-chunk path for every bucket
        monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", 1 << 12)
        als_mod._get_train_loop.cache_clear()
        out_chunk = als_train(ui, ii, r, 40, 25, cfg, compute_rmse=True)
        als_mod._get_train_loop.cache_clear()
        np.testing.assert_allclose(out_chunk.user_factors, out_full.user_factors,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(out_chunk.rmse_history, out_full.rmse_history,
                                   rtol=1e-4)

    def test_split_with_chunking_combined(self, monkeypatch):
        from predictionio_tpu.ops import als as als_mod

        ui, ii, r = self._skewed(seed=7)
        base = ALSConfig(rank=4, iterations=3, reg=0.05, seed=3, split_cap=0)
        out_ref = als_train(ui, ii, r, 40, 25, base)
        monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", 1 << 12)
        als_mod._get_train_loop.cache_clear()
        out = als_train(ui, ii, r, 40, 25,
                        dataclasses.replace(base, split_cap=8))
        als_mod._get_train_loop.cache_clear()
        np.testing.assert_allclose(out.user_factors, out_ref.user_factors,
                                   rtol=2e-4, atol=2e-5)


BUDGET = 1 << 30


def _largest_chunk_rule(r, c, k, row_multiple, budget=BUDGET):
    """The rule before PR 40: the largest chunk the budget admits."""
    per_row = c * k * 4
    if r * per_row <= budget:
        return r
    return min(r, max(1, budget // (per_row * row_multiple)) * row_multiple)


def _placed_buckets(monkeypatch):
    """Spy on `als_train`'s loop: the (user, item) bucket lists each call
    enters it with land in the returned list."""
    from predictionio_tpu.ops import als as als_mod

    placed = []
    real = als_mod._get_train_loop

    def spy(*key, **kw):
        loop = real(*key, **kw)

        def call(*args):
            placed.append((args[2], args[3]))
            return loop(*args)
        return call

    monkeypatch.setattr(als_mod, "_get_train_loop", spy)
    return placed


class TestChunkRule:
    """`_bucket_chunk_rows`: the fewest trips the budget admits, the rows
    spread evenly over them (PR 40)."""

    # ML-20M's chunked buckets (`perf/data.py` at split_cap 32768, the
    # benchmark's two ALS cells): (rank, rows, cap) -> chunk, trips, padded
    ML20M = [
        (64, 176, 12784, 176, 1, 176),
        (64, 160, 19176, 160, 1, 160),
        (64, 152, 28768, 80, 2, 160),
        (64, 208, 43152, 72, 3, 216),
        (128, 176, 12784, 88, 2, 176),
        (128, 160, 19176, 80, 2, 160),
        (128, 152, 28768, 56, 3, 168),
        (128, 208, 43152, 48, 5, 240),
        (128, 17048, 144, 8528, 2, 17056),
        (128, 12680, 216, 6344, 2, 12688),
        (128, 8992, 328, 4496, 2, 8992),
        (128, 5648, 496, 2824, 2, 5648),
        (128, 3272, 744, 1640, 2, 3280),
    ]

    @pytest.mark.parametrize("rank,rows,cap,chunk,trips,padded", ML20M)
    def test_ml20m_buckets(self, rank, rows, cap, chunk, trips, padded):
        from predictionio_tpu.ops.als import (
            _CHUNK_BUDGET_BYTES,
            _bucket_chunk_rows,
        )

        assert _CHUNK_BUDGET_BYTES == BUDGET
        got = _bucket_chunk_rows(rows, cap, rank, 8)
        assert got == chunk
        assert -(-rows // got) == trips
        assert rows + (-rows) % got == padded
        # what the walk recomputes from the padded height
        assert _bucket_chunk_rows(padded, cap, rank, 8) == chunk

    @pytest.mark.parametrize("row_multiple", [1, 8, 16, 24, 32, 64])
    @pytest.mark.parametrize("k", [4, 64, 128, 1024])
    def test_properties_over_a_sweep(self, row_multiple, k):
        from predictionio_tpu.ops.als import _bucket_chunk_rows

        rng = np.random.default_rng(row_multiple * 1000 + k)
        caps = [8, 144, 744, 12784, 43152, 1 << 20, 1 << 26]
        units_of = [1, 2, 3, 5, 19, 26, 409, 2131] + list(
            rng.integers(1, 5000, 24))
        chunked = 0
        for c in caps:
            per_row = c * k * 4
            for units in units_of:
                r = int(units) * row_multiple
                chunk = _bucket_chunk_rows(r, c, k, row_multiple)
                if r * per_row <= BUDGET:
                    assert chunk == r  # whole where it fits
                    continue
                chunked += 1
                assert chunk % row_multiple == 0 and 0 < chunk <= r
                if per_row * row_multiple <= BUDGET:  # one unit fits
                    assert chunk * per_row <= BUDGET
                else:
                    assert chunk == row_multiple
                trips = -(-r // chunk)
                old = _largest_chunk_rule(r, c, k, row_multiple)
                assert trips <= -(-r // old) and chunk <= old
                padded = trips * chunk
                assert padded - r < trips * row_multiple
                assert padded - r <= (-r) % old  # never more than before
                assert _bucket_chunk_rows(padded, c, k, row_multiple) == chunk
        assert chunked

    def test_uneven_chunked_train_matches_unchunked(self, monkeypatch):
        """A bucket of 40 rows where the budget admits 32: two trips of
        24 (48 placed, not 64), every row as the unchunked train has it."""
        from predictionio_tpu.ops import als as als_mod

        rng = np.random.default_rng(11)
        n_users, n_items, per_user = 40, 25, 8
        ui = np.repeat(np.arange(n_users, dtype=np.int32), per_user)
        ii = np.concatenate([rng.choice(n_items, per_user, replace=False)
                             for _ in range(n_users)]).astype(np.int32)
        r = rng.uniform(1, 5, len(ui)).astype(np.float32)
        cfg = ALSConfig(rank=4, iterations=3, reg=0.05, seed=2)
        whole = als_train(ui, ii, r, n_users, n_items, cfg, compute_rmse=True)

        monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", 1 << 12)
        loops = als_mod._get_train_loop
        loops.cache_clear()
        placed = _placed_buckets(monkeypatch)
        chunked = als_train(ui, ii, r, n_users, n_items, cfg,
                            compute_rmse=True)
        loops.cache_clear()
        (ub_dev, _ib_dev), = placed
        assert [b[1].shape for b in ub_dev] == [(48, 8)]
        assert als_mod._bucket_chunk_rows(48, 8, 4, 8) == 24
        np.testing.assert_allclose(chunked.user_factors, whole.user_factors,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(chunked.item_factors, whole.item_factors,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(chunked.rmse_history, whole.rmse_history,
                                   rtol=1e-4)

    def test_grid_padded_heights_are_walked_exactly(self, monkeypatch):
        """A grid's buckets are placed at rank n_grid x rank; the walk
        (traced in `ops/als.py` since a grid runs the train's
        half-iteration) recomputes the same chunk from the padded height."""
        from predictionio_tpu.ops import als as als_mod, als_grid

        ui, ii, r, _ = synth_ratings(n_users=40, n_items=25, seed=4,
                                     density=0.4)
        cfgs = [ALSConfig(rank=4, iterations=2, reg=reg, seed=1)
                for reg in (0.05, 0.2)]
        whole = als_grid.als_train_grid(ui, ii, r, 40, 25, cfgs)

        monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", 1 << 12)
        als_grid._get_grid_train_loop.cache_clear()
        walks = self._spy_walks(monkeypatch, als_mod)
        chunked = als_grid.als_train_grid(ui, ii, r, 40, 25, cfgs)
        als_grid._get_grid_train_loop.cache_clear()
        self._assert_exact(walks)
        for got, want in zip(chunked, whole):
            np.testing.assert_allclose(got.user_factors, want.user_factors,
                                       rtol=2e-4, atol=2e-5)

    def test_foldin_padded_tier_is_walked_exactly(self, monkeypatch):
        """A fold of 9 rows rides the 32-row tier; where the tier is
        chunked its height stays a function of the tier alone."""
        from predictionio_tpu.online import foldin
        from predictionio_tpu.ops import als as als_mod

        rng = np.random.default_rng(5)
        opposing = rng.normal(size=(30, 4)).astype(np.float32)
        entries = [(rng.choice(30, n, replace=False).astype(np.int32),
                    rng.uniform(1, 5, n).astype(np.float32))
                   for n in (3, 5, 8, 2, 7, 6, 4, 8, 1)]
        cfg = ALSConfig(rank=4, reg=0.05, solver="chol")
        whole = foldin.solve_rows(opposing, entries, cfg)

        # a row of the 8-wide tier is 128 bytes: 24 rows a trip at most
        monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", 3 << 10)
        foldin._fold_solver.cache_clear()
        walks = self._spy_walks(monkeypatch, als_mod)
        chunked = foldin.solve_rows(opposing, entries, cfg)
        foldin._fold_solver.cache_clear()
        self._assert_exact(walks)
        assert [(r, chunk) for r, _cap, chunk in walks] == [(32, 16)]
        np.testing.assert_allclose(chunked, whole, rtol=2e-4, atol=2e-5)

    @staticmethod
    def _spy_walks(monkeypatch, mod):
        """(rows, cap, chunk) of every `_walk_bucket_chunks` that `mod`
        traces."""
        from predictionio_tpu.ops import als as als_mod

        walks = []
        real = als_mod._walk_bucket_chunks

        def spy(arrays, cap, k, row_multiple, fn, carry):
            rows = arrays[0].shape[0]
            walks.append((rows, cap, als_mod._bucket_chunk_rows(
                rows, cap, k, row_multiple)))
            return real(arrays, cap, k, row_multiple, fn, carry)

        monkeypatch.setattr(mod, "_walk_bucket_chunks", spy)
        return walks

    @staticmethod
    def _assert_exact(walks):
        assert any(chunk < rows for rows, _cap, chunk in walks)
        for rows, _cap, chunk in walks:
            assert rows % chunk == 0


class TestShardedGJSolver:
    def test_gj_under_8_device_mesh_matches_chol(self, caplog):
        """solver='gj' under a multi-device mesh runs one Pallas kernel per
        device via shard_map (interpret mode on the CPU test mesh); factors
        must match the chol path on the same mesh."""
        import logging

        from predictionio_tpu.parallel.mesh import DATA_AXIS, make_mesh

        mesh = make_mesh({DATA_AXIS: 8})
        ui, ii, r, _ = synth_ratings(n_users=48, n_items=30, seed=4)
        base = ALSConfig(rank=6, iterations=4, reg=0.05, seed=2, split_cap=8)
        out_chol = als_train(ui, ii, r, 48, 30,
                             dataclasses.replace(base, solver="chol"),
                             mesh=mesh)
        with caplog.at_level(logging.WARNING, "predictionio_tpu.ops.als"):
            out_gj = als_train(ui, ii, r, 48, 30,
                               dataclasses.replace(base, solver="gj",
                                                   pallas="interpret"),
                               mesh=mesh)
        # the sharded kernel must actually run, not fall back to chol
        assert not any("falling back" in m for m in caplog.messages)
        np.testing.assert_allclose(out_gj.user_factors, out_chol.user_factors,
                                   rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(out_gj.item_factors, out_chol.item_factors,
                                   rtol=5e-4, atol=5e-5)


class TestModelShardedALS:
    """Factor sharding over the mesh `model` axis (VERDICT r1 #3 /
    SURVEY.md §2.6 row 2): on a (data=4, model=2) mesh the factor
    matrices shard P('model') and per-chunk normal equations combine via
    psum_scatter + all_gather. Results must match the replicated path."""

    def _mesh(self):
        from predictionio_tpu.parallel.mesh import (
            DATA_AXIS, MODEL_AXIS, make_mesh,
        )

        return make_mesh({DATA_AXIS: 4, MODEL_AXIS: 2})

    @pytest.mark.parametrize("implicit", [False, True])
    def test_matches_replicated_path(self, implicit):
        ui, ii, r, _ = synth_ratings(n_users=50, n_items=34, seed=7)
        cfg = ALSConfig(rank=6, iterations=4, reg=0.05, seed=3,
                        implicit=implicit, alpha=2.0, solver="chol",
                        split_cap=8)  # small cap → segment accumulators
        ref = als_train(ui, ii, r, 50, 34, cfg, compute_rmse=True)
        out = als_train(ui, ii, r, 50, 34, cfg, mesh=self._mesh(),
                        compute_rmse=True)
        assert out.user_factors.shape == (50, 6)
        assert out.item_factors.shape == (34, 6)
        np.testing.assert_allclose(out.user_factors, ref.user_factors,
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(out.item_factors, ref.item_factors,
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(out.rmse_history, ref.rmse_history,
                                   rtol=1e-3)

    def test_gj_in_the_sharded_loop_matches_chol(self):
        """The model-sharded loop's kernel branch (interpret mode, one
        kernel a device on the R/m slice its model shard solves, the
        split accumulators' batch included) against its Cholesky run on
        the same (data=2, model=2) mesh."""
        import jax

        from predictionio_tpu.ops import pallas_solve
        from predictionio_tpu.parallel.mesh import (
            DATA_AXIS, MODEL_AXIS, make_mesh,
        )

        mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 2},
                         devices=jax.devices()[:4])
        ui, ii, r, _ = synth_ratings(n_users=50, n_items=34, seed=7)
        base = ALSConfig(rank=6, iterations=3, reg=0.05, seed=3,
                         split_cap=8)
        chol = als_train(ui, ii, r, 50, 34,
                         dataclasses.replace(base, solver="chol"), mesh=mesh)
        built = pallas_solve.SOLVE_CALLS.labels(layout="lanes")
        before = built.value
        gj = als_train(ui, ii, r, 50, 34,
                       dataclasses.replace(base, solver="gj",
                                           pallas="interpret"), mesh=mesh)
        assert built.value > before  # the kernel, not a fallback
        np.testing.assert_allclose(gj.user_factors, chol.user_factors,
                                   rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(gj.item_factors, chol.item_factors,
                                   rtol=5e-4, atol=5e-5)

    def test_uses_sharded_loop_and_sharded_factors(self, monkeypatch):
        """The model-axis mesh must actually route through the sharded
        loop with non-replicated factor specs (guards against silently
        replicating — ROADMAP r1's admitted gap)."""
        import jax
        from predictionio_tpu.ops import als_sharded

        seen_shardings = []
        real = als_sharded.get_train_loop_sharded.__wrapped__

        def spy(*args, **kw):
            fn = real(*args, **kw)

            def wrapper(item_f, user_f, *rest):
                seen_shardings.append(item_f.sharding.spec)
                return fn(item_f, user_f, *rest)

            return wrapper

        monkeypatch.setattr(als_sharded, "get_train_loop_sharded", spy)
        ui, ii, r, _ = synth_ratings(n_users=24, n_items=16, seed=1)
        cfg = ALSConfig(rank=4, iterations=2, reg=0.1, seed=0, solver="chol")
        als_train(ui, ii, r, 24, 16, cfg, mesh=self._mesh())
        assert seen_shardings, "sharded loop was not used on a model-axis mesh"
        from predictionio_tpu.parallel.mesh import MODEL_AXIS

        assert seen_shardings[0][0] == MODEL_AXIS

    def test_chunked_walk_matches(self, monkeypatch):
        """Chunked per-device bucket walk (tiny budget) under the sharded
        path still reproduces the replicated result."""
        import predictionio_tpu.ops.als as als_mod

        ui, ii, r, _ = synth_ratings(n_users=50, n_items=34, seed=9)
        cfg = ALSConfig(rank=4, iterations=3, reg=0.05, seed=5,
                        solver="chol")
        ref = als_train(ui, ii, r, 50, 34, cfg)
        monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", 64 * 1024)
        out = als_train(ui, ii, r, 50, 34, cfg, mesh=self._mesh())
        np.testing.assert_allclose(out.user_factors, ref.user_factors,
                                   rtol=2e-3, atol=2e-4)

    def test_rank_128_smoke(self):
        """Config-5's rank on the 8-device mesh (CPU, 1 iteration): runs,
        shapes right, finite."""
        ui, ii, r, _ = synth_ratings(n_users=40, n_items=24, seed=2)
        cfg = ALSConfig(rank=128, iterations=1, reg=0.1, seed=0,
                        solver="chol")
        out = als_train(ui, ii, r, 40, 24, cfg, mesh=self._mesh())
        assert out.user_factors.shape == (40, 128)
        assert np.isfinite(out.user_factors).all()
        assert np.isfinite(out.item_factors).all()


def _run_loop(which):
    """One small run of the named loop (train | fold | grid | sharded),
    its compiled-program cache emptied first so that it traces here."""
    import jax

    from predictionio_tpu.online import foldin
    from predictionio_tpu.ops import als as als_mod, als_grid, als_sharded
    from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh

    for cache in (als_mod._get_train_loop, als_grid._get_grid_train_loop,
                  als_sharded.get_train_loop_sharded, foldin._fold_solver):
        cache.cache_clear()
    ui, ii, r, _ = synth_ratings(n_users=40, n_items=24, seed=6)
    r = np.abs(r) + 0.5  # confidences: positive
    # implicit, with split rows: the YtY term and the accumulators' solve
    cfg = ALSConfig(rank=4, iterations=2, reg=0.05, seed=1, implicit=True,
                    alpha=2.0, solver="chol", split_cap=8)
    one = make_mesh({DATA_AXIS: 1, MODEL_AXIS: 1}, devices=jax.devices()[:1])
    if which == "train":
        return als_train(ui, ii, r, 40, 24, cfg, mesh=one).user_factors
    if which == "grid":
        cfgs = [cfg, dataclasses.replace(cfg, reg=0.2)]
        return als_grid.als_train_grid(ui, ii, r, 40, 24, cfgs,
                                       mesh=one)[0].user_factors
    if which == "sharded":
        mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 2},
                         devices=jax.devices()[:4])
        return als_train(ui, ii, r, 40, 24, cfg, mesh=mesh).user_factors
    assert which == "fold"
    rng = np.random.default_rng(2)
    opposing = rng.normal(size=(24, 4)).astype(np.float32)
    entries = [(rng.choice(24, n, replace=False).astype(np.int32),
                rng.uniform(1, 5, n).astype(np.float32)) for n in (3, 5, 2)]
    return foldin.solve_rows(opposing, entries, cfg)


LOOPS = pytest.mark.parametrize("which", ["train", "fold", "grid", "sharded"])


class TestOneHalfIteration:
    """A row's normal equations are formed (`als._partial_normal_eqs`) and
    regularised and solved (`als._regularise_and_solve`) in one place for
    the train, the fold, the grid and the model-sharded loop."""

    @LOOPS
    def test_every_loop_reaches_the_shared_functions(self, monkeypatch,
                                                     which):
        from predictionio_tpu.ops import als as als_mod

        calls = {"_partial_normal_eqs": [], "_regularise_and_solve": []}
        for name, seen in calls.items():
            def spy(*args, _real=getattr(als_mod, name), _seen=seen, **kw):
                _seen.append(args[0].ndim)
                return _real(*args, **kw)

            monkeypatch.setattr(als_mod, name, spy)
        out = _run_loop(which)
        assert np.isfinite(out).all()
        formed, solved = calls.values()
        assert formed and solved
        # a grid hands them its G axis; the others are a train's shapes
        assert set(formed) == ({4} if which == "grid" else {3})
        assert set(solved) == ({4} if which == "grid" else {3})
        if which != "fold":  # the split rows' accumulators: solved once more
            assert len(solved) > len(formed)

    @pytest.mark.parametrize("module", ["als_grid", "als_sharded"])
    def test_the_other_loops_hold_no_normal_equations(self, module):
        import importlib
        import inspect

        src = inspect.getsource(importlib.import_module(
            f"predictionio_tpu.ops.{module}"))
        assert "solve_spd" not in src and "_gather_rows_grid" not in src
        assert "def put_buckets" not in src
        if module == "als_grid":
            assert "einsum(" not in src
        else:  # YtY under its psum, and the prediction of `sq_err`
            assert src.count("ne_einsum(") == 1
            assert src.count("einsum(") == 3

    @LOOPS
    def test_one_precision_reaches_every_loop(self, monkeypatch, which):
        """The way in of `perf/tests/control_precision.py::at_precision`:
        replace `als.normal_eq_einsum`, clear the loop. The grid and the
        model-sharded loop bound the name at import until PR 47 and kept
        their own precision."""
        import functools

        import jax.numpy as jnp

        from predictionio_tpu.ops import als as als_mod

        subscripts = []

        def recording(compute_dtype):
            def einsum(spec, *operands, **kw):
                subscripts.append(spec)
                return jnp.einsum(spec, *operands, **kw)

            return functools.partial(
                einsum, preferred_element_type=jnp.float32, precision=None)

        monkeypatch.setattr(als_mod, "normal_eq_einsum", recording)
        _run_loop(which)
        g = "g" if which == "grid" else ""
        want = {f"rc{g}k,rc{g},rc{g}l->r{g}kl", f"rc{g}k,rc{g}->r{g}k",
                f"c{g}k,c{g}l->{g}kl"}
        if which == "fold":  # explicit or implicit, a fold's YtY is there too
            assert want <= set(subscripts)
        else:
            assert set(subscripts) == want


class TestPlaceBuckets:
    """`als.place_buckets`: the one function that pads a side's buckets to
    the walk and puts them on the device."""

    @staticmethod
    def _buckets(rows):
        rng = np.random.default_rng(0)

        def bucket(r, cap, split):
            return Bucket(
                rows=np.arange(r, dtype=np.int32),
                cols=rng.integers(1, 50, (r, cap)).astype(np.int32),
                vals=rng.uniform(1, 5, (r, cap)).astype(np.float32),
                mask=np.ones((r, cap), np.float32),
                segmap=(np.arange(r, dtype=np.int32) % 4 if split else None))

        return [bucket(rows, 8, True), bucket(rows, 16, False),
                bucket(8, 32, False)]

    # rule -> (the chunk of (rows, cap), the budget, the rows of the two
    # buckets that walk, the heights the three are placed with)
    RULES = {
        # 4 KiB at rank 4: 32 rows 8 wide (two trips of 24), 16 rows 16 wide
        "train": (lambda als, r, cap: als._bucket_chunk_rows(r, cap, 4, 8),
                  1 << 12, 40, [48, 48, 8]),
        # 8 KiB at 3 x rank 4: 16 rows 8 wide (three trips), 8 rows 16 wide
        "grid": (lambda als, r, cap: als._bucket_chunk_rows(r, cap, 3 * 4, 8),
                 1 << 13, 40, [48, 40, 8]),
        # two data shards of 22 rows, in units of 2: whole 8 wide, two
        # trips of 12 each 16 wide
        "model_sharded": (
            lambda als, r, cap: 2 * als._bucket_chunk_rows(r // 2, cap, 4, 2),
            1 << 12, 44, [44, 48, 8]),
    }

    @pytest.mark.parametrize("rule", list(RULES))
    def test_pads_with_the_sentinels_under_each_chunk_rule(self, monkeypatch,
                                                           rule):
        import jax

        from predictionio_tpu.ops import als as als_mod

        chunk, budget, rows, heights = self.RULES[rule]
        monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", budget)
        buckets = self._buckets(rows)
        split = np.asarray([3, 9, 11, 30], np.int32)
        here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        chunks = [chunk(als_mod, *b.cols.shape) for b in buckets]
        assert any(c < b.cols.shape[0] for c, b in zip(chunks, buckets))
        placed, split_dev = als_mod.place_buckets(
            "user", buckets, 77, split,
            lambda r, cap: chunk(als_mod, r, cap), here, here)
        np.testing.assert_array_equal(np.asarray(split_dev), split)
        assert [p[0].shape[0] for p in placed] == heights
        for b, p, c in zip(buckets, placed, chunks):
            rows, cols, vals, mask, segmap = (
                None if a is None else np.asarray(a) for a in p)
            r = b.cols.shape[0]
            assert rows.shape[0] % c == 0 and rows.shape[0] - r < c
            # the walk recomputes the chunk from the padded height
            assert chunk(als_mod, rows.shape[0], b.cap) == c
            for got, want in ((rows, b.rows), (cols, b.cols), (vals, b.vals),
                              (mask, b.mask)):
                np.testing.assert_array_equal(got[:r], want)
                assert got.dtype == want.dtype
            assert (rows[r:] == 77).all()  # dropped by the scatter
            assert not cols[r:].any() and not vals[r:].any()
            assert not mask[r:].any()
            if b.segmap is None:
                assert segmap is None
            else:
                np.testing.assert_array_equal(segmap[:r], b.segmap)
                assert (segmap[r:] == len(split)).all()
        assert als_mod.BUCKET_WALK_CELLS.labels(side="user").value == sum(
            h * b.cap for h, b in zip(heights, buckets))

    @pytest.mark.parametrize("which", ["train", "grid"])
    def test_a_train_and_a_grid_set_the_walk_gauge(self, monkeypatch, which):
        """A grid pads at n_grid x rank and reports what it placed (until
        PR 47 only `als_train` set the gauge)."""
        from predictionio_tpu.ops import als as als_mod, als_grid

        ui, ii, r, _ = synth_ratings(n_users=40, n_items=25, seed=4,
                                     density=0.4)
        monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", 1 << 12)
        for side in ("user", "item"):
            als_mod.BUCKET_WALK_CELLS.labels(side=side).set(-1)
        loops = (als_mod._get_train_loop, als_grid._get_grid_train_loop)
        for loop in loops:
            loop.cache_clear()
        cfg = ALSConfig(rank=4, iterations=1, reg=0.05, seed=1)
        placed = []
        real = als_mod.place_buckets

        def spy(side, *args):
            out = real(side, *args)
            placed.append((side, sum(
                b[1].shape[0] * b[1].shape[1] for b in out[0])))
            return out

        monkeypatch.setattr(als_mod, "place_buckets", spy)
        monkeypatch.setattr(als_grid, "place_buckets", spy)
        if which == "train":
            als_train(ui, ii, r, 40, 25, cfg)
        else:
            als_grid.als_train_grid(
                ui, ii, r, 40, 25,
                [cfg, dataclasses.replace(cfg, reg=0.2)])
        for loop in loops:
            loop.cache_clear()
        assert [side for side, _ in placed] == ["user", "item"]
        for side, cells in placed:
            assert als_mod.BUCKET_WALK_CELLS.labels(
                side=side).value == cells > 0
            # padded to the walk: at least the bucketizer's own cells
            assert cells >= als_mod.BUCKET_CELLS.labels(side=side).value > 0

"""Native C++ data loader vs the numpy reference implementation —
bit-identical bucketization (SURVEY.md §2.5: native host-side loader as
the rebuild's runtime-native component)."""

import numpy as np
import pytest

from predictionio_tpu import native
from predictionio_tpu.ops import als


def _numpy_path():
    """Force the numpy path regardless of native availability."""
    import unittest.mock as mock

    return mock.patch.object(native, "bucket_ragged_native",
                             return_value=None)


def _python_buckets(rows, cols, vals, n_rows, row_multiple=8, max_cap=None,
                    cap_growth=1.5):
    with _numpy_path():
        return als.bucket_ragged(rows, cols, vals, n_rows,
                                 row_multiple, max_cap,
                                 cap_growth=cap_growth)


needs_native = pytest.mark.skipif(not native.native_available(),
                                  reason="no C++ toolchain")


def synth(n, n_rows, n_cols, seed, zipf=False):
    rng = np.random.default_rng(seed)
    if zipf:
        raw = rng.zipf(1.5, n).astype(np.int64)
        rows = (raw % n_rows).astype(np.int32)
    else:
        rows = rng.integers(0, n_rows, n).astype(np.int32)
    cols = rng.integers(0, n_cols, n).astype(np.int32)
    vals = rng.uniform(1, 5, n).astype(np.float32)
    return rows, cols, vals


@needs_native
class TestNativeBucketize:
    @pytest.mark.parametrize("seed,zipf", [(0, False), (1, True), (2, True)])
    @pytest.mark.parametrize("row_multiple", [8, 16])
    def test_bit_identical_to_python(self, seed, zipf, row_multiple):
        rows, cols, vals = synth(5000, 300, 200, seed, zipf)
        py = _python_buckets(rows, cols, vals, 300, row_multiple)
        nat = native.bucket_ragged_native(rows, cols, vals, 300, row_multiple)
        assert nat is not None
        nat = nat.buckets
        assert len(py) == len(nat)
        for pb, nb in zip(py, nat):
            np.testing.assert_array_equal(pb.rows, nb.rows)
            np.testing.assert_array_equal(pb.cols, nb.cols)
            np.testing.assert_array_equal(pb.vals, nb.vals)
            np.testing.assert_array_equal(pb.mask, nb.mask)

    def test_max_cap_truncation_matches(self):
        rows, cols, vals = synth(4000, 50, 100, 3, zipf=True)
        py = _python_buckets(rows, cols, vals, 50, max_cap=16)
        nat = native.bucket_ragged_native(rows, cols, vals, 50, 8, 16).buckets
        assert len(py) == len(nat)
        for pb, nb in zip(py, nat):
            np.testing.assert_array_equal(pb.cols, nb.cols)
            np.testing.assert_array_equal(pb.vals, nb.vals)

    def test_non_pow2_max_cap(self):
        rows, cols, vals = synth(3000, 40, 60, 4, zipf=True)
        py = _python_buckets(rows, cols, vals, 40, max_cap=100)
        nat = native.bucket_ragged_native(rows, cols, vals, 40, 8,
                                          100).buckets
        assert len(py) == len(nat)
        assert [b.cap for b in py] == [b.cap for b in nat]
        for pb, nb in zip(py, nat):
            np.testing.assert_array_equal(pb.mask, nb.mask)

    def test_out_of_range_rows_fall_back(self):
        # row id >= n_rows: native defers to numpy so behavior is the
        # same with and without a toolchain
        rows = np.array([0, 5], dtype=np.int32)  # 5 >= n_rows=3
        cols = np.zeros(2, np.int32)
        vals = np.ones(2, np.float32)
        assert native.bucket_ragged_native(rows, cols, vals, 3) is None

    def test_empty_input(self):
        nat = native.bucket_ragged_native(
            np.zeros(0, np.int32), np.zeros(0, np.int32),
            np.zeros(0, np.float32), 10)
        assert nat.buckets == []

    def test_single_row_all_entries(self):
        rows = np.zeros(37, np.int32)
        cols = np.arange(37, dtype=np.int32)
        vals = np.ones(37, np.float32)
        py = _python_buckets(rows, cols, vals, 1)
        nat = native.bucket_ragged_native(rows, cols, vals, 1).buckets
        assert len(nat) == 1 and nat[0].cap == 40  # 1.5 ladder: 8,16,24,40
        np.testing.assert_array_equal(py[0].cols, nat[0].cols)
        nat2 = native.bucket_ragged_native(rows, cols, vals, 1,
                                           cap_growth=2.0).buckets
        assert nat2[0].cap == 64  # pow2 ladder

    def test_als_train_uses_native_and_converges(self):
        # end-to-end: als_train with the native loader reaches the same
        # factors as with the numpy loader
        from tests.test_als import synth_ratings

        ui, ii, r, _ = synth_ratings(n_users=40, n_items=30, seed=5)
        cfg = als.ALSConfig(rank=4, iterations=3, reg=0.05, seed=1)
        out_native = als.als_train(ui, ii, r, 40, 30, cfg)
        import unittest.mock as mock

        with mock.patch.object(native, "bucket_ragged_native",
                               return_value=None):
            out_py = als.als_train(ui, ii, r, 40, 30, cfg)
        np.testing.assert_allclose(out_native.user_factors,
                                   out_py.user_factors, rtol=1e-5, atol=1e-6)


class TestFallback:
    def test_env_disable(self, monkeypatch):
        monkeypatch.setenv("PIO_NATIVE", "0")
        assert native.get_lib() is None
        assert native.bucket_ragged_native(
            np.zeros(1, np.int32), np.zeros(1, np.int32),
            np.ones(1, np.float32), 1) is None


@needs_native
class TestCapGrowthParity:
    """The C++ ladder must match numpy bit-for-bit at every growth."""

    @pytest.mark.parametrize("growth", [2.0, 1.5, 1.25])
    def test_ladder_parity(self, growth):
        rows, cols, vals = synth(5000, 300, 200, seed=11, zipf=True)
        py = _python_buckets(rows, cols, vals, 300, cap_growth=growth)
        nat = native.bucket_ragged_native(rows, cols, vals, 300,
                                          cap_growth=growth)
        assert nat is not None
        nat = nat.buckets
        assert len(py) == len(nat)
        for pb, nb in zip(py, nat):
            np.testing.assert_array_equal(pb.rows, nb.rows)
            np.testing.assert_array_equal(pb.cols, nb.cols)
            np.testing.assert_array_equal(pb.vals, nb.vals)
            np.testing.assert_array_equal(pb.mask, nb.mask)


def _calls(side, path):
    return als.BUCKETIZE_CALLS.labels(side=side, path=path).value


def _coo(n, n_rows, n_cols, seed, hot=()):
    """Uniform entries, then `hot`'s (row, count) pairs topped up to
    exactly `count` entries each, all of it shuffled."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n_rows, n).astype(np.int32)
    for row, count in hot:
        rows = rows[rows != row]
        rows = np.concatenate([rows, np.full(count, row, np.int32)])
    rows = rows[rng.permutation(len(rows))]
    cols = rng.integers(0, n_cols, len(rows)).astype(np.int32)
    vals = rng.uniform(1, 5, len(rows)).astype(np.float32)
    return rows, cols, vals


def _duplicate_pairs():
    # every (row, column) pair four times over, a different value each:
    # ties in the column sort must keep the caller's order
    rows, cols, _ = _coo(300, 12, 20, 21, hot=[(5, 40)])
    rows, cols = np.tile(rows, 4), np.tile(cols, 4)
    vals = np.arange(len(rows), dtype=np.float32)
    return rows, cols, vals, 12


def _column_zero():
    # a third of the entries sit in column 0 and every row is padded: the
    # padding goes after the real column-0 entries, before the rest
    rows, cols, vals = _coo(900, 25, 30, 22, hot=[(7, 70)])
    cols[::3] = 0
    return rows, cols, vals, 25


# name: (rows, cols, vals, n_rows), split_cap, max_cap, the column path
SPLIT_CASES = {
    "no_hot_row": (lambda: (*_coo(2000, 60, 80, 12), 60), 64, None,
                   "native_counting"),
    "no_split_cap": (lambda: (*_coo(2000, 60, 80, 13), 60), None, None,
                     "native_counting"),
    "one_hot_row": (lambda: (*_coo(1500, 60, 80, 14, hot=[(3, 100)]), 60),
                    32, None, "native_counting"),
    "several_hot_rows": (
        lambda: (*_coo(1500, 60, 80, 15,
                       hot=[(0, 45), (17, 200), (59, 33)]), 60),
        32, None, "native_counting"),
    "exact_multiple_of_split_cap": (
        lambda: (*_coo(1500, 60, 80, 16, hot=[(9, 96), (10, 32)]), 60),
        32, None, "native_counting"),
    "every_row_hot": (lambda: (*_coo(800, 6, 40, 17), 6), 8, None,
                      "native_counting"),
    "duplicate_pairs": (_duplicate_pairs, 32, None, "native_counting"),
    "column_zero_in_padded_rows": (_column_zero, 32, None,
                                   "native_counting"),
    "max_cap": (lambda: (*_coo(1500, 40, 80, 18, hot=[(4, 150)]), 40),
                None, 20, "native_counting"),
    # the online fold's shape: a few histories against a catalog
    "few_entries_wide_columns": (
        lambda: (*_coo(200, 16, 1_000_000, 19, hot=[(2, 60)]), 16),
        32, None, "native_comparison"),
    "few_entries_wide_columns_max_cap": (
        lambda: (*_coo(200, 16, 1_000_000, 20, hot=[(2, 60)]), 16),
        None, 24, "native_comparison"),
}


@needs_native
class TestNativeSplitParity:
    """The one-call native bucketizer (split, group, column-sort) against
    the numpy `bucket_ragged_split` / `bucket_ragged`, every array bit
    for bit."""

    @pytest.mark.parametrize("cap_growth", [1.5, 2.0])
    @pytest.mark.parametrize("row_multiple", [8, 32])
    @pytest.mark.parametrize("case", list(SPLIT_CASES))
    def test_bit_identical_to_numpy(self, case, row_multiple, cap_growth):
        make, split_cap, max_cap, path = SPLIT_CASES[case]
        rows, cols, vals, n_rows = make()

        def run():
            if max_cap is not None:
                return (als.bucket_ragged(rows, cols, vals, n_rows,
                                          row_multiple, max_cap, cap_growth,
                                          side=case),
                        np.zeros(0, np.int32))
            return als.bucket_ragged_split(rows, cols, vals, n_rows,
                                           row_multiple, split_cap,
                                           cap_growth, side=case)

        before = _calls(case, path), _calls(case, "numpy")
        nat, nat_split = run()
        assert (_calls(case, path), _calls(case, "numpy")) == (
            before[0] + 1, before[1])
        with _numpy_path():
            ref, ref_split = run()
        assert _calls(case, "numpy") == before[1] + 1

        assert nat_split.dtype == ref_split.dtype
        np.testing.assert_array_equal(nat_split, ref_split)
        assert [b.cols.shape for b in nat] == [b.cols.shape for b in ref]
        for nb, rb in zip(nat, ref):
            for field in ("rows", "cols", "vals", "mask"):
                got, want = getattr(nb, field), getattr(rb, field)
                assert got.dtype == want.dtype, field
                assert got.tobytes() == want.tobytes(), field
            assert (nb.segmap is None) == (rb.segmap is None)
            if rb.segmap is not None:
                assert nb.segmap.dtype == rb.segmap.dtype
                np.testing.assert_array_equal(nb.segmap, rb.segmap)
        # the cases are what their names say
        hot = np.bincount(rows, minlength=n_rows) > (split_cap or len(rows))
        assert (len(ref_split) > 0) == bool(hot.any())
        assert any(b.segmap is not None for b in ref) == bool(hot.any())

    @pytest.mark.parametrize("cols_bad,rows_bad", [(-1, 0), (0, -1), (0, 9)])
    def test_ids_out_of_range_decline(self, cols_bad, rows_bad):
        rows = np.array([0, rows_bad, 2], np.int32)
        cols = np.array([0, cols_bad, 5], np.int32)
        vals = np.ones(3, np.float32)
        assert native.bucket_ragged_native(rows, cols, vals, 9,
                                           split_cap=2) is None

    def test_more_than_63_caps_decline_and_the_call_says_numpy(self):
        # a growth this small climbs the ladder 8 at a time: 70 rows of
        # 8, 16, ... 560 entries are 70 bucket shapes
        rows = np.repeat(np.arange(70, dtype=np.int32),
                         8 * np.arange(1, 71))
        cols = np.arange(len(rows), dtype=np.int32) % 97
        vals = np.ones(len(rows), np.float32)
        assert native.bucket_ragged_native(rows, cols, vals, 70,
                                           cap_growth=1.001) is None
        before = _calls("caps", "numpy")
        buckets = als.bucket_ragged(rows, cols, vals, 70, cap_growth=1.001,
                                    side="caps")
        assert len(buckets) == 70
        assert _calls("caps", "numpy") == before + 1

    def test_max_cap_with_split_cap_declines(self):
        rows, cols, vals = _coo(100, 5, 10, 1)
        assert native.bucket_ragged_native(rows, cols, vals, 5, 8, 4,
                                           split_cap=8) is None

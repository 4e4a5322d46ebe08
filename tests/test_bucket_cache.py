"""ALS bucket cache (VERDICT r2 #5): the host bucketize result is reused
across trains under a fingerprint of the training data + bucketizer
inputs, skipped on any change, and survives corruption."""

import logging
import threading

import numpy as np
import pytest

from predictionio_tpu.ops import als, als_grid
from predictionio_tpu.ops.als import ALSConfig, als_train


def _data(seed=0, nnz=800, n_u=40, n_i=30):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_u, nnz).astype(np.int32),
            rng.integers(0, n_i, nnz).astype(np.int32),
            rng.uniform(1, 5, nnz).astype(np.float32), n_u, n_i)


CFG = ALSConfig(rank=6, iterations=2, reg=0.05, seed=0, solver="chol",
                split_cap=16)


class TestBucketCache:
    def test_hit_after_miss_and_identical_factors(self, tmp_path, caplog):
        ui, ii, r, n_u, n_i = _data()
        cache = str(tmp_path / "cache")
        with caplog.at_level(logging.INFO, "predictionio_tpu.ops.als"):
            a = als_train(ui, ii, r, n_u, n_i, CFG, bucket_cache_dir=cache)
            assert any("bucket cache miss" in m for m in caplog.messages)
            caplog.clear()
            b = als_train(ui, ii, r, n_u, n_i, CFG, bucket_cache_dir=cache)
            assert any("bucket cache hit" in m for m in caplog.messages)
        np.testing.assert_array_equal(a.user_factors, b.user_factors)
        np.testing.assert_array_equal(a.item_factors, b.item_factors)

    @pytest.mark.parametrize("mutate", ["ratings", "split_cap", "growth"])
    def test_invalidation(self, tmp_path, caplog, mutate):
        import dataclasses

        ui, ii, r, n_u, n_i = _data()
        cache = str(tmp_path / "cache")
        als_train(ui, ii, r, n_u, n_i, CFG, bucket_cache_dir=cache)
        cfg = CFG
        if mutate == "ratings":  # one new/changed event must invalidate
            r = r.copy()
            r[0] += 1.0
        elif mutate == "split_cap":
            cfg = dataclasses.replace(CFG, split_cap=24)
        else:
            cfg = dataclasses.replace(CFG, cap_growth=2.0)
        with caplog.at_level(logging.INFO, "predictionio_tpu.ops.als"):
            als_train(ui, ii, r, n_u, n_i, cfg, bucket_cache_dir=cache)
        assert any("bucket cache miss" in m for m in caplog.messages)
        assert not any("bucket cache hit" in m for m in caplog.messages)

    def test_corrupt_cache_rebuckets(self, tmp_path, caplog):
        ui, ii, r, n_u, n_i = _data()
        cache = tmp_path / "cache"
        ref = als_train(ui, ii, r, n_u, n_i, CFG, bucket_cache_dir=str(cache))
        (entry,) = cache.glob("*.npz")
        entry.write_bytes(b"not an npz")
        with caplog.at_level(logging.WARNING, "predictionio_tpu.ops.als"):
            out = als_train(ui, ii, r, n_u, n_i, CFG,
                            bucket_cache_dir=str(cache))
        assert any("unreadable" in m for m in caplog.messages)
        np.testing.assert_array_equal(out.user_factors, ref.user_factors)

    def test_truncated_zip_rebuckets(self, tmp_path, caplog):
        """Corruption AFTER the zip magic (BadZipFile, not ValueError)
        must also fall back instead of crashing the train."""
        ui, ii, r, n_u, n_i = _data()
        cache = tmp_path / "cache"
        ref = als_train(ui, ii, r, n_u, n_i, CFG, bucket_cache_dir=str(cache))
        (entry,) = cache.glob("*.npz")
        entry.write_bytes(entry.read_bytes()[:100])  # keeps PK magic
        with caplog.at_level(logging.WARNING, "predictionio_tpu.ops.als"):
            out = als_train(ui, ii, r, n_u, n_i, CFG,
                            bucket_cache_dir=str(cache))
        assert any("unreadable" in m for m in caplog.messages)
        np.testing.assert_array_equal(out.user_factors, ref.user_factors)

    def test_gc_keeps_newest(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PIO_BUCKET_CACHE_KEEP", "2")
        cache = tmp_path / "cache"
        for seed in range(4):
            ui, ii, r, n_u, n_i = _data(seed=seed)
            als_train(ui, ii, r, n_u, n_i, CFG, bucket_cache_dir=str(cache))
        assert len(list(cache.glob("*.npz"))) == 2

    def test_mesh_shape_invalidates(self, tmp_path, caplog):
        """row_multiple depends on the mesh axes; a cache built for one
        mesh must not feed a differently-aligned one."""
        import jax

        from predictionio_tpu.parallel.mesh import make_mesh

        ui, ii, r, n_u, n_i = _data()
        cache = str(tmp_path / "cache")
        m1 = make_mesh({"data": 1, "model": 1}, devices=jax.devices()[:1])
        als_train(ui, ii, r, n_u, n_i, CFG, mesh=m1, bucket_cache_dir=cache)
        m2 = make_mesh({"data": 4, "model": 2})
        with caplog.at_level(logging.INFO, "predictionio_tpu.ops.als"):
            out = als_train(ui, ii, r, n_u, n_i, CFG, mesh=m2,
                            bucket_cache_dir=cache)
        assert any("bucket cache miss" in m for m in caplog.messages)
        assert np.isfinite(out.user_factors).all()


# -- PR 45's tree digest renamed every entry (`_BUCKET_CACHE_VERSION` 2):
# -- what an older build saved is never loaded, and the GC removes it


def _digest_before_pr45(*arrays):
    """`_arrays_digest` as it stood under `_BUCKET_CACHE_VERSION` 1."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("digest,version", [
    (_digest_before_pr45, 1), (als._arrays_digest, 1),
    (_digest_before_pr45, als._BUCKET_CACHE_VERSION),
], ids=["an_older_builds_key", "todays_digest_under_version_1",
        "the_old_digest_under_todays_version"])
def test_an_entry_under_an_older_builds_key_is_not_loaded_and_is_pruned(
        tmp_path, monkeypatch, caplog, digest, version):
    import hashlib
    import os

    monkeypatch.setenv("PIO_BUCKET_CACHE_KEEP", "1")
    ui, ii, r, n_u, n_i = _data()
    cache = tmp_path / "cache"
    assert als._BUCKET_CACHE_VERSION == 2
    old_key = hashlib.blake2b(
        (digest(ui, ii, r) + repr((n_u, n_i, 8, CFG.split_cap,
                                   CFG.cap_growth, version))).encode(),
        digest_size=16).hexdigest()
    # the orphan holds another train's buckets: loaded, it would show
    other = _data(seed=5)
    als._bucket_cache_save(str(cache), old_key, *als.bucketize_cached(
        *other, 8, CFG.split_cap, CFG.cap_growth, None))
    orphan = cache / f"{old_key}.npz"
    os.utime(orphan, (1.0e9, 1.0e9))
    ref = als_train(ui, ii, r, n_u, n_i, CFG)
    with caplog.at_level(logging.INFO, "predictionio_tpu.ops.als"):
        out = als_train(ui, ii, r, n_u, n_i, CFG,
                        bucket_cache_dir=str(cache))
    assert any("bucket cache miss" in m for m in caplog.messages)
    assert not any("bucket cache hit" in m for m in caplog.messages)
    np.testing.assert_array_equal(out.user_factors, ref.user_factors)
    np.testing.assert_array_equal(out.item_factors, ref.item_factors)
    (kept,) = cache.iterdir()  # keep-newest: today's entry, the orphan gone
    assert kept.name != orphan.name and kept.suffix == ".npz"


# -- a miss runs its pieces beside each other: the two sides on a thread
# -- each, the save behind the rest of the train, joined before it returns


def _train(entry, ui, ii, r, n_u, n_i, cache):
    """One train through `als_train` or the grid's `als_train_grid`."""
    if entry == "als_train":
        return als_train(ui, ii, r, n_u, n_i, CFG, bucket_cache_dir=cache)
    return als_grid.als_train_grid(ui, ii, r, n_u, n_i, [CFG],
                                   bucket_cache_dir=cache)[0]


def _break_the_loop(monkeypatch, entry, raised):
    """The train's device program raises when it is dispatched, which
    is after the save was started."""
    def boom(*a, **k):
        raised.set()
        raise RuntimeError("no chip")
    if entry == "als_train":
        monkeypatch.setattr(als, "_get_train_loop", lambda *a, **k: boom)
    else:
        monkeypatch.setattr(als_grid, "_get_grid_train_loop",
                            lambda *a, **k: boom)


class TestConcurrentMiss:
    @pytest.mark.parametrize("native", ["1", "0"],
                             ids=["native", "PIO_NATIVE=0"])
    def test_both_sides_at_once_equal_the_two_calls_in_series(
            self, tmp_path, monkeypatch, native):
        monkeypatch.setenv("PIO_NATIVE", native)
        ui, ii, r, n_u, n_i = _data()
        got = als.bucketize_cached(ui, ii, r, n_u, n_i, 8, 16, 1.5,
                                   str(tmp_path / "cache"))
        want = (als.bucket_ragged_split(ui, ii, r, n_u, 8, 16,
                                        cap_growth=1.5, side="user")
                + als.bucket_ragged_split(ii, ui, r, n_i, 8, 16,
                                          cap_growth=1.5, side="item"))
        assert len(got) == len(want) == 4
        for got_split, want_split in zip(got[1::2], want[1::2]):
            assert len(want_split) > 0  # a split row on each side
            assert got_split.dtype == want_split.dtype
            np.testing.assert_array_equal(got_split, want_split)
        for got_buckets, want_buckets in zip(got[0::2], want[0::2]):
            assert len(got_buckets) == len(want_buckets)
            assert any(b.segmap is not None for b in want_buckets)
            for g, w in zip(got_buckets, want_buckets):
                for name in ("rows", "cols", "vals", "mask", "segmap"):
                    a, b = getattr(g, name), getattr(w, name)
                    assert (a is None) == (b is None), name
                    if b is not None:
                        assert a.dtype == b.dtype and a.shape == b.shape
                        np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("entry", ["als_train", "als_train_grid"])
    def test_the_entry_stands_when_the_train_returns(self, tmp_path, entry):
        ui, ii, r, n_u, n_i = _data()
        cache = tmp_path / "cache"
        before = set(threading.enumerate())
        a = _train(entry, ui, ii, r, n_u, n_i, str(cache))
        assert set(threading.enumerate()) == before
        (saved,) = cache.iterdir()  # one entry, no `.tmp` beside it
        assert saved.suffix == ".npz"
        assert als._bucket_cache_load(str(cache), saved.stem) is not None
        b = _train(entry, ui, ii, r, n_u, n_i, str(cache))
        assert [p.name for p in cache.iterdir()] == [saved.name]
        np.testing.assert_array_equal(a.user_factors, b.user_factors)
        np.testing.assert_array_equal(a.item_factors, b.item_factors)

    @pytest.mark.parametrize("entry", ["als_train", "als_train_grid"])
    def test_a_loop_that_raises_is_left_with_a_whole_entry_and_no_thread(
            self, tmp_path, monkeypatch, entry):
        """The save is held until the loop has raised, so the raise
        finds it in flight: the way out still joins it."""
        ui, ii, r, n_u, n_i = _data()
        cache = tmp_path / "cache"
        raised = threading.Event()
        _break_the_loop(monkeypatch, entry, raised)
        real_save = als._bucket_cache_save

        def held_save(*a):
            assert raised.wait(30)
            return real_save(*a)
        monkeypatch.setattr(als, "_bucket_cache_save", held_save)
        before = set(threading.enumerate())
        with pytest.raises(RuntimeError, match="no chip"):
            _train(entry, ui, ii, r, n_u, n_i, str(cache))
        assert set(threading.enumerate()) == before
        (saved,) = cache.iterdir()
        assert saved.suffix == ".npz"
        assert als._bucket_cache_load(str(cache), saved.stem) is not None

    def test_a_save_that_cannot_write_warns_and_the_train_goes_on(
            self, tmp_path, monkeypatch, caplog):
        ui, ii, r, n_u, n_i = _data()
        ref = als_train(ui, ii, r, n_u, n_i, CFG)

        def full_disk(*a):
            raise OSError(28, "No space left on device")
        monkeypatch.setattr(als, "_bucket_cache_save", full_disk)
        before = set(threading.enumerate())
        with caplog.at_level(logging.WARNING, "predictionio_tpu.ops.als"):
            out = als_train(ui, ii, r, n_u, n_i, CFG,
                            bucket_cache_dir=str(tmp_path / "cache"))
        assert any("bucket cache save failed" in m and "continuing uncached"
                   in m for m in caplog.messages)
        assert set(threading.enumerate()) == before
        np.testing.assert_array_equal(out.user_factors, ref.user_factors)
        np.testing.assert_array_equal(out.item_factors, ref.item_factors)

    @pytest.mark.parametrize("piece", ["save", "side"])
    def test_any_other_error_of_a_worker_surfaces_in_the_caller(
            self, tmp_path, monkeypatch, piece):
        ui, ii, r, n_u, n_i = _data()

        def bug(*a, **k):
            raise ValueError("a bug, not a full disk")
        monkeypatch.setattr(als, "_bucket_cache_save" if piece == "save"
                            else "bucket_ragged_split", bug)
        before = set(threading.enumerate())
        with pytest.raises(ValueError, match="a bug"):
            als_train(ui, ii, r, n_u, n_i, CFG,
                      bucket_cache_dir=str(tmp_path / "cache"))
        assert set(threading.enumerate()) == before

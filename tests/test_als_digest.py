"""`ops/als.py::_arrays_digest`: a tree hash over leaves of a fixed size,
the leaves hashed side by side on `telemetry.spans.Worker` threads. The
value names bucket-cache entries and checkpoints, so it may depend on the
data, the dtypes, the shapes, `extra` and the leaf size, and on nothing
about the host that hashed it."""

import hashlib
import threading

import numpy as np
import pytest

from predictionio_tpu.ops import als
from predictionio_tpu.telemetry import spans
from predictionio_tpu.telemetry.registry import REGISTRY

LEAF = 4096  # the tests' leaf: above the 2047 bytes `hashlib` keeps the GIL for


@pytest.fixture(autouse=True)
def small_leaves(monkeypatch):
    monkeypatch.setattr(als, "_DIGEST_LEAF_BYTES", LEAF)


def cores(monkeypatch, n):
    """A host on which this process may run on `n` cores."""
    import os

    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(n)),
                        raising=False)


def coo(seed=0, n=5000):
    """Three arrays of several leaves each (5000 x 4 B = 4.9 leaves)."""
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 900, n).astype(np.int32),
            rng.integers(0, 700, n).astype(np.int32),
            rng.uniform(1, 5, n).astype(np.float32))


def reference(*arrays, extra="", leaf=LEAF):
    """The same tree written out serially: no thread, no numpy view."""
    root = hashlib.blake2b(digest_size=16)
    root.update(repr((len(arrays), leaf)).encode())
    for a in arrays:
        a = np.asarray(a)
        root.update(repr((a.dtype.str, a.shape)).encode())
        data = a.tobytes()  # C order, as a contiguous copy would lie
        for lo in range(0, len(data), leaf):
            root.update(hashlib.blake2b(data[lo:lo + leaf],
                                        digest_size=16).digest())
    root.update(extra.encode())
    return root.hexdigest()


def calls():
    got = dict(REGISTRY.get("als_digest_calls_total").collect())
    return {path: got.get((path,), 0) for path in ("parallel", "inline")}


@pytest.mark.parametrize("n_cores", [1, 2, 8, 64])
def test_the_value_is_the_same_on_any_number_of_workers(monkeypatch,
                                                        n_cores):
    u, i, r = coo()
    cores(monkeypatch, n_cores)
    started = []
    real = spans.Worker

    def counting(name, *a):
        started.append(name)
        return real(name, *a)
    monkeypatch.setattr(als, "Worker", counting)
    before = calls()
    got = als._arrays_digest(u, i, r, extra="x")
    assert got == reference(u, i, r, extra="x")
    assert isinstance(got, str) and len(got) == 32
    # 15 leaves: a worker a core, at most eight, none on a one-core host
    want = 0 if n_cores == 1 else min(n_cores, als._DIGEST_MAX_WORKERS)
    assert len(started) == len(set(started)) == want
    path = "inline" if n_cores == 1 else "parallel"
    assert calls() == {**before, path: before[path] + 1}


def flipped(arrays, k):
    """One byte of array k flipped, in its last leaf."""
    out = [a.copy() for a in arrays]
    out[k].view(np.uint8)[-3] ^= 0x10
    return out


CHANGES = {
    "a_byte_of_the_first_array": lambda a: (flipped(a, 0), ""),
    "a_byte_of_the_second_array": lambda a: (flipped(a, 1), ""),
    "a_byte_of_the_third_array": lambda a: (flipped(a, 2), ""),
    "two_arrays_swapped": lambda a: ([a[1], a[0], a[2]], ""),
    "a_dtype": lambda a: ([a[0].view(np.uint32), a[1], a[2]], ""),
    "a_shape": lambda a: ([a[0].reshape(2, -1), a[1], a[2]], ""),
    "extra": lambda a: (list(a), "rank 8"),
    "an_array_more": lambda a: (list(a) + [np.zeros(0, np.int32)], ""),
}


@pytest.mark.parametrize("what", sorted(CHANGES))
def test_the_value_changes_with(monkeypatch, what):
    cores(monkeypatch, 4)
    arrays = coo()
    base = als._arrays_digest(*arrays)
    assert base == als._arrays_digest(*(a.copy() for a in arrays))
    changed, extra = CHANGES[what](arrays)
    got = als._arrays_digest(*changed, extra=extra)
    assert got != base and got == reference(*changed, extra=extra)


@pytest.mark.parametrize("left,right", [
    (([1, 2], [3]), ([1], [2, 3])),
    (([1, 2, 3], []), ([], [1, 2, 3])),
    ((np.arange(3000), np.arange(3000, 4000)),
     (np.arange(2000), np.arange(2000, 4000))),
], ids=["one_element", "a_whole_array", "across_leaves"])
def test_an_element_that_moves_across_an_array_boundary_changes_the_value(
        left, right):
    """The concatenated bytes are the same; the old digest read only
    those."""
    as_int = [[np.asarray(a, np.int64) for a in side]
              for side in (left, right)]
    assert (b"".join(a.tobytes() for a in as_int[0])
            == b"".join(a.tobytes() for a in as_int[1]))
    assert als._arrays_digest(*as_int[0]) != als._arrays_digest(*as_int[1])


def test_the_value_changes_with_the_leaf_size(monkeypatch):
    arrays = coo()
    small = als._arrays_digest(*arrays)
    monkeypatch.setattr(als, "_DIGEST_LEAF_BYTES", 2 * LEAF)
    large = als._arrays_digest(*arrays)
    assert small != large and large == reference(*arrays, leaf=2 * LEAF)


@pytest.mark.parametrize("make", [
    lambda a: a[::2], lambda a: a.reshape(50, -1).T,
    lambda a: a.reshape(50, -1)[:, 3:40], lambda a: a[::-1],
], ids=["strided", "transposed", "a_column_block", "reversed"])
def test_a_non_contiguous_input_equals_its_contiguous_copy(monkeypatch, make):
    cores(monkeypatch, 4)
    view = make(coo()[0])
    assert not view.flags.c_contiguous
    copy = np.ascontiguousarray(view)
    assert (als._arrays_digest(view, extra="e")
            == als._arrays_digest(copy, extra="e")
            == reference(copy, extra="e"))


def test_a_contiguous_input_is_read_in_place(monkeypatch):
    """No `tobytes()`, no copy: every leaf handed to the hash is a view
    of the caller's array."""
    u = coo()[0]
    seen = []
    real = als._leaf_digest
    monkeypatch.setattr(als, "_leaf_digest",
                        lambda leaf: (seen.append(leaf), real(leaf))[1])
    als._arrays_digest(u)
    assert len(seen) == 5 and all(np.shares_memory(leaf, u) for leaf in seen)
    assert sum(leaf.nbytes for leaf in seen) == u.nbytes


@pytest.mark.parametrize("arrays", [
    (np.arange(LEAF // 8, dtype=np.int64),),   # one whole leaf
    (np.arange(7, dtype=np.int32),),
    (np.zeros((0, 3), np.float32),),
    (np.float32(2.5),),
    (),
    tuple(a[:300] for a in coo()),   # three leaves, 3600 bytes in all
], ids=["one_leaf", "a_few_bytes", "no_bytes", "a_scalar", "no_array",
        "three_small_arrays"])
def test_an_input_of_one_leaf_or_fewer_starts_no_thread(monkeypatch, arrays):
    cores(monkeypatch, 8)
    monkeypatch.setattr(als, "Worker", None)  # a thread made would raise
    before = calls()
    assert als._arrays_digest(*arrays) == reference(*arrays)
    assert calls() == {**before, "inline": before["inline"] + 1}


def test_an_input_of_several_leaves_counts_parallel_and_records_its_path(
        monkeypatch):
    cores(monkeypatch, 3)
    before = calls()
    tl, token = spans.begin("test", "train", "RUN", "t-1")
    try:
        als._arrays_digest(*coo())
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    assert calls() == {**before, "parallel": before["parallel"] + 1}
    # one `als.digest` a call, a wall on the calling thread; the path's
    # record inside it; a worker opens no span at all
    got = {n: (s, s + d, nested) for n, s, d, _e, nested in tl.spans}
    assert sorted(n for n, *_ in tl.spans) == ["als.digest",
                                               "als.digest.parallel"]
    lo, hi, nested = got["als.digest"]
    assert nested is False and got["als.digest.parallel"][2] is True
    assert lo <= got["als.digest.parallel"][0]
    assert got["als.digest.parallel"][1] <= hi


@pytest.mark.parametrize("n_cores", [1, 4])
def test_a_leafs_error_reaches_the_caller_after_every_worker_has_ended(
        monkeypatch, n_cores):
    cores(monkeypatch, n_cores)
    u, i, r = coo()
    real = als._leaf_digest
    hashed = []

    def failing(leaf):
        if leaf.ctypes.data == i.ctypes.data + LEAF:
            raise MemoryError("leaf 1 of the second array")
        hashed.append(real(leaf))
        return hashed[-1]
    monkeypatch.setattr(als, "_leaf_digest", failing)
    before = set(threading.enumerate())
    counted = calls()
    with pytest.raises(MemoryError, match="leaf 1 of the second"):
        als._arrays_digest(u, i, r)
    assert set(threading.enumerate()) == before
    assert calls() == counted  # a digest that gave no value counts as none
    if n_cores > 1:
        # the other workers ran to their ends first: only the failing
        # worker's later leaves are missing (leaf 6 of 15, stride 4:
        # leaves 10 and 14 after it)
        assert len(hashed) == 15 - 1 - 2


def test_the_train_names_the_same_entry_on_another_hosts_cores(
        tmp_path, monkeypatch):
    """`bucketize_cached`'s key: an entry saved where one core hashes is
    hit where eight do."""
    u, i, r = coo()
    cache = tmp_path / "cache"
    cores(monkeypatch, 1)
    first = als.bucketize_cached(u, i, r, 900, 700, 8, None, 1.5, str(cache))
    (entry,) = cache.iterdir()
    cores(monkeypatch, 8)
    monkeypatch.setattr(als, "bucket_ragged_split", None)  # a miss would raise
    again = als.bucketize_cached(u, i, r, 900, 700, 8, None, 1.5, str(cache))
    assert [p.name for p in cache.iterdir()] == [entry.name]
    assert len(again[0]) == len(first[0]) and len(again[2]) == len(first[2])

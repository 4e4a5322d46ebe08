"""The seen-items map (`models/als_model.py::SeenItems`): the native
counting pass against the numpy steps that define it, array for array,
and what a train hands on."""

import pickle

import numpy as np
import pytest

from predictionio_tpu import native
from predictionio_tpu.controller import WorkflowContext
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models import als_model
from predictionio_tpu.models.als_model import SeenItems
from predictionio_tpu.telemetry import spans

needs_native = pytest.mark.skipif(not native.native_available(),
                                  reason="no C++ toolchain")


def _builds(path):
    return als_model.SEEN_ITEMS_BUILDS.labels(path=path).value


def _numpy_built(users, items, n_users):
    """The map as the numpy steps alone build it."""
    seen = SeenItems.__new__(SeenItems)
    seen._group_rows_numpy(users, items, n_users)
    return seen


def _random(n, n_users, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_users, n).astype(np.int32),
            rng.integers(0, 50, n).astype(np.int32), n_users)


def _empty_users():
    # rows 0-2, 10-12 and 27-29 of 30 hold nothing
    users, items, _ = _random(400, 24, seed=1)
    users = users + 3
    users[(users >= 10) & (users <= 12)] = 13
    return users, items, 30


def _duplicates():
    users, items, n_users = _random(300, 9, seed=2)
    return np.tile(users, 3), np.tile(items % 4, 3), n_users


def _sorted(reverse):
    users, items, n_users = _random(500, 40, seed=3)
    users = np.sort(users)
    return (users[::-1].copy() if reverse else users), items, n_users


def _int64():
    users, items, n_users = _random(300, 17, seed=4)
    return users.astype(np.int64), items.astype(np.int64), n_users


def _strided():
    users, items, n_users = _random(600, 17, seed=5)
    return users[::2], items[1::2], n_users


def _wide_id():
    # as an int32 it would read 3: a row inside the range
    users, items, n_users = _int64()
    users[7] = 2 ** 32 + 3
    return users, items, n_users


def _with_row(row):
    def make():
        users, items, n_users = _random(200, 11, seed=6)
        users[5] = row
        return users, items, n_users
    return make


# name → (users, items, n_users) maker, the path the build has to take
CASES = {
    "no_entries": (lambda: (np.zeros(0, np.int32), np.zeros(0, np.int32), 5),
                   "native"),
    "no_entries_no_users": (
        lambda: (np.zeros(0, np.int32), np.zeros(0, np.int32), 0), "native"),
    "random": (lambda: _random(5000, 300), "native"),
    "one_user": (lambda: _random(64, 1), "native"),
    "empty_users_start_middle_end": (_empty_users, "native"),
    "duplicate_pairs": (_duplicates, "native"),
    "already_sorted": (lambda: _sorted(False), "native"),
    "reverse_sorted": (lambda: _sorted(True), "native"),
    "int64_inputs": (_int64, "native"),
    "non_contiguous_inputs": (_strided, "native"),
    "lists": (lambda: ([2, 0, 2, 1], [7, 8, 9, 7], 3), "native"),
    "row_past_the_end": (_with_row(11), "numpy"),
    "row_far_past_the_end": (_with_row(2 ** 31 - 1), "numpy"),
    "negative_row": (_with_row(-1), "numpy"),
    "int64_row_that_wraps_into_range": (_wide_id, "numpy"),
    "PIO_NATIVE=0": (lambda: _random(5000, 300), "numpy"),
}


@needs_native
@pytest.mark.parametrize("case", list(CASES))
def test_the_build_equals_the_numpy_steps_array_for_array(case, monkeypatch):
    make, path = CASES[case]
    users, items, n_users = make()
    if case == "PIO_NATIVE=0":
        monkeypatch.setenv("PIO_NATIVE", "0")
    other = "numpy" if path == "native" else "native"
    before = _builds(path), _builds(other)
    got = SeenItems(users, items, n_users)
    assert (_builds(path), _builds(other)) == (before[0] + 1, before[1])

    want = _numpy_built(users, items, n_users)
    for field in ("_items", "_indptr"):
        a, b = getattr(got, field), getattr(want, field)
        assert a.dtype == b.dtype, field
        assert a.shape == b.shape, field
        assert a.flags.c_contiguous, field
        assert a.tobytes() == b.tobytes(), field
    assert got._items.dtype == np.int32 and got._indptr.dtype == np.int64
    assert len(got._indptr) == n_users + 1
    assert len(got) == len(want) == len(users)
    for row in range(-1, n_users + 1):
        a, b = got.get(row), want.get(row)
        assert (a is None) == (b is None), row
        assert a is None or a.tolist() == b.tolist(), row


def test_a_map_pickled_from_the_numpy_path_answers_under_the_change():
    users, items, n_users = _empty_users()
    blob = pickle.dumps(_numpy_built(users, items, n_users))
    there = pickle.loads(blob)
    here = pickle.loads(pickle.dumps(SeenItems(users, items, n_users)))
    assert sorted(vars(there)) == sorted(vars(here)) == ["_indptr", "_items"]
    assert len(there) == len(users) and there
    for row in range(n_users):
        want = items[users == row]
        got = there.get(row, np.empty(0, np.int32))
        assert got.tolist() == want.tolist() == here.get(
            row, np.empty(0, np.int32)).tolist()
    assert there.get(0) is None and there.get(n_users) is None


def test_train_returns_the_finished_map_and_counts_its_build():
    from predictionio_tpu.templates.recommendation import engine

    rng = np.random.default_rng(7)
    n_users, n_items, n = 12, 9, 60
    users = rng.integers(0, n_users, n).astype(np.int32)
    items = rng.integers(0, n_items, n).astype(np.int32)
    pd = engine.PreparedData(
        user_idx=users, item_idx=items,
        ratings=rng.uniform(1, 5, n).astype(np.float32),
        user_ids=BiMap.string_int([f"u{k}" for k in range(n_users)]),
        item_ids=BiMap.string_int([f"i{k}" for k in range(n_items)]))
    algo = engine.ALSAlgorithm(engine.ALSAlgorithmParams(
        rank=4, numIterations=2, seed=1))
    path = "native" if native.native_available() else "numpy"
    before = _builds("native") + _builds("numpy"), _builds(path)

    tl, token = spans.begin("test", "train", "RUN", "t-1")
    try:
        model = algo.train(WorkflowContext(), pd)
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)

    assert (_builds("native") + _builds("numpy"), _builds(path)) == (
        before[0] + 1, before[1] + 1)
    seen = model.seen
    assert type(seen._items) is np.ndarray and seen._items.shape == (n,)
    assert type(seen._indptr) is np.ndarray
    assert seen._indptr.shape == (n_users + 1,) and seen._indptr[-1] == n
    want = _numpy_built(users, items, n_users)
    assert seen._items.tobytes() == want._items.tobytes()
    assert seen._indptr.tobytes() == want._indptr.tobytes()
    # the build's record lies inside the template's span
    at = {name: (start, start + seconds)
          for name, start, seconds, _error, _nested in tl.spans}
    outer, inner = at["model.seen_items"], at[f"model.seen_items.{path}"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]

"""The generator: the seed decides pairs, order and values; the degree
sequences, and with them the program's bucket shapes, are the
configuration's."""

import numpy as np
import pytest

from perf import data
from perf.tests.conftest import load


def test_same_seed_same_arrays(tiny_shape):
    a = data.make_ratings(tiny_shape, 2 ** 31 + 5)
    b = data.make_ratings(tiny_shape, 2 ** 31 + 5)
    c = data.make_ratings(tiny_shape, 2 ** 31 + 6)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[1], c[1])
    assert a[0].dtype == np.int32 and a[1].dtype == np.int32
    assert a[2].dtype == np.float32


def test_every_row_present_and_degrees_are_the_configurations(tiny_shape):
    n_users, n_items = data.table_heights(tiny_shape)
    du, di = data.degree_sequences(tiny_shape)
    for seed in (1, 2):
        u, i, _ = data.make_ratings(tiny_shape, seed)
        assert len(u) == tiny_shape["n_ratings"]
        got_u = np.bincount(u, minlength=n_users)
        got_i = np.bincount(i, minlength=n_items)
        assert got_u.min() >= 1 and got_i.min() >= 1
        assert np.array_equal(np.sort(got_u)[::-1], du)
        assert np.array_equal(np.sort(got_i)[::-1], di)


def test_two_seeds_give_buckets_of_identical_shapes(tiny_shape):
    from predictionio_tpu.ops import als

    n_users, n_items = data.table_heights(tiny_shape)
    shapes = []
    for seed in (3, 2 ** 31 + 4):
        u, i, v = data.make_ratings(tiny_shape, seed)
        ub, us, ib, isp = als.bucketize_cached(
            u, i, v, n_users, n_items, 8, 64, 1.5, None)
        shapes.append(([b.cols.shape for b in ub], len(us),
                       [b.cols.shape for b in ib], len(isp)))
    assert shapes[0] == shapes[1]
    assert shapes[0][3] > 0  # the busiest items are split into segments


@pytest.mark.parametrize("config", ["als64_ml20m", "als128i_ml20m"])
def test_marginals_are_as_the_configuration_states(config):
    shape = load("perf", "configs", config + ".json")["shape"]
    du, di = data.degree_sequences(shape)
    assert (len(du), len(di)) == (138493, 26744)
    assert du.sum() == di.sum() == 20000263
    assert (du.min(), du.max()) == (20, 9254)
    assert np.median(du) == 68 and abs(du.mean() - 144.4) < 0.1
    assert di.min() == 1 and 60000 < di.max() < 70000


@pytest.mark.parametrize("kind,lo,hi", [("half_star", 0.5, 5.0),
                                        ("view_buy", 1.0, 200.0)])
def test_values_follow_their_law(tiny_shape, kind, lo, hi):
    config = "als64_ml20m" if kind == "half_star" else "als128i_ml20m"
    shape = dict(tiny_shape,
                 values=load("perf", "configs", config + ".json")["shape"]["values"])
    _, _, v = data.make_ratings(shape, 9)
    assert v.min() >= lo and v.max() <= hi
    assert np.array_equal(v * 2, np.round(v * 2))  # half stars, whole counts

"""Ratings from a seed, in seconds: the benchmark's one data generator.

The two degree sequences (ratings per user, ratings per item) belong to
the configuration: they are quantiles of the laws its file states, scaled
to the ratings count, and do not depend on the seed. The seed decides
which user and which item gets which degree, who pairs with whom, the
order of the events and the values. The program's bucket shapes depend on
the degree sequences alone, so every seed of a configuration runs the
programs that its first run compiled.

Pairing is the configuration model of a bipartite graph: one stub per
rating on either side, one side shuffled. A (user, item) pair can come up
twice; MLlib's ALS, the program and the reference all sum such entries.

numpy only: the program is handed host arrays, as its DataSource would
hand them over.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _quantile_weights(law: dict, n: int) -> np.ndarray:
    """The law's value at n evenly spaced quantiles, largest first."""
    kind = law["law"]
    if kind == "zipf_mandelbrot":
        k = np.arange(n, dtype=np.float64)
        return (k + float(law["offset"])) ** -float(law["exponent"])
    if kind == "shifted_lognormal":
        inv = np.vectorize(NormalDist().inv_cdf)
        z = inv(1.0 - (np.arange(n, dtype=np.float64) + 0.5) / n)
        return float(law["shift"]) + np.exp(
            float(law["mu"]) + float(law["sigma"]) * z)
    raise ValueError(f"unknown degree law {kind!r}")


def degrees(law: dict, n: int, total: int) -> np.ndarray:
    """n whole degrees, largest first, that sum to `total` exactly and
    stay inside the law's `min` and `max`. A pure function of its
    arguments: no seed."""
    lo, hi = int(law.get("min", 1)), int(law.get("max", total))
    if not lo * n <= total <= hi * n:
        raise ValueError(f"{total} ratings do not fit {n} rows of "
                         f"{lo}..{hi} ratings each")
    w = _quantile_weights(law, n)
    # the scale c at which clip(c * w, lo, hi) sums to the total, by
    # bisection; then whole numbers by largest remainder
    c_lo, c_hi = 0.0, hi / w.min()
    for _ in range(200):
        c = 0.5 * (c_lo + c_hi)
        if np.clip(c * w, lo, hi).sum() < total:
            c_lo = c
        else:
            c_hi = c
    t = np.clip(c_hi * w, lo, hi)
    d = np.floor(t).astype(np.int64)
    short = total - int(d.sum())
    if short < 0:
        raise ValueError("degree sequence overshoots its total")
    frac = np.where(d < hi, t - d, -1.0)
    d[np.argsort(-frac, kind="stable")[:short]] += 1
    if int(d.sum()) != total or d.min() < lo or d.max() > hi:
        raise ValueError("degree sequence does not meet its total")
    return -np.sort(-d, kind="stable")


def degree_sequences(shape: dict) -> tuple[np.ndarray, np.ndarray]:
    """(ratings per user, ratings per item), each largest first."""
    return (degrees(shape["user_degrees"], int(shape["n_users"]),
                    int(shape["n_ratings"])),
            degrees(shape["item_degrees"], int(shape["n_items"]),
                    int(shape["n_ratings"])))


def table_heights(shape: dict) -> tuple[int, int]:
    return int(shape["n_users"]), int(shape["n_items"])


def _values(law: dict, rng, users, items, n_users, n_items) -> np.ndarray:
    n = len(users)
    f32 = np.float32
    kind = law["kind"]
    if kind == "half_star":
        # r = clip(round_half(mean + b_u + b_i + s<u*, v*> + noise), .5, 5)
        k = int(law["planted_rank"])
        u = rng.standard_normal((n_users, k), dtype=f32)
        v = rng.standard_normal((n_items, k), dtype=f32)
        bu = rng.standard_normal(n_users, dtype=f32) * f32(law["bias_std"])
        bi = rng.standard_normal(n_items, dtype=f32) * f32(law["bias_std"])
        r = rng.standard_normal(n, dtype=f32) * f32(law["noise_std"])
        r += f32(law["mean"])
        r += bu[users]
        r += bi[items]
        scale = f32(law["latent_std"] / np.sqrt(k))
        for j in range(k):
            r += scale * (u[:, j][users] * v[:, j][items])
        np.multiply(r, f32(2.0), out=r)
        np.rint(r, out=r)
        np.multiply(r, f32(0.5), out=r)
        return np.clip(r, f32(0.5), f32(5.0), out=r)
    if kind == "view_buy":
        # confidence of a pair as the e-commerce Preparator sums it:
        # views (one, and a geometric tail of repeats) and, for a share of
        # the pairs, one buy
        views = rng.geometric(1.0 - float(law["repeat_rate"]), n)
        buys = rng.random(n, dtype=f32) < f32(law["buy_share"])
        return (views * float(law["view_weight"])
                + buys * float(law["buy_weight"])).astype(f32)
    raise ValueError(f"unknown value law {kind!r}")


def make_ratings(shape: dict, seed: int):
    """(user_idx int32, item_idx int32, values float32) of the shape's
    whole ratings count, in the order the events arrive."""
    n_users, n_items = table_heights(shape)
    du, di = degree_sequences(shape)
    rng = np.random.default_rng(int(seed))
    # which row gets which degree
    users = np.repeat(rng.permutation(n_users).astype(np.int32), du)
    items = np.repeat(rng.permutation(n_items).astype(np.int32), di)
    # who pairs with whom, and in which order the events come
    users = users[rng.permutation(len(users))]
    rng.shuffle(items)
    values = _values(shape["values"], rng, users, items, n_users, n_items)
    return users, items, values

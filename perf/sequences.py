"""User histories from a seed, for the cells that train on sequences.

The lengths belong to the configuration, as the degree sequences of
`perf/data.py` do: they are quantiles of the user-degree law the
configuration's `shape` states (`data.degrees`), cut to `max` (the newest
events of a longer history), and do not depend on the seed. They sum to
the shape's `n_sequences` x `sequence_len` events, and are repaired once
so that packing them first-fit-decreasing fills exactly that many
sequences: the histories that first-fit-decreasing leaves over go, and
their events lengthen the last history of each sequence that had room.
So every seed gives the same packed layout, the same shapes and the same
work on the device.

The seed decides which user has which length and which items each
history holds: items are drawn by the shape's item law (`data.degrees`
over the vocabulary, as popularity weights), in the order the events
arrive. numpy only.
"""

from __future__ import annotations

import numpy as np

from perf import data


def first_fit_decreasing(lengths, cap: int):
    """(free room of each sequence, the lengths placed in each)."""
    room: list[int] = []
    placed: list[list[int]] = []
    for x in sorted((int(v) for v in lengths), reverse=True):
        for b, free in enumerate(room):
            if x <= free:
                break
        else:
            b = len(room)
            room.append(cap)
            placed.append([])
        room[b] -= x
        placed[b].append(x)
    return room, placed


def history_lengths(shape: dict) -> np.ndarray:
    """Whole lengths, largest first, that first-fit-decreasing packs into
    exactly `n_sequences` full sequences of `sequence_len`. A pure
    function of the shape: no seed."""
    n_seq, cap = int(shape["n_sequences"]), int(shape["sequence_len"])
    lengths = data.degrees(shape["user_degrees"], int(shape["n_users"]),
                           n_seq * cap).tolist()
    for _ in range(32):
        room, placed = first_fit_decreasing(lengths, cap)
        if len(room) == n_seq and not any(room):
            return -np.sort(-np.asarray(lengths, np.int64))
        if len(room) < n_seq:
            raise ValueError(f"{sum(lengths)} events do not fill {n_seq} "
                             f"sequences of {cap}")
        lengths = []
        for free, members in zip(room[:n_seq], placed[:n_seq]):
            members[-1] += free
            lengths.extend(members)
    raise ValueError("the history lengths did not settle into whole "
                     "sequences")


def make_histories(shape: dict, seed: int) -> list[np.ndarray]:
    """One int32 array of item rows per user, oldest event first, in the
    order of the user ids (`u0`, `u1`, ...)."""
    lengths = history_lengths(shape)
    n_items = int(shape["n_items"])
    rng = np.random.default_rng(int(seed))
    lengths = lengths[rng.permutation(len(lengths))]
    total = int(lengths.sum())
    # popularity: the item law's degrees over the vocabulary, on items
    # the seed picks; an event draws its item by that weight
    weights = data.degrees(shape["item_degrees"], n_items,
                           max(total, n_items)).astype(np.float64)
    weights = weights[rng.permutation(n_items)]
    items = rng.choice(n_items, size=total, p=weights / weights.sum())
    return np.split(items.astype(np.int32), np.cumsum(lengths)[:-1])

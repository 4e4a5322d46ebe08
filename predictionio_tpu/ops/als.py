"""ALS (alternating least squares) matrix factorization, TPU-first.

Replaces the reference templates' calls into Spark MLlib ALS
(«org.apache.spark.mllib.recommendation.ALS.train / trainImplicit», invoked
from the Recommendation/Similar-Product/E-Commerce templates — SURVEY.md
§2.4 [U]). MLlib block-partitions the interaction matrix and ships factor
blocks over the shuffle every iteration; here the same alternation is two
jitted half-epochs over a device mesh:

- The interaction matrix is ragged (users have wildly different rating
  counts); TPUs want dense tiles. Rows are **bucketed by nnz into
  power-of-two padded dense blocks** (SURVEY.md §7.3): a bucket holds
  [R, C] column-index/value/mask tiles, R padded to the data-axis size.
- One half-epoch solves, for every row r in every bucket, the normal
  equations (Yᵀ_r Y_r + λ(n_r)I) x_r = Yᵀ_r v_r with Y_r the gathered
  opposing factors — batched einsum ([R,C,K] → [R,K,K], MXU work) +
  batched `jnp.linalg.solve`.
- Bucket rows are sharded over the mesh `data` axis; the opposing factor
  matrix is replicated (factors are tiny relative to interactions), so the
  only cross-device traffic is the all_gather of freshly-solved rows that
  GSPMD inserts — the ICI analogue of MLlib's factor-block shuffle.
- Implicit-feedback mode (trainImplicit) uses the Hu-Koren-Volinsky
  confidence weighting: A = YᵀY + Yᵀ(C−I)Y + λI, b = YᵀC·1, with the
  global Gram YᵀY computed once per half-epoch.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import NamedTuple, Optional, Sequence

import numpy as np

from predictionio_tpu.ops.solve import solve_spd
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.telemetry.spans import (
    Worker,
    record as record_span,
    span,
)
from predictionio_tpu.utils import faults

log = logging.getLogger(__name__)

MIN_CAP = 8  # smallest bucket width (sublane-friendly)

# Counted where the buckets are made or loaded, per side (`user` | `item`).
# entries / cells is the share of the gather + Gram work that is not
# padding: `cap_growth` and `splitCap` set it.
BUCKET_ENTRIES = REGISTRY.gauge(
    "als_bucket_entries",
    "Ratings placed into the side's buckets by the last als_train",
    labelnames=("side",))
BUCKET_CELLS = REGISTRY.gauge(
    "als_bucket_cells",
    "Cells (rows x cap, summed over buckets, before chunk padding) of the "
    "side's buckets in the last als_train",
    labelnames=("side",))
# What the train loop walks: `place_buckets` pads a bucket too large for one
# gather to a multiple of its chunk (`_bucket_chunk_rows`), and a trip of
# padding rows costs what a trip of real ones does. entries / walk cells is
# the share of the loop's gather + Gram + solve rows that is not padding.
BUCKET_WALK_CELLS = REGISTRY.gauge(
    "als_bucket_walk_cells",
    "Cells (rows x cap, summed over buckets, after chunk padding) of the "
    "side's buckets as the last als_train or als_train_grid placed them on "
    "the device",
    labelnames=("side",))
# Which way each bucketizer call went. A train whose sides read `numpy`
# fell back: no toolchain, PIO_NATIVE=0, or an input the loader declines.
BUCKETIZE_CALLS = REGISTRY.counter(
    "als_bucketize_calls_total",
    "bucket_ragged / bucket_ragged_split calls by side (user | item | "
    "fold | rows) and the path that built the buckets (native_counting | "
    "native_comparison | numpy)",
    labelnames=("side", "path"))
# Which way each data digest went: leaves spread over threads, or hashed
# by the caller (one leaf or fewer, or one usable core). The value is the
# same either way.
DIGEST_CALLS = REGISTRY.counter(
    "als_digest_calls_total",
    "_arrays_digest calls by the path that hashed the leaves (parallel | "
    "inline)",
    labelnames=("path",))


def _spanned(name: str):
    """Run the function inside `telemetry.spans.span(name)`: a timeline
    record under `pio train`, a trace annotation under the profiler."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


@dataclasses.dataclass
class Bucket:
    """Padded dense block of ragged rows with equal capacity."""

    rows: np.ndarray  # [R] int32 — row ids; padding rows get `n_rows` (sentinel)
    cols: np.ndarray  # [R, C] int32 — column ids, 0-padded
    vals: np.ndarray  # [R, C] float32 — values, 0-padded
    mask: np.ndarray  # [R, C] float32 — 1 where real
    # [R] int32 segment map, only for buckets holding rows split by
    # `bucket_ragged_split`: index into the split-row table for segment
    # rows, == n_split (sentinel, dropped) for whole rows/padding. None
    # for buckets with no segments.
    segmap: Optional[np.ndarray] = None

    @property
    def cap(self) -> int:
        return self.cols.shape[1]


def cap_ladder(max_count: int, min_cap: int, growth: float) -> np.ndarray:
    """Bucket capacity ladder: min_cap, then ceil(prev·growth/8)·8.
    growth=2.0 reproduces the power-of-two caps exactly; smaller growth
    (1.5 default) trades more bucket shapes (compile time) for less
    padding in the gather — measured 1.08× epoch at 2M rank-64 (round
    2). Mirrored bit-identically in native/pio_native.cpp."""
    import math

    if growth <= 1.0:
        raise ValueError(f"cap_growth must be > 1.0, got {growth}")
    ladder = [min_cap]
    while ladder[-1] < max_count:
        nxt = int(math.ceil(ladder[-1] * growth / 8.0)) * 8
        if nxt <= ladder[-1]:
            nxt = ladder[-1] + 8
        ladder.append(nxt)
    return np.asarray(ladder, dtype=np.int64)


def _bucketize(rows, cols, vals, n_rows: int, row_multiple: int,
               max_cap: Optional[int], split_cap: Optional[int],
               cap_growth: float, side: str):
    """(buckets, split_rows) from the native loader, or from the numpy
    reference where the loader is absent or declines the input. Either
    way the call says which: the counter for `/metrics`, the seconds
    under the path's name for the timeline (`train/phases`)."""
    from predictionio_tpu import native as _native

    t0 = time.monotonic()
    nb = _native.bucket_ragged_native(rows, cols, vals, n_rows,
                                      row_multiple, max_cap, MIN_CAP,
                                      cap_growth, split_cap)
    if nb is not None:
        buckets, split_rows, path = nb
    else:
        path = "numpy"
        if split_cap is None:
            buckets, split_rows = _bucket_ragged_numpy(
                rows, cols, vals, n_rows, row_multiple, max_cap,
                cap_growth), np.zeros(0, np.int32)
        else:
            buckets, split_rows = _bucket_ragged_split_numpy(
                rows, cols, vals, n_rows, row_multiple, split_cap,
                cap_growth)
    BUCKETIZE_CALLS.labels(side=side, path=path).inc()
    record_span(f"als.bucketize.{path}", time.monotonic() - t0)
    return buckets, split_rows


def bucket_ragged(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    row_multiple: int = 8,
    max_cap: Optional[int] = None,
    cap_growth: float = 1.5,
    side: str = "rows",
) -> list[Bucket]:
    """COO triplets → per-row padded buckets, bucketed by nnz.

    Rows with no entries are skipped (their factors stay at init).
    `row_multiple` pads each bucket's row count (use mesh data-axis size ×
    8 so shards stay tile-aligned). `max_cap` truncates pathological rows
    to their first `max_cap` entries in the caller's order (keeping the
    most recent ones is the caller's job; default no cap). `cap_growth`
    sets the capacity ladder (see `cap_ladder`). `side` only labels the
    call in `als_bucketize_calls_total`.

    The hot path runs in the native C++ loader (native/pio_native.cpp,
    bit-identical output, linear in the entry count) when a toolchain is
    available; PIO_NATIVE=0, a failed build or an input it declines falls
    back to the numpy implementation below, the reference.
    """
    return _bucketize(rows, cols, vals, n_rows, row_multiple, max_cap, None,
                      cap_growth, side)[0]


def _bucket_ragged_numpy(rows, cols, vals, n_rows: int, row_multiple: int,
                         max_cap: Optional[int],
                         cap_growth: float) -> list[Bucket]:
    rows = np.asarray(rows, dtype=np.int32)
    cols = np.asarray(cols, dtype=np.int32)
    vals = np.asarray(vals, dtype=np.float32)
    order = np.argsort(rows, kind="stable")
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    uniq, start, counts = np.unique(rows_s, return_index=True, return_counts=True)

    if max_cap is not None:
        counts = np.minimum(counts, max_cap)
    ladder = cap_ladder(int(counts.max(initial=1)), MIN_CAP, cap_growth)
    caps = ladder[np.searchsorted(ladder, np.maximum(counts, 1))]
    if max_cap is not None:
        caps = np.minimum(caps, max_cap)

    buckets: list[Bucket] = []
    for cap in np.unique(caps):
        sel = np.nonzero(caps == cap)[0]
        r = len(sel)
        r_pad = -(-r // row_multiple) * row_multiple
        b_rows = np.full(r_pad, n_rows, dtype=np.int32)  # sentinel padding
        b_cols = np.zeros((r_pad, cap), dtype=np.int32)
        b_vals = np.zeros((r_pad, cap), dtype=np.float32)
        b_mask = np.zeros((r_pad, cap), dtype=np.float32)
        for i, j in enumerate(sel):
            c = counts[j]
            s = start[j]
            b_rows[i] = uniq[j]
            b_cols[i, :c] = cols_s[s : s + c]
            b_vals[i, :c] = vals_s[s : s + c]
            b_mask[i, :c] = 1.0
        # sort each padded row by column id: the per-row Gram/RHS sums are
        # order-invariant, and monotonic gather indices are ~20× faster on
        # TPU than random ones (measured v5e, round 1)
        order = np.argsort(b_cols, axis=1, kind="stable")
        b_cols = np.take_along_axis(b_cols, order, axis=1)
        b_vals = np.take_along_axis(b_vals, order, axis=1)
        b_mask = np.take_along_axis(b_mask, order, axis=1)
        buckets.append(Bucket(b_rows, b_cols, b_vals, b_mask))
    return buckets


def bucket_ragged_split(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    row_multiple: int = 8,
    split_cap: Optional[int] = None,
    cap_growth: float = 1.5,
    side: str = "rows",
) -> tuple[list[Bucket], np.ndarray]:
    """`bucket_ragged`, but rows with more than `split_cap` entries are
    **split into segments** instead of padding the whole matrix out to the
    hottest row's capacity (SURVEY.md §7.3's padding-waste risk: one
    pathological row would otherwise set the dense tile width for its
    entire bucket — at ML-20M scale that is an OOM, not a slowdown).

    Each segment becomes its own bucket row carrying the original row id
    and a `segmap` entry pointing into the returned split-row table;
    `_solve_buckets_device` sums the segments' partial normal equations
    (A_r = Σ y_c y_cᵀ is associative over any partition of the row's
    entries) before solving, so results are bit-comparable to the unsplit
    math in f32 accumulation.

    The native loader splits, groups and column-sorts the side in one
    call (`native.bucket_ragged_native` with `split_cap`); where it is
    unavailable or declines, the numpy implementation below runs, with
    the same output bit for bit.

    Returns (buckets, split_rows) where split_rows[u] is the original row
    id of split-table slot u (empty array when nothing was split).
    """
    return _bucketize(rows, cols, vals, n_rows, row_multiple, None, split_cap,
                      cap_growth, side)


def _bucket_ragged_split_numpy(rows, cols, vals, n_rows: int,
                               row_multiple: int, split_cap: int,
                               cap_growth: float):
    def whole_rows():
        return (_bucket_ragged_numpy(rows, cols, vals, n_rows, row_multiple,
                                     None, cap_growth),
                np.zeros(0, np.int32))
    if len(rows) == 0:
        return whole_rows()
    rows = np.asarray(rows, dtype=np.int32)
    counts = np.bincount(rows, minlength=n_rows)
    hot = np.nonzero(counts > split_cap)[0].astype(np.int32)
    if hot.size == 0:
        return whole_rows()

    cols = np.asarray(cols, dtype=np.int32)
    vals = np.asarray(vals, dtype=np.float32)
    # rank of each entry within its row (stable order), so segments keep
    # the caller's entry order
    order = np.argsort(rows, kind="stable")
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    starts = np.concatenate(([0], np.cumsum(counts)))
    rank = np.arange(len(rows_s), dtype=np.int64) - starts[rows_s]
    seg = (rank // split_cap).astype(np.int64)

    # pseudo-row numbering: hot row h's segment s → n_rows + base[h] + s
    # (on the hot-entry subset only: a full-width [nnz] temporary is a
    # pass over memory each)
    nseg = -(-counts[hot] // split_cap)
    base = np.concatenate(([0], np.cumsum(nseg)))[:-1]
    hot_slot = np.full(n_rows, -1, np.int64)
    hot_slot[hot] = np.arange(hot.size)
    idx_hot = np.nonzero(hot_slot[rows_s] >= 0)[0]
    rows2 = rows_s.astype(np.int32, copy=True)
    rows2[idx_hot] = (n_rows + base[hot_slot[rows_s[idx_hot]]]
                      + seg[idx_hot]).astype(np.int32)
    n_rows_eff = int(n_rows + nseg.sum())

    buckets = _bucket_ragged_numpy(rows2, cols_s, vals_s, n_rows_eff,
                                   row_multiple, None, cap_growth)

    # map pseudo ids back: real row ids + segmap into the split table
    pseudo_to_slot = np.repeat(hot_slot[hot], nseg).astype(np.int32)
    for b in buckets:
        is_pseudo = (b.rows >= n_rows) & (b.rows < n_rows_eff)
        if not is_pseudo.any():
            # plain bucket (padding sentinel n_rows_eff still needs fixing)
            b.rows = np.where(b.rows >= n_rows, n_rows, b.rows).astype(np.int32)
            continue
        slot = np.where(
            is_pseudo,
            pseudo_to_slot[(b.rows - n_rows).clip(0, pseudo_to_slot.size - 1)],
            hot.size).astype(np.int32)
        real = np.where(is_pseudo, hot[slot.clip(0, hot.size - 1)], b.rows)
        b.rows = np.where(real >= n_rows, n_rows, real).astype(np.int32)
        b.segmap = slot
    return buckets, hot


SOLVERS = ("auto", "gj", "chol")


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    """Frozen (hashable) so jitted solvers cache across als_train calls."""

    rank: int = 10
    iterations: int = 10
    reg: float = 0.01
    weighted_reg: bool = True  # λ·n_r (ALS-WR, MLlib's scheme) vs plain λ
    implicit: bool = False
    alpha: float = 1.0  # implicit confidence scale
    seed: int = 0
    dtype: str = "float32"
    # Gram/RHS einsum input precision: "bfloat16" feeds the MXU its native
    # dtype (f32 accumulation via preferred_element_type keeps the normal
    # equations well-conditioned); "float32" for bit-stable results.
    compute_dtype: str = "float32"
    # normal-equation solver (anything else is a ValueError):
    #   "auto" — "gj" on TPU when the rank fits its VMEM budget, else "chol"
    #   "gj"   — Pallas batched Gauss-Jordan (ops/pallas_solve.py): the
    #            batched Cholesky custom-call dominates rank-64 epochs
    #            (~66% of device time, v5e profile) and the kernel solves
    #            the same systems ~3.4× faster; under a multi-device mesh
    #            it runs shard_mapped, one kernel per device row shard
    #   "chol" — Cholesky (A is SPD by construction — λ>0 — and two
    #            triangular solves beat LU by ~30% on v5e)
    solver: str = "auto"
    # rows with more entries than this are split into segments whose
    # partial normal equations are summed on device before solving
    # (bucket_ragged_split): bounds the dense tile width a hot row can
    # force on its bucket. Power of two; 0 disables splitting.
    split_cap: int = 32768
    # bucket capacity ladder growth factor (cap_ladder): 2.0 = round-1
    # power-of-two caps; the 1.5 default pads ~13% fewer entries into the
    # gather for ~1.08x epoch at 2M rank-64 (round 2), at the cost of
    # ~50% more bucket shapes to compile
    cap_growth: float = 1.5
    # Pallas mode for the SOLVER kernel (ops/pallas_solve.py):
    # "auto"/"off"/"on" are equivalent today (the GJ solver is selected via
    # `solver`); "interpret" runs it in interpreter mode on any backend
    # (tests). A fused gather+Gram kernel was tried and retired in round 2:
    # TPU row-gather is op-throughput-bound (~40M rows/s, invariant to
    # table size and dtype — docs/performance.md §roofline), Mosaic has no
    # vector-indexed gather to beat it, and the scalar-loop kernel peaked
    # at 1.1× XLA at rank 128 while failing to compile at rank 64.
    pallas: str = "auto"

    def __post_init__(self):
        if self.solver not in SOLVERS:
            raise ValueError(f"unknown ALS solver {self.solver!r} "
                             f"(want {' / '.join(SOLVERS)})")


# HBM budget for one bucket-chunk's [R, C, K] gathered-factor block; buckets
# bigger than this are processed in row chunks via fori_loop so the gather
# never materializes more than the budget (hot-row segments at ML-20M+ scale
# would otherwise allocate tens of GB in one fusion)
_CHUNK_BUDGET_BYTES = 1 << 30


# 2: PR 45's tree digest (`_arrays_digest`) names every entry anew; what an
# older build saved is an orphan the keep-newest GC removes.
_BUCKET_CACHE_VERSION = 2

# The data digest's leaf: each array's bytes are cut every this many and
# each piece hashed on its own, so that the pieces can be hashed side by
# side. Part of the digest's value (a cache key, a checkpoint
# fingerprint), so a constant and not a setting.
_DIGEST_LEAF_BYTES = 8 << 20
_DIGEST_MAX_WORKERS = 8


def _persist_rank() -> int:
    """The checkpoint-writing rank (PIO_PERSIST_RANK, default 0) — see
    parallel/distributed.py::persist_rank."""
    from predictionio_tpu.parallel.distributed import persist_rank

    return persist_rank()


def _bucket_cache_keep() -> int:
    """Fingerprints retained per cache dir. The dir is shared by every
    ALS-family template on the host, so hosts alternating more than this
    many distinct datasets thrash back to full rebucketizes — raise
    PIO_BUCKET_CACHE_KEEP if that's your workload (each 20M-scale entry
    is ~0.5 GB on disk, hence a bound at all)."""
    import os

    return max(1, int(os.environ.get("PIO_BUCKET_CACHE_KEEP", "4")))


def _usable_cores() -> int:
    """Cores this process may run on (its affinity mask where the
    platform has one: a container's share, not the machine's count)."""
    import os

    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


def _leaf_digest(leaf) -> bytes:
    """One leaf's own blake2b. Fed through `update`, which drops the GIL
    over a buffer above 2047 bytes; the constructor's `data` keeps it."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    h.update(leaf)
    return h.digest()


@_spanned("als.digest")
def _arrays_digest(*arrays, extra: str = "") -> str:
    """A tree hash of the arrays: each one's bytes, read in place, are cut
    into leaves of `_DIGEST_LEAF_BYTES`, every leaf is hashed alone, and
    one root blake2b takes, array by array, the dtype, the shape and the
    leaves' digests in order, then `extra`. The value depends on the
    data, the dtypes, the shapes, `extra` and the leaf size, and never on
    who hashed the leaves: an input of more than one leaf's bytes, on a
    host with more than one usable core, has them spread over
    `telemetry.spans.Worker` threads (a fixed stride each, at most
    `_DIGEST_MAX_WORKERS`), started and joined here, inside the caller's
    one `als.digest` span; anything smaller is hashed inline and starts
    no thread. A leaf's error is raised here after every worker has
    ended. The call says which way it went: the counter for `/metrics`,
    the seconds under the path's name for the timeline (`train/phases`)."""
    import hashlib

    t0 = time.monotonic()
    heads, leaves = [], []
    for a in arrays:
        a = np.asarray(a)
        head = repr((a.dtype.str, a.shape)).encode()
        # a view of the array's own memory; a copy only where it is not
        # contiguous
        flat = np.ascontiguousarray(a).reshape(-1).view(np.uint8)
        cuts = range(0, flat.size, _DIGEST_LEAF_BYTES)
        heads.append((head, len(cuts)))
        leaves.extend(flat[lo:lo + _DIGEST_LEAF_BYTES] for lo in cuts)

    def hashed(mine) -> list:
        return [_leaf_digest(leaf) for leaf in mine]

    n_workers = min(len(leaves), _usable_cores(), _DIGEST_MAX_WORKERS)
    if (n_workers > 1
            and sum(leaf.size for leaf in leaves) > _DIGEST_LEAF_BYTES):
        path = "parallel"
        workers = [Worker(f"als-digest-{k}", hashed, leaves[k::n_workers])
                   for k in range(n_workers)]
        for worker in workers:  # all ended before any leaf's error is raised
            worker.wait()
        digests = [b""] * len(leaves)
        for k, worker in enumerate(workers):
            digests[k::n_workers] = worker.join()
    else:
        path = "inline"
        digests = hashed(leaves)
    root = hashlib.blake2b(digest_size=16)
    root.update(repr((len(arrays), _DIGEST_LEAF_BYTES)).encode())
    done = 0
    for head, n in heads:
        root.update(head)
        root.update(b"".join(digests[done:done + n]))
        done += n
    root.update(extra.encode())
    DIGEST_CALLS.labels(path=path).inc()
    record_span(f"als.digest.{path}", time.monotonic() - t0)
    return root.hexdigest()


@_spanned("als.bucket_cache.save")
def _bucket_cache_save(cache_dir: str, key: str,
                       user_buckets: list, u_split: np.ndarray,
                       item_buckets: list, i_split: np.ndarray) -> None:
    """Persist both sides' buckets as one npz, atomically (tmp+rename —
    a crashed writer leaves no half-written cache), then GC old
    fingerprints by mtime."""
    import os
    import tempfile

    arrays: dict[str, np.ndarray] = {"u_split": u_split, "i_split": i_split}
    for side, buckets in (("u", user_buckets), ("i", item_buckets)):
        for n, b in enumerate(buckets):
            arrays[f"{side}{n}_rows"] = b.rows
            arrays[f"{side}{n}_cols"] = b.cols
            arrays[f"{side}{n}_vals"] = b.vals
            arrays[f"{side}{n}_mask"] = b.mask
            if b.segmap is not None:
                arrays[f"{side}{n}_segmap"] = b.segmap
    os.makedirs(cache_dir, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=cache_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)  # uncompressed: load speed is the point
        os.replace(tmp, os.path.join(cache_dir, f"{key}.npz"))
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    entries = []
    for e in os.scandir(cache_dir):
        try:  # a concurrent rank's GC may unlink between scandir and stat
            mtime = e.stat().st_mtime
        except OSError:
            continue
        if e.name.endswith(".npz"):
            entries.append((mtime, e.path))
        elif e.name.endswith(".tmp") and mtime < time.time() - 3600:
            # a SIGKILLed writer's orphan; anything this old is dead
            # (live writers hold their tmp for seconds)
            try:
                os.unlink(e.path)
            except OSError:
                pass
    entries.sort(reverse=True)
    for _, stale in entries[_bucket_cache_keep():]:
        try:
            os.unlink(stale)
        except OSError:
            pass


@_spanned("als.bucket_cache.load")
def _bucket_cache_load(cache_dir: str, key: str):
    """(user_buckets, u_split, item_buckets, i_split) or None on miss."""
    import os

    import zipfile

    path = os.path.join(cache_dir, f"{key}.npz")
    if not os.path.exists(path):
        return None
    try:
        with np.load(path) as z:
            sides = []
            for side in ("u", "i"):
                buckets = []
                n = 0
                while f"{side}{n}_rows" in z:
                    buckets.append(Bucket(
                        rows=z[f"{side}{n}_rows"],
                        cols=z[f"{side}{n}_cols"],
                        vals=z[f"{side}{n}_vals"],
                        mask=z[f"{side}{n}_mask"],
                        segmap=(z[f"{side}{n}_segmap"]
                                if f"{side}{n}_segmap" in z else None),
                    ))
                    n += 1
                sides.append(buckets)
            try:
                os.utime(path)  # freshen for the keep-newest GC
            except OSError:
                pass  # read-only cache dir: loaded fine, just can't freshen
            return sides[0], z["u_split"], sides[1], z["i_split"]
    except (OSError, ValueError, KeyError, EOFError,
            zipfile.BadZipFile) as e:
        log.warning("bucket cache at %s unreadable (%s) — rebucketing",
                    path, e)
        return None


def _save_or_warn(cache_dir: str, key: str, *buckets) -> None:
    """`_bucket_cache_save`, with the failures a disk can cause logged
    and not raised; any other error is the caller's."""
    try:
        # atomic write: concurrent ranks race safely (same bytes)
        _bucket_cache_save(cache_dir, key, *buckets)
        log.info("als_train: bucket cache miss — saved %s", key)
    except OSError as e:
        # the cache is a pure optimization: a full/read-only disk
        # must not fail a train that already bucketized
        log.warning("als_train: bucket cache save failed (%s) — "
                    "continuing uncached", e)


class BucketCacheSave:
    """One train's bucket-cache save, written on a thread of its own
    behind the device loop: nothing in the call reads what it writes,
    and the calling thread spends the loop parked on the device.
    `bucketize_cached` stages the entry on a miss, the train starts the
    thread when its transfers are done (started beside them it slowed
    `als.put_buckets` from 0.22 to 0.35 s: PERF.md, PR 44), and whoever made
    the object joins it, as a context manager on every way out: when a
    train returns or raises the entry is on disk (or the warning logged)
    and no thread is left. `als.bucket_cache.join` is the wait: about
    nothing where the loop outlasted the save, the save's remainder
    where it did not."""

    def __init__(self):
        self._entry: Optional[tuple] = None
        self._worker: Optional[Worker] = None

    def stage(self, cache_dir: str, key: str, *buckets) -> None:
        """What to write, kept until `start`."""
        self._entry = (cache_dir, key, *buckets)

    def start(self) -> None:
        """Start writing what was staged, once: called when the transfers
        are done and the calling thread is about to park on the loop."""
        entry, self._entry = self._entry, None
        if entry is not None:
            self._worker = Worker("als-bucket-cache-save", _save_or_warn,
                                  *entry)

    def join(self) -> None:
        self.start()  # a train that never reached its loop still saves
        worker, self._worker = self._worker, None
        if worker is None:  # a hit, or no cache directory: nothing to wait for
            return
        with span("als.bucket_cache.join"):
            worker.wait()
        worker.join()

    def __enter__(self) -> "BucketCacheSave":
        return self

    def __exit__(self, *exc) -> None:
        self.join()


def bucketize_cached(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    row_multiple: int,
    split_cap: Optional[int],
    cap_growth: float,
    bucket_cache_dir: Optional[str],
    data_digest=None,
    cache_save: Optional[BucketCacheSave] = None,
):
    """Both sides' `bucket_ragged_split`, behind the on-disk fingerprint
    cache when `bucket_cache_dir` is set. Shared by `als_train` and the
    grid evaluator (`ops/als_grid.py`) — the fingerprint covers every
    bucketizer input and NOT the solver hyperparams, which is exactly why
    an eval grid over (λ, α) can reuse the single train's cache entry.
    `data_digest`: optional zero-arg memoized digest of the COO arrays.

    On a miss the two sides are built at once, a thread a side (they read
    the same three arrays and share nothing else; the native loader
    holds no GIL), and both are joined before a bucket is read. The new
    entry is staged on `cache_save`, which the caller starts and joins
    before its train returns; without one it is saved before this returns.

    Returns (user_buckets, u_split, item_buckets, i_split)."""
    if data_digest is None:
        def data_digest():
            return _arrays_digest(user_idx, item_idx, ratings)
    cached = None
    bucket_key = None
    if bucket_cache_dir:
        import hashlib

        # fingerprint = training data + every input the bucketizer reads;
        # new events or a changed mesh shape / splitCap / growth miss
        bucket_key = hashlib.blake2b(
            (data_digest() + repr((n_users, n_items, row_multiple,
                                   split_cap, cap_growth,
                                   _BUCKET_CACHE_VERSION))).encode(),
            digest_size=16).hexdigest()
        cached = _bucket_cache_load(bucket_cache_dir, bucket_key)
    if cached is not None:
        log.info("als_train: bucket cache hit %s (host bucketize skipped)",
                 bucket_key)
        return cached

    def build(side: str, rows, cols, n_rows: int):
        with span("als.bucketize"):
            return bucket_ragged_split(rows, cols, ratings, n_rows,
                                       row_multiple, split_cap,
                                       cap_growth=cap_growth, side=side)

    sides = [Worker(f"als-bucketize-{side}", build, side, rows, cols, n_rows)
             for side, rows, cols, n_rows in (
                 ("user", user_idx, item_idx, n_users),
                 ("item", item_idx, user_idx, n_items))]
    for worker in sides:  # both ended before either's error is raised
        worker.wait()
    (user_buckets, u_split), (item_buckets, i_split) = (
        worker.join() for worker in sides)
    if bucket_cache_dir:
        save = cache_save if cache_save is not None else BucketCacheSave()
        save.stage(bucket_cache_dir, bucket_key, user_buckets, u_split,
                   item_buckets, i_split)
        if save is not cache_save:
            save.join()
    return user_buckets, u_split, item_buckets, i_split


def _bucket_chunk_rows(r: int, c: int, k: int, row_multiple: int) -> int:
    """Rows per chunk for a [r, c] bucket at rank k (== r when no chunking
    is needed). Multiple of row_multiple so shards stay tile-aligned.

    The fewest trips the budget admits, the rows spread evenly over them:
    callers pad the bucket to a multiple of the chunk and every trip costs
    the same whether its rows are real or padding, so the padding stays
    under one `row_multiple` a trip (the largest chunk the budget admits
    could pad by a whole trip less a unit). Idempotent under that padding,
    `rule(trips * chunk) == chunk`: the padded height needs the same trips
    (chunk <= the budget's most) and divides evenly, which is what lets
    `_walk_bucket_chunks` recompute the chunk from the padded height."""
    per_row = c * k * 4
    if r * per_row <= _CHUNK_BUDGET_BYTES:
        return r
    units = -(-r // row_multiple)
    max_units = max(1, _CHUNK_BUDGET_BYTES // (per_row * row_multiple))
    trips = -(-units // max_units)
    return min(r, -(-units // trips) * row_multiple)


def place_buckets(side: str, buckets: Sequence[Bucket], n_rows: int,
                  split_rows: np.ndarray, chunk_rows, row_shard, rep):
    """One side's buckets and split-row ids onto the device, as the loops
    walk them: `(placed, split_rows_dev)`, each placed bucket the tuple
    (rows, cols, vals, mask, segmap-or-None) of `row_shard`ed arrays.

    A bucket's rows are padded to a multiple of `chunk_rows(rows, cap)`,
    the chunk its walk will recompute from the padded height (a train's
    `_bucket_chunk_rows` at its rank; a grid's at n_grid x rank; in
    model-sharded mode the data shards' local rule, n_data of them), so
    the fori_loop of `_walk_bucket_chunks` covers the bucket exactly.
    Padding rows carry the sentinel ids the scatters drop (`n_rows`, and
    `len(split_rows)` for a segment), no columns and a zero mask. Sets the
    side's `BUCKET_WALK_CELLS` to what it placed. The caller opens the
    span (`als.put_buckets`, one a train)."""
    import jax

    placed = []
    for b in buckets:
        r_total, cap = b.cols.shape
        pad = (-r_total) % chunk_rows(r_total, cap)
        arrs = [b.rows, b.cols, b.vals, b.mask, b.segmap]
        if pad:
            arrs[0] = np.concatenate([b.rows, np.full(pad, n_rows, np.int32)])
            for i in (1, 2, 3):
                arrs[i] = np.concatenate(
                    [arrs[i], np.zeros((pad, cap), arrs[i].dtype)])
            if b.segmap is not None:
                arrs[4] = np.concatenate(
                    [b.segmap, np.full(pad, len(split_rows), np.int32)])
        placed.append(tuple(
            None if a is None else jax.device_put(a, row_shard)
            for a in arrs))
    BUCKET_WALK_CELLS.labels(side=side).set(
        sum(b[1].shape[0] * b[1].shape[1] for b in placed))
    return placed, jax.device_put(split_rows, rep)


def _gather_rows(table, cols, mesh=None):
    """[R, C] row-id gather from [V, K] → [R, C, K], or from a grid's
    [V, G, K] → [R, C, G, K]: whatever follows the row axis is one flat
    row to the take (G·K wide for a grid, which costs the gather nothing:
    it is bound by the count of rows, not their width).

    Single device: flat `jnp.take` + reshape — XLA lowers it ~10× faster
    than the direct [R, C] indexed gather on TPU (and the bucketizer sorts
    each row's ids, worth another big factor — docs/performance.md).
    Under a mesh the indexed form is kept: GSPMD shards it cleanly, while
    the flat reshape mixes the sharded row dim into the take."""
    import jax.numpy as jnp

    if mesh is not None and mesh.size > 1:
        return table[cols]
    r, c = cols.shape
    # mode="clip" matches the indexed gather's clamp semantics (the
    # default "fill" would turn an out-of-range id into NaN factors)
    return jnp.take(table.reshape(table.shape[0], -1), cols.reshape(-1),
                    axis=0, mode="clip").reshape(r, c, *table.shape[1:])


def normal_eq_einsum(compute_dtype):
    """`jnp.einsum` for the Gram/RHS sums of the normal equations: f32
    accumulation, products at the precision `compute_dtype` asks for.

    bf16 operands multiply exactly on the MXU. f32 operands do not at a
    TPU's default matmul precision: they are rounded to bf16 on the way
    in — and only where XLA uses the MXU, which is the Gram but not the
    RHS (a VPU reduce that stays f32). A and b then describe slightly
    different factors, and the solve divides the mismatch by λ·n in every
    direction the row's ratings do not determine. Measured on v5e at
    rank 64, λ=0.05 (PERF.md, PR 21): rows with ≤ 2 ratings came out
    ~50 % (median) off the f64 reference, all rows 12 %, against 1e-5
    at HIGHEST — with either solver. So f32 means f32 here, as it does
    in the solver (the lanes kernel eliminates in f32 on the VPU; the
    products of `pallas_solve._schur_rec` are pinned to HIGHEST). On CPU
    the argument changes nothing."""
    import jax
    import jax.numpy as jnp

    exact = jnp.dtype(compute_dtype) == jnp.float32
    return functools.partial(
        jnp.einsum, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if exact else None)


def _walk_bucket_chunks(arrays, cap: int, k: int, row_multiple: int, fn, carry):
    """Fold `fn(sliced_arrays, carry) -> carry` over one bucket's rows.

    Small buckets go through `fn` whole; oversized ones (per
    `_bucket_chunk_rows`) are walked in row chunks under a fori_loop so the
    [R, C, K] gathers inside `fn` never materialize past the budget.
    `arrays` are per-row device arrays (None entries pass through as None);
    place_buckets pads row counts to a chunk multiple with the SAME
    (cap, k, row_multiple) arithmetic, which keeps the walk exact."""
    import jax

    r_total = arrays[0].shape[0]
    chunk = _bucket_chunk_rows(r_total, cap, k, row_multiple)
    if chunk >= r_total:
        return fn(arrays, carry)

    def body(i, c):
        sliced = tuple(
            None if a is None
            else jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, 0)
            for a in arrays)
        return fn(sliced, c)

    return jax.lax.fori_loop(0, r_total // chunk, body, carry)


def _partial_normal_eqs(y, vals, mask, cfg: ALSConfig, alpha):
    """Raw per-row partial normal equations (A, b) of a chunk's gathered
    factors `y` [R, C, K] (a grid's [R, C, G, K], with `alpha` its [G]):
    no global Gram, no regulariser, f32, associative over any split of a
    row's entries. The one place the Gram/RHS sums are written: the train,
    the fold, the grid and the model-sharded loop all form them here."""
    import jax.numpy as jnp

    grid = y.ndim == 4
    g = "g" if grid else ""
    cdtype = jnp.dtype(cfg.compute_dtype)
    ne_einsum = normal_eq_einsum(cdtype)
    # ym on BOTH einsum sides: the mask is 0/1 so m² == m, and keeping
    # the raw `y` alive as a second operand forces XLA to materialize
    # the gather for it (measured 15× slower at the hot-bucket shape)
    ym = (y * mask[(...,) + (None,) * (y.ndim - 2)]).astype(cdtype)
    if not cfg.implicit:
        a = ne_einsum(f"rc{g}k,rc{g}l->r{g}kl", ym, ym)
        b = ne_einsum(f"rc{g}k,rc->r{g}k", ym, vals.astype(cdtype))
        return a, b
    # C - I, zero at padding; one confidence a grid point
    conf = alpha[None, None, :] * vals[:, :, None] if grid else alpha * vals
    a = ne_einsum(f"rc{g}k,rc{g},rc{g}l->r{g}kl", ym, conf.astype(cdtype), ym)
    b = ne_einsum(f"rc{g}k,rc{g}->r{g}k", ym, (1.0 + conf).astype(cdtype))
    return a, b


def _regularise_and_solve(a, b, n, gram, cfg: ALSConfig, reg, dtype,
                          mesh=None, row_sharded=True):
    """Partial (A, b) and entry counts `n` [R] → solved factors in `dtype`:
    adds the implicit mode's YᵀY (`gram`, None in explicit mode) and the
    regulariser (λ·n_r with ALS-WR, else λ), then `solve_spd`. A grid's
    [R, G, K, K] with `reg` its [G] is flattened to the (R·G)-row batch
    the solvers take, so the solver never knows a grid is running. The
    other place the four loops share."""
    import jax.numpy as jnp

    k = a.shape[-1]
    if gram is not None:
        a = a + gram[None]
    grid = a.ndim == 4
    lam = reg[None, :] if grid else reg  # λ a grid point: [1, G]
    weight = n if cfg.weighted_reg else jnp.ones_like(n)  # ALS-WR: λ·n_r
    lam = lam * (weight[:, None] if grid else weight)
    a = a + (lam[..., None, None]
             * jnp.eye(k, dtype=jnp.float32)[(None,) * (a.ndim - 2)])
    x = solve_spd(a.astype(dtype).reshape(-1, k, k),
                  b.astype(dtype).reshape(-1, k),
                  kernel=cfg.solver == "gj",
                  interpret=cfg.pallas == "interpret",
                  mesh=mesh, row_sharded=row_sharded)
    return x.reshape(b.shape)


def _solve_buckets_device(
    opposing,  # [n_cols(+1 pad row), K] — gathered from; a grid's [V, G, K]
    out_rows: int,  # static: rows in the solved-for factor matrix
    buckets_dev: Sequence[tuple],  # per bucket: (rows, cols, vals, mask, segmap)
    cfg: ALSConfig,
    split_rows=None,  # [U] int32 — row ids needing cross-segment combine
    row_multiple: int = 8,
    mesh=None,  # enables the sharded Pallas solve when size > 1
    reg=None,  # λ and α: a grid's traced [G] arrays; None for a train and
    alpha=None,  # a fold, which take cfg's floats
):
    """One half-epoch: solve every row's normal equations, scatter into a
    fresh [out_rows, K] matrix. Pure jittable function of device arrays.

    The half-iteration of a train, a fold and a grid (`ops/als_grid.py`),
    which it tells by what it is handed: a grid's `opposing` is [V, G, K]
    and every row solves G systems that share its gathered entries, so
    the accumulators and the output grow a middle G axis and the chunk
    rule sees rows G·K wide; without one every shape is a train's.

    Rows split into segments (bucket_ragged_split) have their partial
    (A, b, n) scatter-added into a [U, ...] accumulator keyed by segmap and
    are solved once after the bucket loop; oversized buckets are walked in
    row chunks under a fori_loop to bound live gather memory."""
    import jax.numpy as jnp

    import jax

    # Every op below sits in one `jax.named_scope` of a fixed set
    # (als.gather_gram, als.yty, als.solve, als.split_merge, als.scatter;
    # als.rmse in the loop): the names reach the profiler's trace in the
    # ops' metadata and survive a refactor, which compiler names such as
    # `%fusion.1067` do not. Metadata only: no op or fusion changes.
    scope = jax.named_scope
    *grid, k = opposing.shape[1:]  # [] or [G]
    g = "g" if grid else ""
    if reg is None:
        reg, alpha = cfg.reg, cfg.alpha
    with scope("als.scatter"):
        new = jnp.zeros((out_rows, *grid, k), dtype=opposing.dtype)
    n_split = 0 if split_rows is None else split_rows.shape[0]
    if n_split:
        with scope("als.split_merge"):
            acc_a = jnp.zeros((n_split, *grid, k, k), dtype=jnp.float32)
            acc_b = jnp.zeros((n_split, *grid, k), dtype=jnp.float32)
            acc_n = jnp.zeros((n_split,), dtype=jnp.float32)

    solve_trace_s = 0.0  # host seconds building this side's solves
    gram = None
    if cfg.implicit:
        # global Gram over real (non-sentinel-pad) opposing rows (f32: it
        # is summed into per-row partials that may accumulate across
        # segments)
        with scope("als.yty"):
            op_c = opposing.astype(jnp.dtype(cfg.compute_dtype))
            gram = normal_eq_einsum(op_c.dtype)(f"c{g}k,c{g}l->{g}kl",
                                                op_c, op_c)

    def finalize(a, b, n, row_sharded=True):
        nonlocal solve_trace_s
        t0 = time.monotonic()
        x = _regularise_and_solve(a, b, n, gram, cfg, reg, opposing.dtype,
                                  mesh, row_sharded)
        solve_trace_s += time.monotonic() - t0
        return x

    def process(rows_c, cols_c, vals_c, mask_c, segmap_c, new, accs):
        with scope("als.gather_gram"):
            n = mask_c.sum(-1)
            a, b = _partial_normal_eqs(_gather_rows(opposing, cols_c, mesh),
                                       vals_c, mask_c, cfg, alpha)
        rows_eff = rows_c
        if segmap_c is not None:
            with scope("als.split_merge"):
                acc_a, acc_b, acc_n = accs
                accs = (acc_a.at[segmap_c].add(a, mode="drop"),
                        acc_b.at[segmap_c].add(b, mode="drop"),
                        acc_n.at[segmap_c].add(n, mode="drop"))
                # segment rows are combined+solved after the loop; drop
                # their inline (partial) solutions from the scatter
                rows_eff = jnp.where(segmap_c < n_split, out_rows, rows_c)
        with scope("als.solve"):
            x = finalize(a, b, n)
        # sentinel row ids (== out_rows) fall outside and are dropped
        with scope("als.scatter"):
            new = new.at[rows_eff].set(x.astype(new.dtype), mode="drop")
        return new, accs

    accs = (acc_a, acc_b, acc_n) if n_split else ()
    width = int(np.prod(opposing.shape[1:]))  # a gathered row's: K, or G·K
    for bucket in buckets_dev:
        cap = bucket[1].shape[1]
        new, accs = _walk_bucket_chunks(
            bucket, cap, width, row_multiple,
            lambda sliced, carry: process(*sliced, *carry), (new, accs))

    if n_split:
        with scope("als.solve"):
            x_u = finalize(*accs, row_sharded=False)
        with scope("als.scatter"):
            new = new.at[split_rows].set(x_u.astype(new.dtype), mode="drop")
    if cfg.solver == "gj":
        # which kernel this side's half-iteration holds, and what a first
        # train paid to build it: one timeline record a side and trace,
        # as `als.bucketize.<path>` says which way the bucketizer went
        from predictionio_tpu.ops import pallas_solve

        record_span(f"als.solve.{pallas_solve.layout_for(k)}", solve_trace_s)
    return new


def _predict_sq_err(u_factors, i_factors, buckets_dev, row_multiple: int = 8,
                    mesh=None):
    """Σ (uᵀv − r)² over all real entries (for RMSE history), and their
    count; a grid's [V, G, K] tables give one sum a grid point, [G]."""
    import jax.numpy as jnp

    grid = u_factors.shape[1:-1]  # () or (G,)
    g = "g" if grid else ""

    def err_chunk(sliced, carry):
        rows_c, cols_c, vals_c, mask_c, _segmap = sliced
        total, count = carry
        u = u_factors[rows_c.clip(0, u_factors.shape[0] - 1)]  # [R, (G,) K]
        v = _gather_rows(i_factors, cols_c, mesh)  # [R, C, (G,) K]
        pred = jnp.einsum(f"r{g}k,rc{g}k->rc{g}", u, v)
        entries = (...,) + (None,) * len(grid)  # beside a grid's G axis
        err = (pred - vals_c[entries]) * mask_c[entries]
        return (total + jnp.sum(err * err, axis=(0, 1)),
                count + jnp.sum(mask_c))

    total = jnp.zeros(grid, dtype=jnp.float32)
    count = jnp.zeros((), dtype=jnp.float32)
    width = int(np.prod(u_factors.shape[1:]))
    for bucket in buckets_dev:
        cap = bucket[1].shape[1]
        total, count = _walk_bucket_chunks(bucket, cap, width, row_multiple,
                                           err_chunk, (total, count))
    return total, count


@functools.lru_cache(maxsize=64)
def _get_train_loop(n_users: int, n_items: int, cfg: ALSConfig,
                    compute_rmse: bool, n_steps: int, row_multiple: int = 8,
                    mesh=None, checked: bool = False):
    """`n_steps` iterations of training as ONE jitted program: `lax.scan`
    over iterations, so a train is a single dispatch with no host round
    trips (under `jit` everything is traced once and compiled — SURVEY.md
    §7.1's 'no data-dependent Python control flow' rule applied to the ALS
    loop). RMSE history is accumulated on-device and read back once. With
    checkpointing, `n_steps` is the checkpoint interval and the host loop
    re-dispatches between saves (same compiled program each chunk)."""
    import jax
    import jax.numpy as jnp

    def run(item_factors0, user_factors0, ub_dev, ib_dev, u_split, i_split):
        def body(carry, _):
            user_f, item_f = carry
            user_f = _solve_buckets_device(item_f, n_users, ub_dev, cfg,
                                           u_split, row_multiple, mesh)
            item_f = _solve_buckets_device(user_f, n_items, ib_dev, cfg,
                                           i_split, row_multiple, mesh)
            if compute_rmse:
                with jax.named_scope("als.rmse"):
                    total, count = _predict_sq_err(user_f, item_f, ub_dev,
                                                   row_multiple, mesh)
                    rmse = jnp.sqrt(jnp.maximum(total, 0.0)
                                    / jnp.maximum(count, 1.0))
            else:
                rmse = jnp.zeros((), dtype=jnp.float32)
            if checked:
                from jax.experimental import checkify

                checkify.check(
                    jnp.all(jnp.isfinite(user_f))
                    & jnp.all(jnp.isfinite(item_f)),
                    "ALS: non-finite factors after solve (rank-deficient "
                    "normal equations or corrupt input)")
            return (user_f, item_f), rmse

        (user_f, item_f), rmses = jax.lax.scan(
            body, (user_factors0, item_factors0), xs=None, length=n_steps
        )
        return user_f, item_f, rmses

    if checked:
        from predictionio_tpu.utils import checks

        return checks.checked_jit(run)
    from predictionio_tpu.utils.profiling import metered_jit

    return metered_jit(run, label="als.train_steps")


def resolve_solver(cfg: ALSConfig) -> ALSConfig:
    """Resolve `solver='auto'` to a concrete solver for this backend/rank,
    and downgrade an unusable 'gj' request to 'chol' (with a warning).
    Shared by `als_train`, the grid evaluator and fold-in; any other
    `solver` was refused when `cfg` was made."""
    import jax

    if cfg.solver == "auto":
        from predictionio_tpu.ops import pallas_solve

        on_tpu = jax.default_backend() == "tpu"
        use_gj = (pallas_solve.gj_applicable(cfg.rank)
                  and (on_tpu or cfg.pallas == "interpret"))
        cfg = dataclasses.replace(cfg, solver="gj" if use_gj else "chol")
        log.info("als_train: solver='auto' resolved to %r (backend=%s, "
                 "rank=%d)", cfg.solver, jax.default_backend(), cfg.rank)
    elif cfg.solver == "gj":
        from predictionio_tpu.ops import pallas_solve

        if not pallas_solve.gj_applicable(cfg.rank):
            log.warning("als_train: solver='gj' rank %d exceeds the VMEM "
                        "budget; falling back to 'chol'", cfg.rank)
            cfg = dataclasses.replace(cfg, solver="chol")
        elif jax.default_backend() != "tpu" and cfg.pallas != "interpret":
            log.warning("als_train: solver='gj' needs TPU (or "
                        "pallas='interpret'); falling back to 'chol' on %s",
                        jax.default_backend())
            cfg = dataclasses.replace(cfg, solver="chol")
    return cfg


@dataclasses.dataclass
class ALSResult:
    user_factors: np.ndarray  # [n_users, K]
    item_factors: np.ndarray  # [n_items, K]
    rmse_history: list[float]
    epoch_times: list[float] = dataclasses.field(default_factory=list)
    # seconds per iteration, one entry per iteration *executed in this
    # call*, from `loop_chunks` (see `epoch_times_of`); empty when a
    # checkpointed run was already complete and fully resumed
    start_epoch: int = 0
    # first epoch executed in this call (>0 when resumed from a checkpoint)
    loop_chunks: list["LoopChunk"] = dataclasses.field(default_factory=list)
    # one per dispatch of the train loop


class LoopChunk(NamedTuple):
    """One dispatch of the train loop, by the stamps around its
    `als.loop.dispatch` and `als.loop.wait` spans."""

    steps: int
    dispatch_s: float
    wait_s: float
    compiled: bool  # the dispatch traced + compiled (or loaded a cache)


def epoch_times_of(chunks: Sequence[LoopChunk]) -> list[float]:
    """Seconds per iteration from the train loop's own stamps: dispatch +
    fence of the chunks that ran warm, over their steps. A chunk whose
    dispatch compiled (trace, lower, compile or cache load: 5-70 s at
    ML-20M) is left out; when every chunk compiled, their fences alone
    stand in, since the program runs after the dispatch has returned.
    Checkpoint saves and host gathers lie outside both stamps."""
    executed = sum(c.steps for c in chunks)
    warm = [c for c in chunks if not c.compiled]
    if warm:
        seconds = sum(c.dispatch_s + c.wait_s for c in warm)
    else:
        seconds = sum(c.wait_s for c in chunks)
    steps = sum(c.steps for c in (warm or chunks))
    return [seconds / steps] * executed if executed else []


def emit_train_metrics(metrics, result: ALSResult) -> None:
    """One `train/als` record per iteration executed in this call
    (`epoch_time_s`, and `rmse` where it was tracked) into a
    `MetricsLogger`: what every ALS template's `train` reports."""
    import math

    # a resumed run skips its first start_epoch epochs; rmse_history
    # covers all of them
    for off, t in enumerate(result.epoch_times):
        step = result.start_epoch + off + 1
        rec = {"epoch_time_s": t}
        if result.rmse_history and step <= len(result.rmse_history):
            rmse = result.rmse_history[step - 1]
            if not math.isnan(rmse):  # NaN = epoch predates RMSE tracking
                rec["rmse"] = rmse
        metrics.emit("train/als", step=step, **rec)


@_spanned("als.train")
def als_train(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    cfg: ALSConfig,
    mesh=None,
    compute_rmse: bool = False,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    resume: bool = True,
    bucket_cache_dir: Optional[str] = None,
) -> ALSResult:
    """Train ALS factors from COO ratings.

    mesh: a `jax.sharding.Mesh` (default: all local devices on `data`).
    Bucket rows are sharded over the `data` axis; factor matrices are
    replicated. This is SURVEY.md §2.6 strategy 2 (MLlib's block-parallel
    ALS) re-expressed for ICI.

    checkpoint_dir: when set, factors are checkpointed every
    `checkpoint_every` iterations (SURVEY.md §5 'Checkpoint / resume') and
    an interrupted run resumes from the latest saved step (resume=True).
    Checkpointing chunks the single-dispatch scan into
    `checkpoint_every`-sized dispatches; with it off the whole run stays
    one dispatch.

    bucket_cache_dir: when set, the host bucketize result is cached on
    disk under a fingerprint of the training data + every bucketizer
    input (run in series a miss is 2.9 s of a 7.1 s train call at
    ML-20M's size, rank 64, one v5e: ledger, PR 43, `als64.train10`; the
    result is identical across re-trains on unchanged events); new events
    or a changed mesh/splitCap/cap_growth miss and rebucketize. On a miss
    the two sides are bucketized at once, a thread a side, and the new
    entry is saved on a third behind the device loop, joined before this
    function returns or raises (`BucketCacheSave`).
    """
    # the save of a new cache entry runs behind the loop and is joined
    # here on every way out: the entry stands when a train returns
    with BucketCacheSave() as cache_save:
        return _als_train(user_idx, item_idx, ratings, n_users, n_items, cfg,
                          mesh, compute_rmse, checkpoint_dir,
                          checkpoint_every, resume, bucket_cache_dir,
                          cache_save)


def _als_train(user_idx, item_idx, ratings, n_users: int, n_items: int,
               cfg: ALSConfig, mesh, compute_rmse: bool,
               checkpoint_dir: Optional[str], checkpoint_every: int,
               resume: bool, bucket_cache_dir: Optional[str],
               cache_save: BucketCacheSave) -> ALSResult:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from predictionio_tpu.parallel.mesh import DATA_AXIS, make_mesh

    if mesh is None:
        mesh = make_mesh()
    n_data = mesh.shape.get(DATA_AXIS, 1)
    from predictionio_tpu.parallel.mesh import MODEL_AXIS

    n_model = mesh.shape.get(MODEL_AXIS, 1)
    model_sharded = n_model > 1
    if model_sharded:
        # factors shard P('model'); per-device chunks must stay a multiple
        # of the model-axis size for the per-chunk psum_scatter
        from predictionio_tpu.ops import als_sharded

        rm_local = als_sharded.local_row_multiple(n_model)
        row_multiple = rm_local * n_data
    else:
        row_multiple = max(8, n_data)
        if row_multiple % n_data:  # non-pow2 data axis: keep shards even
            row_multiple = 8 * n_data

    from predictionio_tpu.utils import checks as _checks

    if _checks.enabled() and model_sharded:
        log.warning("als_train: --check-asserts is not supported with "
                    "model-axis factor sharding (checkify does not compose "
                    "with the shard_mapped loop); running unchecked")
    if _checks.enabled() and not model_sharded:
        # checkify cannot transform pallas_call (KeyError: closed_call), so
        # assert mode pins the pure-XLA solver path
        if cfg.solver != "chol" or cfg.pallas != "off":
            log.info("als_train: --check-asserts forces the XLA solver path "
                     "(checkify cannot transform Pallas kernels)")
        cfg = dataclasses.replace(cfg, solver="chol", pallas="off")

    cfg = resolve_solver(cfg)

    split_cap = cfg.split_cap if cfg.split_cap > 0 else None

    # hash the (large) training arrays at most once per train; both the
    # bucket-cache key and the checkpoint fingerprint derive from it
    _digest_memo: list[str] = []

    def data_digest() -> str:
        if not _digest_memo:
            _digest_memo.append(_arrays_digest(user_idx, item_idx, ratings))
        return _digest_memo[0]

    user_buckets, u_split, item_buckets, i_split = bucketize_cached(
        user_idx, item_idx, ratings, n_users, n_items, row_multiple,
        split_cap, cfg.cap_growth, bucket_cache_dir, data_digest, cache_save)
    log.info(
        "als_train: %d ratings, %d users (%d buckets, caps %s, %d split), "
        "%d items (%d buckets, caps %s, %d split), rank %d, mesh %s",
        len(ratings), n_users, len(user_buckets),
        [b.cap for b in user_buckets], len(u_split), n_items,
        len(item_buckets), [b.cap for b in item_buckets], len(i_split),
        cfg.rank, dict(mesh.shape),
    )
    for side, buckets in (("user", user_buckets), ("item", item_buckets)):
        # the split bucketizer truncates nothing: every rating is placed
        # once a side
        BUCKET_ENTRIES.labels(side=side).set(len(ratings))
        BUCKET_CELLS.labels(side=side).set(
            sum(b.cols.shape[0] * b.cols.shape[1] for b in buckets))

    dtype = jnp.dtype(cfg.dtype)
    row_shard = NamedSharding(mesh, P(DATA_AXIS))
    rep = NamedSharding(mesh, P())

    def chunk_rows(r_total: int, cap: int) -> int:
        # in model-sharded mode the walk runs per device on local rows,
        # so the alignment is computed in local units × n_data
        if model_sharded:
            return n_data * _bucket_chunk_rows(r_total // n_data, cap,
                                               cfg.rank, rm_local)
        return _bucket_chunk_rows(r_total, cap, cfg.rank, row_multiple)

    with span("als.put_buckets"):
        ub_dev, u_split_dev = place_buckets(
            "user", user_buckets, n_users, u_split, chunk_rows, row_shard, rep)
        ib_dev, i_split_dev = place_buckets(
            "item", item_buckets, n_items, i_split, chunk_rows, row_shard, rep)

    # factor sharding: replicated on a data-only mesh; row-sharded over
    # the `model` axis otherwise (VERDICT r1 #3 — config 5's capability)
    if model_sharded:
        n_users_pad = als_sharded.pad_to(max(n_users, 1), n_model)
        n_items_pad = als_sharded.pad_to(max(n_items, 1), n_model)
        factor_sharding = NamedSharding(mesh, P(MODEL_AXIS, None))
    else:
        n_users_pad, n_items_pad = n_users, n_items
        factor_sharding = rep

    def place_factors(uf, itf):
        """Host/device [n, K] factor pairs → padded, sharded device arrays
        (pad rows are zero so implicit-mode Gram sums are unaffected)."""
        uf = np.asarray(uf)
        itf = np.asarray(itf)
        if model_sharded:
            uf = np.concatenate(
                [uf, np.zeros((n_users_pad - n_users, cfg.rank), uf.dtype)])
            itf = np.concatenate(
                [itf, np.zeros((n_items_pad - n_items, cfg.rank), itf.dtype)])
        return (jax.device_put(uf, factor_sharding),
                jax.device_put(itf, factor_sharding))

    # identity re-shard, not a compute boundary: metering it would count
    # a "compile" for a data movement the inventory can't blame
    replicate = jax.jit(lambda x: x, out_shardings=rep)  # pio-lint: disable=coverage-jit-metering

    def factors_to_host():
        """Host [n, K] copies of the live factor arrays.

        Multi-process with model-sharded factors: the shards span
        non-addressable devices, so `np.asarray` would raise. Re-shard to
        replicated through the jitted identity first — a collective, so
        ALL ranks must call this (rank-0-only callers would deadlock the
        world; see the checkpoint block below)."""
        with span("als.readback"):
            uf, vf = user_factors, item_factors
            if jax.process_count() > 1 and not uf.is_fully_replicated:
                uf, vf = replicate(uf), replicate(vf)
            return np.asarray(uf)[:n_users], np.asarray(vf)[:n_items]

    # init item factors ~ N(0, 1/sqrt(rank)) like MLlib; users solved first
    with span("als.init_factors"):
        key = jax.random.key(cfg.seed)
        item_init = (jax.random.normal(key, (n_items, cfg.rank), dtype=dtype)
                     / np.sqrt(cfg.rank))
        user_factors, item_factors = place_factors(
            jnp.zeros((n_users, cfg.rank), dtype=dtype), item_init)

    checkpoint_every = max(1, checkpoint_every)
    start_iter = 0
    rmse_history: list[float] = []
    manager = None
    # resolve the checkpoint-writing rank ONCE, before any epoch runs —
    # an out-of-range PIO_PERSIST_RANK must fail here, not discard a
    # computed epoch at the first save (single-process runs ignore it)
    ckpt_rank = _persist_rank() if checkpoint_dir else 0
    if checkpoint_dir:
        import hashlib

        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        # fingerprint the training data + solver config: a checkpoint only
        # resumes the *same* run. New ratings (nightly retrain into the
        # same dir) or a changed rank/reg/seed must retrain from scratch,
        # not return yesterday's completed factors.
        fingerprint = hashlib.blake2b(
            (data_digest()
             + repr((n_users, n_items, cfg.rank, cfg.reg, cfg.weighted_reg,
                     cfg.implicit, cfg.alpha, cfg.seed,
                     cfg.dtype))).encode(),
            digest_size=8,
        ).hexdigest()
        manager = CheckpointManager(checkpoint_dir)
        # resume from the largest saved step that (a) doesn't overshoot the
        # requested iteration count and (b) fingerprints as this same run.
        # Other steps are stale; they're purged right before this run's
        # FIRST save (not at start: deleting eagerly would open a window —
        # from run start until the first new save — in which a crash
        # leaves no checkpoint at all; stale steps left in place would
        # shadow the new saves under the keep-highest retention GC).
        restore_step = None
        if resume:
            usable = [s for s in manager.all_steps() if s <= cfg.iterations]
            if usable:
                with span("als.init_factors"):
                    tree, meta = manager.restore(usable[-1])
                    uf = (tree.get("user_factors")
                          if isinstance(tree, dict) else None)
                    vf = (tree.get("item_factors")
                          if isinstance(tree, dict) else None)
                    if (meta.get("fingerprint") == fingerprint
                            and uf is not None and vf is not None
                            and uf.shape == (n_users, cfg.rank)
                            and vf.shape == (n_items, cfg.rank)):
                        user_factors, item_factors = place_factors(uf, vf)
                        restore_step = start_iter = usable[-1]
                        rmse_history = list(
                            meta.get("rmse_history", []))[:start_iter]
                        log.info("als_train: resumed from checkpoint step "
                                 "%d", restore_step)
                    else:
                        log.warning(
                            "als_train: checkpoint at %s is from different "
                            "data/config (or a foreign tree, or an older "
                            "build whose data digest read otherwise) — "
                            "training from scratch", checkpoint_dir)
        if not compute_rmse:
            rmse_history = []
        elif len(rmse_history) < start_iter:
            # resumed from a run that didn't record RMSE: mark the missing
            # prefix so indices stay aligned with absolute epoch numbers
            rmse_history = ([float("nan")] * (start_iter - len(rmse_history))
                            + rmse_history)

    # One dispatch for the whole run (or per checkpoint chunk): the
    # iteration loop is a lax.scan inside a single jitted program, so
    # there are no per-epoch host round trips (a sync per epoch would
    # dwarf the compute at quickstart scale). Epoch times come from the
    # stamps around each chunk's dispatch and fence (`epoch_times_of`).
    loop_chunks: list[LoopChunk] = []
    done = start_iter
    first_save_done = False
    host_copies = None  # (uf, vf) from the last checkpoint save, if any
    while done < cfg.iterations:
        n_steps = (min(checkpoint_every, cfg.iterations - done)
                   if manager else cfg.iterations - done)
        # cache key excludes cfg.iterations (the traced program only sees
        # n_steps) so runs differing in iteration count share the compile
        if model_sharded:
            train = als_sharded.get_train_loop_sharded(
                n_users_pad, n_items_pad,
                dataclasses.replace(cfg, iterations=0), compute_rmse,
                n_steps, rm_local, mesh,
                tuple(b[4] is not None for b in ub_dev),
                tuple(b[4] is not None for b in ib_dev),
                len(u_split), len(i_split))
        else:
            train = _get_train_loop(n_users, n_items,
                                    dataclasses.replace(cfg, iterations=0),
                                    compute_rmse, n_steps, row_multiple,
                                    mesh if mesh.size > 1 else None,
                                    checked=_checks.enabled())
        # `metered_jit` knows whether a call compiled; the checked loop
        # (a debug path) is not metered and counts as warm
        compile_count = getattr(train, "compile_count", lambda: 0)
        compiles_before = compile_count()
        cache_save.start()
        t0 = time.monotonic()
        with span("als.loop.dispatch"):
            user_factors, item_factors, rmses = train(
                item_factors, user_factors, ub_dev, ib_dev,
                u_split_dev, i_split_dev)
        t1 = time.monotonic()
        # execution fence: the scalar readback waits for the dispatch
        # (whether block_until_ready would do on the current chip is not
        # measured; timing is S1's)
        with span("als.loop.wait"):
            float(item_factors[0, 0])
        loop_chunks.append(LoopChunk(n_steps, t1 - t0, time.monotonic() - t1,
                                     compile_count() > compiles_before))
        done += n_steps
        # elastic-recovery drill point (SURVEY.md §5): a rank hard-dying
        # between a computed chunk and its checkpoint save is the worst
        # moment for the rest of the world
        faults.inject("als.epoch_boundary")
        if compute_rmse:
            rmse_history.extend(float(x) for x in np.asarray(rmses))
        # multi-host: all ranks restore (consistent global start state) and
        # all ranks join the host-gather collective, but only the persist
        # rank (PIO_PERSIST_RANK, default 0) writes — N ranks racing
        # save/keep_only on a shared checkpoint dir could interleave
        # delete-vs-write mid-step
        if manager:
            host_copies = uf_host, vf_host = factors_to_host()
            if jax.process_index() == ckpt_rank:
                if not first_save_done:
                    manager.keep_only(restore_step)
                    first_save_done = True
                manager.save(
                    done,
                    {"user_factors": uf_host, "item_factors": vf_host},
                    metadata={"rmse_history": rmse_history,
                              "iterations": cfg.iterations, "rank": cfg.rank,
                              "fingerprint": fingerprint},
                )
    if model_sharded:
        # product invariant, checked on the real train output (not a test
        # spy): config 5's capability is that training factors are
        # genuinely row-sharded over `model` — a silent fallback to
        # replicated factors would still produce correct numbers while
        # quietly giving up the pod-scale memory story (VERDICT r2 #1)
        spec = item_factors.sharding.spec
        if not spec or spec[0] != MODEL_AXIS:
            raise AssertionError(
                f"als_train: mesh {dict(mesh.shape)} requested model-axis "
                f"factor sharding but trained factors came back {spec!r}")
        log.info("als_train: training factors model-sharded %s over mesh %s",
                 tuple(spec), dict(mesh.shape))
    if (manager and jax.process_index() == ckpt_rank
            and not first_save_done and restore_step is not None):
        # fully-resumed run (no new saves): still purge stale steps now —
        # the restore point is on disk, so there's no crash window here.
        # (restore_step=None with no saves means a degenerate run, e.g.
        # iterations=0 — leave the directory untouched.)
        manager.keep_only(restore_step)
    if compute_rmse and rmse_history:
        log.info("als_train: rmse %.4f → %.4f over %d iters",
                 rmse_history[0], rmse_history[-1], cfg.iterations)

    # the last checkpoint save already gathered these exact factors
    uf_host, vf_host = host_copies if host_copies else factors_to_host()
    return ALSResult(
        user_factors=uf_host,
        item_factors=vf_host,
        rmse_history=rmse_history,
        epoch_times=epoch_times_of(loop_chunks),
        start_epoch=start_iter,
        loop_chunks=loop_chunks,
    )

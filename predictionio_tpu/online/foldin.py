"""FoldIn: incremental single-side ALS solves against fixed opposing factors.

ALS alternation already solves each side's rows independently — one row's
normal equations (Σ_j y_j y_jᵀ + λ·n·I) x = Σ_j r_j y_j never read another
row of the same side. Fold-in exploits that: when events touch a handful
of users/items, re-solve exactly those rows against the *fixed* opposite
factors instead of retraining. The solve here is literally one
`ops.als._solve_buckets_device` half-epoch restricted to the dirty rows —
same `bucket_ragged` capacity ladder and per-row column sort, same masked
f32-accumulated Gram einsum, same weighted regularization and solver — so
a folded row is bit-identical to what a fresh half-epoch against the same
opposing factors would produce (the parity tests assert `array_equal`).

Never-seen entity ids get appended rows: the BiMap grows at the end (old
codes keep their factor rows), the factor matrix gains zero rows, and the
next solve fills them. A zero opposing row contributes nothing to a
neighbor's normal equations, so cold items referenced from a user's
history before their own fold are simply ignored — matching what a
retrain without that item would have served.

Hot rows are NOT segment-split here (train's `bucket_ragged_split`): a
fold batch touches few rows, so one bucket per cap is cheap, and
splitting would change f32 partial-sum association vs the parity
reference.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.als_model import ALSModel
from predictionio_tpu.online.metrics import (
    ONLINE_COLD_START_ROWS,
    ONLINE_ROWS_FOLDED,
)
from predictionio_tpu.ops.als import (
    ALSConfig,
    _bucket_chunk_rows,
    _solve_buckets_device,
    bucket_ragged,
    resolve_solver,
)
from predictionio_tpu.telemetry import device as device_telemetry


# fold batches chunk into row-tier-ladder solves — see solve_rows
MAX_ROWS_PER_SOLVE = 128


@functools.lru_cache(maxsize=16)
def _fold_solver(cfg: ALSConfig):
    """One jitted half-epoch solve per (resolved) config; XLA's own jit
    cache handles the per-bucket-shape retraces under it. metered_jit
    (not bare jax.jit) so every fold solve lands in the jit-cache
    inventory and the device clock's attribution — a retrace storm here
    names its changed tier in /debug/jit.json instead of surfacing as
    ingest-backlog mush."""
    from predictionio_tpu.utils.profiling import metered_jit

    def run(opposing, buckets_dev, out_rows):
        return _solve_buckets_device(opposing, out_rows, buckets_dev, cfg)

    return metered_jit(run, label="foldin.solve",
                       static_argnames=("out_rows",))


def solve_rows(opposing: np.ndarray,
               entries: Sequence[Tuple[np.ndarray, np.ndarray]],
               cfg: ALSConfig) -> np.ndarray:
    """Solve the normal equations of `len(entries)` independent rows
    against fixed `opposing` [V, K] factors.

    `entries[i]` is `(cols, vals)` — opposing-row ids and ratings of the
    i-th dirty row's FULL history. Returns `[len(entries), K]` float
    factors (cfg.dtype). A row with an empty history solves to zeros
    (its bucket row is all padding), same as an eventless row in train.
    """
    cfg = resolve_solver(cfg)
    n = len(entries)
    if n == 0:
        return np.zeros((0, opposing.shape[-1]), dtype=opposing.dtype)
    if n > MAX_ROWS_PER_SOLVE:
        # rows are independent (that's the whole fold-in premise), so a
        # huge backlog batch chunks into fixed-ladder solves instead of
        # minting a fresh executable shape for its exact size
        return np.concatenate([
            solve_rows(opposing, entries[i:i + MAX_ROWS_PER_SOLVE], cfg)
            for i in range(0, n, MAX_ROWS_PER_SOLVE)])
    rows = np.concatenate([
        np.full(len(c), i, dtype=np.int32)
        for i, (c, _) in enumerate(entries)] or
        [np.zeros(0, np.int32)])
    cols = np.concatenate([np.asarray(c, np.int32) for c, _ in entries])
    vals = np.concatenate([np.asarray(v, np.float32) for _, v in entries])
    buckets = bucket_ragged(rows, cols, vals, n_rows=n,
                            cap_growth=cfg.cap_growth, side="fold")
    k = opposing.shape[-1]
    # the opposing factor matrix grows a few rows per cold append, and
    # its row count is a traced shape — unpadded, EVERY post-append fold
    # would recompile. Padding rows are never gathered (history cols all
    # point below the real row count), so they change no bit of any
    # solve.
    vtier = 8
    while vtier < opposing.shape[0]:
        vtier *= 4
    if vtier > opposing.shape[0]:
        opposing = np.concatenate([
            opposing,
            np.zeros((vtier - opposing.shape[0], k), opposing.dtype)])
    # A long-lived fold stream must not mint solver shapes forever: a
    # fresh (bucket shapes, out_rows) combination costs an XLA retrace
    # (~0.35 s on CPU uncontended, several seconds under serving load,
    # vs ~1 ms warm — measured; it is the difference between draining an
    # ingest backlog and drowning in it). So every solve collapses to
    # ONE bucket on a coarse ladder: all ragged buckets pad to the
    # power-of-4 cap tier {8, 32, 128, …} of the WIDEST history and
    # merge (a masked pad entry adds an exact-zero term to the Gram sum,
    # so rows stay bit-identical to their own-capacity solve), and the
    # row count — one bucket row per entry — pads to the matching
    # power-of-4 tier with scratch rows that scatter to the sliced-off
    # row `n`. With the MAX_ROWS_PER_SOLVE chunking above, the whole
    # executable space is {8, 32, 128} row tiers × the log-sized cap
    # ladder, each compiled once per server lifetime.
    cap_max = max(b.cols.shape[1] for b in buckets)
    tcap = 8
    while tcap < cap_max:
        tcap *= 4
    parts = []
    for b in buckets:
        wpad = tcap - b.cols.shape[1]
        bc, bv, bm = b.cols, b.vals, b.mask
        if wpad:
            bc = np.pad(bc, ((0, 0), (0, wpad)))
            bv = np.pad(bv, ((0, 0), (0, wpad)))
            bm = np.pad(bm, ((0, 0), (0, wpad)))
        parts.append((b.rows, bc, bv, bm))
    br = np.concatenate([p[0] for p in parts])
    bc = np.concatenate([p[1] for p in parts])
    bv = np.concatenate([p[2] for p in parts])
    bm = np.concatenate([p[3] for p in parts])
    # bucket_ragged pads each bucket's rows to a multiple of 8 with
    # scratch rows (id = n, mask 0); after a merge that leftover varies
    # with how the ladder happened to group histories, which would leak
    # data-dependent row counts into the executable shape. Strip it —
    # a scratch row only scatter-adds zero into the sliced-off row `n`
    # — leaving exactly one bucket row per entry, then re-pad onto the
    # deterministic tier for `n`.
    real = br != n
    br, bc, bv, bm = br[real], bc[real], bv[real], bm[real]
    target = 8
    while target < n:
        target *= 4
    # then to a chunk multiple so _solve_buckets_device's chunk walk
    # covers the bucket exactly (same arithmetic as place_buckets)
    chunk = _bucket_chunk_rows(target, tcap, k, 8)
    pad = (target - n) + ((-target) % chunk)
    if pad:
        br = np.concatenate([br, np.full(pad, n, np.int32)])
        bc = np.concatenate([bc, np.zeros((pad, tcap), bc.dtype)])
        bv = np.concatenate([bv, np.zeros((pad, tcap), bv.dtype)])
        bm = np.concatenate([bm, np.zeros((pad, tcap), bm.dtype)])
    # out_rows is a STATIC jit arg (it shapes the scatter target), so it
    # rides the same row tier: solve into a padded output and slice.
    # Bucket padding rows scatter into row `n` — inside the padded range
    # now, but that scratch row is sliced off with the rest of the pad.
    run = _fold_solver(cfg)
    # device attribution: fold solves bill to the online plane, tiered by
    # the row ladder the executable space is keyed on
    with device_telemetry.attribution("online.foldin", tier=str(target)):
        out = run(np.ascontiguousarray(opposing),
                  ((br, bc, bv, bm, None),), out_rows=target)
    return np.asarray(out[:n])


class SeenOverlay:
    """Immutable seen-items view: a base SeenItems/dict plus per-row
    overrides for folded users. Overlay-on-overlay flattens, so repeated
    fold passes don't build a lookup chain."""

    __slots__ = ("_base", "_delta")

    def __init__(self, base, delta: Dict[int, np.ndarray]):
        if isinstance(base, SeenOverlay):
            merged = dict(base._delta)
            merged.update(delta)
            base, delta = base._base, merged
        self._base = base
        self._delta = delta

    def get(self, user_row: int, default=None):
        hit = self._delta.get(user_row)
        if hit is not None:
            return hit
        if not self._base:
            return default
        return self._base.get(user_row, default)

    def __len__(self) -> int:
        return (len(self._base) if self._base else 0) + len(self._delta)

    def __bool__(self) -> bool:
        return True


def extend_bimap(bimap: BiMap, ids: Sequence[str]) -> Tuple[BiMap, List[str]]:
    """Append never-seen ids with the next dense codes. Existing codes are
    untouched (factor rows stay valid); returns (bimap', appended_ids)."""
    new = [i for i in ids if i not in bimap]
    if not new:
        return bimap, []
    fwd = bimap.to_dict()
    for i in new:
        fwd[i] = len(fwd)
    return BiMap(fwd), new


def _pad_rows(factors: np.ndarray, n_rows: int) -> np.ndarray:
    if factors.shape[0] >= n_rows:
        return factors
    pad = np.zeros((n_rows - factors.shape[0], factors.shape[1]),
                   dtype=factors.dtype)
    return np.concatenate([factors, pad])


@dataclasses.dataclass
class FoldStats:
    folded_users: int = 0
    folded_items: int = 0
    new_users: int = 0
    new_items: int = 0


def fold_model(model: ALSModel, cfg: ALSConfig,
               user_hist: Dict[str, List[Tuple[str, float]]],
               item_hist: Optional[Dict[str, List[Tuple[str, float]]]] = None,
               ) -> Tuple[ALSModel, FoldStats]:
    """Fold dirty users (and optionally items) into a NEW ALSModel.

    `user_hist[user_id]` is the user's full `(item_id, value)` history —
    full, not delta, so replaying a batch after a crash re-solves to the
    identical factors (idempotence is what makes the tailer's
    at-least-once delivery safe). Users fold first against the current
    item factors, then items against the *updated* user factors — the
    same alternation order as a training epoch. The input model is never
    mutated; serving keeps reading the old immutable state until the
    caller swaps.
    """
    item_hist = item_hist or {}
    stats = FoldStats()

    # grow the id spaces first so every history row has a factor row to
    # point at (zero rows until their own side solves)
    new_user_ids = set(user_hist)
    new_item_ids = set(item_hist)
    for h in user_hist.values():
        new_item_ids.update(i for i, _ in h)
    for h in item_hist.values():
        new_user_ids.update(u for u, _ in h)
    user_ids, added_users = extend_bimap(model.user_ids, sorted(new_user_ids))
    item_ids, added_items = extend_bimap(model.item_ids, sorted(new_item_ids))
    user_factors = _pad_rows(np.asarray(model.user_factors), len(user_ids))
    item_factors = _pad_rows(np.asarray(model.item_factors), len(item_ids))
    stats.new_users, stats.new_items = len(added_users), len(added_items)
    if added_users:
        ONLINE_COLD_START_ROWS.labels(side="user").inc(len(added_users))
    if added_items:
        ONLINE_COLD_START_ROWS.labels(side="item").inc(len(added_items))

    def entries(hist, col_map):
        out = []
        for _, pairs in hist:
            cols = np.asarray([col_map[i] for i, _ in pairs], np.int32)
            vals = np.asarray([v for _, v in pairs], np.float32)
            out.append((cols, vals))
        return out

    seen_delta: Dict[int, np.ndarray] = {}
    if user_hist:
        hist = sorted(user_hist.items())
        u_rows = np.asarray([user_ids[u] for u, _ in hist], np.int32)
        solved = solve_rows(item_factors, entries(hist, item_ids), cfg)
        user_factors = user_factors.copy()
        user_factors[u_rows] = solved.astype(user_factors.dtype)
        stats.folded_users = len(hist)
        ONLINE_ROWS_FOLDED.labels(side="user").inc(len(hist))
        for (u, pairs), row in zip(hist, u_rows):
            seen_delta[int(row)] = np.unique(np.asarray(
                [item_ids[i] for i, _ in pairs], np.int32))
    if item_hist:
        hist = sorted(item_hist.items())
        i_rows = np.asarray([item_ids[i] for i, _ in hist], np.int32)
        solved = solve_rows(user_factors, entries(hist, user_ids), cfg)
        item_factors = item_factors.copy()
        item_factors[i_rows] = solved.astype(item_factors.dtype)
        stats.folded_items = len(hist)
        ONLINE_ROWS_FOLDED.labels(side="item").inc(len(hist))

    seen = model.seen
    if seen_delta:
        seen = SeenOverlay(seen, seen_delta)
    folded = dataclasses.replace(
        model, user_factors=user_factors, item_factors=item_factors,
        user_ids=user_ids, item_ids=item_ids, seen=seen)
    return folded, stats


# -- FoldModel protocol -------------------------------------------------------
# The online plane folds MODEL FAMILIES, not ALS specifically: a fold
# handle owns everything family-specific (what a "fold" recomputes, from
# which slice of the histories) while the plane keeps everything
# family-agnostic (tailing, watermarks, history gathering, delta-swap,
# lineage, freshness). A handle implements:
#
#     family: str                      # metric label ("als", "sessionrec")
#     fold(model, user_hist, item_hist) -> (new_model, stats)
#
# where `user_hist[user]` / `item_hist[item]` are the entity's FULL
# keep-last history as [(opposing_id, value, event_time)] triples — full,
# not delta, so any handle's fold is idempotent under the tailer's
# at-least-once replay. Handles must never mutate the input model
# (serving reads the old immutable state until the swap).


class FoldModel:
    """Protocol base for online fold handles (duck-typed; subclassing is
    optional and exists for isinstance-based documentation/tests)."""

    family: str = ""

    def fold(self, model, user_hist, item_hist):  # pragma: no cover - protocol
        raise NotImplementedError


def _strip_times(hist: Optional[Dict[str, list]]) -> Dict[str, list]:
    """[(id, value, t)] → [(id, value)], order preserved — exactly the
    pairs `fold_model` always consumed, so the adapter changes no bit of
    the ALS fold inputs."""
    if not hist:
        return {}
    return {k: [(o, v) for o, v, _ in triples]
            for k, triples in hist.items()}


class ALSFold(FoldModel):
    """The ALS family as a fold handle: a thin adapter over `fold_model`
    (which stays the public, signature-stable entry point) — it only
    drops the event times the generalized history form carries, because
    an ALS re-solve is a pure function of (opposing id, value) pairs."""

    family = "als"

    def __init__(self, cfg: ALSConfig):
        self.cfg = cfg

    def fold(self, model: ALSModel, user_hist, item_hist):
        return fold_model(model, self.cfg, _strip_times(user_hist),
                          _strip_times(item_hist))

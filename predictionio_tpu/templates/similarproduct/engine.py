"""Similar Product engine template (DASE components).

Parity with the reference Similar Product template (SURVEY.md §2.4 [U]):
users `view` items, `$set` item entities carry `categories`; ALS is trained
on implicit view events («ALS.trainImplicit» → ops.als implicit mode) and
the item factors are collected P2L-style («ALSModel(productFeatures.
collectAsMap)» [U]) into an in-memory cosine-similarity model. Queries name
a basket of items and get back the most similar other items, with
whiteList/blackList/categories filters.

Wire shapes (kept reference-compatible):
    query:  {"items": ["i1"], "num": 4,
             "categories": [...]?, "whiteList": [...]?, "blackList": [...]?}
    result: {"itemScores": [{"item": "i5", "score": 0.93}, ...]}
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource as BaseDataSource,
    Engine,
    EngineFactory,
    FirstServing,
    Params,
    Preparator as BasePreparator,
    SanityCheck,
    WorkflowContext,
)
from predictionio_tpu.data.bimap import BiMap, compress_codes
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.ops.als import ALSConfig, als_train, emit_train_metrics
from predictionio_tpu.telemetry.spans import span

log = logging.getLogger(__name__)

Query = dict
PredictedResult = dict


@dataclasses.dataclass
class DataSourceParams(Params):
    appName: str = ""
    similarEvents: list = dataclasses.field(default_factory=lambda: ["view"])
    evalK: int = 0  # >0 enables read_eval with k folds


@dataclasses.dataclass
class TrainingData(SanityCheck):
    """Columnar view events (coded COO via BiMaps — no per-event Python;
    VERDICT r1 #4) + per-item category properties ($set-folded)."""

    user_idx: np.ndarray  # [n] int32 codes into user_ids
    item_idx: np.ndarray  # [n] int32 codes into item_ids
    user_ids: BiMap
    item_ids: BiMap
    item_categories: dict  # item id string → list of category strings

    @property
    def users(self) -> list:
        """Decoded user id strings (debug/compat view; O(n) Python)."""
        return self.user_ids.from_index(self.user_idx)

    @property
    def items(self) -> list:
        return self.item_ids.from_index(self.item_idx)

    def sanity_check(self):
        if not len(self.user_idx):
            raise ValueError(
                "TrainingData has no view events; ingest view events first."
            )


class DataSource(BaseDataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        self.params = params

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        store = PEventStore(ctx.storage)
        cols = store.find_columnar(
            app_name=self.params.appName,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.similarEvents),
            ordered=False,  # per-pair counts are order-invariant
        )
        valid = cols.target_ids >= 0
        item_props = store.aggregate_properties(
            app_name=self.params.appName, entity_type="item"
        )
        item_categories = {
            eid: list(p.get("categories", []) or [])
            for eid, p in item_props.items()
        }
        log.info(
            "DataSource: %d view events, %d items with properties, app %r",
            int(valid.sum()), len(item_categories), self.params.appName,
        )
        return TrainingData(
            user_idx=cols.entity_ids[valid],
            item_idx=cols.target_ids[valid],
            user_ids=cols.entity_bimap,
            item_ids=cols.target_bimap,
            item_categories=item_categories,
        )

    def read_eval(self, ctx: WorkflowContext):
        """k-fold leave-views-out evaluation (round 5 — the reference's
        similarproduct template ships no evaluation; this gives the
        engine one so `pio eval` param grids work, riding the same
        shape the recommendation template's read_eval uses).

        Folds partition distinct (user, item) PAIRS, not raw events:
        repeat views are the training confidence signal, but a pair with
        copies on both sides of the split would let the model score a
        memorized pair as a hit (train/test leakage). Per fold, each
        held-out pair (u, Y) whose user keeps ≥1 training pair with a
        DIFFERENT item X becomes a query {"items": [X], "num": N} with
        actual {"items": [Y]} — "users who viewed X also viewed Y" is
        exactly the item-item claim the model makes. All fold math is
        vectorized numpy; Python touches only the held-out pairs it
        decodes (the no-per-event-Python rule, VERDICT r1 #4)."""
        k = self.params.evalK
        if k <= 1:
            raise ValueError("DataSourceParams.evalK must be >= 2 for "
                             "evaluation")
        td = self.read_training(ctx)
        n_items = max(len(td.item_ids), 1)
        pair = td.user_idx.astype(np.int64) * n_items + td.item_idx
        uniq = np.unique(pair)  # sorted → pu is sorted too
        pu = (uniq // n_items).astype(np.int32)
        pi = (uniq % n_items).astype(np.int32)
        rank_in_user = np.arange(len(uniq)) - np.searchsorted(pu, pu)
        assign = rank_in_user % k
        ev_pair_pos = np.searchsorted(uniq, pair)  # event → its pair row
        inv_items = td.item_ids.inverse()
        folds = []
        for fold in range(k):
            tr = assign != fold
            # fold training data = every RAW event whose pair is kept
            # (repeats preserved — they're the confidence weights)
            keep_ev = tr[ev_pair_pos]
            fold_td = TrainingData(
                user_idx=td.user_idx[keep_ev], item_idx=td.item_idx[keep_ev],
                user_ids=td.user_ids, item_ids=td.item_ids,
                item_categories=td.item_categories)
            # per-user anchor = first KEPT item; pairs are distinct per
            # user, so a kept anchor can never equal a held-out item
            tr_u, tr_i = pu[tr], pi[tr]
            users_with, first = np.unique(tr_u, return_index=True)
            anchor1 = dict(zip(users_with.tolist(), tr_i[first].tolist()))
            qa = []
            for u, i in zip(pu[~tr].tolist(), pi[~tr].tolist()):
                anchor = anchor1.get(u)
                if anchor is None:
                    continue
                qa.append((
                    {"items": [inv_items[anchor]], "num": 10},
                    {"items": [inv_items[i]]},
                ))
            folds.append((fold_td, qa))
        return folds


@dataclasses.dataclass
class PreparedData:
    user_ids: BiMap
    item_ids: BiMap
    user_idx: np.ndarray  # [n] int32
    item_idx: np.ndarray
    counts: np.ndarray  # [n] float32 — view counts per (user, item)
    item_categories: dict


class Preparator(BasePreparator):
    """BiMap ids and fold repeated views into per-pair counts (the implicit
    'rating' — «MLlib ALS.trainImplicit» treats values as confidence)."""

    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> PreparedData:
        # re-code densely over present entities. Items seen only via $set
        # get no factor rows — factors come from interactions;
        # category-only items can never score anyway.
        u, user_ids = compress_codes(td.user_idx, td.user_ids)
        i, item_ids = compress_codes(td.item_idx, td.item_ids)
        pair = u.astype(np.int64) * max(len(item_ids), 1) + i
        uniq, counts = np.unique(pair, return_counts=True)
        return PreparedData(
            user_ids=user_ids,
            item_ids=item_ids,
            user_idx=(uniq // max(len(item_ids), 1)).astype(np.int32),
            item_idx=(uniq % max(len(item_ids), 1)).astype(np.int32),
            counts=counts.astype(np.float32),
            item_categories=td.item_categories,
        )


@dataclasses.dataclass
class SimilarProductModel:
    """P2L model: L2-normalized item factors + id/category maps. Similarity
    scoring is one [Q,K]@[K,N] matmul over the normalized factors."""

    item_factors_unit: np.ndarray  # [n_items, K], rows L2-normalized
    item_ids: BiMap
    item_categories: dict

    def similar(
        self,
        query_items: list,
        num: int,
        categories: Optional[list] = None,
        white_list: Optional[list] = None,
        black_list: Optional[list] = None,
    ) -> list[tuple[str, float]]:
        known = [i for i in query_items if self.item_ids.contains(i)]
        if not known:
            return []
        q = self.item_factors_unit[self.item_ids.to_index(known)]  # [Q, K]
        scores = (q @ self.item_factors_unit.T).mean(axis=0)  # [n_items]

        mask = np.ones(scores.shape[0], dtype=bool)
        mask[self.item_ids.to_index(known)] = False  # basket itself
        if white_list:
            wl = np.zeros_like(mask)
            have = [i for i in white_list if self.item_ids.contains(i)]
            if have:
                wl[self.item_ids.to_index(have)] = True
            mask &= wl
        if black_list:
            have = [i for i in black_list if self.item_ids.contains(i)]
            if have:
                mask[self.item_ids.to_index(have)] = False
        if categories:
            cats = set(categories)
            idxs = np.nonzero(mask)[0]
            for idx, item in zip(idxs, self.item_ids.from_index(idxs)):
                if not cats & set(self.item_categories.get(item, [])):
                    mask[idx] = False

        scores = np.where(mask, scores, -np.inf)
        k = min(num, int(mask.sum()))
        if k <= 0:
            return []
        top = np.argpartition(-scores, k - 1)[:k]
        top = top[np.argsort(-scores[top])]
        items = self.item_ids.from_index(top)
        return [(item, float(scores[idx])) for item, idx in zip(items, top)]


@dataclasses.dataclass
class ALSAlgorithmParams(Params):
    rank: int = 10
    numIterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: Optional[int] = None

    _ALIASES = {"lambda": "lambda_"}


class ALSAlgorithm(Algorithm):
    """«ALSAlgorithm.train» (implicit) → cosine item-item model [U]."""

    params_class = ALSAlgorithmParams
    checkpoint_tags = ("als",)

    def __init__(self, params: ALSAlgorithmParams):
        self.params = params

    def _als_config(self, ctx: WorkflowContext) -> ALSConfig:
        p = self.params
        return ALSConfig(
            rank=p.rank,
            iterations=p.numIterations,
            reg=p.lambda_,
            implicit=True,
            alpha=p.alpha,
            seed=ctx.seed if p.seed is None else p.seed,
        )

    @staticmethod
    def _model_from_item_factors(f: np.ndarray,
                                 pd: PreparedData) -> SimilarProductModel:
        with span("model.unit_norm"):
            norms = np.linalg.norm(f, axis=1, keepdims=True)
            unit = np.where(norms > 0, f / np.maximum(norms, 1e-12), 0.0)
        return SimilarProductModel(
            item_factors_unit=unit.astype(np.float32),
            item_ids=pd.item_ids,
            item_categories=pd.item_categories,
        )

    def train(self, ctx: WorkflowContext, pd: PreparedData) -> SimilarProductModel:
        result = als_train(
            pd.user_idx, pd.item_idx, pd.counts,
            n_users=len(pd.user_ids), n_items=len(pd.item_ids),
            cfg=self._als_config(ctx), mesh=ctx.mesh,
            bucket_cache_dir=ctx.algorithm_cache_dir("als"),
            checkpoint_dir=ctx.algorithm_checkpoint_dir("als"),
            checkpoint_every=ctx.checkpoint_every_or(1),
        )
        emit_train_metrics(ctx.metrics, result)
        return self._model_from_item_factors(result.item_factors, pd)

    @classmethod
    def train_grid(cls, ctx: WorkflowContext, pd: PreparedData,
                   algos) -> Optional[list]:
        """Eval param grid as device programs (ops/als_grid — SURVEY.md
        §2.6 row 4, extended to the similarproduct family in round 5):
        cells varying in (λ, α, seed, iterations — mixed horizons batch)
        share the bucketized data; leftover singletons take the ordinary
        `train` path, mirroring the recommendation template's grid."""
        from predictionio_tpu.ops.als_grid import grid_dispatch

        return grid_dispatch(
            ctx, [a._als_config(ctx) for a in algos],
            pd.user_idx, pd.item_idx, pd.counts,
            n_users=len(pd.user_ids), n_items=len(pd.item_ids),
            train_one=lambda i: algos[i].train(ctx, pd),
            build_model=lambda i, r: cls._model_from_item_factors(
                np.asarray(r.item_factors), pd),
            log_prefix="SimilarProduct train_grid",
            cache_dir=ctx.algorithm_cache_dir("als"),
        )

    def predict(self, model: SimilarProductModel, query: Query) -> PredictedResult:
        sims = model.similar(
            [str(i) for i in query.get("items", [])],
            num=int(query.get("num", 10)),
            categories=query.get("categories"),
            white_list=query.get("whiteList"),
            black_list=query.get("blackList"),
        )
        return {"itemScores": [{"item": i, "score": s} for i, s in sims]}

    def batch_predict(self, model: SimilarProductModel,
                      queries) -> list[PredictedResult]:
        """Batched path for the serving micro-batcher: filterless
        same-`num` queries share one vectorized mask/top-k pass over a
        stacked [B, n_items] score matrix; anything with category/white/
        black filters (or an empty basket) falls back to per-query
        `predict`. Score rows are computed with the exact expression
        `similar()` uses, and argpartition/argsort along axis=1 match
        their 1-D forms row for row, so batched results are bitwise
        identical to sequential ones."""
        unit = model.item_factors_unit
        n_items = unit.shape[0]
        out: list[PredictedResult] = [None] * len(queries)  # type: ignore
        groups: dict[int, list[tuple[int, np.ndarray]]] = {}
        for pos, q in enumerate(queries):
            known = [str(i) for i in (q.get("items") or [])
                     if model.item_ids.contains(str(i))]
            num = int(q.get("num", 10))
            if (not known or num <= 0 or q.get("categories")
                    or q.get("whiteList") or q.get("blackList")):
                out[pos] = self.predict(model, q)
                continue
            groups.setdefault(num, []).append(
                (pos, model.item_ids.to_index(known)))
        for num, entries in groups.items():
            scores = np.empty((len(entries), n_items), dtype=unit.dtype)
            mask = np.ones((len(entries), n_items), dtype=bool)
            for r, (_, ki) in enumerate(entries):
                scores[r] = (unit[ki] @ unit.T).mean(axis=0)
                mask[r, ki] = False
            # rows whose post-mask candidate count undercuts num need a
            # per-row k — rare (giant basket vs tiny catalog); punt them
            # to predict so the vectorized rows keep one uniform k
            avail = mask.sum(axis=1)
            k = min(num, n_items)
            live = []
            for r, (pos, _) in enumerate(entries):
                if avail[r] < k:
                    out[pos] = self.predict(model, queries[pos])
                else:
                    live.append(r)
            if not live:
                continue
            s = np.where(mask[live], scores[live], -np.inf)
            idx = np.argpartition(-s, k - 1, axis=1)[:, :k]
            part = np.take_along_axis(s, idx, axis=1)
            order = np.argsort(-part, axis=1)
            top = np.take_along_axis(idx, order, axis=1)
            top_scores = np.take_along_axis(part, order, axis=1)
            names = model.item_ids.from_index(top.ravel())
            for j, r in enumerate(live):
                pos = entries[r][0]
                base = j * k
                out[pos] = {"itemScores": [
                    {"item": names[base + c], "score": float(top_scores[j, c])}
                    for c in range(k)]}
        return out


class SimilarProductEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class_map=DataSource,
            preparator_class_map=Preparator,
            algorithm_class_map={"als": ALSAlgorithm},
            serving_class_map=FirstServing,
        )

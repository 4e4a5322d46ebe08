"""Recommendation engine template (DASE components).

Mirrors the reference template's `src/main/scala/{DataSource,Preparator,
Algorithm,Serving}.scala` shapes (SURVEY.md §2.4 [U]) with the ALS compute
replaced by `predictionio_tpu.ops.als` (mesh-sharded XLA) instead of Spark
MLlib.

Wire shapes (kept reference-compatible):
    query:  {"user": "1", "num": 4}
    result: {"itemScores": [{"item": "i5", "score": 3.2}, ...]}
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Optional

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
    Serving,
    WorkflowContext,
)
from predictionio_tpu.data.bimap import BiMap, compress_codes
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.models.als_model import ALSModel, SeenItems
from predictionio_tpu.ops.als import ALSConfig, als_train, emit_train_metrics
from predictionio_tpu.telemetry.spans import span

log = logging.getLogger(__name__)

Query = dict  # {"user": str, "num": int}
PredictedResult = dict  # {"itemScores": [{"item": str, "score": float}]}


@dataclasses.dataclass
class DataSourceParams(Params):
    appName: str = ""
    eventNames: list = dataclasses.field(default_factory=lambda: ["rate", "buy"])
    buyRating: float = 4.0  # implicit rating assigned to "buy" (quickstart rule)
    evalK: int = 0  # >0 enables read_eval with k folds


@dataclasses.dataclass
class TrainingData(SanityCheck):
    """Columnar rating events: integer-coded COO + the BiMaps decoding the
    codes (no per-event Python objects — the store scan stays columnar all
    the way to the device; VERDICT r1 #4)."""

    user_idx: np.ndarray  # [n] int32 codes into user_ids
    item_idx: np.ndarray  # [n] int32 codes into item_ids
    ratings: np.ndarray  # [n] float32, aligned
    user_ids: BiMap  # user id string → code
    item_ids: BiMap  # item id string → code

    @property
    def users(self) -> list:
        """Decoded user id strings (debug/compat view; O(n) Python)."""
        return self.user_ids.from_index(self.user_idx)

    @property
    def items(self) -> list:
        return self.item_ids.from_index(self.item_idx)

    def sanity_check(self):
        if len(self.ratings) == 0:
            raise ValueError("TrainingData has no rating events; ingest events first.")


class DataSource(BaseDataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        self.params = params

    def _read_events(self, ctx) -> TrainingData:
        """Columnar scan («PEventStore.find → RDD[Event]» role [U]): the
        backend codes ids and extracts the rating in SQL/C++; the rate-vs-
        implicit rule is three vectorized ops. ordered=True is load-
        bearing: the Preparator's re-rating dedup keeps the LAST
        occurrence in scan order, which must mean latest event time."""
        store = PEventStore(ctx.storage)
        cols = store.find_columnar(
            app_name=self.params.appName,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.eventNames),
            value_key="rating",
        )
        try:
            rate_code = cols.event_names.index("rate")
        except ValueError:
            rate_code = -1
        values = np.where(cols.event_codes == rate_code, cols.values,
                          np.float32(self.params.buyRating))
        valid = (cols.target_ids >= 0) & ~np.isnan(values)
        return TrainingData(
            user_idx=cols.entity_ids[valid],
            item_idx=cols.target_ids[valid],
            ratings=values[valid].astype(np.float32),
            user_ids=cols.entity_bimap,
            item_ids=cols.target_bimap,
        )

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        td = self._read_events(ctx)
        log.info("DataSource: %d rating events from app %r",
                 len(td.ratings), self.params.appName)
        return td

    def read_eval(self, ctx: WorkflowContext):
        """k-fold split by event index («DataSource.readEval» [U]): fold i
        tests on every k-th event, trains on the rest. Queries ask top-10
        for each test user; actual = that user's held-out items."""
        k = self.params.evalK
        if k <= 1:
            raise ValueError("DataSourceParams.evalK must be >= 2 for evaluation")
        td = self._read_events(ctx)
        n = len(td.ratings)
        assign = np.arange(n) % k
        folds = []
        for fold in range(k):
            train_sel = assign != fold
            test_sel = ~train_sel
            fold_td = TrainingData(
                user_idx=td.user_idx[train_sel],
                item_idx=td.item_idx[train_sel],
                ratings=td.ratings[train_sel],
                user_ids=td.user_ids,
                item_ids=td.item_ids,
            )
            # decode only the held-out fold (n/k events) for the actuals
            test_users = td.user_ids.from_index(td.user_idx[test_sel])
            test_items = td.item_ids.from_index(td.item_idx[test_sel])
            actual_by_user: dict[str, set] = {}
            for u, i in zip(test_users, test_items):
                actual_by_user.setdefault(u, set()).add(i)
            qa = [
                ({"user": u, "num": 10}, {"items": sorted(items)})
                for u, items in sorted(actual_by_user.items())
            ]
            folds.append((fold_td, qa))
        return folds


@dataclasses.dataclass
class PreparedData:
    user_ids: BiMap
    item_ids: BiMap
    user_idx: np.ndarray  # [n] int32
    item_idx: np.ndarray
    ratings: np.ndarray  # [n] float32


class Preparator(BasePreparator):
    """BiMap the string ids to dense rows («BiMap.stringLong» before MLlib,
    SURVEY.md §2.2 [U]) and emit device-ready COO arrays. Duplicate
    (user, item) pairs keep the last value (re-rating overwrites)."""

    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> PreparedData:
        # a fold subset, or rows dropped by the rate-without-rating
        # filter, may leave code gaps — re-code densely
        u, user_ids = compress_codes(td.user_idx, td.user_ids)
        i, item_ids = compress_codes(td.item_idx, td.item_ids)
        # dedup keeping last occurrence
        pair = u.astype(np.int64) * max(len(item_ids), 1) + i
        _, last_pos = np.unique(pair[::-1], return_index=True)
        keep = len(pair) - 1 - last_pos
        keep.sort()
        return PreparedData(
            user_ids=user_ids,
            item_ids=item_ids,
            user_idx=u[keep],
            item_idx=i[keep],
            ratings=td.ratings[keep],
        )


@dataclasses.dataclass
class ALSAlgorithmParams(Params):
    rank: int = 10
    numIterations: int = 10
    lambda_: float = 0.01  # engine.json key "lambda" (see _ALIASES)
    implicitPrefs: bool = False
    alpha: float = 1.0
    seed: Optional[int] = None
    computeRMSE: bool = False
    # hot rows with more ratings than this train as summed segments
    # (ops/als.py bucket_ragged_split); 0 disables
    splitCap: int = 32768

    _ALIASES = {"lambda": "lambda_"}


class ALSAlgorithm(Algorithm):
    """«ALSAlgorithm.train» → mesh-sharded ALS; model keeps factors +
    bimaps + seen items for serve-time exclusion."""

    params_class = ALSAlgorithmParams
    checkpoint_tags = ("als",)

    def __init__(self, params: ALSAlgorithmParams):
        self.params = params

    def train(self, ctx: WorkflowContext, pd: PreparedData) -> ALSModel:
        p = self.params
        cfg = self._als_config(ctx)
        result = als_train(
            pd.user_idx, pd.item_idx, pd.ratings,
            n_users=len(pd.user_ids), n_items=len(pd.item_ids),
            cfg=cfg, mesh=ctx.mesh, compute_rmse=p.computeRMSE,
            checkpoint_dir=ctx.algorithm_checkpoint_dir("als"),
            checkpoint_every=ctx.checkpoint_every_or(1),
            bucket_cache_dir=ctx.algorithm_cache_dir("als"),
        )
        emit_train_metrics(ctx.metrics, result)
        with span("model.seen_items"):
            seen = SeenItems(pd.user_idx, pd.item_idx, len(pd.user_ids))
        return ALSModel(
            user_factors=result.user_factors,
            item_factors=result.item_factors,
            user_ids=pd.user_ids,
            item_ids=pd.item_ids,
            seen=seen,
            rmse_history=result.rmse_history,
        )

    def _als_config(self, ctx: WorkflowContext) -> ALSConfig:
        p = self.params
        return ALSConfig(
            rank=p.rank,
            iterations=p.numIterations,
            reg=p.lambda_,
            implicit=p.implicitPrefs,
            alpha=p.alpha,
            seed=ctx.seed if p.seed is None else p.seed,
            split_cap=p.splitCap,
        )

    @classmethod
    def train_grid(cls, ctx: WorkflowContext, pd: PreparedData,
                   algos) -> Optional[list[ALSModel]]:
        """Eval param grid as device programs (ops/als_grid): cells
        varying only in (λ, α, seed) share the bucketized data — and the
        bucket cache entry the production train already wrote — so an
        N-point grid costs ~one train's wall instead of N
        («EvaluationWorkflow» grid loop [U], SURVEY.md §2.6 row 4).
        Mixed grids partition into maximal batchable groups (the stock
        rank×λ grid = one program per rank); leftover singletons take the
        ordinary `train` path."""
        from predictionio_tpu.ops.als_grid import grid_dispatch

        cfgs = [a._als_config(ctx) for a in algos]
        # lazily built: when every guard falls back to sequential trains,
        # the O(n_events) SeenItems pass must not run here at all
        seen_box: list[SeenItems] = []

        def build_model(i, r):
            if not seen_box:
                seen_box.append(
                    SeenItems(pd.user_idx, pd.item_idx, len(pd.user_ids)))
            seen = seen_box[0]
            return ALSModel(
                user_factors=r.user_factors,
                item_factors=r.item_factors,
                user_ids=pd.user_ids,
                item_ids=pd.item_ids,
                seen=seen,
                # the group trains RMSE when ANY cell wants it; a
                # computeRMSE=False cell must still come out empty,
                # exactly as its sequential train would
                rmse_history=(r.rmse_history
                              if algos[i].params.computeRMSE else []),
            )

        # host_factors=False: eval models stay device-resident — the
        # batch_predict top-k runs on device anyway, and the G-wide
        # factor readback was the grid A/B's largest overhead. These
        # models are eval-scoped (never pickled/persisted).
        return grid_dispatch(
            ctx, cfgs, pd.user_idx, pd.item_idx, pd.ratings,
            n_users=len(pd.user_ids), n_items=len(pd.item_ids),
            train_one=lambda i: algos[i].train(ctx, pd),
            build_model=build_model,
            log_prefix="ALSAlgorithm.train_grid",
            rmse_flags=[a.params.computeRMSE for a in algos],
            host_factors=False,
            cache_dir=ctx.algorithm_cache_dir("als"),
        )

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        num = int(query.get("num", 10))
        recs = model.recommend_products(str(query["user"]), num)
        return {"itemScores": [{"item": i, "score": s} for i, s in recs]}

    def batch_predict(self, model: ALSModel, queries) -> list[PredictedResult]:
        """Bulk scoring («pio batchpredict» / evaluation): one vectorized
        top-k over every query's user instead of the base class's
        per-query predict loop — large batches ride the accelerator
        branch of ops/ranking.py (VERDICT r2 #4)."""
        by_num: dict[int, list[int]] = {}
        for pos, q in enumerate(queries):
            by_num.setdefault(int(q.get("num", 10)), []).append(pos)
        out: list[PredictedResult] = [None] * len(queries)  # type: ignore
        for num, idxs in by_num.items():
            # group by num so one outlier query can't force every other
            # query onto its (larger) top-k
            recs = model.recommend_products_batch(
                [queries[i]["user"] for i in idxs], num)
            for i, r in zip(idxs, recs):
                out[i] = {"itemScores": [{"item": item, "score": s}
                                         for item, s in r]}
        return out


@dataclasses.dataclass
class PopularityParams(Params):
    weightByRating: bool = False  # sum rating mass instead of counting


@dataclasses.dataclass
class PopularityModel:
    """Global item-popularity ranks with per-user seen-item exclusion —
    the co-occurrence-free baseline recommender."""

    user_ids: BiMap
    item_ids: BiMap
    counts: np.ndarray  # [n_items] float32 popularity mass
    order: np.ndarray  # [n_items] int32, counts descending (precomputed)
    seen: SeenItems

    def recommend(self, user: str, num: int) -> list[tuple[str, float]]:
        if num <= 0:
            return []
        seen_rows: frozenset = frozenset()
        row = self.user_ids.get(str(user))
        if row is not None:
            s = self.seen.get(int(row))
            if s is not None:
                seen_rows = frozenset(int(x) for x in s)
        inv = self.item_ids.inverse()
        out: list[tuple[str, float]] = []
        for i in self.order:
            i = int(i)
            if i in seen_rows:
                continue
            out.append((inv[i], float(self.counts[i])))
            if len(out) >= num:
                break
        return out


class PopularityAlgorithm(Algorithm):
    """Item-popularity baseline — the second algorithm that makes the
    shipped multi-algorithm engine real (VERDICT r4 missing #2; the
    reference's quickstart-documented "multiple algorithms per engine"
    capability, «Engine.algorithmClassMap» [U]). Deliberately simple and
    *different in kind* from ALS: non-personalized global ranks that the
    Serving layer blends with the personalized factors, the classic
    cold-start backstop. Counting is one scatter-add on the training COO
    (no per-event Python)."""

    params_class = PopularityParams
    # no per-user device work and O(num) serve cost: this is the serving
    # plane's degraded-mode answer when admission sheds under saturation
    degraded_capable = True

    def __init__(self, params: PopularityParams):
        self.params = params

    def train(self, ctx: WorkflowContext, pd: PreparedData) -> PopularityModel:
        n_items = len(pd.item_ids)
        weights = (pd.ratings.astype(np.float32)
                   if self.params.weightByRating
                   else np.ones(len(pd.item_idx), dtype=np.float32))
        counts = np.zeros(n_items, dtype=np.float32)
        np.add.at(counts, pd.item_idx, weights)
        order = np.argsort(-counts, kind="stable").astype(np.int32)
        return PopularityModel(
            user_ids=pd.user_ids,
            item_ids=pd.item_ids,
            counts=counts,
            order=order,
            seen=SeenItems(pd.user_idx, pd.item_idx, len(pd.user_ids)),
        )

    def predict(self, model: PopularityModel, query: Query) -> PredictedResult:
        num = int(query.get("num", 10))
        return {"itemScores": [{"item": i, "score": s}
                               for i, s in model.recommend(
                                   str(query["user"]), num)]}


@dataclasses.dataclass
class WeightedServingParams(Params):
    weights: list = dataclasses.field(default_factory=list)  # per-algo; [] = equal


class WeightedServing(Serving):
    """«LAverageServing» [U] for itemScores: blend every algorithm's
    ranked list into one. Each prediction's scores are min-max
    normalized to [0, 1] first (ALS dot products and popularity counts
    live on incomparable scales), then weighted-summed per item and
    re-ranked. An algorithm that returned nothing for the query (e.g.
    ALS on an unknown user) simply contributes nothing — which is
    exactly why a popularity baseline belongs in the blend."""

    params_class = WeightedServingParams

    def __init__(self, params: WeightedServingParams):
        self.params = params

    def check_against_algorithms(self, algo_names: list) -> None:
        """Engine.components calls this at train/deploy/eval entry so a
        weights/algorithms count mismatch fails the config up front, not
        as a 500 on every query."""
        if self.params.weights and len(self.params.weights) != len(algo_names):
            raise ValueError(
                f"WeightedServing: {len(self.params.weights)} weights "
                f"configured for {len(algo_names)} algorithms "
                f"({algo_names}); fix serving.params.weights in "
                "engine.json")

    def serve(self, query, predictions):
        if not predictions:
            raise ValueError("No predictions to serve.")
        num = int(query.get("num", 10))
        weights = list(self.params.weights) or [1.0] * len(predictions)
        if len(weights) != len(predictions):
            raise ValueError(
                f"WeightedServing: {len(weights)} weights for "
                f"{len(predictions)} algorithm predictions")
        blended: dict[str, float] = {}
        for w, pred in zip(weights, predictions):
            scores = pred.get("itemScores") or []
            if not scores:
                continue
            vals = [float(s["score"]) for s in scores]
            lo, hi = min(vals), max(vals)
            span = hi - lo
            for s, v in zip(scores, vals):
                norm = (v - lo) / span if span > 0 else 1.0
                blended[s["item"]] = blended.get(s["item"], 0.0) + w * norm
        ranked = sorted(blended.items(), key=lambda kv: (-kv[1], kv[0]))
        return {"itemScores": [{"item": i, "score": s}
                               for i, s in ranked[:num]]}


class RecommendationEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class_map=DataSource,
            preparator_class_map=Preparator,
            algorithm_class_map={"als": ALSAlgorithm,
                                 "popular": PopularityAlgorithm},
            serving_class_map={
                # "" keeps unnamed engine.json serving blocks (and every
                # previously stored EngineInstance row) on FirstServing
                "": FirstServing,
                "first": FirstServing,
                "weighted": WeightedServing,
            },
        )

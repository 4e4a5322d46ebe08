"""Grid-batched eval (ops/als_grid + Engine.eval_grid): N hyperparameter
points as one device program, numerically matching sequential trains —
SURVEY.md §2.6 strategy 4's TPU-native form (VERDICT r3 #1)."""

import dataclasses

import numpy as np
import pytest

from predictionio_tpu.ops import pallas_solve
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.ops.als_grid import als_train_grid, grid_compatible


def coo(n=20000, n_u=300, n_i=200, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_u, n).astype(np.int32),
            rng.integers(0, n_i, n).astype(np.int32),
            rng.uniform(1, 5, n).astype(np.float32), n_u, n_i)


def rel_err(a, b):
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-9)


class TestGridCompatible:
    BASE = ALSConfig(rank=8, iterations=3, reg=0.1)

    def test_variable_fields_ok(self):
        cfgs = [dataclasses.replace(self.BASE, reg=r, alpha=a, seed=s)
                for r, a, s in ((0.01, 1.0, 0), (0.1, 2.0, 1))]
        assert grid_compatible(cfgs) is None

    def test_mixed_iterations_ok(self):
        """r5: an iterations sweep — the cheapest and most-used grid
        axis — batches via the traced per-cell horizon instead of
        degrading to sequential trains."""
        cfgs = [dataclasses.replace(self.BASE, iterations=n)
                for n in (2, 5, 3)]
        assert grid_compatible(cfgs) is None

    @pytest.mark.parametrize("field,value", [
        ("rank", 16), ("implicit", True), ("split_cap", 64),
        ("cap_growth", 2.0), ("compute_dtype", "bfloat16"),
        ("weighted_reg", False),
    ])
    def test_static_mismatch_rejected(self, field, value):
        cfgs = [self.BASE, dataclasses.replace(self.BASE, **{field: value})]
        reason = grid_compatible(cfgs)
        assert reason is not None and field in reason

    def test_empty_rejected(self):
        assert grid_compatible([]) is not None

    def test_grid_groups_partitions_mixed_grid(self):
        from predictionio_tpu.ops.als_grid import grid_groups

        cfgs = [dataclasses.replace(self.BASE, rank=r, reg=lam)
                for r in (8, 16) for lam in (0.01, 0.1)]
        cfgs.append(dataclasses.replace(self.BASE, solver="chol"))
        groups = grid_groups(cfgs)
        assert sorted(map(sorted, groups)) == [[0, 1], [2, 3], [4]]


class TestGridMatchesSequential:
    def test_explicit_with_hot_row_segments(self):
        """λ grid over data with rows past split_cap: the segment
        scatter-add/combine path must match sequential too."""
        u, i, v, n_u, n_i = coo()
        base = ALSConfig(rank=16, iterations=3, seed=7, split_cap=64)
        cfgs = [dataclasses.replace(base, reg=r) for r in (0.01, 0.1, 1.0)]
        grid = als_train_grid(u, i, v, n_u, n_i, cfgs, compute_rmse=True)
        assert len(grid) == 3
        for cfg, gr in zip(cfgs, grid):
            seq = als_train(u, i, v, n_u, n_i, cfg, compute_rmse=True)
            assert rel_err(gr.user_factors, seq.user_factors) < 1e-4
            assert rel_err(gr.item_factors, seq.item_factors) < 1e-4
            assert gr.rmse_history == pytest.approx(seq.rmse_history,
                                                    rel=1e-4)
        # different λ must actually produce different factors (the grid
        # axis isn't broadcasting one solution)
        assert rel_err(grid[0].user_factors, grid[2].user_factors) > 1e-3

    def test_implicit_alpha_and_seed_grid(self):
        u, i, v, n_u, n_i = coo(n=8000, n_u=150, n_i=100, seed=1)
        base = ALSConfig(rank=12, iterations=3, implicit=True, reg=0.05,
                         split_cap=0)
        cfgs = [dataclasses.replace(base, alpha=a, seed=s)
                for a, s in ((1.0, 0), (10.0, 1), (40.0, 2))]
        grid = als_train_grid(u, i, v, n_u, n_i, cfgs)
        for cfg, gr in zip(cfgs, grid):
            seq = als_train(u, i, v, n_u, n_i, cfg)
            assert rel_err(gr.user_factors, seq.user_factors) < 1e-4

    def test_mixed_iterations_match_sequential_per_cell(self):
        """r4-weak-#3 closed: cells with DIFFERENT iteration counts in
        one grid program — each must equal its own sequential train
        (the traced horizon freezes a finished cell's factors), and the
        rmse history must be each cell's own length."""
        u, i, v, n_u, n_i = coo()
        base = ALSConfig(rank=8, iterations=0, seed=5, split_cap=64)
        cfgs = [dataclasses.replace(base, iterations=n, reg=r)
                for n, r in ((2, 0.1), (5, 0.1), (3, 0.02))]
        grid = als_train_grid(u, i, v, n_u, n_i, cfgs, compute_rmse=True)
        for cfg, gr in zip(cfgs, grid):
            seq = als_train(u, i, v, n_u, n_i, cfg, compute_rmse=True)
            assert rel_err(gr.user_factors, seq.user_factors) < 1e-4
            assert rel_err(gr.item_factors, seq.item_factors) < 1e-4
            assert len(gr.rmse_history) == cfg.iterations
            assert gr.rmse_history == pytest.approx(seq.rmse_history,
                                                    rel=1e-4)
            assert len(gr.epoch_times) == cfg.iterations
        # the 2-iter and 5-iter cells share λ: the horizon must make
        # them genuinely different, not clones of the longest run
        assert rel_err(grid[0].user_factors, grid[1].user_factors) > 1e-3

    def test_incompatible_grid_raises(self):
        u, i, v, n_u, n_i = coo(n=500, n_u=30, n_i=20)
        cfgs = [ALSConfig(rank=8), ALSConfig(rank=16)]
        with pytest.raises(ValueError, match="rank"):
            als_train_grid(u, i, v, n_u, n_i, cfgs)

    def test_sharded_data_mesh_matches_single_device(self):
        """The grid under the 8-device SPMD mesh (bucket rows sharded over
        `data`) matches the single-device result."""
        import jax
        from jax.sharding import Mesh

        from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        u, i, v, n_u, n_i = coo(n=6000, n_u=120, n_i=80, seed=2)
        base = ALSConfig(rank=8, iterations=2, seed=3, split_cap=0)
        cfgs = [dataclasses.replace(base, reg=r) for r in (0.05, 0.5)]
        devs = np.array(jax.devices()).reshape(-1, 1)
        mesh = Mesh(devs, (DATA_AXIS, MODEL_AXIS))
        single = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                      (DATA_AXIS, MODEL_AXIS))
        grid_m = als_train_grid(u, i, v, n_u, n_i, cfgs, mesh=mesh)
        grid_1 = als_train_grid(u, i, v, n_u, n_i, cfgs, mesh=single)
        for gm, g1 in zip(grid_m, grid_1):
            assert rel_err(gm.user_factors, g1.user_factors) < 1e-4

    @pytest.mark.parametrize("n_devices", [1, 8])
    def test_gj_grid_matches_chol_grid(self, n_devices):
        """The grid's kernel branch (interpret mode): the (row, grid)
        batch flattened into `solve_spd`, on one device and under
        `shard_map` on a data mesh, where the split accumulators' batch
        goes to Cholesky; against the same grid solved by Cholesky."""
        import jax
        from jax.sharding import Mesh

        from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        u, i, v, n_u, n_i = coo(n=6000, n_u=120, n_i=80, seed=2)
        base = ALSConfig(rank=8, iterations=2, seed=3, split_cap=32)
        cfgs = [dataclasses.replace(base, reg=r) for r in (0.05, 0.5)]
        mesh = Mesh(np.array(jax.devices()[:n_devices]).reshape(-1, 1),
                    (DATA_AXIS, MODEL_AXIS))
        chol = als_train_grid(
            u, i, v, n_u, n_i,
            [dataclasses.replace(c, solver="chol") for c in cfgs], mesh=mesh)
        built = pallas_solve.SOLVE_CALLS.labels(layout="lanes")
        before = built.value
        gj = als_train_grid(
            u, i, v, n_u, n_i,
            [dataclasses.replace(c, solver="gj", pallas="interpret")
             for c in cfgs], mesh=mesh)
        assert built.value > before  # the kernel, not a fallback
        for g, c in zip(gj, chol):
            assert rel_err(g.user_factors, c.user_factors) < 5e-4
            assert rel_err(g.item_factors, c.item_factors) < 5e-4

    def test_model_sharded_mesh_rejected(self):
        import jax
        from jax.sharding import Mesh

        from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        u, i, v, n_u, n_i = coo(n=500, n_u=30, n_i=20)
        devs = np.array(jax.devices()).reshape(-1, 2)
        mesh = Mesh(devs, (DATA_AXIS, MODEL_AXIS))
        with pytest.raises(ValueError, match="model"):
            als_train_grid(u, i, v, n_u, n_i, [ALSConfig(rank=8)] * 2,
                           mesh=mesh)


class TestEvalGridIntegration:
    """MetricEvaluator → Engine.eval_grid → ALSAlgorithm.train_grid."""

    def _setup(self, memory_storage, lambdas=(0.01, 0.05, 0.5)):
        from predictionio_tpu.controller import WorkflowContext
        from predictionio_tpu.workflow.workflow_utils import (
            EngineVariant, extract_engine_params, get_engine,
        )
        from tests.test_recommendation_template import (
            FACTORY, ingest_ratings,
        )

        ingest_ratings(memory_storage, n_users=16, n_items=10)
        engine = get_engine(FACTORY)
        eps = []
        for lam in lambdas:
            variant = EngineVariant.from_dict({
                "id": "rec-eval-grid",
                "engineFactory": FACTORY,
                "datasource": {"params": {"appName": "RecApp", "evalK": 3}},
                "algorithms": [{"name": "als", "params": {
                    "rank": 4, "numIterations": 6, "lambda": lam,
                    "seed": 1}}],
            })
            eps.append(extract_engine_params(engine, variant))
        ctx = WorkflowContext(storage=memory_storage, seed=0)
        return engine, eps, ctx

    def _evaluation(self, engine):
        from predictionio_tpu.controller import OptionAverageMetric
        from predictionio_tpu.controller.evaluation import Evaluation
        from predictionio_tpu.ops.ranking import average_precision_at_k

        class MAPat10(OptionAverageMetric):
            def calculate(self, q, p, a):
                predicted = np.asarray(
                    [s["item"] for s in p["itemScores"]], dtype=object)
                return average_precision_at_k(predicted, set(a["items"]), 10)

        class RecEval(Evaluation):
            pass

        RecEval.engine = engine
        RecEval.metric = MAPat10()
        return RecEval()

    def test_grid_scores_match_sequential(self, memory_storage, monkeypatch):
        """The whole point: MetricEvaluator over a λ grid produces the
        same per-point scores whether the grid path or the sequential
        reference loop runs."""
        from predictionio_tpu.controller.engine import Engine
        from predictionio_tpu.controller.evaluation import MetricEvaluator
        from predictionio_tpu.ops import als_grid

        engine, eps, ctx = self._setup(memory_storage)

        calls = {"grid": 0}
        real = als_grid.als_train_grid

        def spy(*a, **k):
            calls["grid"] += 1
            return real(*a, **k)

        monkeypatch.setattr(als_grid, "als_train_grid", spy)
        grid_result = MetricEvaluator.evaluate(ctx, self._evaluation(engine),
                                               eps)
        assert calls["grid"] == 3  # once per fold, not per (fold × cell)

        monkeypatch.setattr(Engine, "eval_grid",
                            lambda self, ctx, eps: None)
        seq_result = MetricEvaluator.evaluate(ctx, self._evaluation(engine),
                                              eps)
        for g, s in zip(grid_result.all_results, seq_result.all_results):
            assert g.scores["MAPat10"] == pytest.approx(
                s.scores["MAPat10"], rel=1e-4, abs=1e-6)
        assert (grid_result.all_results.index(grid_result.best)
                == seq_result.all_results.index(seq_result.best))

    def test_unbatchable_grid_still_shares_folds(self, memory_storage,
                                                 monkeypatch):
        """Grid cells with differing rank: train_grid declines, eval_grid
        still evaluates them (sequential trains, shared fold read)."""
        from predictionio_tpu.controller.evaluation import MetricEvaluator
        from predictionio_tpu.ops import als_grid

        engine, eps, ctx = self._setup(memory_storage, lambdas=(0.01, 0.05))
        eps[1].algorithm_params_list[0][1].rank = 6  # break batchability

        monkeypatch.setattr(
            als_grid, "als_train_grid",
            lambda *a, **k: pytest.fail("train_grid must decline"))
        result = MetricEvaluator.evaluate(ctx, self._evaluation(engine), eps)
        assert len(result.all_results) == 2
        for r in result.all_results:
            assert 0.0 <= r.scores["MAPat10"] <= 1.0

    def test_mixed_rank_lambda_grid_batches_per_rank(self, memory_storage,
                                                     monkeypatch):
        """The stock template shape (rank×λ grid): one grid program per
        rank group, not one per cell."""
        from predictionio_tpu.controller.evaluation import MetricEvaluator
        from predictionio_tpu.ops import als_grid

        engine, eps, ctx = self._setup(memory_storage,
                                       lambdas=(0.01, 0.05, 0.01, 0.05))
        for ep in eps[2:]:
            ep.algorithm_params_list[0][1].rank = 6

        grid_sizes = []
        real = als_grid.als_train_grid

        def spy(*a, **k):
            grid_sizes.append(len(k.get("cfgs") or a[5]))
            return real(*a, **k)

        monkeypatch.setattr(als_grid, "als_train_grid", spy)
        result = MetricEvaluator.evaluate(ctx, self._evaluation(engine), eps)
        # 3 folds × 2 rank groups, each batching its 2 λ cells
        assert grid_sizes == [2] * 6
        assert len(result.all_results) == 4

    def test_check_asserts_declines_grid(self, memory_storage, monkeypatch):
        """--check-asserts must run the checked sequential trains, not the
        (checkify-less) grid program."""
        from predictionio_tpu.ops import als_grid
        from predictionio_tpu.utils import checks

        engine, eps, ctx = self._setup(memory_storage, lambdas=(0.01, 0.05))
        monkeypatch.setattr(checks, "enabled", lambda: True)
        monkeypatch.setattr(
            als_grid, "als_train_grid",
            lambda *a, **k: pytest.fail("grid must decline under checks"))
        from predictionio_tpu.templates.recommendation.engine import (
            ALSAlgorithm,
        )

        _, prep, algos, _ = engine.components(eps[0])
        instances = [engine.components(ep)[2][0][1] for ep in eps]
        td = engine.components(eps[0])[0].read_training(ctx)
        pd = prep.prepare(ctx, td)
        assert ALSAlgorithm.train_grid(ctx, pd, instances) is None

    def test_device_model_similar_products_and_single_query(self):
        """Device-resident grid-eval models must survive every ALSModel
        read path: batch, single-query, and the in-place-mutating
        similar_products."""
        import jax.numpy as jnp

        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models.als_model import ALSModel, SeenItems

        rng = np.random.default_rng(0)
        uf = rng.normal(size=(6, 4)).astype(np.float32)
        vf = rng.normal(size=(5, 4)).astype(np.float32)
        host = ALSModel(
            user_factors=uf, item_factors=vf,
            user_ids=BiMap.string_int([f"u{i}" for i in range(6)]),
            item_ids=BiMap.string_int([f"i{i}" for i in range(5)]),
            seen=SeenItems(np.zeros(1, np.int32), np.zeros(1, np.int32), 6),
        )
        dev = ALSModel(
            user_factors=jnp.asarray(uf), item_factors=jnp.asarray(vf),
            user_ids=host.user_ids, item_ids=host.item_ids, seen=host.seen,
        )
        assert dev.similar_products(["i1"], 3) == pytest.approx(
            host.similar_products(["i1"], 3))
        for h, d in zip(host.recommend_products("u2", 3),
                        dev.recommend_products("u2", 3)):
            assert h[0] == d[0] and h[1] == pytest.approx(d[1], rel=1e-5)
        hb = host.recommend_products_batch([f"u{i}" for i in range(6)], 3)
        db = dev.recommend_products_batch([f"u{i}" for i in range(6)], 3)
        for hrow, drow in zip(hb, db):
            assert [i for i, _ in hrow] == [i for i, _ in drow]

    def test_heterogeneous_datasource_falls_back(self, memory_storage):
        """eval_grid returns None when the grid varies the data source
        params; the sequential path must still produce results."""
        from predictionio_tpu.controller.evaluation import MetricEvaluator

        engine, eps, ctx = self._setup(memory_storage, lambdas=(0.01, 0.05))
        eps[1].data_source_params.evalK = 2
        assert engine.eval_grid(ctx, eps) is None
        result = MetricEvaluator.evaluate(ctx, self._evaluation(engine), eps)
        assert len(result.all_results) == 2


CASES_OF_ONE = pytest.mark.parametrize("split_cap,budget", [
    (64, None), (0, 1 << 14), (64, 1 << 14),
], ids=["split_rows", "chunked_walk", "split_rows_chunked_walk"])
BOTH_MODES = pytest.mark.parametrize("implicit", [False, True],
                                     ids=["explicit", "implicit"])


class TestGridOfOneIsTheTrain:
    """A grid runs the train's half-iteration (`als._solve_buckets_device`
    tells a grid by its [V, G, K] tables), so a grid of one point is the
    train of that point. Bit for bit where the backend's sums do not
    depend on their order: XLA's CPU dot sums `rcgk,rcgl->rgkl` at g = 1
    in another order than `rck,rcl->rkl` (the last bits of A and b; the
    solve of equal systems is equal), so the exact cases hand the
    half-iteration factors, ratings, α and λ whose sums are exact in
    float32 whatever their order, and the whole trains are held to what
    reassociation leaves."""

    @staticmethod
    def _data():
        u, i, v, n_u, n_i = coo(n=6000, n_u=120, n_i=80, seed=3)
        u[:300] = 5  # a hot row: split into segments under split_cap 64
        return u, i, np.round(v * 2) / 2, n_u, n_i  # half stars

    @staticmethod
    def _chunked(walks):
        return any(chunk < rows for rows, chunk in walks)

    @pytest.fixture
    def walks(self, monkeypatch):
        """(rows, chunk) of every bucket walk traced, both loops' caches
        emptied before and after."""
        from predictionio_tpu.ops import als as als_mod, als_grid

        loops = (als_mod._get_train_loop, als_grid._get_grid_train_loop)
        for loop in loops:
            loop.cache_clear()
        seen = []
        real = als_mod._walk_bucket_chunks

        def spy(arrays, cap, k, row_multiple, fn, carry):
            rows = arrays[0].shape[0]
            seen.append((rows, als_mod._bucket_chunk_rows(
                rows, cap, k, row_multiple)))
            return real(arrays, cap, k, row_multiple, fn, carry)

        monkeypatch.setattr(als_mod, "_walk_bucket_chunks", spy)
        yield seen
        for loop in loops:
            loop.cache_clear()

    @BOTH_MODES
    @CASES_OF_ONE
    def test_half_iteration_bit_for_bit(self, monkeypatch, walks, implicit,
                                        split_cap, budget):
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.ops import als as als_mod

        if budget:
            monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", budget)
        u, i, v, n_u, n_i = self._data()
        k = 8
        cfg = ALSConfig(rank=k, reg=0.0625, alpha=3.0, implicit=implicit,
                        solver="chol")
        buckets, split = als_mod.bucket_ragged_split(
            u, i, v.astype(np.float32), n_u, 8, split_cap or None)
        assert bool(len(split)) == bool(split_cap)
        here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        placed, split_dev = als_mod.place_buckets(
            "user", buckets, n_u, split,
            lambda r, cap: als_mod._bucket_chunk_rows(r, cap, k, 8),
            here, here)
        # small integers: every product and sum below 2**24 is exact
        opposing = jnp.asarray(np.random.default_rng(1).integers(
            -3, 4, (n_i, k)).astype(np.float32))

        train = jax.jit(lambda opp: als_mod._solve_buckets_device(
            opp, n_u, placed, cfg, split_dev))(opposing)
        grid = jax.jit(lambda opp, reg, alpha: als_mod._solve_buckets_device(
            opp[:, None, :], n_u, placed, cfg, split_dev, reg=reg,
            alpha=alpha))(opposing, jnp.asarray([cfg.reg], jnp.float32),
                          jnp.asarray([cfg.alpha], jnp.float32))
        assert self._chunked(walks) == bool(budget)
        assert grid.shape == (n_u, 1, k)
        np.testing.assert_array_equal(np.asarray(grid)[:, 0],
                                      np.asarray(train))
        assert np.abs(np.asarray(train)).max() > 0.1

    @BOTH_MODES
    @CASES_OF_ONE
    def test_whole_train(self, monkeypatch, walks, implicit, split_cap,
                         budget):
        from predictionio_tpu.ops import als as als_mod

        if budget:
            monkeypatch.setattr(als_mod, "_CHUNK_BUDGET_BYTES", budget)
        u, i, v, n_u, n_i = self._data()
        cfg = ALSConfig(rank=8, iterations=3, reg=0.07, alpha=3.0, seed=4,
                        implicit=implicit, split_cap=split_cap)
        seq = als_train(u, i, v, n_u, n_i, cfg, compute_rmse=True)
        (one,) = als_train_grid(u, i, v, n_u, n_i, [cfg], compute_rmse=True)
        assert self._chunked(walks) == bool(budget)
        assert rel_err(one.user_factors, seq.user_factors) < 2e-5
        assert rel_err(one.item_factors, seq.item_factors) < 2e-5
        assert one.rmse_history == pytest.approx(seq.rmse_history, rel=1e-6)
        assert len(one.rmse_history) == 3

"""Pallas batched Gauss-Jordan SPD solver (ops/pallas_solve.py),
interpret mode on CPU: correctness against numpy solves, padding-system
semantics, and full ALS parity between solver='gj' and solver='chol'."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import pallas_solve
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.ops.pallas_solve import gj_applicable, gj_solve
from predictionio_tpu.parallel.mesh import make_mesh
from predictionio_tpu.telemetry import spans


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (loop bodies, kernels), each once."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for item in (value if isinstance(value, (list, tuple))
                         else [value]):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def _pallas_calls(jaxpr):
    return [e for e in _walk_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def _built(layout):
    return pallas_solve.SOLVE_CALLS.labels(layout=layout).value


def _spd_batch(rng, r, k, reg=None):
    y = rng.normal(size=(r, k, k)).astype(np.float32)
    a = y @ y.transpose(0, 2, 1)
    a += (reg if reg is not None else 0.5 * k) * np.eye(k, dtype=np.float32)
    b = rng.normal(size=(r, k)).astype(np.float32)
    return a, b


class TestGJSolve:
    @pytest.mark.parametrize("r,k", [(5, 10), (130, 64), (300, 8), (9, 128)])
    def test_matches_numpy_solve(self, r, k):
        rng = np.random.default_rng(0)
        a, b = _spd_batch(rng, r, k)
        x = np.asarray(gj_solve(jnp.asarray(a), jnp.asarray(b),
                                interpret=True))
        ref = np.linalg.solve(a, b[..., None])[..., 0]
        rel = np.abs(x - ref).max() / np.abs(ref).max()
        assert rel < 1e-4, rel

    @pytest.mark.parametrize("layout", pallas_solve._LAYOUTS)
    @pytest.mark.parametrize("r,k", [(33, 64), (9, 128), (7, 100)])
    def test_every_layout_matches(self, monkeypatch, layout, r, k):
        """Both layouts stay numerically exact on either side of the
        VMEM size at which `layout_for` changes over, and the one built
        is the one counted; the size is free to move between them."""
        monkeypatch.setattr(pallas_solve, "_VMEM_BYTES",
                            0 if layout == "schur" else 2**40)
        rng = np.random.default_rng(4)
        a, b = _spd_batch(rng, r, k)
        before = _built(layout)
        x = np.asarray(gj_solve(jnp.asarray(a), jnp.asarray(b),
                                interpret=True))
        assert _built(layout) == before + 1
        ref = np.linalg.solve(a, b[..., None])[..., 0]
        rel = np.abs(x - ref).max() / np.abs(ref).max()
        assert rel < 1e-4, (layout, rel)

    @pytest.mark.parametrize("k", [10, 32, 64, 88, 96, 100, 128])
    @pytest.mark.parametrize("r", [1, 7, 8, 40, 128, 200, 943])
    def test_lanes_layout_matches_float64(self, r, k):
        """One system a lane: batches on both sides of a 128-lane block
        and orders on and off the 8-sublane tile, up to the rank-128
        cell's, against float64; all-zero systems (bucket padding) come
        out exactly 0."""
        rng = np.random.default_rng(1000 * k + r)
        a, b = _spd_batch(rng, r, k)
        zeros = [2, r - 1] if r > 3 else []
        a[zeros] = 0.0
        b[zeros] = 0.0
        live = np.setdiff1d(np.arange(r), zeros)
        x = np.asarray(gj_solve(jnp.asarray(a), jnp.asarray(b),
                                interpret=True))
        ref = np.linalg.solve(a[live].astype(np.float64),
                              b[live, :, None].astype(np.float64))[..., 0]
        assert np.abs(x[live] - ref).max() / np.abs(ref).max() < 1e-5
        np.testing.assert_array_equal(x[zeros], 0.0)

    @pytest.mark.parametrize("k,layout", [(8, "lanes"), (63, "lanes"),
                                          (96, "lanes"), (128, "lanes"),
                                          (288, "schur")])
    def test_auto_routes_by_rank(self, k, layout):
        """The layout goes by the order alone, through the VMEM its
        block asks for: one system a lane wherever three blocks fit
        (up to order 280: every rank `gj_applicable` admits), schur
        above; the counter says which was built."""
        a, b = _spd_batch(np.random.default_rng(k), 3, k)
        before = {name: _built(name) for name in pallas_solve._LAYOUTS}
        x = np.asarray(gj_solve(jnp.asarray(a), jnp.asarray(b),
                                interpret=True))
        after = {name: _built(name) for name in pallas_solve._LAYOUTS}
        before[layout] += 1
        assert after == before
        ref = np.linalg.solve(a, b[..., None])[..., 0]
        assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-4

    def test_lanes_kernel_is_traced_once_for_every_batch(self, monkeypatch):
        """Guard on what a first call pays (PERF.md, PR 29): Pallas
        traces a kernel anew in every `pallas_call`, about forty a train
        program; the lanes body is a jit whose trace every call after
        the first reuses, whatever the batch."""
        from jax.experimental import pallas as pl

        traced = []
        real = pl.multiple_of  # the body calls it once, in its inner loop
        monkeypatch.setattr(
            pl, "multiple_of",
            lambda *a, **kw: traced.append(1) or real(*a, **kw))
        k = 24  # an order no other test builds: its body is not traced yet
        pallas_solve._lanes_kernel.cache_clear()

        def solves(*ab):
            return [gj_solve(ab[i], ab[i + 1])
                    for i in range(0, len(ab), 2)]

        shapes = []
        for r in (5, 128, 300, 1000):
            shapes += [jax.ShapeDtypeStruct((r, k, k), jnp.float32),
                       jax.ShapeDtypeStruct((r, k), jnp.float32)]
        jaxpr = jax.make_jaxpr(solves)(*shapes).jaxpr
        assert len(_pallas_calls(jaxpr)) == 4
        assert len(traced) == 1

    @pytest.mark.parametrize("k", [10, 64, 88, 128])
    def test_lanes_kernel_stays_rolled(self, k):
        """Guard on what a first call pays (PERF.md, PR 29): a train
        program holds one kernel a bucket shape, each traced and lowered
        in every process. Both loops of the kernel stay rolled, so its
        jaxpr does not grow with the order; a body unrolled over the
        columns (64 steps x 64 columns at rank 64) cannot come back
        unseen."""
        a = jax.ShapeDtypeStruct((300, k, k), jnp.float32)
        b = jax.ShapeDtypeStruct((300, k), jnp.float32)
        jaxpr = jax.make_jaxpr(gj_solve)(a, b).jaxpr
        (call,) = _pallas_calls(jaxpr)
        assert sum(1 for _ in _walk_eqns(call.params["jaxpr"])) <= 100
        # around it: casts, the change of layout, its padding, and back
        assert sum(1 for _ in _walk_eqns(jaxpr)) <= 120

    @pytest.mark.parametrize("r,k,m", [(9, 16, 5), (33, 32, 33),
                                       (7, 64, 1), (5, 8, 120)])
    def test_multi_rhs_matches_numpy(self, r, k, m):
        """gj_solve_multi: M right-hand sides ride one augmented block
        (the schur recursion's base call)."""
        from predictionio_tpu.ops.pallas_solve import gj_solve_multi

        rng = np.random.default_rng(6)
        a, _ = _spd_batch(rng, r, k)
        b = rng.normal(size=(r, k, m)).astype(np.float32)
        x = np.asarray(gj_solve_multi(jnp.asarray(a), jnp.asarray(b),
                                      interpret=True))
        ref = np.linalg.solve(a, b)
        assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-4

    @pytest.mark.parametrize("r,k", [(17, 64), (5, 128), (9, 96),
                                     (3, 200), (21, 48)])
    def test_schur_matches_numpy(self, r, k):
        """Recursive Schur solve (MXU formulation; behind `gj_solve` for
        the orders whose lanes block does not fit VMEM): exact against
        numpy, odd split sizes fall back to the base kernel."""
        from predictionio_tpu.ops.pallas_solve import schur_solve

        rng = np.random.default_rng(7)
        a, b = _spd_batch(rng, r, k)
        x = np.asarray(schur_solve(jnp.asarray(a), jnp.asarray(b),
                                   interpret=True))
        ref = np.linalg.solve(a, b[..., None])[..., 0]
        assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-4

    def test_schur_zero_padding_systems(self):
        from predictionio_tpu.ops.pallas_solve import schur_solve

        rng = np.random.default_rng(8)
        a, b = _spd_batch(rng, 6, 64)
        a[2] = 0.0
        b[2] = 0.0
        x = np.asarray(schur_solve(jnp.asarray(a), jnp.asarray(b),
                                   interpret=True))
        assert np.isfinite(x).all()
        np.testing.assert_array_equal(x[2], np.zeros(64, np.float32))

    def test_auto_routes_large_ranks_to_schur(self, monkeypatch):
        """gj_solve sends an order whose lanes block does not fit VMEM
        through schur_solve, and rank 128 no longer."""
        from predictionio_tpu.ops import pallas_solve

        called = []
        real = pallas_solve.schur_solve
        monkeypatch.setattr(pallas_solve, "schur_solve",
                            lambda *a, **k: called.append(1) or real(*a, **k))
        rng = np.random.default_rng(9)
        a, b = _spd_batch(rng, 3, 288)
        gj_solve(jnp.asarray(a), jnp.asarray(b), interpret=True)
        assert called
        called.clear()
        for k in (64, 128):
            a, b = _spd_batch(rng, 3, k)
            gj_solve(jnp.asarray(a), jnp.asarray(b), interpret=True)
        assert not called  # one system a lane

    def test_all_zero_system_solves_to_zero(self):
        """Bucket padding rows arrive as A=0, b=0 and must not NaN."""
        rng = np.random.default_rng(1)
        a, b = _spd_batch(rng, 4, 16)
        a[2] = 0.0
        b[2] = 0.0
        x = np.asarray(gj_solve(jnp.asarray(a), jnp.asarray(b),
                                interpret=True))
        assert np.isfinite(x).all()
        np.testing.assert_array_equal(x[2], np.zeros(16, np.float32))

    def test_applicable_ranks(self):
        assert gj_applicable(10)
        assert gj_applicable(64)
        assert gj_applicable(128)
        assert not gj_applicable(512)
        # and every applicable rank takes one system a lane
        assert pallas_solve.layout_for(pallas_solve._MAX_RANK) == "lanes"

    def test_under_jit(self):
        rng = np.random.default_rng(2)
        a, b = _spd_batch(rng, 12, 8)
        fn = jax.jit(lambda a, b: gj_solve(a, b, interpret=True))
        x = np.asarray(fn(jnp.asarray(a), jnp.asarray(b)))
        ref = np.linalg.solve(a, b[..., None])[..., 0]
        assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-4


class TestALSWithGJ:
    def _data(self):
        rng = np.random.default_rng(3)
        n_u, n_i, nnz = 40, 30, 600
        ui = rng.integers(0, n_u, nnz).astype(np.int32)
        ii = rng.integers(0, n_i, nnz).astype(np.int32)
        r = rng.uniform(1, 5, nnz).astype(np.float32)
        return ui, ii, r, n_u, n_i

    @pytest.mark.parametrize("implicit", [False, True])
    def test_gj_matches_chol_trajectory(self, implicit):
        ui, ii, r, n_u, n_i = self._data()
        mesh = make_mesh({"data": 1, "model": 1}, devices=jax.devices()[:1])
        base = ALSConfig(rank=8, iterations=5, reg=0.05, seed=0,
                         implicit=implicit, pallas="interpret")
        res_gj = als_train(ui, ii, r, n_u, n_i,
                           dataclasses.replace(base, solver="gj"),
                           mesh=mesh, compute_rmse=True)
        res_ch = als_train(ui, ii, r, n_u, n_i,
                           dataclasses.replace(base, solver="chol",
                                               pallas="off"),
                           mesh=mesh, compute_rmse=True)
        np.testing.assert_allclose(res_gj.rmse_history, res_ch.rmse_history,
                                   rtol=2e-3)

    def test_train_loop_builds_one_kernel_a_bucket(self, monkeypatch):
        """Guard on what a first call pays (PERF.md, PR 29): the scan
        body is traced once, and each side's half-iteration builds one
        solve a bucket plus one for its split rows' accumulators, each
        the lanes kernel (at every rank a train can ask the kernel
        for)."""
        from predictionio_tpu.ops import als

        expected = []
        real = als._solve_buckets_device

        def spy(opposing, out_rows, buckets_dev, cfg, split_rows=None,
                *args, **kwargs):
            n_split = 0 if split_rows is None else split_rows.shape[0]
            expected.append(len(buckets_dev) + (1 if n_split else 0))
            return real(opposing, out_rows, buckets_dev, cfg, split_rows,
                        *args, **kwargs)

        monkeypatch.setattr(als, "_solve_buckets_device", spy)
        als._get_train_loop.cache_clear()
        ui, ii, r, n_u, n_i = self._data()
        mesh = make_mesh({"data": 1, "model": 1}, devices=jax.devices()[:1])
        # split_cap 16: some of the 40 users' rows are split, so the
        # accumulators' solve is there
        cfg = ALSConfig(rank=8, iterations=3, reg=0.05, seed=0, solver="gj",
                        pallas="interpret", split_cap=16)
        before = {name: _built(name) for name in pallas_solve._LAYOUTS}
        tl, token = spans.begin("test", "train", "RUN", "t-1")
        try:
            res = als_train(ui, ii, r, n_u, n_i, cfg, mesh=mesh)
        finally:
            spans.finish(tl, token, status=None, duration_s=0.0)
        assert np.isfinite(res.user_factors).all()
        assert len(expected) == 2 and min(expected) >= 2, expected
        built = {name: _built(name) - before[name]
                 for name in pallas_solve._LAYOUTS}
        assert built.pop("lanes") == sum(expected)
        assert not any(built.values()), built
        # the timeline says which kernel the loop holds: one record a
        # side, inside the dispatch that traced it
        by_name = {}
        for name, start, dur, _err, _nested in tl.spans:
            by_name.setdefault(name, []).append((start, start + dur))
        (lo, hi), = by_name["als.loop.dispatch"]
        assert len(by_name["als.solve.lanes"]) == 2
        assert all(lo <= s and e <= hi for s, e in by_name["als.solve.lanes"])

    @pytest.mark.parametrize("rank,layout", [(128, "lanes"), (288, "schur")])
    def test_a_side_holds_one_solve_a_bucket(self, rank, layout):
        """Program-level guard. Rank 128 (the als128i cell's route): one
        side's half-iteration holds one lanes kernel a bucket and one for
        its split rows' accumulators, and no schur recursion: 38 kernels
        in an ML-20M train loop where the recursion held 152. Its twin at
        an order whose lanes block does not fit VMEM: one schur recursion
        each, 288 -> 144 -> 72 -> 36 -> 18 (four levels, sixteen base
        kernels), and no lanes kernel. The counter says which."""
        from predictionio_tpu.ops import als

        f32, i32 = jnp.float32, jnp.int32
        shape = jax.ShapeDtypeStruct

        def bucket(r, c, split):
            return (shape((r,), i32), shape((r, c), i32),
                    shape((r, c), f32), shape((r, c), f32),
                    shape((r,), i32) if split else None)

        cfg = ALSConfig(rank=rank, solver="gj", pallas="interpret")

        def side(opposing, buckets, split_rows):
            return als._solve_buckets_device(opposing, 50, buckets, cfg,
                                             split_rows)

        before = {name: _built(name) for name in pallas_solve._LAYOUTS}
        jaxpr = jax.make_jaxpr(side)(
            shape((41, rank), f32),
            (bucket(16, 8, False), bucket(8, 32, True)),
            shape((3,), i32)).jaxpr
        built = {name: _built(name) - before[name]
                 for name in pallas_solve._LAYOUTS}
        assert built == {"lanes": 0, "schur": 0, layout: 3}
        calls = _pallas_calls(jaxpr)
        lanes = [c for c in calls if c.params["name"] == "gj_lanes"]
        if layout == "lanes":
            assert len(calls) == len(lanes) == 3
            # x comes back batch-minor, one lane a system: [K, R]
            assert sorted(c.params["out_avals"][0].shape for c in calls) \
                == [(128, 3), (128, 8), (128, 16)]
        else:
            assert len(calls) == 16 * 3 and not lanes
            # the base kernels see order 18, its right-hand sides beside
            # it in whole tiles of 128 lanes
            assert {c.params["out_avals"][0].shape[1] for c in calls} == {18}

    def test_schur_layout_matches_chol_trajectory(self, monkeypatch):
        """Full ALS training through the schur solver path (no VMEM for
        a lanes block, so the test's small rank takes it) reproduces the
        Cholesky trajectory."""
        from predictionio_tpu.ops import als

        monkeypatch.setattr(pallas_solve, "_VMEM_BYTES", 0)
        # the loop of `test_gj_matches_chol_trajectory` holds lanes
        # kernels for this very config
        als._get_train_loop.cache_clear()
        ui, ii, r, n_u, n_i = self._data()
        mesh = make_mesh({"data": 1, "model": 1}, devices=jax.devices()[:1])
        base = ALSConfig(rank=8, iterations=5, reg=0.05, seed=0,
                         pallas="interpret")
        before = _built("schur")
        res_s = als_train(ui, ii, r, n_u, n_i,
                          dataclasses.replace(base, solver="gj"),
                          mesh=mesh, compute_rmse=True)
        als._get_train_loop.cache_clear()
        assert _built("schur") > before
        res_c = als_train(ui, ii, r, n_u, n_i,
                          dataclasses.replace(base, solver="chol",
                                              pallas="off"),
                          mesh=mesh, compute_rmse=True)
        np.testing.assert_allclose(res_s.rmse_history, res_c.rmse_history,
                                   rtol=2e-3)

    def test_auto_resolves_to_chol_on_cpu(self):
        """On the CPU test backend (no interpret flag) auto must not pick
        the TPU-only kernel."""
        ui, ii, r, n_u, n_i = self._data()
        mesh = make_mesh({"data": 1, "model": 1}, devices=jax.devices()[:1])
        cfg = ALSConfig(rank=8, iterations=2, reg=0.05, solver="auto")
        res = als_train(ui, ii, r, n_u, n_i, cfg, mesh=mesh)
        assert np.isfinite(res.user_factors).all()

    def test_gj_falls_back_on_cpu_backend(self):
        """Explicit solver='gj' without interpret on a non-TPU backend
        must fall back to 'chol' instead of crashing inside jit."""
        ui, ii, r, n_u, n_i = self._data()
        mesh = make_mesh({"data": 1, "model": 1}, devices=jax.devices()[:1])
        cfg = ALSConfig(rank=8, iterations=2, reg=0.05, solver="gj",
                        pallas="off")
        res = als_train(ui, ii, r, n_u, n_i, cfg, mesh=mesh)
        assert np.isfinite(res.user_factors).all()

    def test_gj_falls_back_under_mesh(self):
        """solver='gj' under a multi-device mesh must fall back (the
        kernel is a single-device program) and still converge."""
        ui, ii, r, n_u, n_i = self._data()
        mesh = make_mesh({"data": 4, "model": 1})
        cfg = ALSConfig(rank=8, iterations=2, reg=0.05, solver="gj",
                        pallas="off")
        res = als_train(ui, ii, r, n_u, n_i, cfg, mesh=mesh,
                        compute_rmse=True)
        assert np.isfinite(res.user_factors).all()
        assert res.rmse_history[-1] < 2.0

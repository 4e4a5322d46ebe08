"""What the ALS loops lower to, written out a case a file: the proof a
refactor of `ops/als*.py` is held to (ROADMAP.md D16). Run it on two trees
and compare the directories:

    python3 benchmarks/als_lowered_text.py --tree .parent --out A
    python3 benchmarks/als_lowered_text.py --tree . --out B
    python3 benchmarks/als_lowered_text.py --compare A B

`--compare` is `diff -r` that can also read a TPU lowering: there a Pallas
kernel rides in its `tpu_custom_call` as a serialized Mosaic module whose
location table holds file paths, line numbers and the calling frames'
names, so two trees at two paths never lower to the same bytes; it says
`same but for kernel locations` where every other byte is equal and every
kernel body is equal once printed without its locations.

`--shape tests` (the default) lowers on whatever backend the process has,
with the Pallas solver in interpreter mode, on abstract buckets: one that
walks in trips under a budget cut to 1 MiB and holds split rows' segments,
one that does neither. `--shape cells` makes a cell's ratings from
`perf/data.py`, bucketizes them and lowers at the placed buckets' shapes
with `solver` as the cell resolves it: for the chip, or with
`--described-v5e` here, for a v5e that is described and not attached (the
TPU's lowering, kernels and all, with no chip). `--sharded` trains
on the CPU meshes of `tests/test_als.py::TestModelShardedALS` instead and
writes the factors' digests (`XLA_FLAGS=--xla_force_host_platform_device_count=8`):
the model-sharded loop's text is written beside them, and may differ in
the order of independent ops where its results may not.
"""

import argparse
import dataclasses
import hashlib
import json
import os
import re
import sys


def _cases():
    """(name, ALSConfig fields) of the two ALS cells' configurations."""
    return [("als64", dict(rank=64, reg=0.05, split_cap=32768)),
            ("als128i", dict(rank=128, reg=0.1, alpha=40.0, implicit=True))]


def _plain_cases():
    """Off the cells' route: Cholesky, and λ not weighted by the count."""
    return [("chol8", dict(rank=8, reg=0.05, solver="chol",
                           weighted_reg=False)),
            ("chol8i", dict(rank=8, reg=0.1, alpha=4.0, implicit=True,
                            solver="chol", weighted_reg=False))]


ON = None  # the sharding every abstract argument carries (--described-v5e)


def _s(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=ON)


def _abstract_bucket(r, c, split):
    import jax.numpy as jnp

    return (_s((r,), jnp.int32), _s((r, c), jnp.int32),
            _s((r, c), jnp.float32), _s((r, c), jnp.float32),
            _s((r,), jnp.int32) if split else None)


def _placed_shapes(buckets, width, row_multiple, als):
    """The shapes `als_train` places a side's buckets with."""
    out = []
    for b in buckets:
        r, cap = b.cols.shape
        r += (-r) % als._bucket_chunk_rows(r, cap, width, row_multiple)
        out.append(_abstract_bucket(r, cap, b.segmap is not None))
    return out


def _write(out_dir, name, lowered):
    text = lowered.as_text()
    with open(os.path.join(out_dir, name + ".txt"), "w") as f:
        f.write(text)
    print(name, len(text), hashlib.sha256(text.encode()).hexdigest()[:16],
          flush=True)


def _lower_all(out_dir, name, cfg, n_users, n_items, ub, ib, n_usplit,
               n_isplit, als, als_grid, grid_sides=None):
    import jax
    import jax.numpy as jnp

    s = _s
    f32 = jnp.float32
    k = cfg.rank
    splits = (s((n_usplit,), jnp.int32), s((n_isplit,), jnp.int32))
    for rmse in (False, True):
        loop = als._get_train_loop(n_users, n_items, cfg, rmse, 10, 8, None)
        _write(out_dir, f"{name}.train.rmse{int(rmse)}", loop.jitted.lower(
            s((n_items, k), f32), s((n_users, k), f32), ub, ib, *splits))
    g = 3
    gub, gib = grid_sides or (ub, ib)
    gcfg = dataclasses.replace(cfg, reg=0.0, alpha=1.0, seed=0, iterations=0)
    for rmse in (False, True):
        loop = als_grid._get_grid_train_loop(n_users, n_items, gcfg, g, rmse,
                                             10, 8, None)
        keys = jax.eval_shape(
            lambda: jnp.stack([jax.random.key(i) for i in range(g)]))
        keys = jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=ON)
        _write(out_dir, f"{name}.grid3.rmse{int(rmse)}", loop.jitted.lower(
            keys, s((g,), f32), s((g,), f32), s((g,), jnp.int32), gub, gib,
            *splits))


def lower_tests_shape(out_dir, als, als_grid):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.online import foldin

    als._CHUNK_BUDGET_BYTES = 1 << 20
    for name, fields in _cases() + _plain_cases():
        cfg = als.resolve_solver(als.ALSConfig(
            iterations=0, pallas="interpret", **fields))
        assert cfg.solver == fields.get("solver", "gj"), cfg
        # 1 MiB admits 32 rows of 128 x 64 x 4 B: the first bucket walks
        ub = [_abstract_bucket(96, 128, True), _abstract_bucket(16, 8, False)]
        ib = [_abstract_bucket(64, 256, True), _abstract_bucket(24, 16, False)]
        _lower_all(out_dir, name, cfg, 300, 200, ub, ib, 3, 2, als, als_grid)
        fold = foldin._fold_solver(cfg)
        _write(out_dir, f"{name}.fold", fold.jitted.lower(
            _s((512, cfg.rank), jnp.float32),
            (_abstract_bucket(32, 8, False),), out_rows=32))


def lower_cells_shape(out_dir, als, als_grid, root, described):
    sys.path.insert(1, root)
    from perf import data

    for name, fields in _cases():
        with open(os.path.join(root, "perf", "configs",
                               f"{name}_ml20m.json")) as f:
            shape = json.load(f)["shape"]
        n_users, n_items = data.table_heights(shape)
        ui, ii, vals = data.make_ratings(shape, 11)
        # a described chip is not the process's backend: what the cells
        # resolve `auto` to on the chip (`gj`) is then said, not asked
        cfg = (als.ALSConfig(iterations=0, solver="gj", **fields) if described
               else als.resolve_solver(als.ALSConfig(iterations=0, **fields)))
        print(name, "solver", cfg.solver, flush=True)
        ubk, u_split, ibk, i_split = als.bucketize_cached(
            ui, ii, vals, n_users, n_items, 8,
            cfg.split_cap if cfg.split_cap > 0 else None, cfg.cap_growth,
            None)
        sides = [[_placed_shapes(b, w, 8, als) for b in (ubk, ibk)]
                 for w in (cfg.rank, 3 * cfg.rank)]
        print(name, "user buckets", [b[1].shape for b in sides[0][0]],
              "item buckets", [b[1].shape for b in sides[0][1]], flush=True)
        _lower_all(out_dir, name, cfg, n_users, n_items, *sides[0],
                   len(u_split), len(i_split), als, als_grid,
                   grid_sides=sides[1])


def train_sharded(out_dir, als):
    import jax
    import numpy as np

    from predictionio_tpu.ops import als_sharded
    from predictionio_tpu.parallel.mesh import make_mesh

    entered = []  # (loop, arguments) of every dispatch of the sharded loop
    real = als_sharded.get_train_loop_sharded

    def spy(*key):
        loop = real(*key)

        def call(*args):
            entered.append((loop, args))
            return loop(*args)
        return call

    als_sharded.get_train_loop_sharded = spy
    rng = np.random.default_rng(3)
    n_users, n_items, n = 90, 60, 2500
    ui = rng.integers(0, n_users, n).astype(np.int32)
    ui[:400] = 7  # a hot row: split segments under split_cap
    ii = rng.integers(0, n_items, n).astype(np.int32)
    r = rng.uniform(1, 5, n).astype(np.float32)
    for data, model in ((2, 2), (4, 2)):
        mesh = make_mesh({"data": data, "model": model},
                         devices=jax.devices()[:data * model])
        for implicit in (False, True):
            for solver in ("chol", "gj"):
                cfg = als.ALSConfig(
                    rank=8, iterations=3, reg=0.05, seed=1, solver=solver,
                    weighted_reg=solver == "gj",
                    pallas="interpret", implicit=implicit, alpha=4.0,
                    split_cap=64)
                res = als.als_train(ui, ii, r, n_users, n_items, cfg,
                                    mesh=mesh, compute_rmse=True)
                name = f"sharded.d{data}m{model}.i{int(implicit)}.{solver}"
                loop, args = entered.pop()
                _write(out_dir, name, loop.jitted.lower(*args))
                digest = hashlib.sha256(
                    res.user_factors.tobytes() + res.item_factors.tobytes()
                    + np.asarray(res.rmse_history).tobytes()).hexdigest()
                print(name, digest[:16], flush=True)
                with open(os.path.join(out_dir, name + ".sha"), "w") as f:
                    f.write(digest + "\n")


_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def _kernels(text):
    """The text with its kernel bodies cut out, and the bodies printed
    without their locations."""
    import base64

    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    bodies = []
    with ctx:
        for b64 in _BODY.findall(text):
            module = ir.Module.parse(base64.b64decode(b64))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
    return _BODY.sub("BODY", text), bodies


def compare(a_dir, b_dir):
    """0 where every file of the two directories is the same, but for the
    locations inside kernel bodies at most."""
    names = sorted(set(os.listdir(a_dir)) | set(os.listdir(b_dir)))
    worst = 0
    for name in names:
        try:
            with open(os.path.join(a_dir, name)) as fa, \
                    open(os.path.join(b_dir, name)) as fb:
                a, b = fa.read(), fb.read()
        except OSError as e:
            print(name, "MISSING", e)
            worst = 1
            continue
        if a == b:
            verdict = "same"
        elif _kernels(a) == _kernels(b):
            verdict = (f"same but for kernel locations "
                       f"({len(_BODY.findall(a))} kernels)")
        else:
            verdict, worst = "DIFFERS", 1
        print(name, verdict, flush=True)
    return worst


def main():
    if sys.argv[1:2] == ["--compare"]:
        sys.exit(compare(*sys.argv[2:4]))
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=".")
    ap.add_argument("--out", required=True)
    ap.add_argument("--shape", choices=("tests", "cells"), default="tests")
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--described-v5e", action="store_true")
    args = ap.parse_args()
    root = os.path.abspath(args.tree)
    sys.path.insert(0, root)
    os.makedirs(args.out, exist_ok=True)
    from predictionio_tpu.ops import als, als_grid

    assert os.path.abspath(als.__file__).startswith(root), als.__file__
    if args.described_v5e:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        global ON
        ON = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    if args.sharded:
        train_sharded(args.out, als)
    elif args.shape == "tests":
        lower_tests_shape(args.out, als, als_grid)
    else:
        lower_cells_shape(args.out, als, als_grid, root, args.described_v5e)


if __name__ == "__main__":
    main()

"""The SPD solve of an ALS half-iteration: x = A⁻¹ b for a batch of
λ-regularised normal equations, and how it is dispatched.

Three questions, one home each. *Kernel or Cholesky* is decided once a
train by `ops.als.resolve_solver` (backend, rank, assert mode) and arrives
here as `kernel`. *How it is dispatched* (plain, or one kernel a device
under `shard_map`) is `solve_spd` below. *Which kernel* is
`pallas_solve.layout_for` (the order alone). Every half-iteration calls
`solve_spd`: `ops/als.py`, `ops/als_grid.py` (which flattens its grid axis
into the batch round the call) and `ops/als_sharded.py` (already inside
its own `shard_map`, so it passes no mesh).
"""

from __future__ import annotations

import functools


def chol_solve(a, b):
    """x = A⁻¹ b by Cholesky and two triangular solves (A is SPD by
    construction, λ > 0; ~30 % faster than LU on v5e).

    a: [R, K, K]; b: [R, K] → x: [R, K]."""
    import jax
    import jax.numpy as jnp

    chol = jnp.linalg.cholesky(a)
    y1 = jax.lax.linalg.triangular_solve(
        chol, b[..., None], left_side=True, lower=True)
    return jax.lax.linalg.triangular_solve(
        chol, y1, left_side=True, lower=True, transpose_a=True)[..., 0]


def solve_spd(a, b, *, kernel: bool, interpret: bool, mesh=None,
              row_sharded: bool = True):
    """x = A⁻¹ b for a batch of SPD systems, a: [R, K, K], b: [R, K].

    `kernel` false: Cholesky. Else the Pallas Gauss-Jordan kernel in f32
    (`interpret`: in interpreter mode, on any backend), run as `mesh`
    asks: under a mesh of more than one device one kernel a device on its
    shard of the rows, and Cholesky for a batch that is not `row_sharded`
    (the [U] split accumulators: not a multiple of the data axis, and
    tiny)."""
    meshed = mesh is not None and mesh.size > 1
    if not kernel or (meshed and not row_sharded):
        return chol_solve(a, b)
    import jax.numpy as jnp

    from predictionio_tpu.ops import pallas_solve

    f32 = jnp.float32
    solve = functools.partial(pallas_solve.gj_solve, interpret=interpret)
    if meshed:
        # pallas_call is a single-device program GSPMD can't partition;
        # shard_map runs one kernel per device on its local row shard
        # (rows are bucketed to multiples of the data-axis size, so
        # shards are even)
        import jax
        from jax.sharding import PartitionSpec as P

        from predictionio_tpu.parallel.mesh import DATA_AXIS

        spec = P(DATA_AXIS)  # als_train requires a 'data' axis
        solve = jax.shard_map(
            solve, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
            # pallas_call out_shape carries no varying-mesh-axes info;
            # the kernel is elementwise over rows, so the replication
            # check adds nothing here
            check_vma=False)
    return solve(a.astype(f32), b.astype(f32)).astype(a.dtype)

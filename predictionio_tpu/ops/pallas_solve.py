"""Pallas TPU kernels: batched SPD solve via vectorized Gauss-Jordan.

The other ALS hot op: after the Gram/RHS einsums, each bucket needs
x_r = A_r⁻¹ b_r for thousands of small (K×K, K = rank) SPD systems. XLA
lowers `jnp.linalg.cholesky` to a custom-call whose batched factorization
dominates rank-64 epochs (v5e profile, round 1: 873 ms of a 1.8 s 10-iter
loop on the 12 664-row bucket — ~66% of device time including the paired
triangular solves).

Two layouts, both Gauss-Jordan reductions in data-independent steps of
elementwise VPU work, vectorized over the batch so throughput scales with
the batch instead of the sequential critical path of one factorization.
`layout_for` picks by what the order's block asks of VMEM; the device-time
A/Bs that settled it, and the layouts that lost: docs/performance.md.

- ``lanes`` (wherever three blocks fit VMEM: every order `gj_applicable`
  admits): ONE SYSTEM A LANE. A block is [K+1, K, 128]: leading index =
  column (b last), sublanes = row, lanes = 128 systems. The pivot column
  is taken by a dynamic index on the leading dim and a column's pivot-row
  entry is read from the pivot column by symmetry, so a step is one masked
  sublane reduce (the pivot) and then multiply, subtract, select over the
  live columns: no one-hot selection, no cross-lane traffic, no padded
  lane. Around it one XLA copy turns [R, K, K] batch-minor; b and x are
  batch-minor already as XLA lays them out. A few rows cost a whole
  block: 19 µs at K 64, 113 µs at K 128. Of its bytes bound the kernel
  reaches 14.0 % in an ML-20M rank-64 iteration (ledger, PR 29).

- ``schur`` (orders whose block does not fit; none up to `_MAX_RANK`):
  recursive Schur complements. The elimination becomes [R, K/2, K/2]
  batched MXU matmuls round a multi-RHS kernel at order ≤ 32
  (`gj_solve_multi`), which keeps one system in a [K, lanes] tile,
  carries its right-hand sides as extra lanes and selects pivots through
  one-hot iota masks (one fused FMA+select pass over the block).

Hypotheses refuted on device time, so nobody tries them again (the tables
are in docs/performance.md; the kernels were deleted in PR 30):
- several systems side by side in one system's lanes: 0.77× the one-hot
  single-system kernel at rank 64 — the per-group pivot reductions cost
  more than the lane padding they recover. ``lanes`` is NOT this
  packing: it has no one-hot selection and no per-group reduce;
- two pivots a step through a 2×2 pivot-block inverse: 0.89× / 0.71× at
  rank 64 / 128 — the one-hot kernel is bound by VPU throughput at what
  Mosaic achieves, not by the length of its chain;
- a batched CG in place of the factorization: 1.5–2.8 s/epoch vs 1.07 s
  (its matvecs re-read the [R, K, K] Gram from HBM every iteration).

Mosaic lessons baked in (round-1 findings, kept so nobody re-learns them):
- dynamic slices/stores on the sublane/lane dims miscompile silently
  (compiled output diverged while interpret mode was exact) — selection
  on those dims goes through one-hot masks, and the grid walks the outer
  (batch) dim only; a dynamic index on a LEADING (untiled) dim is sound
  (chip check of PR 28: K 10/32/64/88, R 1…5000 within 1e-5 of float64),
  and is what ``lanes`` rests on;
- `input_output_aliases` does NOT deliver the input inside the out block
  once the grid pipelines (>1 tile ⇒ NaNs) — the working copy is an
  explicit VMEM scratch instead.

Gauss-Jordan does ~2·K³ useful FLOPs per system (vs Cholesky's K³/3) but
they are perfectly batch-parallel VPU FMAs instead of a sequential
custom-call. No pivoting: A = YᵀWY + λ(n)I is SPD (hence symmetric) with
strictly positive diagonal, the same assumption MLlib's dppsv Cholesky
makes. All-zero systems (bucket padding rows) short-circuit to x = 0 via
the pivot guard.

No reference counterpart: PredictionIO delegates these solves to Spark
MLlib's JNI BLAS («org.apache.spark.mllib.recommendation.ALS» →
CholeskyDecomposition.solve — SURVEY.md §2.5 [U]); this kernel is the
TPU-native equivalent of that native layer.
"""

from __future__ import annotations

import functools

from predictionio_tpu.telemetry.registry import REGISTRY

_LAYOUTS = ("lanes", "schur")
# The layout is chosen while a program is traced, so this counts the
# solves a process built into its programs (one a bucket of a train
# loop, however many iterations run it), not the solves it ran.
SOLVE_CALLS = REGISTRY.counter(
    "als_solve_calls_total",
    "gj_solve calls traced into a program, by the kernel layout built "
    "for them (lanes | schur)",
    labelnames=("layout",))

_LANES = 128
_SUBLANES = 8
_MAX_RANK = 256
# `layout_for`: "lanes" where `_lanes_vmem_bytes(k)` fits this, the VMEM
# of one v5e core (orders up to 280), "schur" above.
_VMEM_BYTES = 128 * 2**20
# `schur_solve` recurses down to this order, then `gj_solve_multi`
_SCHUR_BASE = 32
# lanes layout: columns eliminated a turn of the inner loop. The loop's
# body is lowered once for every bucket shape of a train program, in
# every process: 8 is 5 % faster on the chip (4.32 against 4.53 ms at
# [31296, 64, 64]) and a third more equations to lower.
_LANES_GROUP = 4


def _lane_pad(n: int) -> int:
    return -(-n // _LANES) * _LANES


def _sub_pad(n: int) -> int:
    return -(-n // _SUBLANES) * _SUBLANES


def _row_tile(per_row_bytes: int, budget: int) -> int:
    """Batch tile (multiple of 8, ≤128) sized so ~4 blocks (pipelined
    input blocks + the scratch working copy + the output) fit in `budget`
    bytes of VMEM."""
    t = budget // (4 * per_row_bytes)
    return max(8, min(128, t // 8 * 8))


def gj_applicable(rank: int) -> bool:
    return rank <= _MAX_RANK


@functools.lru_cache(maxsize=8)
def _lanes_kernel(k: int):
    """The kernel body of the lanes layout at order k: column-GJ with ONE
    SYSTEM A LANE on a block M[c, i, r] of [kr + 1, kr, 128] (order k
    zero-padded to kr = sub_pad(k), whole sublane tiles): leading index
    c = column of A (c = kr: b), sublanes i = row, lanes r = 128 systems.
    Nothing crosses lanes, no lane is padding.

    Step j takes its pivot column by a dynamic index on the leading
    (untiled) dim, `scr[j]`, and eliminates row j from every column
    c > j. Column c's row-j entry is read as colj[c]: the trailing block
    of an SPD matrix stays symmetric under elimination, so only the b
    column extracts its own. Columns c <= j are dead (x is wanted, not
    A^-1): the inner loop starts at the pivot's own group of columns,
    and what it still does to that group's dead columns is never read.
    A group is a window `scr.at[pl.ds(first, g)]` indexed statically: as
    `scr[first + t]` the compiler cannot tell the columns apart and
    orders every load behind the store before it (5.9 against 4.3 ms at
    [31296, 64, 64]; chip runs of PR 28).

    What a first call pays (PERF.md, PR 29). A train program holds one
    `pallas_call` a bucket shape, about forty, and Pallas traces the
    kernel anew for each: on the chip's host that was 2.2 s of a first
    train for this body and 4.2 s with 8 columns a group. The block does
    not depend on the batch, so the body is a `jax.jit` that is traced
    once a process and inlined into each kernel after that. Lowering
    stays one pass over the body a bucket, so the body is kept short:
    both loops rolled, `lax` selects and an integer `lax.div` where
    `jnp.where` and `//` would each leave a nested jit in it.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    g = _LANES_GROUP
    kr = _sub_pad(k)
    tile = (kr, _LANES)

    def rows(v):  # [1, 128] -> every sublane
        return lax.broadcast_in_dim(v, tile, (0, 1))

    def body(a_ref, b_ref, x_ref, scr, fn_ref):
        scr[:kr] = a_ref[...]
        scr[kr] = b_ref[...]
        sub = lax.broadcasted_iota(jnp.int32, tile, 0)
        zeros = jnp.zeros(tile, jnp.float32)

        def step(j, _):
            colj = scr[j]  # [kr, 128]: column j of every system
            is_j = sub == j

            def entry_j(col):  # [1, 128]
                return jnp.sum(lax.select(is_j, col, zeros), axis=0,
                               keepdims=True)

            def eliminated(col, f):
                return lax.select(is_j, rows(f), col - colj * f)

            d = entry_j(colj)
            # all-zero (padding) systems: every factor is 0, x stays 0
            d = lax.select(jnp.abs(d) < 1e-30, jnp.ones_like(d), d)
            fn_ref[...] = colj / d  # row c = A[j, c] / d by symmetry
            bcol = scr[kr]
            scr[kr] = eliminated(bcol, entry_j(bcol) / d)

            def group(i, _):
                first = pl.multiple_of(i * g, g)
                f = fn_ref[pl.ds(first, g), :]
                cols = scr.at[pl.ds(first, g)]
                for t in range(g):
                    cols[t] = eliminated(cols[t], f[t:t + 1, :])
                return 0

            lax.fori_loop(lax.div(j, g), kr // g, group, 0)
            return 0

        lax.fori_loop(0, k, step, 0)
        x_ref[...] = scr[kr]

    # inlined into the kernel that is being traced: never dispatched,
    # never compiled by itself, so there is nothing to meter
    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


def _build_solver_lanes(k: int, r: int, interpret: bool):
    """`pallas_call` of the lanes kernel on a [kr, kr, R] and b [kr, R],
    the batch minor: a grid over blocks of 128 systems."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kr = _sub_pad(k)

    return pl.pallas_call(
        _lanes_kernel(k),
        # the batch is the minor dim and is not padded: the last block's
        # lanes past R hold whatever the buffer held, solve to anything
        # (lanes never meet) and are not written back
        grid=(-(-r // _LANES),),
        in_specs=[pl.BlockSpec((kr, kr, _LANES), lambda t: (0, 0, t)),
                  pl.BlockSpec((kr, _LANES), lambda t: (0, t))],
        out_specs=pl.BlockSpec((kr, _LANES), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((kr, r), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kr + 1, kr, _LANES), jnp.float32),
                        pltpu.VMEM((kr, _LANES), jnp.float32)],
        # what `layout_for` held against the chip's VMEM
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_lanes_vmem_bytes(k)),
        name="gj_lanes",
        interpret=interpret,
    )


@functools.lru_cache(maxsize=32)
def _build_solver_aug_multi(k: int, kp: int, r_tile: int, n_tiles: int,
                            interpret: bool):
    """Multi-RHS row-GJ: the augmented block carries M RHS columns
    (lanes k..k+M-1) instead of one; the elimination loop is identical
    (it already sweeps every lane), and the OUTPUT is the whole reduced
    block — the RHS region is sliced outside the kernel, trading one
    extra block write for zero new Mosaic surface."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(aug_ref, x_ref, scr):
        scr[:] = aug_ref[:]
        sub = jax.lax.broadcasted_iota(jnp.int32, (1, k, 1), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, kp), 2)

        def step(j, _):
            a = scr[:]
            is_row = sub == j
            is_col = lane == j
            row = jnp.sum(jnp.where(is_row, a, 0.0), axis=1, keepdims=True)
            d = jnp.sum(jnp.where(is_col, row, 0.0), axis=2, keepdims=True)
            d = jnp.where(jnp.abs(d) < 1e-30, 1.0, d)
            row = row / d
            col = jnp.sum(jnp.where(is_col, a, 0.0), axis=2, keepdims=True)
            col = jnp.where(is_row, 0.0, col)
            scr[:] = jnp.where(is_row, row, a - col * row)
            return 0

        jax.lax.fori_loop(0, k, step, 0, unroll=False)
        x_ref[:] = scr[:]

    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((r_tile, k, kp), lambda g: (g, 0, 0))],
        out_specs=pl.BlockSpec((r_tile, k, kp), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * r_tile, k, kp),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((r_tile, k, kp), jnp.float32)],
        interpret=interpret,
    )


def gj_solve_multi(a, b, interpret: bool = False):
    """X = A⁻¹ B for a batch of SPD systems with M right-hand sides.

    a: [R, K, K] f32; b: [R, K, M] f32 → X: [R, K, M] f32. The building
    block of `schur_solve`'s recursion; cost is set by lane_pad(K+M), so
    up to 128−K RHS columns ride free next to a K-column system.
    """
    import jax.numpy as jnp

    r, k, _ = a.shape
    m = b.shape[2]
    kp = _lane_pad(k + m)
    # full-block output doubles the block traffic vs the single-RHS
    # kernel: halve the per-block budget to stay inside scoped VMEM
    r_tile = _row_tile(k * kp * 4, budget=6 * 1024 * 1024)
    r_pad = -(-r // r_tile) * r_tile
    aug = jnp.concatenate(
        [a.astype(jnp.float32), b.astype(jnp.float32)], axis=-1)
    aug = jnp.pad(aug, ((0, r_pad - r), (0, 0), (0, kp - (k + m))))
    out = _build_solver_aug_multi(k, kp, r_tile, r_pad // r_tile,
                                  interpret)(aug)
    return out[:r, :, k:k + m]


def schur_solve(a, b, interpret: bool = False):
    """x = A⁻¹ b via recursive Schur complements: the elimination work
    becomes [R, K/2, K/2] batched MXU matmuls plus multi-RHS GJ kernels
    at order ≤ `_SCHUR_BASE`.

    Round-3 finding: the elementwise GJ kernel is VPU-throughput-bound
    (docs/performance.md layout A/B), so the only way to move the solve
    is onto the MXU — batched matmuls measured 0.63 TFLOP/s at h=32 and
    2.26 at h=64 vs the kernel's effective 0.35. For SPD A the split
    pivots are SPD (leading principal blocks and their Schur
    complements), so no pivoting is needed at any level — the same
    assumption the base kernel makes.

    a: [R, K, K] f32 SPD; b: [R, K] or [R, K, M] f32.
    """
    import jax.numpy as jnp

    single = b.ndim == 2
    if single:
        b = b[..., None]
    x = _schur_rec(a, b, interpret)
    return x[..., 0] if single else x


def _schur_rec(a, b, interpret: bool):
    import jax
    import jax.numpy as jnp

    k = a.shape[1]
    if k <= _SCHUR_BASE or k % 2:
        return gj_solve_multi(a, b, interpret)
    h = k // 2

    def mm(x, y):
        # HIGHEST: the default TPU matmul precision multiplies in bf16,
        # which costs ~3 decimal digits on the Schur updates (measured
        # rel 1.8e-3 vs 3e-7) — elimination must stay full f32
        return jnp.einsum("rij,rjk->rik", x, y,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    a11 = a[:, :h, :h]
    a12 = a[:, :h, h:]
    a21 = a[:, h:, :h]
    a22 = a[:, h:, h:]
    b1, b2 = b[:, :h], b[:, h:]
    # one base call solves A11 against [A12 | B1] together (the RHS
    # columns ride in the same lane-padded block)
    w = _schur_rec(a11, jnp.concatenate([a12, b1], axis=2), interpret)
    w12, w1b = w[:, :, :h], w[:, :, h:]
    s = a22 - mm(a21, w12)  # SPD Schur complement
    y2 = _schur_rec(s, b2 - mm(a21, w1b), interpret)
    y1 = w1b - mm(w12, y2)
    return jnp.concatenate([y1, y2], axis=1)


def _solve_lanes(a, b, interpret: bool):
    import jax.numpy as jnp

    r, k, _ = a.shape
    kr = _sub_pad(k)
    # the batch goes last: [K, K, R] and [K, R] (which of A's two index
    # orders leads does not matter, A is symmetric). Written as a 2-D
    # transpose between reshapes so that XLA keeps it one copy AFTER the
    # caller's regularisation (fused into the Gram's product); as
    # `transpose(1, 2, 0)` it moved the layout change up to the Gram's
    # output and the regularisation became a second pass.
    a_t = a.astype(jnp.float32).reshape(r, k * k).T.reshape(k, k, r)
    b_t = b.astype(jnp.float32).T
    if kr != k:  # order padded to whole sublane tiles with zeros
        a_t = jnp.pad(a_t, ((0, kr - k), (0, kr - k), (0, 0)))
        b_t = jnp.pad(b_t, ((0, kr - k), (0, 0)))
    x_t = _build_solver_lanes(k, r, interpret)(a_t, b_t)
    return x_t[:k].T


def layout_for(k: int) -> str:
    """The kernel layout `gj_solve` builds at order k: "lanes" (one system
    a lane) wherever the kernel's three blocks fit the chip's VMEM, which
    is every order `gj_applicable` admits; "schur" (recursive Schur over
    MXU matmuls) above. `_lanes_vmem_bytes` has the chip's readings."""
    return "lanes" if _lanes_vmem_bytes(k) <= _VMEM_BYTES else "schur"


def gj_solve(a, b, interpret: bool = False):
    """Solve x = A⁻¹ b for a batch of SPD systems.

    a: [R, K, K] f32 — SPD, hence symmetric (λ-regularized normal
       equations; the lanes layout's column elimination relies on the
       symmetry); all-zero systems (bucket padding rows) yield x = 0.
    b: [R, K] f32
    returns x: [R, K] f32. The layout built (`layout_for`) is counted in
    `als_solve_calls_total{layout}`.
    """
    layout = layout_for(a.shape[1])
    SOLVE_CALLS.labels(layout=layout).inc()
    if layout == "schur":
        return schur_solve(a, b, interpret)
    return _solve_lanes(a, b, interpret)


def _lanes_vmem_bytes(k: int) -> int:
    """VMEM the lanes kernel asks for at order k, which `layout_for` holds
    against the chip's: two pipelined input blocks and the working copy,
    [K+1, K, 128] f32 each, and 8 MiB for b, x, the factors and the
    compiler (16 MiB at least, which is K 64's; 32 MiB at K 128, 104 at
    K 256, 123 at K 280, the last order that fits 128 MiB).

    That is the whole rule because lanes won wherever it fits (my chip
    runs, PR 31; kernels and the XLA ops round them, the table is in
    docs/performance.md): 40.1 ms against schur's 135.6 at
    [31248, 128, 128], every batch of bucket height from order 64 to 256
    (1.58x there), a fold's 8 rows up to order 128 (0.119 against 0.140
    ms); schur is ahead only on 8 rows from order 160 up, by 0.01-0.66
    ms.

    (Down here, below `gj_solve`: a kernel's call sites ride in its
    Mosaic payload and so in the compile-cache key of every program that
    holds it. Lines added above `gj_solve` cost every checkout one
    compile of its train loops.)"""
    kr = _sub_pad(k)
    block = (kr + 1) * kr * _LANES * 4
    return max(16, 3 * block // 2**20 + 8) * 2**20

"""Pallas TPU kernels: Mamba-2's scan in its chunked matrix form
(`ops/ssd.py`), a chunk at a time with the heads' states on the chip.

`ops/ssd.py`'s docstring has the three sums (`Y_intra`, `Y_inter`,
`S_0 -> S_Q`), the masks and the stated precision; nothing of that
changes here. What changes is where a chunk's intermediates live. As
plain `jax.numpy` the [chunks, heads, Q, Q] decay tensors went through
HBM five times a pass (difference, mask, exponential, product with
C B^T, cast: 0.5 GB each at Granite's chunk of 256) and their cotangents
on top. Here a grid step is one chunk of a block of heads of one B/C
group: it forms C B^T [Q, Q] and the pair mask once, then for each head
the decay matrix exp(La_i - La_j) and its masked product, all in VMEM,
and carries the heads' states in scratch along the innermost, sequential
grid axis (the chunks). It writes y, the skip d x already in it, and,
where a backward pass will follow, the states the chunk started from.

Heads in pairs. A head is P = 64 channels, half a lane tile, so two
heads stand side by side in every [Q, 128] tile of the flat x
[B, L, H P] and their states one above the other in a [128, N] tile.
The read-out C S^T and the state's build (to_end u)^T B are then one
full-width product a pair; the masked product is taken of each head's
[Q, Q] matrix with the pair's u, and each head keeps its own half of
the lanes. A grid step holds `heads_a_step` heads (8, 4 or 2, all of one
group): B's and C's block index is the group of the head block, g(h) =
h // (H / G), so one group and eight run the same body.

What a head needs as a column over the chunk's tokens (La_i, dt_i) comes
as one [Q, 2 H] tile of (La | dt) for all heads, from which a head's
column is a lane select and a lane sum; what it needs as a row (La_j)
and the chunk's count of first tokens come as [1, Q] rows. La, the
running sum of dt a inside a chunk, is formed in XLA round the kernel,
so that its reverse sum in the backward pass stays there too.

Backward (`_backward_body`): the same grid with the chunk axis reversed
and dS in scratch. A chunk's decay matrices are recomputed from La and
r, the incoming states are read back, and the three sums are
differentiated by hand. The cotangent of C B^T is summed over the
block's heads in float32 before its two products, as autodiff of one
group's shared C B^T does; dB and dC leave as one partial a head block
and are summed over a group's blocks in XLA; dLa leaves in two parts, a
column part with ddt in one [Q, 128] tile a block and a row part; dx
holds the skip's d dy, and the skip's own dd = sum dy x stays XLA's.

Precision is `ops/ssd.py`'s: dt a, its sums, the exponentials, the masks
and the state float32; `dtype` operands with float32 accumulation in the
four products and in their transposes in the backward pass. A masked
pair's exponent is minus infinity before the exponential.

What a first call pays: Pallas traces a kernel's function anew in every
`pallas_call`; the bodies are `jax.jit(..., inline=True)` over the refs
under `functools.lru_cache`, so each is traced once a process whatever
the number of layers (`pallas_kda`, PERF.md PR 29 and PR 34). And every
`pallas_call` site lowers its body to Mosaic anew: the two kernels of a
shape are themselves `jax.jit`s under `lru_cache` (`_forward_call`,
`_backward_call`), which a program lowers once for all its layers.
"""

from __future__ import annotations

import functools

from predictionio_tpu.ops.pallas_kda import (_LANES, _MAX_STATE_BYTES,
                                             _VMEM_BYTES)

WIDTH = _LANES // 2     # channels a head: two heads a lane tile
MAX_CHUNK = 256         # tokens a grid step: [Q, Q] float32 is 64 vregs
_HEADS = (8, 4, 2)      # heads a grid step, the most that divide a group


def heads_a_step(h: int, g: int) -> int:
    """Heads that share a grid step: whole pairs, all of one B/C group.
    Eight where a group has them: C B^T and the pair mask are formed
    once a step and dB, dC leave once a step, so a step of two heads
    forms them four times as often and writes four times the partials.
    0 where no such block exists. (`pallas_kda._heads_a_step` stops at
    two: its body is the larger and is lowered at every call site.)"""
    if g < 1 or h % g:
        return 0
    return next((s for s in _HEADS if (h // g) % s == 0), 0)


def applicable(chunk: int, p: int, n: int, h: int, g: int) -> bool:
    """Whether the kernels take a scan of this shape: chunks of whole
    lane tiles up to 256 tokens, heads of 64 channels, a state width of
    whole lane tiles, G groups dividing H heads into whole pairs, and a
    block's states leaving VMEM room."""
    hs = heads_a_step(h, g)
    return (chunk % _LANES == 0 and 0 < chunk <= MAX_CHUNK and p == WIDTH
            and n > 0 and n % _LANES == 0 and hs > 0
            and hs * p * n * 4 <= _MAX_STATE_BYTES)


def _chunk_math(q: int, heads: int, dtype):
    """The per-chunk mathematics on values, shared by both bodies: q
    tokens a chunk, `heads` heads in the (La | dt) tile."""
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    nn, nt, tn = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))  # a b, a b^T, a^T b

    def dot(a, b, dims=nn):  # `dtype` operands, float32 accumulation
        return lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (dims, ((), ())), preferred_element_type=f32)

    def iota(shape, dim):
        return lax.broadcasted_iota(jnp.int32, shape, dim)

    def where(m, a, b):
        shape = jnp.broadcast_shapes(m.shape, jnp.shape(a), jnp.shape(b))
        return lax.select(*(jnp.broadcast_to(v, shape) for v in (m, a, b)))

    def left():   # the first head's lanes of a pair's tile
        return iota((1, _LANES), 1) < WIDTH

    def upper():  # and its rows of the pair's states
        return iota((_LANES, 1), 0) < WIDTH

    def halves(a, b):
        """Two columns [R, 1] side by side: [R, 128], a over the first
        head's lanes."""
        return where(left(), a, b)

    def half_sums(z):
        """The lane sums [R, 1] of each head's half of z [R, 128]."""
        return (jnp.sum(where(left(), z, 0.0), axis=1, keepdims=True),
                jnp.sum(where(left(), 0.0, z), axis=1, keepdims=True))

    def last(row):  # [1, q] -> [1, 1]
        return jnp.sum(where(iota((1, q), 1) == q - 1, row, 0.0), axis=1,
                       keepdims=True)

    def masks(r_row):
        """From the chunk's count of first tokens r [1, q]: the [q, q]
        pair mask and the columns as 0/1 float32."""
        t, s = iota((q, q), 0), iota((q, q), 1)
        r_col = jnp.sum(where(t == s, r_row, 0.0), axis=1, keepdims=True)
        r_last = jnp.max(r_row, axis=1, keepdims=True)          # [1, 1]
        return {"pair": (r_col == jnp.broadcast_to(r_row, (q, q)))
                & (s <= t),
                "sees": (r_col == 0.0).astype(f32),
                "in_last": (r_col == r_last).astype(f32),
                "keeps": (r_last == 0.0).astype(f32)}

    def forward(x2, cols, la_rows, head, m, cb, b, c, d2, s2):
        """Everything of a chunk of a pair of heads: x2 [q, 128], the
        (La | dt) tile cols [q, 2 heads], the pair's La rows (two
        [1, q]), `head` the pair's first head, the masks, cb = C B^T
        [q, q], b and c [q, N], the skip's d a channel d2 [1, 128], the
        incoming states s2 [128, N]."""
        lane = iota(cols.shape, 1)

        def column(at):
            return jnp.sum(where(lane == at, cols, 0.0), axis=1,
                           keepdims=True)

        la_c = [column(head + k) for k in (0, 1)]
        dt2 = halves(column(heads + head), column(heads + head + 1))
        la2 = halves(*la_c)
        u2 = dt2 * x2
        ub = u2.astype(dtype)
        decay = [jnp.exp(where(m["pair"], la_c[k] - la_rows[k], -jnp.inf))
                 for k in (0, 1)]
        masked = [(cb * d).astype(dtype) for d in decay]
        reach2 = m["sees"] * jnp.exp(la2)
        read2 = dot(c, s2, nt)                                  # [q, 128]
        y2 = where(left(), dot(masked[0], ub), dot(masked[1], ub)) \
            + reach2 * read2 + d2 * x2
        end = [last(la_rows[k]) for k in (0, 1)]                # [1, 1]
        te2 = m["in_last"] * jnp.exp(halves(*end) - la2)
        v2 = te2 * u2
        carried = [m["keeps"] * jnp.exp(e) for e in end]
        s2_new = where(upper(), *carried) * s2 + dot(v2, b, tn)   # [128, N]
        return {"dt2": dt2, "u2": u2, "ub": ub, "decay": decay,
                "masked": masked, "reach2": reach2, "read2": read2,
                "y2": y2, "te2": te2, "v2": v2, "carried": carried,
                "s2_new": s2_new}

    def backward(x2, cols, la_rows, head, m, cb, b, c, d2, s2, dy2, ds2):
        """From dy2 [q, 128] and the later chunk's ds2 [128, N]: dx2
        (the skip's d dy in it), each head's (dLa column, ddt column,
        dLa row), the pair's part of d(C B^T), dB and dC, and ds2 for
        the chunk before."""
        f = forward(x2, cols, la_rows, head, m, cb, b, c, d2, s2)
        # S' = carried S + v^T B
        ds0 = where(upper(), *f["carried"]) * ds2
        held = jnp.sum(s2 * ds2, axis=1, keepdims=True)         # [128, 1]
        dcarried = (
            jnp.sum(where(upper(), held, 0.0), axis=0, keepdims=True),
            jnp.sum(where(upper(), 0.0, held), axis=0, keepdims=True))
        dv2 = dot(b, ds2, nt)                                   # [q, 128]
        db = dot(f["v2"], ds2)                                  # [q, N]
        du2 = f["te2"] * dv2
        g_te = dv2 * f["v2"]            # dte te, before its lane sums
        # y = intra + reach (C S^T)
        dr2 = f["reach2"] * dy2
        g_la = dy2 * (f["reach2"] * f["read2"]) - g_te
        dc = dot(dr2, s2)                                       # [q, N]
        ds0 = ds0 + dot(dr2, c, tn)
        # intra = (cb decay) u, a head at a time
        dyb = dy2.astype(dtype)
        dcb, du_heads, sums = 0.0, [], []
        for k, mine in enumerate((left(), ~left())):
            dm = dot(where(mine, dy2, 0.0), f["ub"], nt)        # [q, q]
            du_heads.append(dot(f["masked"][k], dyb, tn))       # [q, 128]
            e = dm * f["decay"][k]
            dcb = dcb + e
            de = e * cb
            sums.append((jnp.sum(de, axis=1, keepdims=True),
                         jnp.sum(de, axis=0, keepdims=True)))
        du2 = du2 + where(left(), *du_heads)
        dx2 = du2 * f["dt2"] + d2 * dy2
        la_cols, dt_cols = half_sums(g_la), half_sums(du2 * x2)
        at_end = iota((1, q), 1) == q - 1
        per_head = []
        # dLa at a chunk's end: the tokens' dte te, summed down the chunk
        # before the lanes, and what the carried state held
        te_ends = half_sums(jnp.sum(g_te, axis=0, keepdims=True))
        for k in (0, 1):
            dend = te_ends[k] + f["carried"][k] * dcarried[k]   # [1, 1]
            per_head.append((sums[k][0] + la_cols[k], dt_cols[k],
                             where(at_end, dend, 0.0) - sums[k][1]))
        return dx2, per_head, dcb, db, dc, ds0

    return masks, forward, backward, dot, where, iota


def _pairs(hs: int):
    """(index, slice) for each pair of heads of a grid step: the pair's
    128 lanes of a wide tile are also its 128 rows of the states."""
    from jax.experimental import pallas as pl

    return [(k, pl.ds(k * _LANES, _LANES)) for k in range(hs // 2)]


@functools.lru_cache(maxsize=16)
def _forward_body(q: int, hs: int, heads: int, dtype: str, save: bool):
    """Kernel body of the forward pass, `hs` heads a grid step: refs x
    [1, q, hs P], cols [1, q, 2 heads] (La | dt), rows [hs, 1, 2, q] (La;
    the count of first tokens), b, c [1, q, N], d [1, hs P] (the skip's,
    a channel) -> y [1, q, hs P] and, with `save`, the states each chunk
    started from, [1, 1, hs P, N]; the states in scratch [hs P, N]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype)
    masks, forward, _, dot, _, _ = _chunk_math(q, heads, dtype)

    def body(x_ref, cols_ref, rows_ref, b_ref, c_ref, d_ref, y_ref, *rest):
        st_ref = rest[-1]

        @pl.when(pl.program_id(1) == 0)
        def _():
            st_ref[...] = jnp.zeros(st_ref.shape, jnp.float32)

        first = (pl.program_id(0) % (heads // hs)) * hs
        m = masks(rows_ref[0, 0, 1:2, :])
        b, c, cols = b_ref[0], c_ref[0], cols_ref[0]
        cb = dot(c, b, ((1,), (1,)))
        for k, at in _pairs(hs):
            s2 = st_ref[at, :]
            if save:
                rest[0][0, 0, at, :] = s2
            f = forward(x_ref[0, :, at], cols,
                        [rows_ref[2 * k + j, 0, 0:1, :] for j in (0, 1)],
                        first + 2 * k, m, cb, b, c, d_ref[:, at], s2)
            y_ref[0, :, at] = f["y2"]
            st_ref[at, :] = f["s2_new"]

    # inlined into the kernel being traced: never dispatched or compiled
    # by itself, so there is nothing to meter
    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


@functools.lru_cache(maxsize=16)
def _backward_body(q: int, hs: int, heads: int, dtype: str):
    """Kernel body of the backward pass over the reversed chunk axis:
    refs x, cols, rows, b, c, d as forward, s0 [1, 1, hs P, N], dy
    [1, q, hs P] -> dx [1, q, hs P], dcols [1, q, 128] (lane j: dLa's
    column part of the block's head j; lane hs + j: its ddt), drows
    [hs, 1, 1, q] (dLa's row part), db, dc [1, q, N] (the block's
    partial); dS in scratch [hs P, N]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype)
    masks, _, backward, dot, where, iota = _chunk_math(q, heads, dtype)

    def body(x_ref, cols_ref, rows_ref, b_ref, c_ref, d_ref, s0_ref, dy_ref,
             dx_ref, dcols_ref, drows_ref, db_ref, dc_ref, dst_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            dst_ref[...] = jnp.zeros(dst_ref.shape, jnp.float32)

        first = (pl.program_id(0) % (heads // hs)) * hs
        m = masks(rows_ref[0, 0, 1:2, :])
        b, c, cols = b_ref[0], c_ref[0], cols_ref[0]
        cb = dot(c, b, ((1,), (1,)))
        lane = iota((1, _LANES), 1)
        dcb, db, dc = 0.0, 0.0, 0.0
        dcols = jnp.zeros((q, _LANES), jnp.float32)
        for k, at in _pairs(hs):
            dx2, per_head, dcb_k, db_k, dc_k, ds0 = backward(
                x_ref[0, :, at], cols,
                [rows_ref[2 * k + j, 0, 0:1, :] for j in (0, 1)],
                first + 2 * k, m, cb, b, c, d_ref[:, at],
                s0_ref[0, 0, at, :], dy_ref[0, :, at], dst_ref[at, :])
            dx_ref[0, :, at] = dx2
            dst_ref[at, :] = ds0
            dcb, db, dc = dcb + dcb_k, db + db_k, dc + dc_k
            for j, (dla_col, ddt_col, dla_row) in enumerate(per_head):
                dcols = where(lane == 2 * k + j, dla_col, dcols)
                dcols = where(lane == hs + 2 * k + j, ddt_col, dcols)
                drows_ref[2 * k + j, 0] = dla_row
        dcols_ref[0] = dcols
        dc_ref[0] = dc + dot(dcb, b)
        db_ref[0] = db + dot(dcb, c, ((0,), (0,)))

    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


def _pallas_call(body, name, bsz, l, h, g, q, n_state, reverse, ins, outs,
                 out_shape, interpret):
    """`pallas_call` of a body over the grid (B H / heads a step, chunks)
    of flat x [B, L, H P]; `ins` and `outs` name each operand's spec."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, hs = l // q, heads_a_step(h, g)
    blocks, wide = h // hs, hs * WIDTH
    at = (lambda j: n - 1 - j) if reverse else (lambda j: j)
    by_block = lambda *tile: pl.BlockSpec(  # noqa: E731
        (1, q) + tile, lambda i, j: (i // blocks, at(j), i % blocks))
    per_head = lambda rows: pl.BlockSpec(  # noqa: E731
        (hs, 1, rows, q), lambda i, j: (i, at(j), 0, 0))
    spec = {
        "wide": by_block(wide), "part": by_block(n_state),
        "dcols": by_block(_LANES),
        "cols": pl.BlockSpec((1, q, 2 * h),
                             lambda i, j: (i // blocks, at(j), 0)),
        "rows": per_head(2), "drows": per_head(1),
        "skip": pl.BlockSpec((1, wide), lambda i, j: (0, i % blocks)),
        "group": pl.BlockSpec(
            (1, q, n_state),
            lambda i, j: (i // blocks, at(j), (i % blocks) * hs // (h // g))),
        "state": pl.BlockSpec(
            (1, 1, wide, n_state),
            lambda i, j: (i // blocks, at(j), i % blocks, 0)),
    }
    return pl.pallas_call(
        body(q, hs, h), grid=(bsz * blocks, n),
        in_specs=[spec[s] for s in ins], out_specs=[spec[s] for s in outs],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((wide, n_state), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name=name, interpret=interpret)


@functools.lru_cache(maxsize=32)
def _forward_call(bsz: int, l: int, h: int, g: int, q: int, n_state: int,
                  dtype: str, save: bool, interpret: bool):
    """The forward kernel of one shape as a jitted function of (x, cols,
    rows, b, c, d). A jit, not a bare `pallas_call`: a step's nine layers
    then call one lowered function, where every `pallas_call` site lowers
    its body to Mosaic anew in every process (0.2 s a site of set-up on
    the chip's host; PERF.md, PR 49)."""
    import jax
    import jax.numpy as jnp

    wide = h * WIDTH
    call = _pallas_call(
        functools.partial(_forward_body, dtype=dtype, save=save),
        "ssd_chunks_fwd", bsz, l, h, g, q, n_state, False,
        ("wide", "cols", "rows", "group", "group", "skip"),
        ("wide", "state") if save else ("wide",),
        [jax.ShapeDtypeStruct((bsz, l, wide), jnp.float32)]
        + ([jax.ShapeDtypeStruct((bsz, l // q, wide, n_state), jnp.float32)]
           if save else []),
        interpret)

    def ssd_chunks_fwd(x, cols, rows, b, c, d):
        return call(x, cols, rows, b.astype(dtype), c.astype(dtype),
                    jnp.repeat(d, WIDTH)[None])

    # traced into the caller's program: never dispatched by itself there
    return jax.jit(ssd_chunks_fwd)  # pio-lint: disable=coverage-jit-metering


@functools.lru_cache(maxsize=32)
def _backward_call(bsz: int, l: int, h: int, g: int, q: int, n_state: int,
                   dtype: str, interpret: bool):
    """The backward kernel of one shape as a jitted function of (x, cols,
    rows, b, c, d, s0, dy), as `_forward_call`."""
    import jax
    import jax.numpy as jnp

    blocks = h // heads_a_step(h, g)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    part = shape(bsz, l, blocks * n_state)
    call = _pallas_call(
        functools.partial(_backward_body, dtype=dtype), "ssd_chunks_bwd",
        bsz, l, h, g, q, n_state, True,
        ("wide", "cols", "rows", "group", "group", "skip", "state", "wide"),
        ("wide", "dcols", "drows", "part", "part"),
        [shape(bsz, l, h * WIDTH), shape(bsz, l, blocks * _LANES),
         shape(bsz * h, l // q, 1, q), part, part],
        interpret)

    def ssd_chunks_bwd(x, cols, rows, b, c, d, s0, dy):
        return call(x, cols, rows, b.astype(dtype), c.astype(dtype),
                    jnp.repeat(d, WIDTH)[None], s0, dy)

    return jax.jit(ssd_chunks_bwd)  # pio-lint: disable=coverage-jit-metering


def _call_forward(x, cols, rows, b, c, d, h, g, dtype, save, interpret):
    bsz, l, _ = x.shape
    return _forward_call(bsz, l, h, g, rows.shape[3], b.shape[2] // g, dtype,
                         save, interpret)(x, cols, rows, b, c, d)


def _call_backward(x, cols, rows, b, c, d, s0, dy, h, g, dtype, interpret):
    bsz, l, _ = x.shape
    return _backward_call(bsz, l, h, g, rows.shape[3], b.shape[2] // g,
                          dtype, interpret)(x, cols, rows, b, c, d, s0, dy)


@functools.lru_cache(maxsize=16)
def _scan(h: int, g: int, dtype: str, scope: str, interpret: bool):
    """The differentiable scan on flat arrays: x [B, L, H P], cols
    [B, L, 2 H] (La | dt), rows [B H, chunks, 2, Q] (La; the count of
    first tokens), b, c [B, L, G N], d [H] -> y [B, L, H P]."""
    import jax

    @jax.custom_vjp
    def scan(x, cols, rows, b, c, d):
        return _call_forward(x, cols, rows, b, c, d, h, g, dtype, False,
                             interpret)[0]

    def fwd(x, cols, rows, b, c, d):
        y, s0 = _call_forward(x, cols, rows, b, c, d, h, g, dtype, True,
                              interpret)
        return y, (x, cols, rows, b, c, d, s0)

    def bwd(res, dy):
        # a backward pass is traced outside the caller's scopes: it opens
        # the one it was given, so that a trace can tell whose time it is
        import jax.numpy as jnp

        x, cols, rows, b, c, d, s0 = res
        with jax.named_scope(scope):
            dx, dcols, drows, db, dc = _call_backward(
                x, cols, rows, b, c, d, s0, dy, h, g, dtype, interpret)
            bsz, l, _ = x.shape
            hs = heads_a_step(h, g)
            # a block's tile holds its heads' dLa columns, then their ddt
            tiles = dcols.reshape(bsz, l, h // hs, _LANES)
            dcols = jnp.concatenate(
                [tiles[..., :hs].reshape(bsz, l, h),
                 tiles[..., hs:2 * hs].reshape(bsz, l, h)], axis=-1)
            drows = jnp.concatenate([drows, jnp.zeros_like(drows)], axis=2)
            # the skip's d stays XLA's sum: taken in the kernel it is 1.3
            # ms a layer less and the compiler's plan for Granite's step
            # 0.48 GB more (14.72 for 14.24; PERF.md, PR 49)
            dd = jnp.sum((dy * x).reshape(bsz, l, h, -1), axis=(0, 1, 3))
            # a group's blocks' partials
            of_group = lambda v: v.reshape(  # noqa: E731
                bsz, l, g, h // hs // g, -1).sum(axis=3).reshape(b.shape)
        return dx, dcols, drows, of_group(db), of_group(dc), dd

    scan.defvjp(fwd, bwd)
    return scan


def ssd_chunks(x, dt, a, b, c, d, starts, chunk: int, dtype, scope: str,
               interpret: bool = False):
    """`ops.ssd.ssd_scan`'s three sums and the skip d x by the kernels.
    x [B, L, H, P]; dt [B, L, H]; a [H]; b, c [B, L, N] or [B, L, G, N];
    d [H]; starts [B, L] bool (`history_starts`); an `applicable` shape. Returns
    y [B, L, H, P] float32. A tail that no chunk holds is padded, every
    padded token a history of its own with x = dt = 0. The backward
    pass's ops are traced under `scope`; `interpret` runs the kernels in
    interpreter mode, on any backend (tests)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    bsz, l, h, p = x.shape
    g = 1 if b.ndim == 3 else b.shape[2]
    n = -(-l // chunk)
    short, full = n * chunk - l, n * chunk
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    if short:
        pad = lambda v, **kw: jnp.pad(  # noqa: E731
            v, ((0, 0), (0, short)) + ((0, 0),) * (v.ndim - 2), **kw)
        x, dt, b, c = (pad(v) for v in (x, dt, b, c))
        starts = pad(starts, constant_values=True)
    # the running sum of dt a inside a chunk                [B, n, Q, H]
    la = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, n, chunk, h), axis=2)
    r = jnp.cumsum(starts.reshape(bsz, 1, n, chunk).astype(f32), axis=-1)
    cols = jnp.concatenate([la.reshape(bsz, full, h), dt], axis=-1)
    rows = jnp.stack([la.transpose(0, 3, 1, 2),
                      jnp.broadcast_to(r, (bsz, h, n, chunk))],
                     axis=3).reshape(bsz * h, n, 2, chunk)
    flat = lambda v: v.reshape(bsz, full, -1)  # noqa: E731
    y = _scan(h, g, jnp.dtype(dtype).name, scope, bool(interpret))(
        flat(x), cols, rows, flat(b), flat(c), d.astype(f32))
    return y.reshape(bsz, full, h, p)[:, :l]

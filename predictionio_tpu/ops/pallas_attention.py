"""Pallas TPU kernels: causal attention held inside packed histories
(`ops/attention.py::segment_attention` above one block), a tile pair at a
time with the scores and the running accumulators on the chip.

`ops/attention.py` has the mathematics and the stated precision; nothing
of that changes here. What changes is where a pair's intermediates live.
As plain `jax.numpy` a block pair's [H, 512, 512] scores, masks and
exponentials went through HBM, and the backward pass rewrote whole
[H, L, d] accumulators once a pair (the four largest device operations of
`joyai.fit8_pack8k`'s window were those copies; PERF.md, PR 38). Here a
grid step is one query tile of a few heads of one sequence. The heads'
keys and values stand in VMEM whole (a block per sequence and heads:
fetched once, not once a pair), and the step walks the key tiles that can hold a
visible key, from the first one (scalar prefetch, `first_key_blocks` at
the kernel's own tile) to the query tile's own, in a loop whose bounds are
data: a skipped pair costs neither a DMA nor an MXU push.

Which pairs count. Histories lie one after another and `positions` counts
each token from its history's start, which is what `first_key_blocks`
rests on already; so the keys a query at t sees are those from
`first[t] = t - positions[t]` (with a window the later of that and
t - window + 1) to t, and the mask is two comparisons against one vector
of the query side. A history never reads another: the mask is a select,
not a small number.

Both kernels are key-major: s^T = k q^T [tk, tq] in float32, so that
what belongs to a query (the running maximum and denominator, the
log-sum-exp, delta, `first`) is a row of tq / 128 vregs that broadcasts
along sublanes with no relayout, where a [tq, 1] column is tq / 8 vregs,
as many as the score tile's own at 128 keys.

Forward (`_forward_body`): the mask, the running maximum, denominator
and the output accumulator o^T [dv, tq] as the loop's carry,
`ops/attention.py::_online_fold`'s recurrence; the output (turned once a
query tile) and the log-sum-exp are written once a query tile.

Backward (`_backward_body`), hand-written from the saved o and
log-sum-exp. dq is the loop's carry and written once a query tile; dk
and dv are accumulated in float32 VMEM scratch that holds the heads'
whole length, over the query tiles of the grid's innermost, sequential
axis, and written once a head.

A grid step holds a few heads (`HEADS`): they walk the same key tiles
under the same mask, made once a pair, and are independent chains of
products, exponentials and reductions for the scheduler to interleave.

Precision is `ops/attention.py`'s: operands in the dtype they arrive in,
float32 accumulation; scores, softmax, log-sum-exp and delta float32; p
and ds cast to the operands' dtype for their products where the
`jax.numpy` path casts them; a true division, no approximated exponent.

What a first call pays: JoyAI's train step holds nine of these kernels
(three call sites, the scanned layers one x forward, the recomputation,
backward) and Pallas traces a kernel's function anew in every
`pallas_call`. The bodies are `jax.jit(..., inline=True)` over the refs,
so each is traced once a process (`pallas_solve._lanes_kernel`, PR 29).
"""

from __future__ import annotations

import functools

from predictionio_tpu.telemetry.registry import REGISTRY

_PATHS = ("kernel", "jnp")
# counted while a program is traced: the attentions a process built into
# its programs, by the path `ops.attention.segment_attention` chose
SEGMENT_CALLS = REGISTRY.counter(
    "encoder_segment_attention_calls_total",
    "segment_attention calls above one block traced into a program, by "
    "the path built for them (kernel: the Pallas kernels of "
    "ops/pallas_attention.py | jnp: plain jax.numpy)",
    labelnames=("path",))

# (query tile, key tile) of the forward and of the backward kernel: the
# kernels' own, whatever `block` the caller names (docs/performance.md,
# "Segment attention as kernels")
TILES = {"fwd": (256, 256), "bwd": (256, 256)}
# heads that share a grid step, at most: they walk the same key tiles under
# the same mask, independent chains for the scheduler to interleave
HEADS = {"fwd": 4, "bwd": 2}
_NEG_INF = -1e30  # `ops/attention.py`'s
_LANES = 128
_VMEM_BYTES = 96 * 2**20  # of a v5e's 128 MiB


def _padded(rows: int, width: int, itemsize: int) -> int:
    """Bytes of a [rows, width] VMEM buffer: lanes in whole tiles."""
    return rows * -(-width // _LANES) * _LANES * itemsize


def vmem_bytes(l: int, dk: int, dv: int, itemsize: int) -> int:
    """What the kernels ask of VMEM at most, a grid step of the most
    heads: forward the heads' keys and values double-buffered; backward
    those, their gradients double-buffered and the two float32
    accumulators; either way a query tile's operands and room for the
    pairs' [tk, tq] intermediates."""
    whole = _padded(l, dk, itemsize) + _padded(l, dv, itemsize)

    def tiles(which):
        tq, tk = TILES[which]
        return (4 * (_padded(tq, dk, itemsize) + _padded(tq, dv, 4))
                + 6 * tq * tk * 4)

    return max(HEADS["fwd"] * (2 * whole + tiles("fwd")),
               HEADS["bwd"] * (4 * whole + _padded(l, dk, 4)
                               + _padded(l, dv, 4) + tiles("bwd")))


def applicable(l: int, dk: int, dv: int, itemsize: int) -> bool:
    """Whether the kernels take an attention of this shape: the sequence
    a whole number of both kernels' tiles, head widths the layouts take
    (the keys' a multiple of 64, the values' whole lane tiles: 192 | 128
    and 64 | 128 both), and VMEM for a head's whole length."""
    whole = all(l % t == 0 for tile in TILES.values() for t in tile)
    return (whole and dk % 64 == 0 and dv % _LANES == 0
            and itemsize in (2, 4)
            and vmem_bytes(l, dk, dv, itemsize) <= _VMEM_BYTES)


def _dot(a, b, dims):
    """a (x) b contracted over `dims`, float32 accumulation, operands as
    they are."""
    import jax.numpy as jnp
    from jax import lax

    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))  # a b, a b^T, a^T b


def _visible(keys, queries, first):
    """The pairs that count [tk, tq]: key index (a column) <= query index
    (a row: causal) and >= the query's first visible key (a row: its
    history's start, or the window's)."""
    return (keys <= queries) & (keys >= first)


def _walk(lo_ref, first_ref, tq, tk, pair, init):
    """The loop both bodies run for the query tile of this grid step:
    `pair(rows, visible, carry)` over its key tiles, from the first that
    can hold a visible key (`lo_ref`, SMEM) to the tile's own; `rows` the
    key tile's slice of a whole-length ref, `visible` its mask [tk, tq],
    made once for all heads of the step."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    b, i = pl.program_id(0), pl.program_id(2)
    first = first_ref[0]                                         # [1, tq]
    queries = i * tq + lax.broadcasted_iota(jnp.int32, (1, tq), 1)
    sublane = lax.broadcasted_iota(jnp.int32, (tk, 1), 0)

    def step(j, carry):
        rows = pl.ds(pl.multiple_of(j * tk, tk), tk)
        return pair(rows, _visible(sublane + j * tk, queries, first), carry)

    return lax.fori_loop(lo_ref[b, i], ((i + 1) * tq - 1) // tk + 1, step,
                         init)


@functools.lru_cache(maxsize=16)
def _forward_body(tq: int, tk: int, scale: float):
    """Kernel body of the forward pass over the grid (B, H / G, L / tq),
    key-major: s^T = k q^T [tk, tq], so the running maximum and
    denominator are rows [1, tq] and the accumulator o^T [dv, tq]; refs
    lo [B, L / tq] (SMEM: the first key tile of each query tile, which is
    `ops.attention.first_key_blocks` at the kernel's tile), q [1, G, tq,
    dk], k [1, G, L, dk], v [1, G, L, dv], first [1, 1, tq] -> o [1, G,
    tq, dv] float32, lse [1, G, 1, tq]. The G heads of a step walk the
    same key tiles under the same mask: G independent chains for the
    scheduler."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def body(lo_ref, q_ref, k_ref, v_ref, first_ref, o_ref, lse_ref):
        heads, dv = q_ref.shape[1], v_ref.shape[-1]

        def pair(rows, visible, carry):
            out = []
            for h, (acc, m, den) in enumerate(carry):
                kj, vj = k_ref[0, h, rows, :], v_ref[0, h, rows, :]
                s = _dot(kj, q_ref[0, h], _NT) * scale           # [tk, tq]
                s = jnp.where(visible, s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                den = den * alpha + jnp.sum(p, axis=0, keepdims=True)
                acc = acc * alpha + _dot(vj, p.astype(vj.dtype), _TN)
                out.append((acc, m_new, den))
            return tuple(out)

        done = _walk(
            lo_ref, first_ref, tq, tk, pair,
            ((jnp.zeros((dv, tq), f32), jnp.full((1, tq), _NEG_INF, f32),
              jnp.zeros((1, tq), f32)),) * heads)
        for h, (acc, m, den) in enumerate(done):
            o_ref[0, h] = (acc / den).T
            lse_ref[0, h] = m + jnp.log(den)

    # inlined into the kernel being traced: never dispatched or compiled
    # by itself, so there is nothing to meter
    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


@functools.lru_cache(maxsize=16)
def _backward_body(tq: int, tk: int, scale: float):
    """Kernel body of the backward pass over the grid (B, H / G, L / tq),
    the query tiles in turn: refs lo, q, k, v as forward, do [1, G, tq,
    dv] (the operands' dtype), lse, delta [1, G, 1, tq], first [1, 1, tq]
    -> dq [1, G, tq, dk], dk [1, G, L, dk], dv [1, G, L, dv] (written
    after the heads' last query tile); dk and dv in float32 scratch
    [G, L, d]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    def body(lo_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
             first_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc):
        i, heads = pl.program_id(2), q_ref.shape[1]

        @pl.when(i == 0)
        def _():
            dk_acc[...] = jnp.zeros(dk_acc.shape, f32)
            dv_acc[...] = jnp.zeros(dv_acc.shape, f32)

        def pair(rows, visible, dqs):
            out = []
            for h, dq in enumerate(dqs):
                q, do = q_ref[0, h], do_ref[0, h]
                kj, vj = k_ref[0, h, rows, :], v_ref[0, h, rows, :]
                s = _dot(kj, q, _NT) * scale                     # [tk, tq]
                p = jnp.where(visible, jnp.exp(s - lse_ref[0, h]), 0.0)
                dv_acc[h, rows, :] += _dot(p.astype(vj.dtype), do, _NN)
                dp = _dot(vj, do, _NT)
                ds = (p * (dp - delta_ref[0, h]) * scale).astype(q.dtype)
                dk_acc[h, rows, :] += _dot(ds, q, _NN)
                out.append(dq + _dot(ds, kj, _TN))
            return tuple(out)

        dqs = _walk(lo_ref, first_ref, tq, tk, pair,
                    (jnp.zeros(q_ref.shape[2:], f32),) * heads)
        for h, dq in enumerate(dqs):
            dq_ref[0, h] = dq.astype(dq_ref.dtype)

        @pl.when(i == pl.num_programs(2) - 1)
        def _():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


def _heads_a_step(h: int, which: str) -> int:
    """Heads that share a grid step: the largest divisor of the heads
    that is at most HEADS[which]."""
    return max(g for g in range(1, HEADS[which] + 1) if h % g == 0)


def _specs(g, tq, l, dk, dv):
    """Block specs over the grid (B, H / g, L / tq); the index maps take
    the prefetched list last."""
    from jax.experimental import pallas as pl

    tile = lambda d: pl.BlockSpec(  # noqa: E731
        (1, g, tq, d), lambda b, h, i, lo: (b, h, i, 0))
    whole = lambda d: pl.BlockSpec(  # noqa: E731
        (1, g, l, d), lambda b, h, i, lo: (b, h, 0, 0))
    return {
        "q": tile(dk), "o": tile(dv), "k": whole(dk), "v": whole(dv),
        # of a head: [B, H, 1, L]; of a sequence: [B, 1, L]
        "head_row": pl.BlockSpec((1, g, 1, tq),
                                 lambda b, h, i, lo: (b, h, 0, i)),
        "row": pl.BlockSpec((1, 1, tq), lambda b, h, i, lo: (b, 0, i)),
    }


def _pallas_call(body, which, q, v, first, ins, outs, out_shape, scratch,
                 order, interpret):
    """`pallas_call` of `body(tq, tk)` at the tile of `which` ("fwd" |
    "bwd") over the grid (B, H / G, L / tq); -> a function of the
    operands `ins` names, between the list of first key tiles and the
    row of first visible keys, which it makes from `first` [B, L]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, l, dk = q.shape
    dv = v.shape[-1]
    tq, tk = TILES[which]
    g = _heads_a_step(h, which)
    spec = _specs(g, tq, l, dk, dv)
    call = pl.pallas_call(
        body(tq, tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // g, l // tq),
            in_specs=[spec[x] for x in ins + ("row",)],
            out_specs=[spec[x] for x in outs],
            scratch_shapes=scratch(g)),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", order),
            vmem_limit_bytes=_VMEM_BYTES),
        name=f"segment_attention_{which}", interpret=interpret)
    # the first key tile of each query tile: where its first token's
    # first visible key lies (`first` never falls along a sequence)
    return lambda *operands: call(first[:, ::tq] // tk, *operands,
                                  first[:, None, :])


def _call_forward(q, k, v, first, scale, interpret):
    import jax
    import jax.numpy as jnp

    b, h, l, _ = q.shape
    return _pallas_call(
        functools.partial(_forward_body, scale=scale), "fwd", q, v, first,
        ("q", "k", "v"), ("o", "head_row"),
        [jax.ShapeDtypeStruct(v.shape, jnp.float32),
         jax.ShapeDtypeStruct((b, h, 1, l), jnp.float32)],
        lambda g: [], "parallel", interpret)(q, k, v)


def _call_backward(q, k, v, first, lse, delta, do, scale, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    l, dk, dv = q.shape[2], q.shape[3], v.shape[3]
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return _pallas_call(
        functools.partial(_backward_body, scale=scale), "bwd", q, v, first,
        ("q", "k", "v", "o", "head_row", "head_row"),
        ("q", "k", "v"), [like(q), like(k), like(v)],
        lambda g: [pltpu.VMEM((g, l, dk), jnp.float32),
                   pltpu.VMEM((g, l, dv), jnp.float32)],
        "arbitrary", interpret)(q, k, v, do, lse, delta)


@functools.lru_cache(maxsize=32)
def _attention(scale: float, scope: str, interpret: bool):
    """The differentiable attention: q, k [B, H, L, dk], v [B, H, L, dv],
    first [B, L] int32 -> o [B, H, L, dv] float32."""
    import jax

    @jax.custom_vjp
    def attention(q, k, v, first):
        return _call_forward(q, k, v, first, scale, interpret)[0]

    def fwd(q, k, v, first):
        o, lse = _call_forward(q, k, v, first, scale, interpret)
        return o, (q, k, v, first, o, lse)

    def bwd(res, do):
        # a backward pass is traced outside the caller's scopes: it opens
        # the one it was given, so that a trace can tell whose time it is
        import jax.numpy as jnp

        q, k, v, first, o, lse = res
        with jax.named_scope(scope):
            delta = jnp.sum(do * o, axis=-1)[:, :, None, :]
            dq, dk, dv = _call_backward(q, k, v, first, lse, delta,
                                        do.astype(v.dtype), scale, interpret)
        return dq, dk, dv, None

    attention.defvjp(fwd, bwd)
    return attention


def first_visible(positions, window=None):
    """[B, L] int32: the first key each query sees: its history's start
    and, with a window, no earlier than the oldest key the window
    leaves it."""
    import jax.numpy as jnp

    at = jnp.arange(positions.shape[1], dtype=jnp.int32)[None, :]
    first = at - positions.astype(jnp.int32)
    if window is not None:
        first = jnp.maximum(first, at - (int(window) - 1))
    return first


def segment_pairs(q, k, v, positions, scale: float, scope: str,
                  window=None, interpret: bool = False):
    """`ops.attention.segment_attention` by the kernels. q, k [B, H, L,
    dk]; v [B, H, L, dv]; `positions` [B, L]; `applicable` shapes.
    Returns o [B, H, L, dv] float32. The backward pass's ops are traced
    under `scope`; `interpret` runs the kernels in interpreter mode, on
    any backend (tests)."""
    return _attention(float(scale), scope, bool(interpret))(
        q, k, v, first_visible(positions, window))

"""Pallas TPU kernels: the Kimi Delta Attention scan (`ops/kda.py`), a
chunk at a time with the head's state on the chip.

`ops/kda.py`'s docstring has the mathematics, the masks and the stated
precision; nothing of that changes here. What changes is where a chunk's
intermediates live. As plain `jax.numpy` every one of them went through
HBM (the sub-blocks' channel-by-channel pair sums alone 537 MB a pass of
four heads at the Kimi cell's size), the triangular inverse was fifteen
fusions and two merges over all chunks, and the state ran as a
`lax.scan` of 128 launch-bound steps a pass. Here a grid step is one
chunk of one head: it loads the chunk's q, k, v, log a tiles [64, d],
beta and the running count of first tokens, and keeps in VMEM the
running sum g, the two [64, 64] pair matrices, (I + A)^-1 and the
state, which it carries in scratch along the innermost, sequential grid
axis (the chunks). It writes o and, where a backward pass will follow,
the state the chunk started from.

Forward (`_forward_body`), for a chunk with r the count of first tokens
at or before each token:

- masks from r: `pair` (one history, s <= t), `sees` (r = 0: the tokens
  the incoming state reaches), `in_last` (the chunk's last history),
  `keeps` (no first token in the chunk);
- g = pair @ log a, so the sum starts anew at a first token;
- the pair sums: inside a sub-block of 16 by LAG, not by block: for
  j = 0..15 the rows of k and g are rolled down j sublanes, so
  e^(g_t - g_(t-j)) k_(t-j) is one [64, d] product and its sum over the
  channels one lane reduction, P[t, t-j] for every t at once (rows whose
  lag leaves the sub-block are masked); between sub-blocks both factors
  against the later block's first token, a matrix product. No exponent
  is ever positive;
- (I + A)^-1: the four diagonal blocks of 16 by elimination, all four
  at once (X <- X - A[:, j] X[j, :] for j = 0..14, rank-1 updates on
  the VPU), then neighbours merged twice, X <- X - X A_off X, on the
  MXU: `_unit_lower_inverse`'s scheme;
- w, u0 = T (b K e^g), T (b V); u = u0 - w S; o = (q e^g) S + P_q u;
  S <- carry * S + k_end^T u. The state is held transposed, [d_v, d_k],
  so that `carry` (a row over d_k) meets it along lanes.

Backward (`_backward_body`): the same grid with the chunk axis reversed
and dS in scratch. A chunk's forward quantities are recomputed in VMEM
from the inputs and the saved incoming state, then differentiated by
hand; the gradient of g through the pair sums needs no third pass over
the pairs (`pair_sums_bwd`).

Precision is `ops/kda.py`'s: float32 at the highest matmul precision up
to and including T's two products; `dtype` operands with float32
accumulation in the four products with the state or the pseudo-values
and in their transposes in the backward pass; the state float32.

What a first call pays: a train step holds twelve of these kernels
(four KDA layers x forward, the pass's recomputation, backward), and
Pallas traces a kernel's function anew in every `pallas_call`. The
bodies are `jax.jit(..., inline=True)` over the refs, so each is traced
once a process (`pallas_solve._lanes_kernel`, PERF.md PR 29).

Mosaic, as met here: a [64, 1] column is made from a [1, 64] row (and
back) by a select against the identity and a reduction, never by a
reshape or a transpose; slices of values are static and on sublane
multiples of 8 except the single rows of the elimination.
"""

from __future__ import annotations

import functools

from predictionio_tpu.telemetry.registry import REGISTRY

_PATHS = ("kernel", "jnp")
# counted while a program is traced: the scans a process built into its
# programs, by the path `ops.kda.kda_scan` chose for them
SCAN_CALLS = REGISTRY.counter(
    "encoder_kda_scan_calls_total",
    "kda_scan calls traced into a program, by the path built for them "
    "(kernel: the Pallas kernels of ops/pallas_kda.py | jnp: plain "
    "jax.numpy)",
    labelnames=("path",))

CHUNK = 64      # tokens a grid step
_SUB = 16       # sub-block inside which pairs are summed by channel
_LANES = 128
# the tiles of a grid step, double-buffered, the state and what the
# compiler spills: well under this at the widths `applicable` admits
_VMEM_BYTES = 32 * 2**20
_MAX_STATE_BYTES = 2 * 2**20


def applicable(chunk: int, dk: int, dv: int) -> bool:
    """Whether the kernels take a scan of this shape: chunks of 64, both
    head widths whole lane tiles, and a state that leaves VMEM room."""
    return (chunk == CHUNK and dk % _LANES == 0 and dv % _LANES == 0
            and dk * dv * 4 <= _MAX_STATE_BYTES)


def _chunk_math(dtype):
    """The per-chunk mathematics on values, shared by both bodies."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    c, sub = CHUNK, _SUB
    hi = lax.Precision.HIGHEST

    nn, nt, tn = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))  # a b, a b^T, a^T b

    def mm(a, b, dims=nn):  # float32 at the highest precision
        return lax.dot_general(a, b, (dims, ((), ())), precision=hi,
                               preferred_element_type=f32)

    def dot(a, b, dims=nn):  # `dtype` operands, float32 accumulation
        return lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (dims, ((), ())), preferred_element_type=f32)

    mm_nt, mm_tn = (functools.partial(mm, dims=d) for d in (nt, tn))
    dot_nt, dot_tn = (functools.partial(dot, dims=d) for d in (nt, tn))

    def iota(shape, dim):
        return lax.broadcasted_iota(jnp.int32, shape, dim)

    def where(m, a, b):
        return lax.select(m, a, b)

    def zeros(*shape):
        return jnp.zeros(shape, f32)

    def rolled(x, by):  # rows down by `by` sublanes: out[t] = x[t - by]
        return x if by % c == 0 else pltpu.roll(x, by % c, 0)

    def masks(r_row, beta_row):
        """From the chunk's count of first tokens r [1, C] and beta
        [1, C]: the [C, C] masks, the [C, 1] columns as 0/1 float32."""
        t, s = iota((c, c), 0), iota((c, c), 1)
        eye = t == s

        def column(row):
            return jnp.sum(where(eye, jnp.broadcast_to(row, (c, c)),
                                 zeros(c, c)), axis=1, keepdims=True)

        r_col = column(r_row)
        same = r_col == jnp.broadcast_to(r_row, (c, c))
        r_last = jnp.max(r_row, axis=1, keepdims=True)        # [1, 1]
        block = sub.bit_length() - 1
        same_sub = lax.shift_right_logical(t, block) == \
            lax.shift_right_logical(s, block)
        pair = same & (s <= t)
        return {
            "t": t, "s": s, "eye": eye,
            "pair": pair, "pair_t": same & (s >= t),
            "strict": same & (s < t),
            "diag": pair & same_sub, "off": pair & ~same_sub,
            "same_sub": same_sub,
            "sees": (r_col == 0.0).astype(f32),
            "in_last": (r_col == r_last).astype(f32),
            "keeps": (r_last == 0.0).astype(f32),
            "beta": column(beta_row),
        }

    def lag_mask(m, j):
        """[C, C]: s = t - j inside one sub-block."""
        return (m["s"] == m["t"] - j) & ((m["t"] & (sub - 1)) >= j)

    def lag(k, g, j):
        """e^(g_t - g_(t-j)) [C, d] and that times k_(t-j), j > 0."""
        e = jnp.exp(jnp.minimum(g - rolled(g, j), 0.0))
        return e, e * rolled(k, j)

    def off_factors(q, k, g, i):
        at = i * sub
        ref = g[at:at + 1, :]
        ea = jnp.exp(jnp.minimum(g[at:at + sub, :] - ref, 0.0))   # [16, d]
        eb = jnp.exp(jnp.minimum(ref - g, 0.0))                   # [C, d]
        rows = jnp.concatenate([q[at:at + sub, :] * ea,
                                k[at:at + sub, :] * ea], axis=0)  # [32, d]
        return ea, eb, rows, k * eb

    def pair_sums(q, k, g, m):
        """P_q, P_k [C, C], zero outside `pair`."""
        d_q = jnp.broadcast_to(jnp.sum(q * k, axis=1, keepdims=True), (c, c))
        d_k = zeros(c, c)  # its diagonal is not in `strict`: never read
        for j in range(1, sub):
            _, ce = lag(k, g, j)
            at = lag_mask(m, j)
            d_q = where(at, jnp.broadcast_to(
                jnp.sum(q * ce, axis=1, keepdims=True), (c, c)), d_q)
            d_k = where(at, jnp.broadcast_to(
                jnp.sum(k * ce, axis=1, keepdims=True), (c, c)), d_k)
        left_q, left_k = [zeros(sub, c)], [zeros(sub, c)]
        for i in range(1, c // sub):
            _, _, rows, ke = off_factors(q, k, g, i)
            left = mm_nt(rows, ke)                                # [32, C]
            left_q.append(left[:sub])
            left_k.append(left[sub:])
        o_q = jnp.concatenate(left_q, axis=0)
        o_k = jnp.concatenate(left_k, axis=0)
        nought = zeros(c, c)
        return (where(m["diag"], d_q, where(m["off"], o_q, nought)),
                where(m["diag"], d_k, where(m["off"], o_k, nought)))

    def pair_sums_bwd(q, k, g, m, dp_q, dp_k):
        """(dq, dk, dg) through the pair sums, from the cotangents of
        P_q and P_k (any values outside `pair`). With dq_P, dk_r and
        dk_c the sums' contributions to q, to k as a row of P_k and to k
        as a column of either, dg = q dq_P + k (dk_r - dk_c); lag 0,
        whose factor is 1 whatever g, stays out of dg."""
        nought = zeros(c, c)
        dq_p, dk_r, dk_c = 0.0, 0.0, 0.0
        for j in range(1, sub):
            e, ce = lag(k, g, j)
            at = lag_mask(m, j) & m["pair"]
            a_q = jnp.sum(where(at, dp_q, nought), axis=1, keepdims=True)
            a_k = jnp.sum(where(at, dp_k, nought), axis=1, keepdims=True)
            dq_p = dq_p + a_q * ce
            dk_r = dk_r + a_k * ce
            dk_c = dk_c + rolled((a_q * q + a_k * k) * e, -j)
        off_q = where(m["off"], dp_q, nought)
        off_k = where(m["off"], dp_k, nought)
        rows_q, rows_k = [zeros(sub, q.shape[1])], [zeros(sub, q.shape[1])]
        for i in range(1, c // sub):
            at = i * sub
            ea, eb, rows, ke = off_factors(q, k, g, i)
            dl = jnp.concatenate([off_q[at:at + sub, :],
                                  off_k[at:at + sub, :]], axis=0)  # [32, C]
            d_rows = mm(dl, ke)                                   # [32, d]
            rows_q.append(ea * d_rows[:sub])
            rows_k.append(ea * d_rows[sub:])
            dk_c = dk_c + eb * mm_tn(dl, rows)
        dq_p = dq_p + jnp.concatenate(rows_q, axis=0)
        dk_r = dk_r + jnp.concatenate(rows_k, axis=0)
        dg = q * dq_p + k * (dk_r - dk_c)
        # lag 0: P_q's diagonal, sum_c q k (P_k's is not in `strict`)
        a_q = jnp.sum(where(m["eye"], dp_q, nought), axis=1, keepdims=True)
        return dq_p + a_q * k, dk_r + dk_c + a_q * q, dg

    def unit_lower_inverse(a, m):
        """(I + a)^-1, a [C, C] strictly lower triangular."""
        nought = zeros(c, c)
        a_d = where(m["same_sub"], a, nought)
        lane = m["s"] & (sub - 1)
        eye = m["eye"].astype(f32)
        blocks = [eye[b * sub:(b + 1) * sub, :] for b in range(c // sub)]
        for j in range(sub - 1):
            col = jnp.sum(where(lane == j, a_d, nought), axis=1,
                          keepdims=True)                          # [C, 1]
            blocks = [x - col[b * sub:(b + 1) * sub, :] * x[j:j + 1, :]
                      for b, x in enumerate(blocks)]
        x = jnp.concatenate(blocks, axis=0)
        size = sub
        while size < c:
            shift = size.bit_length() - 1
            tb = lax.shift_right_logical(m["t"], shift)
            sb = lax.shift_right_logical(m["s"], shift)
            below = ((tb & 1) == 1) & (sb == tb - 1)
            x = x - mm(mm(x, where(below, a, nought)), x)
            size *= 2
        return x

    def forward(q, k, v, la, m, st):
        """Everything of a chunk, from its inputs [C, d], its masks and
        the transposed incoming state st [d_v, d_k]."""
        beta, sees = m["beta"], m["sees"]
        g = mm(m["pair"].astype(f32), la)
        eg = jnp.exp(g)
        p_q, p_k = pair_sums(q, k, g, m)
        t = unit_lower_inverse(
            beta * where(m["strict"], p_k, zeros(c, c)), m)
        kb = (sees * beta) * k * eg
        vb = beta * v
        w, u0 = mm(t, kb), mm(t, vb)
        u = u0 - dot_nt(w, st)
        qd = sees * q * eg
        o = dot_nt(qd, st) + dot(p_q, u)
        g_end = g[c - 1:c, :]
        e_end = m["in_last"] * jnp.exp(jnp.minimum(g_end - g, 0.0))
        k_end = k * e_end
        carry = m["keeps"] * jnp.exp(g_end)                       # [1, d_k]
        st_new = carry * st + dot_tn(u, k_end)
        return {"g": g, "eg": eg, "p_q": p_q, "p_k": p_k, "t": t, "kb": kb,
                "vb": vb, "w": w, "u": u, "qd": qd, "o": o, "e_end": e_end,
                "k_end": k_end, "carry": carry, "st_new": st_new}

    def backward(q, k, v, la, m, st, do, dst):
        """(dq, dk, dv, dlog_a, dbeta [C, 1], dst for the chunk before)
        from do [C, d_v] and the later chunk's dst [d_v, d_k]."""
        f = forward(q, k, v, la, m, st)
        beta, sees, nought = m["beta"], m["sees"], zeros(c, c)
        g, eg, t, u, w = f["g"], f["eg"], f["t"], f["u"], f["w"]
        # S' = carry S + k_end^T u
        du = dot_nt(f["k_end"], dst)
        dk_end = dot(u, dst)
        dcarry = jnp.sum(st * dst, axis=0, keepdims=True)
        dst_out = f["carry"] * dst
        # o = qd S + P_q u
        dqd = dot(do, st)
        dst_out = dst_out + dot_tn(do, f["qd"])
        dp_q = dot_nt(do, u)
        du = du + dot_tn(f["p_q"], do)
        # u = u0 - w S
        dw = -dot(du, st)
        dst_out = dst_out - dot_tn(du, w)
        # w, u0 = T kb, T vb
        dt = mm_nt(dw, f["kb"]) + mm_nt(du, f["vb"])
        dkb, dvb = mm_tn(t, dw), mm_tn(t, du)
        # T = (I + A)^-1, A = beta strict(P_k)
        da = -mm_tn(t, mm_nt(dt, t))
        dp_k = where(m["strict"], beta * da, nought)
        dbeta = jnp.sum(where(m["strict"], f["p_k"] * da, nought), axis=1,
                        keepdims=True)
        # kb = sees beta k e^g, vb = beta v, qd = sees q e^g
        dk = (sees * beta) * eg * dkb
        deg = (sees * beta) * k * dkb + sees * q * dqd
        dbeta = dbeta + jnp.sum(sees * k * eg * dkb, axis=1, keepdims=True) \
            + jnp.sum(v * dvb, axis=1, keepdims=True)
        dv = beta * dvb
        dq = sees * eg * dqd
        # k_end = in_last k e^(g_end - g), carry = keeps e^(g_end)
        dk = dk + f["e_end"] * dk_end
        dgx = f["k_end"] * dk_end
        dg_end = jnp.sum(dgx, axis=0, keepdims=True) + f["carry"] * dcarry
        last = (iota((c, 1), 0) == c - 1).astype(f32)
        dg = eg * deg - dgx + last * dg_end
        # the pair sums
        dq_p, dk_p, dg_p = pair_sums_bwd(q, k, g, m, dp_q, dp_k)
        dq, dk, dg = dq + dq_p, dk + dk_p, dg + dg_p
        dla = mm(m["pair_t"].astype(f32), dg)
        return dq, dk, dv, dla, dbeta, dst_out

    def row_of(column, m):  # [C, 1] -> [1, C]
        return jnp.sum(where(m["eye"], jnp.broadcast_to(column, (c, c)),
                             zeros(c, c)), axis=0, keepdims=True)

    return masks, forward, backward, row_of


@functools.lru_cache(maxsize=16)
def _forward_body(heads: int, dk: int, dv: int, dtype: str, save: bool):
    """Kernel body of the forward pass, `heads` heads a grid step: refs
    q, k, log_a [1, C, heads dk], v [1, C, heads dv], aux [heads, 1, 2, C]
    (beta; the count of first tokens) -> o [1, C, heads dv] and, with
    `save`, the state each chunk started from, [heads, 1, d_v, d_k]; the
    state in scratch [heads, d_v, d_k]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    masks, forward, _, _ = _chunk_math(jnp.dtype(dtype))

    def body(q_ref, k_ref, v_ref, la_ref, aux_ref, o_ref, *rest):
        st_ref = rest[-1]

        @pl.when(pl.program_id(1) == 0)
        def _():
            st_ref[...] = jnp.zeros(st_ref.shape, jnp.float32)

        for h in range(heads):
            ks, vs = pl.ds(h * dk, dk), pl.ds(h * dv, dv)
            m = masks(aux_ref[h, 0, 1:2, :], aux_ref[h, 0, 0:1, :])
            st = st_ref[h]
            if save:
                rest[0][h, 0] = st
            f = forward(q_ref[0, :, ks], k_ref[0, :, ks], v_ref[0, :, vs],
                        la_ref[0, :, ks], m, st)
            o_ref[0, :, vs] = f["o"]
            st_ref[h] = f["st_new"]

    # inlined into the kernel being traced: never dispatched or compiled
    # by itself, so there is nothing to meter
    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


@functools.lru_cache(maxsize=16)
def _backward_body(heads: int, dk: int, dv: int, dtype: str):
    """Kernel body of the backward pass over the reversed chunk axis:
    refs q, k, v, log_a, aux as forward, s0 [heads, 1, d_v, d_k], do
    [1, C, heads dv] -> dq, dk, dv, dlog_a and dbeta [heads, 1, 1, C];
    dS in scratch [heads, d_v, d_k]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    masks, _, backward, row_of = _chunk_math(jnp.dtype(dtype))

    def body(q_ref, k_ref, v_ref, la_ref, aux_ref, s0_ref, do_ref,
             dq_ref, dk_ref, dv_ref, dla_ref, dbeta_ref, dst_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            dst_ref[...] = jnp.zeros(dst_ref.shape, jnp.float32)

        for h in range(heads):
            ks, vs = pl.ds(h * dk, dk), pl.ds(h * dv, dv)
            m = masks(aux_ref[h, 0, 1:2, :], aux_ref[h, 0, 0:1, :])
            dq, dk_, dv_, dla, dbeta, dst = backward(
                q_ref[0, :, ks], k_ref[0, :, ks], v_ref[0, :, vs],
                la_ref[0, :, ks], m, s0_ref[h, 0], do_ref[0, :, vs],
                dst_ref[h])
            dq_ref[0, :, ks] = dq
            dk_ref[0, :, ks] = dk_
            dv_ref[0, :, vs] = dv_
            dla_ref[0, :, ks] = dla
            dbeta_ref[h, 0] = row_of(dbeta, m)
            dst_ref[h] = dst

    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


def _heads_a_step(h: int) -> int:
    """Heads that share a grid step. Two: 5 % under one a step at the
    Kimi cell's pass (2.40 + 6.19 ms forward and forward + backward
    against 2.56 + 6.49; my chip runs, PR 34), half the grid steps and
    two independent chains for the scheduler. Four are 5 % faster again
    and double the bodies, which every call site lowers in every process
    (docs/performance.md)."""
    return 2 if h % 2 == 0 else 1


def _specs(b, n, h, hs, dk, dv, reverse):
    """Block specs over the grid (b * h / hs, n): a [B, L, H d] array is
    read a [C, hs d] tile at a time, with no copy to a chunked layout."""
    from jax.experimental import pallas as pl

    groups = h // hs
    at = (lambda j: n - 1 - j) if reverse else (lambda j: j)
    wide = lambda d: pl.BlockSpec(  # noqa: E731
        (1, CHUNK, hs * d), lambda i, j: (i // groups, at(j), i % groups))
    per_head = lambda *tile: pl.BlockSpec(  # noqa: E731
        (hs, 1) + tile, lambda i, j: (i, at(j), 0, 0))
    return wide(dk), wide(dv), per_head(2, CHUNK), per_head(dv, dk), \
        per_head(1, CHUNK)


def _pallas_call(body, name, q, v, h, reverse, ins, outs, out_shape,
                 interpret):
    """`pallas_call` of a body over the grid (B H / heads a step, N) of
    flat q [B, L, H d_k] and v [B, L, H d_v]; `ins` and `outs` name each
    operand's spec ("keys" | "values" | "aux" | "state" | "beta")."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, hdk = q.shape
    dk, dv, n = hdk // h, v.shape[2] // h, l // CHUNK
    hs = _heads_a_step(h)
    spec = dict(zip(("keys", "values", "aux", "state", "beta"),
                    _specs(b, n, h, hs, dk, dv, reverse)))
    return pl.pallas_call(
        body(hs, dk, dv), grid=(b * h // hs, n),
        in_specs=[spec[x] for x in ins], out_specs=[spec[x] for x in outs],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hs, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name=name, interpret=interpret)


def _states_shape(q, v, h):
    import jax
    import jax.numpy as jnp

    b, l, hdk = q.shape
    return jax.ShapeDtypeStruct(
        (b * h, l // CHUNK, v.shape[2] // h, hdk // h), jnp.float32)


def _call_forward(q, k, v, la, aux, h, dtype, save, interpret):
    import jax
    import jax.numpy as jnp

    body = functools.partial(_forward_body, dtype=dtype, save=save)
    return _pallas_call(
        body, "kda_chunks_fwd", q, v, h, False,
        ("keys", "keys", "values", "keys", "aux"),
        ("values", "state") if save else ("values",),
        [jax.ShapeDtypeStruct(v.shape, jnp.float32)]
        + ([_states_shape(q, v, h)] if save else []),
        interpret)(q, k, v, la, aux)


def _call_backward(q, k, v, la, aux, s0, do, h, dtype, interpret):
    import jax
    import jax.numpy as jnp

    like = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)  # noqa: E731
    b, l, _ = q.shape
    return _pallas_call(
        functools.partial(_backward_body, dtype=dtype), "kda_chunks_bwd",
        q, v, h, True,
        ("keys", "keys", "values", "keys", "aux", "state", "values"),
        ("keys", "keys", "values", "keys", "beta"),
        [like(q), like(k), like(v), like(la),
         jax.ShapeDtypeStruct((b * h, l // CHUNK, 1, CHUNK), jnp.float32)],
        interpret)(q, k, v, la, aux, s0, do)


@functools.lru_cache(maxsize=16)
def _scan(h: int, dtype: str, scope: str, interpret: bool):
    """The differentiable scan on flat arrays: q, k, log_a [B, L, H d_k],
    v [B, L, H d_v], aux [B H, N, 2, C] -> o [B, L, H d_v]."""
    import jax

    @jax.custom_vjp
    def scan(q, k, v, la, aux):
        return _call_forward(q, k, v, la, aux, h, dtype, False,
                             interpret)[0]

    def fwd(q, k, v, la, aux):
        o, s0 = _call_forward(q, k, v, la, aux, h, dtype, True, interpret)
        return o, (q, k, v, la, aux, s0)

    def bwd(res, do):
        # a backward pass is traced outside the caller's scopes: it opens
        # the one it was given, so that a trace can tell whose time it is
        import jax.numpy as jnp

        q, k, v, la, aux, s0 = res
        with jax.named_scope(scope):
            dq, dk, dv, dla, dbeta = _call_backward(
                q, k, v, la, aux, s0, do, h, dtype, interpret)
            daux = jnp.concatenate([dbeta, jnp.zeros_like(dbeta)], axis=2)
        return dq, dk, dv, dla, daux

    scan.defvjp(fwd, bwd)
    return scan


def kda_chunks(q, k, v, log_a, beta, starts, dtype, scope: str,
               interpret: bool = False):
    """`ops.kda.kda_scan`'s recurrence by the kernels. q, k, log_a
    [B, L, H, d_k]; v [B, L, H, d_v]; beta [B, L, H]; starts [B, L] bool
    (`history_starts`); `applicable` widths. Returns o [B, L, H, d_v]
    float32. The backward pass's ops are traced under `scope`;
    `interpret` runs the kernels in interpreter mode, on any backend
    (tests)."""
    import jax.numpy as jnp

    b, l, h, _ = q.shape
    n = -(-l // CHUNK)
    short = n * CHUNK - l
    if short:  # a tail of padding, every token a history of its own
        q, k, v, log_a = (jnp.pad(a, ((0, 0), (0, short), (0, 0), (0, 0)))
                          for a in (q, k, v, log_a))
        beta = jnp.pad(beta, ((0, 0), (0, short), (0, 0)))
        starts = jnp.pad(starts, ((0, 0), (0, short)), constant_values=True)
    f32 = jnp.float32
    flat = lambda a: a.astype(f32).reshape(b, n * CHUNK, -1)  # noqa: E731
    r = jnp.cumsum(starts.reshape(b, 1, n, CHUNK).astype(f32), axis=-1)
    aux = jnp.stack(
        [beta.astype(f32).transpose(0, 2, 1).reshape(b, h, n, CHUNK),
         jnp.broadcast_to(r, (b, h, n, CHUNK))],
        axis=3).reshape(b * h, n, 2, CHUNK)
    o = _scan(h, jnp.dtype(dtype).name, scope, bool(interpret))(
        flat(q), flat(k), flat(v), flat(log_a), aux)
    return o.reshape(b, n * CHUNK, h, -1)[:, :l]

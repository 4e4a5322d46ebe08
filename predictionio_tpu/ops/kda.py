"""Kimi Delta Attention: a gated delta rule with one forget gate a
channel, computed in chunks, over packed histories.

The recurrence, for one head with a state S [d_k, d_v] (zero at a
history's first token):

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

`quality/encoder_reference.py::delta_rule` is that, a token at a time.
`kda_scan` computes the same in chunks of C tokens. With g_t the running sum of log a inside a chunk
and u_t = b_t (v_t - (Diag(a_t) S_{t-1})^T k_t), a chunk that starts
from S_0 has

    (I + A) U = b (V - (K e^g) S_0),   A[t, s] = b_t sum_c k_tc k_sc e^(g_tc - g_sc)  (s < t)
    o_t = (q_t e^(g_t))^T S_0 + sum_{s <= t} (sum_c q_tc k_sc e^(g_tc - g_sc)) u_s
    S_C = Diag(e^(g_C)) S_0 + sum_s Diag(e^(g_C - g_s)) k_s u_s^T

so the tokens of a chunk meet in two [C, C] matrices and a triangular
solve, all chunks at once, and only `S_0 -> S_C` runs chunk after chunk.

Resets are exact and are masks, not gates: a history's first token may
stand anywhere in a chunk, and a gate of minus infinity has no place in
a running sum. With r_t the number of first tokens at or before t in
the chunk, a pair (t, s) counts where r_t = r_s, S_0 reaches the tokens
with r_t = 0, and S_C keeps the tokens of the chunk's last history and,
where the chunk holds no first token, S_0. The running sum g itself
starts anew at a first token (a product with the pairs' 0/1 mask), so
that no token's number, and no gradient, holds a term of another
history, not even one that cancels.

No exponent here is ever positive. The pair sums split the chunk into
sub-blocks of 16: inside one they are summed channel by channel with
e^(g_t - g_s) itself; between two, both factors are taken against the
later block's first token, e^(g_t - g_ref) e^(g_ref - g_s), which is a
matrix product. Where a pair is of two histories the exponent is cut at
zero and the pair masked.

Precision: log a, its sums and exponentials, b, the pair matrices, the
triangular inverse and its two products are float32 at the highest
matmul precision; the four products with the state or the pseudo-values
(W S_0, K^T U, Q S_0, P U) take `dtype` operands and accumulate in
float32; the state is float32. The caller wraps what it wants recomputed
in `jax.checkpoint`.

Two paths, one `kda_scan`, which decides from what it can observe: on a
TPU, with chunks of 64 and head widths that are whole lane tiles, the
Pallas kernels of `ops/pallas_kda.py` (a chunk's intermediates and the
state stay in VMEM; a hand-written backward pass); else the plain
`jax.numpy` below, differentiated by autodiff, which is the CPU's path
and the kernels' oracle in the tests.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import pallas_conv, pallas_kda
from predictionio_tpu.telemetry.spans import record as record_span

_SUB = 16  # sub-block of a chunk inside which pairs are summed by channel


def history_starts(seg, xp=jnp):
    """[B, L] bool: the token is the first of its history (or of a run
    of padding): its segment id differs from the token before it."""
    first = xp.ones_like(seg[:, :1], dtype=bool)
    return xp.concatenate([first, seg[:, 1:] != seg[:, :-1]], axis=1)


def chunk_stats(seg, chunk: int) -> tuple[int, int, int]:
    """Of a batch of packed sequences seg [B, L] (numpy): (chunks of
    `chunk` tokens, those that hold a history's first token, first
    tokens of real histories, padding's not counted)."""
    seg = np.asarray(seg)
    starts = history_starts(seg, np)
    b, l = seg.shape
    n = -(-l // chunk)
    padded = np.zeros((b, n * chunk), bool)
    padded[:, :l] = starts
    return (b * n, int(padded.reshape(b, n, chunk).any(-1).sum()),
            int((starts & (seg != 0)).sum()))


def causal_conv(x, w, seg, bias=None, *, first: int = 0, silu: bool = False,
                unit=None, scope: str = "conv"):
    """Causal depthwise convolution along L of x [B, L, C] with taps w
    [W, C] (and `bias` [C]), the last tap on the token itself. A tap that
    would read another history reads zero. Where x is wider than the
    taps, the channels first .. first + C of it are convolved (a window
    of Mamba-2's [z | xBC | dt]); `silu` applies SiLU to the sum; `unit`
    (a head's channels, epsilon, scale) then gives every head of that
    many channels unit length, times the scale (KDA's q and k).

    It decides its path from what it can observe: on a TPU, with
    channels (and `first`) a multiple of the lane width, L a multiple of
    a token tile and at most eight taps, the kernels of
    `ops/pallas_conv.py` (x read once a pass, a hand-written backward
    pass, traced under `scope`); else the plain `jax.numpy` below under
    autodiff, which is the CPU's path and the kernels' oracle in the
    tests. Which was built is counted in
    `encoder_causal_conv_calls_total{path}` and left in the timeline as
    `enc.conv.<path>` (the host seconds spent building it)."""
    t0, c = time.monotonic(), w.shape[1]
    path = ("kernel" if jax.default_backend() == "tpu" and x.ndim == 3
            and pallas_conv.applicable(x.shape[1], c, w.shape[0], first,
                                       unit)
            else "jnp")
    pallas_conv.CONV_CALLS.labels(path=path).inc()
    if path == "kernel":
        out = pallas_conv.causal_conv(x, w, seg, bias, first, silu, unit,
                                      scope)
    else:
        if x.shape[-1] != c:
            x = x[..., first:first + c]
        out = _causal_conv(x, w, seg, bias)
        if silu:
            out = jax.nn.silu(out)
        if unit is not None:
            out = _unit_heads(out, *unit)
    record_span(f"enc.conv.{path}", time.monotonic() - t0)
    return out


def _unit_heads(a, head: int, eps: float, scale: float):
    """Each run of `head` channels of a [.., C] over its length, times
    `scale`: a rsqrt(sum a^2 + eps) scale."""
    heads = a.reshape(a.shape[:-1] + (a.shape[-1] // head, head))
    heads = heads * jax.lax.rsqrt(
        jnp.sum(heads * heads, axis=-1, keepdims=True) + eps)
    if scale != 1.0:
        heads = heads * scale
    return heads.reshape(a.shape)


def _causal_conv(x, w, seg, bias):
    width = w.shape[0]
    out = x * w[width - 1] if bias is None else x * w[width - 1] + bias
    for back in range(1, width):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :x.shape[1]]
        same = jnp.pad(seg, ((0, 0), (back, 0)),
                       constant_values=-1)[:, :x.shape[1]] == seg
        out = out + jnp.where(same[..., None], shifted, 0.0) * w[width - 1
                                                                  - back]
    return out


def _unit_lower_inverse(a):
    """(I + a)^-1 for a [..., C, C] strictly lower triangular: forward
    substitution on the diagonal blocks of `_SUB`, all of them at once,
    then neighbours merged pair by pair until one block is left
    ([[X, 0], [Y, Z]]^-1 = [[X^-1, 0], [-Z^-1 Y X^-1, Z^-1]])."""
    c = a.shape[-1]
    size = min(_SUB, c)
    mm = functools.partial(jnp.matmul, precision="highest")

    def blocks(of, row, col, step):
        """[..., m, size, size]: the blocks of `of` at block row `row +
        step j`, block column `col + step j`."""
        return jnp.stack(
            [of[..., (row + j) * size:(row + j + 1) * size,
                (col + j) * size:(col + j + 1) * size]
             for j in range(0, c // size, step)], axis=-3)

    diag = blocks(a, 0, 0, 1)
    eye = jnp.eye(size, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (size,))]
    for i in range(1, size):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], jnp.stack(rows, axis=-2),
            precision="highest"))
    inv = jnp.stack(rows, axis=-2)                   # [..., C / size, size, size]
    while size < c:
        x, z = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        y = -mm(mm(z, blocks(a, 1, 0, 2)), x)
        inv = jnp.concatenate(
            [jnp.concatenate([x, jnp.zeros_like(x)], axis=-1),
             jnp.concatenate([y, z], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


@jax.checkpoint
def _pairs_in_sub_blocks(rows, k, g, pair):
    """sum_c rows[.., t, c] k[.., s, c] e^(g[.., t, c] - g[.., s, c]) for
    the pairs (t, s) of `pair` inside each sub-block: rows [R, ..., n, c,
    d], k and g [..., n, c, d], pair [..., n, c, c] -> [R, ..., n, c, c].
    Channel by channel, the exponent taken of the difference; recomputed
    in the backward pass, which would otherwise keep [c, c, d] for every
    sub-block."""
    diff = jnp.where(pair[..., None],
                     g[..., :, None, :] - g[..., None, :, :], -jnp.inf)
    e = jnp.exp(diff) * k[..., None, :, :]
    return jnp.einsum("r...tc,...tsc->r...ts", rows, e, precision="highest")


def _pair_sums(rows, k, g, pair):
    """P[r, .., t, s] = sum_c rows[r, .., t, c] k[.., s, c] e^(g_tc -
    g_sc) for the pairs (t, s) of `pair` [..., C, C] (s <= t, one
    history), zero elsewhere. rows [R, ..., C, d]; k, g [..., C, d]."""
    c_all, d = k.shape[-2:]
    sub = min(_SUB, c_all)
    n = c_all // sub
    lead = k.shape[:-2]
    split = lambda a: a.reshape(a.shape[:-2] + (n, sub, d))  # noqa: E731
    on_diagonal = jnp.stack(
        [pair[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
         for i in range(n)], axis=-3)
    diag = _pairs_in_sub_blocks(split(rows), split(k), split(g),
                                on_diagonal)
    bands = []
    for i in range(n):
        at = i * sub
        block = diag[..., i, :, :]
        if i:
            ref = g[..., at:at + 1, :]
            # of one history both exponents are <= 0; else cut and masked
            left = jnp.einsum(
                "r...tc,...sc->r...ts",
                rows[..., at:at + sub, :] * jnp.exp(jnp.minimum(
                    g[..., at:at + sub, :] - ref, 0.0)),
                k[..., :at, :] * jnp.exp(jnp.minimum(
                    ref - g[..., :at, :], 0.0)),
                precision="highest")
            block = jnp.concatenate([left, block], axis=-1)
        room = c_all - at - sub
        if room:
            block = jnp.concatenate(
                [block, jnp.zeros(rows.shape[:1] + lead + (sub, room),
                                  block.dtype)], axis=-1)
        bands.append(block)
    return jnp.where(pair, jnp.concatenate(bands, axis=-2), 0.0)


def kda_scan(q, k, v, log_a, beta, seg, chunk: int = 64,
             dtype=jnp.float32, scope: str = "kda.scan"):
    """The recurrence in chunks of `chunk` tokens (16 times a power of
    two). q, k, log_a [B, L, H, dk]; v [B, L, H, dv]; beta [B, L, H];
    seg [B, L]. Returns o [B, L, H, dv], float32; `dtype` is the
    operands' in the products with the state (see the module's
    docstring). Its ops are traced under `scope`, the backward pass's
    too. Which path was built is counted in
    `encoder_kda_scan_calls_total{path}` and left in the timeline as
    `enc.kda.scan.<path>` (the host seconds spent building it)."""
    chunk, t0 = int(chunk), time.monotonic()
    path = ("kernel" if jax.default_backend() == "tpu"
            and pallas_kda.applicable(chunk, q.shape[-1], v.shape[-1])
            else "jnp")
    pallas_kda.SCAN_CALLS.labels(path=path).inc()
    with jax.named_scope(scope):
        if path == "kernel":
            o = pallas_kda.kda_chunks(q, k, v, log_a, beta,
                                      history_starts(seg), dtype, scope)
        else:
            o = _kda_scan(q, k, v, log_a, beta, seg, chunk, jnp.dtype(dtype))
    record_span(f"enc.kda.scan.{path}", time.monotonic() - t0)
    return o


def _kda_scan(q, k, v, log_a, beta, seg, chunk, dtype):
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-l // chunk)
    short = n * chunk - l
    if short:  # a tail of padding: a history of its own after every token
        q, k, v, log_a = (jnp.pad(a, ((0, 0), (0, short), (0, 0), (0, 0)))
                          for a in (q, k, v, log_a))
        beta = jnp.pad(beta, ((0, 0), (0, short), (0, 0)))
        seg = jnp.pad(seg, ((0, 0), (0, short)),
                      constant_values=jnp.iinfo(seg.dtype).min)
    f32 = jnp.float32
    # [B, H, N, C, d]
    chunks = lambda a: a.astype(f32).reshape(  # noqa: E731
        b, n, chunk, h, -1).transpose(0, 3, 1, 2, 4)
    q, k, v, log_a = chunks(q), chunks(k), chunks(v), chunks(log_a)
    beta = chunks(beta[..., None])                      # [B, H, N, C, 1]
    r = jnp.cumsum(history_starts(seg).reshape(b, 1, n, chunk)
                   .astype(jnp.int32), axis=-1)         # [B, 1, N, C]
    # pairs (t, s) of one history, s <= t                 [B, 1, N, C, C]
    pair = (r[..., :, None] == r[..., None, :]) & jnp.tril(
        jnp.ones((chunk, chunk), bool))
    sees_s0 = (r == 0)[..., None]                       # [B, 1, N, C, 1]
    in_last = (r == r[..., -1:])[..., None]
    keeps_s0 = (r[..., -1] == 0)[..., None]             # [B, 1, N, 1]

    mm = functools.partial(jnp.matmul, precision="highest")
    g = mm(pair.astype(f32), log_a)  # the running sum inside a history
    p_q, p_k = _pair_sums(jnp.stack([q, k]), k, g, pair)
    a = beta * jnp.tril(p_k, -1)
    t = _unit_lower_inverse(a)
    decayed = jnp.exp(g)
    w = mm(t, jnp.where(sees_s0, beta * k * decayed, 0.0))   # [.., C, dk]
    u0 = mm(t, beta * v)                                     # [.., C, dv]
    g_end = g[..., -1:, :]
    k_end = jnp.where(in_last, k * jnp.exp(jnp.minimum(g_end - g, 0.0)),
                      0.0)
    carry = jnp.where(keeps_s0, jnp.exp(g_end[..., 0, :]), 0.0)  # [B,H,N,dk]

    def dot(x, y):
        return jnp.matmul(x.astype(dtype), y.astype(dtype),
                          preferred_element_type=f32)

    def one(s, xs):
        w_n, u0_n, k_end_n, carry_n = xs
        u = u0_n - dot(w_n, s)
        return (carry_n[..., None] * s + dot(k_end_n.swapaxes(-1, -2), u),
                (s, u))

    chunk_first = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    _, (s0, u) = jax.lax.scan(
        one, jnp.zeros((b, h, dk, dv), f32),
        tuple(chunk_first(x) for x in (w, u0, k_end, carry)))
    s0, u = jnp.moveaxis(s0, 0, 2), jnp.moveaxis(u, 0, 2)
    o = dot(jnp.where(sees_s0, q * decayed, 0.0), s0) + dot(p_q, u)
    o = o.transpose(0, 2, 3, 1, 4).reshape(b, n * chunk, h, dv)
    return o[:, :l]

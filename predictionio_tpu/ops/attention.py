"""Sequence-parallel attention: ring attention + Ulysses (all-to-all).

The reference has no sequence dimension (SURVEY.md §5 'Long-context':
PredictionIO predates transformers; its only big-tensor shard is MLlib's
block-partitioned ALS interaction matrix). The rebuild still ships
long-context sequence parallelism as first-class infrastructure, because
a TPU-native framework's scale story is shaped by it:

- `ring_attention`: queries/keys/values sharded over the mesh sequence
  axis; K/V blocks rotate around the ring via `ppermute` while each step
  folds one block into a numerically-stable online softmax (the
  flash/ring-attention recurrence). Peak memory per device is O(S/n · d)
  and the ICI traffic overlaps with the per-block matmuls.
- `ulysses_attention`: `all_to_all` re-shards seq → heads, computes
  full-sequence attention locally per head group, and all_to_alls back —
  cheaper collective volume when heads % n_shards == 0.

Both are exact (not approximations) and match `dense_attention` to float
tolerance; causal masking uses global positions so it is shard-layout
invariant. Shapes: [batch, heads, seq, head_dim], seq sharded.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops import pallas_attention
from predictionio_tpu.parallel.mesh import DATA_AXIS
from predictionio_tpu.telemetry.spans import record as record_span

_NEG_INF = -1e30  # large-negative instead of -inf: keeps fully-masked rows
# (causal ring blocks entirely in the future) NaN-free after softmax


def dense_attention(q, k, v, causal: bool = False, segment_ids=None,
                    scale: Optional[float] = None,
                    window: Optional[int] = None):
    """Reference single-device attention. q,k: [B, H, S, D]; v: [B, H, S,
    Dv]. `segment_ids` [B, S] keeps a query inside the keys of its own
    segment (packed histories); `scale` defaults to 1/sqrt(D); with a
    `window` a query at t sees the keys s with t - s < window."""
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    sq, sk = s.shape[-2], s.shape[-1]
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), sk - sq)[None, None]
    if segment_ids is not None:
        same = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None]
        mask = same if mask is None else mask & same
    if window is not None:
        near = (jnp.arange(sq)[:, None] + (sk - sq) - jnp.arange(sk)[None, :]
                < window)[None, None]
        mask = near if mask is None else mask & near
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


# -- blockwise causal attention inside segments ------------------------------
#
# One sequence at a time, a query block at a time, over the key blocks
# that can hold a visible key: from the block where the segment of the
# query block's first token starts (`kv_lo`) to the query block itself;
# with a window, from the block of the oldest key the window leaves the
# query block's first token, where that is the later one.
# The score matrix of a block pair is all that is ever held; the backward
# pass recomputes it from the saved log-sum-exp (the flash-attention
# recurrence). Two paths, one `segment_attention`, which decides from what
# it can observe: on a TPU, at shapes `pallas_attention.applicable` admits,
# the kernels of `ops/pallas_attention.py` (a pair's scores and the running
# accumulators stay in VMEM, at the kernel's own tile); else the plain
# jax.numpy below, which is the CPU's path and the kernels' oracle.

def _slab(x, start, size, axis=1):
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis)


def _pair_scores(qi, kj, seg_q, seg_k, i, j, blk, scale, window=None):
    s = jnp.einsum("hqd,hkd->hqk", qi, kj,
                   preferred_element_type=jnp.float32) * scale
    q_pos = i * blk + jnp.arange(blk)
    k_pos = j * blk + jnp.arange(blk)
    mask = (k_pos[None, :] <= q_pos[:, None]) & (seg_q[:, None]
                                                 == seg_k[None, :])
    if window is not None:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
    return s, mask[None]


def _segment_fwd_seq(q, k, v, seg, kv_lo, blk, scale, window=None):
    h, l, _ = q.shape
    dv = v.shape[-1]

    def q_block(i):
        qi, seg_q = _slab(q, i * blk, blk), _slab(seg, i * blk, blk, 0)

        def fold(j, carry):
            kj, vj = _slab(k, j * blk, blk), _slab(v, j * blk, blk)
            s, mask = _pair_scores(qi, kj, seg_q, _slab(seg, j * blk, blk, 0),
                                   i, j, blk, scale, window)
            return _online_fold(*carry, jnp.where(mask, s, _NEG_INF), vj)

        o, m, den = jax.lax.fori_loop(
            kv_lo[i], i + 1, fold,
            (jnp.zeros((h, blk, dv), jnp.float32),
             jnp.full((h, blk), _NEG_INF, jnp.float32),
             jnp.zeros((h, blk), jnp.float32)))
        return o / den[..., None], m + jnp.log(den)

    o, lse = jax.lax.map(q_block, jnp.arange(l // blk))
    return (o.transpose(1, 0, 2, 3).reshape(h, l, dv),
            lse.transpose(1, 0, 2).reshape(h, l))


def _segment_bwd_seq(q, k, v, seg, kv_lo, o, lse, do, blk, scale,
                     window=None):
    h, l, dk_ = q.shape
    delta = jnp.sum(do * o, axis=-1)  # [H, L]

    def q_block(carry, i):
        qi, seg_q = _slab(q, i * blk, blk), _slab(seg, i * blk, blk, 0)
        doi = _slab(do, i * blk, blk).astype(v.dtype)
        lse_i, delta_i = _slab(lse, i * blk, blk), _slab(delta, i * blk, blk)

        def fold(j, inner):
            dq_i, dk, dv = inner
            kj, vj = _slab(k, j * blk, blk), _slab(v, j * blk, blk)
            s, mask = _pair_scores(qi, kj, seg_q, _slab(seg, j * blk, blk, 0),
                                   i, j, blk, scale, window)
            p = jnp.where(mask, jnp.exp(s - lse_i[..., None]), 0.0)
            dp = jnp.einsum("hqd,hkd->hqk", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_i[..., None]) * scale).astype(q.dtype)
            dv_j = jnp.einsum("hqk,hqd->hkd", p.astype(v.dtype), doi,
                              preferred_element_type=jnp.float32)
            dk_j = jnp.einsum("hqk,hqd->hkd", ds, qi,
                              preferred_element_type=jnp.float32)
            dq_i = dq_i + jnp.einsum("hqk,hkd->hqd", ds, kj,
                                     preferred_element_type=jnp.float32)
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, _slab(dk, j * blk, blk) + dk_j, j * blk, 1)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, _slab(dv, j * blk, blk) + dv_j, j * blk, 1)
            return dq_i, dk, dv

        dq_i, dk, dv = jax.lax.fori_loop(
            kv_lo[i], i + 1, fold,
            (jnp.zeros((h, blk, dk_), jnp.float32),) + carry)
        return (dk, dv), dq_i

    (dk, dv), dq = jax.lax.scan(
        q_block, (jnp.zeros(k.shape, jnp.float32),
                  jnp.zeros(v.shape, jnp.float32)), jnp.arange(l // blk))
    return dq.transpose(1, 0, 2, 3).reshape(q.shape), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _segment_attention(q, k, v, seg, kv_lo, blk, scale, scope, window):
    return _segment_attention_fwd(q, k, v, seg, kv_lo, blk, scale, scope,
                                  window)[0]


def _segment_attention_fwd(q, k, v, seg, kv_lo, blk, scale, scope, window):
    with jax.named_scope(scope):
        o, lse = jax.lax.map(
            lambda a: _segment_fwd_seq(*a, blk, scale, window),
            (q, k, v, seg, kv_lo))
    return o, (q, k, v, seg, kv_lo, o, lse)


def _segment_attention_bwd(blk, scale, scope, window, res, do):
    # a backward pass is traced outside the caller's scopes: it opens the
    # one it was given, so that a trace can tell whose time it is
    q, k, v, seg, kv_lo, o, lse = res
    with jax.named_scope(scope):
        dq, dk, dv = jax.lax.map(
            lambda a: _segment_bwd_seq(*a, blk, scale, window),
            (q, k, v, seg, kv_lo, o, lse, do))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_segment_attention.defvjp(_segment_attention_fwd, _segment_attention_bwd)


def first_key_blocks(positions, block: int, window: Optional[int] = None,
                     xp=jnp):
    """[B, L / block]: for each query block the first key block it
    visits: where the history of its first token starts and, with a
    window, no earlier than the block of that token's oldest visible
    key. `positions` [B, L] counts each token from its history's start."""
    l = positions.shape[1]
    starts = (xp.arange(l)[None, :] - positions)[:, ::block]
    if window is not None:
        starts = xp.maximum(starts, xp.arange(0, l, block)[None, :]
                            - (window - 1))
    return xp.clip(starts // block, 0, None).astype(xp.int32)


def segment_attention(q, k, v, segment_ids, positions, block: int = 512,
                      scale: Optional[float] = None,
                      scope: str = "attention.segment",
                      window: Optional[int] = None):
    """Causal attention held inside segments, for packed sequences.

    q, k: [B, H, L, D]; v: [B, H, L, Dv]; `segment_ids` [B, L] (tokens of
    one history share an id, histories lie one after another);
    `positions` [B, L] counts each token from its segment's start, which
    is where the first key block a query block needs is read from. A
    sequence that one block holds takes `dense_attention`; a longer one
    goes a block pair at a time and never holds [H, L, L]; its ops,
    those of the backward pass too, are traced under `scope`. With a
    `window` a query at t sees the keys s of its history with t - s <
    window, and a query block skips the key blocks the window leaves
    out. Returns [B, H, L, Dv] in float32. Values narrower than a lane
    tile go through the kernels padded to one. The kernels tell a history
    by `positions` alone (its keys are those from t - positions[t] on,
    what `first_key_blocks` rests on too). Which path was built above
    one block is counted in `encoder_segment_attention_calls_total{path}`
    and left in the timeline as `enc.attention.<path>` (the host seconds
    spent building it)."""
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    l = q.shape[2]
    if l <= block:
        return dense_attention(q, k, v, causal=True,
                               segment_ids=segment_ids, scale=scale,
                               window=window)
    if l % block:
        raise ValueError(f"sequence {l} is not a multiple of block {block}")
    t0 = time.monotonic()
    # the kernels' values are whole lane tiles: narrower ones (64) ride
    # beside columns of zeros, which the result is cut back from
    dv = v.shape[-1]
    lanes = -(-dv // 128) * 128
    path = ("kernel" if jax.default_backend() == "tpu"
            and pallas_attention.applicable(l, q.shape[-1], lanes,
                                            q.dtype.itemsize)
            else "jnp")
    pallas_attention.SEGMENT_CALLS.labels(path=path).inc()
    if path == "kernel":
        with jax.named_scope(scope):
            if lanes != dv:
                v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, lanes - dv)))
            o = pallas_attention.segment_pairs(q, k, v, positions, scale,
                                               scope, window)
            if lanes != dv:
                o = o[..., :dv]
    else:
        kv_lo = first_key_blocks(positions, block, window)
        o = _segment_attention(q, k, v, segment_ids, kv_lo, block, scale,
                               scope, window)
    record_span(f"enc.attention.{path}", time.monotonic() - t0)
    return o


def _online_fold(o, m, l, s, v_blk):
    """Fold one K/V block, given its masked scores s [..., Sq, Skv], into
    the running (o, m, l) softmax state."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # rescale old accumulators, then add this block's contribution
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=o.dtype)
    return o_new, m_new, l_new


def _online_block(o, m, l, q, k_blk, v_blk, q_pos, kv_pos, causal):
    """Fold one K/V block into the running (o, m, l) softmax state."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) / math.sqrt(q.shape[-1])
    if causal:
        mask = kv_pos[None, :] <= q_pos[:, None]  # [Sq, Skv]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    return _online_fold(o, m, l, s, v_blk)


def ring_attention(q, k, v, mesh: Mesh, axis: str = DATA_AXIS,
                   causal: bool = False):
    """Exact attention with seq sharded over `axis`; K/V ring-rotate.

    q, k, v: [B, H, S, D] jax arrays (global view); S % mesh.shape[axis]
    == 0. Returns [B, H, S, D] sharded like q.
    """
    n = mesh.shape[axis]
    seq = q.shape[2]
    if seq % n != 0:
        raise ValueError(f"seq {seq} not divisible by {axis}={n}")
    blk = seq // n
    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec)
    def run(q_blk, k_blk, v_blk):
        idx = jax.lax.axis_index(axis)
        q_pos = idx * blk + jnp.arange(blk)
        o = jnp.zeros_like(q_blk)
        m = jnp.full(q_blk.shape[:-1], _NEG_INF, dtype=q_blk.dtype)
        l = jnp.zeros(q_blk.shape[:-1], dtype=q_blk.dtype)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_cur, v_cur = k_blk, v_blk
        for step in range(n):  # static ring walk, unrolled under jit
            src = (idx - step) % n  # whose block we currently hold
            kv_pos = src * blk + jnp.arange(blk)
            o, m, l = _online_block(o, m, l, q_blk, k_cur, v_cur,
                                    q_pos, kv_pos, causal)
            if step + 1 < n:
                k_cur = jax.lax.ppermute(k_cur, axis, perm)
                v_cur = jax.lax.ppermute(v_cur, axis, perm)
        return o / jnp.maximum(l, 1e-30)[..., None]

    return run(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = DATA_AXIS,
                      causal: bool = False):
    """Exact attention via all-to-all head/seq re-sharding (DeepSpeed-
    Ulysses style). Requires H % n == 0 and S % n == 0."""
    n = mesh.shape[axis]
    b, h, seq, d = q.shape
    if h % n != 0:
        raise ValueError(f"heads {h} not divisible by {axis}={n}")
    if seq % n != 0:
        raise ValueError(f"seq {seq} not divisible by {axis}={n}")
    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec)
    def run(q_blk, k_blk, v_blk):
        # [B, H, S/n, D] → all_to_all → [B, H/n, S, D]: full sequence,
        # head-group local
        def to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        def to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh, kh, vh = to_heads(q_blk), to_heads(k_blk), to_heads(v_blk)
        out = dense_attention(qh, kh, vh, causal=causal)
        return to_seq(out)

    return run(q, k, v)


def sequence_sharded_attention(q, k, v, mesh: Mesh, axis: str = DATA_AXIS,
                               causal: bool = False,
                               method: Optional[str] = None):
    """Pick the sequence-parallel strategy: 'ring', 'ulysses', or None =
    ulysses when heads divide evenly (lower collective volume), else
    ring."""
    n = mesh.shape[axis]
    if method is None:
        method = "ulysses" if q.shape[1] % n == 0 else "ring"
    if method == "ring":
        return ring_attention(q, k, v, mesh, axis, causal)
    if method == "ulysses":
        return ulysses_attention(q, k, v, mesh, axis, causal)
    raise ValueError(f"Unknown method {method!r} (ring | ulysses)")

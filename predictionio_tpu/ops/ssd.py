"""Mamba-2's state-space layer over packed histories, in its chunked
matrix form (state-space duality, SSD).

The recurrence, for one head of P channels with a state S [P, N] (zero
entering a history's first token) and one scalar decay a token:

    S_t = exp(dt_t a) S_{t-1} + (dt_t x_t) (x) B_t        a < 0, dt_t > 0
    y_t = S_t C_t + d x_t

B_t and C_t [N] are shared by all heads (one group), or by the heads of
a group: with G groups head h reads B_{g(h),t} and C_{g(h),t}, g(h) =
h // (heads / G), in the update and in the read-out alike.
`quality/encoder_reference.py::ssd_recurrence` is that, a token at a
time. `ssd_scan` computes the same with the sequence cut into chunks of
Q tokens. With La the running sum of dt a inside a chunk and r_t the
number of first tokens at or before t in the chunk:

    Y_intra[i] = sum_{j <= i, r_j = r_i} exp(La_i - La_j) (C_i . B_j) dt_j x_j
    Y_inter[i] = [r_i = 0] exp(La_i) S_0 C_i
    S_Q = [r_Q = 0] exp(La_Q) S_0 + sum_j [r_j = r_Q] exp(La_Q - La_j) dt_j x_j (x) B_j

so the tokens of a chunk meet in one [Q, Q] product C B^T for all heads
(G of them with G groups, each read by its heads),
masked head by head with the decays, and only `S_0 -> S_Q` runs chunk
after chunk. Any Q gives the same result. The state's build and its
read-out take a head's own group of B and C.

`gated_group_norm` is what follows the scan in a model with groups: the
gate, then an RMSNorm over each group's channels and not over all.

Resets are exact and are masks, not gates: a history's first token may
stand anywhere in a chunk. A pair (i, j) counts where no first token
lies in (j, i], S_0 reaches the tokens before the chunk's first such
token only, and S_Q keeps the tokens of the chunk's last history and,
where the chunk holds no first token, S_0. a < 0 and dt > 0, so every
exponent that is kept is <= 0; a masked pair's is set to minus infinity
before the exponential, so that no gradient holds a term of another
history.

Precision: dt a, its running sums and exponentials, the masks and the
carried state are float32. The four matrix products (C B^T, the masked
product with dt x, the state's build and its read-out) take `dtype`
operands and accumulate in float32.

What is held: the [B, chunks, heads, Q, Q] decay matrices and the
[B, chunks, heads, P, N] states that enter the chunks, for the scan's
own backward pass. The caller wraps what it wants recomputed in
`jax.checkpoint` (the encoder: a block), so that neither outlives its
layer. (Heads a pass at a time under a `lax.map`, as `ops/ssm.py` takes
its channels, made the compiler's plan for the whole step larger, not
smaller: 16.8 GiB for 13.5 at the benchmark's size.)

Two paths, one `ssd_scan`, which decides from what it can observe, as
`ops/kda.py::kda_scan` does: on a TPU, where
`pallas_ssd.applicable(chunk, P, N, H, G)` holds (chunks of whole lane
tiles up to 256, heads of 64 channels, a state width of whole lane
tiles, G dividing H into whole pairs of heads, states that leave VMEM
room), the Pallas kernels of `ops/pallas_ssd.py`. There a grid step is
one chunk of a block of heads of one group: the pair mask, C B^T, a
head's decay matrix and its masked product never leave VMEM, the
states [P, N] are carried in scratch from chunk to chunk, the skip d x
is added there, and what is
saved for the hand-written backward pass is the [B, chunks, heads, P,
N] states that enter the chunks and nothing of [Q, Q]; a tail that no
chunk holds is padded, every padded token a history of its own. Else
the plain `jax.numpy` below, differentiated by autodiff: the CPU's
path, the path of every other shape (a head of another width, an odd
chunk), and the kernels' oracle in the tests. What is held above is
that path's.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import pallas_ssd
from predictionio_tpu.ops.kda import history_starts
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.telemetry.spans import record as record_span

# counted while a program is traced: the scans a process built into its
# programs, by the path `ssd_scan` chose for them
SCAN_CALLS = REGISTRY.counter(
    "encoder_ssd_scan_calls_total",
    "ssd_scan calls traced into a program, by the path built for them "
    "(kernel: the Pallas kernels of ops/pallas_ssd.py | jnp: plain "
    "jax.numpy, chunked)",
    labelnames=("path",))


def ssd_scan(x, dt, a, b, c, d, seg, chunk: int = 128, dtype=jnp.float32,
             scope: str = "ssd.scan"):
    """The recurrence in chunks of `chunk` tokens. x [B, L, H, P] (the
    convolved input, through its SiLU); dt [B, L, H] (through its
    softplus); a [H] (negative); b, c [B, L, N] (one group) or [B, L, G,
    N] (G groups, G dividing H); d [H]; seg [B, L].
    Returns y [B, L, H, P], float32; `dtype` is the operands' in the
    four matrix products (see the module's docstring). Its ops are
    traced under `scope`, the backward pass's too. Which path was built
    is counted in `encoder_ssd_scan_calls_total{path}` and left in the
    timeline as `enc.ssd.scan.<path>` (the host seconds spent building
    it)."""
    t0, f32, chunk = time.monotonic(), jnp.float32, int(chunk)
    h, p = x.shape[2:]
    groups = b.shape[2] if b.ndim == 4 else 1
    if h % groups:
        raise ValueError(f"{groups} B/C groups do not divide {h} heads")
    path = ("kernel" if jax.default_backend() == "tpu"
            and pallas_ssd.applicable(chunk, p, b.shape[-1], h, groups)
            else "jnp")
    SCAN_CALLS.labels(path=path).inc()
    with jax.named_scope(scope):
        if path == "kernel":
            y = pallas_ssd.ssd_chunks(x, dt, a, b, c, d, history_starts(seg),
                                      chunk, dtype, scope)
        else:
            y = _ssd_scan(x, dt, a, b, c, history_starts(seg), chunk,
                          jnp.dtype(dtype)) \
                + d.astype(f32)[:, None] * x.astype(f32)
    record_span(f"enc.ssd.scan.{path}", time.monotonic() - t0)
    return y


def _ssd_scan(x, dt, a, b, c, first, chunk, dtype):
    """The three sums as plain `jax.numpy`, without the skip: the
    arguments of `ssd_scan`, `first` [B, L] its first tokens."""
    f32 = jnp.float32
    bsz, l, h, _ = x.shape
    n = -(-l // chunk)
    short = n * chunk - l
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    if short:  # a tail that adds nothing: x = dt = 0
        pad = lambda v: jnp.pad(  # noqa: E731
            v, ((0, 0), (0, short)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b, c, first = (pad(v) for v in (x, dt, b, c, first))
    chunks = lambda v: v.reshape((bsz, n, chunk) + v.shape[2:])  # noqa: E731
    heads_first = lambda v: jnp.moveaxis(chunks(v), 3, 2)  # noqa: E731
    # first tokens at or before a token of its chunk        [B, n, Q]
    r = jnp.cumsum(chunks(first).astype(jnp.int32), axis=-1)
    y = _ssd_chunks(heads_first(x), heads_first(dt), a.astype(f32),
                    chunks(b), chunks(c), r, dtype)
    return jnp.moveaxis(y, 2, 3).reshape(bsz, n * chunk, h, -1)[:, :l]


def _ssd_chunks(x, dt, a, b, c, r, dtype):
    """x [B, n, H, Q, P]; dt [B, n, H, Q]; a [H]; b, c [B, n, Q, N] or,
    with groups, [B, n, Q, G, N]; r [B, n, Q]. Returns y [B, n, H, Q, P]
    without the skip. With groups the heads of the three products that
    meet B or C stand as [G, H / G]; one group runs the program it ran
    before groups were known here."""
    f32 = jnp.float32
    q = x.shape[3]
    grouped = b.ndim == 5
    if grouped:
        g = b.shape[3]
        by_group = lambda v: v.reshape(  # noqa: E731
            v.shape[:2] + (g, v.shape[2] // g) + v.shape[3:])
        cb = jnp.einsum("bnigk,bnjgk->bngij", c.astype(dtype),
                        b.astype(dtype), preferred_element_type=f32)
    else:
        cb = jnp.einsum("bnik,bnjk->bnij", c.astype(dtype), b.astype(dtype),
                        preferred_element_type=f32)            # [B, n, Q, Q]
    la = jnp.cumsum(dt * a[:, None], axis=-1)                  # [B, n, H, Q]
    u = dt[..., None] * x                                      # dt x
    # pairs (i, j) of one history, j <= i                      [B, n, Q, Q]
    pair = (r[..., :, None] == r[..., None, :]) & jnp.tril(
        jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(pair[:, :, None],
                              la[..., :, None] - la[..., None, :], -jnp.inf))
    masked = ((cb[:, :, :, None] * by_group(decay)).reshape(decay.shape)
              if grouped else cb[:, :, None] * decay)
    y = jnp.einsum("bnhij,bnhjp->bnhip", masked.astype(dtype),
                   u.astype(dtype), preferred_element_type=f32)
    # what a chunk leaves: the tokens of its last history, decayed to its end
    la_end = la[..., -1:]
    to_end = jnp.where((r == r[..., -1:])[:, :, None],
                       jnp.exp(la_end - la), 0.0)
    if grouped:
        local = jnp.einsum(
            "bngrjp,bnjgk->bngrpk",
            by_group((to_end[..., None] * u).astype(dtype)), b.astype(dtype),
            preferred_element_type=f32)
        local = local.reshape(x.shape[:3] + local.shape[-2:])
    else:
        local = jnp.einsum(
            "bnhjp,bnjk->bnhpk", (to_end[..., None] * u).astype(dtype),
            b.astype(dtype), preferred_element_type=f32)       # [B, n, H, P, N]
    carried = jnp.where((r[..., -1] == 0)[..., None],
                        jnp.exp(la_end[..., 0]), 0.0)          # [B, n, H]

    def one(s0, xs):
        keep, made = xs
        return keep[..., None, None] * s0 + made, s0

    chunk_first = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    _, s0 = jax.lax.scan(one, jnp.zeros_like(local[:, 0]),
                         (chunk_first(carried), chunk_first(local)))
    s0 = jnp.moveaxis(s0, 0, 1)                                # [B, n, H, P, N]
    # S_0 reaches the tokens before the chunk's first first token
    reach = jnp.where((r == 0)[:, :, None], jnp.exp(la), 0.0)  # [B, n, H, Q]
    if grouped:
        read = jnp.einsum("bnigk,bngrpk->bngrip", c.astype(dtype),
                          by_group(s0.astype(dtype)),
                          preferred_element_type=f32).reshape(y.shape)
        return y + reach[..., None] * read
    return y + reach[..., None] * jnp.einsum(
        "bnik,bnhpk->bnhip", c.astype(dtype), s0.astype(dtype),
        preferred_element_type=f32)


def gated_group_norm(y, z, w, groups: int, eps: float):
    """RMSNorm_per_group(y SiLU(z)) w on y, z [.., C]: the gate first,
    then one norm over each of the `groups` runs of C / groups channels
    (a group's heads), float32; w [C]."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by_group = gated.reshape(gated.shape[:-1] + (groups, -1))
    normed = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return normed.reshape(gated.shape) * w

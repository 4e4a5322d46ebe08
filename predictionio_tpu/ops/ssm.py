"""Mamba's selective scan over packed histories, computed in chunks.

The recurrence, for one channel with a state s of N numbers (zero
entering a history's first token):

    s_t = exp(dt_t a) * s_{t-1} + (dt_t x_t) b_t        a < 0, dt_t > 0
    y_t = s_t . c_t + d x_t

`quality/encoder_reference.py::selective_scan` is that, a token at a
time. `selective_scan` here computes the same with the sequence cut into
chunks of C tokens. Every chunk runs the recurrence from a zero state,
all chunks at once, token after token of the chunk (C steps on a state
[chunks, N, channels]); with g_t the running sum of dt inside the chunk,
the state S_0 that enters a chunk reaches token t as exp(g_t a) S_0, so

    y_t = s_t^local . c_t + (exp(g_t a) * S_0) . c_t + d x_t
    S_C = exp(g_C a) * S_0 + s_C^local

and only `S_0 -> S_C` runs chunk after chunk.

Resets are exact and are masks, not gates: at a history's first token
the local state and the running sum g start anew (a `where`, never a
decay of minus infinity), S_0 reaches the tokens before the chunk's
first such token only, and S_C keeps S_0 only where the chunk holds
none. a < 0 and dt > 0, so no exponent here is ever positive.

What is held: a chunk loop's carry [B, chunks, N, channels] and, for the
backward pass, that carry once a step of the loop, which is the layer's
per-token states for the channels of one pass. So the channels go a pass
at a time (`channels`), each pass recomputed in the backward pass: of
16384 tokens x 5120 channels x 16 states (5.4 GB in float32) a pass of
640 channels keeps an eighth. The layout keeps the channels last (the
lanes) and the states before them.

Everything is float32 (`dtype`): dt a and its exponentials, the state,
the running sums, the read-out.

One `selective_scan`, which decides its path from what it can observe,
as `ops/kda.py::kda_scan` does. Today every shape takes the plain
`jax.numpy` below, differentiated by autodiff; a kernel that keeps a
chunk's state in VMEM would replace the inside and nothing above it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.kda import history_starts
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.telemetry.spans import record as record_span

# counted while a program is traced: the scans a process built into its
# programs, by the path `selective_scan` chose for them
SCAN_CALLS = REGISTRY.counter(
    "encoder_ssm_scan_calls_total",
    "selective_scan calls traced into a program, by the path built for "
    "them (jnp: plain jax.numpy, chunked)",
    labelnames=("path",))


def selective_scan(x, dt, a, b, c, d, seg, chunk: int = 64,
                   dtype=jnp.float32, scope: str = "ssm.scan",
                   channels: int = 0):
    """The recurrence in chunks of `chunk` tokens. x, dt [B, L, D] (the
    convolved input and the step, already through their SiLU and
    softplus); a [D, N] (negative); b, c [B, L, N]; d [D]; seg [B, L].
    Returns y [B, L, D] in `dtype`, the state's and every sum's.
    `channels` is how many channels go through at a time on the
    `jax.numpy` path (0, or no divisor of D: all at once), each pass
    recomputed in the backward pass. Its ops are traced under `scope`,
    the backward pass's too. Which path was built is counted in
    `encoder_ssm_scan_calls_total{path}` and left in the timeline as
    `enc.ssm.scan.<path>` (the host seconds spent building it)."""
    t0, path = time.monotonic(), "jnp"
    SCAN_CALLS.labels(path=path).inc()
    f = jnp.dtype(dtype)
    width = x.shape[-1]
    per = int(channels) if channels and width % int(channels) == 0 else width
    first = history_starts(seg)
    with jax.named_scope(scope):
        args = (x.astype(f), dt.astype(f), a.astype(f).T, d.astype(f))
        shared = (b.astype(f), c.astype(f), first, int(chunk))
        if per == width:
            y = _scan_pass(*args, *shared)
        else:
            # [passes, .., per]: a pass's channels lie side by side
            split = lambda v: jnp.moveaxis(  # noqa: E731
                v.reshape(v.shape[:-1] + (width // per, per)), -2, 0)
            y = jax.lax.map(
                lambda p: jax.checkpoint(_scan_pass, static_argnums=(7,))(
                    *p, *shared),
                tuple(split(v) for v in args))
            y = jnp.moveaxis(y, 0, -2).reshape(x.shape)
    record_span(f"enc.ssm.scan.{path}", time.monotonic() - t0)
    return y


def _scan_pass(x, dt, a_t, d, b, c, first, chunk):
    """x, dt [B, L, D]; a_t [N, D]; d [D]; b, c [B, L, N]; first [B, L]
    (the token starts a history). Returns y [B, L, D]."""
    bsz, l, width = x.shape
    n = -(-l // chunk)
    short = n * chunk - l
    if short:  # a tail that adds nothing: x = dt = 0
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, short), (0, 0)))
                       for v in (x, dt, b, c))
        first = jnp.pad(first, ((0, 0), (0, short)))
    # [C, B, chunks, ..]: the loop runs over a chunk's tokens
    steps = lambda v: jnp.moveaxis(  # noqa: E731
        v.reshape((bsz, n, chunk) + v.shape[2:]), 2, 0)
    x_s, dt_s, b_s, c_s, first_s = (steps(v) for v in (x, dt, b, c, first))

    @jax.checkpoint
    def token(carry, xs):
        s, g = carry                      # [B, n, N, D], [B, n, D]
        x_t, dt_t, b_t, c_t, new = xs
        new = new[..., None]
        g = jnp.where(new, 0.0, g) + dt_t
        s = (jnp.where(new[..., None], 0.0, s)
             * jnp.exp(dt_t[..., None, :] * a_t)
             + (dt_t * x_t)[..., None, :] * b_t[..., None])
        return (s, g), (jnp.sum(s * c_t[..., None], axis=-2), g)

    states = a_t.shape[0]
    (s_end, g_end), (y, g) = jax.lax.scan(
        token, (jnp.zeros((bsz, n, states, width), x.dtype),
                jnp.zeros((bsz, n, width), x.dtype)),
        (x_s, dt_s, b_s, c_s, first_s))
    seen = jnp.cumsum(first_s.astype(jnp.int32), axis=0)   # [C, B, n]
    keeps_s0 = (seen[-1] == 0)[..., None, None]
    carried = jnp.where(keeps_s0, jnp.exp(g_end[..., None, :] * a_t), 0.0)

    def one(s0, xs):
        decay, local = xs
        return decay * s0 + local, s0

    chunk_first = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    _, s0 = jax.lax.scan(one, jnp.zeros((bsz, states, width), x.dtype),
                         (chunk_first(carried), chunk_first(s_end)))
    s0 = jnp.moveaxis(s0, 0, 1)                            # [B, n, N, D]

    @jax.checkpoint
    def from_s0(g, s0, c_s, seen):
        """(exp(g_t a) * S_0) . c_t for the tokens S_0 reaches."""
        reach = jnp.exp(g[..., None, :] * a_t) * s0 * c_s[..., None]
        return jnp.where((seen == 0)[..., None],
                         jnp.sum(reach, axis=-2), 0.0)

    y = y + from_s0(g, s0, c_s, seen)
    y = jnp.moveaxis(y, 0, 2).reshape(bsz, n * chunk, width)[:, :l]
    return y + d * x[:, :l]

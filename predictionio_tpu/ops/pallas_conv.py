"""Pallas TPU kernels: the mixers' short causal convolution
(`ops/kda.py::causal_conv`), its SiLU and, where asked, its heads' unit
norm in one pass over x, forward and a hand-written backward.

The job is a handful of bytes a channel and token: read x, write y;
backward read x and dy, write dx. As plain `jax.numpy` every tap is a
padded, shifted copy of x through HBM and a five-operand sum on top, and
autodiff turns each pad into a slice-and-pad of the cotangent and each
tap into a reduction over all tokens: a fifth of what the bytes allow
(PERF.md, PR 50). Here a grid step is a tile [tokens, channels] of one
sequence, tokens on the sublanes and channels on the lanes, walked in
strips of `_STRIP_ELEMENTS`: a strip's rows and the eight before them
are one load from VMEM, the taps are shifts along the sublanes, and
nothing but the strip's y (or dx) goes back.

The tokens before a tile: the tile's x comes with the eight rows above it,
a second block of the same array under an index map of its own (`_HALO`
rows, one sublane tile, enough for `MAX_WIDTH` taps). A sequence's first
tile is handed its own first rows there, which no tap reads: which taps
read what is in the tap masks, never in the position.

The tap masks are `causal_conv`'s `same`, to the letter, taken in XLA
from the segment ids ([B, L], a few integer ops) and handed over as one
int32 a token: bit k - 1 says token t reads token t - k (same history,
inside the sequence), bit 8 + k - 1 says token t + k reads token t,
which is what the backward pass needs of the rows below. A masked tap is
a select of zero, not a product with it.

Backward: channels are the outer grid axis, then sequences, then the
token tiles from a sequence's last to its first. A tile recomputes u =
sum taps + bias from x (nothing but x and the masks is saved), du = dy
silu'(u), and dx[t] = sum_k du[t + k] w[W - 1 - k] through the masks; the
du of the first eight rows of the tile after it waits in VMEM scratch
(zeros after a sequence's last tile), so no block is read twice. dw
[W, C] and dbias [C] are sums over every token: float32, in eight
sublanes a tap while a tile is walked, added to an output block that
stays resident along the sequence and token axes of the grid.

An input that is a channel window of a wider array (Mamba-2's xBC inside
[z | xBC | dt]) is read in place: `start` is a whole number of channel
tiles and the index maps add it, so the slice is never copied; its
cotangent is dx padded to the array's width, as the slice's own
transpose would be.

Heads of unit length (KDA's q and k: a rsqrt(sum a^2 + eps) scale over
each head's channels after the SiLU) are the forward body's epilogue
and the backward body's first step: a head is whole lane tiles, so its
sum is a lane sum of values the strip already holds. Left to XLA behind
the kernel the norm is reshapes to [B, L, heads, d] and back, each a
relayout, and costs more than the convolution (PERF.md, PR 50).

float32 throughout, as every configuration's `dtype_policy` states for
the convolution.

What a first call pays is `ops/pallas_ssd.py`'s: the bodies are
`jax.jit(..., inline=True)` under `functools.lru_cache` (traced once a
process) and the two kernels of a shape are `jax.jit`s under
`lru_cache` (lowered once a program, whatever the number of layers).
"""

from __future__ import annotations

import functools
import math

from predictionio_tpu.ops.pallas_kda import _LANES, _VMEM_BYTES
from predictionio_tpu.telemetry.registry import REGISTRY

# counted while a program is traced: the convolutions a process built into
# its programs, by the path `ops.kda.causal_conv` chose for them
CONV_CALLS = REGISTRY.counter(
    "encoder_causal_conv_calls_total",
    "causal_conv calls traced into a program, by the path built for them "
    "(kernel: the Pallas kernels of ops/pallas_conv.py | jnp: plain "
    "jax.numpy)",
    labelnames=("path",))

MAX_WIDTH = 8           # taps: the rows before a tile are one sublane tile
_HALO = 8               # rows of the block above a tile
_AHEAD = 8              # bit of the first mask that looks at the rows below
_CHANNEL_TILES = (4 * _LANES, 2 * _LANES, _LANES)
_TOKEN_TILES = (1024, 512, 256, 128)
_TILE_BYTES = 2**20     # a tile of x: double-buffered beside y, or dy and dx
# a strip's [rows, channels], 32 vregs a value: strips of 8 vregs left the
# scheduler a quarter less to overlap (497 GB/s for 630; PERF.md, PR 50)
_STRIP_ELEMENTS = 32768


def tile(l: int, c: int, start: int = 0):
    """(tokens, channels) of a grid step for x [.., L, start:start + C],
    or None where no tile divides it: the widest channel tile that
    divides the window and its offset, then the tallest token tile that
    divides L and keeps a tile of x within `_TILE_BYTES`."""
    span = math.gcd(c, start) if start else c
    tc = next((t for t in _CHANNEL_TILES if span > 0 and span % t == 0), 0)
    tl = next((t for t in _TOKEN_TILES
               if l % t == 0 and t * tc * 4 <= _TILE_BYTES), 0)
    return (tl, tc) if tc and tl else None


def applicable(l: int, c: int, width: int, start: int = 0,
               unit=None) -> bool:
    """Whether the kernels take a convolution of this shape: channels a
    multiple of the lane width (the window's offset too), L a multiple
    of a token tile, at most `MAX_WIDTH` taps, and a head to norm (the
    first of `unit`) whole lane tiles that divide the channel tile."""
    tiles = tile(l, c, start)
    return (1 <= width <= MAX_WIDTH and tiles is not None
            and (unit is None
                 or (unit[0] % _LANES == 0 and tiles[1] % unit[0] == 0)))


def tap_masks(seg, width: int):
    """[B, L, 1] int32 from the segment ids [B, L]: bit k - 1, token t
    reads token t - k (`causal_conv`'s `same`); bit 8 + k - 1, token
    t + k reads token t."""
    import jax.numpy as jnp

    l = seg.shape[1]
    bits = jnp.zeros(seg.shape, jnp.int32)
    for back in range(1, width):
        same = jnp.pad(seg, ((0, 0), (back, 0)),
                       constant_values=-1)[:, :l] == seg
        ahead = jnp.pad(same[:, back:], ((0, 0), (0, back)))
        bits = bits | (same.astype(jnp.int32) << (back - 1)) \
            | (ahead.astype(jnp.int32) << (_AHEAD + back - 1))
    return bits[..., None]


def _strip_math(width: int, rows: int, silu: bool, unit):
    """The mathematics of a strip of `rows` tokens on values, shared by
    both bodies. `unit` (a head's channels, epsilon, scale) or None."""
    import jax.numpy as jnp
    from jax import lax

    def reads(m, bit):  # [rows, 1] bool
        return ((m >> bit) & 1) == 1

    def taps(ext, m, w, bias):
        """From ext [HALO + rows, C] (the strip under the eight rows
        before it), the masks m [rows, 1], the taps w [W, C] and the
        bias [1, C] or None: u [rows, C] and each tap's masked, shifted
        x, `causal_conv`'s sum in its order."""
        u = ext[_HALO:] * w[width - 1:width]
        if bias is not None:
            u = u + bias
        shifted = []
        for back in range(1, width):
            xs = jnp.where(reads(m, back - 1),
                           ext[_HALO - back:_HALO - back + rows], 0.0)
            shifted.append(xs)
            u = u + xs * w[width - 1 - back:width - back]
        return u, shifted

    def by_head(fn, *vs):
        """fn on each head's lanes of [rows, C] values."""
        head = unit[0]
        return jnp.concatenate(
            [fn(*(v[:, at:at + head] for v in vs))
             for at in range(0, vs[0].shape[1], head)], axis=1)

    def lanes_sum(v):
        return jnp.sum(v, axis=1, keepdims=True)

    def act(u):
        """What leaves for the sum u: SiLU, then each head of `unit` over
        its length (a rsqrt(sum a^2 + eps) scale)."""
        a = u / (1.0 + jnp.exp(-u)) if silu else u
        if unit is None:
            return a
        _, eps, scale = unit

        def normed(a):
            y = a * lax.rsqrt(lanes_sum(a * a) + eps)
            return y if scale == 1.0 else y * scale

        return by_head(normed, a)

    def act_grad(u, dy):
        """du from the cotangent of `act(u)`."""
        s = 1.0 / (1.0 + jnp.exp(-u)) if silu else None
        if unit is not None:
            _, eps, scale = unit

            def normed_grad(a, dy):  # d(a r) = r (dy - (a r) sum(dy a r))
                r = lax.rsqrt(lanes_sum(a * a) + eps)
                y = a * r
                g = dy if scale == 1.0 else dy * scale
                return r * (g - y * lanes_sum(g * y))

            dy = by_head(normed_grad, u * s if silu else u, dy)
        return dy * (s * (1.0 + u * (1.0 - s))) if silu else dy

    def back_taps(du, below, m, w):
        """dx [rows, C] from the strip's du, the du of the eight rows
        below it, the masks and the taps."""
        ext = jnp.concatenate([du, below], axis=0)
        dx = du * w[width - 1:width]
        for back in range(1, width):
            dx = dx + jnp.where(reads(m, _AHEAD + back - 1),
                                ext[back:back + rows], 0.0) \
                * w[width - 1 - back:width - back]
        return dx

    def by_sublane(v):  # [rows, C] -> [8, C]: the rows' sum, a sublane tile
        return functools.reduce(
            lambda a, b: a + b, [v[at:at + 8] for at in range(0, rows, 8)])

    return taps, act, act_grad, back_taps, by_sublane


def _strip_rows(tl: int, tc: int) -> int:
    return min(tl, max(8, _STRIP_ELEMENTS // tc))


@functools.lru_cache(maxsize=16)
def _forward_body(width: int, tl: int, tc: int, has_bias: bool, silu: bool,
                  unit):
    """Kernel body of the forward pass: refs x [1, tl, tc], the rows
    above [1, 8, tc], the masks [1, tl, 1], w [W, tc] (and bias [1, tc])
    -> y [1, tl, tc]."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    rows = _strip_rows(tl, tc)
    taps, act, _, _, _ = _strip_math(width, rows, silu, unit)

    def body(x_ref, above_ref, m_ref, w_ref, *rest):
        y_ref = rest[-1]
        w = w_ref[...]
        bias = rest[0][...] if has_bias else None

        def strip(ext, at):
            u, _ = taps(ext, m_ref[0, pl.ds(at, rows), :], w, bias)
            y_ref[0, pl.ds(at, rows), :] = act(u)

        strip(jnp.concatenate([above_ref[0], x_ref[0, 0:rows, :]], axis=0), 0)

        def later(i, carry):
            at = pl.multiple_of(i * rows, rows)
            strip(x_ref[0, pl.ds(at - _HALO, _HALO + rows), :], at)
            return carry

        if tl > rows:
            lax.fori_loop(1, tl // rows, later, 0)

    # inlined into the kernel being traced: never dispatched or compiled
    # by itself, so there is nothing to meter
    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


@functools.lru_cache(maxsize=16)
def _backward_body(width: int, tl: int, tc: int, has_bias: bool, silu: bool,
                   unit):
    """Kernel body of the backward pass over the reversed token axis:
    refs x, the rows above, the masks, w (and bias) as forward, dy
    [1, tl, tc] -> dx [1, tl, tc], dw [W, tc] and dbias [1, tc] (both
    resident along the grid's sequence and token axes); the du of the
    first eight rows of the tile below in scratch [8, tc]."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    rows = _strip_rows(tl, tc)
    n = tl // rows
    taps, _, act_grad, back_taps, by_sublane = _strip_math(width, rows, silu,
                                                           unit)

    def body(x_ref, above_ref, m_ref, w_ref, *rest):
        dy_ref, dx_ref, dw_ref, db_ref, below_ref = rest[-5:]
        w = w_ref[...]
        bias = rest[0][...] if has_bias else None

        @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
        def _():
            dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)
            db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)

        @pl.when(pl.program_id(2) == 0)  # a sequence's last tile
        def _():
            below_ref[...] = jnp.zeros(below_ref.shape, jnp.float32)

        def strip(ext, at, carry):
            below, sums = carry
            m = m_ref[0, pl.ds(at, rows), :]
            u, shifted = taps(ext, m, w, bias)
            du = act_grad(u, dy_ref[0, pl.ds(at, rows), :])
            dx_ref[0, pl.ds(at, rows), :] = back_taps(du, below, m, w)
            # dw[W - 1 - back], back = 0 the token itself; then dbias
            of = [du * ext[_HALO:]] + [du * xs for xs in shifted] + [du]
            return du[:8], tuple(s + by_sublane(v) for s, v in zip(sums, of))

        def earlier(i, carry):  # strips n - 1 .. 1
            at = pl.multiple_of((n - 1 - i) * rows, rows)
            return strip(x_ref[0, pl.ds(at - _HALO, _HALO + rows), :], at,
                         carry)

        zero = jnp.zeros((8, tc), jnp.float32)
        carry = below_ref[...], (zero,) * (width + 1)
        if n > 1:
            carry = lax.fori_loop(0, n - 1, earlier, carry)
        below, sums = strip(
            jnp.concatenate([above_ref[0], x_ref[0, 0:rows, :]], axis=0), 0,
            carry)
        below_ref[...] = below
        for back in range(width):
            at = width - 1 - back
            dw_ref[at:at + 1, :] += jnp.sum(sums[back], axis=0, keepdims=True)
        db_ref[...] += jnp.sum(sums[width], axis=0, keepdims=True)

    return jax.jit(body, inline=True)  # pio-lint: disable=coverage-jit-metering


def _specs(tl: int, tc: int, start: int, width: int, order):
    """The operands' block specs: `order` maps a grid step to (sequence,
    token tile, channel tile)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    first, above = start // tc, tl // _HALO
    at = lambda f: (lambda *ids: f(*order(*ids)))  # noqa: E731
    return {
        "x": pl.BlockSpec((1, tl, tc), at(lambda b, t, c: (b, t, first + c))),
        # the eight rows before the tile; a sequence's first tile is handed
        # its own first rows, which its masks never read
        "above": pl.BlockSpec(
            (1, _HALO, tc),
            at(lambda b, t, c: (b, jnp.maximum(t * above - 1, 0),
                                first + c))),
        "tile": pl.BlockSpec((1, tl, tc), at(lambda b, t, c: (b, t, c))),
        "masks": pl.BlockSpec((1, tl, 1), at(lambda b, t, c: (b, t, 0))),
        "taps": pl.BlockSpec((width, tc), at(lambda b, t, c: (0, c))),
        "row": pl.BlockSpec((1, tc), at(lambda b, t, c: (0, c))),
    }


@functools.lru_cache(maxsize=32)
def _forward_call(bsz: int, l: int, start: int, c: int, width: int,
                  has_bias: bool, silu: bool, unit, interpret: bool):
    """The forward kernel of one shape as a jitted function of (x, masks,
    w[, bias]); x [B, L, any width], of which the kernel reads the
    channels start .. start + c. A jit, not a bare `pallas_call`: a step's layers
    call one lowered function (`ops/pallas_ssd.py::_forward_call`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tl, tc = tile(l, c, start)
    spec = _specs(tl, tc, start, width, lambda b, t, c: (b, t, c))
    call = pl.pallas_call(
        _forward_body(width, tl, tc, has_bias, silu, unit),
        grid=(bsz, l // tl, c // tc),
        in_specs=[spec[s] for s in ("x", "above", "masks", "taps")
                  + (("row",) if has_bias else ())],
        out_specs=spec["tile"],
        out_shape=jax.ShapeDtypeStruct((bsz, l, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="causal_conv_fwd", interpret=interpret)

    def causal_conv_fwd(x, masks, w, *bias):
        return call(x, x, masks, w, *(b[None] for b in bias))

    # traced into the caller's program: never dispatched by itself there
    return jax.jit(causal_conv_fwd)  # pio-lint: disable=coverage-jit-metering


@functools.lru_cache(maxsize=32)
def _backward_call(bsz: int, l: int, start: int, c: int, width: int,
                   has_bias: bool, silu: bool, unit, interpret: bool):
    """The backward kernel of one shape as a jitted function of (x, masks,
    w[, bias], dy) -> (dx [B, L, c], dw [W, c], dbias [c]), as
    `_forward_call`."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tl, tc = tile(l, c, start)
    n = l // tl
    spec = _specs(tl, tc, start, width, lambda c, b, j: (b, n - 1 - j, c))
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    call = pl.pallas_call(
        _backward_body(width, tl, tc, has_bias, silu, unit),
        grid=(c // tc, bsz, n),
        in_specs=[spec[s] for s in ("x", "above", "masks", "taps")
                  + (("row",) if has_bias else ()) + ("tile",)],
        out_specs=[spec["tile"], spec["taps"], spec["row"]],
        out_shape=[shape(bsz, l, c), shape(width, c), shape(1, c)],
        scratch_shapes=[pltpu.VMEM((8, tc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="causal_conv_bwd", interpret=interpret)

    def causal_conv_bwd(x, masks, w, *rest):
        *bias, dy = rest
        dx, dw, db = call(x, x, masks, w, *(b[None] for b in bias), dy)
        return dx, dw, db[0]

    return jax.jit(causal_conv_bwd)  # pio-lint: disable=coverage-jit-metering


@functools.lru_cache(maxsize=32)
def _conv(start: int, c: int, has_bias: bool, silu: bool, unit, scope: str,
          interpret: bool):
    """The differentiable convolution: (x [B, L, total], masks
    [B, L, 1], w [W, c][, bias [c]]) -> act(conv(x[.., start:start + c]))
    [B, L, c]."""
    import jax
    import jax.numpy as jnp

    def key(x, w):
        return (x.shape[0], x.shape[1], start, c, w.shape[0], has_bias, silu,
                unit, interpret)

    @jax.custom_vjp
    def conv(x, masks, w, *bias):
        return _forward_call(*key(x, w))(x, masks, w, *bias)

    def fwd(x, masks, w, *bias):
        return conv(x, masks, w, *bias), (x, masks, w, bias)

    def bwd(res, dy):
        # a backward pass is traced outside the caller's scopes: it opens
        # the one it was given, so that a trace can tell whose time it is
        x, masks, w, bias = res
        with jax.named_scope(scope):
            dx, dw, db = _backward_call(*key(x, w))(x, masks, w, *bias, dy)
            if x.shape[2] != c:  # the window's cotangent, as a slice's
                dx = jnp.pad(dx, ((0, 0), (0, 0),
                                  (start, x.shape[2] - start - c)))
        return (dx, None, dw) + ((db,) if has_bias else ())

    conv.defvjp(fwd, bwd)
    return conv


def causal_conv(x, w, seg, bias, start: int, silu: bool, unit, scope: str,
                interpret: bool = False):
    """`ops.kda.causal_conv` by the kernels, of the channels start ..
    start + C of x [B, L, total] (C = w.shape[1]; an `applicable`
    shape); `silu` applies SiLU to the sum, `unit` (a head's channels,
    epsilon, scale) then norms each head over its length. Returns
    [B, L, C] float32. The backward pass's ops are traced under `scope`;
    `interpret` runs the kernels in interpreter mode, on any backend
    (tests)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    operands = (x.astype(f32), tap_masks(seg, w.shape[0]), w.astype(f32))
    if bias is not None:
        operands += (bias.astype(f32),)
    if unit is not None:
        unit = (int(unit[0]), float(unit[1]), float(unit[2]))
    return _conv(int(start), w.shape[1], bias is not None, bool(silu), unit,
                 scope, bool(interpret))(*operands)

"""`ops/pallas_conv.py`: the short convolution's kernels in interpret mode
against `ops/kda.py`'s `jax.numpy` convolution under autodiff, outputs and
every gradient; histories that start on a tile's first token, on its last
and inside the rows a tile reads of the one before it; a channel window
read in place; and which path `causal_conv` takes. CPU, seeded inputs."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import kda, pallas_conv
from predictionio_tpu.telemetry import spans
from predictionio_tpu.telemetry.registry import REGISTRY
from tests.test_kda import segments

B = 2
# name -> (channels, taps, length, the histories' lengths a row); a row's
# rest is padding (segment id 0)
CASES = {
    # tiles of 1024 x 128. Row 0: a history fills the first tile, so the
    # next starts on the second tile's first token and is one token long,
    # and the last starts two tokens before the sequence's end. Row 1:
    # histories start three, one and no token before the second tile, so
    # its first rows read the rows above through each mask
    "width4_channels128": (128, 4, 2048, [[1024, 1, 1021, 2],
                                          [1021, 2, 1, 500, 400]]),
    # five channel tiles; a history starts on a tile's last token
    "width4_channels640": (640, 4, 2048, [[1023, 1, 1, 1023],
                                          [2, 1022, 3, 1021]]),
    "width2_channels128": (128, 2, 2048, [[1024, 1, 1021, 2],
                                          [1023, 1, 1, 1023]]),
    # tiles of 512 x 512, three a sequence: the middle one has a tile on
    # both sides
    "width4_channels512": (512, 4, 1536, [[512, 511, 2, 400],
                                          [509, 1, 1, 1, 1, 1023]]),
    # tiles of 1024 x 256 in strips of 128 rows
    "width3_channels256": (256, 3, 2048, [[1022, 4, 1000], [2048]]),
    # a tile that is one strip, a sequence that is one tile
    "one_strip": (128, 4, 256, [[3, 1, 200, 52], [256]]),
}
WHAT = ("y", "x", "w", "bias")


def inputs(c, width, length, seed=1):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return f(B, length, c), f(width, c), f(c)


def kernel(seg, first=0, unit=None):
    return lambda x, w, *bias: pallas_conv.causal_conv(
        x, w, seg, bias[0] if bias else None, first, True, unit, "enc.conv",
        interpret=True)


def jnp_conv(seg, first=0, unit=None):
    def fn(x, w, *bias):
        y = jax.nn.silu(kda._causal_conv(
            x[..., first:first + w.shape[1]], w, seg,
            bias[0] if bias else None))
        return y if unit is None else kda._unit_heads(y, *unit)
    return fn


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _outputs_and_gradients(make, first, unit, args, seg):
    # the segment ids are an argument: one compilation a shape, not a case
    fn = make(seg, first, unit)
    weights = jnp.asarray(np.random.default_rng(5).standard_normal(
        args[0].shape[:2] + args[1].shape[1:]), jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                     argnums=tuple(range(len(args))))(*args)
    return (fn(*args),) + grads


def outputs_and_gradients(make, args, seg, first=0, unit=None):
    return dict(zip(WHAT, _outputs_and_gradients(make, first, unit, args,
                                                 seg)))


def close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale


@pytest.fixture(scope="module")
def computed():
    """(kernel, jax.numpy) of a case with or without a bias, each outputs
    and gradients, computed once a module."""
    cache = {}

    def get(case, biased):
        if (case, biased) not in cache:
            c, width, length, rows = CASES[case]
            args = inputs(c, width, length)[:3 if biased else 2]
            seg = segments(length, rows)
            cache[case, biased] = tuple(
                outputs_and_gradients(make, args, seg)
                for make in (kernel, jnp_conv))
        return cache[case, biased]

    return get


@pytest.mark.parametrize("biased,what", [
    (biased, what) for biased in (False, True)
    for what in WHAT[:4 if biased else 3]])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_the_jnp_convolution(computed, case, biased, what):
    """The same sum in the same order, float32: the output and the
    cotangents of x, the taps and the bias to rounding."""
    got, want = computed(case, biased)
    close(got[what], want[what], 1e-6)


@pytest.mark.parametrize("case", list(CASES))
def test_a_history_never_reads_another_in_the_convolution(case):
    """Exactly: what one history holds of x moves neither the outputs nor
    the cotangents of the others' tokens by a bit (a masked tap is a
    select of zero). The moved history is the one that starts nearest a
    tile's edge in row 0. The taps' and the bias's cotangents are sums
    over all tokens and are left out."""
    c, width, length, rows = CASES[case]
    seg = segments(length, rows)
    args = inputs(c, width, length, seed=3)
    lo = rows[0][0]
    hi = lo + rows[0][1]          # the second history of row 0
    moved = (args[0].at[0, lo:hi].set(
        inputs(c, width, length, seed=4)[0][0, lo:hi]),) + args[1:]
    a, b = (outputs_and_gradients(kernel, v, seg) for v in (args, moved))
    outside = np.ones(length, bool)
    outside[lo:hi] = False
    for name in ("y", "x"):
        assert np.array_equal(np.asarray(a[name])[0, outside],
                              np.asarray(b[name])[0, outside]), name
        assert np.array_equal(np.asarray(a[name])[1], np.asarray(b[name])[1])
    assert not np.array_equal(np.asarray(a["y"])[0, lo:hi],
                              np.asarray(b["y"])[0, lo:hi])


@pytest.mark.parametrize("what", WHAT)
def test_a_window_of_a_wider_array_is_read_in_place(what):
    """Mamba-2's xBC inside [z | xBC | dt]: the kernels read channels 256
    .. 640 of 768 where they stand, and x's cotangent is the window's,
    padded with zeros, as the slice's transpose is."""
    c, width, length, first = 384, 4, 1024, 256
    seg = segments(length, [[500, 1, 523], [1024]])
    wide, _, _ = inputs(768, width, length, seed=2)
    _, w, bias = inputs(c, width, length, seed=6)
    got, want = (outputs_and_gradients(make, (wide, w, bias), seg, first)
                 for make in (kernel, jnp_conv))
    assert got["x"].shape == wide.shape
    close(got[what], want[what], 1e-6)
    if what == "x":
        assert not np.asarray(got["x"])[..., :first].any()
        assert not np.asarray(got["x"])[..., first + c:].any()


@pytest.mark.parametrize("what", WHAT[:3])
@pytest.mark.parametrize("scale", [1.0, 128 ** -0.5])
@pytest.mark.parametrize("case", ["width4_channels512",
                                  "width3_channels256"])
def test_heads_leave_with_unit_length_where_asked(case, scale, what):
    """KDA's q and k: after the SiLU every head of 128 channels over its
    length, times a scale (q's 1 / sqrt(d)); four heads a channel tile
    and two. The backward pass norms the recomputed sum again and takes
    the cotangent through it."""
    c, width, length, rows = CASES[case]
    args, seg = inputs(c, width, length, seed=9)[:2], segments(length, rows)
    unit = (128, 1e-6, scale)
    got, want = (outputs_and_gradients(make, args, seg, 0, unit)
                 for make in (kernel, jnp_conv))
    close(got[what], want[what], 2e-6)
    if what == "y":
        lengths = np.linalg.norm(np.asarray(got["y"]).reshape(
            B, length, c // 128, 128), axis=-1)
        close(lengths, np.full_like(lengths, scale), 1e-4)


@pytest.mark.parametrize("scale", [1.0, 128 ** -0.5])
def test_the_jnp_path_norms_a_head_as_the_mixer_did(scale):
    """`causal_conv(..., unit=)` on the CPU is what `models/encoder.py::kda`
    wrote out before PR 50, bit for bit: SiLU, heads of 128, a rsqrt(sum
    a^2 + eps), then the scale."""
    c, width, length = 256, 4, 64
    seg = segments(length, [[1, 40, 23], [64]])
    x, w, _ = inputs(c, width, length, seed=11)
    got = kda.causal_conv(x, w, seg, silu=True, unit=(128, 1e-6, scale))
    a = jax.nn.silu(kda.causal_conv(x, w, seg)).reshape(B, length, 2, 128)
    want = a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    want = want * scale if scale != 1.0 else want
    assert np.array_equal(np.asarray(got), np.asarray(want.reshape(
        B, length, c)))


def test_without_silu_the_kernels_are_the_bare_convolution():
    c, width, length = 128, 4, 1024
    seg = segments(length, [[3, 1000, 21], [1024]])
    x, w, bias = inputs(c, width, length, seed=8)
    bare = lambda x, w, b: pallas_conv.causal_conv(  # noqa: E731
        x, w, seg, b, 0, False, None, "enc.conv", interpret=True)
    close(bare(x, w, bias), kda._causal_conv(x, w, seg, bias), 1e-6)
    g = jax.grad(lambda *a: jnp.sum(bare(*a)), argnums=(0, 1, 2))(x, w, bias)
    want = jax.grad(lambda *a: jnp.sum(kda._causal_conv(a[0], a[1], seg,
                                                        a[2])),
                    argnums=(0, 1, 2))(x, w, bias)
    for got_one, want_one in zip(g, want):
        close(got_one, want_one, 1e-6)


@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_the_tap_masks_are_the_convolutions_same(width):
    """Bit k - 1: `causal_conv`'s `same` for the tap k tokens back; bit
    8 + k - 1: the same of the token k ahead, false past the end."""
    length = 40
    seg = segments(length, [[1, 2, 3, 20, 14], [40]])
    bits = np.asarray(pallas_conv.tap_masks(seg, width))
    assert bits.shape == (2, length, 1) and bits.dtype == np.int32
    ids = np.asarray(seg)
    for back in range(1, 8):
        same = np.zeros((2, length), bool)
        same[:, back:] = ids[:, back:] == ids[:, :-back]
        ahead = np.zeros((2, length), bool)
        ahead[:, :-back] = same[:, back:]
        if back >= width:
            same[:], ahead[:] = False, False
        assert np.array_equal((bits[..., 0] >> (back - 1)) & 1, same)
        assert np.array_equal((bits[..., 0] >> (8 + back - 1)) & 1, ahead)


@pytest.mark.parametrize("l,c,width,first,tile", [
    (8192, 512, 4, 0, (512, 512)),       # a pass of Kimi's q, k or v
    (8192, 5120, 4, 0, (512, 512)),      # Phi's Mamba layer
    (8192, 4352, 4, 4096, (1024, 256)),  # Granite's xBC in [z | xBC | dt]
    (8192, 6144, 4, 4096, (512, 512)),   # Nemotron's
    (8192, 4352, 4, 0, (1024, 256)), (2048, 640, 2, 0, (1024, 128)),
    (1536, 512, 8, 0, (512, 512)), (128, 128, 1, 0, (128, 128)),
    (8192, 512, 9, 0, None), (8192, 512, 0, 0, None),     # taps
    (8192, 5, 4, 0, None), (8192, 576, 4, 0, None),       # channels
    (8192, 512, 4, 64, None),                             # the offset
    (40, 128, 4, 0, None), (1000, 128, 4, 0, None),       # no tile divides
    (8256, 128, 4, 0, None)])
def test_which_shapes_the_kernels_take(l, c, width, first, tile):
    assert pallas_conv.applicable(l, c, width, first) is (tile is not None)
    if tile:
        assert pallas_conv.tile(l, c, first) == tile


@pytest.mark.parametrize("c,head,takes", [
    (512, 128, True), (512, 256, True), (512, 512, True), (640, 128, True),
    (512, 64, False),      # a head that is half a lane tile
    (640, 256, False),     # heads that straddle the channel tiles of 128
    (512, 384, False)])
def test_which_heads_the_kernels_norm(c, head, takes):
    assert pallas_conv.applicable(8192, c, 4, 0, (head, 1e-6, 1.0)) is takes


def _built(path):
    return REGISTRY.get("encoder_causal_conv_calls_total").labels(
        path=path).value


@pytest.mark.parametrize("backend,total,c,length,width,first,head,path", [
    ("cpu", 128, 128, 1024, 4, 0, None, "jnp"),
    ("tpu", 128, 128, 1024, 4, 0, None, "kernel"),
    ("tpu", 384, 256, 1024, 4, 128, None, "kernel"),
    ("tpu", 256, 256, 1024, 4, 0, 128, "kernel"),
    ("cpu", 256, 256, 1024, 4, 0, 128, "jnp"),
    ("tpu", 256, 256, 1024, 4, 0, 64, "jnp"),   # heads of half a lane tile
    ("tpu", 384, 256, 1024, 4, 64, None, "jnp"),  # a window off the lanes
    ("tpu", 130, 130, 1024, 4, 0, None, "jnp"),   # an odd channel count
    ("tpu", 128, 128, 1000, 4, 0, None, "jnp"),   # no tile divides 1000
    ("tpu", 128, 128, 1024, 9, 0, None, "jnp")])  # more taps than a halo
def test_causal_conv_decides_from_the_backend_and_the_shape(
        monkeypatch, backend, total, c, length, width, first, head, path):
    """One `causal_conv`, no option: a TPU and a shape the kernels admit
    -> the kernels (run here in interpret mode by a spy), else
    `jax.numpy`; counted and left in the timeline either way."""
    taken = []
    real = pallas_conv.causal_conv

    def spy(*a, **kw):
        taken.append(a[-1])
        return real(*a, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pallas_conv, "causal_conv", spy)
    x, _, _ = inputs(total, width, length)
    _, w, bias = inputs(c, width, length, seed=2)
    seg = segments(length, [[40, 900, 10], [length]])
    paths = ("kernel", "jnp")
    before = {k: _built(k) for k in paths}
    tl, token = spans.begin("test", "train", "RUN", "t-1")
    try:
        unit = head and (head, 1e-6, 0.5)
        got = kda.causal_conv(x, w, seg, bias, first=first, silu=True,
                              unit=unit, scope="enc.ssd.conv")
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    assert taken == (["enc.ssd.conv"] if path == "kernel" else [])
    assert {k: _built(k) - before[k] for k in paths} == {
        k: float(k == path) for k in paths}
    assert [name for name, *_ in tl.spans] == [f"enc.conv.{path}"]
    close(got, jnp_conv(seg, first, unit)(x, w, bias), 1e-6)


def test_each_body_is_traced_once_a_process(monkeypatch):
    """What a first call pays (PERF.md, PR 29, 34, 49): Kimi's step holds
    seventy-two of these kernels (four layers x q, k, v x forward, the
    pass's recomputation, backward) and Pallas traces a kernel's function
    in every `pallas_call`; the bodies are jits over the refs, so a
    second call site of the same shape finds its trace."""
    traced = []
    real = pallas_conv._strip_math

    def counting(*key):
        taps, *rest = real(*key)

        def counted(*a):
            traced.append(1)
            return taps(*a)

        return (counted, *rest)

    def forget():
        for cached in (pallas_conv._forward_body, pallas_conv._backward_body,
                       pallas_conv._forward_call, pallas_conv._backward_call,
                       pallas_conv._conv):
            cached.cache_clear()

    monkeypatch.setattr(pallas_conv, "_strip_math", counting)
    forget()
    try:
        c, width, length, rows = CASES["width4_channels128"]
        seg, args = segments(length, rows), inputs(c, width, length, seed=7)
        fn = kernel(seg)
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2))
        jax.jit(lambda *a: (fn(*a), grad(*a))).lower(*args)
        # a body walks its first strip and, in a loop, the others: the
        # forward body's two and the backward body's two
        assert len(traced) == 4
        # other call sites (q, k and v of every layer), another program:
        # nothing is traced again
        jax.jit(lambda *a: (fn(*a) * 2.0, grad(*a), fn(*a),
                            grad(*(v * 2.0 for v in a)))).lower(*args)
        assert len(traced) == 4
    finally:
        forget()

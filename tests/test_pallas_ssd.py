"""`ops/pallas_ssd.py`: the SSD scan's kernels in interpret mode against
`ops/ssd.py`'s `jax.numpy` scan and against the recurrence a token at a
time (`quality/encoder_reference.py::ssd_recurrence`), outputs and all
six gradients, with float32 operands (tight) and bfloat16 (the cells');
one group at chunks of 256 and eight groups at chunks of 128; and which
path `ssd_scan` takes. CPU, seeded inputs, heads of 64 channels and a
state of 128 (the widths the kernels admit)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import pallas_ssd, ssd
from predictionio_tpu.ops.kda import history_starts
from predictionio_tpu.quality import encoder_reference as ref
from predictionio_tpu.telemetry import spans
from predictionio_tpu.telemetry.registry import REGISTRY
from tests.test_kda import segments

B, P, N = 2, 64, 128
NAMES = ("x", "dt", "a", "b", "c", "d")
WHAT = ("y",) + NAMES

# name -> (heads, groups, chunk, length, the histories' lengths a row); a
# row's rest is padding (segment id 0). Every length leaves a tail that
# no chunk holds, and histories start on a chunk's edge, mid-chunk and
# one token after another
CASES = {
    # four heads a grid step, one block: first tokens at 256 (an edge),
    # 257, 300 (mid-chunk) and a history of more than a chunk
    "one_group_chunk256": (4, 1, 256, 600,
                           [[256, 1, 43, 290], [100, 30, 1, 1, 400, 68]]),
    # two heads a grid step, each block its own group of B and C
    "eight_groups_chunk128": (16, 8, 128, 300,
                              [[128, 1, 60, 100], [70, 200, 30]]),
    # eight heads a grid step, two blocks: dB and dC are summed over them
    "one_group_two_blocks": (16, 1, 128, 200, [[90, 60, 50], [200]]),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def inputs(h, g, length, seed=1):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    bc = (B, length, N) if g == 1 else (B, length, g, N)
    return (f(B, length, h, P), jax.nn.softplus(f(B, length, h) - 1.0),
            -jnp.linspace(0.5, 4.0, h), f(*bc) * 0.3, f(*bc) * 0.3, f(h))


def with_skip(y, args):
    return y + args[5][:, None] * args[0]


def kernel(seg, chunk, dtype):
    return lambda *a: pallas_ssd.ssd_chunks(
        *a, history_starts(seg), chunk, dtype, "ssd.scan", interpret=True)


def jnp_scan(seg, chunk, dtype):
    return lambda *a: with_skip(ssd._ssd_scan(
        *a[:5], history_starts(seg), chunk, jnp.dtype(dtype)), a)


def recurrent(seg, chunk, dtype):
    def fn(x, dt, a, b, c, d):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda x, dt, b, c, first: ref.ssd_recurrence(
                x, dt, a, b, c, d, first, None, None))(
                    x, dt, b, c, history_starts(seg))
    return fn


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _outputs_and_gradients(make, chunk, dtype, args, seg):
    # the segment ids are an argument: one compilation a shape, not a case
    fn = make(seg, chunk, dtype)
    weights = jnp.asarray(np.random.default_rng(5).standard_normal(
        args[0].shape), jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                     argnums=tuple(range(6)))(*args)
    return (fn(*args),) + grads


def outputs_and_gradients(make, chunk, dtype, args, seg):
    return dict(zip(WHAT, _outputs_and_gradients(make, chunk, dtype, args,
                                                 seg)))


def close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale


@pytest.fixture(scope="module")
def computed():
    """(kernel, jax.numpy scan, recurrence) of a case and a dtype, each
    outputs and gradients, computed once a module."""
    cache = {}

    def get(case, dtype):
        h, g, chunk, length, rows = CASES[case]
        args, seg = inputs(h, g, length), segments(length, rows)
        if case not in cache:
            cache[case] = outputs_and_gradients(recurrent, chunk, None,
                                                args, seg)
        if (case, dtype) not in cache:
            cache[case, dtype] = tuple(
                outputs_and_gradients(make, chunk, DTYPES[dtype], args, seg)
                for make in (kernel, jnp_scan))
        return cache[case, dtype] + (cache[case],)

    return get


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_the_jnp_scan(computed, case, dtype, what):
    """Same three sums, same precision: in float32 to rounding; with
    bfloat16 operands the outputs to rounding and the gradients to the
    operands' (here autodiff multiplies a float32 cotangent, the
    kernel's backward a bfloat16 one, as the TPU's default precision
    does to both)."""
    got, want, _ = computed(case, dtype)
    # a's cotangent is one sum over every token of every sequence
    tight = 5e-5 if what == "a" else 2e-5
    close(got[what], want[what],
          tight if dtype == "float32" or what == "y" else 1e-2)


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_the_recurrence(computed, case, dtype, what):
    got, _, want = computed(case, dtype)
    tight = 5e-5 if what == "a" else 2e-5
    close(got[what], want[what], tight if dtype == "float32" else 6e-2)


@pytest.mark.parametrize("moved", ["x_b_c", "dt_too"])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", ["one_group_chunk256",
                                  "eight_groups_chunk128"])
def test_a_history_never_reads_another_in_the_kernels(case, dtype, moved):
    """Exactly, as `test_pallas_kda.py`'s: what one history holds of x, B
    and C moves neither the outputs nor the cotangents of the others'
    tokens by a bit (the masks are zeros, not small numbers). The
    history starts and ends mid-chunk. Its dt moves the chunk's running
    sum La under the later tokens of the chunk, as in `_ssd_chunks`:
    their differences La_i - La_j stay to rounding, not to the bit, and
    so does dt's own cotangent in either case. a and d are sums over all
    tokens and are left out."""
    h, g, chunk, length, _ = CASES[case]
    histories = [70, 1, 90, 40, 60, 30]
    seg = segments(length, [histories, histories[::-1]])
    args = inputs(h, g, length, seed=3)
    lo, hi = 71, 161  # the third history of row 0
    keep = (1, 2, 5) if moved == "x_b_c" else (2, 5)
    other = tuple(a if at in keep else a.at[0, lo:hi].set(b[0, lo:hi])
                  for at, (a, b) in enumerate(zip(
                      args, inputs(h, g, length, seed=4))))
    a, b = (outputs_and_gradients(kernel, chunk, DTYPES[dtype], x, seg)
            for x in (args, other))
    outside = np.ones(length, bool)
    outside[lo:hi] = False
    for name in ("y", "x", "dt", "b", "c"):
        # dt's cotangent is a reverse running sum over the chunk: the
        # moved history's terms cancel in it to rounding (the operands'
        # where a moved La rounds a masked product the other way)
        same = (np.array_equal if moved == "x_b_c" and name != "dt"
                else functools.partial(
                    close, tol=2e-5 if dtype == "float32" else 1e-2))
        assert same(np.asarray(a[name])[0, outside],
                    np.asarray(b[name])[0, outside]) is not False, name
        assert np.array_equal(np.asarray(a[name])[1], np.asarray(b[name])[1])
    assert not np.array_equal(np.asarray(a["y"])[0, lo:hi],
                              np.asarray(b["y"])[0, lo:hi])


def test_a_tail_no_chunk_holds_is_padded_with_histories_of_their_own(
        monkeypatch):
    """What reaches the grid is whole chunks: a length of 300 at chunks
    of 128 becomes 384, the 84 padded tokens with x = dt = 0 and each the
    first of a history (the count of first tokens rises by one a
    token), as `pallas_kda.kda_chunks` pads. `score()`'s odd lengths
    cannot reach an unpadded grid."""
    seen = {}

    def spy(*key):
        def scan(x, cols, rows, b, c, d):
            seen.update(x=x, cols=cols, rows=rows, b=b, c=c)
            return x
        return scan

    monkeypatch.setattr(pallas_ssd, "_scan", spy)
    h, g, chunk, length = 4, 1, 128, 300
    args = inputs(h, g, length)
    y = pallas_ssd.ssd_chunks(*args, history_starts(segments(
        length, [[300], [250, 50]])), chunk, jnp.float32, "ssd.scan")
    assert y.shape == (B, length, h, P)
    assert seen["x"].shape == (B, 384, h * P)
    assert seen["cols"].shape == (B, 384, 2 * h)
    assert seen["rows"].shape == (B * h, 3, 2, chunk)
    assert seen["b"].shape == seen["c"].shape == (B, 384, N)
    assert not np.asarray(seen["x"])[:, length:].any()
    assert not np.asarray(seen["cols"])[:, length:, h:].any()    # dt
    r = np.asarray(seen["rows"])[:, 2, 1]         # the last chunk's count
    at = length - 2 * chunk
    assert (np.diff(r[:, at - 1:], axis=1) == 1.0).all()
    # and La stands still over the tail
    la = np.asarray(seen["rows"])[:, 2, 0]
    assert (la[:, at:] == la[:, at - 1:at]).all()


@pytest.mark.parametrize("chunk,p,n,h,g,takes", [
    (256, 64, 128, 64, 1, True), (128, 64, 128, 64, 8, True),
    (128, 64, 256, 4, 1, True), (128, 64, 128, 16, 8, True),
    (64, 64, 128, 64, 1, False), (192, 64, 128, 64, 1, False),
    (512, 64, 128, 64, 1, False), (128, 128, 128, 64, 1, False),
    (128, 8, 128, 64, 1, False), (128, 64, 6, 64, 1, False),
    (128, 64, 64, 64, 1, False), (128, 64, 128, 8, 8, False),
    (128, 64, 128, 9, 1, False), (128, 64, 128, 64, 3, False),
    (128, 64, 2048, 64, 1, False)])
def test_which_shapes_the_kernels_take(chunk, p, n, h, g, takes):
    assert pallas_ssd.applicable(chunk, p, n, h, g) is takes


@pytest.mark.parametrize("h,g,heads", [
    (64, 1, 8), (64, 8, 8), (16, 8, 2), (4, 1, 4), (12, 1, 4), (6, 1, 2),
    (8, 8, 0), (9, 1, 0), (64, 3, 0)])
def test_the_heads_of_a_grid_step_are_whole_pairs_of_one_group(h, g, heads):
    assert pallas_ssd.heads_a_step(h, g) == heads


def _built(path):
    return REGISTRY.get("encoder_ssd_scan_calls_total").labels(
        path=path).value


@pytest.mark.parametrize("backend,p,g,path", [
    ("cpu", 64, 1, "jnp"), ("tpu", 64, 1, "kernel"), ("tpu", 64, 2, "kernel"),
    ("tpu", 8, 1, "jnp")])
def test_ssd_scan_decides_from_the_backend_and_the_shape(monkeypatch,
                                                         backend, p, g, path):
    """One `ssd_scan`, no option: a TPU and a shape the kernels admit ->
    the kernels (run here in interpret mode by a spy), else `jax.numpy`;
    counted and left in the timeline either way. The length is one no
    chunk divides."""
    taken = []
    real = pallas_ssd.ssd_chunks

    def spy(*a, **kw):
        taken.append(a[-1])
        return real(*a, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pallas_ssd, "ssd_chunks", spy)
    rng = np.random.default_rng(0)
    length, h, chunk = 150, 4, 128
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    bc = (1, length, N) if g == 1 else (1, length, g, N)
    args = (f(1, length, h, p), jax.nn.softplus(f(1, length, h)),
            -jnp.arange(1.0, h + 1.0), f(*bc), f(*bc), f(h))
    seg = segments(length, [[40, 100, 10]])
    paths = ("kernel", "jnp")
    before = {k: _built(k) for k in paths}
    tl, token = spans.begin("test", "train", "RUN", "t-1")
    try:
        got = ssd.ssd_scan(*args, seg, chunk, jnp.float32, "enc.ssd.scan")
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    assert taken == (["enc.ssd.scan"] if path == "kernel" else [])
    assert {k: _built(k) - before[k] for k in paths} == {
        k: float(k == path) for k in paths}
    assert [name for name, *_ in tl.spans] == [f"enc.ssd.scan.{path}"]
    close(got, jnp_scan(seg, chunk, jnp.float32)(*args), 2e-5)


def test_each_body_is_traced_once_a_process(monkeypatch):
    """What a first call pays (PERF.md, PR 29 and PR 34): Granite's step
    holds twenty-seven of these kernels (nine layers x forward, the
    block's recomputation, backward) and Pallas traces a kernel's
    function in every `pallas_call`; the bodies are jits over the refs,
    so a second call site of the same shape finds its trace."""
    traced = []
    real = pallas_ssd._chunk_math

    def counting(*key):
        masks, *rest = real(*key)

        def counted(*a):
            traced.append(1)
            return masks(*a)

        return (counted, *rest)

    def forget():
        for cached in (pallas_ssd._forward_body, pallas_ssd._backward_body,
                       pallas_ssd._forward_call, pallas_ssd._backward_call,
                       pallas_ssd._scan):
            cached.cache_clear()

    monkeypatch.setattr(pallas_ssd, "_chunk_math", counting)
    forget()
    try:
        h, g, chunk, length, rows = CASES["one_group_chunk256"]
        seg, args = segments(length, rows), inputs(h, g, length, seed=7)
        fn = kernel(seg, chunk, jnp.bfloat16)
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=tuple(range(6)))
        jax.jit(lambda *a: (fn(*a), grad(*a))).lower(*args)
        # the forward pass, the forward pass that keeps the states, the
        # backward pass: the masks of each once
        assert len(traced) == 3
        # other call sites (nine layers), another program: nothing is
        # traced again
        jax.jit(lambda *a: (fn(*a) * 2.0, grad(*a), fn(*a),
                            grad(*(v * 2.0 for v in a)))).lower(*args)
        assert len(traced) == 3
    finally:
        forget()

"""`ops/pallas_kda.py`: the KDA scan's kernels in interpret mode against
`ops/kda.py`'s `jax.numpy` scan and against the recurrence a token at a
time (`quality/encoder_reference.py::delta_rule`), outputs and all five
gradients, with float32 operands (tight) and bfloat16 (the Kimi cell's);
and which path `kda_scan` takes. CPU, seeded inputs, heads of 128 x 128
(the widths the kernels admit)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import kda, pallas_kda
from predictionio_tpu.quality import encoder_reference as ref
from predictionio_tpu.telemetry import spans
from predictionio_tpu.telemetry.registry import REGISTRY
from tests.test_kda import segments

B, H, D = 2, 2, 128
NAMES = ("q", "k", "v", "log_a", "beta")
WHAT = ("o",) + NAMES
LENGTH = 192

# name -> (length, the histories' lengths a row, log a's scale); a row's
# rest is padding (segment id 0)
CASES = {
    # a first token on a chunk's edge (64, 128) and one after the other
    "on_a_chunks_edge": (LENGTH, [[64, 64, 64], [128, 64]], 0.3),
    # inside a sub-block of 16 (70, 150) and on a sub-block's edge (80)
    "inside_a_sub_block": (LENGTH, [[70, 80, 42], [80, 112]], 0.3),
    "several_in_one_chunk": (LENGTH, [[3, 14, 1, 22, 30, 100, 22],
                                      [66, 1, 1, 1, 59, 64]], 0.3),
    "every_token_its_own_history": (LENGTH, [[1] * LENGTH, [1] * LENGTH],
                                    0.3),
    "a_padded_tail": (LENGTH, [[100, 30], [64]], 0.3),
    "a_length_off_the_chunk": (150, [[50, 100], [141, 9]], 0.3),
    # a running sum of log a of some -400 inside a chunk
    "a_strong_decay": (150, [[50, 100], [150]], 8.0),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def inputs(length, seed=1, decay=0.3):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((B, length, H, D))) * D ** -0.5
    k = unit(rng.standard_normal((B, length, H, D)))
    v = rng.standard_normal((B, length, H, D))
    log_a = -np.abs(rng.standard_normal((B, length, H, D))) * decay
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((B, length, H))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, log_a, beta))


def kernel(seg, dtype):
    return lambda *a: pallas_kda.kda_chunks(
        *a, kda.history_starts(seg), dtype, "kda.scan", interpret=True)


def recurrent(seg):
    return lambda *a: jax.vmap(lambda *x: ref.delta_rule(*x, None, None))(
        *a, kda.history_starts(seg))


def jnp_scan(seg, dtype):
    return lambda *a: kda._kda_scan(*a, seg, 64, jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _outputs_and_gradients(make, dtype, args, seg):
    # the segment ids are an argument: one compilation a shape, not a case
    fn = make(seg, dtype) if dtype else make(seg)
    weights = jnp.asarray(np.random.default_rng(5).standard_normal(
        args[2].shape), jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                     argnums=tuple(range(5)))(*args)
    return (fn(*args),) + grads


def outputs_and_gradients(make, dtype, args, seg):
    return dict(zip(WHAT, _outputs_and_gradients(make, dtype, args, seg)))


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
        length, rows, decay = CASES[case]
        args, seg = inputs(length, decay=decay), segments(length, rows)
        if case not in cache:
            cache[case] = outputs_and_gradients(recurrent, None, args, seg)
        if (case, dtype) not in cache:
            cache[case, dtype] = tuple(
                outputs_and_gradients(make, DTYPES[dtype], args, seg)
                for make in (kernel, jnp_scan))
        return cache[case, dtype] + (cache[case],)

    return get


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_the_jnp_scan(computed, case, dtype, what):
    """Same mathematics, same precision: in float32 to rounding; with
    bfloat16 operands the outputs to rounding and the gradients to the
    operands' (here autodiff multiplies a float32 cotangent, the
    kernel's backward a bfloat16 one, as the TPU's default precision
    does to both)."""
    got, want, _ = computed(case, dtype)
    strong = case == "a_strong_decay"
    tol = ((1e-4 if strong else 2e-5) if dtype == "float32" or what == "o"
           else 1e-2)
    close(got[what], want[what], tol)


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_the_recurrence(computed, case, dtype, what):
    got, _, want = computed(case, dtype)
    strong = case == "a_strong_decay"
    tol = (1e-4 if strong else 2e-5) if dtype == "float32" else 2e-2
    close(got[what], want[what], tol)


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_a_history_never_reads_another_in_the_kernels(dtype):
    """Exactly, as `test_kda.py::test_a_history_never_reads_another`:
    what one history holds moves neither the outputs nor the gradients
    of the others by a bit (the masks are zeros, not small numbers)."""
    histories = [64, 1, 30, 32, 23, 42]
    seg = segments(LENGTH, [histories, histories[::-1]])
    args = inputs(LENGTH, seed=3)
    lo, hi = 65, 95  # the third history of row 0
    other = tuple(a.at[0, lo:hi].set(b[0, lo:hi])
                  for a, b in zip(args, inputs(LENGTH, seed=4)))
    a, b = (outputs_and_gradients(kernel, DTYPES[dtype], x, seg)
            for x in (args, other))
    outside = np.ones(LENGTH, bool)
    outside[lo:hi] = False
    for name in a:
        assert np.array_equal(np.asarray(a[name])[0, outside],
                              np.asarray(b[name])[0, outside]), name
        assert np.array_equal(np.asarray(a[name])[1], np.asarray(b[name])[1])
    assert not np.array_equal(np.asarray(a["o"])[0, lo:hi],
                              np.asarray(b["o"])[0, lo:hi])


@pytest.mark.parametrize("chunk,dk,dv,takes", [
    (64, 128, 128, True), (64, 256, 128, True), (64, 64, 128, False),
    (64, 128, 96, False), (16, 128, 128, False), (128, 128, 128, False),
    (64, 1024, 1024, False)])
def test_which_shapes_the_kernels_take(chunk, dk, dv, takes):
    assert pallas_kda.applicable(chunk, dk, dv) is takes


def _built(path):
    return REGISTRY.get("encoder_kda_scan_calls_total").labels(
        path=path).value


@pytest.mark.parametrize("backend,d,path", [
    ("cpu", 128, "jnp"), ("tpu", 128, "kernel"), ("tpu", 64, "jnp")])
def test_kda_scan_decides_from_the_backend_and_the_shape(monkeypatch,
                                                         backend, d, path):
    """One `kda_scan`, no option: a TPU and widths the kernels admit ->
    the kernels (run here in interpret mode by a spy), else `jax.numpy`;
    counted and left in the timeline either way."""
    taken = []
    real = pallas_kda.kda_chunks

    def spy(*a, **kw):
        taken.append(a[-1])
        return real(*a, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pallas_kda, "kda_chunks", spy)
    rng = np.random.default_rng(0)
    length = 70
    q, k, v, log_a = (jnp.asarray(rng.standard_normal((1, length, 1, d))
                                  * 0.1, jnp.float32) for _ in range(4))
    log_a = -jnp.abs(log_a)
    beta = jnp.full((1, length, 1), 0.5, jnp.float32)
    seg = segments(length, [[40, 30]])
    before = {p: _built(p) for p in pallas_kda._PATHS}
    tl, token = spans.begin("test", "train", "RUN", "t-1")
    try:
        got = kda.kda_scan(q, k, v, log_a, beta, seg, 64, jnp.float32,
                           "enc.kda.scan")
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    assert taken == (["enc.kda.scan"] if path == "kernel" else [])
    built = {p: _built(p) - before[p] for p in pallas_kda._PATHS}
    assert built == {p: float(p == path) for p in pallas_kda._PATHS}
    assert [name for name, *_ in tl.spans] == [f"enc.kda.scan.{path}"]
    close(got, kda._kda_scan(q, k, v, log_a, beta, seg, 64,
                             jnp.dtype(jnp.float32)), 2e-5)


def test_each_body_is_traced_once_a_process(monkeypatch):
    """What a first call pays (PERF.md, PR 29 and PR 34): a train step
    holds twelve of these kernels and Pallas traces a kernel's function
    in every `pallas_call`; the bodies are jits over the refs, so a
    second call site of the same shape finds its trace."""
    traced = []
    real = pallas_kda._chunk_math

    def counting(dtype):
        masks, *rest = real(dtype)

        def counted(*a):
            traced.append(1)
            return masks(*a)

        return (counted, *rest)

    def forget():
        for cached in (pallas_kda._forward_body, pallas_kda._backward_body,
                       pallas_kda._scan):
            cached.cache_clear()

    monkeypatch.setattr(pallas_kda, "_chunk_math", counting)
    forget()
    try:
        seg = segments(128, [[100, 28], [128]])
        args = inputs(128, seed=7)
        fn = kernel(seg, jnp.bfloat16)
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)),
                        argnums=(0, 1, 2, 3, 4))
        jax.jit(lambda *a: (fn(*a), grad(*a))).lower(*args)
        # the forward pass, the forward pass that keeps the states, the
        # backward pass: each once, the heads of a grid step unrolled
        once = 3 * pallas_kda._heads_a_step(H)
        assert len(traced) == once
        # other call sites, another program: nothing is traced again
        jax.jit(lambda *a: (fn(*a) * 2.0, grad(*a), fn(*a))).lower(*args)
        assert len(traced) == once
    finally:
        forget()

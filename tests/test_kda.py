"""`ops/kda.py`: the chunked Kimi Delta Attention scan against the
recurrence a token at a time, outputs and gradients, on packed histories
whose boundaries fall on the first, a middle and the last slot of a
chunk; the convolution at a boundary. CPU, float32, seeded inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import kda
from predictionio_tpu.quality import encoder_reference as ref

B, H, DK, DV = 2, 3, 8, 8
NAMES = ("q", "k", "v", "log_a", "beta")
# boundaries at 64 (first slot of a chunk of 16 and of 64), 65 (a history
# of one token), 95 (last slot of a chunk of 16, slot 31 of 64), 127
# (last slot of both)
HISTORIES = [64, 1, 30, 32, 23]


def inputs(length, seed=1, decay=0.3):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((B, length, H, DK))) * DK ** -0.5
    k = unit(rng.standard_normal((B, length, H, DK)))
    v = rng.standard_normal((B, length, H, DV))
    log_a = -np.abs(rng.standard_normal((B, length, H, DK))) * decay
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((B, length, H))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, log_a, beta))


def recurrent(q, k, v, log_a, beta, seg):
    """The reference's recurrence a token at a time, a sequence each."""
    return jax.vmap(lambda *a: ref.delta_rule(*a, None, None))(
        q, k, v, log_a, beta, kda.history_starts(seg))


def segments(length, rows):
    seg = np.zeros((len(rows), length), np.int32)
    for b, lens in enumerate(rows):
        at = 0
        for n, ln in enumerate(lens):
            seg[b, at:at + ln] = n + 1
            at += ln
    return jnp.asarray(seg)


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale


def outputs_and_gradients(fn, args, length):
    weights = jnp.asarray(np.random.default_rng(5).standard_normal(
        (B, length, H, DV)), jnp.float32)
    out = fn(*args)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                     argnums=tuple(range(5)))(*args)
    return dict(zip(("o",) + NAMES, (out,) + grads))


@pytest.fixture(scope="module")
def against_the_recurrence():
    cache = {}

    def get(chunk, length):
        if (chunk, length) not in cache:
            args = inputs(length)
            seg = segments(length, [[length // 3, length // 2],
                                    [length - 9, 9]])
            cache[chunk, length] = (
                outputs_and_gradients(
                    lambda *a: kda.kda_scan(*a, seg, chunk=chunk), args,
                    length),
                outputs_and_gradients(
                    lambda *a: recurrent(*a, seg), args, length))
        return cache[chunk, length]

    return get


@pytest.mark.parametrize("what", ("o",) + NAMES)
@pytest.mark.parametrize("length", [77, 150])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_scan_equals_the_recurrence(against_the_recurrence,
                                                chunk, length, what):
    got, want = against_the_recurrence(chunk, length)
    close(got[what], want[what])


@pytest.fixture(scope="module")
def packed_and_apart():
    cache = {}

    def get(chunk):
        if chunk in cache:
            return cache[chunk]
        length = sum(HISTORIES)
        args = inputs(length, seed=2)
        seg = segments(length, [HISTORIES, HISTORIES[::-1]])
        packed = outputs_and_gradients(
            lambda *a: kda.kda_scan(*a, seg, chunk=chunk), args, length)
        weights = jnp.asarray(np.random.default_rng(5).standard_normal(
            (B, length, H, DV)), jnp.float32)
        apart = {name: np.zeros(np.shape(a), np.float32)
                 for name, a in packed.items()}
        for b, lens in enumerate([HISTORIES, HISTORIES[::-1]]):
            at = 0
            for ln in lens:
                one = tuple(a[b:b + 1, at:at + ln] for a in args)
                alone = jnp.ones((1, ln), jnp.int32)
                w = weights[b:b + 1, at:at + ln]
                o = kda.kda_scan(*one, alone, chunk=chunk)
                g = jax.grad(lambda *a: jnp.sum(
                    kda.kda_scan(*a, alone, chunk=chunk) * w),
                    argnums=tuple(range(5)))(*one)
                for name, part in zip(("o",) + NAMES, (o,) + g):
                    apart[name][b, at:at + ln] = part[0]
                at += ln
        cache[chunk] = packed, apart
        return cache[chunk]

    return get


@pytest.mark.parametrize("what", ("o",) + NAMES)
@pytest.mark.parametrize("chunk", [16, 64])
def test_a_packed_sequence_equals_its_histories_run_one_at_a_time(
        packed_and_apart, chunk, what):
    """Boundaries at the first, a middle and the last slot of a chunk
    and a history of one token: forward and every gradient."""
    packed, apart = packed_and_apart(chunk)
    close(packed[what], apart[what])


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_history_never_reads_another(chunk):
    """Exactly: what one history holds moves neither the outputs nor the
    gradients of the others by a bit."""
    length = sum(HISTORIES)
    seg = segments(length, [HISTORIES, HISTORIES[::-1]])
    args = inputs(length, seed=3)
    lo, hi = 65, 95  # the third history of row 0
    other = tuple(a.at[0, lo:hi].set(b[0, lo:hi])
                  for a, b in zip(args, inputs(length, seed=4)))
    fn = lambda *a: kda.kda_scan(*a, seg, chunk=chunk)  # noqa: E731
    a, b = (outputs_and_gradients(fn, x, length) for x in (args, other))
    outside = np.ones(length, bool)
    outside[lo:hi] = False
    for name in a:
        assert np.array_equal(np.asarray(a[name])[0, outside],
                              np.asarray(b[name])[0, outside]), name
        assert np.array_equal(np.asarray(a[name])[1], np.asarray(b[name])[1])
    assert not np.array_equal(np.asarray(a["o"])[0, lo:hi],
                              np.asarray(b["o"])[0, lo:hi])


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_strong_decay_stays_finite(chunk):
    """A running sum of log a of -400 inside a chunk: no exponent is
    taken of a positive number, so nothing overflows."""
    length = 150
    args = inputs(length, decay=8.0)
    seg = segments(length, [[50, 100], [150]])
    got = outputs_and_gradients(
        lambda *a: kda.kda_scan(*a, seg, chunk=chunk), args, length)
    want = outputs_and_gradients(
        lambda *a: recurrent(*a, seg), args, length)
    for name in got:
        assert np.isfinite(np.asarray(got[name])).all(), name
        close(got[name], want[name], 1e-4)


def test_the_triangular_inverse():
    rng = np.random.default_rng(0)
    a = np.tril(rng.standard_normal((3, 64, 64)), -1).astype(np.float32)
    got = kda._unit_lower_inverse(jnp.asarray(a))
    close(got, np.linalg.inv(np.eye(64) + a.astype(np.float64)), 1e-4)


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_convolution_reads_zero_before_a_historys_first_token(what):
    rng = np.random.default_rng(0)
    length, c, width = 40, 5, 4
    x = rng.standard_normal((2, length, c)).astype(np.float32)
    w = rng.standard_normal((width, c)).astype(np.float32)
    rows = [[1, 2, 3, 20, 14], [40]]
    seg = segments(length, rows)
    if what == "gradient":
        # d sum(y) / dx_s = the taps that still reach a token of s's history
        g = np.asarray(jax.grad(lambda x: jnp.sum(
            kda.causal_conv(x, jnp.asarray(w), seg)))(jnp.asarray(x)))
        want = np.zeros_like(x)
        for b, lens in enumerate(rows):
            at = 0
            for ln in lens:
                for s in range(ln):
                    want[b, at + s] = w[::-1][:min(width, ln - s)].sum(0)
                at += ln
        return close(g, want, 1e-6)
    got = np.asarray(kda.causal_conv(jnp.asarray(x), jnp.asarray(w), seg))
    for b, lens in enumerate(rows):
        at = 0
        for ln in lens:
            alone = np.concatenate([np.zeros((width - 1, c), np.float32),
                                    x[b, at:at + ln]])
            want = sum(alone[j:j + ln] * w[j] for j in range(width))
            close(got[b, at:at + ln], want, 1e-6)
            at += ln


@pytest.mark.parametrize("rows", [[[1, 2, 3, 20, 14], [40]],
                                  [[40], [3, 3, 3, 31]],
                                  [[2] * 20, [39, 1]]])
def test_three_bare_taps_are_three_shifted_sums(rows):
    """The convolution as a mixer of its own (LFM2's): three taps, no
    bias, no SiLU, no unit norm behind it, a history's first token
    anywhere: y_t = w_2 x_t + w_1 x_{t-1} + w_0 x_{t-2}, a shifted term
    kept where the earlier token is of the same history."""
    rng = np.random.default_rng(len(rows[0]))
    length, c = 40, 6
    x = rng.standard_normal((2, length, c)).astype(np.float32)
    w = rng.standard_normal((3, c)).astype(np.float32)
    seg = np.asarray(segments(length, rows))
    got = kda.causal_conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(seg),
                          first=0)
    want = x * w[2]
    for back in (1, 2):
        same = seg[:, back:] == seg[:, :-back]
        want[:, back:] += np.where(same[..., None], x[:, :-back], 0) * w[2 - back]
    close(got, want, 1e-6)
    # bare: the same call with the activation on is another function
    assert not np.allclose(got, kda.causal_conv(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(seg), silu=True))


def test_the_convolution_adds_a_bias_where_one_is_given():
    """Mamba's: a bias a channel on every token, a history's first too;
    without one (the KDA layers) the traced program is what it was."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 40, 5)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 5)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(5), jnp.float32)
    seg = segments(40, [[1, 2, 3, 20, 14], [40]])
    plain = kda.causal_conv(x, w, seg)
    close(kda.causal_conv(x, w, seg, bias), plain + bias, 1e-6)
    g = jax.grad(lambda b: jnp.sum(kda.causal_conv(x, w, seg, b)))(bias)
    close(g, np.full(5, 80.0), 1e-6)
    assert str(jax.make_jaxpr(lambda x, w: kda.causal_conv(x, w, seg))(
        x, w)) == str(jax.make_jaxpr(
            lambda x, w: kda.causal_conv(x, w, seg, None))(x, w))


def test_the_chunk_statistics():
    seg = np.asarray(segments(150, [HISTORIES, [100]]))
    # row 0: chunks of 64 at 0, 64, 128: starts in the first two; row 1:
    # a history in the first chunk, padding starts at 100 in the second
    assert kda.chunk_stats(seg, 64) == (6, 4, 6)
    chunks, boundary, resets = kda.chunk_stats(seg, 16)
    assert (chunks, resets) == (20, 6)
    # row 0: 0, 64 (and 65), 95, 127 -> chunks 0, 4, 5, 7; row 1: 0, 6
    assert boundary == 6

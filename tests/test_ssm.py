"""Mamba's selective scan in chunks (`ops/ssm.py::selective_scan`)
against the recurrence a token at a time
(`quality/encoder_reference.py::selective_scan`) on the CPU, float32,
seeded: the output and every gradient; a first token at every offset of
a chunk; a history longer than several chunks; one-token histories;
chunk sizes that do and do not divide a history or the sequence; the
channels in one pass and in several."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.ssm import selective_scan
from predictionio_tpu.quality import encoder_reference as ref
from predictionio_tpu.telemetry.registry import REGISTRY

D, N = 8, 4
NAMES = ("x", "dt", "a", "b", "c", "d")
# sequence 0: a first token at every offset of a chunk of 16 (histories
# of 17), then one-token histories; sequence 1: a history of 70 tokens
# (more than four chunks of 16, more than one of 64), then short ones
LENGTHS = [[17] * 16 + [1, 1, 1, 5], [70, 3, 1, 64, 90, 47, 5]]
L = 280


def inputs(lengths=LENGTHS, l=L, seed=0):
    rng = np.random.default_rng(seed)
    b = len(lengths)
    seg = np.zeros((b, l), np.int32)
    for row, lens in enumerate(lengths):
        at = 0
        for n, ln in enumerate(lens):
            seg[row, at:at + ln] = n + 1
            at += ln
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    args = {"x": f(b, l, D), "dt": jax.nn.softplus(f(b, l, D) - 1.0),
            "a": -jnp.exp(f(D, N)), "b": f(b, l, N), "c": f(b, l, N),
            "d": f(D)}
    return args, jnp.asarray(seg)


def recurrence(args, seg):
    """The reference, a sequence at a time."""
    first = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    return jnp.stack([
        ref.selective_scan(args["x"][n], args["dt"][n], args["a"],
                           args["b"][n], args["c"][n], args["d"], first[n],
                           None, lambda fn: fn)
        for n in range(seg.shape[0])])


def both(chunk, channels, lengths=LENGTHS, l=L):
    """{name: (got, want)} for the output `y` and each gradient of a
    weighted sum of it."""
    args, seg = inputs(lengths, l)
    weight = jnp.asarray(np.random.default_rng(5).standard_normal(
        (len(lengths), l, D)), jnp.float32)

    def program(args):
        y = selective_scan(*(args[k] for k in NAMES), seg, chunk,
                           channels=channels)
        return jnp.sum(y * weight), y

    def reference(args):
        with jax.default_matmul_precision("highest"):
            y = recurrence(args, seg)
        return jnp.sum(y * weight), y

    (_, y), g = jax.jit(jax.value_and_grad(program, has_aux=True))(args)
    (_, y_ref), g_ref = jax.jit(jax.value_and_grad(reference,
                                                   has_aux=True))(args)
    return {"y": (y, y_ref), **{k: (g[k], g_ref[k]) for k in NAMES}}


def close(pair, tol=2e-5):
    a, b = (np.asarray(v) for v in pair)
    assert np.isfinite(a).all()
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-3)


@pytest.fixture(scope="module")
def against_the_recurrence():
    cache = {}
    return lambda chunk, channels: cache.setdefault(
        (chunk, channels), both(chunk, channels))


@pytest.mark.parametrize("what", ("y",) + NAMES)
@pytest.mark.parametrize("chunk,channels", [(16, 0), (16, 4), (64, 2),
                                            (24, 0)])
def test_the_chunked_scan_equals_the_recurrence(against_the_recurrence,
                                                chunk, channels, what):
    """Chunks of 16 and 64 divide no history here and, at 24, not the
    sequence either (a padded tail)."""
    close(against_the_recurrence(chunk, channels)[what])


@pytest.mark.parametrize("what", ("y", "x", "dt", "b"))
def test_one_token_histories_alone(what):
    close(both(16, 0, [[1] * 40], 40)[what])


def test_a_history_never_reads_another():
    """A change inside one history moves no output of any other."""
    args, seg = inputs()
    y = selective_scan(*(args[k] for k in NAMES), seg, 16)
    moved = dict(args)
    inside = np.asarray(seg[1] == 4)             # the history of 64 tokens
    for k in ("x", "dt", "b", "c"):
        moved[k] = args[k].at[1].set(
            jnp.where(inside[:, None], args[k][1] * 1.5 + 0.25, args[k][1]))
    y2 = selective_scan(*(moved[k] for k in NAMES), seg, 16)
    outside = ~inside
    assert np.array_equal(np.asarray(y[1])[outside],
                          np.asarray(y2[1])[outside])
    assert np.array_equal(np.asarray(y[0]), np.asarray(y2[0]))
    assert not np.allclose(np.asarray(y[1])[inside],
                           np.asarray(y2[1])[inside])


def test_a_strong_decay_and_a_long_history_stay_finite():
    """No exponent is positive: a step of 50 a token over 280 tokens
    overflows nothing, forward or backward."""
    args, seg = inputs([[280]], 280)
    args["dt"] = args["dt"] * 0 + 50.0
    args["a"] = args["a"] * 0 - 16.0

    def total(args):
        return jnp.sum(selective_scan(*(args[k] for k in NAMES), seg, 64))

    value, g = jax.value_and_grad(total)(args)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(v)).all() for v in g.values())


def test_the_path_built_is_counted():
    family = REGISTRY.get("encoder_ssm_scan_calls_total")
    before = dict(family.collect())
    args, seg = inputs()
    selective_scan(*(args[k] for k in NAMES), seg, 16)
    after = dict(family.collect())
    key = next(k for k in after if "jnp" in str(k))
    assert after[key] == before.get(key, 0) + 1

"""Mamba-2's scan in its chunked matrix form (`ops/ssd.py::ssd_scan`)
against the recurrence a token at a time
(`quality/encoder_reference.py::ssd_recurrence`) on the CPU, float32,
seeded: the output and every gradient; first tokens at, at the edges of
and between chunks, at chunks of 16, 64 and 256 alike; a history longer
than several chunks; one-token histories; a sequence no chunk divides;
lower-precision operands."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.ssd import ssd_scan
from predictionio_tpu.quality import encoder_reference as ref
from predictionio_tpu.telemetry.registry import REGISTRY

H, P, N = 4, 8, 6
NAMES = ("x", "dt", "a", "b", "c", "d")
# sequence 0: a first token at every offset of a chunk of 16 (histories
# of 17), then one-token histories; sequence 1: first tokens at 0, 255,
# 256, 257 (the edges of a chunk of 256, of 64 and of 16), a history of
# 200 tokens (more than three chunks of 64), then short ones
LENGTHS = [[17] * 16 + [1, 1, 1, 5] + [33] * 8,
           [255, 1, 1, 200, 63, 1, 19]]
L = 560


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
    args = {"x": f(b, l, H, P), "dt": jax.nn.softplus(f(b, l, H) - 1.0),
            "a": -jnp.arange(1.0, H + 1.0), "b": f(b, l, N), "c": f(b, l, N),
            "d": f(H)}
    return args, jnp.asarray(seg)


def recurrence(args, seg):
    """The reference, a sequence at a time."""
    first = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    return jnp.stack([
        ref.ssd_recurrence(args["x"][n], args["dt"][n], args["a"],
                           args["b"][n], args["c"][n], args["d"], first[n],
                           None, lambda fn: fn)
        for n in range(seg.shape[0])])


def both(chunk, lengths=LENGTHS, l=L):
    """{name: (got, want)} for the output `y` and each gradient of a
    weighted sum of it."""
    args, seg = inputs(lengths, l)
    weight = jnp.asarray(np.random.default_rng(5).standard_normal(
        (len(lengths), l, H, P)), jnp.float32)

    def program(args):
        with jax.default_matmul_precision("highest"):
            y = ssd_scan(*(args[k] for k in NAMES), seg, chunk)
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


@pytest.fixture(scope="module", params=[16, 64, 128, 256],
                ids=lambda chunk: f"chunk{chunk}")
def pairs(request):
    return both(request.param)


@pytest.mark.parametrize("name", ("y",) + NAMES)
def test_the_chunked_scan_equals_the_recurrence(pairs, name):
    """First tokens at every offset of a chunk, at both edges of a chunk
    of every size and in one-token histories: the output and the
    gradient of every input, at every chunk size alike."""
    close(pairs[name])


@pytest.mark.parametrize("chunk", [16, 64, 256])
def test_any_chunk_gives_the_same_result(chunk):
    """The chunk is the program's, not the function's."""
    args, seg = inputs()
    with jax.default_matmul_precision("highest"):
        one = ssd_scan(*(args[k] for k in NAMES), seg, 32)
        other = ssd_scan(*(args[k] for k in NAMES), seg, chunk)
    close((other, one))


def test_a_sequence_no_chunk_divides():
    args, seg = inputs([[40, 3, 27]], 70)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*(args[k] for k in NAMES), seg, 64)
        close((got, recurrence(args, seg)))


def test_the_state_does_not_cross_a_history():
    """The tokens of a history read nothing of the one before it: moved
    behind another history they come out the same."""
    args, seg = inputs([[100, 60]], 160)
    with jax.default_matmul_precision("highest"):
        whole = ssd_scan(*(args[k] for k in NAMES), seg, 64)
        alone = ssd_scan(*(v[:, 100:] if v.ndim > 1 else v
                           for v in (args[k] for k in NAMES)),
                         seg[:, 100:], 64)
    close((whole[:, 100:], alone), 1e-5)
    # and without the boundary they do not
    with jax.default_matmul_precision("highest"):
        run_on = ssd_scan(*(args[k] for k in NAMES), jnp.ones_like(seg), 64)
    assert np.abs(np.asarray(run_on[:, 100:] - alone)).max() > 1e-2


def test_lower_precision_operands_stay_near():
    """bfloat16 operands in the four products, float32 sums, decays and
    state: within bfloat16's rounding of the float32 result."""
    args, seg = inputs()
    with jax.default_matmul_precision("highest"):
        want = recurrence(args, seg)
    got = ssd_scan(*(args[k] for k in NAMES), seg, 64, jnp.bfloat16)
    assert got.dtype == jnp.float32
    err = np.abs(np.asarray(got - want)).max()
    assert 0 < err <= 0.03 * np.abs(np.asarray(want)).max()


def test_the_path_built_is_counted():
    family = REGISTRY.get("encoder_ssd_scan_calls_total")
    before = dict(family.collect())
    args, seg = inputs([[10, 6]], 16)
    ssd_scan(*(args[k] for k in NAMES), seg, 16)
    after = dict(family.collect())
    key = next(k for k in after if "jnp" in str(k))
    assert after[key] == before.get(key, 0) + 1

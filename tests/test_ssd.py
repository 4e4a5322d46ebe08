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


def both(chunk, lengths=LENGTHS, l=L, args=None):
    """{name: (got, want)} for the output `y` and each gradient of a
    weighted sum of it; `args`: other inputs than `inputs` makes."""
    made, seg = inputs(lengths, l)
    args = made if args is None else args
    weight = jnp.asarray(np.random.default_rng(5).standard_normal(
        args["x"].shape), jnp.float32)

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


def grouped(groups, chunk=64, heads=8):
    """`both` with `heads` heads over `groups` groups of B and C ([B, L,
    G, N]; one group as the plain [B, L, N]), first tokens anywhere in a
    chunk."""
    rng = np.random.default_rng(20 + groups)
    b, l = len(LENGTHS), L
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    bc = (b, l, N) if groups == 1 else (b, l, groups, N)
    return both(chunk, args={
        "x": f(b, l, heads, P), "dt": jax.nn.softplus(f(b, l, heads) - 1.0),
        "a": -jnp.arange(1.0, heads + 1.0), "b": f(*bc), "c": f(*bc),
        "d": f(heads)})


@pytest.fixture(scope="module", params=[1, 2, 8],
                ids=lambda groups: f"groups{groups}")
def grouped_pairs(request):
    return grouped(request.param)


@pytest.mark.parametrize("name", ("y",) + NAMES)
def test_groups_of_b_and_c_equal_the_recurrence(grouped_pairs, name):
    """Head h reads B and C of group h // (heads / groups) in the update
    and in the read-out, with resets anywhere in a chunk: 1, 2 and 8
    groups of 8 heads against the recurrence a token at a time, the
    output and the gradient of every input."""
    close(grouped_pairs[name])


def test_one_group_given_as_a_group_axis_is_the_plain_scan():
    args, seg = inputs()
    with jax.default_matmul_precision("highest"):
        plain = ssd_scan(*(args[k] for k in NAMES), seg, 64)
        one = ssd_scan(*(args[k][:, :, None] if k in "bc" else args[k]
                         for k in NAMES), seg, 64)
    close((one, plain))


def test_groups_that_do_not_divide_the_heads_are_refused():
    args, seg = inputs()
    three = {**args, "b": jnp.zeros((2, L, 3, N)), "c": jnp.zeros((2, L, 3, N))}
    with pytest.raises(ValueError, match="3 B/C groups do not divide 4"):
        ssd_scan(*(three[k] for k in NAMES), seg, 64)


def test_the_gated_group_norm_is_a_norm_a_group_after_the_gate():
    from predictionio_tpu.ops.ssd import gated_group_norm

    rng = np.random.default_rng(3)
    y, z = (jnp.asarray(rng.standard_normal((5, 24)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.standard_normal(24), jnp.float32)
    got = gated_group_norm(y, z, w, 4, 1e-5)
    gated = np.asarray(y * jax.nn.silu(z), np.float64).reshape(5, 4, 6)
    want = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(5, 24) * np.asarray(w, np.float64)
    close((got, want))
    # one group is the norm over all channels; four are not
    whole = gated.reshape(5, 24)
    whole = whole / np.sqrt((whole ** 2).mean(-1, keepdims=True) + 1e-5) * w
    close((gated_group_norm(y, z, w, 1, 1e-5), whole))
    assert np.abs(np.asarray(got) - whole).max() > 1e-2


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


def test_the_path_built_is_counted(monkeypatch):
    """`jnp` on a CPU, whatever the shape; `kernel` on a TPU at a shape
    the kernels take (here the test says "tpu" and nothing runs: the
    kernels' call is what is counted, not what it computes)."""
    from predictionio_tpu.ops import pallas_ssd

    family = REGISTRY.get("encoder_ssd_scan_calls_total")
    built = lambda path: family.labels(path=path).value  # noqa: E731
    before = {path: built(path) for path in ("jnp", "kernel")}
    args, seg = inputs([[10, 6]], 16)
    ssd_scan(*(args[k] for k in NAMES), seg, 16)
    assert (built("jnp"), built("kernel")) == (before["jnp"] + 1,
                                               before["kernel"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_ssd, "ssd_chunks",
                        lambda x, *a, **kw: x.astype(jnp.float32))
    rng = np.random.default_rng(0)
    wide = {**args, "x": jnp.zeros((1, 16, H, 64)),
            "b": jnp.asarray(rng.standard_normal((1, 16, 128)), jnp.float32)}
    wide["c"] = wide["b"]
    # a CPU's shape on a TPU: still jnp; the kernels' widths: kernel
    ssd_scan(*(args[k] for k in NAMES), seg, 128)
    assert (built("jnp"), built("kernel")) == (before["jnp"] + 2,
                                               before["kernel"])
    ssd_scan(*(wide[k] for k in NAMES), seg, 128)
    assert (built("jnp"), built("kernel")) == (before["jnp"] + 2,
                                               before["kernel"] + 1)

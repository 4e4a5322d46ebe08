"""`ops/pallas_attention.py`: the segment-attention kernels in interpret
mode against `dense_attention` and against `ops/attention.py`'s blockwise
`jax.numpy` path, the output and dq, dk, dv, at both cells' head widths
(192 | 128 and 64 | 128), with and without a window shorter than a
history, with float32 operands (tight) and bfloat16 (the cells'); that a
history never reads another and a late first key tile nothing before it;
what a first call pays; and which path `segment_attention` takes. CPU,
seeded inputs, tiles of 32 x 16 forward and 16 x 32 backward (the shipped
tiles once, at two of their largest side)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import attention, pallas_attention
from predictionio_tpu.telemetry import spans
from predictionio_tpu.telemetry.registry import REGISTRY

B, H, LENGTH = 2, 2, 128
TILES = {"fwd": (32, 16), "bwd": (16, 32)}
BLOCK = 32  # the `jax.numpy` path's
WHAT = ("o", "dq", "dk", "dv")
WIDTHS = {"192_128": (192, 128), "64_128": (64, 128)}
WINDOWS = {"no_window": None, "window_20": 20}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# the histories' lengths a row; a row's rest is padding (segment id 0)
CASES = {
    # first tokens on a query tile's edge, on a key tile's, on both
    "on_the_tiles_edges": [[32, 48, 48], [64, 64]],
    "inside_a_tile": [[50, 30, 48], [100, 3, 5, 20]],
    "one_history_and_a_padded_tail": [[128], [70, 20]],
}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(pallas_attention, "TILES", TILES)


def packed(length, rows):
    """(segment ids, positions) [B, length] as `pack_histories` makes
    them: padding is segment 0 and counts from where it starts."""
    seg = np.zeros((len(rows), length), np.int32)
    pos = np.zeros_like(seg)
    for b, lens in enumerate(rows):
        at = 0
        for n, ln in enumerate(lens):
            seg[b, at:at + ln] = n + 1
            pos[b, at:at + ln] = np.arange(ln)
            at += ln
        pos[b, at:] = np.arange(length - at)
    return jnp.asarray(seg), jnp.asarray(pos)


def inputs(dk, dv, dtype, length=LENGTH, seed=1):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, H, length, d)), dtype)
                 for d in (dk, dk, dv))


def kernel(seg, pos, window):
    return lambda q, k, v: pallas_attention.segment_pairs(
        q, k, v, pos, q.shape[-1] ** -0.5, "attention.segment", window,
        interpret=True)


def blockwise(seg, pos, window):
    return lambda q, k, v: attention._segment_attention(
        q, k, v, seg, attention.first_key_blocks(pos, BLOCK, window), BLOCK,
        q.shape[-1] ** -0.5, "attention.segment", window)


def dense(seg, pos, window):
    # the oracle: float32 mathematics on the operands as they are
    return lambda q, k, v: attention.dense_attention(
        *(a.astype(jnp.float32) for a in (q, k, v)), causal=True,
        segment_ids=seg, window=window)


def _run(make, window, args, seg, pos):
    fn = make(seg, pos, window)
    weights = jnp.asarray(np.random.default_rng(5).standard_normal(
        args[2].shape), jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                     argnums=(0, 1, 2))(*args)
    return (fn(*args),) + grads


def named(results):
    return dict(zip(WHAT, (np.asarray(a, np.float32) for a in results)))


def outputs_and_gradients(make, window, args, seg, pos):
    return named(jax.jit(lambda *a: _run(make, window, *a))(args, seg, pos))


def error(got, want):
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.abs(got - want).max()) / max(float(np.abs(want).max()),
                                                 1e-6)


@pytest.fixture(scope="module")
def computed():
    """(kernel, blockwise jax.numpy, dense) of a case, each the output
    and three gradients, computed once a module; one compilation of each
    for every (widths, window, dtype)."""
    compiled, cache = {}, {}

    def get(case, widths, window, dtype):
        key = (widths, window, dtype)
        if key not in compiled:
            compiled[key] = tuple(
                jax.jit(lambda *a, make=make: _run(make, WINDOWS[window],
                                                   *a))
                for make in (kernel, blockwise, dense))
        if (case,) + key not in cache:
            seg, pos = packed(LENGTH, CASES[case])
            args = inputs(*WIDTHS[widths], DTYPES[dtype])
            # the ids are arguments: one compilation a shape, not a case
            cache[(case,) + key] = tuple(named(run(args, seg, pos))
                                         for run in compiled[key])
        return cache[(case,) + key]

    return get


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("widths", list(WIDTHS))
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_the_jnp_path(computed, case, widths, window, dtype,
                                        what):
    """Same mathematics, same precision: in float32 to rounding (a row's
    key tiles are summed in another order, nothing else differs); with
    bfloat16 operands no further from the float32 oracle than the
    `jax.numpy` path's own rounding puts it."""
    got, want, oracle = computed(case, widths, window, dtype)
    if dtype == "float32":
        assert error(got[what], want[what]) <= 2e-6
    else:
        assert error(got[what], want[what]) <= 1e-2
        assert error(got[what], oracle[what]) <= (
            1.5 * error(want[what], oracle[what]) + 1e-3)


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("widths", list(WIDTHS))
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_equal_dense_attention(computed, case, widths, window,
                                           what):
    got, _, want = computed(case, widths, window, "float32")
    assert error(got[what], want[what]) <= 2e-6


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_the_shipped_tile_equals_dense_attention(monkeypatch, widths):
    """The tile the chip runs (the other tests' is smaller), at four
    query tiles' length: a window and first tokens inside a tile."""
    monkeypatch.undo()
    tq = max(t for tile in pallas_attention.TILES.values() for t in tile)
    length = 2 * tq
    seg, pos = packed(length, [[tq + 40, tq - 90, 50], [37, length - 37]])
    args = inputs(*WIDTHS[widths], jnp.float32, length)
    got, want = (outputs_and_gradients(make, tq // 2 + 3, args, seg, pos)
                 for make in (kernel, dense))
    for what in WHAT:
        assert error(got[what], want[what]) <= 2e-6, what


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("window", list(WINDOWS))
def test_a_history_never_reads_another_in_the_kernels(window, dtype):
    """Exactly: what one history's keys and values hold moves neither the
    rows nor the gradients of the others by a bit (the mask is a select,
    not a small number)."""
    histories = [40, 9, 30, 32, 17]
    seg, pos = packed(LENGTH, [histories, histories[::-1]])
    args = inputs(192, 128, DTYPES[dtype], seed=3)
    lo, hi = 49, 79  # the third history of row 0
    q, k, v = args
    other = (q,) + tuple(a.at[0, :, lo:hi].set(b[0, :, lo:hi]) for a, b in
                         zip((k, v), inputs(192, 128, DTYPES[dtype],
                                            seed=4)[1:]))
    a, b = (outputs_and_gradients(kernel, WINDOWS[window], x, seg, pos)
            for x in (args, other))
    outside = np.ones(LENGTH, bool)
    outside[lo:hi] = False
    for name in WHAT:
        assert np.array_equal(a[name][0][:, outside],
                              b[name][0][:, outside]), name
        assert np.array_equal(a[name][1], b[name][1]), name
    assert not np.array_equal(a["o"][0][:, lo:hi], b["o"][0][:, lo:hi])


@pytest.mark.parametrize("window", list(WINDOWS))
def test_a_late_first_key_tile_reads_nothing_before_it(window):
    """The second history starts on a tile's edge, so every query tile of
    it starts its walk there: with the tiles before it full of NaN, its
    rows and its gradients are what they were, bit for bit (a tile that
    was read and masked would have left NaN: 0 x NaN)."""
    first = 64  # two query tiles forward, four backward
    seg, pos = packed(LENGTH, [[first, LENGTH - first]] * B)
    q, k, v = inputs(64, 128, jnp.float32, seed=6)
    poisoned = tuple(a.at[:, :, :first].set(jnp.nan) for a in (k, v))
    clean, dirty = (outputs_and_gradients(kernel, WINDOWS[window], x, seg, pos)
                    for x in ((q, k, v), (q,) + poisoned))
    for name in WHAT:
        assert np.isnan(dirty[name][:, :, :first]).any()
        assert np.array_equal(clean[name][:, :, first:],
                              dirty[name][:, :, first:]), name


def test_the_first_tile_list_is_first_key_blocks_at_the_kernels_tile():
    """What rides in as scalar prefetch: for a query tile of 32 the key
    tile of 16 that holds its first token's first visible key."""
    _, pos = packed(LENGTH, [[50, 30, 48]])
    tq, tk = TILES["fwd"]
    first = pallas_attention.first_visible(pos, 20)
    assert first[0, [0, 32, 49, 50, 64, 96]].tolist() == [0, 13, 30, 50, 50,
                                                          80]
    for window in (None, 20):
        first = pallas_attention.first_visible(pos, window)
        lo = attention.first_key_blocks(pos, tk, window)[:, ::tq // tk]
        assert np.array_equal(np.asarray(first)[:, ::tq] // tk,
                              np.asarray(lo))
    assert lo.tolist() == [[0, 0, 3, 5]]  # 0, 13 // 16, 50 // 16, 80 // 16


def test_each_body_is_traced_once_a_process(monkeypatch):
    """What a first call pays (PERF.md, PR 29 and PR 34): a train step
    holds nine of these kernels and Pallas traces a kernel's
    function in every `pallas_call`; the bodies are jits over the refs,
    so a second call site of the same shape finds its trace."""
    traced = []
    real = pallas_attention._visible

    def counted(*a):
        traced.append(1)
        return real(*a)

    def forget():
        for cached in vars(pallas_attention).values():
            if hasattr(cached, "cache_clear"):
                cached.cache_clear()

    monkeypatch.setattr(pallas_attention, "_visible", counted)
    forget()
    try:
        seg, pos = packed(LENGTH, [[100, 28], [128]])
        args = inputs(192, 128, jnp.bfloat16, seed=7)
        fn = kernel(seg, pos, None)
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2))
        jax.jit(lambda *a: (fn(*a), grad(*a))).lower(*args)
        assert len(traced) == 2  # the forward body, the backward body
        # other call sites, another program: nothing is traced again
        jax.jit(lambda *a: (fn(*a) * 2.0, grad(*a), fn(*a))).lower(*args)
        assert len(traced) == 2
    finally:
        forget()


@pytest.mark.parametrize("l,dk,dv,itemsize,takes", [
    (8192, 192, 128, 2, True), (8192, 64, 128, 2, True),
    (8192, 128, 128, 4, True), (1024, 192, 128, 2, True),
    (8192 + 128, 192, 128, 2, False),   # not whole query tiles
    (8192, 96, 128, 2, False), (8192, 192, 64, 2, False),
    (8192, 192, 128, 1, False),
    (65536, 192, 128, 2, False)])       # a head's length outgrows VMEM
def test_which_shapes_the_kernels_take(monkeypatch, l, dk, dv, itemsize,
                                       takes):
    monkeypatch.undo()  # the shipped tile
    assert pallas_attention.applicable(l, dk, dv, itemsize) is takes


def _built(path):
    return REGISTRY.get("encoder_segment_attention_calls_total").labels(
        path=path).value


@pytest.mark.parametrize("backend,length,path", [
    ("cpu", 128, "jnp"), ("tpu", 128, "kernel"), ("tpu", 144, "jnp")])
def test_segment_attention_decides_from_the_backend_and_the_shape(
        monkeypatch, backend, length, path):
    """One `segment_attention`, no option: a TPU and a shape the kernels
    admit -> the kernels (run here in interpret mode by a spy), else
    `jax.numpy` (a sequence that is not whole query tiles, and the CPU);
    counted and left in the timeline either way."""
    taken = []
    real = pallas_attention.segment_pairs

    def spy(*a, **kw):
        taken.append(a[5])
        return real(*a, **kw, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pallas_attention, "segment_pairs", spy)
    seg, pos = packed(length, [[length - 50, 30], [length]])
    q, k, v = inputs(64, 128, jnp.float32, length)
    before = {p: _built(p) for p in pallas_attention._PATHS}
    tl, token = spans.begin("test", "train", "RUN", "t-1")
    try:
        got = attention.segment_attention(q, k, v, seg, pos, block=16,
                                          scope="enc.mla", window=40)
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    assert taken == (["enc.mla"] if path == "kernel" else [])
    built = {p: _built(p) - before[p] for p in pallas_attention._PATHS}
    assert built == {p: float(p == path) for p in pallas_attention._PATHS}
    assert [name for name, *_ in tl.spans] == [f"enc.attention.{path}"]
    want = attention.dense_attention(q, k, v, causal=True, segment_ids=seg,
                                     window=40)
    assert error(np.asarray(got), np.asarray(want)) <= 2e-6


def test_one_block_takes_neither_path(monkeypatch):
    """A sequence one block holds is `dense_attention`, on a TPU too, and
    is not counted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seg, pos = packed(LENGTH, [[100, 28], [128]])
    before = {p: _built(p) for p in pallas_attention._PATHS}
    attention.segment_attention(*inputs(64, 128, jnp.float32), seg, pos,
                                block=LENGTH)
    assert before == {p: _built(p) for p in pallas_attention._PATHS}

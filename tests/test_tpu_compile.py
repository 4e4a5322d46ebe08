"""The main path's Pallas kernels compiled for a TPU that is described,
not attached (no chip here): what Mosaic refuses (a slice off the tiling,
too much VMEM, an index it cannot lower) fails here and costs no chip
time. Interpret mode cannot show any of that. Nothing runs, so no result
and no time is checked.

The topology is described inside a fixture, never at import: only the
worker that is handed this file loads the TPU's library."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from predictionio_tpu.ops import pallas_solve


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, r, k):
    a = jax.ShapeDtypeStruct((r, k, k), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((r, k), jnp.float32, sharding=one_chip)

    def solve(a, b):
        with jax.named_scope("als.solve"):
            return pallas_solve.gj_solve(a, b)

    return jax.jit(solve).lower(a, b).compile().as_text()


# the hot bucket of an ML-20M train (a ragged last block: 31296 = 244.5
# blocks), a fold's handful of rows under one block, a rank off the
# sublane tile, a rank between the cells', the rank-128 cell's hot bucket
# (als128i.train10: a block of 8.45 MB, 32 MiB of VMEM asked for) and its
# fold's rows, the largest block `gj_applicable` admits (104 MiB)
@pytest.mark.parametrize("r,k", [(31296, 64), (8, 64), (40, 10), (1000, 88),
                                 (31232, 128), (8, 128), (1000, 256)])
def test_lanes_solver_compiles_for_v5e(one_chip, r, k):
    text = _compiled_text(one_chip, r, k)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the benchmark's readers find the kernel by its scope and its call
    assert "als.solve" in text and "gj_lanes" in text


# a bucket's height (what fits the chip beside its temporaries at this
# order) and a fold's rows
@pytest.mark.parametrize("r", [1024, 8])
def test_schur_solver_compiles_for_v5e(one_chip, r):
    """Order 288, the first whose lanes block does not fit VMEM, goes
    288 -> 144 -> 72 -> 36 -> 18: sixteen base kernels (the one-hot
    multi-RHS kernel) with MXU products between them, all under the
    scope the benchmark's readers look for."""
    assert pallas_solve.layout_for(288) == "schur"
    text = _compiled_text(one_chip, r, 288)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 16
    assert all("als.solve" in line for line in calls)
    assert "gj_lanes" not in text


@pytest.mark.parametrize("k", [64, 128])
def test_lanes_solver_compiles_a_device_under_shard_map(topo, k):
    """`als_train`'s branch for a mesh: each of four chips solves its own
    row shard, padded to whole blocks of 128 lanes by itself, with no
    collective; at the rank-128 cell's order too."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows = NamedSharding(mesh, PartitionSpec("data"))
    a = jax.ShapeDtypeStruct((4 * 1000, k, k), jnp.float32, sharding=rows)
    b = jax.ShapeDtypeStruct((4 * 1000, k), jnp.float32, sharding=rows)
    spec = PartitionSpec("data")
    solve = jax.shard_map(pallas_solve.gj_solve, mesh=mesh,
                          in_specs=(spec, spec), out_specs=spec,
                          check_vma=False)
    text = jax.jit(solve).lower(a, b).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "gj_lanes" in text and "all-" not in text


def _kda_pass(one_chip, monkeypatch, d):
    """`kda_scan`, forward and all five gradients, compiled for the
    described chip at the size of one pass of `kimi_linear.fit8_pack8k`'s
    mixer (2 x 8192 tokens, 4 heads of a d x d state, chunks of 64,
    bfloat16 operands). The backend here is the CPU, so the test says
    "tpu" where `kda_scan` asks (it is the kernel that is compiled for
    the chip, by Mosaic; nothing runs)."""
    from predictionio_tpu.ops import kda

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)
    wide, narrow = shape(2, 8192, 4, d), shape(2, 8192, 4)
    seg = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def loss(q, k, v, log_a, beta, seg):
        return jnp.sum(kda.kda_scan(q, k, v, log_a, beta, seg, 64,
                                    jnp.bfloat16, "enc.kda.scan"))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, wide, narrow, seg).compile()


def test_a_pass_of_the_kda_scan_compiles_for_v5e_at_the_cells_size(
        one_chip, monkeypatch):
    """Two kernels (`ops/pallas_kda.py`): the forward pass that keeps
    each chunk's incoming state, and the backward pass; both under the
    scope the benchmark's readers book the scan's time to, the backward
    one too, which is traced outside the caller's scopes. What Mosaic
    refuses or the chip cannot fit fails here."""
    compiled = _kda_pass(one_chip, monkeypatch, 128)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert all("enc.kda.scan" in line for line in calls)
    assert sum("kda_chunks_fwd" in line for line in calls) == 1
    assert sum("kda_chunks_bwd" in line for line in calls) == 1
    # one pass of four heads, residuals and all, stays under the 3 GiB
    # held since the scan was plain `jax.numpy` (the kernels: the saved
    # states, 64 MiB, and the flat tensors round them)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30


def test_a_scan_the_kernels_decline_takes_the_jnp_path(one_chip,
                                                        monkeypatch):
    """Heads of 64 x 64 are not whole lane tiles: `kda_scan` builds the
    plain `jax.numpy` scan, on a TPU too."""
    text = _kda_pass(one_chip, monkeypatch, 64).as_text()
    assert "tpu_custom_call" not in text and "enc.kda.scan" in text


def _ssd_pass(one_chip, monkeypatch, groups, chunk, p=64):
    """`ssd_scan`, forward and all six gradients, compiled for the
    described chip at the size of one pass of a Mamba-2 mixer of
    `granite4h.fit16_pack8k` or `nemotron3nano.fit16_pack8k` (1 x 8192
    tokens, 4096 channels in heads of p, a state of 128, bfloat16
    operands), the test saying "tpu" where `ssd_scan` asks."""
    from predictionio_tpu.ops import ssd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)
    h = 4096 // p
    bc = shape(1, 8192, 128) if groups == 1 else shape(1, 8192, groups, 128)
    seg = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)

    def loss(x, dt, a, b, c, d, seg):
        return jnp.sum(ssd.ssd_scan(x, dt, a, b, c, d, seg, chunk,
                                    jnp.bfloat16, "enc.ssd.scan"))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        shape(1, 8192, h, p), shape(1, 8192, h), shape(h), bc, bc, shape(h),
        seg).compile()


@pytest.mark.parametrize("groups,chunk", [(1, 256), (8, 128)])
def test_a_pass_of_the_ssd_scan_compiles_for_v5e_at_the_cells_sizes(
        one_chip, monkeypatch, groups, chunk):
    """Two kernels (`ops/pallas_ssd.py`), one group at Granite's chunk
    of 256 and eight at Nemotron's 128 through the same bodies: the
    forward pass that keeps each chunk's incoming states, and the
    backward pass; both under the scope the benchmark's readers book the
    scan's time to. What Mosaic refuses or the chip cannot fit fails
    here."""
    compiled = _ssd_pass(one_chip, monkeypatch, groups, chunk)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert all("enc.ssd.scan" in line for line in calls)
    # by the kernel's own name on the op's path (a backward call's
    # operands are named after the forward kernel that made them)
    assert sum("ssd_chunks_fwd/pallas_call" in line for line in calls) == 1
    assert sum("ssd_chunks_bwd/pallas_call" in line for line in calls) == 1
    # the saved states (8192 / chunk x 64 heads x 32 KiB) and the flat
    # tensors round them; the [chunks, heads, Q, Q] tensors alone were
    # 0.5 GiB each
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_an_ssd_scan_the_kernels_decline_takes_the_jnp_path(one_chip,
                                                           monkeypatch):
    """Heads of 128 channels are not the pairs the body takes:
    `ssd_scan` builds the plain `jax.numpy` scan, on a TPU too."""
    text = _ssd_pass(one_chip, monkeypatch, 1, 256, p=128).as_text()
    assert "tpu_custom_call" not in text and "enc.ssd.scan" in text


# a pass of each cell's convolution: Kimi's q (its heads normed) or v of
# four heads, Phi's Mamba layer, Granite's and Nemotron's xBC where it stands in
# [z | xBC | dt] (total, first, channels, a bias)
@pytest.mark.parametrize("seqs,total,first,c,bias,unit", [
    (2, 512, 0, 512, False, (128, 1e-6, 128 ** -0.5)),
    (2, 512, 0, 512, False, None), (2, 5120, 0, 5120, True, None),
    (1, 8512, 4096, 4352, True, None), (1, 10304, 4096, 6144, True, None)])
def test_a_pass_of_the_convolution_compiles_for_v5e_at_the_cells_sizes(
        one_chip, monkeypatch, seqs, total, first, c, bias, unit):
    """Two kernels (`ops/pallas_conv.py`), the forward pass and the
    hand-written backward pass, the second under the scope it is handed:
    what Mosaic refuses (a shift off the sublane tiling, a block off the
    lane tiles) fails here. A window's slice is not copied: beside the
    output and the cotangents the program holds no array of x's size."""
    from predictionio_tpu.ops import kda

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((seqs, 8192), jnp.int32, sharding=one_chip)

    def loss(x, w, seg, *b):
        with jax.named_scope("enc.ssd.conv"):
            return jnp.sum(kda.causal_conv(
                x, w, seg, b[0] if b else None, first=first, silu=True,
                unit=unit, scope="enc.ssd.conv") ** 2)

    compiled = jax.jit(jax.grad(
        loss, argnums=(0, 1, 3) if bias else (0, 1))).lower(
            shape(seqs, 8192, total), shape(4, c), seg,
            *((shape(c),) if bias else ())).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert all("enc.ssd.conv" in line for line in calls)
    assert sum("causal_conv_fwd/pallas_call" in line for line in calls) == 1
    assert sum("causal_conv_bwd/pallas_call" in line for line in calls) == 1
    # y, dy and dx (and dx padded to the array's width): no copy of x
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * seqs * 8192 * (3 * c + total) + 2 ** 24


def test_the_bare_three_tap_convolution_compiles_for_v5e_at_lfm2s_size(
        one_chip, monkeypatch):
    """`lfm2.fit8_pack8k`'s convolution, a mixer of its own: three taps
    over 2048 channels of 2 x 8192 tokens, no bias, no SiLU, no unit
    norm, the gates' products XLA's on either side: the same two
    kernels under the mixer's scope."""
    from predictionio_tpu.ops import kda

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def loss(b, x, c, w, seg):
        with jax.named_scope("enc.sconv.conv"):
            mixed = kda.causal_conv(b * x, w, seg, scope="enc.sconv.conv")
        return jnp.sum(c * mixed)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        *(shape(2, 8192, 2048),) * 3, shape(3, 2048), seg).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("causal_conv_fwd/pallas_call" in line for line in calls) == 1
    assert sum("causal_conv_bwd/pallas_call" in line for line in calls) == 1
    assert all("enc.sconv.conv" in line for line in calls)


# the three encoder cells' attentions: joyai.fit8_pack8k's and
# kimi_linear.fit8_pack8k's MLA heads, phi4flash.fit8_pack8k's stacked
# differential heads with its window and without
@pytest.mark.parametrize("h,dk,window", [(32, 192, None), (40, 64, 512),
                                         (40, 64, None)])
def test_segment_attention_compiles_for_v5e_at_the_cells_sizes(
        one_chip, monkeypatch, h, dk, window):
    """Two kernels (`ops/pallas_attention.py`), forward and backward, at
    2 x 8192 tokens with bfloat16 operands: a head's keys, values and
    float32 gradient accumulators whole in VMEM. Both stand under the
    scope the benchmark's readers book attention to, the backward one
    too, which is traced outside the caller's scopes. The backend here
    is the CPU, so the test says "tpu" where `segment_attention` asks."""
    from predictionio_tpu.ops import attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda d, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (2, h, 8192, d), dt, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seg, pos):
        return jnp.sum(attention.segment_attention(
            q, k, v, seg, pos, block=512, scale=dk ** -0.5,
            scope="enc.attn.pairs", window=window))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(dk), shape(dk), shape(128), ids, ids).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert all("enc.attn.pairs" in line for line in calls)
    assert sum("segment_attention_fwd" in line for line in calls) == 1
    assert sum("segment_attention_bwd" in line for line in calls) == 1
    # the output, its cotangent in the operands' dtype, log-sum-exp and
    # delta: no [H, L, L] and no score tile goes through HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * 2 ** 30


def test_the_decoder_hybrid_decoders_step_compiles_for_v5e_at_the_cells_size(
        one_chip):
    """The whole train step of `phi4flash.fit8_pack8k` (2 x 8192 tokens,
    the published widths, 577 M parameters with their gradients and
    Adam moments in float32): the chip's compiler refuses a program
    that does not fit its 15.75 GiB, and every scope the benchmark's
    readers look for is in what it built."""
    from predictionio_tpu.models import encoder as enc

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = enc.EncoderConfig.from_json(os.path.join(
        root, "perf", "configs", "phi4_mini_flash_1of8.json"))
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    state = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda k: enc.init_state(cfg, cfg.vocab_size, k), jax.random.key(0)))
    batch = jax.ShapeDtypeStruct((cfg.seqs_per_step, cfg.pack_len),
                                 jnp.int32, sharding=one_chip)
    compiled = jax.jit(enc.train_step(cfg, 1e-5), donate_argnums=(0,)).lower(
        state, batch, batch, batch).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 12 * 577_199_232
    assert memory.peak_memory_in_bytes < 15.75 * 2 ** 30
    text = compiled.as_text()
    for scope in ("enc.mamba.scan", "enc.mamba.conv", "enc.mamba.dt",
                  "enc.attn.pairs", "enc.attn.subln", "enc.cross.pairs",
                  "enc.gmu", "enc.dense_ffn", "enc.head_loss", "enc.adam"):
        assert scope in text, scope


def test_the_mamba_2_hybrids_step_compiles_for_v5e_at_the_cells_size(
        one_chip, monkeypatch):
    """The whole train step of `granite4h.fit16_pack8k` (1 x 8192 tokens,
    the published widths, 772 M parameters with their gradients and Adam
    moments in float32: 9.27 GB of arguments before a temporary), as the
    chip builds it, with the attention kernels at 64 | 64 (the values
    padded to a lane tile): the chip's compiler refuses a program that
    does not fit its 15.75 GiB, and every scope the benchmark's readers
    look for is in what it built."""
    from predictionio_tpu.models import encoder as enc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = enc.EncoderConfig.from_json(os.path.join(
        root, "perf", "configs", "granite_4_0_h_micro_1of8.json"))
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    state = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda k: enc.init_state(cfg, cfg.vocab_size, k), jax.random.key(0)))
    batch = jax.ShapeDtypeStruct((cfg.seqs_per_step, cfg.pack_len),
                                 jnp.int32, sharding=one_chip)
    compiled = jax.jit(enc.train_step(cfg, 1e-5), donate_argnums=(0,)).lower(
        state, batch, batch, batch).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 12 * 772_160_448
    assert memory.peak_memory_in_bytes < 15.0 * 2 ** 30
    text = compiled.as_text()
    for scope in ("enc.ssd.proj", "enc.ssd.conv", "enc.ssd.dt",
                  "enc.ssd.scan", "enc.ssd.norm", "enc.ssd.out",
                  "enc.gqa.proj", "enc.gqa.pairs", "enc.gqa.out",
                  "enc.dense_ffn", "enc.head_loss", "enc.adam"):
        assert scope in text, scope
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum("segment_attention_fwd" in line for line in calls) == 2
    assert sum("segment_attention_bwd" in line for line in calls) == 1
    # nine Mamba-2 layers: forward, the block's recomputation (which keeps
    # the chunks' incoming states) and backward, all under the scan's scope
    scans = [line for line in calls if "ssd_chunks" in line]
    assert sum("ssd_chunks_fwd/pallas_call" in line for line in scans) == 18
    assert sum("ssd_chunks_bwd/pallas_call" in line for line in scans) == 9
    assert all("enc.ssd.scan" in line for line in scans)
    # and their convolutions, xBC read in place
    convs = [line for line in calls if "causal_conv" in line]
    assert sum("causal_conv_fwd/pallas_call" in line for line in convs) == 18
    assert sum("causal_conv_bwd/pallas_call" in line for line in convs) == 9
    assert all("enc.ssd.conv" in line for line in convs)

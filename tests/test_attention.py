"""Sequence-parallel attention on a real 8-device mesh: ring and Ulysses
must match dense attention exactly (long-context infrastructure — the
rebuild's first-class sequence-parallel story)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.ops.attention import (
    dense_attention,
    ring_attention,
    sequence_sharded_attention,
    ulysses_attention,
)
from predictionio_tpu.parallel.mesh import DATA_AXIS, make_mesh


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh({DATA_AXIS: 8})


def qkv(b=2, h=4, s=64, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    return mk(), mk(), mk()


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, mesh8, causal):
        q, k, v = qkv()
        want = dense_attention(q, k, v, causal=causal)
        got = ring_attention(q, k, v, mesh8, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_sharded_inputs_stay_sharded(self, mesh8):
        q, k, v = qkv()
        spec = NamedSharding(mesh8, P(None, None, DATA_AXIS, None))
        qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
        out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh8))(qs, ks, vs)
        assert out.sharding.spec == P(None, None, DATA_AXIS, None)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dense_attention(q, k, v)),
                                   rtol=2e-4, atol=2e-5)

    def test_rejects_indivisible_seq(self, mesh8):
        q, k, v = qkv(s=60)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention(q, k, v, mesh8)

    def test_long_sequence_causal(self, mesh8):
        # longer-than-block causality: every query only sees its past
        q, k, v = qkv(b=1, h=2, s=256, d=8, seed=3)
        got = ring_attention(q, k, v, mesh8, causal=True)
        want = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, mesh8, causal):
        q, k, v = qkv(h=8)
        want = dense_attention(q, k, v, causal=causal)
        got = ulysses_attention(q, k, v, mesh8, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_rejects_indivisible_heads(self, mesh8):
        q, k, v = qkv(h=4)  # 4 % 8 != 0
        with pytest.raises(ValueError, match="heads"):
            ulysses_attention(q, k, v, mesh8)


class TestDispatch:
    def test_auto_picks_ulysses_when_heads_divide(self, mesh8):
        q, k, v = qkv(h=8)
        got = sequence_sharded_attention(q, k, v, mesh8)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(dense_attention(q, k, v)),
                                   rtol=2e-4, atol=2e-5)

    def test_auto_falls_back_to_ring(self, mesh8):
        q, k, v = qkv(h=4)
        got = sequence_sharded_attention(q, k, v, mesh8)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(dense_attention(q, k, v)),
                                   rtol=2e-4, atol=2e-5)

    def test_unknown_method(self, mesh8):
        q, k, v = qkv()
        with pytest.raises(ValueError, match="Unknown method"):
            sequence_sharded_attention(q, k, v, mesh8, method="flash")


# -- rotation and a window on grouped-query attention (`models/encoder.py`) -------

class TestRotatedWindowedGroupedQuery:
    """`rope` in its two pairings and `gqa` with a window and rotation,
    each against the arithmetic written out in numpy."""

    @staticmethod
    def _rotate_half(x, positions, theta):
        d = x.shape[-1]
        inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        ang = positions[..., None, None] * inv
        lo, hi = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([lo * np.cos(ang) - hi * np.sin(ang),
                               lo * np.sin(ang) + hi * np.cos(ang)], -1)

    @pytest.mark.parametrize("what", ["pairs", "interleaved", "relative"])
    def test_half_rotation_rope(self, what):
        from predictionio_tpu.models import encoder as enc

        rng = np.random.default_rng(0)
        x = rng.standard_normal((2, 12, 3, 8))
        pos = np.tile(np.arange(12), (2, 1))
        got = np.asarray(enc.rope(jnp.asarray(x, jnp.float32),
                                  jnp.asarray(pos), 1.5e6, interleave=False))
        if what == "pairs":  # pair i is (x[i], x[i + d/2])
            np.testing.assert_allclose(
                got, self._rotate_half(x, pos, 1.5e6), atol=1e-5)
            np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)
        elif what == "interleaved":
            # the other pairing on the channels permuted into pairs
            perm = np.arange(8).reshape(2, 4).T.reshape(-1)
            other = np.asarray(enc.rope(
                jnp.asarray(x[..., perm], jnp.float32), jnp.asarray(pos),
                1.5e6))
            np.testing.assert_allclose(other, got[..., perm], atol=1e-5)
        else:  # a score reads the distance between two positions alone
            far = np.asarray(enc.rope(
                jnp.asarray(x, jnp.float32), jnp.asarray(pos + 100), 1.5e6,
                interleave=False))
            np.testing.assert_allclose(
                np.einsum("bqhd,bkhd->bhqk", got, got),
                np.einsum("bqhd,bkhd->bhqk", far, far), atol=2e-4)

    @pytest.mark.parametrize("window,rotate", [(None, False), (5, False),
                                               (None, True), (5, True)])
    def test_a_window_and_rotation_on_gqa(self, window, rotate):
        from predictionio_tpu.models import encoder as enc

        cfg = enc.EncoderConfig(
            hidden_size=16, intermediate_size=0, num_hidden_layers=1,
            num_attention_heads=4, num_key_value_heads=2, head_dim=6,
            rope_theta=100.0, rope_interleave=False, attention_block=8)
        rng = np.random.default_rng(1)
        p = {name: jnp.asarray(0.3 * rng.standard_normal(shape), jnp.float32)
             for name, shape in enc._gqa_shapes(cfg).items()}
        assert p["w_q"].shape == (16, 24) and p["w_o"].shape == (24, 16)
        x = rng.standard_normal((1, 32, 16))
        seg = np.array([[1] * 20 + [2] * 12])
        pos = np.array([list(range(20)) + list(range(12))])
        got = enc.gqa(p, cfg, jnp.asarray(x, jnp.float32), jnp.asarray(seg),
                      jnp.asarray(pos), window=window, rotate=rotate)
        w = {k: np.asarray(v, np.float64) for k, v in p.items()}
        q = (x[0] @ w["w_q"]).reshape(32, 4, 6)
        k = (x[0] @ w["w_k"]).reshape(32, 2, 6)
        v = (x[0] @ w["w_v"]).reshape(32, 2, 6)
        if rotate:
            q, k = (self._rotate_half(a, pos[0], 100.0) for a in (q, k))
        t = np.arange(32)
        mask = (t[None, :] <= t[:, None]) & (seg[0][:, None] == seg[0][None])
        if window:
            mask &= t[:, None] - t[None, :] < window
        out = np.zeros((32, 4, 6))
        for head in range(4):
            s = q[:, head] @ k[:, head // 2].T / np.sqrt(6)
            s = np.where(mask, s, -np.inf)
            prob = np.exp(s - s.max(-1, keepdims=True))
            out[:, head] = prob / prob.sum(-1, keepdims=True) @ v[:, head // 2]
        np.testing.assert_allclose(np.asarray(got[0]),
                                   out.reshape(32, 24) @ w["w_o"], atol=2e-5)

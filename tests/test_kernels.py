"""Per-kernel validation: sweep shapes/dtypes, assert allclose against the
ref.py pure-jnp oracles (Pallas kernels run in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: property tests skip, unit tests run
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jax.random.randint(key, shape, -8, 8, dtype)
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# conv2d_stream (the paper's centerpiece kernel)
# ---------------------------------------------------------------------------


class TestConv2dStream:
    @pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("k", [1, 3, 5])
    def test_kernel_sizes_dtypes(self, dtype, k):
        kx, kw = jax.random.split(jax.random.key(0))
        x = _rand(kx, (2, 12, 12, 4), dtype)
        w = _rand(kw, (k, k, 4, 8), dtype)
        out = ops.conv2d_stream(x, w)
        exp = ref.conv2d(x, w)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            atol=1e-2 if dtype == jnp.bfloat16 else 1e-4, rtol=1e-2,
        )

    @pytest.mark.parametrize("hw", [(8, 8), (16, 8), (9, 13), (32, 32)])
    def test_shapes(self, hw):
        h, w_ = hw
        kx, kw = jax.random.split(jax.random.key(1))
        x = _rand(kx, (1, h, w_, 3), jnp.int8)
        w = _rand(kw, (3, 3, 3, 16), jnp.int8)
        out = ops.conv2d_stream(x, w)
        exp = ref.conv2d(x, w)
        assert out.shape == exp.shape == (1, h, w_, 16)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))

    def test_fused_relu(self):
        kx, kw = jax.random.split(jax.random.key(2))
        x = _rand(kx, (1, 8, 8, 2), jnp.int8)
        w = _rand(kw, (3, 3, 2, 4), jnp.int8)
        out = ops.conv2d_stream(x, w, fuse_relu=True)
        exp = ref.conv2d(x, w, fuse_relu=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))
        assert (np.asarray(out) >= 0).all()

    @pytest.mark.parametrize("rows", [1, 2, 4])
    def test_rows_per_block_invariant(self, rows):
        """The DSE's row-tiling choice must not change results."""
        kx, kw = jax.random.split(jax.random.key(3))
        x = _rand(kx, (1, 10, 10, 3), jnp.int8)
        w = _rand(kw, (3, 3, 3, 4), jnp.int8)
        out = ops.conv2d_stream(x, w, rows_per_block=rows)
        exp = ref.conv2d(x, w)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))

    def test_int8_accumulates_int32(self):
        kx, kw = jax.random.split(jax.random.key(4))
        x = jnp.full((1, 8, 8, 64), 127, jnp.int8)
        w = jnp.full((3, 3, 64, 4), 127, jnp.int8)
        out = ops.conv2d_stream(x, w)
        assert out.dtype == jnp.int32
        exp = ref.conv2d(x, w)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))

    @pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("stride,k,hw,rows", [
        (2, 3, (13, 11), None), (2, 3, (12, 12), 2), (3, 5, (17, 14), 3),
        (3, 2, (12, 9), None), (2, 1, (8, 8), 4), (4, 3, (15, 15), None),
    ])
    def test_strided(self, dtype, stride, k, hw, rows):
        """Stride-s convs (phase-split columns, leading-axis row split)
        agree with the dense oracle, for any row blocking."""
        kx, kw = jax.random.split(jax.random.key(5))
        x = _rand(kx, (2,) + hw + (3,), dtype)
        w = _rand(kw, (k, k, 3, 5), dtype)
        for padding in ("SAME", "VALID"):
            out = ops.conv2d_stream(x, w, stride=stride, padding=padding,
                                    rows_per_block=rows)
            exp = ref.conv2d(x, w, stride=stride, padding=padding)
            assert out.shape == exp.shape
            if dtype == jnp.int8:
                np.testing.assert_array_equal(np.asarray(out),
                                              np.asarray(exp))
                continue
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(exp, np.float32),
                atol=1e-2 if dtype == jnp.bfloat16 else 1e-4, rtol=1e-2,
            )

    @pytest.mark.parametrize("dtypes", [(jnp.int32, jnp.int8),
                                        (jnp.int8, jnp.int16)],
                             ids=["int32_x", "int16_w"])
    def test_wide_integers_refused_when_compiled(self, dtypes):
        """The TPU kernel has no int16/int32 matmul: asking for the
        compiled kernel names the dtype rule instead of failing inside
        the TPU compiler."""
        from repro.kernels.conv2d_stream import KernelDtypeError

        x = jnp.ones((1, 8, 8, 4), dtypes[0])
        w = jnp.ones((3, 3, 4, 8), dtypes[1])
        with pytest.raises(KernelDtypeError, match="at most 8 bits"):
            ops.conv2d_stream(x, w, interpret=False)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("group", [1, 4])
    def test_gqa_causal(self, causal, group):
        ks = jax.random.split(jax.random.key(0), 3)
        hkv = 2
        q = _rand(ks[0], (2, hkv * group, 32, 16), jnp.float32)
        k = _rand(ks[1], (2, hkv, 32, 16), jnp.float32)
        v = _rand(ks[2], (2, hkv, 32, 16), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        exp = ref.attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("sq,sk,bq,bk", [
        (16, 16, 16, 16), (64, 64, 16, 32), (32, 64, 32, 16), (128, 128, 64, 64),
    ])
    def test_block_shapes(self, sq, sk, bq, bk):
        ks = jax.random.split(jax.random.key(1), 3)
        q = _rand(ks[0], (1, 4, sq, 32), jnp.float32)
        k = _rand(ks[1], (1, 4, sk, 32), jnp.float32)
        v = _rand(ks[2], (1, 4, sk, 32), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=False, block_q=bq, block_k=bk)
        exp = ref.attention(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5
        )

    def test_decode_q_offset(self):
        """Decode semantics: q at absolute position q_offset attends to the
        full prefix."""
        ks = jax.random.split(jax.random.key(2), 3)
        q = _rand(ks[0], (1, 2, 8, 16), jnp.float32)
        k = _rand(ks[1], (1, 2, 32, 16), jnp.float32)
        v = _rand(ks[2], (1, 2, 32, 16), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, q_offset=24,
                                  block_q=8, block_k=16)
        exp = ref.attention(q, k, v, causal=True, q_offset=24)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5
        )

    def test_bfloat16(self):
        ks = jax.random.split(jax.random.key(3), 3)
        q = _rand(ks[0], (1, 2, 32, 32), jnp.bfloat16)
        k = _rand(ks[1], (1, 2, 32, 32), jnp.bfloat16)
        v = _rand(ks[2], (1, 2, 32, 32), jnp.bfloat16)
        out = ops.flash_attention(q, k, v, block_q=16, block_k=16)
        exp = ref.attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            atol=3e-2, rtol=3e-2,
        )


# ---------------------------------------------------------------------------
# fused MLP
# ---------------------------------------------------------------------------


class TestFusedMlp:
    @pytest.mark.parametrize("act", ["silu", "gelu", "relu", "squared_relu"])
    @pytest.mark.parametrize("gated", [True, False])
    def test_acts_gating(self, act, gated):
        ks = jax.random.split(jax.random.key(0), 4)
        x = _rand(ks[0], (32, 64), jnp.float32)
        wg = _rand(ks[1], (64, 128), jnp.float32) * 0.1 if gated else None
        wu = _rand(ks[2], (64, 128), jnp.float32) * 0.1
        wd = _rand(ks[3], (128, 64), jnp.float32) * 0.1
        out = ops.fused_mlp(x, wg, wu, wd, act=act, block_m=16, block_f=32)
        exp = ref.mlp(x, wg, wu, wd, act=act)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), atol=5e-4, rtol=5e-4
        )

    @pytest.mark.parametrize("m,f,bm,bf", [
        (8, 32, 8, 32), (64, 256, 16, 64), (128, 512, 128, 128),
    ])
    def test_tilings(self, m, f, bm, bf):
        ks = jax.random.split(jax.random.key(1), 4)
        x = _rand(ks[0], (m, 32), jnp.float32)
        wg = _rand(ks[1], (32, f), jnp.float32) * 0.1
        wu = _rand(ks[2], (32, f), jnp.float32) * 0.1
        wd = _rand(ks[3], (f, 32), jnp.float32) * 0.1
        out = ops.fused_mlp(x, wg, wu, wd, block_m=bm, block_f=bf)
        exp = ref.mlp(x, wg, wu, wd)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), atol=5e-4, rtol=5e-4
        )

    def test_leading_dims(self):
        ks = jax.random.split(jax.random.key(2), 4)
        x = _rand(ks[0], (2, 8, 32), jnp.float32)
        wg = _rand(ks[1], (32, 64), jnp.float32) * 0.1
        wu = _rand(ks[2], (32, 64), jnp.float32) * 0.1
        wd = _rand(ks[3], (64, 32), jnp.float32) * 0.1
        out = ops.fused_mlp(x, wg, wu, wd, block_m=8, block_f=32)
        assert out.shape == x.shape
        exp = ref.mlp(x.reshape(16, 32), wg, wu, wd).reshape(2, 8, 32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), atol=5e-4, rtol=5e-4
        )


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------


class TestMamba2Ssd:
    def _inputs(self, key, b=2, l=32, h=4, p=8, n=8):
        ks = jax.random.split(key, 5)
        x = _rand(ks[0], (b, l, h, p), jnp.float32)
        dt = jax.nn.softplus(_rand(ks[1], (b, l, h), jnp.float32))
        a = -jnp.exp(_rand(ks[2], (h,), jnp.float32) * 0.3)
        bm = _rand(ks[3], (b, l, n), jnp.float32) * 0.5
        cm = _rand(ks[4], (b, l, n), jnp.float32) * 0.5
        return x, dt, a, bm, cm

    @pytest.mark.parametrize("chunk", [4, 8, 16, 32])
    def test_chunk_sizes_vs_sequential(self, chunk):
        x, dt, a, bm, cm = self._inputs(jax.random.key(0))
        y, sf = ops.mamba2_ssd(x, dt, a, bm, cm, chunk=chunk)
        ye, se = ref.ssd(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(sf), np.asarray(se),
                                   atol=1e-3, rtol=1e-3)

    def test_chunked_oracle_matches_sequential(self):
        """ref.ssd_chunked (the algorithm the kernel implements) must be
        exactly equivalent to the sequential recurrence."""
        x, dt, a, bm, cm = self._inputs(jax.random.key(1))
        y1, s1 = ref.ssd_chunked(x, dt, a, bm, cm, chunk=8)
        y2, s2 = ref.ssd(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=1e-4, rtol=1e-4)

    def test_init_state_carried(self):
        """Splitting a sequence across two kernel calls with state carry
        must equal one full-length call (the decode/prefill contract)."""
        x, dt, a, bm, cm = self._inputs(jax.random.key(2), l=32)
        y_full, s_full = ops.mamba2_ssd(x, dt, a, bm, cm, chunk=8)
        y1, s1 = ops.mamba2_ssd(
            x[:, :16], dt[:, :16], a, bm[:, :16], cm[:, :16], chunk=8
        )
        y2, s2 = ops.mamba2_ssd(
            x[:, 16:], dt[:, 16:], a, bm[:, 16:], cm[:, 16:],
            init_state=s1, chunk=8,
        )
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([y1, y2], axis=1)),
            np.asarray(y_full), atol=1e-3, rtol=1e-3,
        )
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                                   atol=1e-3, rtol=1e-3)

    def test_decode_step_matches_scan(self):
        """O(1) recurrent decode step == one step of the full scan."""
        x, dt, a, bm, cm = self._inputs(jax.random.key(3), l=8)
        _, state = ref.ssd(x[:, :7], dt[:, :7], a, bm[:, :7], cm[:, :7])
        y_step, s_step = ref.ssd_decode_step(
            state, x[:, 7], dt[:, 7], a, bm[:, 7], cm[:, 7]
        )
        y_full, s_full = ref.ssd(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full[:, 7]),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(s_step), np.asarray(s_full),
                                   atol=1e-4, rtol=1e-4)

    @given(st.integers(1, 4), st.integers(1, 3))
    @settings(max_examples=10, deadline=None)
    def test_property_random_shapes(self, b, h):
        x, dt, a, bm, cm = self._inputs(jax.random.key(4), b=b, l=16, h=h)
        y, sf = ops.mamba2_ssd(x, dt, a, bm, cm, chunk=8)
        ye, se = ref.ssd(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                                   atol=1e-3, rtol=1e-3)

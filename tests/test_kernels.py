"""Per-kernel allclose tests: Pallas (interpret=True) vs ref.py oracles,
swept over shapes/dtypes + hypothesis property tests (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.cim_gemm import cim_gemm_int8

# every test here drives the Pallas kernels through the CPU interpreter
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


def keys(n):
    return jax.random.split(KEY, n)


# ---------------------------------------------------------------------------
# cim_gemm
# ---------------------------------------------------------------------------
class TestCimGemm:
    @pytest.mark.parametrize("m,k,n", [(256, 128, 256), (512, 512, 512),
                                       (256, 1024, 512), (1024, 256, 1024)])
    def test_int8_exact(self, m, k, n):
        k1, k2 = keys(2)
        x = jax.random.randint(k1, (m, k), -127, 128, jnp.int8)
        w = jax.random.randint(k2, (k, n), -127, 128, jnp.int8)
        out = cim_gemm_int8(x, w, interpret=True)
        expect = ref.cim_gemm_int8_ref(x, w)
        assert (np.asarray(out) == np.asarray(expect)).all()

    @pytest.mark.parametrize("bm,bn,bk", [(256, 256, 128), (256, 512, 512)])
    def test_block_shape_invariance(self, bm, bn, bk):
        k1, k2 = keys(2)
        x = jax.random.randint(k1, (512, 512), -127, 128, jnp.int8)
        w = jax.random.randint(k2, (512, 512), -127, 128, jnp.int8)
        out = cim_gemm_int8(x, w, block_m=bm, block_n=bn, block_k=bk,
                            interpret=True)
        assert (np.asarray(out) ==
                np.asarray(ref.cim_gemm_int8_ref(x, w))).all()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_quantized_matmul_close_to_float(self, dtype):
        k1, k2 = keys(2)
        x = jax.random.normal(k1, (64, 256), dtype)
        w = jax.random.normal(k2, (256, 384), jnp.float32) * 0.1
        w_q, w_s = ops.quantize_weights_int8(w)
        out = ops.cim_quantized_matmul(x, w_q, w_s, interpret=True)
        expect = x.astype(jnp.float32) @ w
        rel = np.abs(np.asarray(out) - np.asarray(expect)) / \
            (np.abs(np.asarray(expect)) + 1e-2)
        assert np.median(rel) < 0.05  # int8 quantization error budget

    def test_quantized_matches_ref_path(self):
        k1, k2 = keys(2)
        x = jax.random.normal(k1, (32, 128), jnp.float32)
        w = jax.random.normal(k2, (128, 256), jnp.float32)
        w_q, w_s = ops.quantize_weights_int8(w)
        out = ops.cim_quantized_matmul(x, w_q, w_s, interpret=True)
        expect = ref.quantized_matmul_ref(x, w_q, w_s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)

    @given(m=st.sampled_from([256, 512]), k=st.sampled_from([128, 256, 384]),
           n=st.sampled_from([256, 512, 768]))
    @settings(max_examples=8, deadline=None)
    def test_property_shapes(self, m, k, n):
        k1, k2 = keys(2)
        x = jax.random.randint(k1, (m, k), -127, 128, jnp.int8)
        w = jax.random.randint(k2, (k, n), -127, 128, jnp.int8)
        out = cim_gemm_int8(x, w, interpret=True)
        assert (np.asarray(out) ==
                np.asarray(ref.cim_gemm_int8_ref(x, w))).all()


# ---------------------------------------------------------------------------
# fused INT8 epilogue pipeline (quant -> GEMM -> dequant/bias/act)
# ---------------------------------------------------------------------------
RAGGED_SHAPES = [(48, 200, 300),    # nothing block-aligned
                 (17, 128, 256),    # ragged M only
                 (256, 512, 384),   # block-multiple M/K, ragged N
                 (512, 512, 512)]   # fully aligned


class TestQuantizeRows:
    @pytest.mark.parametrize("m,k", [(48, 200), (17, 128), (256, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_ref(self, m, k, dtype):
        x = jax.random.normal(KEY, (m, k), dtype)
        q, s = ops.quantize_rows_int8(x, interpret=True)
        q_r, s_r = ref.quantize_rows_int8_ref(x)
        assert (np.asarray(q) == np.asarray(q_r)).all()
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_r),
                                   rtol=1e-6, atol=0)

    def test_padded_rows_do_not_leak(self):
        """M padding inside the wrapper never changes real-row output."""
        x = jax.random.normal(KEY, (5, 131), jnp.float32)
        q, s = ops.quantize_rows_int8(x, interpret=True)
        assert q.shape == (5, 131) and s.shape == (5, 1)
        q_r, _ = ref.quantize_rows_int8_ref(x)
        assert (np.asarray(q) == np.asarray(q_r)).all()


class TestFusedEpilogue:
    @pytest.mark.parametrize("m,k,n", RAGGED_SHAPES)
    def test_dequant_parity_ragged(self, m, k, n):
        k1, k2 = keys(2)
        x = jax.random.normal(k1, (m, k), jnp.float32)
        w = jax.random.normal(k2, (k, n), jnp.float32) * 0.1
        w_q, w_s = ops.quantize_weights_int8(w)
        out = ops.cim_quantized_matmul_fused(x, w_q, w_s, interpret=True)
        expect = ref.fused_matmul_ref(x, w_q, w_s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("activation", [None, "gelu", "silu"])
    @pytest.mark.parametrize("with_bias", [False, True])
    def test_bias_activation_fused(self, activation, with_bias):
        k1, k2, k3 = keys(3)
        m, k, n = 48, 200, 300
        x = jax.random.normal(k1, (m, k), jnp.float32)
        w = jax.random.normal(k2, (k, n), jnp.float32) * 0.1
        bias = jax.random.normal(k3, (n,), jnp.float32) * 0.1 \
            if with_bias else None
        w_q, w_s = ops.quantize_weights_int8(w)
        out = ops.cim_quantized_matmul_fused(x, w_q, w_s, bias=bias,
                                             activation=activation,
                                             interpret=True)
        expect = ref.fused_matmul_ref(x, w_q, w_s, bias=bias,
                                      activation=activation)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("out_dtype,tol", [(jnp.float32, 1e-4),
                                               (jnp.bfloat16, 2e-2)])
    def test_out_dtypes(self, out_dtype, tol):
        k1, k2 = keys(2)
        x = jax.random.normal(k1, (32, 128), jnp.float32)
        w = jax.random.normal(k2, (128, 256), jnp.float32) * 0.1
        w_q, w_s = ops.quantize_weights_int8(w)
        out = ops.cim_quantized_matmul_fused(x, w_q, w_s,
                                             out_dtype=out_dtype,
                                             interpret=True)
        assert out.dtype == out_dtype
        expect = ref.fused_matmul_ref(x, w_q, w_s)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=tol, atol=tol)

    def test_int32_accumulator_not_an_output(self):
        """The fused call's HBM-resident outputs carry no int32 tensor."""
        k1, k2 = keys(2)
        x = jax.random.normal(k1, (32, 128), jnp.float32)
        w_q, w_s = ops.quantize_weights_int8(
            jax.random.normal(k2, (128, 256), jnp.float32))
        shapes = jax.eval_shape(
            lambda a: ops.cim_quantized_matmul_fused(a, w_q, w_s,
                                                     interpret=True), x)
        leaves = jax.tree.leaves(shapes)
        assert all(s.dtype != jnp.int32 for s in leaves)


class TestFusedGatedMLP:
    @pytest.mark.parametrize("activation", ["gelu", "silu"])
    @pytest.mark.parametrize("d,ff", [(96, 176), (128, 256)])
    def test_gated_vs_ref(self, activation, d, ff):
        k1, k2, k3, k4 = keys(4)
        x = jax.random.normal(k1, (24, d), jnp.float32) * 0.5
        uq, us = ops.quantize_weights_int8(
            jax.random.normal(k2, (d, ff), jnp.float32) * 0.1)
        gq, gs = ops.quantize_weights_int8(
            jax.random.normal(k3, (d, ff), jnp.float32) * 0.1)
        dq, ds = ops.quantize_weights_int8(
            jax.random.normal(k4, (ff, d), jnp.float32) * 0.1)
        out = ops.cim_quantized_mlp(x, uq, us, dq, ds, gate_q=gq,
                                    gate_scale=gs, activation=activation,
                                    interpret=True)
        expect = ref.quantized_mlp_ref(
            x, {"up": (uq, us), "gate": (gq, gs), "down": (dq, ds)},
            activation)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)

    def test_nongated_vs_ref(self):
        k1, k2, k3 = keys(3)
        d, ff = 96, 176
        x = jax.random.normal(k1, (24, d), jnp.float32) * 0.5
        uq, us = ops.quantize_weights_int8(
            jax.random.normal(k2, (d, ff), jnp.float32) * 0.1)
        dq, ds = ops.quantize_weights_int8(
            jax.random.normal(k3, (ff, d), jnp.float32) * 0.1)
        out = ops.cim_quantized_mlp(x, uq, us, dq, ds, activation="gelu",
                                    interpret=True)
        expect = ref.quantized_mlp_ref(x, {"up": (uq, us),
                                           "down": (dq, ds)}, "gelu")
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# grouped-expert fused GEMMs (expert index as a grid dimension)
# ---------------------------------------------------------------------------
class TestGroupedGemm:
    """cim_grouped_gemm_int8 == per-expert cim_gemm_int8_fused, exactly."""

    def _stacked(self, E, m, k, n, seed=0):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = jax.random.randint(k1, (E, m, k), -127, 128, jnp.int8)
        xs = jnp.abs(jax.random.normal(k2, (E, m, 1), jnp.float32)) + 0.01
        w = jax.random.randint(k3, (E, k, n), -127, 128, jnp.int8)
        ws = jnp.abs(jax.random.normal(k2, (E, 1, n), jnp.float32)) * 0.01
        return x, xs, w, ws

    @pytest.mark.parametrize("activation", [None, "gelu", "silu"])
    def test_matches_per_expert_fused(self, activation):
        from repro.kernels.cim_gemm import (cim_gemm_int8_fused,
                                            cim_grouped_gemm_int8)
        E, m, k, n = 3, 32, 128, 256
        x, xs, w, ws = self._stacked(E, m, k, n)
        grouped = cim_grouped_gemm_int8(x, w, xs, ws, activation=activation,
                                        interpret=True)
        for e in range(E):
            one = cim_gemm_int8_fused(x[e], w[e], xs[e], ws[e],
                                      activation=activation, interpret=True)
            assert (np.asarray(grouped[e]) == np.asarray(one)).all()

    def test_quantize_out_matches_per_expert(self):
        from repro.kernels.cim_gemm import (cim_gemm_int8_fused,
                                            cim_grouped_gemm_int8)
        E, m, k, n = 3, 32, 128, 256
        x, xs, w, ws = self._stacked(E, m, k, n, seed=1)
        gq, gs = cim_grouped_gemm_int8(x, w, xs, ws, activation="gelu",
                                       quantize_out=True, interpret=True)
        for e in range(E):
            oq, os_ = cim_gemm_int8_fused(x[e], w[e], xs[e], ws[e],
                                          activation="gelu",
                                          quantize_out=True, interpret=True)
            assert (np.asarray(gq[e]) == np.asarray(oq)).all()
            assert (np.asarray(gs[e]) == np.asarray(os_)).all()

    def test_gated_matches_per_expert(self):
        from repro.kernels.cim_gemm import (cim_gated_gemm_int8,
                                            cim_grouped_gated_gemm_int8)
        E, m, k, n = 2, 32, 128, 256
        x, xs, wg, gs = self._stacked(E, m, k, n, seed=2)
        _, _, wu, us = self._stacked(E, m, k, n, seed=3)
        grouped = cim_grouped_gated_gemm_int8(x, wg, wu, xs, gs, us,
                                              activation="silu",
                                              interpret=True)
        for e in range(E):
            one = cim_gated_gemm_int8(x[e], wg[e], wu[e], xs[e], gs[e],
                                      us[e], activation="silu",
                                      interpret=True)
            assert (np.asarray(grouped[e]) == np.asarray(one)).all()

    @pytest.mark.parametrize("E,t,d,ff", [(2, 5, 36, 24),   # ragged all
                                          (4, 32, 128, 256)])  # aligned
    def test_grouped_mlp_wrapper_vs_ref(self, E, t, d, ff):
        k1, k2, k3, k4 = keys(4)
        x = jax.random.normal(k1, (E, t, d), jnp.float32) * 0.5
        uq, us = jax.vmap(ops.quantize_weights_int8)(
            jax.random.normal(k2, (E, d, ff), jnp.float32) * 0.1)
        gq, gs = jax.vmap(ops.quantize_weights_int8)(
            jax.random.normal(k3, (E, d, ff), jnp.float32) * 0.1)
        dq, ds = jax.vmap(ops.quantize_weights_int8)(
            jax.random.normal(k4, (E, ff, d), jnp.float32) * 0.1)
        out = ops.cim_quantized_grouped_mlp(x, uq, us, dq, ds, gate_q=gq,
                                            gate_scale=gs,
                                            activation="silu",
                                            interpret=True)
        expect = ref.grouped_quantized_mlp_ref(
            x, {"up": (uq, us), "gate": (gq, gs), "down": (dq, ds)}, "silu")
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
class TestFlashAttention:
    @pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                               (True, 48)])
    @pytest.mark.parametrize("kh", [1, 2, 4])
    def test_vs_ref(self, causal, window, kh):
        B, S, H, D = 2, 256, 4, 32
        k1, k2, k3 = keys(3)
        q = jax.random.normal(k1, (B, S, H, D), jnp.float32)
        k = jax.random.normal(k2, (B, S, kh, D), jnp.float32)
        v = jax.random.normal(k3, (B, S, kh, D), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  block_q=64, block_k=64, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)])
    def test_dtypes(self, dtype, tol):
        B, S, H, D = 1, 128, 2, 64
        k1, k2, k3 = keys(3)
        q = jax.random.normal(k1, (B, S, H, D), dtype)
        k = jax.random.normal(k2, (B, S, H, D), dtype)
        v = jax.random.normal(k3, (B, S, H, D), dtype)
        out = ops.flash_attention(q, k, v, block_q=64, block_k=64,
                                  interpret=True)
        expect = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=tol, atol=tol)

    @given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64]))
    @settings(max_examples=6, deadline=None)
    def test_block_invariance(self, bq, bk):
        B, S, H, D = 1, 128, 2, 16
        k1, k2, k3 = keys(3)
        q = jax.random.normal(k1, (B, S, H, D), jnp.float32)
        k = jax.random.normal(k2, (B, S, H, D), jnp.float32)
        v = jax.random.normal(k3, (B, S, H, D), jnp.float32)
        out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                  interpret=True)
        expect = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------
class TestDecodeAttention:
    @pytest.mark.parametrize("window", [None, 64])
    @pytest.mark.parametrize("kh,g", [(1, 8), (4, 2), (8, 1)])
    def test_vs_ref(self, window, kh, g):
        B, S, D = 2, 256, 32
        k1, k2, k3 = keys(3)
        q = jax.random.normal(k1, (B, kh, g, D), jnp.float32)
        k = jax.random.normal(k2, (B, S, kh, D), jnp.float32)
        v = jax.random.normal(k3, (B, S, kh, D), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
        q_pos = jnp.array([S - 1, S // 2], jnp.int32)
        out = ops.decode_attention(q, k, v, pos, q_pos, window=window,
                                   block_k=64, interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos, q_pos, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    def test_ring_buffer_positions(self):
        """Slots hold out-of-order positions (ring semantics)."""
        B, S, KH, G, D = 1, 128, 2, 2, 16
        k1, k2, k3, k4 = keys(4)
        q = jax.random.normal(k1, (B, KH, G, D), jnp.float32)
        k = jax.random.normal(k2, (B, S, KH, D), jnp.float32)
        v = jax.random.normal(k3, (B, S, KH, D), jnp.float32)
        pos = jax.random.permutation(k4, jnp.arange(2 * S)[:S])[None, :]
        pos = pos.astype(jnp.int32)
        q_pos = jnp.array([3 * S // 2], jnp.int32)
        out = ops.decode_attention(q, k, v, pos, q_pos, window=S,
                                   block_k=32, interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos, q_pos, window=S)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    # -- int8 KV (in-kernel dequant) ------------------------------------
    @staticmethod
    def _rand_kv(B, S, KH, G, D, seed=0):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(k1, (B, KH, G, D), jnp.float32)
        k = jax.random.normal(k2, (B, S, KH, D), jnp.float32)
        v = jax.random.normal(k3, (B, S, KH, D), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
        return q, k, v, pos

    @staticmethod
    def _quant(x):
        from repro.models.attention import _quantize_kv
        return _quantize_kv(x)

    @pytest.mark.parametrize("s", [128, 4096])
    def test_int8_kv_close_to_fp(self, s):
        """Acceptance bar: int8-KV decode matches the fp oracle within
        the quantization budget at short AND long contexts (the int8
        error does not accumulate with S — softmax renormalizes)."""
        B, KH, G, D = 2, 2, 2, 32
        q, k, v, pos = self._rand_kv(B, s, KH, G, D)
        q_pos = jnp.array([s - 1, s // 2], jnp.int32)
        kq, ks = self._quant(k)
        vq, vs = self._quant(v)
        out8 = ops.decode_attention(q, kq, vq, pos, q_pos, k_scale=ks,
                                    v_scale=vs, block_k=128, n_splits=1,
                                    interpret=True)
        fp = ref.decode_attention_ref(q, k, v, pos, q_pos)
        np.testing.assert_allclose(np.asarray(out8), np.asarray(fp),
                                   rtol=2e-2, atol=2e-2)
        # and the kernel's in-kernel dequant matches the XLA dequant
        # oracle to kernel precision
        r8 = ref.decode_attention_ref(q, kq, vq, pos, q_pos,
                                      k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out8), np.asarray(r8),
                                   rtol=2e-5, atol=2e-5)

    def test_int8_kv_windowed_vs_ref(self):
        B, S, KH, G, D = 2, 256, 4, 2, 16
        q, k, v, pos = self._rand_kv(B, S, KH, G, D, seed=3)
        q_pos = jnp.array([S - 1, 70], jnp.int32)
        kq, ks = self._quant(k)
        vq, vs = self._quant(v)
        out = ops.decode_attention(q, kq, vq, pos, q_pos, k_scale=ks,
                                   v_scale=vs, window=50, block_k=64,
                                   interpret=True)
        expect = ref.decode_attention_ref(q, kq, vq, pos, q_pos, window=50,
                                          k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    # -- split-KV (flash-decode) ----------------------------------------
    @pytest.mark.parametrize("quantized", [False, True])
    def test_splitkv_single_split_bitwise(self, quantized):
        """Acceptance bar: split-KV at n_splits=1 equals the
        single-dispatch kernel bit-for-bit (the combine's
        renormalization terms are exact identities)."""
        B, S, KH, G, D = 2, 256, 2, 4, 32
        q, k, v, pos = self._rand_kv(B, S, KH, G, D, seed=1)
        q_pos = jnp.array([S - 1, S // 3], jnp.int32)
        sc = {}
        if quantized:
            k, sc["k_scale"] = self._quant(k)
            v, sc["v_scale"] = self._quant(v)
        base = ops.decode_attention(q, k, v, pos, q_pos, block_k=64,
                                    n_splits=1, interpret=True, **sc)
        split = ops.decode_attention_splitkv(q, k, v, pos, q_pos,
                                             block_k=64, n_splits=1,
                                             interpret=True, **sc)
        assert (np.asarray(split) == np.asarray(base)).all()

    @pytest.mark.parametrize("n_splits", [2, 4])
    def test_splitkv_matches_single_dispatch(self, n_splits):
        B, S, KH, G, D = 2, 512, 2, 2, 32
        q, k, v, pos = self._rand_kv(B, S, KH, G, D, seed=2)
        q_pos = jnp.array([S - 1, S // 2], jnp.int32)
        base = ops.decode_attention(q, k, v, pos, q_pos, block_k=64,
                                    n_splits=1, interpret=True)
        split = ops.decode_attention_splitkv(q, k, v, pos, q_pos,
                                             block_k=64, n_splits=n_splits,
                                             interpret=True)
        np.testing.assert_allclose(np.asarray(split), np.asarray(base),
                                   rtol=2e-6, atol=2e-6)

    def test_splitkv_auto_dispatch_long_context(self):
        """ops.decode_attention auto-selects split-KV beyond 2048 slots;
        result still matches the reference."""
        B, S, KH, G, D = 1, 4096, 1, 2, 16
        q, k, v, pos = self._rand_kv(B, S, KH, G, D, seed=4)
        q_pos = jnp.array([S - 1], jnp.int32)
        out = ops.decode_attention(q, k, v, pos, q_pos, block_k=512,
                                   interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos, q_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    # -- ring-buffer edge cases (pinned against the XLA oracle) ---------
    def test_all_empty_sentinel_cache(self):
        """A never-written cache (every slot 2**30) must reproduce the
        reference's uniform-softmax output, not zeros — the skip list
        keeps all blocks on all-masked rows."""
        B, S, KH, G, D = 2, 128, 2, 2, 16
        q, k, v, _ = self._rand_kv(B, S, KH, G, D, seed=5)
        pos = jnp.full((B, S), 2 ** 30, jnp.int32)
        q_pos = jnp.array([0, 7], jnp.int32)
        out = ops.decode_attention(q, k, v, pos, q_pos, block_k=32,
                                   interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos, q_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)
        assert np.isfinite(np.asarray(out)).all()

    def test_window_equals_cache_length(self):
        B, S, KH, G, D = 2, 128, 2, 2, 16
        q, k, v, pos = self._rand_kv(B, S, KH, G, D, seed=6)
        q_pos = jnp.array([S - 1, S - 1], jnp.int32)
        out = ops.decode_attention(q, k, v, pos, q_pos, window=S,
                                   block_k=32, interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos, q_pos, window=S)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    def test_single_valid_token(self):
        """One written slot, everything else empty: output == that
        slot's V row exactly (softmax over one logit)."""
        B, S, KH, G, D = 1, 128, 2, 2, 16
        q, k, v, _ = self._rand_kv(B, S, KH, G, D, seed=7)
        pos = jnp.full((B, S), 2 ** 30, jnp.int32).at[:, 5].set(0)
        q_pos = jnp.array([0], jnp.int32)
        out = ops.decode_attention(q, k, v, pos, q_pos, block_k=32,
                                   interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos, q_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)
        want = np.broadcast_to(np.asarray(v)[:, 5][:, :, None, :],
                               (B, KH, G, D))
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5,
                                   atol=1e-5)

    def test_gqa_groups_with_window(self):
        """G>1 GQA groups share one KV head under a sliding window."""
        B, S, KH, G, D = 2, 256, 2, 4, 16
        q, k, v, pos = self._rand_kv(B, S, KH, G, D, seed=8)
        q_pos = jnp.array([S - 1, 100], jnp.int32)
        out = ops.decode_attention(q, k, v, pos, q_pos, window=33,
                                   block_k=64, interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos, q_pos, window=33)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    # -- block-skip list ------------------------------------------------
    def test_block_skip_bitwise_and_coverage(self):
        """A short sequence in a long ring cache skips the fully-masked
        tail blocks; skipping is bit-identical to streaming them (the
        masked probabilities underflow to exactly 0)."""
        from repro.kernels.decode_attention import _block_keep
        B, S, KH, G, D = 2, 512, 2, 2, 16
        q, k, v, pos = self._rand_kv(B, S, KH, G, D, seed=9)
        q_pos = jnp.array([40, 500], jnp.int32)
        skip = np.asarray(_block_keep(pos, q_pos, None, 64))
        assert skip.shape == (B, 8)
        assert skip[0].sum() == 1 and skip[1].sum() == 8  # tail skipped
        out = ops.decode_attention(q, k, v, pos, q_pos, block_k=64,
                                   interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos, q_pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)
        # sliding window: only the blocks inside the window survive
        skip_w = np.asarray(_block_keep(pos, q_pos, 64, 64))
        assert skip_w[1].sum() == 2


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------
class TestSSDScan:
    @pytest.mark.parametrize("chunk", [16, 32, 64])
    def test_vs_naive(self, chunk):
        BH, S, P, N = 4, 128, 16, 8
        k1, k2, k3, k4 = keys(4)
        x = jax.random.normal(k1, (BH, S, P), jnp.float32)
        log_a = -jnp.abs(jax.random.normal(k2, (BH, S))) * 0.3
        b = jax.random.normal(k3, (BH, S, N), jnp.float32)
        c = jax.random.normal(k4, (BH, S, N), jnp.float32)
        y, h = ops.ssd_scan(x, log_a, b, c, chunk=chunk, interpret=True)
        y_ref, h_ref = ref.ssd_scan_ref(x, log_a, b, c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_matches_model_oracle(self):
        """Kernel agrees with models.ssm.ssd_chunked (the model path)."""
        from repro.models.ssm import ssd_chunked
        B, S, H, P, N = 2, 64, 2, 8, 4
        k1, k2, k3, k4 = keys(4)
        x = jax.random.normal(k1, (B, S, H, P), jnp.float32)
        log_a = -jnp.abs(jax.random.normal(k2, (B, S, H))) * 0.3
        b = jax.random.normal(k3, (B, S, 1, N), jnp.float32)
        c = jax.random.normal(k4, (B, S, 1, N), jnp.float32)
        y_m, h_m = ssd_chunked(x, log_a, b, c, 16)
        xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
        laf = log_a.transpose(0, 2, 1).reshape(B * H, S)
        bf = jnp.repeat(b, H, 2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
        cf = jnp.repeat(c, H, 2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
        y_k, h_k = ops.ssd_scan(xf, laf, bf, cf, chunk=16, interpret=True)
        np.testing.assert_allclose(
            np.asarray(y_k.reshape(B, H, S, P).transpose(0, 2, 1, 3)),
            np.asarray(y_m), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(h_k.reshape(B, H, P, N)), np.asarray(h_m),
            rtol=2e-4, atol=2e-4)

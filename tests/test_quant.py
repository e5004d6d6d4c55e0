"""INT8 serving-quantization tests (paper's INT8 CIM mode end to end)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import attention_apply, attention_init
from repro.models.layers import mlp_apply, mlp_init, param_values
from repro.models.moe import MoEConfig, moe_apply, moe_init
from repro.analysis import manifest, passes
from repro.analysis import jaxpr_tools as jt
from repro.quant import (kernel_mode, plan_is_applied,
                         quantize_attention, quantize_mlp,
                         quantize_moe_experts, quantized_mlp_apply,
                         quantized_moe_apply, quantized_moe_apply_looped,
                         QuantPlan)
from repro.quant.linear import quantize_linear, quantized_matmul

KEY = jax.random.PRNGKey(0)


class TestQuantizedLinear:
    def test_matches_float_within_int8_budget(self):
        k1, k2 = jax.random.split(KEY)
        x = jax.random.normal(k1, (16, 128))
        w = jax.random.normal(k2, (128, 256)) * 0.05
        q = quantize_linear(w)
        out = quantized_matmul(x, q)
        ref = x @ w
        rel = np.abs(np.asarray(out - ref)) / (np.abs(np.asarray(ref)) + 1e-2)
        assert np.median(rel) < 0.05

    @pytest.mark.slow
    def test_kernel_and_oracle_paths_agree(self):
        k1, k2 = jax.random.split(KEY)
        x = jax.random.normal(k1, (8, 128))
        w = jax.random.normal(k2, (128, 256))
        q = quantize_linear(w)
        a = quantized_matmul(x, q, use_kernel=True)   # fused Pallas path
        b = quantized_matmul(x, q, use_kernel=False)  # jnp oracle
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.slow
    def test_fused_bias_activation_matches_oracle(self):
        k1, k2, k3 = jax.random.split(KEY, 3)
        x = jax.random.normal(k1, (8, 130))           # ragged K
        w = jax.random.normal(k2, (130, 200))         # ragged N
        bias = jax.random.normal(k3, (200,)) * 0.1
        q = quantize_linear(w)
        a = quantized_matmul(x, q, use_kernel=True, bias=bias,
                             activation="gelu")
        b = quantized_matmul(x, q, use_kernel=False, bias=bias,
                             activation="gelu")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)

    def test_dequantize_roundtrip(self):
        w = jax.random.normal(KEY, (64, 32)) * 0.1
        q = quantize_linear(w)
        back = (q.q.astype(jnp.float32) * q.scale[None, :])
        assert float(jnp.max(jnp.abs(back - w))) < float(
            jnp.max(jnp.abs(w))) / 100


class TestQuantizedMLP:
    @pytest.mark.parametrize("activation", ["geglu", "gelu"])
    def test_mlp_parity(self, activation):
        d, ff = 64, 128
        params = param_values(mlp_init(KEY, d, ff, activation,
                                       dtype=jnp.float32))
        qparams = quantize_mlp(params)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d)) * 0.5
        ref = mlp_apply(params, x, activation)
        out = quantized_mlp_apply(qparams, x, activation)
        err = np.abs(np.asarray(out - ref))
        scale = np.abs(np.asarray(ref)).mean() + 1e-3
        assert err.mean() / scale < 0.05, "int8 MLP drifted beyond budget"

    @pytest.mark.slow
    @pytest.mark.parametrize("activation", ["geglu", "swiglu", "gelu"])
    def test_fused_kernel_end_to_end(self, activation):
        """quantized_mlp_apply(use_kernel=True) — the fused pipeline (one
        quantize kernel + two fused GEMM kernels for gated MLPs) agrees
        with the jnp oracle within 1e-4 relative error."""
        d, ff = 64, 128
        params = param_values(mlp_init(KEY, d, ff, activation,
                                       dtype=jnp.float32))
        qparams = quantize_mlp(params)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d)) * 0.5
        fused = quantized_mlp_apply(qparams, x, activation, use_kernel=True)
        oracle = quantized_mlp_apply(qparams, x, activation,
                                     use_kernel=False)
        rel = np.abs(np.asarray(fused - oracle)) / \
            (np.abs(np.asarray(oracle)) + 1e-6)
        assert rel.max() < 1e-4
        if activation == "geglu":
            assert "gate" in qparams   # exercised the gated fused kernel

    def test_fused_pipeline_structure(self):
        """The fused gated MLP matches the manifest's pipeline profile
        (quantize + two fused GEMMs at these dims) and no kernel emits
        an HBM-resident int32 accumulator (the acceptance bar for the
        epilogue fusion).  Checked structurally on the jaxpr — no kernel
        execution, fast."""
        d, ff = 64, 128
        params = param_values(mlp_init(KEY, d, ff, "geglu",
                                       dtype=jnp.float32))
        qparams = quantize_mlp(params)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
        jaxpr = jax.make_jaxpr(
            lambda a: quantized_mlp_apply(qparams, a, "geglu",
                                          use_kernel=True))(x)
        sites = jt.pallas_sites(jaxpr)
        assert passes.dispatch_audit(sites,
                                     manifest.mlp_sites(ff)) == []
        assert jt.int32_escapes(jaxpr) == []
        # no XLA dequant/activation between kernels: the only wide f32
        # tensor any kernel emits is the final down-projection output
        # (narrow f32 outvars are the per-row quantization scales)
        f32_outs = [v for s in sites for v in s.eqn.outvars
                    if v.aval.dtype == jnp.float32 and v.aval.shape[-1] > 1]
        assert len(f32_outs) == 1

    def test_mlp_apply_dispatches_on_quantized_leaves(self):
        """models.layers.mlp_apply auto-routes QuantizedLinear trees."""
        d, ff = 64, 128
        params = param_values(mlp_init(KEY, d, ff, "geglu",
                                       dtype=jnp.float32))
        qparams = quantize_mlp(params)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, d)) * 0.5
        via_layers = mlp_apply(qparams, x, "geglu")
        via_quant = quantized_mlp_apply(qparams, x, "geglu",
                                        use_kernel=False)
        np.testing.assert_allclose(np.asarray(via_layers),
                                   np.asarray(via_quant),
                                   rtol=1e-6, atol=1e-6)

    def test_memory_halves(self):
        d, ff = 64, 128
        params = param_values(mlp_init(KEY, d, ff, "geglu",
                                       dtype=jnp.bfloat16))
        qparams = quantize_mlp(params)
        bf16_bytes = sum(v.size * 2 for v in params.values())
        q_bytes = sum(v.q.size + v.scale.size * 4 for v in qparams.values())
        assert q_bytes < 0.6 * bf16_bytes


class TestQuantizedAttention:
    """Fused QKV (one wide GEMM) + out-projection w/ residual epilogue."""

    def _setup(self, d=52, H=4, KH=2, Dh=12, B=2, S=5):
        # deliberately ragged: no dim is a multiple of the CIM tile
        params = param_values(attention_init(KEY, d, H, KH, Dh,
                                             dtype=jnp.float32))
        x = jax.random.normal(jax.random.PRNGKey(3), (B, S, d)) * 0.5
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return params, x, pos

    def test_parity_with_bf16_reference_ragged(self):
        params, x, pos = self._setup()
        ref, _ = attention_apply(params, x, pos, residual=x)
        qparams = quantize_attention(params)
        assert "q" not in qparams and "qkv" in qparams   # fused leaf
        out, _ = attention_apply(qparams, x, pos, residual=x)
        err = np.abs(np.asarray(out - ref))
        scale = np.abs(np.asarray(ref)).mean() + 1e-3
        assert err.mean() / scale < 0.06, "int8 attention drifted"

    def test_partial_plan_out_only(self):
        """attn_out covered without attn_qkv: q/k/v stay bf16 einsums,
        only the out-projection (+ residual) runs the fused path."""
        params, x, pos = self._setup()
        ref, _ = attention_apply(params, x, pos, residual=x)
        qparams = quantize_attention(params, qkv=False, out=True)
        assert "q" in qparams                            # untouched
        out, _ = attention_apply(qparams, x, pos, residual=x)
        err = np.abs(np.asarray(out - ref))
        scale = np.abs(np.asarray(ref)).mean() + 1e-3
        assert err.mean() / scale < 0.05

    def test_decode_cache_path(self):
        """Quantized projections against the ring-buffer decode path."""
        from repro.models.attention import init_kv_cache
        params, x, pos = self._setup()
        qparams = quantize_attention(params)
        B, S, _ = x.shape
        full, _ = attention_apply(qparams, x, pos, residual=x)
        cache = init_kv_cache(B, 8, 2, 12, dtype=jnp.float32)
        outs = []
        for t in range(S):
            o, cache = attention_apply(qparams, x[:, t:t + 1],
                                       pos[:, t:t + 1], cache=cache,
                                       residual=x[:, t:t + 1])
            outs.append(o)
        step = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.slow
    def test_kernel_and_oracle_agree_ragged(self):
        params, x, pos = self._setup(B=1, S=3)
        qparams = quantize_attention(params)
        with kernel_mode(False):
            oracle, _ = attention_apply(qparams, x, pos, residual=x)
        with kernel_mode(True):
            fused, _ = attention_apply(qparams, x, pos, residual=x)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                                   rtol=1e-4, atol=1e-4)


class TestQuantizedMoE:
    """Grouped-expert fused INT8 pipeline over the dispatched tokens."""

    CFG = MoEConfig(n_routed_experts=4, top_k=2, d_expert=24,
                    n_shared_experts=1, shared_d_ff=20)

    def _setup(self, d=36):
        params = param_values(moe_init(KEY, d, self.CFG, "swiglu",
                                       dtype=jnp.float32))
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 6, d)) * 0.5
        return params, x

    def test_parity_with_bf16_reference_ragged(self):
        params, x = self._setup()
        ref, aux_ref = moe_apply(params, x, self.CFG, "swiglu")
        qparams = quantize_moe_experts(params)
        out, aux = moe_apply(qparams, x, self.CFG, "swiglu")
        # the router is unquantized: identical dispatch, identical aux
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)
        err = np.abs(np.asarray(out - ref))
        scale = np.abs(np.asarray(ref)).mean() + 1e-3
        assert err.mean() / scale < 0.06, "int8 MoE drifted"

    @pytest.mark.slow
    def test_kernel_and_oracle_agree(self):
        params, _ = self._setup()
        qparams = quantize_moe_experts(params)
        xe = jax.random.normal(jax.random.PRNGKey(6), (4, 5, 36)) * 0.5
        fused = quantized_moe_apply(qparams, xe, "swiglu", use_kernel=True)
        oracle = quantized_moe_apply(qparams, xe, "swiglu",
                                     use_kernel=False)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                                   rtol=1e-4, atol=1e-4)

    # -- grouped kernel vs the retired per-expert loop -------------------
    def _moe_weights(self, E, d, F, key=7, gated=True):
        ks = jax.random.split(jax.random.PRNGKey(key), 3)
        p = {"up": jax.random.normal(ks[0], (E, d, F)) * 0.1,
             "down": jax.random.normal(ks[1], (E, F, d)) * 0.1}
        if gated:
            p["gate"] = jax.random.normal(ks[2], (E, d, F)) * 0.1
        return quantize_moe_experts(p)

    @pytest.mark.slow
    @pytest.mark.parametrize("gated,activation", [(True, "swiglu"),
                                                  (False, "gelu")])
    def test_grouped_matches_looped_bitwise(self, gated, activation):
        """The grouped kernel IS the per-expert loop, restructured: same
        per-row integer math, so outputs are bit-for-bit identical."""
        E, d, F, T = 3, 36, 24, 5
        qparams = self._moe_weights(E, d, F, gated=gated)
        xe = jax.random.normal(jax.random.PRNGKey(8), (E, T, d)) * 0.5
        grouped = quantized_moe_apply(qparams, xe, activation,
                                      use_kernel=True)
        looped = quantized_moe_apply_looped(qparams, xe, activation,
                                            use_kernel=True)
        assert (np.asarray(grouped) == np.asarray(looped)).all()

    @pytest.mark.slow
    def test_grouped_matches_looped_without_fused_requant(self, monkeypatch):
        """When d_expert exceeds the in-epilogue requant budget both paths
        fall back to a separate hidden-state quantize dispatch — still
        bit-for-bit equal (unique shapes so the jit caches re-trace under
        the patched budget)."""
        from repro.kernels import ops as kops
        monkeypatch.setattr(kops, "MAX_FUSED_QUANT_N", 0)
        try:
            E, d, F, T = 3, 44, 40, 6
            qparams = self._moe_weights(E, d, F, key=9)
            xe = jax.random.normal(jax.random.PRNGKey(10), (E, T, d)) * 0.5
            grouped = quantized_moe_apply(qparams, xe, "swiglu",
                                          use_kernel=True)
            looped = quantized_moe_apply_looped(qparams, xe, "swiglu",
                                                use_kernel=True)
            assert (np.asarray(grouped) == np.asarray(looped)).all()
        finally:
            # jit caches key on shapes, not the patched budget global —
            # drop the budget-0 traces so later same-shape calls retrace
            jax.clear_caches()

    @pytest.mark.slow
    def test_zero_capacity_expert(self):
        """An expert that received no tokens (all-zero capacity buffer)
        contributes exactly zeros and never perturbs its neighbours."""
        E, d, F, T = 4, 36, 24, 5
        qparams = self._moe_weights(E, d, F)
        xe = jax.random.normal(jax.random.PRNGKey(11), (E, T, d)) * 0.5
        xe = xe.at[2].set(0.0)
        grouped = quantized_moe_apply(qparams, xe, "swiglu",
                                      use_kernel=True)
        looped = quantized_moe_apply_looped(qparams, xe, "swiglu",
                                            use_kernel=True)
        assert (np.asarray(grouped) == np.asarray(looped)).all()
        assert (np.asarray(grouped[2]) == 0).all()
        # populated experts still agree with the jnp oracle
        oracle = quantized_moe_apply(qparams, xe, "swiglu",
                                     use_kernel=False)
        np.testing.assert_allclose(np.asarray(grouped), np.asarray(oracle),
                                   rtol=1e-4, atol=1e-5)

    def test_dispatch_count_constant_in_experts(self):
        """Acceptance bar: the MoE expert pipeline is a constant number
        of Pallas dispatches (the manifest's grouped profile: quantize +
        grouped gated GEMM + grouped down GEMM) whether the layer has 2
        experts or 16.  Structural on the jaxpr — no kernel execution."""
        expected = manifest.mlp_pipeline_dispatches(24, grouped=True)
        counts = {}
        for E in (2, 16):
            qparams = self._moe_weights(E, 36, 24)
            xe = jnp.zeros((E, 5, 36))
            jaxpr = jax.make_jaxpr(
                lambda a, q=qparams: quantized_moe_apply(
                    q, a, "swiglu", use_kernel=True))(xe)
            counts[E] = len(jt.pallas_sites(jaxpr))
        assert counts[2] == counts[16] == expected, counts

    @pytest.mark.slow
    def test_zero_capacity_skip_list_bitwise(self):
        """The scalar-prefetch skip list (``expert_counts``): experts the
        router assigned no tokens run no MXU work inside the grouped
        kernels, yet the outputs stay bit-identical to the unskipped
        grouped pipeline AND the per-expert loop — including the
        quantize_out intermediates consumed by the down GEMM."""
        E, d, F, T = 4, 36, 24, 5
        qparams = self._moe_weights(E, d, F)
        xe = jax.random.normal(jax.random.PRNGKey(12), (E, T, d)) * 0.5
        xe = xe.at[1].set(0.0).at[3].set(0.0)
        counts = jnp.array([2, 0, 4, 0], jnp.int32)
        skipped = quantized_moe_apply(qparams, xe, "swiglu",
                                      use_kernel=True, expert_counts=counts)
        unskipped = quantized_moe_apply(qparams, xe, "swiglu",
                                        use_kernel=True)
        looped = quantized_moe_apply_looped(qparams, xe, "swiglu",
                                            use_kernel=True)
        assert (np.asarray(skipped) == np.asarray(unskipped)).all()
        assert (np.asarray(skipped) == np.asarray(looped)).all()
        assert (np.asarray(skipped)[1] == 0).all()
        assert (np.asarray(skipped)[3] == 0).all()

    @pytest.mark.parametrize("gated", [True, False])
    def test_ragged_tiles_match_their_experts_bitwise(self, gated):
        """Ragged form: row tile t runs expert ``groups[t]``'s weights,
        bit-for-bit the dense pipeline on that expert; an empty tile
        (skip list 0) reads zeros, and the jnp oracle agrees."""
        from repro.quant import QuantizedLinear, quantized_mlp_apply
        E, d, F, tm = 3, 36, 24, 32
        qparams = self._moe_weights(E, d, F, key=13, gated=gated)
        groups = jnp.array([0, 0, 2, 1, 1], jnp.int32)
        live = jnp.array([1, 1, 1, 1, 0], jnp.int32)
        x = jax.random.normal(jax.random.PRNGKey(14), (5, tm, d)) * 0.5
        x = x.at[4].set(0.0)
        act = "swiglu" if gated else "gelu"
        got = quantized_moe_apply(qparams, x, act, use_kernel=True,
                                  expert_counts=live, groups=groups)
        oracle = quantized_moe_apply(qparams, x, act, use_kernel=False,
                                     expert_counts=live, groups=groups)
        for t in range(4):
            one = {k: QuantizedLinear(v.q[int(groups[t])],
                                      v.scale[int(groups[t])])
                   for k, v in qparams.items()}
            want = quantized_mlp_apply(one, x[t], act, use_kernel=True)
            assert (np.asarray(got[t]) == np.asarray(want)).all()
        assert (np.asarray(got[4]) == 0).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                                   rtol=1e-4, atol=1e-5)

    def test_skip_list_keeps_dispatch_count(self):
        """The skip list rides the existing grouped dispatches as a
        scalar-prefetch operand — no extra Pallas kernels, and the
        dispatch audit sees the prefetch the manifest requires."""
        E = 4
        qparams = self._moe_weights(E, 36, 24)
        xe = jnp.zeros((E, 5, 36))
        counts = jnp.ones((E,), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda a, c, q=qparams: quantized_moe_apply(
                q, a, "swiglu", use_kernel=True, expert_counts=c))(xe,
                                                                   counts)
        sites = jt.pallas_sites(jaxpr)
        assert passes.dispatch_audit(
            sites, manifest.mlp_sites(24, grouped=True)) == []


class TestQuantPlan:
    """The whole-model INT8 execution plan (ISSUE 2 acceptance bar)."""

    def _model(self, arch="gemma-2b"):
        from repro.configs import get_config, reduced_config
        from repro.models import build_model
        cfg = reduced_config(get_config(arch))
        m = build_model(cfg)
        return m, m.init(KEY)

    def test_apply_plan_covers_declared_layers(self):
        m, params = self._model()
        full = QuantPlan.full()
        qparams = m.quantize(params, full)
        assert plan_is_applied(m.groups, qparams, full)
        # idempotent
        again = m.quantize(qparams, full)
        assert plan_is_applied(m.groups, again, full)
        # partial plan leaves uncovered layers alone
        mlp_only = QuantPlan.mlp_only()
        qp2 = m.quantize(params, mlp_only)
        assert plan_is_applied(m.groups, qp2, mlp_only)
        assert not plan_is_applied(m.groups, qp2, full)
        assert "q" in qp2["group_0"]["attn"]             # still bf16

    def test_layer_table(self):
        m, _ = self._model()
        rows = QuantPlan.full().layer_table(m.groups)
        assert rows[0]["fused"] == ["attn_qkv", "attn_out", "attn_kv", "mlp"]
        assert QuantPlan.none().layer_table(m.groups)[0]["fused"] == []
        assert "int8[" in QuantPlan.full().describe(m.groups)

    def test_quantize_mlps_shim_warns_and_matches(self):
        m, params = self._model()
        with pytest.warns(DeprecationWarning):
            shim = m.quantize_mlps(params)
        assert plan_is_applied(m.groups, shim, QuantPlan.mlp_only())
        x = {"inputs": jnp.ones((1, 4), jnp.int32)}
        a, _, _ = m.forward(m.quantize(params, QuantPlan.mlp_only()), x)
        b, _, _ = m.forward(shim, x)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_full_plan_decode_matches_manifest(self):
        """Acceptance bar: one decode step of a dense attention+MLP block
        executes exactly the manifest's dispatch schedule (6 fused Pallas
        dispatches at reduced dims — its ENTIRE compute, attention
        included) with clean dtype flow: no kernel emits int32 to HBM,
        no XLA dot_general consumes int8, no int8 tensor is dequantized
        at the XLA level.  Structural on the jaxpr — no kernel
        execution."""
        m, params = self._model()
        assert m.groups == [(("attn", "dense"), 4)]      # one scan body
        qparams = m.quantize(params)
        cache = m.init_cache(2, 16)
        batch = {"inputs": jnp.ones((2, 1), jnp.int32)}
        with kernel_mode(True):
            jaxpr = jax.make_jaxpr(
                lambda p, b, c: m.decode_step(p, b, c))(qparams, batch,
                                                        cache)
        sites = jt.pallas_sites(jaxpr)
        expected = manifest.model_sites(m, "decode", kv_len=16)
        assert sum(expected.values()) == 6               # the paper bar
        assert passes.dispatch_audit(sites, expected) == []
        assert passes.dtype_flow_audit(jaxpr) == []
        # f32 GEMM outputs exist only as final fused-epilogue emissions
        # (QKV, out-proj(+res), down(+res) — the attention kernel emits
        # at the activation dtype)
        wide_f32 = [v for s in sites for v in s.eqn.outvars
                    if v.aval.dtype == jnp.float32 and v.aval.shape[-1] > 1]
        assert len(wide_f32) == 3

    def test_full_plan_forward_close_to_bf16(self):
        m, params = self._model()
        qparams = m.quantize(params)
        batch = {"inputs": jnp.arange(12).reshape(2, 6) % 256}
        ref, _, _ = m.forward(params, batch)
        out, _, _ = m.forward(qparams, batch)
        a, b = np.asarray(ref), np.asarray(out)
        corr = np.corrcoef(a.ravel(), b.ravel())[0, 1]
        assert corr > 0.99, corr
        assert (np.argmax(a, -1) == np.argmax(b, -1)).mean() > 0.9

    def test_full_plan_moe_model_forward(self):
        m, params = self._model("qwen2-moe-a2.7b")
        qparams = m.quantize(params)
        assert plan_is_applied(m.groups, qparams, QuantPlan.full())
        batch = {"inputs": jnp.arange(8).reshape(2, 4) % 256}
        ref, _, _ = m.forward(params, batch)
        out, _, _ = m.forward(qparams, batch)
        a, b = np.asarray(ref), np.asarray(out)
        corr = np.corrcoef(a.ravel(), b.ravel())[0, 1]
        assert corr > 0.99, corr

    def test_full_plan_moe_decode_dispatches_constant_in_experts(self):
        """Acceptance bar: a full-plan MoE-block decode step pins expert
        compute at the manifest's dispatch schedule independent of the
        expert count (9 per block at reduced dims: attention + grouped
        routed pipeline + shared-expert MLP; the per-expert loop this
        replaces traced 3·E + 6).  Structural on the jaxpr — no
        execution."""
        import dataclasses
        from repro.configs import get_config, reduced_config
        from repro.models import build_model

        for E in (4, 16):
            cfg = reduced_config(get_config("qwen2-moe-a2.7b"))
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, n_routed_experts=E))
            m = build_model(cfg)
            qparams = m.quantize(m.init(KEY))
            cache = m.init_cache(2, 16)
            batch = {"inputs": jnp.ones((2, 1), jnp.int32)}
            with kernel_mode(True):
                jaxpr = jax.make_jaxpr(
                    lambda p, b, c, mm=m: mm.decode_step(p, b, c))(
                        qparams, batch, cache)
            expected = manifest.model_sites(m, "decode", kv_len=16)
            assert sum(expected.values()) == 9           # the paper bar
            assert passes.dispatch_audit(jt.pallas_sites(jaxpr),
                                         expected) == []
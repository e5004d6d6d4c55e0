"""Execution-contract auditor tests (mutation tests per pass).

Each audit pass gets at least one seeded violation: a trace that breaks
the contract in a known way must produce exactly the expected violation
code, anchored to the right site — and the un-mutated twin must stay
clean.  This is what makes `make audit` trustworthy: a checker that
can't fail can't prove anything.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import jaxpr_tools as jt
from repro.analysis import manifest, passes
from repro.kernels import ops
from repro.kernels.cim_gemm import cim_gemm_int8, quantize_rows_int8
from repro.launch.mesh import make_mesh
from repro.quant import QuantPlan, kernel_mode, quantize_moe_experts, \
    quantized_moe_apply

KEY = jax.random.PRNGKey(0)


def _codes(violations):
    return [(v.pass_name, v.code) for v in violations]


def _reduced_model(arch="gemma-2b"):
    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    return build_model(reduced_config(get_config(arch)))


def _decode_jaxpr(m, qparams, kv_len=16):
    cache = m.init_cache(2, kv_len)
    batch = {"inputs": jnp.ones((2, 1), jnp.int32)}
    with kernel_mode(True):
        return jax.make_jaxpr(
            lambda p, b, c: m.decode_step(p, b, c))(qparams, batch, cache)


def _model_mesh():
    return make_mesh((1,), (manifest.TP_AXIS,))


# ---------------------------------------------------------------------------
# Pass 1: dispatch audit
# ---------------------------------------------------------------------------
class TestDispatchMutations:
    def test_partial_plan_flags_count_mismatch(self):
        """A decode step quantized with a *partial* plan (mlp-only) runs
        attention as bf16 einsums — the audit against the full-plan
        manifest must flag the missing fused dispatches, not pass."""
        m = _reduced_model()
        qparams = m.quantize(m.init(KEY), QuantPlan.mlp_only())
        jaxpr = _decode_jaxpr(m, qparams)
        expected = manifest.model_sites(m, "decode", kv_len=16)
        out = passes.dispatch_audit(jt.pallas_sites(jaxpr), expected)
        assert ("dispatch", "count_mismatch") in _codes(out), out
        # ... and the full plan's twin trace is clean
        full = m.quantize(m.init(KEY))
        clean = passes.dispatch_audit(
            jt.pallas_sites(_decode_jaxpr(m, full)), expected)
        assert clean == []

    def test_dropped_skip_list_flags_missing_prefetch(self):
        """Grouped-MoE dispatches without the ``expert_counts`` scalar
        prefetch (the zero-capacity skip list dropped) are a contract
        violation — dead MXU work on empty experts."""
        E, d, F = 4, 36, 24
        ks = jax.random.split(KEY, 3)
        qp = quantize_moe_experts(
            {"up": jax.random.normal(ks[0], (E, d, F)) * 0.1,
             "down": jax.random.normal(ks[1], (E, F, d)) * 0.1,
             "gate": jax.random.normal(ks[2], (E, d, F)) * 0.1})
        xe = jnp.zeros((E, 5, d))
        expected = manifest.mlp_sites(F, grouped=True)
        dropped = jax.make_jaxpr(
            lambda a: quantized_moe_apply(qp, a, "swiglu",
                                          use_kernel=True))(xe)
        out = passes.dispatch_audit(jt.pallas_sites(dropped), expected)
        assert ("dispatch", "missing_prefetch") in _codes(out), out
        kept = jax.make_jaxpr(
            lambda a, c: quantized_moe_apply(
                qp, a, "swiglu", use_kernel=True, expert_counts=c))(
                    xe, jnp.ones((E,), jnp.int32))
        assert passes.dispatch_audit(jt.pallas_sites(kept),
                                     expected) == []

    def test_unknown_kernel_flagged(self):
        """A pallas kernel missing from the manifest's site table cannot
        silently count toward any class."""
        site = jt.PallasSite(kernel="_rogue_kernel", src="rogue at x:1",
                             blocks=(), scratch_bytes=0, num_prefetch=0,
                             out_dtypes=())
        out = passes.dispatch_audit([site], manifest.mlp_sites(64))
        assert ("dispatch", "unknown_kernel") in _codes(out)


# ---------------------------------------------------------------------------
# Pass 2: dtype-flow audit
# ---------------------------------------------------------------------------
class TestDtypeFlowMutations:
    def test_unpsummed_accumulator_flagged(self):
        """An int32 partial accumulator returned to XLA with no psum
        consuming it is the classic epilogue-fusion regression."""
        xq = jnp.ones((8, 128), jnp.int8)
        wq = jnp.ones((128, 256), jnp.int8)
        jaxpr = jax.make_jaxpr(
            lambda a, b: ops.cim_int8_gemm_acc(a, b, interpret=True))(
                xq, wq)
        out = passes.dtype_flow_audit(jaxpr)
        assert ("dtype_flow", "int32_escape") in _codes(out), out
        assert any("_cim_gemm_kernel" in v.site for v in out)

    def test_psummed_accumulator_clean(self):
        """The sanctioned escape: the same accumulator consumed by a
        model-axis psum (TP row-parallel) — across the pjit levels
        between the kernel and the collective."""
        mesh = _model_mesh()
        xq = jnp.ones((8, 128), jnp.int8)
        wq = jnp.ones((128, 256), jnp.int8)

        @jax.jit
        def sharded(a, b):
            def body(a, b):
                acc = ops.cim_int8_gemm_acc(a, b, interpret=True)
                return jax.lax.psum(acc, manifest.TP_AXIS)
            return jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(), check_vma=False)(a, b)

        jaxpr = jax.make_jaxpr(sharded)(xq, wq)
        assert passes.dtype_flow_audit(jaxpr) == []

    def test_accumulator_dequantized_before_psum_flagged(self):
        """Converting the int32 accumulator to f32 *before* the psum
        breaks cross-shard exactness even though a psum follows."""
        mesh = _model_mesh()
        xq = jnp.ones((8, 128), jnp.int8)
        wq = jnp.ones((128, 256), jnp.int8)

        @jax.jit
        def sharded(a, b):
            def body(a, b):
                acc = ops.cim_int8_gemm_acc(a, b, interpret=True)
                return jax.lax.psum(acc.astype(jnp.float32),
                                    manifest.TP_AXIS)
            return jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(), check_vma=False)(a, b)

        out = passes.dtype_flow_audit(jax.make_jaxpr(sharded)(xq, wq))
        assert ("dtype_flow", "int32_escape") in _codes(out), out

    def test_xla_int8_dot_flagged(self):
        xq = jnp.ones((8, 64), jnp.int8)
        wq = jnp.ones((64, 32), jnp.int8)
        jaxpr = jax.make_jaxpr(
            lambda a, b: jax.lax.dot(a, b,
                                     preferred_element_type=jnp.int32))(
                xq, wq)
        out = passes.dtype_flow_audit(jaxpr)
        assert ("dtype_flow", "int8_xla_dot") in _codes(out), out

    def test_dequant_leak_flagged_in_decode_not_prefill(self):
        q = jnp.ones((4, 64), jnp.int8)
        s = jnp.ones((4, 1), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda a, b: a.astype(jnp.float32) * b)(q, s)
        assert ("dtype_flow", "dequant_leak") in _codes(
            passes.dtype_flow_audit(jaxpr, phase="decode"))
        # prefill attention legitimately dequantizes the int8 cache
        assert passes.dtype_flow_audit(jaxpr, phase="prefill") == []

    def test_kv_not_int8_flagged(self):
        out = passes.dtype_flow_audit(
            jax.make_jaxpr(lambda x: x + 1)(jnp.ones(3)),
            kv_avals=[("cache/k", jax.ShapeDtypeStruct(
                (2, 8), jnp.float32))])
        assert _codes(out) == [("dtype_flow", "kv_not_int8")]


# ---------------------------------------------------------------------------
# Pass 3: collective audit
# ---------------------------------------------------------------------------
class TestCollectiveMutations:
    def _sharded_jaxpr(self, body):
        mesh = _model_mesh()
        x = jnp.ones((4, 8))
        return jax.make_jaxpr(
            lambda a: jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                    out_specs=P(), check_vma=False)(a))(x)

    def test_all_gather_flagged(self):
        """An all-gather on the model axis re-opens the data-movement
        tax the TP layout exists to avoid."""
        jaxpr = self._sharded_jaxpr(
            lambda a: jax.lax.all_gather(a, manifest.TP_AXIS))
        out = passes.collective_audit(jaxpr, sharded=True)
        assert ("collective", "forbidden_collective") in _codes(out), out

    def test_float_psum_flagged(self):
        jaxpr = self._sharded_jaxpr(
            lambda a: jax.lax.psum(a, manifest.TP_AXIS))
        out = passes.collective_audit(jaxpr, sharded=True)
        assert ("collective", "psum_not_int") in _codes(out), out

    def test_int_psum_clean_and_counted(self):
        from collections import Counter
        jaxpr = self._sharded_jaxpr(
            lambda a: jax.lax.psum(a.astype(jnp.int32),
                                   manifest.TP_AXIS))
        key = ("psum", (manifest.TP_AXIS,))
        assert passes.collective_audit(
            jaxpr, sharded=True, expected=Counter({key: 1})) == []
        out = passes.collective_audit(
            jaxpr, sharded=True, expected=Counter({key: 2}))
        assert ("collective", "count_mismatch") in _codes(out), out

    def test_unsharded_trace_must_have_no_collectives(self):
        jaxpr = self._sharded_jaxpr(
            lambda a: jax.lax.psum(a.astype(jnp.int32),
                                   manifest.TP_AXIS))
        out = passes.collective_audit(jaxpr, sharded=False)
        assert ("collective", "unexpected_collective") in _codes(out)


# ---------------------------------------------------------------------------
# Pass 4: VMEM / block-shape audit
# ---------------------------------------------------------------------------
class TestVmemMutations:
    def test_over_budget_flagged(self):
        """A real traced rowquant site fails against a budget smaller
        than its block footprint (and passes the hardware budget)."""
        x = jnp.ones((256, 512), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda a: quantize_rows_int8(a, interpret=True))(x)
        sites = jt.pallas_sites(jaxpr)
        assert sites, "no pallas sites traced"
        assert passes.vmem_audit(sites) == []
        out = passes.vmem_audit(sites, budget_bytes=1024)
        assert ("vmem", "over_budget") in _codes(out), out

    def test_bad_block_geometry_flagged(self):
        """A weight block that is neither a core-tile multiple nor the
        full axis (here block_n=64 over N=512 with n_dim=256) would map
        onto partial CIM cores — flagged."""
        xq = jnp.ones((256, 512), jnp.int8)
        wq = jnp.ones((512, 512), jnp.int8)
        jaxpr = jax.make_jaxpr(
            lambda a, b: cim_gemm_int8(a, b, block_n=64,
                                       interpret=True))(xq, wq)
        out = passes.vmem_audit(jt.pallas_sites(jaxpr))
        assert ("vmem", "bad_block_geometry") in _codes(out), out
        clean = jax.make_jaxpr(
            lambda a, b: cim_gemm_int8(a, b, interpret=True))(xq, wq)
        assert passes.vmem_audit(jt.pallas_sites(clean)) == []


    def test_hbm_operands_and_semaphores_take_no_vmem(self):
        """The paged decode kernel leaves its pools in HBM and DMAs their
        pages itself: its footprint is the blocks it maps into VMEM and
        its VMEM scratch, not the pools, DMA semaphores or SMEM scalars,
        so a pool far over the budget still passes."""
        B, KH, G, D, bs, nb, L, NB = 2, 2, 2, 128, 16, 4, 2, 4097
        pool = jax.ShapeDtypeStruct((L, NB, bs, KH, D), jnp.int8)
        scales = jax.ShapeDtypeStruct((L, NB, bs, KH), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, pos, bt, qp, ks, vs: ops.decode_attention_paged(
                q, k, v, pos, bt, qp, 0, k_scale_pages=ks, v_scale_pages=vs,
                interpret=True))(
            jax.ShapeDtypeStruct((B, KH, G, D), jnp.bfloat16), pool, pool,
            jax.ShapeDtypeStruct((L, NB, bs), jnp.int32),
            jax.ShapeDtypeStruct((B, nb), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32), scales, scales)
        (site,) = jt.pallas_sites(jaxpr)
        assert [b.nbytes for b in site.blocks][1:3] == [0, 0]
        T = nb * bs                        # one compute block: the table
        buffers = 2 * 2 * T * KH * D       # K and V, double-buffered int8
        state = 4 * KH * G * (D + 2)       # m, l, acc in f32
        assert site.scratch_bytes == buffers + state
        budget = L * NB * bs * KH * D      # one pool's bytes
        assert passes.vmem_audit([site], budget_bytes=budget) == []


# ---------------------------------------------------------------------------
# Pass 5: retrace guard
# ---------------------------------------------------------------------------
class TestRetraceMutations:
    def test_retraced_step_flagged(self):
        f = jax.jit(lambda x: x + 1)
        f(jnp.zeros((3,)))
        f(jnp.zeros((4,)))          # shape change -> second trace
        out = passes.retrace_audit({"step": f}, limits={"step": 1})
        assert _codes(out) == [("retrace", "trace_cache_miss")]

    def test_stable_step_clean(self):
        f = jax.jit(lambda x: x + 1)
        f(jnp.zeros((3,)))
        f(jnp.zeros((3,)))          # same shape -> cache hit
        assert passes.retrace_audit({"step": f},
                                    limits={"step": 1}) == []

    def test_never_traced_and_not_jitted_flagged(self):
        cold = jax.jit(lambda x: x)
        out = passes.retrace_audit(
            {"cold": cold, "plain": lambda x: x},
            limits={"cold": 1, "plain": 1})
        assert ("retrace", "never_traced") in _codes(out)
        assert ("retrace", "not_jitted") in _codes(out)


# ---------------------------------------------------------------------------
# Manifest derivation: one contract honest at every scale
# ---------------------------------------------------------------------------
class TestManifestDerivation:
    def test_gemma2b_threshold_crossing(self):
        """Full-size gemma-2b (d_ff 16384 > MAX_FUSED_QUANT_N) takes a
        7th decode dispatch — the standalone hidden requant — while the
        reduced config stays at the canonical 6.  The manifest derives
        both from the dims instead of pinning either number."""
        from repro.configs import get_config, reduced_config
        from repro.models import build_model
        full = build_model(get_config("gemma-2b"))
        red = build_model(reduced_config(get_config("gemma-2b")))
        n_full = sum(manifest.model_sites(full, "decode",
                                          kv_len=128).values())
        n_red = sum(manifest.model_sites(red, "decode",
                                         kv_len=16).values())
        assert (n_red, n_full) == (6, 7)

    def test_splitkv_adds_combine(self):
        from repro.configs import get_config
        from repro.models import build_model
        m = build_model(get_config("gemma-2b"))
        short = manifest.model_sites(m, "decode", kv_len=128)
        long = manifest.model_sites(m, "decode",
                                    kv_len=manifest.SPLITKV_THRESHOLD * 2)
        assert short["attn_combine"] == 0
        assert long["attn_combine"] == 1

    def test_audit_lm_end_to_end_reduced(self):
        """The whole pipeline — abstract trace, manifest derivation,
        all four static passes — on one reduced arch."""
        from repro.analysis import audit_lm
        rep = audit_lm("gemma-2b", "decode", reduced=True, kv_len=16)
        assert rep.ok, rep.diff_lines()
        assert rep.n_dispatches == 6

    def test_full_plan_archs_nonempty(self):
        from repro.analysis import full_plan_archs
        archs = full_plan_archs()
        assert "gemma-2b" in archs
        assert "qwen2-moe-a2.7b" in archs


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])

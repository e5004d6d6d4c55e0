"""Tensor-parallel fused INT8 pipeline tests (shard_map over a model-axis
mesh of forced host devices).

Every test runs in a subprocess so XLA_FLAGS can force 8 CPU devices
before jax initializes (the same pattern as test_distribution); `make
test-tp` runs this file explicitly as part of `make verify`.

The parity contract is *bitwise*: under 1-, 2-, and 4-way model meshes
the sharded pipelines (column-parallel QKV/up/gate, row-parallel
out-proj/down with the int32 psum folded in before the residual
epilogue, expert-parallel grouped MoE) must equal the unsharded jnp
oracle — and, on the kernel path, the unsharded Pallas pipeline —
bit-for-bit.  Comparisons are jit-vs-jit (XLA's scalar-chain rewrites
differ between eager and jit, so eager references are not the target).
"""
import textwrap

import pytest

from conftest import run_forced_devices_subprocess as _run_subprocess


# Shared setup: ragged-free dims divisible by 4 (divisibility is a
# fallback, tested separately) and a per-mesh fresh jit so the sharding
# context is active at trace time.
_SETUP = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.models.layers import param_values, mlp_init
    from repro.models.attention import attention_init
    from repro.parallel.context import sharding_context
    from repro.quant import (quantize_attention, quantize_mlp,
                             quantize_moe_experts, quantized_mlp_apply,
                             quantized_moe_apply, quantized_out_proj,
                             quantized_qkv_proj)

    def check(name, mk_ref, mk_tp, *args):
        ref = jax.jit(mk_ref())(*args)
        for p in (1, 2, 4):
            mesh = make_mesh((p,), ("model",))
            f = jax.jit(mk_tp())          # fresh jit per mesh: the
            with sharding_context(mesh):  # context is read at trace time
                out = f(*args)
            assert (np.asarray(out) == np.asarray(ref)).all(), (name, p)
        print(name, "OK")
""")


class TestTPParity:
    def test_fused_mlp_parity_oracle(self):
        """TP fused MLP (gated + non-gated, w/ residual) == unsharded jnp
        oracle bit-for-bit at 1/2/4 shards."""
        out = _run_subprocess(_SETUP + textwrap.dedent("""
            d, ff = 64, 128
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, d)) * 0.5
            res = jax.random.normal(jax.random.PRNGKey(2), (4, 6, d)) * 0.5
            for act in ("geglu", "gelu"):
                qp = quantize_mlp(param_values(mlp_init(
                    jax.random.PRNGKey(0), d, ff, act, dtype=jnp.float32)))
                mk = lambda qp=qp, act=act: (
                    lambda a, r: quantized_mlp_apply(
                        qp, a, act, use_kernel=False, residual=r))
                check(f"mlp_{act}", mk, mk, x, res)
        """))
        assert "mlp_geglu OK" in out and "mlp_gelu OK" in out

    def test_wide_qkv_and_out_proj_parity_oracle(self):
        """Column-parallel wide QKV and row-parallel out-projection (+
        fused residual) == unsharded oracle bit-for-bit."""
        out = _run_subprocess(_SETUP + textwrap.dedent("""
            d, H, KH, Dh = 64, 4, 2, 16
            qa = quantize_attention(param_values(attention_init(
                jax.random.PRNGKey(0), d, H, KH, Dh, dtype=jnp.float32)))
            x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, d)) * 0.5
            ao = jax.random.normal(jax.random.PRNGKey(2), (2, 5, H, Dh)) * 0.5
            res = jax.random.normal(jax.random.PRNGKey(3), (2, 5, d)) * 0.5
            mk = lambda: (lambda a: quantized_qkv_proj(
                qa["qkv"], a, use_kernel=False))
            check("qkv", mk, mk, x)
            mk = lambda: (lambda a, r: quantized_out_proj(
                qa["o"], a, residual=r, use_kernel=False))
            check("out_proj", mk, mk, ao, res)
        """))
        assert "qkv OK" in out and "out_proj OK" in out

    def test_grouped_moe_parity_oracle(self):
        """Expert-parallel grouped MoE pipeline (with a zero-capacity
        expert and its skip list) == unsharded oracle bit-for-bit."""
        out = _run_subprocess(_SETUP + textwrap.dedent("""
            E, d, F, T = 4, 36, 24, 6
            ks = jax.random.split(jax.random.PRNGKey(7), 3)
            qm = quantize_moe_experts({
                "up": jax.random.normal(ks[0], (E, d, F)) * 0.1,
                "down": jax.random.normal(ks[1], (E, F, d)) * 0.1,
                "gate": jax.random.normal(ks[2], (E, d, F)) * 0.1})
            xe = jax.random.normal(jax.random.PRNGKey(8), (E, T, d)) * 0.5
            xe = xe.at[1].set(0.0)
            counts = jnp.array([3, 0, 2, 1], jnp.int32)
            mk_ref = lambda: (lambda a, c: quantized_moe_apply(
                qm, a, "swiglu", use_kernel=False))
            check("grouped_moe", mk_ref,
                  lambda: (lambda a, c: quantized_moe_apply(
                      qm, a, "swiglu", use_kernel=False, expert_counts=c)),
                  xe, counts)
        """))
        assert "grouped_moe OK" in out

    @pytest.mark.slow
    def test_kernel_path_parity(self):
        """The same four TP paths on the Pallas kernel pipeline
        (interpret mode) == the unsharded kernel pipeline bit-for-bit."""
        out = _run_subprocess(_SETUP + textwrap.dedent("""
            d, ff, H, KH, Dh = 64, 128, 4, 2, 16
            qp = quantize_mlp(param_values(mlp_init(
                jax.random.PRNGKey(0), d, ff, "geglu", dtype=jnp.float32)))
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, d)) * 0.5
            res = jax.random.normal(jax.random.PRNGKey(2), (4, 6, d)) * 0.5
            mk = lambda: (lambda a, r: quantized_mlp_apply(
                qp, a, "geglu", use_kernel=True, residual=r))
            check("mlp_kernel", mk, mk, x, res)

            qa = quantize_attention(param_values(attention_init(
                jax.random.PRNGKey(0), d, H, KH, Dh, dtype=jnp.float32)))
            ao = jax.random.normal(jax.random.PRNGKey(3), (2, 5, H, Dh)) * 0.5
            r2 = jax.random.normal(jax.random.PRNGKey(4), (2, 5, d)) * 0.5
            mk = lambda: (lambda a: quantized_qkv_proj(
                qa["qkv"], a, use_kernel=True))
            check("qkv_kernel", mk, mk, x[:2, :5])
            mk = lambda: (lambda a, r: quantized_out_proj(
                qa["o"], a, residual=r, use_kernel=True))
            check("out_proj_kernel", mk, mk, ao, r2)

            E, F, T = 4, 24, 6
            ks = jax.random.split(jax.random.PRNGKey(7), 3)
            qm = quantize_moe_experts({
                "up": jax.random.normal(ks[0], (E, 36, F)) * 0.1,
                "down": jax.random.normal(ks[1], (E, F, 36)) * 0.1,
                "gate": jax.random.normal(ks[2], (E, 36, F)) * 0.1})
            xe = jax.random.normal(jax.random.PRNGKey(8), (E, T, 36)) * 0.5
            xe = xe.at[1].set(0.0)
            counts = jnp.array([3, 0, 2, 1], jnp.int32)
            mk_ref = lambda: (lambda a, c: quantized_moe_apply(
                qm, a, "swiglu", use_kernel=True))
            check("moe_kernel", mk_ref,
                  lambda: (lambda a, c: quantized_moe_apply(
                      qm, a, "swiglu", use_kernel=True, expert_counts=c)),
                  xe, counts)
        """))
        for name in ("mlp_kernel", "qkv_kernel", "out_proj_kernel",
                     "moe_kernel"):
            assert f"{name} OK" in out

    def test_nondivisible_dims_fall_back_to_unsharded(self):
        """Dims the model axis does not divide run the unsharded path
        under an active context (replicate-on-indivisible), with
        unchanged results."""
        out = _run_subprocess(_SETUP + textwrap.dedent("""
            d, ff = 36, 20                       # 20 % 8 != 0
            qp = quantize_mlp(param_values(mlp_init(
                jax.random.PRNGKey(0), d, ff, "geglu", dtype=jnp.float32)))
            x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, d)) * 0.5
            ref = jax.jit(lambda a: quantized_mlp_apply(
                qp, a, "geglu", use_kernel=False))(x)
            mesh = make_mesh((8,), ("model",))
            f = jax.jit(lambda a: quantized_mlp_apply(
                qp, a, "geglu", use_kernel=False))
            with sharding_context(mesh):
                out = f(x)
            assert (np.asarray(out) == np.asarray(ref)).all()
            print("FALLBACK_OK")
        """))
        assert "FALLBACK_OK" in out


class TestTPStructure:
    def test_per_shard_contract_audited(self):
        """Acceptance bar: under a 2-way model mesh each full-plan
        decode step passes the execution-contract audit — per-shard
        dispatch counts from the manifest (6 for a dense block,
        attention included; 9 for a MoE block at reduced dims), the
        exact pmax/psum collective budget with integer psums, clean
        dtype flow through the shard_map body, and in-budget VMEM
        blocks.  Structural on the jaxpr; no execution."""
        out = _run_subprocess("""
            from repro.analysis import audit_lm

            for arch in ("gemma-2b", "qwen2-moe-a2.7b"):
                rep = audit_lm(arch, "decode", tp=2, reduced=True,
                               kv_len=16)
                assert rep.ok, rep.diff_lines()
                print(arch, "DISPATCHES", rep.n_dispatches)
        """)
        assert "gemma-2b DISPATCHES 6" in out
        assert "qwen2-moe-a2.7b DISPATCHES 9" in out


class TestTPEngine:
    @pytest.mark.slow
    def test_quant_plan_engine_bit_identical_generations(self):
        """Acceptance bar: a full-plan ServingEngine on a 2-way model
        mesh generates bit-identically to the unsharded engine, with
        the quantized weights (q AND scale) actually device_put sharded
        on the model axis."""
        out = _run_subprocess("""
            import jax, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.configs import get_config, reduced_config
            from repro.models import build_model
            from repro.quant import QuantPlan
            from repro.serving import Request, ServingEngine

            cfg = reduced_config(get_config("gemma-2b"))
            m = build_model(cfg)
            params = m.init(jax.random.PRNGKey(0))
            rng = np.random.default_rng(0)
            prompts = [rng.integers(0, cfg.vocab, 5 + i).astype(np.int32)
                       for i in range(3)]

            def run(mesh):
                eng = ServingEngine(m, params, n_slots=2, max_len=64,
                                    prefill_bucket=8,
                                    quant_plan=QuantPlan.full(), mesh=mesh)
                reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
                        for i, p in enumerate(prompts)]
                for r in reqs:
                    eng.submit(r)
                eng.run_until_done(max_iters=100)
                return [r.generated for r in reqs], eng

            base, _ = run(None)
            mesh = make_mesh((2,), ("model",))
            gens, eng = run(mesh)
            assert gens == base, (gens, base)
            up = eng.params["group_0"]["mlp"]["up"]
            assert "model" in tuple(up.q.sharding.spec), up.q.sharding
            # the scale co-shards with q on the output-channel axis
            assert "model" in tuple(up.scale.sharding.spec), \
                up.scale.sharding
            print("ENGINE_TP_OK")
        """)
        assert "ENGINE_TP_OK" in out

    @pytest.mark.slow
    def test_kv_cache_sharded_decode_parity(self):
        """Acceptance bar: TP decode at 2/4-way meshes runs with the
        int8 KV cache *sharded* over KV heads (per-shard KV memory is
        1/p of the replicated cache — decode attention is memory-bound
        and the cache is the memory), head-parallel flash-decode with no
        collectives, and generations equal to the unsharded engine."""
        out = _run_subprocess("""
            import dataclasses
            import jax, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.configs import get_config, reduced_config
            from repro.models import build_model
            from repro.quant import QuantPlan
            from repro.serving import Request, ServingEngine

            # 4 KV heads so 2- and 4-way model meshes divide them
            cfg = dataclasses.replace(reduced_config(get_config("gemma-2b")),
                                      n_kv_heads=4)
            m = build_model(cfg)
            params = m.init(jax.random.PRNGKey(0))
            rng = np.random.default_rng(1)
            prompts = [rng.integers(0, cfg.vocab, 4 + i).astype(np.int32)
                       for i in range(3)]

            def run(mesh):
                eng = ServingEngine(m, params, n_slots=2, max_len=64,
                                    prefill_bucket=8,
                                    quant_plan=QuantPlan.full(), mesh=mesh)
                reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
                        for i, p in enumerate(prompts)]
                for r in reqs:
                    eng.submit(r)
                eng.run_until_done(max_iters=100)
                return [r.generated for r in reqs], eng

            base, eng0 = run(None)
            assert eng0.kv_dtype == "int8"      # plan covers attn_kv
            for p in (2, 4):
                mesh = make_mesh((p,), ("model",))
                gens, eng = run(mesh)
                assert gens == base, (p, gens, base)
                ck = eng.cache["group_0"]["k"]
                # [layers, slots, kv_seq, kv_heads, D] — heads on model
                assert ck.dtype == jax.numpy.int8
                assert tuple(ck.sharding.spec)[3] == "model", \
                    ck.sharding.spec
                shard_shape = ck.addressable_shards[0].data.shape
                assert shard_shape[3] == 4 // p, shard_shape
                ks = eng.cache["group_0"]["k_scale"]
                assert tuple(ks.sharding.spec)[3] == "model"
                print("KV_SHARD_OK", p)
        """)
        assert "KV_SHARD_OK 2" in out and "KV_SHARD_OK 4" in out

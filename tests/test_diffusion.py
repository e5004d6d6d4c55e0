"""DiT diffusion-subsystem tests (ISSUE 5 acceptance bars).

Pins, in order: the adaLN DiT model's structure and quantized parity,
the full-plan denoise step's 6-Pallas-dispatch invariant (structural
jaxpr, like the 5-dense/8-MoE LLM pins), traced-block MACs ==
``core.workloads.dit_block_ops`` (simulator cross-validation), the
DDIM/Euler + CFG sampler semantics, the batched DiffusionEngine, the
plan-consistent simulator lowering, and bitwise tensor-parallel parity
under a model-axis mesh.
"""
import dataclasses
import math
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_forced_devices_subprocess as _run_subprocess
from repro.configs import DIT_ARCH_IDS, get_dit_config
from repro.core.bridge import dit_graph_from_config, dit_spec
from repro.core.operators import MatMulOp, OpKind
from repro.core.workloads import dit_block_ops, dit_tokens, dit_xl2
from repro.diffusion import (DiffusionEngine, DiffusionSchedule,
                             ImageRequest, guided_eps, sample)
from repro.models.dit import (DiTModel, dit_block_apply, patchify,
                              unpatchify)
from repro.quant import QuantPlan, QuantizedLinear, kernel_mode

KEY = jax.random.PRNGKey(0)
CFG = get_dit_config("dit-test")


from repro.analysis import iter_eqns as iter_jaxpr_eqns  # noqa: E402
from repro.analysis import jaxpr_tools as jt  # noqa: E402
from repro.analysis import manifest, passes  # noqa: E402


def _dot_general_macs(eqn) -> int:
    """MACs of one dot_general eqn: prod(lhs shape) x rhs free dims."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    free = [s for i, s in enumerate(rhs.shape)
            if i not in set(rc) | set(rb)]
    return math.prod(lhs.shape) * math.prod(free)


def _model_and_params(cfg=CFG):
    m = DiTModel(cfg)
    return m, m.init(KEY)


def _latents(key, cfg=CFG, batch=2):
    return jax.random.normal(
        key, (batch, cfg.in_channels, cfg.input_size, cfg.input_size),
        jnp.float32)


class TestDiTModel:
    def test_patchify_roundtrip(self):
        x = jax.random.normal(KEY, (2, 4, 8, 8))
        tok = patchify(x, 2)
        assert tok.shape == (2, 16, 16)
        back = unpatchify(tok, 2, 4, 8)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_forward_shapes(self):
        m, params = _model_and_params()
        x = _latents(jax.random.PRNGKey(1))
        t = jnp.array([500, 10], jnp.int32)
        y = jnp.array([3, 7], jnp.int32)
        out = m.forward(params, x, t, y)
        assert out.shape == x.shape          # learn_sigma=False: eps only
        assert np.isfinite(np.asarray(out)).all()

    def test_learn_sigma_doubles_output_channels(self):
        cfg = dataclasses.replace(CFG, learn_sigma=True)
        m, params = _model_and_params(cfg)
        x = _latents(jax.random.PRNGKey(1), cfg)
        out = m.forward(params, x, jnp.zeros((2,), jnp.int32),
                        jnp.zeros((2,), jnp.int32))
        assert out.shape == (2, 2 * cfg.in_channels, cfg.input_size,
                             cfg.input_size)

    def test_conditioning_depends_on_t_and_y(self):
        m, params = _model_and_params()
        t = jnp.array([0, 999], jnp.int32)
        y = jnp.array([1, 1], jnp.int32)
        c = m.conditioning(params, t, y)
        assert c.shape == (2, CFG.d_model)
        assert not np.allclose(np.asarray(c[0]), np.asarray(c[1]))
        c2 = m.conditioning(params, t, jnp.array([1, 2], jnp.int32))
        assert not np.allclose(np.asarray(c[1]), np.asarray(c2[1]))

    def test_param_count_matches_init(self):
        m, params = _model_and_params()
        actual = sum(int(np.prod(v.shape))
                     for v in jax.tree.leaves(params))
        assert abs(actual - CFG.param_count()) / actual < 0.02

    def test_registry_dit_configs(self):
        from repro.configs import get_config
        assert set(DIT_ARCH_IDS) == {"dit-xl-2", "dit-test"}
        xl = get_dit_config("dit-xl-2")
        spec = dit_xl2()                       # paper Table III
        assert (xl.d_model, xl.n_heads, xl.n_layers) == \
            (spec.layer.d_model, spec.layer.n_heads, spec.n_layers)
        assert xl.tokens == dit_tokens(512) == 1024
        with pytest.raises(KeyError):
            get_dit_config("gemma-2b")
        with pytest.raises(KeyError):
            get_config("dit-xl-2")             # routed to get_dit_config


class TestDiTQuant:
    def test_full_plan_forward_close_to_bf16(self):
        m, params = _model_and_params()
        qparams = m.quantize(params)
        x = _latents(jax.random.PRNGKey(1))
        t = jnp.array([500, 10], jnp.int32)
        y = jnp.array([3, 7], jnp.int32)
        ref = m.forward(params, x, t, y)
        out = m.forward(qparams, x, t, y)
        a, b = np.asarray(ref), np.asarray(out)
        corr = np.corrcoef(a.ravel(), b.ravel())[0, 1]
        assert corr > 0.99, corr

    def test_partial_plans_and_idempotence(self):
        m, params = _model_and_params()
        blocks = m.quantize(params, QuantPlan.none())["blocks"]
        assert not isinstance(blocks["adaln"]["kernel"], QuantizedLinear)
        assert "q" in blocks["attn"]                     # untouched bf16
        mlp_only = m.quantize(params, QuantPlan.mlp_only())["blocks"]
        assert isinstance(mlp_only["mlp"]["up"], QuantizedLinear)
        assert not isinstance(mlp_only["adaln"]["kernel"], QuantizedLinear)
        q1 = m.quantize(params)
        q2 = m.quantize(q1)                              # idempotent
        b1, b2 = q1["blocks"], q2["blocks"]
        assert (np.asarray(b1["adaln"]["kernel"].q) ==
                np.asarray(b2["adaln"]["kernel"].q)).all()
        assert (np.asarray(b1["attn"]["qkv"].q) ==
                np.asarray(b2["attn"]["qkv"].q)).all()

    def test_full_plan_denoise_step_matches_manifest(self):
        """Acceptance bar: a full-plan DiT-block denoise step executes
        exactly the manifest's schedule (6 fused Pallas dispatches at
        these dims: adaLN modulation GEMM + wide QKV + out-projection +
        the 3-dispatch MLP pipeline) — and because the N blocks scan
        over stacked params, the whole-model forward traces those same
        kernels.  Dtype flow is clean: no int32 to HBM, no XLA int8
        dot, no XLA dequant.  Structural on the jaxpr."""
        m, params = _model_and_params()
        qparams = m.quantize(params)
        x = _latents(jax.random.PRNGKey(1))
        t = jnp.zeros((2,), jnp.int32)
        y = jnp.zeros((2,), jnp.int32)
        with kernel_mode(True):
            jaxpr = jax.make_jaxpr(
                lambda p, a, b, c: m.forward(p, a, b, c))(qparams, x, t, y)
        expected = manifest.dit_sites(CFG)
        assert sum(expected.values()) == 6               # the paper bar
        assert passes.dispatch_audit(jt.pallas_sites(jaxpr),
                                     expected) == []
        assert passes.dtype_flow_audit(jaxpr, phase="step") == []

    def test_dispatch_count_constant_in_depth(self):
        """Doubling the block count changes nothing structurally — the
        blocks scan, so the denoise step's kernel trace is depth-free."""
        counts = {}
        for L in (2, 4):
            cfg = dataclasses.replace(CFG, n_layers=L)
            m, params = _model_and_params(cfg)
            qparams = m.quantize(params)
            x = _latents(jax.random.PRNGKey(1), cfg)
            zeros = jnp.zeros((2,), jnp.int32)
            with kernel_mode(True):
                jaxpr = jax.make_jaxpr(
                    lambda p, a, b, c, mm=m: mm.forward(p, a, b, c))(
                        qparams, x, zeros, zeros)
            counts[L] = len(jt.pallas_sites(jaxpr))
        assert counts[2] == counts[4] == \
            sum(manifest.dit_sites(CFG).values()), counts

    def test_traced_block_macs_match_dit_block_ops(self):
        """Acceptance bar: the executable DiT block's traced MAC count
        equals the simulator's analytic ``dit_block_ops`` for the same
        shapes — the paper-table DiT rows are backed by runnable code.
        Counted on the bf16 trace (every weight GEMM is a dot_general;
        the quantized path runs the same logical contractions inside
        padded Pallas kernels)."""
        m, params = _model_and_params()
        block = jax.tree.map(lambda a: a[0], params["blocks"])
        B, T, d = 2, CFG.tokens, CFG.d_model
        x = jnp.zeros((B, T, d))
        c = jnp.zeros((B, d))
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        jaxpr = jax.make_jaxpr(
            lambda bx, bc: dit_block_apply(block, bx, bc, CFG, pos))(x, c)
        traced = sum(_dot_general_macs(e)
                     for e in iter_jaxpr_eqns(jaxpr.jaxpr)
                     if e.primitive.name == "dot_general")
        analytic = sum(op.macs for op in dit_block_ops(dit_spec(CFG), B, T)
                       if isinstance(op, MatMulOp))
        assert traced == analytic, (traced, analytic)

    @pytest.mark.slow
    def test_kernel_and_oracle_agree_block(self):
        """One full-plan block on the fused Pallas pipeline (interpret
        mode) vs the jnp oracle."""
        m, params = _model_and_params()
        block = jax.tree.map(lambda a: a[0], m.quantize(params)["blocks"])
        B, T, d = 2, CFG.tokens, CFG.d_model
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, d)) * 0.5
        c = jax.random.normal(jax.random.PRNGKey(2), (B, d)) * 0.5
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        with kernel_mode(False):
            oracle = dit_block_apply(block, x, c, CFG, pos)
        with kernel_mode(True):
            fused = dit_block_apply(block, x, c, CFG, pos)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                                   rtol=2e-4, atol=2e-4)


class TestSampler:
    def _setup(self):
        m, params = _model_and_params()
        y = jnp.array([1, 5], jnp.int32)
        return m, params, y

    def test_ddim_fixed_seed_deterministic(self):
        m, params, y = self._setup()
        a = sample(m, params, y, key=jax.random.PRNGKey(3), num_steps=3)
        b = sample(m, params, y, key=jax.random.PRNGKey(3), num_steps=3)
        assert (np.asarray(a) == np.asarray(b)).all()
        c = sample(m, params, y, key=jax.random.PRNGKey(4), num_steps=3)
        assert not np.allclose(np.asarray(a), np.asarray(c))

    def test_cfg_batched_equals_two_passes(self):
        """The 2B-stacked cond+uncond evaluation equals two separate
        B-row passes — at the eps level and through the whole sampler."""
        m, params, y = self._setup()
        x = _latents(jax.random.PRNGKey(5))
        t = jnp.full((2,), 700, jnp.int32)
        eb = guided_eps(m, params, x, t, y, cfg_scale=2.0, batched=True)
        es = guided_eps(m, params, x, t, y, cfg_scale=2.0, batched=False)
        np.testing.assert_allclose(np.asarray(eb), np.asarray(es),
                                   rtol=1e-5, atol=1e-5)
        sb = sample(m, params, y, x_init=x, num_steps=2, cfg_scale=2.0,
                    cfg_batched=True)
        ss = sample(m, params, y, x_init=x, num_steps=2, cfg_scale=2.0,
                    cfg_batched=False)
        np.testing.assert_allclose(np.asarray(sb), np.asarray(ss),
                                   rtol=1e-4, atol=1e-4)

    def test_zero_steps_returns_initial_noise(self):
        m, params, y = self._setup()
        x = _latents(jax.random.PRNGKey(6))
        out = sample(m, params, y, x_init=x, num_steps=0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    def test_one_step_is_single_ddim_jump(self):
        """num_steps=1 evaluates the model once at t=T-1 and jumps to
        the x0 prediction (alpha_bar_prev == 1)."""
        m, params, y = self._setup()
        sched = DiffusionSchedule()
        x = _latents(jax.random.PRNGKey(7))
        out = sample(m, params, y, x_init=x, num_steps=1, schedule=sched)
        ab = sched.alpha_bars()[sched.n_train_steps - 1]
        t = jnp.full((2,), sched.n_train_steps - 1, jnp.int32)
        eps = guided_eps(m, params, x, t, y)
        x0 = (x - np.sqrt(1 - ab) * eps) / np.sqrt(ab)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0),
                                   rtol=1e-5, atol=1e-5)

    def test_euler_runs_and_differs_from_ddim(self):
        m, params, y = self._setup()
        x = _latents(jax.random.PRNGKey(8))
        e = sample(m, params, y, x_init=x, num_steps=3, method="euler")
        d = sample(m, params, y, x_init=x, num_steps=3, method="ddim")
        assert np.isfinite(np.asarray(e)).all()
        assert not np.allclose(np.asarray(e), np.asarray(d))
        with pytest.raises(ValueError):
            sample(m, params, y, x_init=x, num_steps=1, method="heun")

    def test_schedule_timesteps(self):
        sched = DiffusionSchedule(n_train_steps=100)
        ts = sched.timesteps(4)
        assert list(ts) == [99, 66, 33, 0]
        assert sched.timesteps(0).size == 0
        assert list(sched.timesteps(1)) == [99]
        ab = sched.alpha_bars()
        assert ab.shape == (100,) and (np.diff(ab) < 0).all()


class TestDiffusionEngine:
    def _engine(self, **kw):
        m, params = _model_and_params()
        return m, DiffusionEngine(m, params, batch_size=2, **kw)

    def test_serves_batches_and_pads(self):
        m, eng = self._engine()
        reqs = [ImageRequest(uid=i, label=i % CFG.n_classes, num_steps=2,
                             seed=9) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in reqs)
        assert eng.stats.images_out == 5
        assert eng.stats.batches == 3                 # 2 + 2 + 1(padded)
        assert eng.stats.batch_occupancy == [1.0, 1.0, 0.5]
        for r in reqs:
            assert r.latents.shape == (CFG.in_channels, CFG.input_size,
                                       CFG.input_size)
            assert np.isfinite(r.latents).all()

    def test_matches_direct_sampler_bitwise(self):
        """An engine batch == the jitted sampler on the same stacked
        noise/labels (the engine adds batching, never numerics)."""
        m, eng = self._engine()
        reqs = [ImageRequest(uid=i, label=i + 1, num_steps=2, seed=11)
                for i in range(2)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        noise = jnp.stack([eng._noise(r) for r in reqs])
        y = jnp.asarray([r.label for r in reqs], jnp.int32)
        direct = jax.jit(
            lambda p, n, yy: sample(m, p, yy, x_init=n, num_steps=2))(
                eng.params, noise, y)
        for i, r in enumerate(reqs):
            assert (np.asarray(direct)[i] == r.latents).all()

    def test_groups_by_trace_key(self):
        """Requests with different (steps, cfg, method) keys never share
        a batch; queue order is preserved within each key."""
        m, eng = self._engine()
        reqs = [ImageRequest(uid=0, label=1, num_steps=2),
                ImageRequest(uid=1, label=2, num_steps=1),
                ImageRequest(uid=2, label=3, num_steps=2)]
        for r in reqs:
            eng.submit(r)
        eng.step()                                   # batches uid 0 + 2
        assert reqs[0].done and reqs[2].done and not reqs[1].done
        eng.run_until_done()
        assert all(r.done for r in reqs)
        assert eng.stats.batches == 2

    def test_int8_plan_engine(self):
        """quant_plan=full serves the fused INT8 denoise path; its
        single-step output stays correlated with the bf16 engine's."""
        m, eng_bf16 = self._engine()
        _, eng_int8 = self._engine(quant_plan=QuantPlan.full())
        req16 = ImageRequest(uid=0, label=3, num_steps=1, seed=13)
        req8 = ImageRequest(uid=0, label=3, num_steps=1, seed=13)
        eng_bf16.submit(req16)
        eng_int8.submit(req8)
        eng_bf16.run_until_done()
        eng_int8.run_until_done()
        assert req16.done and req8.done
        from repro.quant import QuantizedLinear as QL
        assert isinstance(eng_int8.params["blocks"]["mlp"]["up"], QL)
        corr = np.corrcoef(req16.latents.ravel(),
                           req8.latents.ravel())[0, 1]
        assert corr > 0.99, corr

    def test_submit_validation(self):
        m, eng = self._engine()
        with pytest.raises(ValueError):
            eng.submit(ImageRequest(uid=0, label=CFG.n_classes))  # null id
        with pytest.raises(ValueError):
            eng.submit(ImageRequest(uid=0, label=-1))
        with pytest.raises(ValueError):
            eng.submit(ImageRequest(uid=0, label=0, num_steps=-1))
        with pytest.raises(ValueError):
            eng.submit(ImageRequest(uid=0, label=0, method="heun"))


class TestBridgeDiT:
    def test_plan_costs_conditioning_consistently(self):
        """Acceptance for the simulator satellite: under
        ``dit_graph_from_config(quant_plan=)`` the CONDITIONING vector
        ops ride at the plan's element width (8-bit I/O when ``adaln``
        is covered) instead of always at the fp path, and covered weight
        matmuls hit the INT8 point while attention stays bf16."""
        full = dit_graph_from_config(CFG, 2, quant_plan=QuantPlan.full())
        none = dit_graph_from_config(CFG, 2, quant_plan=QuantPlan.none())
        cond_full = [o for o in full.ops if o.kind == OpKind.CONDITIONING]
        cond_none = [o for o in none.ops if o.kind == OpKind.CONDITIONING]
        assert cond_full and all(o.bits == 8 for o in cond_full)
        assert all(o.bits == 16 for o in cond_none)
        by_kind = {o.kind: o for o in full.ops if isinstance(o, MatMulOp)}
        for k in (OpKind.QKV, OpKind.PROJ, OpKind.FFN, OpKind.OTHER_MATMUL):
            assert by_kind[k].act_bits == by_kind[k].weight_bits == 8
        for k in (OpKind.ATTN_QK, OpKind.ATTN_SV):
            assert by_kind[k].act_bits == 16
        # no-adaln plan: modulation GEMM and CONDITIONING both at bf16
        noada = dit_graph_from_config(
            CFG, 2, quant_plan=QuantPlan(adaln=False))
        assert all(o.bits == 16 for o in noada.ops
                   if o.kind == OpKind.CONDITIONING)
        assert [o for o in noada.ops
                if o.kind == OpKind.OTHER_MATMUL][0].act_bits == 16

    def test_graph_macs_match_analytic_and_simulate(self):
        from repro.core import get_hardware, simulate_graph, \
            tpuv4i_baseline
        g = dit_graph_from_config(CFG, 2)
        assert g.repeat == CFG.n_layers
        per_block = sum(op.macs for op in dit_block_ops(dit_spec(CFG), 2,
                                                        CFG.tokens)
                        if isinstance(op, MatMulOp))
        assert g.total_macs == CFG.n_layers * per_block
        base, cim = tpuv4i_baseline(), get_hardware("cim-16x8")
        int8 = simulate_graph(cim, dit_graph_from_config(
            CFG, 2, quant_plan=QuantPlan.full()))
        bf16 = simulate_graph(cim, dit_graph_from_config(
            CFG, 2, quant_plan=QuantPlan.none()))
        assert 0 < int8.mxu_energy_j < bf16.mxu_energy_j
        assert simulate_graph(base, g).latency_s > 0


_TP_SETUP = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import get_dit_config
    from repro.models.dit import DiTModel
    from repro.parallel.context import sharding_context
    from repro.quant import kernel_mode

    cfg = get_dit_config("dit-test")
    m = DiTModel(cfg)
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, cfg.in_channels, cfg.input_size,
                           cfg.input_size))
    t = jnp.array([500, 10], jnp.int32)
    y = jnp.array([3, 7], jnp.int32)
""")


class TestDiTTensorParallel:
    """Acceptance bar: the full-plan DiT denoise step is bit-identical
    under a model-axis mesh (2-way pinned; 1/4-way too), through the
    same shard_map'd apply sites as the LLM stack — including with the
    quantized tree device_put per its plan axes."""

    def test_forward_bitwise_under_model_mesh(self):
        out = _run_subprocess(_TP_SETUP + textwrap.dedent("""
            qp = m.quantize(params)
            with kernel_mode(False):
                ref = jax.jit(lambda p,a,b,c: m.forward(p,a,b,c))(
                    qp, x, t, y)
                for p in (1, 2, 4):
                    mesh = make_mesh((p,), ("model",))
                    f = jax.jit(lambda pp,a,b,c: m.forward(pp,a,b,c))
                    with sharding_context(mesh):
                        got = f(qp, x, t, y)
                    assert (np.asarray(got) == np.asarray(ref)).all(), p
                    print(f"shards{p} OK")
                # mesh-placed weights (q + scale co-sharded) too
                mesh = make_mesh((2,), ("model",))
                qps = m.quantize(params, mesh=mesh)
                f = jax.jit(lambda pp,a,b,c: m.forward(pp,a,b,c))
                with sharding_context(mesh):
                    got = f(qps, x, t, y)
                assert (np.asarray(got) == np.asarray(ref)).all()
                print("placed OK")
        """))
        for tag in ("shards1 OK", "shards2 OK", "shards4 OK", "placed OK"):
            assert tag in out

    @pytest.mark.slow
    def test_kernel_path_bitwise_2way(self):
        """The same parity on the Pallas kernel pipeline (interpret
        mode) at 2 shards."""
        out = _run_subprocess(_TP_SETUP + textwrap.dedent("""
            qp = m.quantize(params)
            with kernel_mode(True):
                ref = jax.jit(lambda p,a,b,c: m.forward(p,a,b,c))(
                    qp, x, t, y)
                mesh = make_mesh((2,), ("model",))
                f = jax.jit(lambda pp,a,b,c: m.forward(pp,a,b,c))
                with sharding_context(mesh):
                    got = f(qp, x, t, y)
                assert (np.asarray(got) == np.asarray(ref)).all()
                print("kernel2 OK")
        """), devices=2)
        assert "kernel2 OK" in out

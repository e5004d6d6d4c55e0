"""Compile rehearsals for a TPU v5e: the main-path Pallas kernels at
published widths, compiled by the TPU compiler for a described (not
attached) v5e.

Interpret mode cannot see Mosaic's block-tiling rule (the last two block
dims divisible by 8 and 128, or whole) or its scoped-VMEM limit; this
compiler can, at no chip time.  A compile that passes is not a chip run:
nothing here executes.  The topology is described inside a fixture (one
process at a time may load the TPU library), and every compile runs in
the test's own process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# deepseek-67b (arXiv:2401.02954), DiT-XL/2 (arXiv:2212.09748) and
# qwen2-moe-a2.7b (its routed experts) widths
D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 8192, 22016, 64, 8, 128
DIT_D, DIT_ROWS = 1152, 4 * 1024         # 2 images x CFG x 1024 tokens
MOE_D, MOE_FF, MOE_EXPERTS = 2048, 1408, 60


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_v5e(one_chip, monkeypatch):
    """Compile ``fn`` for one v5e chip from (shape, dtype) pairs and
    check that the Mosaic kernels are in the program.  The kernel
    wrappers choose interpret mode from ``jax.default_backend()``, which
    is the CPU here; the test steers them to the compiled kernels."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled
    return run


ROWS = pytest.mark.parametrize("M", [8, 512], ids=["decode", "prefill512"])


@ROWS
def test_fused_qkv_gemm(compile_v5e, M):
    from repro.kernels import ops
    n = (HEADS + 2 * KV_HEADS) * HEAD_DIM
    compile_v5e(lambda x, w, s: ops.cim_quantized_matmul_fused(x, w, s),
                ((M, D_MODEL), jnp.bfloat16), ((D_MODEL, n), jnp.int8),
                ((n,), jnp.float32))


@ROWS
def test_swiglu_mlp(compile_v5e, M):
    from repro.kernels import ops
    compile_v5e(
        lambda x, u, us, dn, ds, g, gs, r: ops.cim_quantized_mlp(
            x, u, us, dn, ds, gate_q=g, gate_scale=gs, residual=r,
            activation="silu"),
        ((M, D_MODEL), jnp.bfloat16), ((D_MODEL, D_FF), jnp.int8),
        ((D_FF,), jnp.float32), ((D_FF, D_MODEL), jnp.int8),
        ((D_MODEL,), jnp.float32), ((D_MODEL, D_FF), jnp.int8),
        ((D_FF,), jnp.float32), ((M, D_MODEL), jnp.bfloat16))


@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_paged_decode_attention(compile_v5e, kv):
    from repro.kernels import ops
    B, G, bs, nb, NB = 8, HEADS // KV_HEADS, 16, 36, 1 + 8 * 36
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    pool = ((NB, bs, KV_HEADS, HEAD_DIM), dt)
    shapes = [((B, KV_HEADS, G, HEAD_DIM), jnp.bfloat16), pool, pool,
              ((NB, bs), jnp.int32), ((B, nb), jnp.int32), ((B,), jnp.int32)]
    if kv == "int8":
        shapes += [((NB, bs, KV_HEADS), jnp.float32)] * 2
    compile_v5e(lambda *a: ops.decode_attention_paged(*a), *shapes)


@pytest.mark.parametrize("n_splits", [1, 4], ids=["ring", "splitkv"])
def test_ring_decode_attention(compile_v5e, n_splits):
    """The int8-KV ring kernel over a 2048-slot cache: one dispatch, or
    four KV splits and their combine."""
    from repro.kernels import ops
    B, G, S = 8, HEADS // KV_HEADS, 2048
    kv = ((B, S, KV_HEADS, HEAD_DIM), jnp.int8)
    scale = ((B, S, KV_HEADS), jnp.float32)
    compile_v5e(lambda q, k, v, pos, qp, ks, vs: ops.decode_attention(
        q, k, v, pos, qp, k_scale=ks, v_scale=vs, n_splits=n_splits),
        ((B, KV_HEADS, G, HEAD_DIM), jnp.bfloat16), kv, kv,
        ((B, S), jnp.int32), ((B,), jnp.int32), scale, scale)


def test_grouped_moe_mlp(compile_v5e):
    """The grouped SwiGLU expert MLP over every expert's capacity rows."""
    from repro.kernels import ops
    E, T = MOE_EXPERTS, 16
    w_in = ((E, MOE_D, MOE_FF), jnp.int8)
    s_in = ((E, MOE_FF), jnp.float32)
    compile_v5e(
        lambda x, u, us, dn, ds, g, gs: ops.cim_quantized_grouped_mlp(
            x, u, us, dn, ds, gate_q=g, gate_scale=gs, activation="silu"),
        ((E, T, MOE_D), jnp.bfloat16), w_in, s_in,
        ((E, MOE_FF, MOE_D), jnp.int8), ((E, MOE_D), jnp.float32),
        w_in, s_in)


def test_dit_mlp(compile_v5e):
    from repro.kernels import ops
    f = 4 * DIT_D
    compile_v5e(
        lambda x, u, us, dn, ds: ops.cim_quantized_mlp(x, u, us, dn, ds,
                                                       activation="gelu"),
        ((DIT_ROWS, DIT_D), jnp.bfloat16), ((DIT_D, f), jnp.int8),
        ((f,), jnp.float32), ((f, DIT_D), jnp.int8), ((DIT_D,), jnp.float32))


def test_dit_adaln_gemm(compile_v5e):
    from repro.kernels import ops
    n = 6 * DIT_D
    compile_v5e(lambda c, w, s, b: ops.cim_quantized_matmul_fused(c, w, s,
                                                                  bias=b),
                ((4, DIT_D), jnp.float32), ((DIT_D, n), jnp.int8),
                ((n,), jnp.float32), ((n,), jnp.float32))

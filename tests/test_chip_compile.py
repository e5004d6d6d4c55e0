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
import dataclasses
import os
import re
from types import SimpleNamespace

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
    """The paged decode kernel on 16-token pages: 8 rows of 36-entry
    tables (two 32-page compute blocks, the second padded with null
    entries), and ds67b-decode's own shapes: 32 rows of 256-entry tables
    (4096 tokens, eight compute blocks) over a 4-layer pool stack."""
    from repro.kernels import ops
    G, bs, L = HEADS // KV_HEADS, 16, 4
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    for B, nb in ((8, 36), (32, 256)):
        NB = 1 + B * nb
        pool = ((L, NB, bs, KV_HEADS, HEAD_DIM), dt)
        shapes = [((B, KV_HEADS, G, HEAD_DIM), jnp.bfloat16), pool, pool,
                  ((L, NB, bs), jnp.int32), ((B, nb), jnp.int32),
                  ((B,), jnp.int32), ((), jnp.int32)]
        if kv == "int8":
            shapes += [((L, NB, bs, KV_HEADS), jnp.float32)] * 2
        compiled = compile_v5e(lambda *a: ops.decode_attention_paged(*a),
                               *shapes)
        # the kernel keeps the name the benchmark's trace reader finds
        assert re.search(r"%decode_attention_paged\S* = \S+ custom-call",
                         compiled.as_text())


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


def test_mla_decode_paged(compile_v5e):
    """The latent decode kernel at DeepSeek-V3's widths as the benchmark
    serves it: 32 rows, 128 heads, latent 512 + rope 64 in 640-wide pool
    rows, 256-token blocks over 16,384 positions, a 4-layer stack."""
    from repro.kernels import ops
    B, H, R, Dr, W, L, bs, nb = 32, 128, 512, 64, 640, 4, 256, 64
    NB = 1 + B * nb
    compile_v5e(
        lambda ql, qr, lat, sc, sr, bt, qp, ly: ops.mla_decode_paged(
            ql, qr, lat, sc, sr, bt, qp, ly, scale=0.1),
        ((B, H, R), jnp.bfloat16), ((B, H, Dr), jnp.bfloat16),
        ((L, NB, bs, W), jnp.int8), ((L, NB, bs), jnp.float32),
        ((L, NB, bs), jnp.float32), ((B, nb), jnp.int32),
        ((B,), jnp.int32), ((), jnp.int32))


def test_mla_prefill_paged(compile_v5e):
    """The latent chunked-prefill kernel at DeepSeek-V3's widths as the
    benchmark serves it: one row's 256-token chunk, 128 heads, the same
    pool and tables as the decode kernel."""
    from repro.kernels import ops
    S, H, R, Dr, W, L, bs, nb = 256, 128, 512, 64, 640, 4, 256, 64
    NB = 1 + 32 * nb
    compile_v5e(
        lambda ql, qr, lat, sc, sr, bt, pos, ly: ops.mla_prefill_paged(
            ql, qr, lat, sc, sr, bt, pos, ly, scale=0.1),
        ((1, S, H, R), jnp.bfloat16), ((1, S, H, Dr), jnp.bfloat16),
        ((L, NB, bs, W), jnp.int8), ((L, NB, bs), jnp.float32),
        ((L, NB, bs), jnp.float32), ((1, nb), jnp.int32),
        ((1, S), jnp.int32), ((), jnp.int32))


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


def test_ragged_moe_mlp(compile_v5e):
    """The ragged form at DeepSeek-V3's expert widths as a 16k-token
    prefill would run it on one chip's 8 experts: 520 row tiles of 256
    rows, each against its tile's expert, with the skip list."""
    from repro.kernels import ops
    E, n_tiles, tm, d, F = 8, 520, 256, 7168, 2048
    w_in = ((E, d, F), jnp.int8)
    s_in = ((E, F), jnp.float32)
    compile_v5e(
        lambda x, u, us, dn, ds, g, gs, c, gr: ops.cim_quantized_grouped_mlp(
            x, u, us, dn, ds, gate_q=g, gate_scale=gs, expert_counts=c,
            groups=gr, activation="silu"),
        ((n_tiles, tm, d), jnp.bfloat16), w_in, s_in,
        ((E, F, d), jnp.int8), ((E, d), jnp.float32), w_in, s_in,
        ((n_tiles,), jnp.int32), ((n_tiles,), jnp.int32))


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


# deepseek-67b-4L as the benchmark serves it: 32 slots, 4096-token block
# tables of 16-token blocks (8,193 with the null block), int8 KV and
# 256-token prefill chunks
LM_LAYERS, SLOTS, BLOCK, MAX_BLOCKS, CHUNK = 4, 32, 16, 256, 256
NUM_BLOCKS = 1 + SLOTS * MAX_BLOCKS
POOL_SHAPES = tuple(f"s8[{dims}]" for dims in (
    f"{LM_LAYERS},{NUM_BLOCKS},{BLOCK},{KV_HEADS},{HEAD_DIM}",
    f"1,{NUM_BLOCKS},{BLOCK},{KV_HEADS},{HEAD_DIM}",
    f"{NUM_BLOCKS},{BLOCK},{KV_HEADS},{HEAD_DIM}"))
MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _paged_step_hlo(one_chip, step: str) -> str:
    """The paged engine's decode step or one prefill chunk, compiled for
    one v5e with every Pallas kernel, as optimised HLO text."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.quant import QuantPlan, kernel_mode
    from repro.serving import PagedServingEngine

    cfg = dataclasses.replace(get_config("deepseek-67b"),
                              n_layers=LM_LAYERS)
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda k: model.init_quantized(k, QuantPlan.full()),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        SLOTS, NUM_BLOCKS, BLOCK, MAX_BLOCKS, kv_dtype="int8"))

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, cache = jax.tree.map(lambda a: on_chip(a.shape, a.dtype),
                                 (params, cache))
    eng = object.__new__(PagedServingEngine)
    eng.model, eng.mesh, eng.rules, eng.degraded = model, None, None, False
    eng.paged = SimpleNamespace(
        allocator=SimpleNamespace(num_blocks=NUM_BLOCKS),
        max_blocks=MAX_BLOCKS)
    eng._build_steps()
    tables = on_chip((SLOTS, MAX_BLOCKS), jnp.int32)
    if step == "decode":
        fn = eng._decode_masked
        args = (on_chip((SLOTS,), jnp.int32), on_chip((SLOTS,), jnp.bool_),
                tables)
    else:
        fn = eng._prefill_chunk_fn
        scalar = on_chip((), jnp.int32)
        args = (on_chip((CHUNK,), jnp.int32), scalar, scalar, scalar, tables)
    with kernel_mode(True):
        hlo = fn.lower(params, cache, *args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_paged_step_updates_kv_pools_in_place(one_chip, monkeypatch, step):
    """The layer scan carries the stacked KV pools: the compiled step
    neither slices a layer's pool out, writes one back, nor copies the
    stack, and every pool leaf is donated (aliased to an output)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    hlo = _paged_step_hlo(one_chip, step)
    moved = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \(?(\S+?)\{\S* ([\w-]+)\(",
                     line)
        if m is None or m[2] not in POOL_SHAPES:
            continue
        name, op = m[1], re.sub(r"-(start|done)$", "", m[3])
        if op in MOVES or (op == "fusion" and any(k in name for k in MOVES)):
            moved.append(name)
    assert moved == []

    pools = re.findall(r"%cache\S*_pages\S* = \S+ parameter\((\d+)\)", hlo)
    aliased = re.findall(r"\((\d+), \{[^}]*\}, (?:may|must)-alias\)", hlo)
    assert len(pools) == 5                 # K, V, their scales, positions
    assert set(pools) <= set(aliased)

"""Kernel microbenchmarks: interpret-mode Pallas vs pure-jnp oracle.

On CPU these numbers measure the *interpreter*, not TPU performance —
they exist to confirm the kernels execute and to provide the harness that
would time them on real hardware (same entry points).  The fused-vs-
unfused pairs track the INT8 epilogue fusion (quant -> GEMM -> dequant/
bias/act in one Pallas kernel vs separate XLA passes around the GEMM):
the dispatch-count and HBM-traffic win is structural, so the pair is
reported on every backend.

``python -m benchmarks.bench_kernels`` writes BENCH_kernels.json
directly; ``python -m benchmarks.run`` includes these rows in the same
trajectory file.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.launch.mesh import model_mesh

KEY = jax.random.PRNGKey(0)
BENCH_JSON = "BENCH_kernels.json"


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def bench_kernels():
    rows = []
    k1, k2, k3, k4 = jax.random.split(KEY, 4)

    # ------------------------------------------------------------------
    # CIM GEMM 512^3: unfused (XLA quant + Pallas int32 GEMM + XLA
    # dequant) vs fused (Pallas quantize kernel + fused-epilogue GEMM).
    # ------------------------------------------------------------------
    x = jax.random.normal(k1, (512, 512), jnp.float32)
    w = jax.random.normal(k2, (512, 512), jnp.float32) * 0.1
    w_q, w_s = ops.quantize_weights_int8(w)
    t_unfused = _time(ops.cim_quantized_matmul, x, w_q, w_s)
    rows.append(("kernel_cim_gemm_512_unfused", t_unfused,
                 "int8 512^3; XLA quant/dequant around int32-out GEMM"))
    t_fused = _time(ops.cim_quantized_matmul_fused, x, w_q, w_s)
    rows.append(("kernel_cim_gemm_512_fused", t_fused,
                 f"quant+GEMM+dequant in-kernel; "
                 f"vs_unfused={t_unfused/t_fused:.2f}x"))

    # ------------------------------------------------------------------
    # Gated MLP (geglu, d=256 ff=512): the old 3-GEMM + XLA-elementwise
    # pipeline vs the fused 3-dispatch pipeline (quantize, gated GEMM
    # with in-epilogue requant, down GEMM).
    # ------------------------------------------------------------------
    d, ff = 256, 512
    xm = jax.random.normal(k1, (256, d), jnp.float32) * 0.5
    wu_q, wu_s = ops.quantize_weights_int8(
        jax.random.normal(k2, (d, ff), jnp.float32) * 0.1)
    wg_q, wg_s = ops.quantize_weights_int8(
        jax.random.normal(k3, (d, ff), jnp.float32) * 0.1)
    wd_q, wd_s = ops.quantize_weights_int8(
        jax.random.normal(k4, (ff, d), jnp.float32) * 0.1)

    @jax.jit
    def mlp_unfused(a):
        up = ops.cim_quantized_matmul(a, wu_q, wu_s)
        gate = ops.cim_quantized_matmul(a, wg_q, wg_s)
        h = jax.nn.gelu(gate, approximate=True) * up
        return ops.cim_quantized_matmul(h, wd_q, wd_s)

    @jax.jit
    def mlp_fused(a):
        return ops.cim_quantized_mlp(a, wu_q, wu_s, wd_q, wd_s,
                                     gate_q=wg_q, gate_scale=wg_s,
                                     activation="gelu")

    t_mlp_unfused = _time(mlp_unfused, xm)
    rows.append(("kernel_gated_mlp_unfused", t_mlp_unfused,
                 "geglu 256x256x512; 3 GEMM kernels + XLA act/dequant"))
    t_mlp_fused = _time(mlp_fused, xm)
    rows.append(("kernel_gated_mlp_fused", t_mlp_fused,
                 f"quantize + gated GEMM + down GEMM (3 dispatches); "
                 f"vs_unfused={t_mlp_unfused/t_mlp_fused:.2f}x"))

    # row-quantize kernel on its own
    t_q = _time(ops.quantize_rows_int8, xm)
    rows.append(("kernel_quantize_rows", t_q, "dynamic row absmax int8"))

    # ------------------------------------------------------------------
    # Attention projections (QuantPlan attn_qkv + attn_out): three
    # separate quantized GEMMs + XLA residual add vs ONE wide fused QKV
    # dispatch + one out-proj dispatch with the residual in its epilogue.
    # ------------------------------------------------------------------
    from repro.quant import (quantize_attention, quantized_out_proj,
                             quantized_qkv_proj)
    from repro.models.layers import param_values
    from repro.models.attention import attention_init

    d, H, KH, Dh = 256, 4, 2, 64
    aparams = param_values(attention_init(KEY, d, H, KH, Dh,
                                          dtype=jnp.float32))
    qattn = quantize_attention(aparams)
    xq = jax.random.normal(k1, (128, d), jnp.float32) * 0.5
    res = jax.random.normal(k4, (128, d), jnp.float32) * 0.5
    wq_q, wq_s = ops.quantize_weights_int8(aparams["q"].reshape(d, -1))
    wk_q, wk_s = ops.quantize_weights_int8(aparams["k"].reshape(d, -1))
    wv_q, wv_s = ops.quantize_weights_int8(aparams["v"].reshape(d, -1))
    wo_q, wo_s = ops.quantize_weights_int8(aparams["o"].reshape(-1, d))

    @jax.jit
    def attn_proj_unfused(a, r):
        q = ops.cim_quantized_matmul(a, wq_q, wq_s)
        k = ops.cim_quantized_matmul(a, wk_q, wk_s)
        v = ops.cim_quantized_matmul(a, wv_q, wv_s)
        o = ops.cim_quantized_matmul(q, wo_q, wo_s)  # stand-in attn out
        del k, v
        return r + o

    @jax.jit
    def attn_proj_fused(a, r):
        wide = quantized_qkv_proj(qattn["qkv"], a, use_kernel=True)
        q = wide[:, :H]
        return quantized_out_proj(qattn["o"], q, residual=r,
                                  use_kernel=True)

    t_ap_unfused = _time(attn_proj_unfused, xq, res)
    rows.append(("kernel_attn_proj_unfused", t_ap_unfused,
                 "q/k/v/o as 4 int32-out GEMMs + XLA quant/dequant/add"))
    t_ap_fused = _time(attn_proj_fused, xq, res)
    rows.append(("kernel_attn_proj_fused", t_ap_fused,
                 f"1 wide QKV dispatch + 1 out-proj w/ fused residual; "
                 f"vs_unfused={t_ap_unfused/t_ap_fused:.2f}x"))

    # ------------------------------------------------------------------
    # Grouped MoE experts (QuantPlan moe_experts): the retired per-expert
    # Python loop (3·E fused dispatches) vs the grouped kernels (3
    # dispatches, expert index a grid dim — constant in E).  E=8 is the
    # reduced-config scale; E=60 is qwen2-moe's real expert count, where
    # the loop's dispatch overhead dominates.
    # ------------------------------------------------------------------
    from repro.quant import (quantize_moe_experts, quantized_moe_apply,
                             quantized_moe_apply_looped)

    for E, T, reps in ((8, 64, 3), (60, 32, 1)):
        dm, F = 64, 128
        ke = jax.random.split(jax.random.PRNGKey(E), 4)
        qmoe = quantize_moe_experts({
            "up": jax.random.normal(ke[0], (E, dm, F), jnp.float32) * 0.1,
            "gate": jax.random.normal(ke[1], (E, dm, F), jnp.float32) * 0.1,
            "down": jax.random.normal(ke[2], (E, F, dm), jnp.float32) * 0.1,
        })
        xe = jax.random.normal(ke[3], (E, T, dm), jnp.float32) * 0.5

        @jax.jit
        def moe_looped(a, q=qmoe):
            return quantized_moe_apply_looped(q, a, "silu", use_kernel=True)

        @jax.jit
        def moe_grouped(a, q=qmoe):
            return quantized_moe_apply(q, a, "silu", use_kernel=True)

        t_looped = _time(moe_looped, xe, reps=reps)
        rows.append((f"kernel_grouped_moe_looped_e{E}", t_looped,
                     f"{E} silu experts; per-expert loop = {3*E} Pallas "
                     f"dispatches"))
        t_grouped = _time(moe_grouped, xe, reps=reps)
        rows.append((f"kernel_grouped_moe_fused_e{E}", t_grouped,
                     f"grouped kernels, 3 dispatches (const in E); "
                     f"vs_looped={t_looped/t_grouped:.2f}x"))

    # ------------------------------------------------------------------
    # DiT block (the diffusion workload class): the full-plan fused
    # block — 6 Pallas dispatches (adaLN modulation + wide QKV +
    # out-proj + 3-dispatch MLP) — vs the unfused form (5 int32-out GEMM
    # kernels with XLA quant/dequant/bias/modulate passes around them).
    # ------------------------------------------------------------------
    rows.extend(bench_dit_block())

    # ------------------------------------------------------------------
    # Tensor-parallel fused MLP (QuantPlan mlp under a model-axis mesh):
    # the shard_map pipeline at 1 vs 2 vs 4 shards, on this process's
    # own devices (4 needed; on CPU the numbers time the interpreter +
    # collectives, but the 1-shard row doubles as the shard_map-overhead
    # baseline against kernel_gated_mlp_fused).
    # ------------------------------------------------------------------
    rows.extend(bench_tp_mlp())

    # The full-plan DiT block under a 1/2-way model mesh (the paper's
    # Design B partitions the DiT weight-stationary arrays the same way).
    rows.extend(bench_tp_dit())

    # flash attention 2x256x4x32
    q = jax.random.normal(k1, (2, 256, 4, 32), jnp.float32)
    kk = jax.random.normal(k2, (2, 256, 2, 32), jnp.float32)
    v = jax.random.normal(k3, (2, 256, 2, 32), jnp.float32)
    t_fa = _time(lambda *a: ops.flash_attention(*a, block_q=64, block_k=64),
                 q, kk, v)
    t_ref = _time(ref.flash_attention_ref, q, kk, v)
    rows.append(("kernel_flash_attention", t_fa,
                 f"interp_vs_jnp_ref={t_fa/t_ref:.1f}x (CPU interpreter)"))

    # decode attention: fp vs int8-KV at short and long cache lengths,
    # plus the explicit split-KV dispatch.  B=4, GQA 2 KV heads x 4
    # groups, D=64; int8 rows stream the quantized cache + per-head
    # scale vectors through the same kernel.
    from repro.models.attention import _quantize_kv
    for S in (512, 4096):
        qd = jax.random.normal(k1, (4, 2, 4, 64), jnp.float32)
        kd = jax.random.normal(k2, (4, S, 2, 64), jnp.float32)
        vd = jax.random.normal(k3, (4, S, 2, 64), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S)[None], (4, S)).astype(jnp.int32)
        qp = jnp.full((4,), S - 1, jnp.int32)
        t_fp = _time(lambda *a: ops.decode_attention(*a, block_k=512),
                     qd, kd, vd, pos, qp)
        rows.append((f"kernel_decode_attn_fp_s{S}", t_fp,
                     f"B4 KV{S} GQA 2x4 fp32 cache"))
        kq, ks = _quantize_kv(kd)
        vq, vs = _quantize_kv(vd)
        t_q = _time(lambda *a: ops.decode_attention(*a, block_k=512),
                    qd, kq, vq, pos, qp, ks, vs)
        rows.append((f"kernel_decode_attn_int8kv_s{S}", t_q,
                     f"B4 KV{S} GQA 2x4 int8 cache, in-kernel dequant"))
        if S == 4096:
            t_sp = _time(
                lambda *a: ops.decode_attention_splitkv(
                    *a, block_k=512, n_splits=4),
                qd, kq, vq, pos, qp, ks, vs)
            rows.append(("decode_attn_splitkv", t_sp,
                         f"B4 KV{S} int8 cache, 4-way split-KV + combine"))

    # ssd scan
    xs = jax.random.normal(k1, (8, 256, 16), jnp.float32)
    la = -jnp.abs(jax.random.normal(k2, (8, 256))) * 0.3
    bb = jax.random.normal(k3, (8, 256, 16), jnp.float32)
    t_ssd = _time(lambda *a: ops.ssd_scan(*a, chunk=64)[0], xs, la, bb, bb)
    rows.append(("kernel_ssd_scan", t_ssd, "BH8 S256 P16 N16 chunk64"))

    # online softmax
    sm = jax.random.normal(k1, (512, 4096), jnp.float32)
    t_sm = _time(lambda a: ops.online_softmax(a, block_r=128, block_c=1024),
                 sm)
    rows.append(("kernel_online_softmax", t_sm, "512x4096 two-phase"))
    return rows


def bench_dit_block():
    """`kernel_dit_block_{fused,unfused}` rows: one full-plan DiT block
    on the fused pipeline vs per-GEMM int32-out kernels with XLA
    epilogues (both from the same int8 weights, full attention + adaLN
    math included in both)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_dit_config
    from repro.models.dit import DiTModel, dit_block_apply, _ln
    from repro.quant import kernel_mode

    cfg = get_dit_config("dit-test")
    model = DiTModel(cfg)
    qparams = model.quantize(model.init(KEY))
    block = jax.tree.map(lambda a: a[0], qparams["blocks"])
    B, T, d = 2, cfg.tokens, cfg.d_model
    H, Dh = cfg.n_heads, cfg.head_dim
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (B, T, d), jnp.float32) * 0.5
    c = jax.random.normal(k2, (B, d), jnp.float32) * 0.5
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    adaln, attn, mlp = block["adaln"], block["attn"], block["mlp"]
    qkv_q = attn["qkv"].q.reshape(d, -1)
    qkv_s = attn["qkv"].scale.reshape(-1)
    o_q = attn["o"].q.reshape(H * Dh, d)

    @jax.jit
    def dit_block_unfused(a, cc):
        mod = ops.cim_quantized_matmul(jax.nn.silu(cc), adaln["kernel"].q,
                                       adaln["kernel"].scale)
        mod = mod + adaln["bias"]
        sm, scm, gm, s2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        h = _ln(a) * (1 + scm[:, None]) + sm[:, None]
        wide = ops.cim_quantized_matmul(h.reshape(B * T, d), qkv_q, qkv_s)
        wide = wide.reshape(B, T, 3 * H, Dh)
        q, kk, v = jnp.split(wide, 3, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(float(Dh))
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B * T, H * Dh)
        o = ops.cim_quantized_matmul(o, o_q, attn["o"].scale)
        a = a + gm[:, None] * o.reshape(B, T, d)
        h = _ln(a) * (1 + sc2[:, None]) + s2[:, None]
        up = ops.cim_quantized_matmul(h.reshape(B * T, d), mlp["up"].q,
                                      mlp["up"].scale)
        hh = jax.nn.gelu(up, approximate=True)
        dn = ops.cim_quantized_matmul(hh, mlp["down"].q, mlp["down"].scale)
        return a + g2[:, None] * dn.reshape(B, T, d)

    @jax.jit
    def dit_block_fused(a, cc):
        return dit_block_apply(block, a, cc, cfg, pos)

    with kernel_mode(True):
        t_unfused = _time(dit_block_unfused, x, c)
        t_fused = _time(dit_block_fused, x, c)
    return [("kernel_dit_block_unfused", t_unfused,
             "adaLN DiT block; 5 int32-out GEMM kernels + XLA "
             "quant/dequant/modulate"),
            ("kernel_dit_block_fused", t_fused,
             f"full-plan fused block, 6 dispatches (adaLN + QKV + "
             f"out-proj + 3 MLP); vs_unfused={t_unfused/t_fused:.2f}x")]


def _time_tp(f, mesh, *args) -> float:
    """Mean µs per call of ``f`` traced under ``mesh`` (3 timed calls)."""
    from repro.parallel.context import sharding_context
    with sharding_context(mesh):
        jax.block_until_ready(f(*args))       # compile
        t0 = time.perf_counter()
        for _ in range(3):
            r = f(*args)
        jax.block_until_ready(r)
    return (time.perf_counter() - t0) / 3 * 1e6


def bench_tp_dit():
    """`dit_tp_s{1,2}` rows: the full-plan fused DiT block under a
    model-axis mesh at 1 vs 2 shards."""
    from repro.configs import get_dit_config
    from repro.models.dit import DiTModel, dit_block_apply
    from repro.quant import kernel_mode

    cfg = get_dit_config("dit-test")
    model = DiTModel(cfg)
    qparams = model.quantize(model.init(KEY))
    block = jax.tree.map(lambda a: a[0], qparams["blocks"])
    B, T, d = 2, cfg.tokens, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, d)) * 0.5
    c = jax.random.normal(jax.random.PRNGKey(2), (B, d)) * 0.5
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    times = {}
    with kernel_mode(True):
        for p in (1, 2):
            f = jax.jit(lambda a, cc: dit_block_apply(block, a, cc, cfg, pos))
            times[p] = _time_tp(f, model_mesh(p), x, c)
    return [(f"dit_tp_s{p}", times[p],
             f"full-plan DiT block shard_map {p}-way model mesh"
             + ("" if p == 1 else f"; vs_1shard={times[1]/times[p]:.2f}x"))
            for p in (1, 2)]


def bench_tp_mlp():
    """`tp_fused_mlp` rows: the tensor-parallel fused MLP pipeline at
    1/2/4 shards."""
    from repro.models.layers import mlp_init, param_values
    from repro.quant import quantize_mlp, quantized_mlp_apply

    d, ff = 256, 512
    qp = quantize_mlp(param_values(mlp_init(
        jax.random.PRNGKey(0), d, ff, "geglu", dtype=jnp.float32)))
    x = jax.random.normal(jax.random.PRNGKey(1), (256, d), jnp.float32) * 0.5
    times = {}
    for p in (1, 2, 4):
        f = jax.jit(lambda a: quantized_mlp_apply(qp, a, "geglu",
                                                  use_kernel=True))
        times[p] = _time_tp(f, model_mesh(p), x)
    return [(f"kernel_tp_fused_mlp_s{p}", times[p],
             f"geglu 256x256x512 shard_map {p}-way model mesh"
             + ("" if p == 1 else f"; vs_1shard={times[1]/times[p]:.2f}x"))
            for p in (1, 2, 4)]


SUITES = ("kernels", "resilience", "serving", "simulator")


def suite_of(name: str) -> str:
    """Which row family a bench row belongs to, by name prefix — the
    granularity at which stale-row pruning is scoped."""
    if name.startswith(("kernel_", "decode_attn_", "dit_tp_")):
        return "kernels"   # this module's rows; not all carry kernel_
    if name.startswith(("resilience_", "ecc_")):
        return "resilience"
    if name.startswith("serving_"):
        return "serving"
    return "simulator"


def write_bench_json(rows, path: str = BENCH_JSON,
                     full_run: bool = False,
                     ran_suites=None) -> None:
    """Persist (name, us, derived) rows as the cross-PR perf trajectory.

    Merges into an existing file instead of overwriting, so partial runs
    (``--skip-kernels``, ``make verify``'s smoke pass, a single-module
    run) update their rows without dropping everyone else's.  Stale-row
    pruning is scoped to ``ran_suites`` — the row families this
    invocation actually measured (see :func:`suite_of`): within a suite
    that ran, rows absent from this run are renamed/deleted benches and
    are dropped; suites that did NOT run keep their rows untouched.
    ``full_run=True`` is shorthand for "every suite ran".  Each row
    records the backend it was measured on (merged-in rows may predate
    the ``_meta`` header's run).
    """
    if ran_suites is None:
        ran_suites = set(SUITES) if full_run else set()
    ran_suites = set(ran_suites)
    try:
        with open(path) as f:
            existing = json.load(f).get("benches", {})
    except (FileNotFoundError, ValueError):
        existing = {}
    fresh = {name for name, _us, _d in rows}
    existing = {name: row for name, row in existing.items()
                if name in fresh or suite_of(name) not in ran_suites}
    existing.update({name: {"us": round(us, 1), "derived": derived,
                            "backend": jax.default_backend()}
                     for name, us, derived in rows})
    payload = {
        "_meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "note": "cpu-backend rows time the Pallas interpreter, not "
                    "TPU perf; rows merge across partial runs (last "
                    "writer per row wins; per-row 'backend' is "
                    "authoritative) and are pruned on full runs",
        },
        "benches": existing,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    bench_rows = bench_kernels()
    for name, us, derived in bench_rows:
        print(f"{name},{us:.1f},{derived}")
    write_bench_json(bench_rows, ran_suites={"kernels"})
    print(f"wrote {BENCH_JSON}")

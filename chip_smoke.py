#!/usr/bin/env python3
"""Chip smoke run: the serving paths on a TPU at published widths.

    python3 chip_smoke.py           # one chip: deepseek-67b + DiT-XL/2
    python3 chip_smoke.py --tp 4    # four chips: 4-way TP vs one chip

A smoke run, not a benchmark: every time it prints is one reading of one
run.  It exits non-zero (and prints no result) without a TPU, and the
last line of stdout, printed only when every phase passed, is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

One chip:

1. deepseek-67b (arXiv:2401.02954) at its published widths, depth cut
   to 4 layers, random weights from ``--seed``, served by the paged
   engine with the full INT8 plan (int8 weights and KV) through
   ``repro.launch.serve``: 8 greedy requests, prompts of 128-512
   tokens, 32 new tokens each, 256-token prefill chunks.  It serves
   the requests twice on one engine: the first run compiles, the
   second reuses its slots and blocks and must give the same tokens.
2. LM parity: one request's decode logits against a forward of the
   same tokens with the same int8 params on the jnp oracle path.
3. DiT-XL/2 through ``examples/generate_images.py``: 2 images, CFG on,
   4 DDIM steps, full INT8 plan; then one denoise step against the
   oracle path.
4. The compiled decode step and DiT sampler must hold Mosaic kernels
   (``tpu_custom_call``) for the fused GEMM, the paged decode attention
   and the adaLN GEMM — no interpreter or jnp fallback on the chip.

``--tp 4`` runs only the four-chip comparison: the same deepseek-67b
engine on a 4-way ``model`` mesh and on one chip, in one process; the
greedy tokens must be equal, the weights and KV pools sharded.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

# LM parity: the engine attends over an int8 KV cache (per-position,
# per-head absmax quantization, ~0.4% relative error per element) that
# the oracle forward does not have, and its prefill runs in 256-token
# chunks.  Measured against the RMS of the oracle logits, that error
# stays a few percent; a wrong kernel (layout, mask, scale) gives errors
# of the order of the logits themselves.
LM_REL_RMS_TOL = 0.05
LM_REL_MAX_TOL = 0.5
# DiT parity: kernel and oracle share the int8 weights and the
# activation quantization; only the f32 epilogue order and bf16
# rounding of the residual stream differ.
DIT_REL_RMS_TOL = 0.02
# TP parity: the row-parallel GEMMs sum int32 partials exactly, and
# every other op is per-head or per-column, so four shards should
# reproduce one chip up to float reassociation in XLA's own fusions.
TP_REL_MAX_TOL = 0.05

# Kernel families that must appear as Mosaic custom calls, matched
# against the op_name path of each tpu_custom_call.
LM_KERNELS = {"fused GEMM": ("cim_gemm_int8_fused", "cim_gated_gemm_int8"),
              "paged decode attention": ("decode_attention_paged",)}
DIT_KERNELS = {"fused GEMM": ("cim_gemm_int8_fused",),
               "adaLN GEMM": ("/adaln/",)}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu(n: int) -> dict:
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform "
                         f"{info['platform']!r}); nothing was run")
    if info["count"] < n:
        raise SystemExit(f"chip_smoke: needs {n} TPU chips, "
                         f"{info['count']} visible")
    return info


def mosaic_kernels(compiled) -> list[str]:
    """op_name of every Mosaic kernel in a compiled program."""
    import re
    names = []
    for line in compiled.as_text().split("\n"):
        if "tpu_custom_call" in line:
            m = re.search(r'op_name="([^"]*)"', line)
            names.append(m.group(1) if m else line)
    return names


def check_kernels(what: str, compiled, want: dict) -> None:
    names = mosaic_kernels(compiled)
    missing = [k for k, pats in want.items()
               if not any(p in n for n in names for p in pats)]
    log(f"{what}: {len(names)} Mosaic kernels; "
        + ", ".join(f"{k} present" for k in want if k not in missing))
    if missing:
        raise AssertionError(f"{what}: no tpu_custom_call for {missing}; "
                             f"kernels found: {sorted(set(names))}")


class DecodeLogits:
    """Engine ``fault_hook`` that keeps each decoding request's logits
    per step, keyed by (uid, tokens generated so far); never alters
    them.  ``uids`` limits what it keeps."""

    def __init__(self, uids=None):
        self.engine = None
        self.uids = uids
        self.rows: dict = {}

    def __call__(self, phase, logits):
        import numpy as np
        if phase != "decode":
            return None
        for slot, req in enumerate(self.engine.slot_req):
            if req is None or slot in self.engine.slot_fill:
                continue
            if self.uids is None or req.uid in self.uids:
                self.rows[(req.uid, len(req.generated))] = np.array(
                    logits[slot], np.float32)
        return None


def _rel(a, b) -> tuple[float, float]:
    """(RMS, max) of |a - b| relative to the RMS of ``b``."""
    import numpy as np
    err = np.abs(a - b)
    scale = float(np.sqrt(np.mean(np.square(b))))
    return (float(np.sqrt(np.mean(np.square(err)))) / scale,
            float(err.max()) / scale)


def lm_args(seed: int):
    from repro.launch import serve
    return serve.parse_args([
        "--arch", "deepseek-67b", "--layers", "4", "--int8",
        "--requests", "8", "--slots", "8", "--prompt-len", "128", "512",
        "--max-new", "32", "--prefill-chunk", "256", "--seed", str(seed)])


def load_lm(args):
    import jax
    from repro.launch import serve
    from repro.models import build_model
    from repro.quant import QuantPlan
    cfg = serve.serving_config(args.arch, args.layers)
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}")
    log(serve.describe_budget(
        serve.memory_budget(build_model(cfg), QuantPlan.full(), args.slots,
                            serve.max_len_for(args)),
        cfg.n_layers))
    t = time.perf_counter()
    model, params, plan = serve.load(args)
    jax.block_until_ready(params)
    log(f"init + int8 quantize, layer by layer: "
        f"{time.perf_counter() - t:.2f} s")
    return model, params, plan


def decode_step_compiled(engine):
    import jax.numpy as jnp
    n = engine.n_slots
    return engine._decode_masked.lower(
        engine.params, engine.cache, jnp.zeros(n, jnp.int32),
        jnp.ones(n, bool), jnp.asarray(engine.paged.tables)).compile()


def lm_phase(seed: int, check: bool = True) -> None:
    import numpy as np
    from repro.launch import serve
    from repro.serving import RequestStatus
    args = lm_args(seed)
    model, params, plan = load_lm(args)
    vocab = model.cfg.vocab
    engine = serve.build_engine(args, model, params, plan)

    warm_reqs = serve.make_requests(args, vocab)
    warm = serve.serve(engine, warm_reqs)
    reqs = serve.make_requests(args, vocab)
    rec = DecodeLogits(uids={reqs[0].uid})
    rec.engine = engine
    engine.fault_hook = rec
    run = serve.serve(engine, reqs)
    engine.fault_hook = None

    bad = [r.uid for r in reqs if r.status is not RequestStatus.OK
           or len(r.generated) != args.max_new]
    assert not bad, f"requests not served in full: {bad}"
    assert all(a.generated == b.generated for a, b in zip(warm_reqs, reqs)), \
        "the second run on reused slots and blocks changed the tokens"
    log(f"prompt lengths {[len(r.prompt) for r in reqs]}, "
        f"{run['tokens_out']} tokens out")
    log(f"compile + first run {warm['wall_s']:.2f} s; second run "
        f"{run['wall_s']:.2f} s on the same engine (slots and blocks "
        f"reused)")
    log(f"second run: TTFT s min/median/max "
        f"{min(run['ttft_s']):.4f}/{np.median(run['ttft_s']):.4f}/"
        f"{max(run['ttft_s']):.4f}; decode step s median "
        f"{np.median(run['decode_step_s']):.5f} over "
        f"{len(run['decode_step_s'])} steps; prefill-chunk step s median "
        f"{np.median(run['prefill_step_s']):.5f}")
    lm_parity(model, engine.params, reqs[0], rec)
    if check:
        check_kernels("deepseek-67b decode step", decode_step_compiled(engine),
                      LM_KERNELS)


def lm_parity(model, params, req, rec) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.quant import kernel_mode
    toks = np.concatenate([req.prompt, req.generated[:-1]]).astype(np.int32)
    with kernel_mode(False):
        fwd = jax.jit(lambda p, t: model.forward(p, {"inputs": t[None]})[0][0])
        oracle = np.asarray(fwd(params, jnp.asarray(toks)), np.float32)
    keys = sorted(k for k in rec.rows if k[0] == req.uid)
    L = len(req.prompt)
    eng = np.stack([rec.rows[k] for k in keys])
    orc = oracle[[L - 1 + n for _, n in keys]]
    rms, mx = _rel(eng, orc)
    top1 = float(np.mean(eng.argmax(-1) == orc.argmax(-1)))
    log(f"LM parity (request {req.uid}, {len(keys)} decode steps x "
        f"{eng.shape[1]} logits, engine vs jnp oracle forward): rel RMS "
        f"{rms:.5f} (tol {LM_REL_RMS_TOL}), rel max {mx:.5f} (tol "
        f"{LM_REL_MAX_TOL}), greedy top-1 agreement {top1:.3f}")
    assert len(keys) == len(req.generated) - 1, keys
    assert rms <= LM_REL_RMS_TOL and mx <= LM_REL_MAX_TOL, "LM parity"


def dit_args(seed: int, arch: str = "dit-xl-2"):
    import generate_images as gi
    return gi.parse_args(["--arch", arch, "--int8", "--images", "2",
                          "--batch", "2", "--cfg", "4.0", "--steps", "4",
                          "--seed", str(seed)])


def dit_phase(seed: int, arch: str = "dit-xl-2", check: bool = True):
    import generate_images as gi
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.quant import kernel_mode
    from repro.serving import RequestStatus
    args = dit_args(seed, arch)
    model, engine = gi.build(args)
    cfg = model.cfg
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, {cfg.tokens} tokens per image")
    warm_reqs, warm_s = gi.generate(engine, args)
    reqs, run_s = gi.generate(engine, args)
    for a, b in zip(warm_reqs, reqs):
        assert a.status is RequestStatus.OK and b.status is RequestStatus.OK
        assert np.isfinite(b.latents).all(), "non-finite latents"
        assert np.array_equal(a.latents, b.latents), "DiT not deterministic"
    log(f"{len(reqs)} images, CFG {args.cfg}, {args.steps} DDIM steps: "
        f"compile + first run {warm_s:.2f} s; second run {run_s:.3f} s "
        f"({run_s / args.steps:.4f} s per denoise step of "
        f"{2 * len(reqs)} CFG rows)")

    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (2, cfg.in_channels, cfg.input_size,
                                cfg.input_size), jnp.float32)
    t = jnp.asarray([999.0, 500.0])
    y = jnp.asarray([r.label for r in reqs], jnp.int32)
    out = np.asarray(jax.jit(lambda p, *a: model.forward(p, *a))(
        engine.params, x, t, y))
    with kernel_mode(False):
        ref = np.asarray(jax.jit(lambda p, *a: model.forward(p, *a))(
            engine.params, x, t, y))
    rms, mx = _rel(out, ref)
    log(f"DiT parity (one denoise step, 2 latents, kernels vs jnp "
        f"oracle): rel RMS {rms:.6f} (tol {DIT_REL_RMS_TOL}), rel max "
        f"{mx:.5f}")
    assert np.isfinite(out).all() and rms <= DIT_REL_RMS_TOL, "DiT parity"
    if check:
        noise = jnp.zeros((args.batch, cfg.in_channels, cfg.input_size,
                           cfg.input_size), jnp.float32)
        labels = jnp.zeros((args.batch,), jnp.int32)
        sampler = engine._sampler(args.steps, args.cfg, args.method)
        check_kernels("DiT-XL/2 sampler",
                      sampler.lower(engine.params, noise, labels).compile(),
                      DIT_KERNELS)


def _sharded(leaf, n: int) -> bool:
    """Spread over ``n`` devices, each holding a strict part of it."""
    sh = leaf.sharding
    return (len(sh.device_set) == n and not sh.is_fully_replicated
            and leaf.addressable_shards[0].data.size < leaf.size)


def tp_phase(seed: int, tp: int) -> None:
    import jax
    import numpy as np
    from repro.launch import serve
    from repro.quant import QuantizedLinear
    args = lm_args(seed)
    model, params, plan = load_lm(args)
    mesh = serve.tp_mesh(tp)
    outs = {}
    for name, m in (("one chip", None), (f"{tp}-way TP", mesh)):
        rec = DecodeLogits()
        engine = serve.build_engine(args, model, params, plan, mesh=m,
                                    fault_hook=rec)
        rec.engine = engine
        reqs = serve.make_requests(args, model.cfg.vocab)
        res = serve.serve(engine, reqs)
        log(f"{name}: {res['tokens_out']} tokens in {res['wall_s']:.2f} s "
            f"(compilation included)")
        outs[name] = (engine, reqs, rec)
    (e1, r1, rec1), (e4, r4, rec4) = outs.values()

    same = [a.generated == b.generated for a, b in zip(r1, r4)]
    keys = sorted(set(rec1.rows) & set(rec4.rows))
    a = np.stack([rec1.rows[k] for k in keys])
    b = np.stack([rec4.rows[k] for k in keys])
    rms, mx = _rel(b, a)
    log(f"TP parity: greedy tokens equal for {sum(same)}/{len(same)} "
        f"requests; over {len(keys)} decode steps the logits differ by "
        f"max abs {float(np.abs(a - b).max()):.6f} (rel max {mx:.6f}, tol "
        f"{TP_REL_MAX_TOL}; rel RMS {rms:.7f})")

    # every int8 weight is sharded; the scales of the row-parallel
    # layers (out-projection, down) span the unsharded output axis and
    # are replicated by design
    qls = [ql for ql in jax.tree.leaves(
        e4.params, is_leaf=lambda x: isinstance(x, QuantizedLinear))
        if isinstance(ql, QuantizedLinear)]
    kv = [v for g in e4.cache.values() for k, v in g.items()
          if "pages" in k and "pos" not in k]
    w_ok = sum(_sharded(ql.q, tp) for ql in qls)
    s_ok = sum(_sharded(ql.scale, tp) for ql in qls)
    kv_ok = sum(_sharded(x, tp) for x in kv)
    tables = [x for k, v in e4.params.items() if not k.startswith("group_")
              for x in jax.tree.leaves(v) if x.ndim == 2]
    t_ok = sum(_sharded(x, tp) for x in tables)
    log(f"TP placement: {w_ok}/{len(qls)} int8 weights ({s_ok} of their "
        f"scales), {t_ok}/{len(tables)} embedding/head tables and "
        f"{kv_ok}/{len(kv)} KV pool leaves sharded over {tp} devices; "
        f"KV pool spec {kv[0].sharding.spec}")
    hlo = decode_step_compiled(e4)
    n_ar = hlo.as_text().count("all-reduce")
    check_kernels(f"{tp}-way TP decode step", hlo, LM_KERNELS)
    log(f"{tp}-way TP decode step: {n_ar} all-reduce ops")
    assert all(same), "TP greedy tokens differ from one chip"
    assert mx <= TP_REL_MAX_TOL, "TP logits differ from one chip"
    assert (w_ok, t_ok, kv_ok) == (len(qls), len(tables), len(kv)), \
        "TP placement"
    assert n_ar > 0, "no cross-shard reduction in the TP decode step"


def main() -> int:
    ap = argparse.ArgumentParser(description="chip smoke run")
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the N-chip tensor-parallel comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    info = require_tpu(max(args.tp, 1))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"smoke run, not a benchmark; device {info['kind']} "
        f"x{info['count']}; compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.tp > 1:
        tp_phase(args.seed, args.tp)
    else:
        lm_phase(args.seed)
        dit_phase(args.seed)
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device 0 peak memory {stats['peak_bytes_in_use'] / 1e9:.2f} GB")
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""End-to-end diffusion serving: batched class-conditional image
generation with the DiT subsystem (sample loop + latency report).

    PYTHONPATH=src python examples/generate_images.py [--arch A] \
        [--int8] [--tp N] [--steps S] [--batch B] [--cfg W] [--method M]

``--arch`` picks a DiT config (``dit-test``, the CPU-sized default, or
``dit-xl-2`` at its published widths for an accelerator).  ``--int8``
runs every denoise step on the full QuantPlan: the adaLN modulation
GEMM, wide QKV, out-projection, and MLP all dispatch the fused quantize
-> INT8 GEMM -> dequant/act pipeline — a DiT block is exactly 6 Pallas
dispatches.  ``--tp N`` shards those pipelines over an N-way model mesh
(on CPU run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N``); generations
are bit-identical to the unsharded engine.  ``--cfg W`` enables
classifier-free guidance (cond+uncond stacked into one batch).
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import DIT_ARCH_IDS, get_dit_config
from repro.diffusion import DiffusionEngine, ImageRequest
from repro.launch.mesh import model_mesh
from repro.models.dit import DiTModel
from repro.quant import QuantPlan


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=DIT_ARCH_IDS, default="dit-test")
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--tp", type=int, default=0)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cfg", type=float, default=0.0,
                    help="classifier-free guidance scale (0 = off)")
    ap.add_argument("--method", choices=("ddim", "euler"), default="ddim")
    ap.add_argument("--images", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build(args):
    """(model, engine) for ``args``; weights random from ``--seed``."""
    mesh = None
    if args.tp:
        if not args.int8:
            raise SystemExit("--tp shards the fused INT8 pipeline; "
                             "pass --int8 as well")
        mesh = model_mesh(args.tp)
    model = DiTModel(get_dit_config(args.arch))
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = DiffusionEngine(
        model, params, batch_size=args.batch,
        quant_plan=QuantPlan.full() if args.int8 else None, mesh=mesh)
    return model, engine


def generate(engine, args) -> tuple[list, float]:
    """Submit ``args.images`` requests and run them: (requests, seconds)."""
    cfg = engine.model.cfg
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.images):
        req = ImageRequest(uid=i, label=int(rng.integers(cfg.n_classes)),
                           num_steps=args.steps, cfg_scale=args.cfg,
                           method=args.method, seed=args.seed + 1)
        reqs.append(req)
        engine.submit(req)
    t0 = time.perf_counter()
    engine.run_until_done()
    return reqs, time.perf_counter() - t0


def main(argv=None):
    args = parse_args(argv)
    model, engine = build(args)
    cfg = model.cfg
    if args.int8:
        print("serving the full INT8 QuantPlan (6 fused dispatches per "
              "DiT block" + (f", {args.tp}-way tensor parallel)"
                             if args.tp else ")"))
    reqs, dt = generate(engine, args)
    st = engine.stats
    evals = st.denoise_steps * (2 if args.cfg > 0 else 1)
    print(f"{cfg.name}: generated {st.images_out} latents "
          f"({cfg.tokens} tokens each) in {dt:.2f}s "
          f"({st.images_out/dt:.2f} img/s on {jax.default_backend()}, "
          f"compilation included)")
    print(f"batches: {st.batches}, denoise steps/batch: {args.steps}, "
          f"model evals (w/ CFG stacking): {evals}, "
          f"mean batch occupancy: {np.mean(st.batch_occupancy):.2f}")
    for r in reqs[:3]:
        lat = r.latents
        print(f"  img {r.uid}: class {r.label:4d} -> latent "
              f"{lat.shape}, mean {lat.mean():+.3f}, std {lat.std():.3f}")
    return reqs


if __name__ == "__main__":
    main()

"""End-to-end serving driver (the paper is an inference paper): serve a
small model with continuously-batched requests.

    PYTHONPATH=src python examples/serve_batched.py [--int8] [--tp N]

``--int8`` serves in the paper's INT8 CIM mode with the **full
QuantPlan**: attention QKV/out-projections, dense MLPs, and MoE experts
all run the fused quant -> GEMM -> dequant/act/residual pipeline
(Pallas kernels on TPU, their oracle on CPU) — one decode step of a
dense block is exactly 5 fused GEMM-pipeline dispatches.

``--tp N`` serves the INT8 plan tensor-parallel on an N-way model mesh
(shard_map'd per-device pipelines, weights device_put per shard; on CPU
run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
Generations are bit-identical to the unsharded engine.
"""
import sys
import time

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.launch.mesh import model_mesh
from repro.models import build_model
from repro.quant import QuantPlan
from repro.serving import Request, ServingEngine


def main():
    int8 = "--int8" in sys.argv
    tp = 0
    if "--tp" in sys.argv:
        try:
            tp = int(sys.argv[sys.argv.index("--tp") + 1])
        except (IndexError, ValueError):
            raise SystemExit("--tp takes a shard count, e.g. --tp 2")
    mesh = None
    if tp:
        if not int8:
            raise SystemExit("--tp shards the fused INT8 pipeline; "
                             "pass --int8 as well")
        mesh = model_mesh(tp)
    cfg = reduced_config(get_config("gemma-2b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=4, max_len=128,
                           prefill_bucket=16,
                           quant_plan=QuantPlan.full() if int8 else None,
                           mesh=mesh)
    if int8:
        print("serving the full INT8 QuantPlan (fused CIM pipeline"
              + (f", {tp}-way tensor parallel" if tp else "") + "):")
        print(QuantPlan.full().describe(model.groups))

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(10):
        plen = int(rng.integers(4, 14))
        req = Request(uid=i,
                      prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                      max_new_tokens=int(rng.integers(8, 24)),
                      temperature=0.8, top_k=40, seed=1)
        reqs.append(req)
        engine.submit(req)

    t0 = time.perf_counter()
    engine.run_until_done()
    dt = time.perf_counter() - t0
    st = engine.stats
    print(f"served {len(reqs)} requests / {st.tokens_out} tokens in "
          f"{dt:.2f}s ({st.tokens_out/dt:.1f} tok/s on CPU)")
    print(f"decode steps: {st.decode_steps}, mean slot occupancy: "
          f"{np.mean(st.batch_occupancy):.2f} (continuous batching)")
    for r in reqs[:3]:
        print(f"  req {r.uid}: {len(r.prompt)}-token prompt -> "
              f"{len(r.generated)} generated {r.generated[:8]}...")


if __name__ == "__main__":
    main()

"""Serving driver: the paged continuous-batching engine over one model.

On an accelerator the model keeps its published widths; ``--layers``
cuts only the depth.  On the CPU (where the Pallas kernels run in the
interpreter) it serves ``reduced_config`` instead.

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-67b \
        --layers 4 --int8 --requests 8 --slots 8

``--int8`` serves the full QuantPlan (int8 weights, int8 KV); its
weights are initialised and quantized one layer at a time
(``Model.init_quantized``).  ``--tp N`` serves over an N-way ``model``
mesh.  The memory budget is printed before anything is allocated.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.console import emit
from repro.launch.mesh import model_mesh
from repro.models import build_model
from repro.serving import PagedServingEngine, Request

GB = 1e9
BLOCK_SIZE = 16          # KV positions per paged block


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", choices=ARCH_IDS, default="deepseek-67b")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the published depth to this many layers")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(128, 512),
                    metavar=("MIN", "MAX"))
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prefill-chunk", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--int8", action="store_true",
                    help="serve the full INT8 QuantPlan (fused CIM "
                         "pipeline for attn projections/MLPs/MoE experts, "
                         "int8 KV cache)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (a 'model' mesh axis)")
    return ap.parse_args(argv)


def serving_config(arch: str, layers: int | None = None):
    """Published widths on an accelerator (depth cut to ``layers``);
    ``reduced_config`` on the CPU."""
    cfg = get_config(arch)
    if jax.default_backend() == "cpu":
        return reduced_config(cfg)
    if layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg


def max_len_for(args) -> int:
    """Table width: the longest prompt plus its answer, in whole blocks."""
    n = args.prompt_len[1] + args.max_new
    return -(-n // BLOCK_SIZE) * BLOCK_SIZE


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def memory_budget(model, plan, n_slots: int, max_len: int) -> dict:
    """Device bytes of the serving state, from shapes alone."""
    key = jax.random.PRNGKey(0)
    fp = jax.eval_shape(model.init, key)
    groups = [k for k in fp if k.startswith("group_")]
    layers_fp = _nbytes([fp[k] for k in groups])
    out = {"head_bytes": _nbytes({k: v for k, v in fp.items()
                                  if k not in groups}),
           "layers_fp_bytes": layers_fp,
           "layer_fp_bytes": layers_fp // model.cfg.n_layers}
    if plan is not None:
        qp = jax.eval_shape(lambda k: model.init_quantized(k, plan), key)
        out["layers_bytes"] = _nbytes([qp[k] for k in groups])
    else:
        out["layers_bytes"] = layers_fp
    max_blocks = max_len // BLOCK_SIZE
    kv = "int8" if plan is not None and plan.attn_kv else None
    out["kv_bytes"] = _nbytes(jax.eval_shape(
        lambda: model.init_paged_cache(n_slots, 1 + n_slots * max_blocks,
                                       BLOCK_SIZE, max_blocks, kv_dtype=kv)))
    out["resident_bytes"] = (out["head_bytes"] + out["layers_bytes"]
                             + out["kv_bytes"])
    return out


def describe_budget(b: dict, n_layers: int, tp: int = 1) -> str:
    dev = jax.devices()[0]
    cap = (dev.memory_stats() or {}).get("bytes_limit")
    cap_s = f"{cap / GB:.2f} GB" if cap else "unknown"
    return (f"memory budget: layers {b['layers_bytes'] / GB:.2f} GB | "
            f"embed+head {b['head_bytes'] / GB:.2f} GB | "
            f"one bf16 layer while it is quantized "
            f"{b['layer_fp_bytes'] / GB:.2f} GB (all {n_layers}: "
            f"{b['layers_fp_bytes'] / GB:.2f} GB, never live together) | "
            f"KV pool {b['kv_bytes'] / GB:.3f} GB (donated, one copy) | "
            f"resident {b['resident_bytes'] / GB:.2f} GB over {tp} "
            f"device(s), {cap_s} each")


def load(args):
    """(model, params, plan): INT8 params are built layer by layer."""
    from repro.quant import QuantPlan
    model = build_model(serving_config(args.arch, args.layers))
    if model.cfg.frontend == "audio":
        raise SystemExit("audio-frontend archs need embedding inputs; "
                         "use the token-backbone archs for this driver")
    key = jax.random.PRNGKey(args.seed)
    plan = QuantPlan.full() if args.int8 else None
    params = (model.init_quantized(key, plan) if plan is not None
              else model.init(key))
    return model, params, plan


def tp_mesh(tp: int):
    return model_mesh(tp) if tp > 1 else None


def build_engine(args, model, params, plan, mesh=None, fault_hook=None):
    return PagedServingEngine(
        model, params, n_slots=args.slots, max_len=max_len_for(args),
        block_size=BLOCK_SIZE, prefill_chunk=args.prefill_chunk,
        quant_plan=plan, mesh=mesh, fault_hook=fault_hook)


def make_requests(args, vocab: int) -> list[Request]:
    rng = np.random.default_rng(args.seed)
    lo, hi = args.prompt_len
    return [Request(uid=i,
                    prompt=rng.integers(0, vocab, int(rng.integers(lo, hi + 1))
                                        ).astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature, top_k=40, seed=args.seed)
            for i in range(args.requests)]


def serve(engine, reqs, max_iters: int = 100_000) -> dict:
    """Submit ``reqs`` and step the engine until they are all terminal.

    Every engine step ends in a host fetch of the logits it sampled
    from, so a step's wall time covers its device work.  Steps that ran
    a prefill chunk are kept apart from pure decode steps."""
    for r in reqs:
        engine.submit(r)
    decode_s, prefill_step_s = [], []
    t0 = time.perf_counter()
    for _ in range(max_iters):
        if not engine.pending():
            break
        chunks = engine.stats.prefill_chunks
        ts = time.perf_counter()
        engine.step()
        dt = time.perf_counter() - ts
        (prefill_step_s if engine.stats.prefill_chunks > chunks
         else decode_s).append(dt)
    else:
        raise RuntimeError(f"engine still busy after {max_iters} steps")
    return {"wall_s": time.perf_counter() - t0,
            "ttft_s": [r.first_token_at - r.submitted_at for r in reqs
                       if r.first_token_at is not None],
            "decode_step_s": decode_s, "prefill_step_s": prefill_step_s,
            "tokens_out": sum(len(r.generated) for r in reqs)}


def main(argv=None) -> dict:
    enable_compile_cache()
    args = parse_args(argv)
    model, params, plan = load(args)
    cfg = model.cfg
    emit(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, d_ff "
         f"{cfg.d_ff}, vocab {cfg.vocab} on "
         f"{jax.devices()[0].device_kind} x{max(args.tp, 1)}")
    emit(describe_budget(memory_budget(model, plan, args.slots,
                                       max_len_for(args)),
                         cfg.n_layers, max(args.tp, 1)))
    if plan is not None:
        emit(plan.describe(model.groups))
    engine = build_engine(args, model, params, plan, mesh=tp_mesh(args.tp))
    reqs = make_requests(args, cfg.vocab)
    res = serve(engine, reqs)
    st = engine.stats
    occ = float(np.mean(st.batch_occupancy)) if st.batch_occupancy else 0.0
    emit(f"served {len(reqs)} requests: {res['tokens_out']} tokens in "
         f"{res['wall_s']:.2f}s (first run: compilation included), "
         f"{st.decode_steps} decode steps, mean occupancy {occ:.2f}")
    for r in reqs[:4]:
        emit(f"  req {r.uid}: prompt[{len(r.prompt)}] -> {r.generated}")
    return res


if __name__ == "__main__":
    main()

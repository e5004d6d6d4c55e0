"""Drive the DiT engine (``repro.diffusion.DiffusionEngine``) with a
queue that always holds work: class-conditional requests, each
``num_steps`` DDIM steps with classifier-free guidance, served in
batches of ``batch`` images (``2 * batch`` rows per evaluation).

The window opens at a batch boundary after one compile warm-up batch
and closes at the first batch completion after ``seconds``, so the rate
counts whole batches over the time they took.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

from bench.lib import trace, traffic
from bench.lib.compiles import CompileLog
from bench.lib.record import Run


def program_model(cfg: dict):
    from repro.configs import get_dit_config
    from repro.models.dit import DiTModel
    base = get_dit_config(cfg["program_arch"])
    mcfg = dataclasses.replace(
        base, n_layers=cfg["depth"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_heads"], patch_size=cfg["patch_size"],
        in_channels=cfg["in_channels"], input_size=cfg["input_size"],
        mlp_ratio=int(cfg["mlp_ratio"]), n_classes=cfg["num_classes"],
        learn_sigma=cfg["learn_sigma"],
        freq_dim=cfg["frequency_embedding_size"])
    return DiTModel(mcfg)


def run(spec: dict, seed: int, seconds: float, trace_dir, t_process: float,
        device: dict, log, hooks=None,
        control_bits: int | None = None) -> dict:
    import jax
    from repro.diffusion import DiffusionEngine, ImageRequest
    from repro.quant import QuantPlan

    from bench.lib import names

    cfg, mix, wl = spec["config"], spec["traffic"], spec["workload"]
    B = wl["engine"]["batch"]
    key_seed = traffic.key_seed(seed)
    key = jax.random.PRNGKey(key_seed)

    model = program_model(cfg)
    t = time.perf_counter()
    params = model.init(key)
    engine = DiffusionEngine(model, params, batch_size=B,
                             quant_plan=QuantPlan.full())
    del params
    jax.block_until_ready(engine.params)
    log(f"init + int8 quantize {time.perf_counter() - t:.2f} s")

    n_req = 0
    labels = traffic.image_labels(mix, seed, 4096)
    reqs: dict[int, object] = {}

    def top_up():
        nonlocal n_req
        while engine.pending() < 2 * B:
            r = ImageRequest(uid=n_req, label=labels[n_req % len(labels)],
                             num_steps=mix["num_steps"],
                             cfg_scale=mix["cfg_scale"],
                             method=mix["method"], seed=key_seed)
            engine.submit(r)
            reqs[n_req] = r
            n_req += 1

    t = time.perf_counter()
    top_up()
    engine.step()                       # compile warm-up batch
    log(f"compile warm-up batch {time.perf_counter() - t:.2f} s")
    if hooks is not None:
        hooks(engine)

    compiles = CompileLog()
    run_rec = Run(kind="dit", config=cfg,
                  peaks=names.peaks(device["kind"]),
                  ops=names.ops(cfg["family"]))
    rows = 2 * B if mix["cfg_scale"] > 0 else B
    evals = [run_rec.ops.evaluation(cfg, rows)] * mix["num_steps"]
    ann = None
    t_open = time.perf_counter()
    if trace_dir is not None:
        trace.start(trace_dir)
        ann = jax.profiler.TraceAnnotation("bench.window")
        ann.__enter__()
        t_open = time.perf_counter()
    run_rec.traced = (t_open, t_open)
    while True:
        top_up()
        head = list(engine.queue)[:B]
        ts = time.perf_counter()
        with (jax.profiler.TraceAnnotation("bench.step") if ann is not None
              else contextlib.nullcontext()):
            engine.step()
        te = time.perf_counter()
        run_rec.steps.append({"t0": ts, "t1": te, "rows": rows,
                              "evals": mix["num_steps"], "work": evals,
                              "uids": [r.uid for r in head]})
        if te >= t_open + seconds:
            t_close = te
            break
    if ann is not None:
        run_rec.traced = (t_open, time.perf_counter())
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    run_rec.t_open, run_rec.t_close = t_open, t_close
    late = compiles.within(t_open, t_close)
    compiles.close()
    if late:
        log(f"compiled inside the window: {late}")
    done = [u for s in run_rec.steps for u in s["uids"]]
    failed = sum(reqs[u].status.value != "ok" for u in done)
    e2e = {"images_per_s": (len(done) - failed) / (t_close - t_open),
           "setup_s": t_open - t_process}
    log(f"window {t_close - t_open:.3f} s: {len(run_rec.steps)} batches, "
        f"{len(done)} images, {failed} failed")
    stats_mem = jax.devices()[0].memory_stats() or {}
    peak = int(stats_mem.get("peak_bytes_in_use", 0))

    check = wl["check"]
    pick = traffic.rng_for(seed, "check").permutation(len(done))
    pick = [done[i] for i in pick[:check["sample_images"]]]
    got = np.stack([reqs[u].latents for u in pick])
    del engine
    gc.collect()
    t = time.perf_counter()
    ref_mod = names.reference(cfg["family"])
    w = ref_mod.weights(cfg, key)
    noise = jax.numpy.stack([ref_mod.noise(cfg, key_seed, u) for u in pick])
    lab = jax.numpy.asarray([reqs[u].label for u in pick], jax.numpy.int32)
    ref = np.asarray(ref_mod.sample(cfg, w, noise, lab, mix["num_steps"],
                                    mix["cfg_scale"]))
    err = [_rel_rms(got[i], ref[i]) for i in range(len(pick))]
    log(f"reference over {len(pick)} images, "
        f"{time.perf_counter() - t:.2f} s; rel RMS {err}")
    checks = {check["name"]: {"value": max(err), "limit": check["limit"]}}
    if control_bits:
        del w
        w4 = ref_mod.weights(cfg, key, bits=control_bits)
        ctl = np.asarray(ref_mod.sample(cfg, w4, noise, lab,
                                        mix["num_steps"], mix["cfg_scale"],
                                        bits=control_bits))
        checks["control"] = {
            "value": max(_rel_rms(ctl[i], ref[i]) for i in range(len(pick))),
            "limit": check["limit"]}
    return {"attempted": len(done), "failed": failed, "end_to_end": e2e,
            "memory_peak_bytes": peak, "run": run_rec, "checks": checks}


def _rel_rms(got, ref) -> float:
    """RMS of the difference over the RMS of the reference."""
    return float(np.sqrt(np.mean((got - ref) ** 2))
                 / np.sqrt(np.mean(ref ** 2)))

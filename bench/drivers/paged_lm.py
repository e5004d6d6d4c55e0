"""Drive the paged INT8 LM engine (``repro.serving.PagedServingEngine``)
as users would: ``submit`` requests on the mix's schedule and ``step``.

Open loop: arrivals on the mix's schedule start after the compile
warm-up, so the engine is in steady state when the window opens
``ramp_s`` later.  Closed loop: ``clients`` requests, each with the part
of its answer the mix counts as already given, are prefilled before the
window opens; a client whose request finishes submits the next one.

Times are taken on the host clock at step ends, where the engine hands
tokens to the user (every step that decodes ends in a logits fetch).
TTFT counts from the request's due time, so a stall that delays
submission counts too.
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

TERMINAL_BAD = ("failed", "rejected", "timed_out")


def program_model(cfg: dict):
    """The program's model at the configuration's sizes."""
    from repro.configs import get_config
    from repro.models import build_model
    base = get_config(cfg["program_arch"])
    mcfg = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"]
                         // cfg["num_attention_heads"]),
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]))
    if (mcfg.activation, mcfg.norm, mcfg.tie_embeddings) != (
            "swiglu", "rmsnorm", cfg["tie_word_embeddings"]):
        raise ValueError(f"program config {mcfg.name} is not the "
                         f"configuration's architecture")
    return build_model(mcfg)


class _Annotate:
    """Wrap engine callables in profiler spans (traced runs only).  A
    missing callable is an error: the breakdown would lose its name."""

    def __init__(self, engine):
        import jax
        self.ann = jax.profiler.TraceAnnotation
        for attr, name in (("_admit", "bench.admit"),
                           ("_prefill_chunk_fn", "bench.dispatch_chunk"),
                           ("_decode_masked", "bench.dispatch_decode"),
                           ("_sample", "bench.sample"),
                           ("_clear_slot", "bench.release")):
            setattr(engine, attr, self._wrap(getattr(engine, attr), name))

    def _wrap(self, fn, name):
        ann = self.ann

        def wrapped(*a, **k):
            with ann(name):
                return fn(*a, **k)
        return wrapped


def run(spec: dict, seed: int, seconds: float, trace_dir, t_process: float,
        device: dict, log, hooks=None,
        control_bits: int | None = None, check: bool = True) -> dict:
    import jax
    from repro.quant import QuantPlan
    from repro.serving import PagedServingEngine, Request

    from bench.lib import names

    cfg, mix, wl = spec["config"], spec["traffic"], spec["workload"]
    eng = wl["engine"]
    C, max_len = eng["prefill_chunk"], eng["max_len"]
    key_seed = traffic.key_seed(seed)
    key = jax.random.PRNGKey(key_seed)

    model = program_model(cfg)
    plan = QuantPlan.full()
    t = time.perf_counter()
    params = model.init_quantized(key, plan)
    jax.block_until_ready(params)
    log(f"init + int8 quantize {time.perf_counter() - t:.2f} s")
    engine = PagedServingEngine(
        model, params, n_slots=eng["slots"], max_len=max_len,
        block_size=eng["block_size"], prefill_chunk=C, quant_plan=plan)
    vocab = cfg["vocab_size"]

    # compile warm-up: a full and a partial chunk, then decode steps
    t = time.perf_counter()
    warm = Request(uid=-1, prompt=traffic.prompt_tokens(seed, -1, C + 7,
                                                        vocab),
                   max_new_tokens=3, temperature=0.0)
    engine.submit(warm)
    engine.run_until_done()
    log(f"compile warm-up {time.perf_counter() - t:.2f} s")
    if hooks is not None:
        hooks(engine)

    compiles = CompileLog()
    stream = traffic.lm_requests(mix, seed, _n_requests(mix, seconds),
                                 max_len)
    recs: dict[int, dict] = {}
    live: list[dict] = []
    nxt = 0
    closed = mix["loop"] == "closed"

    def submit(r, now, due):
        n_prompt = r["prompt_len"] + r["answered"]
        req = Request(uid=r["uid"],
                      prompt=traffic.prompt_tokens(seed, r["uid"], n_prompt,
                                                   vocab),
                      max_new_tokens=r["max_new"] - r["answered"],
                      temperature=0.0)
        rec = {"uid": r["uid"], "L": n_prompt, "due": due,
               "submit": now, "admit": None, "first": None, "times": [],
               "req": req}
        engine.submit(req)
        recs[r["uid"]] = rec
        live.append(rec)

    ann = _Annotate(engine) if trace_dir is not None else None
    run_rec = Run(kind="lm", config=cfg,
                  peaks=names.peaks(device["kind"]),
                  ops=names.ops(cfg["family"]))
    steps = run_rec.steps
    t0 = time.perf_counter()
    if closed:
        for _ in range(mix["clients"]):
            submit(stream[nxt], t0, t0)
            nxt += 1
    win = {"open": None, "close": None, "ann": None}
    max_lag = 0.0

    def ready(now) -> bool:
        if closed:
            return all(r["first"] is not None for r in live)
        return now - t0 >= mix["ramp_s"]

    def open_window():
        if trace_dir is not None:
            jax.block_until_ready(engine.cache)
            trace.start(trace_dir)
            win["ann"] = jax.profiler.TraceAnnotation("bench.window")
            win["ann"].__enter__()
        win["open"] = time.perf_counter()
        win["queue_open"] = len(engine.queue)
        run_rec.traced = (win["open"], win["open"])

    def stop_trace():
        if win["ann"] is not None:
            jax.block_until_ready(engine.cache)
            run_rec.traced = (run_rec.traced[0], time.perf_counter())
            win["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            win["ann"] = None

    while win["close"] is None:
        now = time.perf_counter()
        if not closed:
            while nxt < len(stream) and t0 + stream[nxt]["due_s"] <= now:
                due = t0 + stream[nxt]["due_s"]
                if win["open"] is not None:
                    max_lag = max(max_lag, now - due)
                submit(stream[nxt], now, due)
                nxt += 1
            if nxt >= len(stream):
                raise RuntimeError("traffic ran out before the window "
                                   "closed; raise the request count")
        if win["open"] is None and ready(now):
            open_window()
        if not engine.pending():
            time.sleep(min(0.002, max(0.0, t0 + stream[nxt]["due_s"] - now)))
            now = time.perf_counter()
            if win["open"] is not None and now >= win["open"] + seconds:
                win["close"] = now
            continue
        fill_before = {s: int(v[1]) for s, v in engine.slot_fill.items()}
        n_before = {r["uid"]: len(r["req"].generated) for r in live}
        chunks0 = engine.stats.prefill_chunks
        ts = time.perf_counter()
        with (jax.profiler.TraceAnnotation("bench.step")
              if win["ann"] is not None else contextlib.nullcontext()):
            engine.step()
        te = time.perf_counter()
        step = {"t0": ts, "t1": te, "chunks": [], "ctx": [],
                "n_chunks": engine.stats.prefill_chunks - chunks0}
        for r in list(live):
            req = r["req"]
            if r["admit"] is None and req.status.value != "queued":
                r["admit"] = te
            for j in range(n_before.get(r["uid"], 0), len(req.generated)):
                r["times"].append(te)
                if j == 0:
                    r["first"] = te
                    off = C * ((r["L"] - 1) // C)
                    step["chunks"].append((r["L"] - off, off, True))
                else:
                    step["ctx"].append(r["L"] + j)
            if req.status.value in ("ok",) + TERMINAL_BAD:
                live.remove(r)
                if closed and win["open"] is not None:
                    # the client's next request: the stream's sizes, in turn
                    submit(dict(stream[nxt % len(stream)], uid=nxt,
                                answered=0), te, te)
                    nxt += 1
        for slot, fill in engine.slot_fill.items():
            off = int(fill[1])
            if off > fill_before.get(slot, 0):
                step["chunks"].append((C, off - C, False))
        step["work"] = (
            [run_rec.ops.prefill_chunk(cfg, v, o, last)
             for v, o, last in step["chunks"]]
            + ([run_rec.ops.decode(cfg, step["ctx"])] if step["ctx"] else []))
        if len(step["chunks"]) != step["n_chunks"]:
            step["work"] = None       # chunk bookkeeping lost: not counted
        if win["open"] is not None:
            steps.append(step)
            if te >= win["open"] + seconds:
                win["close"] = te
    stop_trace()
    t_open, t_close = win["open"], win["close"]
    queue = (win["queue_open"], len(engine.queue))
    run_rec.t_open, run_rec.t_close = t_open, t_close

    late = compiles.within(t_open, t_close)
    compiles.close()
    if late:
        log(f"compiled inside the window: {late}")
    # requests the window judges: due in it (open) or live in it (closed)
    judged = [r for r in recs.values()
              if (t_open <= r["due"] <= t_close if not closed
                  else r["submit"] <= t_close)]
    run_rec.requests = judged
    status = {r["uid"]: r["req"].status.value for r in recs.values()}
    failed = sum(status[r["uid"]] in TERMINAL_BAD for r in judged)
    stats = engine.stats
    log(f"window {t_close - t_open:.3f} s: {len(steps)} steps, "
        f"{sum(s['n_chunks'] for s in steps)} prefill chunks, "
        f"{sum(len(r['times']) for r in recs.values())} tokens so far, "
        f"{len(judged)} requests judged, {failed} failed, "
        f"{stats.preemptions} preemptions, queue {queue[0]} -> {queue[1]}, "
        f"generator lag max {max_lag * 1e3:.1f} ms")

    e2e = _end_to_end(judged, recs, t_open, t_close, closed)
    e2e["setup_s"] = t_open - t_process
    stats_mem = jax.devices()[0].memory_stats() or {}
    peak = int(stats_mem.get("peak_bytes_in_use", 0))

    if not check:
        del engine, params, live, ann
        gc.collect()
        return {"attempted": len(judged), "failed": failed,
                "end_to_end": e2e, "memory_peak_bytes": peak,
                "run": run_rec, "queue": queue, "checks": {}}
    seqs = _sample(recs, status, t_open, seed, wl["check"])
    del engine, params, live, ann
    gc.collect()
    t = time.perf_counter()
    ref = names.reference(cfg["family"]).served_gaps(
        cfg, key, seqs, bits_control=control_bits)
    gap = max(r["gap"] for r in ref)
    log(f"reference over {len(seqs)} requests, "
        f"{sum(r['tokens'] for r in ref)} served tokens, "
        f"{time.perf_counter() - t:.2f} s; top-1 agreement "
        f"{np.mean([r['top1'] for r in ref]):.3f}")
    check = wl["check"]
    checks = {check["name"]: {"value": gap, "limit": check["limit"]}}
    if control_bits:
        checks["control"] = {"value": max(r["control_gap"] for r in ref),
                             "limit": check["limit"]}
    return {"attempted": len(judged), "failed": failed,
            "end_to_end": e2e, "memory_peak_bytes": peak, "run": run_rec,
            "checks": checks}


def _n_requests(mix: dict, seconds: float) -> int:
    if mix["loop"] == "closed":
        # every client's first request plus replacements: none of the
        # long generations finishes in a window, but some may
        return 4 * mix["clients"]
    return int(mix["rate_per_s"] * (mix["ramp_s"] + seconds + 60)) + 16


def _end_to_end(judged, recs, t_open, t_close, closed) -> dict:
    out = {}
    n_tokens = sum(t_open < x <= t_close
                   for r in recs.values() for x in r["times"])
    out["tokens_per_s"] = n_tokens / (t_close - t_open)
    if not closed:
        ttft = [(r["first"] if r["first"] is not None
                 and r["first"] <= t_close else t_close) - r["due"]
                for r in judged]
        if ttft:
            out["ttft_p90_s"] = float(np.percentile(ttft, 90))
    return out


def _sample(recs, status, t_open, seed, check) -> list:
    """Requests for the reference: the longest the window finished (or,
    when it finished none, the longest in flight), then the others it
    finished, then those in flight, each group in an order drawn from
    the seed, until ``sample_tokens`` served tokens or ``max_requests``
    requests are covered."""
    done = [r for r in recs.values() if status[r["uid"]] == "ok"
            and r["times"] and r["times"][-1] > t_open]
    flight = [r for r in recs.values() if r["req"].generated
              and status[r["uid"]] != "ok"]
    longest = lambda r: -(r["L"] + len(r["req"].generated))  # noqa: E731
    done.sort(key=longest)
    flight.sort(key=longest)
    if done:
        first, done = done[:1], done[1:]
    else:
        first, flight = flight[:1], flight[1:]
    rng = traffic.rng_for(seed, "check")
    order = (first + [done[i] for i in rng.permutation(len(done))]
             + [flight[i] for i in rng.permutation(len(flight))])
    seqs, n = [], 0
    for r in order:
        req = r["req"]
        toks = np.concatenate([req.prompt, np.asarray(req.generated,
                                                      np.int32)])
        seqs.append((toks, len(req.prompt)))
        n += len(req.generated)
        if n >= check["sample_tokens"] or len(seqs) >= check["max_requests"]:
            break
    return seqs

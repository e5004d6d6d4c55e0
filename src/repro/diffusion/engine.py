"""Batched image-generation serving: the DiT sibling of ``ServingEngine``.

Diffusion inference has no KV cache and no per-token progress — every
request is ``num_steps`` full denoise evaluations over a fixed latent
token grid (1024 tokens for DiT-XL/2).  The engine therefore batches
*whole requests*: compatible queued requests (same step count, guidance
scale, and sampler method — the static shape/trace key) are stacked into
fixed-size batches of ``batch_size`` latents and run through one jitted
sampler; short batches pad by repeating the last row (padded rows are
computed and discarded — the price of static shapes, same trade as the
LLM engine's prefill buckets).

``quant_plan`` puts every denoise step on the fused INT8 CIM pipeline
(6 Pallas dispatches per DiT block); ``mesh`` serves it tensor-parallel
via the shard_map'd apply sites (quant/tp.py), bit-identical to the
unsharded engine.

Both engines share one request lifecycle (serving/lifecycle.py): an
``ImageRequest`` carries the same terminal :class:`RequestStatus` and
deadline/TTL plumbing as the LLM engine's ``Request`` — bounded-queue
backpressure, deadline expiry while queued, non-finite-latent health
checks, and loud stalls.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracing import span, step_span, trace_gc
from repro.serving.lifecycle import (EngineStallError, LifecycleMixin,
                                     RequestStatus)
from .sampler import DEFAULT_SCHEDULE, DiffusionSchedule, sample


@dataclass
class ImageRequest(LifecycleMixin):
    uid: int
    label: int                          # class id in [0, n_classes)
    num_steps: int = 8
    cfg_scale: float = 0.0              # 0 = unguided
    method: str = "ddim"
    seed: int = 0
    deadline_s: Optional[float] = None  # TTL from submission (engine clock)

    # filled by the engine (``done`` is the shared lifecycle property)
    latents: Optional[np.ndarray] = None   # [C, H, W]
    status: RequestStatus = RequestStatus.QUEUED
    error: Optional[str] = None
    submitted_at: float = 0.0
    finished_at: Optional[float] = None    # engine clock; span close


@dataclass
class DiffusionStats:
    batches: int = 0
    denoise_steps: int = 0              # model evaluations (per batch)
    images_out: int = 0
    batch_occupancy: list = field(default_factory=list)
    # reliability counters (monotone, mirrors serving.EngineStats)
    submitted: int = 0
    completed: int = 0
    failed: int = 0
    rejected: int = 0
    timed_out: int = 0


class DiffusionEngine:
    def __init__(self, model, params, batch_size: int = 4,
                 quant_plan=None, mesh=None, rules=None,
                 schedule: DiffusionSchedule = DEFAULT_SCHEDULE,
                 max_queue: Optional[int] = None, degraded: bool = False,
                 health_checks: bool = True,
                 fault_hook: Optional[Callable] = None, clock=None,
                 obs=None):
        self.model = model
        self.mesh = mesh
        self.rules = rules
        if quant_plan is not None:
            params = model.quantize(params, quant_plan, mesh=mesh,
                                    rules=rules)
        self.quant_plan = quant_plan
        self.params = params
        self.batch = batch_size
        self.schedule = schedule
        self.max_queue = max_queue
        self.degraded = degraded
        self.health_checks = health_checks
        self.fault_hook = fault_hook
        self.closed = False
        self._clock = clock if clock is not None else time.monotonic
        self.queue: deque[ImageRequest] = deque()
        self.stats = DiffusionStats()
        self._samplers: dict = {}
        self._step_num = 0              # engine.step's step_num
        self.obs = obs
        if obs is not None:
            obs.bind_dit_engine(self)
        trace_gc()

    # ------------------------------------------------------------------
    def _mesh_ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.parallel.context import sharding_context
        return sharding_context(self.mesh, self.rules)

    @contextlib.contextmanager
    def _step_ctx(self):
        with self._mesh_ctx():
            if self.degraded:
                from repro.quant import degraded_mode
                with degraded_mode(True):
                    yield
            else:
                yield

    def _sampler(self, num_steps: int, cfg_scale: float, method: str):
        """One jitted sampler per (steps, guidance, method) trace key."""
        key = (num_steps, cfg_scale, method)
        if key not in self._samplers:
            step_ctx = self._step_ctx

            @jax.jit
            def run(params, noise, labels):
                with step_ctx():
                    return sample(self.model, params, labels, x_init=noise,
                                  num_steps=num_steps, cfg_scale=cfg_scale,
                                  method=method, schedule=self.schedule)

            self._samplers[key] = run
        return self._samplers[key]

    # ------------------------------------------------------------------
    def _finish(self, req: ImageRequest, status: RequestStatus,
                error: Optional[str] = None) -> RequestStatus:
        now = self._clock()
        req.finish(status, error, now=now)
        if status is RequestStatus.OK:
            self.stats.completed += 1
        elif status is RequestStatus.FAILED:
            self.stats.failed += 1
        elif status is RequestStatus.TIMED_OUT:
            self.stats.timed_out += 1
        else:
            self.stats.rejected += 1
        if self.obs is not None:
            self.obs.on_finish(req, status, req.error, now)
        return status

    def submit(self, req: ImageRequest) -> RequestStatus:
        """Queue a request; returns its (possibly terminal) status.

        Malformed requests raise ``ValueError`` (label outside the model's
        class space — the null class is reserved for CFG — or bad step
        count / sampler method); capacity rejections (closed engine,
        bounded queue full) return a typed ``RequestStatus.REJECTED``.
        """
        if not (0 <= req.label < self.model.cfg.n_classes):
            self._finish(req, RequestStatus.REJECTED, "label out of range")
            raise ValueError(
                f"label {req.label} outside [0, {self.model.cfg.n_classes})"
                " (the last embedding row is the reserved CFG null class)")
        if req.num_steps < 0:
            self._finish(req, RequestStatus.REJECTED, "negative num_steps")
            raise ValueError("num_steps must be >= 0")
        if req.method not in ("ddim", "euler"):
            self._finish(req, RequestStatus.REJECTED, "unknown method")
            raise ValueError(f"unknown sampler method {req.method!r}")
        if self.closed:
            return self._finish(req, RequestStatus.REJECTED,
                                "engine closed (draining or shut down)")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return self._finish(
                req, RequestStatus.REJECTED,
                f"queue full ({self.max_queue} waiting): backpressure")
        req.status = RequestStatus.QUEUED
        req.submitted_at = self._clock()
        self.queue.append(req)
        self.stats.submitted += 1
        if self.obs is not None:
            self.obs.on_submit(req, req.submitted_at, len(self.queue))
        return RequestStatus.QUEUED

    def _noise(self, req: ImageRequest) -> jax.Array:
        cfg = self.model.cfg
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed), req.uid)
        return jax.random.normal(
            key, (cfg.in_channels, cfg.input_size, cfg.input_size),
            jnp.float32)

    def _purge_expired(self, now: float) -> None:
        if not any(r.deadline_s is not None for r in self.queue):
            return
        keep: deque[ImageRequest] = deque()
        while self.queue:
            r = self.queue.popleft()
            if r.expired(now):
                self._finish(r, RequestStatus.TIMED_OUT,
                             "deadline expired while queued")
            else:
                keep.append(r)
        self.queue = keep

    def step(self) -> None:
        """Run one batch: pop up to ``batch_size`` queued requests that
        share the head-of-queue trace key, pad, sample, deliver; inside
        one ``engine.step`` profiler span split by phase
        (:mod:`repro.obs.tracing`)."""
        self._step_num += 1
        with step_span(self._step_num):
            self._step()

    def _step(self) -> None:
        self._purge_expired(self._clock())
        if not self.queue:
            return
        with span("engine.dit.prepare"):
            head = self.queue[0]
            key = (head.num_steps, head.cfg_scale, head.method)
            batch: list[ImageRequest] = []
            rest: deque[ImageRequest] = deque()
            while self.queue and len(batch) < self.batch:
                r = self.queue.popleft()
                if (r.num_steps, r.cfg_scale, r.method) == key:
                    r.status = RequestStatus.ACTIVE
                    batch.append(r)
                else:
                    rest.append(r)
            self.queue = rest + self.queue   # preserve order of the skipped
            pad = self.batch - len(batch)
            rows = batch + [batch[-1]] * pad          # padded rows discarded
            noise = jnp.stack([self._noise(r) for r in rows])
            labels = jnp.asarray([r.label for r in rows], jnp.int32)
        with span("engine.dit.fetch"):
            lat = np.asarray(self._sampler(*key)(self.params, noise, labels))
        with span("engine.dit.deliver"):
            if self.fault_hook is not None:
                out = self.fault_hook("denoise", lat)
                if out is not None:
                    lat = np.asarray(out)
            if self.obs is not None:
                # CFG stacks conditional + null rows into one 2B batch, so
                # a guided image costs two model evaluations per step
                evals = head.num_steps * (2 if head.cfg_scale > 0.0 else 1)
                self.obs.on_denoise_batch(batch, evals, self._clock())
            delivered = 0
            for i, r in enumerate(batch):
                if self.health_checks and not np.isfinite(lat[i]).all():
                    self._finish(r, RequestStatus.FAILED,
                                 "non-finite latents")
                    continue
                r.latents = lat[i]
                self._finish(r, RequestStatus.OK)
                delivered += 1
            self.stats.batches += 1
            self.stats.denoise_steps += head.num_steps
            self.stats.images_out += delivered
            self.stats.batch_occupancy.append(len(batch) / self.batch)

    def pending(self) -> int:
        return len(self.queue)

    def run_until_done(self, max_iters: int = 10_000,
                       on_stall: str = "raise") -> None:
        """Step until the queue is empty; a stall is never silent
        (same contract as ``ServingEngine.run_until_done``)."""
        if on_stall not in ("raise", "timeout"):
            raise ValueError(f"on_stall must be 'raise' or 'timeout', "
                             f"got {on_stall!r}")
        for _ in range(max_iters):
            if not self.queue:
                return
            self.step()
        if not self.queue:
            return
        if on_stall == "timeout":
            while self.queue:
                self._finish(self.queue.popleft(), RequestStatus.TIMED_OUT,
                             "engine stalled at max_iters")
            return
        raise EngineStallError(
            f"run_until_done hit max_iters={max_iters} with "
            f"{len(self.queue)} request(s) still queued")

    def drain(self, max_iters: int = 10_000,
              on_stall: str = "timeout") -> None:
        """Stop admitting new work and run the accepted queue dry."""
        self.closed = True
        self.run_until_done(max_iters, on_stall=on_stall)

    def shutdown(self, drain: bool = True, max_iters: int = 10_000) -> None:
        if drain:
            self.drain(max_iters)
            return
        self.closed = True
        while self.queue:
            self._finish(self.queue.popleft(), RequestStatus.REJECTED,
                         "engine shutdown")

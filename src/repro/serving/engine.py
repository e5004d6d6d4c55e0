"""Serving engine: continuous batching over fixed-shape decode slots.

The paper is an inference paper — this is the end-to-end driver layer
that its CIM-TPU would sit under.  Architecture (vLLM-style, adapted to
JAX's static shapes):

  * ``n_slots`` concurrent sequences share one batched KV cache (the
    model's ring-buffer caches, leading batch dim = n_slots).
  * Requests queue up; free slots are *prefilled one request at a time*
    (slot-masked cache write) and then join the batched decode step.
  * Every decode step advances all active slots by one token; finished
    sequences (EOS or max_tokens) free their slot immediately — classic
    continuous batching, no head-of-line blocking on long generations.
  * Sampling: greedy / temperature / top-k, seeded per request.

All step functions are jitted once (static shapes: n_slots x 1 decode,
1 x prefill_len prefill buckets).

Reliability layer (see docs/architecture.md §8): every request carries a
terminal :class:`~repro.serving.lifecycle.RequestStatus` instead of a
bare ``done`` flag, the queue is bounded with typed backpressure
(``submit`` returns ``REJECTED`` instead of growing unboundedly),
per-request deadlines expire queued *and* active work, health checks
fail a slot's request on non-finite logits instead of sampling from
NaNs, ``run_until_done`` surfaces stalls instead of silently returning,
and ``drain``/``shutdown`` guarantee every request terminates.  With
health checks passing and no faults injected the serving behavior is
bit-identical to the pre-reliability engine (regression-pinned by
tests/test_reliability.py).
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention import paged_walk_pages
from repro.obs.tracing import span, step_span, trace_gc

from .lifecycle import (EngineStallError, LifecycleMixin,
                        RequestStatus)
from .paged_cache import PoolExhausted


@dataclass
class Request(LifecycleMixin):
    uid: int
    prompt: np.ndarray                  # [prompt_len] int32
    max_new_tokens: int = 32
    temperature: float = 0.0            # 0 = greedy
    top_k: int = 0
    eos_id: Optional[int] = None
    seed: int = 0
    deadline_s: Optional[float] = None  # TTL from submission (engine clock)

    # filled by the engine (``done`` is now a derived property:
    # status in TERMINAL_STATUSES — see serving/lifecycle.py)
    generated: list = field(default_factory=list)
    status: RequestStatus = RequestStatus.QUEUED
    error: Optional[str] = None
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None      # engine clock; first admission
    first_token_at: Optional[float] = None   # engine clock; TTFT source
    finished_at: Optional[float] = None      # engine clock; span close


@dataclass
class EngineStats:
    prefills: int = 0
    decode_steps: int = 0
    tokens_out: int = 0
    batch_occupancy: list = field(default_factory=list)
    # reliability counters (all monotone non-decreasing)
    submitted: int = 0
    completed: int = 0          # reached OK
    failed: int = 0             # reached FAILED
    rejected: int = 0           # reached REJECTED
    timed_out: int = 0          # reached TIMED_OUT
    prefill_failures: int = 0   # health check tripped on prefill logits
    # paged-engine counters (zero on the ring engine)
    preemptions: int = 0        # sequences evicted for blocks, requeued
    prefill_chunks: int = 0     # chunked-prefill dispatches
    pool_exhaustions: int = 0   # KV pool allocation failures (grow/admit)
    evicted_blocks: int = 0     # blocks freed by preemption evictions
    cache_utilization: list = field(default_factory=list)
    # held-expert load per MoE layer (depth order), summed over decode
    # steps: (row, expert) pairs routed to this chip's experts, the
    # most rows on one of them, and how many of them got any row (paged
    # engine; None without MoE layers)
    moe_rows_held: Optional[np.ndarray] = None
    moe_rows_max_held: Optional[np.ndarray] = None
    moe_experts_touched: Optional[np.ndarray] = None
    # paged decode kernel walk, summed over decode steps (paged engine
    # with attention layers): table entries of the decoding rows' live
    # compute blocks in one full-attention layer, and the entries of
    # those rows' tables
    kv_pages_walked: int = 0
    kv_pages_table: int = 0


class ServingEngine:
    def __init__(self, model, params, n_slots: int = 4,
                 max_len: int = 512, prefill_bucket: int = 64,
                 quant_plan=None,
                 mesh=None, rules=None, max_queue: Optional[int] = None,
                 degraded: bool = False, health_checks: bool = True,
                 fault_hook: Optional[Callable] = None, clock=None,
                 obs=None):
        """``mesh`` (a jax Mesh with a ``model`` axis) serves the
        quant-plan decode path tensor-parallel: quantized weights are
        device_put sharded per their logical axes (q + scale co-sharded
        on the output-channel axis) and every prefill/decode step traces
        under a sharding context, so the fused INT8 pipelines run as
        shard_map'd per-device kernels (quant/tp.py) — bit-identical to
        the unsharded engine, with per-shard dispatch counts unchanged.

        Reliability knobs:

        * ``max_queue`` — bounded admission queue; when full, ``submit``
          returns a typed ``RequestStatus.REJECTED`` (backpressure)
          instead of growing unboundedly.
        * ``degraded`` — trace the step functions under
          :func:`repro.quant.degraded_mode`: each quantized layer
          screens its fused output and falls back to the sanitized
          reference path when non-finite (lax.cond, so the healthy path
          pays one reduction).
        * ``health_checks`` — fail a slot's request on non-finite
          logits (prefill or decode) instead of sampling from NaNs.
          On finite logits this is a no-op, so the default-on check
          keeps the fault-free path bit-identical.
        * ``fault_hook(phase, logits) -> logits | None`` — host-side
          interception point after every prefill/decode fetch; the
          chaos harness (reliability/chaos.py) uses it to inject
          non-finite logits deterministically.
        * ``clock`` — injectable monotonic clock (seconds) for
          deadline/TTL accounting; defaults to ``time.monotonic``.
        * ``obs`` — an :class:`repro.obs.Observability` instance.  Every
          instrumentation point is host-side and guarded by a single
          ``obs is not None`` check, so an uninstrumented engine runs
          exactly the pre-obs code path (bitwise-identical outputs,
          jaxpr/dispatch pins untouched).
        """
        self.model = model
        self.mesh = mesh
        self.rules = rules
        if quant_plan is not None:
            # INT8 decode path (the paper's CIM serving mode): every
            # plan-covered weight matmul — attention QKV/out-projection,
            # dense-FFN MLPs, MoE experts — becomes int8 QuantizedLinear
            # leaves, and every prefill/decode step runs the fused
            # quant->GEMM->dequant/act/residual Pallas pipeline instead
            # of bf16 einsums + XLA elementwise ops.
            params = model.quantize(params, quant_plan, mesh=mesh,
                                    rules=rules)
        self.quant_plan = quant_plan
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.bucket = prefill_bucket
        self.max_queue = max_queue
        self.degraded = degraded
        self.health_checks = health_checks
        self.fault_hook = fault_hook
        self.closed = False
        self._clock = clock if clock is not None else time.monotonic
        # a plan covering attn_kv stores the KV cache int8 at write time
        # (half the decode HBM traffic; the flash-decode kernel
        # dequantizes in-kernel); the fp cache stays the oracle path
        self.kv_dtype = ("int8" if quant_plan is not None
                         and getattr(quant_plan, "attn_kv", False) else None)
        self.cache = self._init_cache()
        self.slot_req: list[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int32)
        self.slot_last = np.zeros(n_slots, np.int32)
        self.queue: deque[Request] = deque()
        self.stats = EngineStats()
        self._build_steps()
        self.obs = obs
        if obs is not None:
            obs.bind_llm_engine(self)

    # ------------------------------------------------------------------
    def _init_cache(self):
        """Build (and mesh-place) the KV cache; the paged engine
        overrides this with block pools + tables."""
        cache = self.model.init_cache(self.n_slots, self.max_len,
                                      kv_dtype=self.kv_dtype)
        if self.mesh is not None:
            # place the cache per its logical axes: KV heads bind the
            # model axis (when divisible), so TP decode holds 1/p of
            # the KV cache per shard instead of replicating it
            from repro.parallel.sharding import make_shardings
            cache = jax.device_put(
                cache,
                make_shardings(self.mesh, cache,
                               self.model.cache_axes(kv_dtype=self.kv_dtype),
                               self.rules))
        return cache

    def _mesh_ctx(self):
        """Active sharding context for step tracing when serving on a
        mesh (turns on the shard_map TP paths in quant/tp.py)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.parallel.context import sharding_context
        return sharding_context(self.mesh, self.rules)

    @contextlib.contextmanager
    def _step_ctx(self):
        """Trace-time context for the jitted step bodies: sharding plus,
        when ``degraded`` is set, the quant layer's finite-screen
        fallback (the context executes while jit traces the body, like
        the mesh context — so ``degraded`` must be fixed at build)."""
        with self._mesh_ctx():
            if self.degraded:
                from repro.quant import degraded_mode
                with degraded_mode(True):
                    yield
            else:
                yield

    def _build_steps(self):
        model = self.model
        step_ctx = self._step_ctx

        @jax.jit
        def prefill_one(params, cache, tokens, slot, length):
            """Prefill one request into slot ``slot`` of the batched cache.

            Cache leaves are stacked [layers, batch, ...]; a fresh
            single-slot view is prefetched, reset (zeros, empty position
            sentinel, index 0), prefilled with batch=1, and written back.

            ``tokens`` is the bucket-padded prompt and ``length`` its true
            length: pad positions are written with the empty-slot
            sentinel (2**30) so the model never attends to them, the
            returned logits are the last *real* token's, and the write
            index resumes at ``length`` (decode overwrites the pad
            slots).  Recurrent mixers (SSM/xLSTM) have no position-keyed
            cache, so for them padding remains approximate.
            """
            def take(a):
                return jax.lax.dynamic_slice_in_dim(a, slot, 1, 1)

            sub = jax.tree.map(take, cache)
            sub = jax.tree.map(jnp.zeros_like, sub)
            sub = _set_pos_empty(sub)
            with step_ctx():
                logits, sub = model.prefill_padded(
                    params, {"inputs": tokens[None]}, sub,
                    jnp.asarray([length], jnp.int32))

            def put(full, s):
                return jax.lax.dynamic_update_slice_in_dim(
                    full, s.astype(full.dtype), slot, 1)

            cache = jax.tree.map(put, cache, sub)
            return logits[0, -1], cache

        @jax.jit
        def decode_all(params, cache, last_tokens):
            with step_ctx():
                logits, cache = model.decode_step(
                    params, {"inputs": last_tokens[:, None]}, cache)
            return logits[:, 0], cache

        self._prefill_one = prefill_one
        self._decode_all = decode_all

    # ------------------------------------------------------------------
    def _obs_kv_slots(self) -> int:
        """Cache positions a decode kernel streams per sequence — the
        manifest's split-KV discriminant (the paged engine overrides
        with its block-table capacity)."""
        return self.max_len

    def _finish(self, req: Request, status: RequestStatus,
                error: Optional[str] = None) -> RequestStatus:
        """Move ``req`` to a terminal status and book it in the stats.

        The single terminal funnel: ``req.finish`` enforces the
        exactly-once transition, so the obs span-close hook here fires
        exactly once per request on every terminal path.
        """
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

    def submit(self, req: Request) -> RequestStatus:
        """Queue a request; returns its (possibly terminal) status.

        Malformed requests raise ``ValueError`` up front (admission
        would otherwise fail late or corrupt state silently):

        * empty prompts — ``_admit`` pads by repeating the final token
          (``prompt[-1]``), which raises IndexError mid-serve on a
          zero-length prompt;
        * prompts whose *bucket-padded* length reaches ``max_len`` —
          the prefill write would wrap the ring cache and silently
          overwrite the oldest prompt tokens (and decode needs at least
          one free slot past the prompt).

        Capacity rejections are *typed, not raised*: a closed/draining
        engine or a full bounded queue returns
        ``RequestStatus.REJECTED`` (with ``req.error`` set) so callers
        can apply backpressure without exception plumbing.
        """
        L = len(req.prompt)
        if L == 0:
            self._finish(req, RequestStatus.REJECTED, "empty prompt")
            raise ValueError("empty prompt: requests must contain at "
                             "least one token")
        padded = L + (-L) % self.bucket
        if padded >= self.max_len:
            self._finish(req, RequestStatus.REJECTED,
                         "padded prompt would wrap the ring cache")
            raise ValueError(
                f"prompt of length {L} pads to the {padded}-token prefill "
                f"bucket, but max_len={self.max_len}: the ring cache would "
                f"wrap and silently drop the oldest prompt tokens. Raise "
                f"max_len (or shrink prefill_bucket) so padded prompts "
                f"stay strictly below it.")
        return self._enqueue(req)

    def _enqueue(self, req: Request) -> RequestStatus:
        """Shared admission tail: capacity rejections are typed, not
        raised (see :meth:`submit`)."""
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

    def _sample(self, req: Request, logits: np.ndarray, step: int) -> int:
        """Sample the next token; hardened against non-finite logits.

        On fully-finite rows this is bit-identical to the naive
        implementation (the non-finite mask is the identity).  Rows the
        health check did not catch (``health_checks=False``) must still
        never crash the serve loop: NaN/+inf entries are masked to
        -inf before softmax/argmax (previously ``p /= p.sum()`` turned
        an all--inf row into NaN probabilities and ``rng.choice``
        raised mid-serve), and a row with no finite entry at all
        deterministically yields token 0.
        """
        logits = np.asarray(logits)
        finite = np.isfinite(logits)
        if not finite.any():
            return 0
        masked = np.where(finite, logits, -np.inf)
        if req.temperature <= 0.0:
            return int(np.argmax(masked))
        rng = np.random.default_rng((req.seed, req.uid, step))
        x = masked.astype(np.float64) / req.temperature
        if req.top_k:
            kth = np.partition(x, -req.top_k)[-req.top_k]
            x = np.where(x < kth, -np.inf, x)
        m = x.max()
        if not np.isfinite(m):        # top-k landed entirely on -inf
            return int(np.argmax(masked))
        p = np.exp(x - m)
        p /= p.sum()
        return int(rng.choice(len(p), p=p))

    def _apply_fault_hook(self, phase: str, logits: np.ndarray) -> np.ndarray:
        if self.fault_hook is None:
            return logits
        out = self.fault_hook(phase, logits)
        return logits if out is None else np.asarray(out)

    # ------------------------------------------------------------------
    def _admit(self, now: float) -> None:
        """Fill free slots from the queue (prefill path).

        Expired queued requests are purged (TIMED_OUT) and a prefill
        whose logits fail the health check frees its candidate slot for
        the next queued request instead of occupying it with a poisoned
        sequence (the next prefill resets the slot's cache view).
        """
        for slot in range(self.n_slots):
            while self.slot_req[slot] is None and self.queue:
                req = self.queue.popleft()
                if req.expired(now):
                    self._finish(req, RequestStatus.TIMED_OUT,
                                 "deadline expired while queued")
                    continue
                L = len(req.prompt)
                pad = (-L) % self.bucket
                # pad to the bucket by repeating the final token: keeps
                # the prefill shape static (one jit trace per bucket
                # count).  The pad region is masked inside prefill
                # (empty-position sentinel), so generations are identical
                # to an exact-length prefill and decode resumes at the
                # true position L.
                toks = np.concatenate(
                    [req.prompt,
                     np.full(pad, req.prompt[-1])]).astype(np.int32)
                req.admitted_at = now
                if self.obs is not None:
                    self.obs.on_admit(req, slot, now)
                logits, self.cache = self._prefill_one(
                    self.params, self.cache, jnp.asarray(toks), slot, L)
                self.stats.prefills += 1
                if self.obs is not None:
                    # ring prefill computes the full bucket-padded prompt
                    self.obs.on_prefill(req, len(toks), len(toks), now)
                    self.obs.on_prefill_done(req, now)
                logits = self._apply_fault_hook("prefill",
                                                np.asarray(logits))
                if self.health_checks and not np.isfinite(logits).all():
                    self.stats.prefill_failures += 1
                    self._finish(req, RequestStatus.FAILED,
                                 "non-finite prefill logits")
                    continue
                nxt = self._sample(req, logits, 0)
                req.status = RequestStatus.ACTIVE
                req.generated.append(nxt)
                if req.first_token_at is None:
                    req.first_token_at = self._clock()
                    if self.obs is not None:
                        self.obs.on_first_token(req, req.first_token_at)
                if self.obs is not None:
                    self.obs.on_token(req, nxt, now)
                self.slot_req[slot] = req
                self.slot_pos[slot] = L
                self.slot_last[slot] = nxt

    def _active(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def _clear_slot(self, slot: int) -> None:
        """Free a slot after its request went terminal (the paged engine
        additionally releases the slot's KV blocks here)."""
        self.slot_req[slot] = None

    def step(self) -> None:
        """One engine iteration: expire + admit + one batched decode."""
        now = self._clock()
        for slot in self._active():
            req = self.slot_req[slot]
            if req.expired(now):
                self._finish(req, RequestStatus.TIMED_OUT,
                             "deadline expired mid-decode")
                self._clear_slot(slot)
        self._admit(now)
        if self.obs is not None:
            self.obs.queue_depth.set(len(self.queue))
        active = self._active()
        if not active:
            return
        self.stats.batch_occupancy.append(len(active) / self.n_slots)
        last = jnp.asarray(self.slot_last)
        logits, self.cache = self._decode_all(self.params, self.cache, last)
        logits = self._apply_fault_hook("decode", np.asarray(logits))
        self.stats.decode_steps += 1
        if self.obs is not None:
            self.obs.on_decode_rows(
                [(self.slot_req[s], int(self.slot_pos[s]) + 1)
                 for s in active], now)
        for slot in active:
            req = self.slot_req[slot]
            if self.health_checks and not np.isfinite(logits[slot]).all():
                self._finish(req, RequestStatus.FAILED,
                             "non-finite logits")
                self._clear_slot(slot)        # slot freed, cache reset
                continue                      # on its next prefill
            tok = self._sample(req, logits[slot], len(req.generated))
            req.generated.append(tok)
            self.stats.tokens_out += 1
            if self.obs is not None:
                self.obs.on_token(req, tok, now)
            self.slot_last[slot] = tok
            self.slot_pos[slot] += 1
            if ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.generated) >= req.max_new_tokens
                    or self.slot_pos[slot] >= self.max_len - 1):
                self._finish(req, RequestStatus.OK)
                self._clear_slot(slot)       # slot freed immediately

    def pending(self) -> int:
        """Requests not yet terminal: queued + active."""
        return len(self.queue) + len(self._active())

    def run_until_done(self, max_iters: int = 10_000,
                       on_stall: str = "raise") -> None:
        """Step until every request is terminal.

        A stall (``max_iters`` exhausted with work still pending) is
        never silent: ``on_stall='raise'`` (default) raises
        :class:`~repro.serving.lifecycle.EngineStallError`;
        ``on_stall='timeout'`` instead finishes every pending request as
        ``TIMED_OUT`` and returns — the graceful-drain flavor.
        """
        if on_stall not in ("raise", "timeout"):
            raise ValueError(f"on_stall must be 'raise' or 'timeout', "
                             f"got {on_stall!r}")
        for _ in range(max_iters):
            if not self.pending():
                return
            self.step()
        if not self.pending():
            return
        if on_stall == "timeout":
            self._expire_pending("engine stalled at max_iters")
            return
        raise EngineStallError(
            f"run_until_done hit max_iters={max_iters} with "
            f"{len(self.queue)} queued and {len(self._active())} active "
            f"request(s) still pending")

    def _expire_pending(self, why: str) -> None:
        while self.queue:
            self._finish(self.queue.popleft(), RequestStatus.TIMED_OUT, why)
        for slot in self._active():
            self._finish(self.slot_req[slot], RequestStatus.TIMED_OUT, why)
            self._clear_slot(slot)

    def drain(self, max_iters: int = 10_000,
              on_stall: str = "timeout") -> None:
        """Graceful drain: stop admitting new work (subsequent ``submit``
        calls get a typed ``REJECTED``) and run everything already
        accepted to a terminal status."""
        self.closed = True
        self.run_until_done(max_iters, on_stall=on_stall)

    def shutdown(self, drain: bool = True, max_iters: int = 10_000) -> None:
        """Stop the engine; every pending request reaches a terminal
        status.  ``drain=True`` finishes accepted work first; ``False``
        aborts immediately (queued -> REJECTED, active -> FAILED)."""
        if drain:
            self.drain(max_iters)
            return
        self.closed = True
        while self.queue:
            self._finish(self.queue.popleft(), RequestStatus.REJECTED,
                         "engine shutdown")
        for slot in self._active():
            self._finish(self.slot_req[slot], RequestStatus.FAILED,
                         "engine shutdown with request in flight")
            self._clear_slot(slot)


def _set_pos_empty(cache):
    """Reset ring-buffer position arrays to the empty sentinel."""
    def fix(path, a):
        name = str(path[-1]) if path else ""
        if "pos" in name and hasattr(a, "dtype") and a.dtype == jnp.int32 \
                and a.ndim >= 2:
            return jnp.full_like(a, 2 ** 30)
        return a
    return jax.tree_util.tree_map_with_path(fix, cache)


class PagedServingEngine(ServingEngine):
    """Continuously batched engine over the paged (block-table) KV cache.

    Differences from the ring-cache base engine (docs/architecture.md
    §10):

    * **Paged KV storage** — slots hold per-sequence block tables into
      shared fixed-size block pools (:mod:`repro.serving.paged_cache`);
      a short sequence consumes blocks for its actual length, not a
      ``max_len`` ring, so ``num_blocks`` can be provisioned well below
      ``n_slots * max_blocks`` and freed blocks recirculate every step.
    * **Chunked prefill** — prompts stream through
      ``Model.prefill_padded(offset=...)`` one ``prefill_chunk``-token
      chunk per engine step, interleaved with decode for the already-
      running slots, so a long prompt no longer stalls every other
      sequence for its full prefill.
    * **Preemption** — when the pool runs dry mid-decode, the youngest
      sequence is evicted (blocks freed, request requeued at the front)
      and later resumed by recomputation: its resume prefill covers
      prompt + generated-so-far, rebuilding the evicted logical KV
      state (recomputed KV can differ from decode-written KV in the
      last float bit — chunk-prefill vs kernel-decode reduction
      shapes — so greedy generations continue unchanged, sampled ones
      continue from the same distribution).
    * **Block-granular admission** — ``submit`` bounds prompts by the
      block table (``max_blocks * block_size`` positions, with one
      position of decode headroom), not by the prefill bucket padding
      of the ring layout.

    Scheduling never changes tokens: every per-row computation depends
    only on that row's logical KV content, so continuous batching here
    is bitwise-identical to static batching of the same requests
    (pinned by tests/test_serving.py).
    """

    def __init__(self, model, params, n_slots: int = 8,
                 max_len: int = 512, prefill_bucket: int = 64,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None, **kw):
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else prefill_bucket)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be positive")
        # slot -> [resume tokens (prompt + generated), next chunk offset]
        self.slot_fill: dict[int, list] = {}
        self._slot_seq = np.zeros(n_slots, np.int64)   # admission order
        self._admit_order = 0
        self._step_num = 0              # engine.step's step_num
        super().__init__(model, params, n_slots=n_slots, max_len=max_len,
                         prefill_bucket=prefill_bucket, **kw)
        trace_gc()

    # -- cache ---------------------------------------------------------
    def _init_cache(self):
        from .paged_cache import PagedKVCache
        self.paged = PagedKVCache(self.model, self.n_slots, self.max_len,
                                  self.block_size,
                                  num_blocks=self.num_blocks,
                                  kv_dtype=self.kv_dtype, mesh=self.mesh,
                                  rules=self.rules)
        # the engine owns the pools from here: every step donates them
        cache, self.paged.cache = self.paged.cache, None
        self._walks_kv = any(spec[0] in ("attn", "attn_local")
                             for spec, _ in self.model.groups)
        return cache

    def _tables(self):
        return jnp.asarray(self.paged.tables)

    # -- jitted steps --------------------------------------------------
    def _build_steps(self):
        model = self.model
        step_ctx = self._step_ctx
        num_blocks = self.paged.allocator.num_blocks

        def per_row(name: str) -> bool:
            # leaves with a leading [layers, batch, ...] layout; the
            # pools are [layers, num_blocks, ...] and shared by all rows
            return ("block_tables" in name
                    or ("index" in name and "pos" not in name))

        def install_tables(cache, tables):
            def fix(path, a):
                name = str(path[-1]) if path else ""
                if "block_tables" in name:
                    return jnp.broadcast_to(
                        tables[None].astype(a.dtype), a.shape)
                return a
            return jax.tree_util.tree_map_with_path(fix, cache)

        # Every step donates the pool tree (``cache``): XLA updates the
        # pools in place instead of holding two copies across the step.
        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_chunk(params, cache, tokens, slot, length, offset,
                          tables):
            """Prefill one chunk of one request into slot ``slot``.

            Unlike the ring engine's ``prefill_one`` the sub-view is
            *not* zeroed: the pools are shared by every sequence, and a
            fresh slot's blocks are already clean (positions scrubbed to
            the empty sentinel on release).  ``tokens`` is the padded
            chunk, ``length`` its valid length, ``offset`` the running
            position of the chunk's first token; the write index resumes
            at ``offset + length``.
            """
            cache = install_tables(cache, tables)

            def take(path, a):
                name = str(path[-1]) if path else ""
                if not per_row(name):
                    return a
                a = jax.lax.dynamic_slice_in_dim(a, slot, 1, 1)
                if "index" in name:
                    # the chunk writes from ``offset``: a reused slot
                    # still holds its previous sequence's write index
                    a = jnp.full_like(a, offset)
                return a

            sub = jax.tree_util.tree_map_with_path(take, cache)
            with step_ctx():
                logits, sub = model.prefill_padded(
                    params, {"inputs": tokens[None]}, sub,
                    jnp.asarray([length], jnp.int32),
                    offset=jnp.asarray([offset], jnp.int32))

            def put(path, full, s):
                name = str(path[-1]) if path else ""
                if per_row(name):
                    return jax.lax.dynamic_update_slice_in_dim(
                        full, s.astype(full.dtype), slot, 1)
                return s.astype(full.dtype)

            cache = jax.tree_util.tree_map_with_path(put, cache, sub)
            return logits[0, -1], cache

        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode_all(params, cache, last_tokens, decode_mask, tables):
            """One decode step for every slot in ``decode_mask``.

            Non-decoding slots (empty or mid-prefill) get their write
            index masked to the empty sentinel: their KV/position writes
            land out of range and are dropped (``mode="drop"``), their
            garbage logits are discarded host-side, and their true index
            is restored by their next prefill chunk — so a shared-pool
            decode step never perturbs a row that is not decoding.
            """
            cache = install_tables(cache, tables)

            def mask_idx(path, a):
                name = str(path[-1]) if path else ""
                if "index" in name and "pos" not in name:
                    return jnp.where(decode_mask[None, :], a, 2 ** 30)
                return a

            cache = jax.tree_util.tree_map_with_path(mask_idx, cache)
            with step_ctx():
                logits, cache, load = model.decode_step_with_load(
                    params, {"inputs": last_tokens[:, None]}, cache)
            # what the host fetches (logits and the MoE layers' held-
            # expert load, one transfer), then the donated pools
            return (logits[:, 0], load), cache

        @functools.partial(jax.jit, donate_argnums=(0,))
        def scrub(cache, blocks):
            """Reset freed blocks' positions to the empty sentinel so a
            reallocated block never exposes its previous sequence's
            stale positions.  ``blocks`` is padded to the table width
            with ``num_blocks`` (out of range -> dropped)."""
            def fix(path, a):
                name = str(path[-1]) if path else ""
                if "pos_pages" in name:
                    return a.at[:, blocks].set(2 ** 30, mode="drop")
                return a
            return jax.tree_util.tree_map_with_path(fix, cache)

        self._prefill_chunk_fn = prefill_chunk
        self._decode_masked = decode_all
        self._scrub = scrub
        self._scrub_width = self.paged.max_blocks
        self._scrub_pad = num_blocks

    # -- admission -----------------------------------------------------
    def submit(self, req: Request) -> RequestStatus:
        """Queue a request; block-granular admission bounds.

        The ring engine rejects prompts whose *bucket-padded* length
        reaches ``max_len``; here the bound is the block table: the
        prompt plus one decode position must fit in ``max_blocks``
        blocks (``paged.capacity_tokens`` positions).  A prompt of
        exactly ``capacity_tokens - 1`` tokens — one block of headroom,
        rejected by the ring layout whenever it pads up to ``max_len``
        — is admissible here.
        """
        L = len(req.prompt)
        if L == 0:
            self._finish(req, RequestStatus.REJECTED, "empty prompt")
            raise ValueError("empty prompt: requests must contain at "
                             "least one token")
        cap = self.paged.capacity_tokens
        if L + 1 > cap:
            self._finish(req, RequestStatus.REJECTED,
                         "prompt exceeds the slot's block table")
            raise ValueError(
                f"prompt of length {L} (+1 decode position) needs "
                f"{self.paged.allocator.blocks_for(L + 1)} blocks but the "
                f"block table holds {self.paged.max_blocks} x "
                f"{self.block_size}-token blocks ({cap} positions). "
                f"Raise max_len (table width) or block_size.")
        return self._enqueue(req)

    def _obs_kv_slots(self) -> int:
        return self.paged.capacity_tokens

    def _used_tokens(self) -> int:
        """KV positions actually written across all slots (filling slots
        count their chunk offset, decoding slots their position)."""
        used = 0
        for slot in self._active():
            if slot in self.slot_fill:
                used += int(self.slot_fill[slot][1])
            else:
                used += int(self.slot_pos[slot])
        return used

    def _clear_slot(self, slot: int) -> None:
        with span("engine.release"):
            freed = self.paged.release(slot)
            if freed:
                pad = np.full(self._scrub_width, self._scrub_pad, np.int32)
                pad[:len(freed)] = freed
                self.cache = self._scrub(self.cache, jnp.asarray(pad))
            self.slot_req[slot] = None
            self.slot_fill.pop(slot, None)

    def _admit(self, now: float) -> None:
        """Assign queued requests to free slots (FIFO, no reordering).

        Admission only *claims* the slot and stages the resume tokens
        (prompt + any generated-before-preemption); the actual cache
        writes happen in the chunked-prefill phase of :meth:`step`.
        Admission stops — preserving FIFO order — as soon as the head
        request's first-token block demand exceeds the free pool.
        """
        for slot in range(self.n_slots):
            if self.slot_req[slot] is not None:
                continue
            while self.queue:
                req = self.queue[0]
                if req.expired(now):
                    self.queue.popleft()
                    self._finish(req, RequestStatus.TIMED_OUT,
                                 "deadline expired while queued")
                    continue
                toks = np.asarray(req.prompt, np.int32)
                if req.generated:    # resume-by-recompute after preemption
                    toks = np.concatenate(
                        [toks, np.asarray(req.generated, np.int32)])
                if not self.paged.can_fit(len(toks) + 1):
                    return
                self.queue.popleft()
                req.status = RequestStatus.ACTIVE
                if req.admitted_at is None:    # a resume keeps the first
                    req.admitted_at = now
                self.slot_req[slot] = req
                self.slot_fill[slot] = [toks, 0]
                self._slot_seq[slot] = self._admit_order
                self._admit_order += 1
                if self.obs is not None:
                    self.obs.on_admit(req, slot, now,
                                      resumed=bool(req.generated))
                break

    # -- block pressure ------------------------------------------------
    def _pick_victim(self, requester: int) -> Optional[int]:
        cands = [s for s in self._active()
                 if s != requester and self.paged.n_blocks_of[s] > 0]
        if not cands:
            return None
        return max(cands, key=lambda s: self._slot_seq[s])

    def _preempt(self, slot: int) -> None:
        """Evict ``slot`` to free its blocks; the request requeues at
        the *front* (it is the oldest waiting work) and resumes later by
        recomputing prompt + generated-so-far."""
        req = self.slot_req[slot]
        freed = int(self.paged.n_blocks_of[slot])
        self._clear_slot(slot)
        req.status = RequestStatus.QUEUED
        self.queue.appendleft(req)
        self.stats.preemptions += 1
        self.stats.evicted_blocks += freed
        if self.obs is not None:
            self.obs.on_preempt(req, slot, freed, self._clock())

    def _ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot`` to cover ``n_tokens`` positions, preempting
        younger sequences under pool pressure.  Returns False when
        ``slot`` itself went terminal (pool exhausted with no victim
        left — the request fails rather than stalling the engine)."""
        while True:
            try:
                self.paged.ensure(slot, n_tokens)
                return True
            except PoolExhausted:
                self.stats.pool_exhaustions += 1
                if self.obs is not None:
                    self.obs.on_pool_exhausted(self.slot_req[slot], slot,
                                               self._clock())
                victim = self._pick_victim(slot)
                if victim is None:
                    self._finish(self.slot_req[slot], RequestStatus.FAILED,
                                 "KV block pool exhausted")
                    self._clear_slot(slot)
                    return False
                self._preempt(victim)

    def _count_moe_load(self, load: np.ndarray) -> None:
        """Sum one decode step's held-expert load ([n_moe_layers, 3])."""
        st = self.stats
        if st.moe_rows_held is None:
            st.moe_rows_held = np.zeros(len(load), np.int64)
            st.moe_rows_max_held = np.zeros(len(load), np.int64)
            st.moe_experts_touched = np.zeros(len(load), np.int64)
        st.moe_rows_held += load[:, 0]
        st.moe_rows_max_held += load[:, 1]
        st.moe_experts_touched += load[:, 2]
        if self.obs is not None:
            self.obs.on_moe_load(load)

    def _count_kv_walk(self, rows: list) -> None:
        """Count one decode step's paged-kernel walk from the slots'
        positions on the host (a sliding-window layer walks fewer)."""
        if not self._walks_kv:
            return
        bs, nb = self.paged.block_size, self.paged.max_blocks
        walked = int(paged_walk_pages(self.slot_pos[rows] + 1, bs, nb).sum())
        table = nb * len(rows)
        self.stats.kv_pages_walked += walked
        self.stats.kv_pages_table += table
        if self.obs is not None:
            self.obs.on_kv_walk(walked, table)

    def _maybe_finish(self, slot: int, req: Request, tok: int) -> None:
        if ((req.eos_id is not None and tok == req.eos_id)
                or len(req.generated) >= req.max_new_tokens
                or self.slot_pos[slot] >= self.paged.capacity_tokens - 1):
            self._finish(req, RequestStatus.OK)
            self._clear_slot(slot)

    # -- the engine loop -----------------------------------------------
    def step(self) -> None:
        """One engine iteration: expire + admit + one prefill chunk per
        filling slot + one batched decode for every running slot, inside
        one ``engine.step`` profiler span split by phase
        (:mod:`repro.obs.tracing`)."""
        self._step_num += 1
        with step_span(self._step_num):
            self._step()

    def _step(self) -> None:
        now = self._clock()
        with span("engine.admit"):
            for slot in self._active():
                req = self.slot_req[slot]
                if req.expired(now):
                    self._finish(req, RequestStatus.TIMED_OUT,
                                 "deadline expired mid-decode")
                    self._clear_slot(slot)
            self._admit(now)

        # chunked prefill: one chunk per filling slot, interleaved with
        # decode below (a long prompt never stalls running sequences)
        C = self.prefill_chunk
        for slot in sorted(self.slot_fill):
            if slot not in self.slot_fill:       # preempted this step
                continue
            req = self.slot_req[slot]
            toks, off = self.slot_fill[slot]
            with span("engine.prefill.dispatch"):
                chunk = toks[off:off + C]
                valid = len(chunk)
                if valid < C:                    # pad by repeating
                    chunk = np.concatenate(
                        [chunk, np.full(C - valid, chunk[-1])]
                    ).astype(np.int32)
                if not self._ensure(slot, off + valid):
                    continue
                logits, self.cache = self._prefill_chunk_fn(
                    self.params, self.cache, jnp.asarray(chunk), slot,
                    valid, off, self._tables())
            self.stats.prefill_chunks += 1
            if self.obs is not None:
                # the dispatch computes C padded query positions at
                # ``off``, attending the off + C cached positions
                self.obs.on_prefill(req, len(chunk), off + len(chunk),
                                    now, chunk=True, offset=off)
            off += valid
            if off < len(toks):
                self.slot_fill[slot][1] = off
                continue
            # final chunk: the request joins the decode batch
            self.stats.prefills += 1
            if self.obs is not None:
                self.obs.on_prefill_done(req, now)
            with span("engine.prefill.fetch"):
                logits = np.asarray(logits)
            with span("engine.prefill.sample"):
                logits = self._apply_fault_hook("prefill", logits)
                if self.health_checks and not np.isfinite(logits).all():
                    self.stats.prefill_failures += 1
                    self._finish(req, RequestStatus.FAILED,
                                 "non-finite prefill logits")
                    self._clear_slot(slot)
                    continue
                tok = self._sample(req, logits, len(req.generated))
                req.generated.append(tok)
                if self.obs is not None:
                    self.obs.on_token(req, tok, now)
                if req.first_token_at is None:
                    req.first_token_at = self._clock()
                    if self.obs is not None:
                        self.obs.on_first_token(req, req.first_token_at)
                del self.slot_fill[slot]
                self.slot_pos[slot] = len(toks)
                self.slot_last[slot] = tok
                self._maybe_finish(slot, req, tok)

        # batched decode over every slot that is past prefill
        with span("engine.decode.dispatch"):
            ok = []
            for slot in self._active():
                if slot in self.slot_fill or self.slot_req[slot] is None:
                    continue
                if self._ensure(slot, int(self.slot_pos[slot]) + 1):
                    ok.append(slot)
            ok = [s for s in ok if self.slot_req[s] is not None
                  and s not in self.slot_fill]   # drop preempted victims
            if ok:
                self.stats.batch_occupancy.append(len(ok) / self.n_slots)
                mask = np.zeros(self.n_slots, bool)
                mask[ok] = True
                self._count_kv_walk(ok)
                fetched, self.cache = self._decode_masked(
                    self.params, self.cache, jnp.asarray(self.slot_last),
                    jnp.asarray(mask), self._tables())
        if ok:
            with span("engine.decode.fetch"):
                logits, load = jax.device_get(fetched)
            self.stats.decode_steps += 1
            if len(load):
                self._count_moe_load(load)
            with span("engine.decode.sample"):
                logits = self._apply_fault_hook("decode", logits)
                if self.obs is not None:
                    self.obs.on_decode_rows(
                        [(self.slot_req[s], int(self.slot_pos[s]) + 1)
                         for s in ok], now)
                for slot in ok:
                    req = self.slot_req[slot]
                    if self.health_checks \
                            and not np.isfinite(logits[slot]).all():
                        self._finish(req, RequestStatus.FAILED,
                                     "non-finite logits")
                        self._clear_slot(slot)
                        continue
                    tok = self._sample(req, logits[slot],
                                       len(req.generated))
                    req.generated.append(tok)
                    self.stats.tokens_out += 1
                    if self.obs is not None:
                        self.obs.on_token(req, tok, now)
                    self.slot_last[slot] = tok
                    self.slot_pos[slot] += 1
                    self._maybe_finish(slot, req, tok)
        self.stats.cache_utilization.append(self.paged.utilization())
        if self.obs is not None:
            self.obs.on_kv_state(
                self.paged.utilization(),
                self.paged.fragmentation(self._used_tokens()))
            self.obs.queue_depth.set(len(self.queue))

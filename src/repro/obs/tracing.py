"""Per-request tracing: structured event log + request span records,
and the engines' profiler spans.

Every request served by an instrumented engine leaves two artifacts:

  * a stream of **events** in the engine-global :class:`EventLog` —
    plain dicts ``{"ts": <engine-clock>, "event": <name>, "uid": ...,
    ...}`` in emission order.  Timestamps come from the engine's
    injectable clock, so a step-clocked test or traffic harness gets a
    fully deterministic log (two seeded runs produce identical logs,
    pinned in tests/test_obs.py);
  * a :class:`RequestTrace` — the request's span summary (queue-wait,
    prefill, decode, preemptions) plus its attributed tokens, modeled
    MACs, and joules by component.

The span-close contract: every request that enters the system emits
exactly one ``request_end`` event, on whichever terminal
:class:`~repro.serving.lifecycle.RequestStatus` path it takes (finish,
deadline, stall-timeout, preempt-resume, chaos-failed slot, typed
rejection).  ``RequestTrace.close`` enforces single closure the same
way ``LifecycleMixin.finish`` enforces single terminal assignment.

Event names (the schema; docs/architecture.md §12):

=================  ======================================================
event              fields beyond ``ts``/``uid``
=================  ======================================================
submit             queue_depth
admit              slot, resumed (preemption-resume re-admissions)
prefill            q_len, kv_len, chunk (bool), offset
first_token        ttft_steps
decode             kv_len (one per request per batched decode step)
token              token, n (1-based index into the generation)
preempt            slot, freed_blocks
pool_exhausted     slot
chaos              kind (weight_injection / logit_nan), detail fields
denoise_batch      evals, batch (diffusion engine)
request_end        status, error, tokens, joules, span close — exactly
                   once per request
=================  ======================================================

Profiler spans stand apart from both, per engine and not per request:
named host intervals on the profiler's clock, written with
``jax.profiler.TraceAnnotation`` so they land in the same trace as the
device ops and name what the host does while the device waits.  The
paged and diffusion engines emit them.  They are always on and
independent of ``obs=``: with no profiler attached each costs about a
microsecond, and nothing inside a jitted function changes.  Phases nest inside their
``engine.step``; ``engine.release`` and ``engine.gc`` may nest inside
any other span.

=========================  ============================================
span                       what it covers
=========================  ============================================
engine.step                one ``step()`` call; ``step_num`` is the
                           engine's own step count
engine.admit               paged: deadline expiry and slot admission
engine.prefill.dispatch    paged, per chunk: padding, block growth,
                           chunk and table uploads, the jitted call (a
                           chunk whose growth fails its request stops
                           before the call)
engine.prefill.fetch       paged: the final chunk's logits to the host
engine.prefill.sample      paged: that row's health check and sample
engine.decode.dispatch     paged: block growth for the decode batch,
                           mask and table uploads, the jitted call
engine.decode.fetch        paged: the batch's logits to the host (the
                           host blocked on the device, then the copy)
engine.decode.sample       paged: health checks, sampling and per-row
                           bookkeeping of the batch
engine.release             paged: a slot's block release and scrub
                           dispatch
engine.dit.prepare         diffusion: batch, noise and labels
engine.dit.fetch           diffusion: sampler dispatch and the latents
                           to the host
engine.dit.deliver         diffusion: health checks and delivery
engine.gc                  one garbage collection (:func:`trace_gc`);
                           ``generation`` is the collected generation
=========================  ============================================
"""
from __future__ import annotations

import gc
import json
from dataclasses import dataclass, field
from typing import Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """A profiler span named ``name``; ``meta`` rides along as stats."""
    return TraceAnnotation(name, **meta)


def step_span(n: int) -> StepTraceAnnotation:
    """The ``engine.step`` span of step ``n`` (the profiler's step
    marker, so trace viewers group the host and device work by step)."""
    return StepTraceAnnotation("engine.step", step_num=n)


_gc_open: list = []


def _gc_span(phase: str, info: dict) -> None:
    # a collection starts and stops on one thread, and collections never
    # overlap, so one open span at a time
    if phase == "start":
        ann = TraceAnnotation("engine.gc", generation=info["generation"])
        ann.__enter__()
        _gc_open.append(ann)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def trace_gc() -> None:
    """Put every garbage collection in an ``engine.gc`` span, once per
    process however often it is called."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


class EventLog:
    """Append-only structured event stream (host-side dicts)."""

    def __init__(self, max_events: Optional[int] = None):
        self.events: list[dict] = []
        self.max_events = max_events
        self.dropped = 0

    def emit(self, event: str, ts: float, **fields) -> dict:
        # hot path (one call per decode row / token): reuse the kwargs
        # dict as the record instead of merging into a fresh one
        fields["ts"] = float(ts)
        fields["event"] = event
        if self.max_events is not None \
                and len(self.events) >= self.max_events:
            self.dropped += 1          # bounded log: drop, never grow
            return fields
        self.events.append(fields)
        return fields

    def __len__(self) -> int:
        return len(self.events)

    def __iter__(self):
        return iter(self.events)

    def select(self, event: str, uid: Optional[int] = None) -> list:
        return [e for e in self.events if e["event"] == event
                and (uid is None or e.get("uid") == uid)]

    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(e, sort_keys=True)
                         for e in self.events)

    def clear(self) -> None:
        self.events.clear()
        self.dropped = 0


@dataclass
class RequestTrace:
    """Span summary for one request (LLM token request or DiT image)."""

    uid: int
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None    # first slot/batch admission
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    status: Optional[str] = None
    error: Optional[str] = None
    tokens: int = 0
    prefill_chunks: int = 0
    decode_steps: int = 0
    preemptions: int = 0
    # modeled attribution (core/energy.py pricing of this request's rows)
    macs: float = 0.0
    mxu_j: float = 0.0
    vpu_j: float = 0.0
    memory_j: float = 0.0
    closed: bool = field(default=False, repr=False)

    @property
    def joules(self) -> float:
        return self.mxu_j + self.vpu_j + self.memory_j

    @property
    def queue_wait(self) -> Optional[float]:
        if self.admitted_at is None:
            return None
        return self.admitted_at - self.submitted_at

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def itl(self) -> Optional[float]:
        """Mean inter-token latency over the decode span."""
        if (self.first_token_at is None or self.finished_at is None
                or self.tokens < 2):
            return None
        return (self.finished_at - self.first_token_at) / (self.tokens - 1)

    def add_energy(self, mxu_j: float, vpu_j: float, memory_j: float,
                   macs: float) -> None:
        self.mxu_j += mxu_j
        self.vpu_j += vpu_j
        self.memory_j += memory_j
        self.macs += macs

    def close(self, status: str, error: Optional[str], now: float) -> None:
        """Single-closure guard — the tracing mirror of
        ``LifecycleMixin.finish``."""
        if self.closed:
            raise RuntimeError(
                f"request {self.uid}: span already closed "
                f"({self.status}); refusing second close ({status})")
        self.closed = True
        self.status = status
        self.error = error
        self.finished_at = now

    def summary(self) -> dict:
        """JSON-able per-request record for snapshots/reports."""
        return {
            "uid": self.uid,
            "status": self.status,
            "error": self.error,
            "submitted_at": self.submitted_at,
            "queue_wait": self.queue_wait,
            "ttft": self.ttft,
            "itl": self.itl,
            "finished_at": self.finished_at,
            "tokens": self.tokens,
            "prefill_chunks": self.prefill_chunks,
            "decode_steps": self.decode_steps,
            "preemptions": self.preemptions,
            "macs": self.macs,
            "joules": self.joules,
            "mxu_j": self.mxu_j,
            "vpu_j": self.vpu_j,
            "memory_j": self.memory_j,
        }

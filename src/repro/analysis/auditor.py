"""Trace model steps abstractly and audit them against the manifest.

Tracing is *abstract end to end*: parameters and caches are built with
``jax.eval_shape`` (no memory is allocated), so the auditor runs the
full paper-scale registry — command-r-plus at d_model 12288 included —
on a laptop in seconds.  ``jax.make_jaxpr`` accepts the resulting
``ShapeDtypeStruct`` trees directly.

Every entry point returns an :class:`AuditReport`; nothing here raises
on a contract violation (callers decide severity), only on auditor
misuse (unknown arch, missing devices for a TP audit).
"""
from __future__ import annotations

import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_mesh
from . import jaxpr_tools as jt
from . import manifest, passes
from .passes import Violation

_KEY = jax.random.PRNGKey(0)
_KV_LEAF_NAMES = ("k", "v", "k_pages", "v_pages", "latent_pages")


@dataclasses.dataclass
class AuditReport:
    target: str                 # arch id
    phase: str                  # prefill | decode_ring | decode_paged | step
    sharded: bool
    expected: dict              # site class -> count (manifest)
    actual: dict                # site class -> count (traced)
    violations: list
    skipped: str | None = None  # reason, when the target has no contract

    @property
    def ok(self) -> bool:
        return not self.violations

    @property
    def n_dispatches(self) -> int:
        return sum(self.actual.values())

    def to_dict(self) -> dict:
        return {
            "target": self.target, "phase": self.phase,
            "sharded": self.sharded, "ok": self.ok,
            "skipped": self.skipped,
            "dispatches": self.n_dispatches,
            "expected": dict(self.expected), "actual": dict(self.actual),
            "violations": [v.to_dict() for v in self.violations],
        }

    def diff_lines(self) -> list:
        """Human-readable diff vs the manifest, one finding per line."""
        tag = f"{self.target}/{self.phase}" + ("/tp" if self.sharded
                                               else "")
        if self.skipped:
            return [f"SKIP {tag}: {self.skipped}"]
        if self.ok:
            return [f"ok   {tag}: {self.n_dispatches} dispatches "
                    f"{dict(sorted(self.actual.items()))}"]
        lines = [f"FAIL {tag}:"]
        for cls in sorted(set(self.expected) | set(self.actual)):
            e, a = self.expected.get(cls, 0), self.actual.get(cls, 0)
            if e != a:
                lines.append(f"       {cls}: manifest {e} != traced {a}")
        for v in self.violations:
            if v.code != "count_mismatch":
                lines.append(f"       [{v.pass_name}/{v.code}] "
                             f"{v.site}: {v.message}")
        return lines


# ---------------------------------------------------------------------------
# Abstract step tracing
# ---------------------------------------------------------------------------
def _build(arch: str, reduced: bool):
    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
    return build_model(cfg)


def _abstract_quantized(model, mesh=None):
    """ShapeDtypeStruct tree of the full-plan quantized params — built
    under eval_shape so no weight memory is ever allocated."""
    return jax.eval_shape(
        lambda: model.quantize(model.init(_KEY), mesh=mesh))


def _decode_batch(cfg, batch: int, steps: int = 1):
    if cfg.frontend == "audio":
        return {"frame_embeddings": jax.ShapeDtypeStruct(
            (batch, steps, cfg.d_model), jnp.float32)}
    return {"inputs": jax.ShapeDtypeStruct((batch, steps), jnp.int32)}


def _kv_avals(out_shapes):
    """(path, aval) pairs of the KV storage leaves in a step's returned
    cache tree — the int8-storage contract is checked on these."""
    leaves = jax.tree_util.tree_flatten_with_path(out_shapes)[0]
    found = []
    for path, leaf in leaves:
        name = ""
        for p in reversed(path):
            name = str(getattr(p, "key", getattr(p, "name", "")))
            if name:
                break
        if name in _KV_LEAF_NAMES:
            found.append(("/".join(str(getattr(p, "key", p))
                                   for p in path), leaf))
    return found


def _mesh(tp: int):
    if tp <= 1:
        return None
    if len(jax.devices()) < tp:
        raise RuntimeError(
            f"TP-{tp} audit needs {tp} devices "
            f"(run under XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={tp}, as `make audit` does)")
    return make_mesh((tp,), (manifest.TP_AXIS,))


def trace_lm_step(model, phase: str, paged: bool = False, tp: int = 1,
                  batch: int = 2, kv_len: int = 128,
                  prompt_len: int = 32):
    """Trace one full-plan model step abstractly.

    Returns ``(closed_jaxpr, kv_avals)`` where ``kv_avals`` are the
    (path, aval) pairs of the KV leaves the step returns.
    """
    from repro.parallel.context import sharding_context
    from repro.quant import kernel_mode

    mesh = _mesh(tp)
    qparams = _abstract_quantized(model, mesh=mesh)
    if phase == "decode":
        if paged:
            block_size = 16
            max_blocks = max(1, kv_len // block_size)
            cache = jax.eval_shape(
                lambda: model.init_paged_cache(
                    batch, num_blocks=batch * max_blocks + 1,
                    block_size=block_size, max_blocks=max_blocks,
                    kv_dtype="int8"))
        else:
            cache = jax.eval_shape(
                lambda: model.init_cache(batch, kv_len, kv_dtype="int8"))
        b = _decode_batch(model.cfg, batch)
        step = lambda p, bt, c: model.decode_step(p, bt, c)  # noqa: E731
        args = (qparams, b, cache)
    elif phase == "prefill":
        cache = jax.eval_shape(
            lambda: model.init_cache(batch, kv_len, kv_dtype="int8"))
        b = _decode_batch(model.cfg, batch, steps=prompt_len)
        lengths = jax.ShapeDtypeStruct((batch,), jnp.int32)
        step = lambda p, bt, c, ln: model.prefill_padded(  # noqa: E731
            p, bt, c, ln)
        args = (qparams, b, cache, lengths)
    else:
        raise ValueError(f"unknown LM phase {phase!r}")

    ctx = sharding_context(mesh) if mesh is not None else _nullcontext()
    with kernel_mode(True), ctx:
        jaxpr = jax.make_jaxpr(step)(*args)
        out_shapes = jax.eval_shape(step, *args)
    return jaxpr, _kv_avals(out_shapes)


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


# ---------------------------------------------------------------------------
# Audit entry points
# ---------------------------------------------------------------------------
def audit_lm(arch: str, phase: str = "decode", paged: bool = False,
             tp: int = 1, kv_len: int = 128, reduced: bool = False,
             batch: int = 2) -> AuditReport:
    """Audit one LM arch x phase x layout cell of the contract matrix."""
    label = {"decode": "decode_paged" if paged else "decode_ring",
             "prefill": "prefill"}[phase]
    model = _build(arch, reduced)
    if not manifest.supports_full_plan(model):
        return AuditReport(arch, label, tp > 1, {}, {}, [],
                           skipped="no full-plan contract for this "
                                   "arch's mixers yet (ROADMAP item 3)")
    jaxpr, kv_avals = trace_lm_step(model, phase, paged=paged, tp=tp,
                                    kv_len=kv_len, batch=batch)
    expected = manifest.model_sites(model, phase, sharded=tp > 1,
                                    kv_len=kv_len if phase == "decode"
                                    else 0, paged=paged)
    sites = jt.pallas_sites(jaxpr)
    violations = []
    violations += passes.dispatch_audit(sites, expected)
    violations += passes.dtype_flow_audit(jaxpr, phase=phase,
                                          kv_avals=kv_avals)
    exp_coll = _expected_collectives(model) if tp > 1 else None
    violations += passes.collective_audit(jaxpr, sharded=tp > 1,
                                          expected=exp_coll)
    violations += passes.vmem_audit(sites)
    return AuditReport(arch, label, tp > 1, dict(expected),
                       dict(passes.classify(sites)), violations)


def _expected_collectives(model) -> Counter:
    total: Counter = Counter()
    for _spec, _count in model.groups:
        total += Counter(manifest.BLOCK_TP_COLLECTIVES)
    return total


def audit_dit(arch: str = "dit-xl-2", batch: int = 2) -> AuditReport:
    """Audit one DiT sampler step (the whole forward: the N blocks scan
    over stacked params, so one traced block body covers the model).
    ``dit-test`` is the registry's reduced config."""
    from repro.configs import get_dit_config
    from repro.models.dit import DiTModel
    from repro.quant import kernel_mode

    cfg = get_dit_config(arch)
    m = DiTModel(cfg)
    qparams = jax.eval_shape(lambda: m.quantize(m.init(_KEY)))
    c = cfg.in_channels
    hw = cfg.input_size
    x = jax.ShapeDtypeStruct((batch, c, hw, hw), jnp.float32)
    t = jax.ShapeDtypeStruct((batch,), jnp.int32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    with kernel_mode(True):
        jaxpr = jax.make_jaxpr(
            lambda p, a, b_, c_: m.forward(p, a, b_, c_))(qparams, x, t, y)
    expected = manifest.dit_sites(cfg)
    sites = jt.pallas_sites(jaxpr)
    violations = []
    violations += passes.dispatch_audit(sites, expected)
    violations += passes.dtype_flow_audit(jaxpr, phase="step")
    violations += passes.collective_audit(jaxpr, sharded=False)
    violations += passes.vmem_audit(sites)
    return AuditReport(arch, "step", False, dict(expected),
                       dict(passes.classify(sites)), violations)


# ---------------------------------------------------------------------------
# Retrace guard (pass 5) — the one dynamic audit
# ---------------------------------------------------------------------------
def audit_serving_retrace(arch: str = "gemma-2b") -> AuditReport:
    """Drive a small PagedServingEngine through every lifecycle
    transition — chunked prefill, continuous decode, eviction at
    completion, preemption on pool exhaustion, re-admission — then
    assert each jitted step function still holds exactly one trace.
    Runs real (reduced-config) compute, unlike the static passes."""
    import numpy as np
    from repro.serving.engine import PagedServingEngine, Request

    model = _build(arch, reduced=True)
    params = model.quantize(model.init(_KEY))
    eng = PagedServingEngine(model, params, n_slots=3, max_len=64,
                             prefill_bucket=16, prefill_chunk=8,
                             block_size=4, num_blocks=24)
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(1, 100, size=n),
                    max_new_tokens=6)
            for i, n in enumerate((5, 19, 11, 3, 17, 7))]
    for r in reqs[:4]:
        eng.submit(r)
    for step in range(80):
        eng.step()
        if step == 3:
            for r in reqs[4:]:
                eng.submit(r)
        if all(r.done for r in reqs):
            break
    violations = []
    if not all(r.done for r in reqs):
        violations.append(Violation(
            "retrace", "scenario_stalled", arch,
            "audit scenario did not complete all requests"))
    if eng.stats.preemptions + eng.stats.prefill_chunks == 0:
        violations.append(Violation(
            "retrace", "scenario_too_easy", arch,
            "audit scenario exercised neither chunked prefill nor "
            "preemption — the guard proved nothing"))
    violations += passes.retrace_audit(
        {"prefill_chunk": eng._prefill_chunk_fn,
         "decode_masked": eng._decode_masked,
         "scrub": eng._scrub},
        limits={"prefill_chunk": 1, "decode_masked": 1, "scrub": 1})
    return AuditReport(arch, "serving_retrace", False, {}, {}, violations)


# ---------------------------------------------------------------------------
# Registry matrix
# ---------------------------------------------------------------------------
def full_plan_archs() -> list:
    """Every registered LM arch whose layer groups all have a contract
    entry (the `make audit` matrix rows)."""
    from repro.configs import ARCH_IDS
    out = []
    for arch in ARCH_IDS:
        try:
            if manifest.supports_full_plan(_build(arch, reduced=False)):
                out.append(arch)
        except NotImplementedError:
            continue
    return out

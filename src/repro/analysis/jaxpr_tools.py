"""Jaxpr introspection for the execution-contract auditor.

Everything the audit passes know about a traced step comes through this
module: a duck-typed recursive equation walker (``pjit``/``scan``/
``cond``/``shard_map``/``custom_vjp`` all carry their sub-jaxpr in
``eqn.params``), plus extractors for the facts the contract is stated
over — Pallas kernel names, BlockSpec block shapes, scalar-prefetch and
scratch operands, and collective ops with their mesh axes.

The extractors are deliberately defensive (``getattr`` with fallbacks):
jax moves these internals between minor versions, and an auditor that
crashes on a field rename is worse than one that reports a little less
source info.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterator

import jax.numpy as jnp

# Collective primitives that may appear under a shard_map body.  psum2
# is what jax.lax.psum lowers to on some versions; both spellings are
# normalized to "psum" in CollectiveInfo.
COLLECTIVE_PRIMS = {
    "psum": "psum", "psum2": "psum", "pmax": "pmax", "pmin": "pmin",
    "all_gather": "all_gather", "all_to_all": "all_to_all",
    "ppermute": "ppermute", "pbroadcast": "pbroadcast",
    "reduce_scatter": "reduce_scatter", "psum_scatter": "psum_scatter",
}


def iter_eqns(jx, into_pallas: bool = True) -> Iterator[Any]:
    """Yield every eqn of ``jx`` (a Jaxpr or anything with ``.eqns``),
    recursing into sub-jaxprs.  ``into_pallas=False`` stops at
    ``pallas_call`` boundaries so the caller sees only XLA-level ops —
    the dtype-flow pass uses that to tell "inside a kernel" from
    "escaped to XLA"."""
    for eqn in jx.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_pallas:
            continue
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield from iter_eqns(v.jaxpr, into_pallas)
            elif hasattr(v, "eqns"):
                yield from iter_eqns(v, into_pallas)


def unwrap(jx):
    """Accept a ClosedJaxpr, Jaxpr, or anything wrapping one."""
    return getattr(jx, "jaxpr", jx)


def _kernel_debug_info(eqn):
    return getattr(eqn.params.get("jaxpr"), "debug_info", None)


def kernel_name(eqn) -> str:
    """The Pallas kernel name of a ``pallas_call`` eqn: the explicit
    ``name=`` when one was given, else the kernel function's name."""
    name = eqn.params.get("name")
    if name:
        return name
    info = _kernel_debug_info(eqn)
    return getattr(info, "func_name", None) or eqn.primitive.name


def src_info(eqn) -> str:
    """Best-effort ``kernel_fn at file:line`` string for reports."""
    info = _kernel_debug_info(eqn)
    return getattr(info, "func_src_info", None) or kernel_name(eqn)


@dataclasses.dataclass(frozen=True)
class BlockInfo:
    """One BlockSpec-mapped operand (input or output) of a pallas_call."""
    block_shape: tuple      # mapped/squeezed dims normalized to 1
    array_shape: tuple
    dtype: Any
    in_vmem: bool = True    # False: left in HBM for the kernel's own DMAs

    @property
    def nbytes(self) -> int:
        if not self.in_vmem:
            return 0
        return math.prod(self.block_shape) * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class PallasSite:
    """Everything the audit passes need about one pallas_call eqn."""
    kernel: str                  # kernel function name
    src: str                     # "kernel_fn at file:line"
    blocks: tuple                # BlockInfo per mapped operand (in + out)
    scratch_bytes: int           # VMEM scratch allocations
    num_prefetch: int            # scalar-prefetch operand count
    out_dtypes: tuple            # outvar dtypes
    eqn: Any = dataclasses.field(repr=False, compare=False, default=None)

    @property
    def vmem_bytes(self) -> int:
        """Static VMEM footprint: all mapped blocks + scratch.  This is
        the single-buffered figure; the manifest budget decides what
        head-room to demand for pipelining."""
        return sum(b.nbytes for b in self.blocks) + self.scratch_bytes


def _block_dim(d) -> int:
    """One block dim as an int: plain ints, ``Blocked(block_size=n)``,
    and squeezed/mapped dims (``None`` / ``Squeezed``) as 1."""
    if isinstance(d, int):
        return d
    size = getattr(d, "block_size", None)
    return size if isinstance(size, int) else 1


def _block_infos(eqn) -> tuple:
    gm = eqn.params.get("grid_mapping")
    out = []
    for bm in getattr(gm, "block_mappings", ()) or ():
        aval = bm.array_aval
        out.append(BlockInfo(
            block_shape=tuple(_block_dim(d) for d in bm.block_shape),
            array_shape=tuple(aval.shape),
            dtype=aval.dtype,
            in_vmem=str(getattr(bm.block_aval, "memory_space", None)
                        or "vmem") == "vmem"))
    return tuple(out)


def _scratch_bytes(eqn) -> int:
    """VMEM scratch bytes; DMA semaphores and SMEM scalars take none."""
    gm = eqn.params.get("grid_mapping")
    return sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
               for a in getattr(gm, "scratch_avals", ()) or ()
               if str(getattr(a, "memory_space", None) or "vmem") == "vmem")


def pallas_sites(jx) -> list:
    """All pallas_call sites in a (Closed)Jaxpr, in trace order."""
    sites = []
    for eqn in iter_eqns(unwrap(jx)):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        sites.append(PallasSite(
            kernel=kernel_name(eqn),
            src=src_info(eqn),
            blocks=_block_infos(eqn),
            scratch_bytes=_scratch_bytes(eqn),
            num_prefetch=getattr(gm, "num_index_operands", 0) or 0,
            out_dtypes=tuple(v.aval.dtype for v in eqn.outvars),
            eqn=eqn))
    return sites


@dataclasses.dataclass(frozen=True)
class CollectiveInfo:
    op: str                      # normalized primitive name ("psum", ...)
    axes: tuple                  # mesh axis names
    dtypes: tuple                # operand dtypes

    @property
    def key(self) -> tuple:
        return (self.op, self.axes)


def _collective_axes(eqn) -> tuple:
    p = eqn.params
    axes = p.get("axes")
    if axes is None:
        axes = p.get("axis_name")
    if axes is None:
        axes = p.get("axis_index_groups")
    if axes is None:
        return ()
    if isinstance(axes, (str, int)):
        return (axes,)
    return tuple(axes)


def collectives(jx) -> list:
    """All collective eqns (outside pallas kernels) with their axes."""
    out = []
    for eqn in iter_eqns(unwrap(jx), into_pallas=False):
        norm = COLLECTIVE_PRIMS.get(eqn.primitive.name)
        if norm is None:
            continue
        out.append(CollectiveInfo(
            op=norm, axes=_collective_axes(eqn),
            dtypes=tuple(getattr(v.aval, "dtype", None)
                         for v in eqn.invars)))
    return out


# Eqns a wide-integer accumulator may flow through on its way to the
# cross-shard psum without counting as an "escape": pure layout ops plus
# sharding annotations.  convert_element_type is transparent only while
# the value stays integer — a float conversion before the psum would
# break the exactness contract and is flagged at the origin kernel.
_TAINT_TRANSPARENT = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "slice", "copy", "sharding_constraint",
})


def _call_subjaxprs(eqn) -> list:
    """Sub-jaxprs of a call-like eqn (pjit/scan/cond/shard_map/...).
    ``cond`` carries a tuple of branches; everything else a single
    (Closed)Jaxpr."""
    subs = []
    for v in eqn.params.values():
        cands = v if isinstance(v, (tuple, list)) else (v,)
        for cand in cands:
            sub = getattr(cand, "jaxpr", None)
            if sub is None and hasattr(cand, "eqns"):
                sub = cand
            if sub is not None and hasattr(sub, "eqns"):
                subs.append(sub)
    return subs


def int32_escapes(jx) -> list:
    """Pallas eqns whose int32/int16 outvars escape to XLA without being
    consumed by a ``psum`` (the TP row-parallel exact-accumulation path
    is the one sanctioned escape: partial int32 accumulators cross the
    kernel boundary precisely so the cross-shard sum stays exact).

    The accumulator typically crosses several jaxpr levels between the
    kernel and the psum (the pallas_call sits inside pjit bodies, the
    psum in the shard_map body above), so this is a taint propagation:
    wide-int pallas outvars are tainted, taint flows through layout ops
    and positionally across call boundaries, a psum consumes it, and any
    other non-trivial consumer — or reaching the top-level outputs —
    flags the originating kernel."""
    wide = (jnp.int32, jnp.int16)
    bad: dict = {}   # id(origin eqn) -> eqn, insertion-ordered

    def walk(jaxpr, in_taint):
        """``in_taint`` aligns with ``jaxpr.invars``; returns taint
        aligned with ``jaxpr.outvars`` (origin eqn or None each)."""
        taint: dict = {}
        for v, t in zip(jaxpr.invars, in_taint):
            if t is not None:
                taint[id(v)] = t
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            hot = [taint.get(id(v)) for v in eqn.invars]
            if name == "pallas_call":
                for t in hot:
                    if t is not None:
                        bad.setdefault(id(t), t)
                for v in eqn.outvars:
                    if getattr(v.aval, "dtype", None) in wide:
                        taint[id(v)] = eqn
                continue
            if COLLECTIVE_PRIMS.get(name) == "psum":
                continue   # sanctioned consumption; outvars are clean
            subs = _call_subjaxprs(eqn)
            if subs:
                for sub in subs:
                    n = len(sub.invars)
                    tin = hot[-n:] if n <= len(hot) else \
                        [None] * (n - len(hot)) + hot
                    tout = walk(sub, tin)
                    m = min(len(tout), len(eqn.outvars))
                    for ov, t in zip(eqn.outvars[-m:], tout[-m:]):
                        if t is not None:
                            taint[id(ov)] = t
                continue
            live = [t for t in hot if t is not None]
            if not live:
                continue
            if name == "convert_element_type":
                dst = eqn.params.get("new_dtype")
                if dst is not None and jnp.issubdtype(dst, jnp.integer):
                    taint[id(eqn.outvars[0])] = live[0]
                else:
                    bad.setdefault(id(live[0]), live[0])
            elif name in _TAINT_TRANSPARENT:
                for ov in eqn.outvars:
                    taint[id(ov)] = live[0]
            else:
                for t in live:
                    bad.setdefault(id(t), t)
        return [taint.get(id(v)) for v in jaxpr.outvars]

    top = unwrap(jx)
    for t in walk(top, [None] * len(top.invars)):
        if t is not None:
            bad.setdefault(id(t), t)
    return list(bad.values())


def int8_dequant_leaks(jx) -> list:
    """XLA-level ``convert_element_type`` eqns taking int8 to a float
    dtype — a dequantized tensor materialized outside any kernel, i.e.
    the start of a quantize->dequantize->(re)quantize round trip.  The
    float->int8 direction (activation/KV quantization staged at the XLA
    level, e.g. the TP global row-quant) is part of the contract and is
    not flagged."""
    leaks = []
    for eqn in iter_eqns(unwrap(jx), into_pallas=False):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval.dtype
        dst = eqn.params.get("new_dtype")
        if src == jnp.int8 and dst is not None \
                and jnp.issubdtype(dst, jnp.floating):
            leaks.append(eqn)
    return leaks


def int8_xla_dots(jx) -> list:
    """XLA ``dot_general`` eqns consuming int8 — int8 tensors must only
    ever be contracted inside Pallas kernels."""
    return [e for e in iter_eqns(unwrap(jx), into_pallas=False)
            if e.primitive.name == "dot_general"
            and any(getattr(v.aval, "dtype", None) == jnp.int8
                    for v in e.invars)]

"""Profiler trace: capture a window and reduce it to numbers.

The reduction works on plain records so that it can be checked on a
small trace recorded on the CPU:

* device ops: ``(start_ns, end_ns, name)`` -- every operation that ran on
  the device, from the TPU planes' ``XLA Ops`` lines (on the CPU, the
  host events that carry an ``hlo_op`` stat);
* modules: ``(start_ns, end_ns, name)`` -- whole jitted programs;
* host spans: ``(start_ns, end_ns, name)`` -- the harness's own
  ``TraceAnnotation`` spans, named ``bench.*``.

Kernels are found by the stable names the program gives them: a Pallas
kernel's HLO instruction is named after its kernel function
(``cim_gemm_int8_fused.20``, ``decode_attention_paged.6``), and a jitted
step's module after its function (``jit_prefill_chunk(...)``).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field


@dataclass
class Trace:
    ops: list = field(default_factory=list)       # (start, end, text)
    modules: list = field(default_factory=list)   # (start, end, name)
    spans: list = field(default_factory=list)     # (start, end, name)
    n_devices: int = 1


CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


def op_name(name: str) -> str:
    """An HLO event's instruction name: ``%cim_gemm_int8_fused.20 = f32[..]
    custom-call(..)`` -> ``cim_gemm_int8_fused.20``."""
    return name.split(" = ", 1)[0].lstrip("%")


def family(name: str) -> str:
    """``cim_gemm_int8_fused.20`` -> ``cim_gemm_int8_fused``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def start(trace_dir) -> None:
    """Start the profiler: device ops and the harness's host spans (host
    level 1), no Python function tracing (it doubled the host's time per
    decode step)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    tr = Trace()
    n_tpu = sum(p.name.startswith("/device:TPU:") for p in pd.planes)
    tr.n_devices = max(1, n_tpu)
    for plane in pd.planes:
        on_tpu = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            lname = line.name
            for ev in line.events:
                t0 = float(ev.start_ns)
                t1 = t0 + float(ev.duration_ns)
                if on_tpu and lname == "XLA Ops":
                    tr.ops.append((t0, t1, op_name(ev.name)))
                elif on_tpu and lname == "XLA Modules":
                    tr.modules.append((t0, t1, ev.name))
                elif not on_tpu and ev.name.startswith("bench."):
                    tr.spans.append((t0, t1, ev.name))
                elif not n_tpu and plane.name.startswith("/host") \
                        and any(k == "hlo_op" for k, _ in ev.stats):
                    # CPU backend: XLA ops run on host threads
                    tr.ops.append((t0, t1, op_name(ev.name)))
    return tr


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    """Intervals cut to ``[lo, hi]``; those outside are dropped."""
    out = []
    for s, e, *rest in intervals:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            out.append((s2, e2, *rest))
    return out


def busy_ns(ops, lo: float, hi: float, n_devices: int = 1) -> float:
    """Union of device-op time inside ``[lo, hi]``, averaged over devices
    (ops of all devices are pooled, so the union bounds each one's)."""
    return sum(e - s for s, e in union(clip(ops, lo, hi))) / n_devices


def kernel_ns(ops, patterns, lo: float, hi: float) -> tuple[float, int]:
    """(summed duration, count) of the ops whose name holds any pattern."""
    total, n = 0.0, 0
    for s, e, text in clip(ops, lo, hi):
        if any(p in text for p in patterns):
            total += e - s
            n += 1
    return total, n


def module_ns(modules, pattern: str, lo: float, hi: float
              ) -> tuple[float, int]:
    total, n = 0.0, 0
    for s, e, name in clip(modules, lo, hi):
        if pattern in name:
            total += e - s
            n += 1
    return total, n


def top_ops(ops, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` op families that took the most device time, [name, s];
    loops and calls, which hold other ops, are left out."""
    acc: dict[str, float] = {}
    for s, e, name in clip(ops, lo, hi):
        fam = family(name)
        if fam.startswith(CONTAINERS):
            continue
        acc[fam] = acc.get(fam, 0.0) + (e - s)
    return [[n, t / 1e9] for n, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(ops, spans, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` longest device-idle gaps in ``[lo, hi]``, each named by
    the innermost harness span open at its middle, [name, s]."""
    busy = union(clip(ops, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = 0.5 * (s + e)
        inner = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        name = (min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner
                else "bench.outside_spans")
        out.append([name, (e - s) / 1e9])
    return out

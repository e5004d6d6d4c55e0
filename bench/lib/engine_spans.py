"""The engine's own profiler spans in a traced run.

The paged and diffusion engines name their host phases with
``engine.*`` spans (``repro.obs.tracing``): ``engine.step`` per call,
one span per phase inside it, ``engine.gc`` per garbage collection.
:func:`bench.lib.trace.load` keeps only the harness's ``bench.*``
spans, so the readers here take the engine's from the run's own trace
file: the ``.xplane.pb`` under the temporary directory whose
``bench.window`` span is the run's window, read while the run still
holds it.  A program without engine spans, or a run without a trace,
gives an empty list, and the readers then report nothing.

Records are ``(start_ns, end_ns, name)``, as in ``bench.lib.trace``.
"""
from __future__ import annotations

import bisect
import glob
import os
import tempfile

from bench.lib import trace

PREFIX = "engine."
NESTED = ("engine.release", "engine.gc")   # may open inside any phase

_cache: dict = {}


def load(trace_dir: str) -> tuple[list, list]:
    """``(engine spans, bench spans)`` of the newest ``.xplane.pb`` under
    ``trace_dir``, host planes only."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return [], []
    eng, bench = [], []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out = eng
                elif ev.name.startswith("bench."):
                    out = bench
                else:
                    continue
                t0 = float(ev.start_ns)
                out.append((t0, t0 + float(ev.duration_ns), ev.name))
    return eng, bench


def of_run(run) -> list:
    """The engine spans of ``run``'s trace, or ``[]``."""
    if run.trace is None:
        return []
    key = tuple(run.traced_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = _find(key)
    return _cache[key]


def _find(window) -> list:
    pattern = os.path.join(tempfile.gettempdir(), "bench-trace-*")
    for d in sorted(glob.glob(pattern), key=os.path.getmtime, reverse=True):
        eng, bench = load(d)
        if any((s, e) == window for s, e, n in bench
               if n == "bench.window"):
            return eng
    return []


def inside(spans, lo: float, hi: float, name: str | None = None) -> list:
    """Spans wholly inside ``[lo, hi]``, of one name if given."""
    return [sp for sp in spans if lo <= sp[0] and sp[1] <= hi
            and (name is None or sp[2] == name)]


def covered_ns(outer, others) -> float:
    """How much of ``outer`` the union of ``others`` covers; ``others``
    sorted by start."""
    starts = [sp[0] for sp in others]
    a = bisect.bisect_left(starts, outer[0])
    b = bisect.bisect_right(starts, outer[1])
    parts = trace.clip(others[a:b], outer[0], outer[1])
    return sum(e - s for s, e in trace.union(parts))


def innermost(spans, lo: float, hi: float) -> list:
    """``[lo, hi]`` cut into ``(start, end, name)`` pieces, each named by
    the innermost span open over it (the latest opened; the spans of one
    thread nest), or ``"outside"`` where none is."""
    events = sorted([(s, 1, -(e - s), i) for i, (s, e, _) in
                     enumerate(spans)]
                    + [(e, 0, 0, i) for i, (s, e, _) in enumerate(spans)])
    out, stack, t = [], [], lo
    for x, is_start, _, i in events:
        x = min(max(x, lo), hi)
        if x > t:
            out.append((t, x, spans[stack[-1]][2] if stack else "outside"))
            t = x
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
    if hi > t:
        out.append((t, hi, spans[stack[-1]][2] if stack else "outside"))
    return out


def idle_by_span(ops, spans, lo: float, hi: float) -> dict:
    """Device-idle seconds in ``[lo, hi]``, by the innermost span the
    host was in at the time."""
    busy = trace.union(trace.clip(ops, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out: dict[str, float] = {}
    j = 0
    for s, e, name in innermost(spans, lo, hi):
        while j < len(idle) and idle[j][1] <= s:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < e:
            ov = min(e, idle[k][1]) - max(s, idle[k][0])
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov / 1e9
            k += 1
    return out

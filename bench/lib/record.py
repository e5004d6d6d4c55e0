"""What a run leaves for the per-layer metric readers.

A driver fills one :class:`Run`; each reader in ``bench/metrics/`` takes
it and returns a number, or ``None`` when it finds nothing to read.
Times are host ``perf_counter`` seconds, except the trace's nanoseconds.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class Run:
    kind: str                         # "lm" | "dit"
    config: dict
    peaks: dict
    ops: object                       # bench.ops.<family>
    t_open: float = 0.0
    t_close: float = 0.0
    # LM: one entry per engine step in the window:
    #   {"t0", "t1", "chunks": [(n_valid, offset, last)], "ctx": [...]}
    # DiT: one entry per batch: {"t0", "t1", "rows", "evals", "images"}
    steps: list = field(default_factory=list)
    # LM: one entry per request due (open loop) or live (closed loop)
    # in the window: {"due", "admit", "first", "times", ...}
    requests: list = field(default_factory=list)
    trace: Optional[object] = None    # lib.trace.Trace
    traced: tuple = (0.0, 0.0)        # host seconds the trace covers
    traced_ns: tuple = (0.0, 0.0)     # the same, in the trace's clock

    def traced_steps(self) -> list:
        lo, hi = self.traced
        return [s for s in self.steps if s["t0"] >= lo and s["t1"] <= hi]

    def traced_work(self) -> list:
        """``{family: [(count, call)]}`` of every call in the trace; a
        ``None`` marks a step whose calls the driver could not count."""
        out = []
        for s in self.traced_steps():
            out.extend([None] if s["work"] is None else s["work"])
        return out

    def traced_s(self) -> float:
        return (self.traced_ns[1] - self.traced_ns[0]) / 1e9

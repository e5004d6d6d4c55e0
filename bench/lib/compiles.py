"""Count the programs JAX traces and compiles (or loads from its cache)
while a run measures: the window should hold none."""
from __future__ import annotations

import time

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileLog:
    def __init__(self):
        import jax.monitoring
        self.events: list[tuple[float, str, float, str]] = []
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event in EVENTS:
            self.events.append((time.perf_counter(), event.rsplit("/", 1)[1],
                                duration, str(kw.get("fun_name", ""))))

    def within(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] <= t1]

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on_event)

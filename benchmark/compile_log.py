"""Every XLA backend compilation of the process, from JAX's own
monitoring events, stamped with the host clock: what the warm-up waits
on (a stretch with none) and what ``compiles_in_window`` counts. After
``chip_smoke.CompileLog``."""

from __future__ import annotations

import threading
import time

COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: (function name, seconds, perf_counter at the end)
        self.events: list[tuple[str, float, float]] = []

    def install(self) -> None:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name != COMPILE:
            return
        with self._lock:
            self.events.append(
                (str(kw.get("fun_name", "?")), secs, time.perf_counter())
            )

    def last(self) -> float:
        """perf_counter of the newest compilation's end, or 0."""
        with self._lock:
            return self.events[-1][2] if self.events else 0.0

    def between(self, t0: float, t1: float) -> list[tuple[str, float, float]]:
        with self._lock:
            return [e for e in self.events if t0 <= e[2] < t1]

    def total_seconds(self) -> float:
        with self._lock:
            return sum(e[1] for e in self.events)

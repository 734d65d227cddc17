"""The benchmark's own spans: set-up phases on the host clock, kept in
memory, and host spans written into the profiler's trace so that they sit
on the device trace's clock."""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, t_start: float):
        self.t_start = t_start           # perf_counter at process start
        self.seconds: dict[str, float] = {}

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start

    @contextlib.contextmanager
    def span(self, name: str):
        """Time a set-up phase; phases of one name add up."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)


def host_span(name: str):
    """A host span on the profiler's clock (no cost worth naming when no
    trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)

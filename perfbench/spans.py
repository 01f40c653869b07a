"""The benchmark's own host spans: a wall clock it reads itself, and the
same spans written into the profiler's trace when one is being taken."""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple


class Clock:
    """Seconds since construction on ``time.perf_counter``.  Handed to the
    scheduler as its clock (``now()``), so request arrivals and the
    benchmark's timings share one origin; it never skips."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def skip_to(self, t: float) -> None:  # the scheduler's idle-skip hook
        raise RuntimeError("the benchmark's clock is the wall clock")


class Spans:
    """``with spans("tick"):`` records ``(name, start, end)`` on ``clock``
    and, while ``annotate`` is set, a ``pb:<name>`` profiler annotation."""

    def __init__(self, clock: Clock):
        self.clock = clock
        self.log: List[Tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("pb:" + name)
            ann.__enter__()
        t0 = self.clock.now()
        try:
            yield
        finally:
            t1 = self.clock.now()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.log.append((name, t0, t1))

    def totals(self, t_from: float = 0.0, t_to: float = float("inf")) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, a, b in self.log:
            if a >= t_from and b <= t_to:
                out[name] = out.get(name, 0.0) + (b - a)
        return out

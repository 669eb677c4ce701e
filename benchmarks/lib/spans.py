"""Host spans and counters of one run, kept in memory.

``Spans.span(name)`` records ``(name, start_s, end_s)`` on the run's clock
and, while the profiler is on, also writes a ``jax.profiler.TraceAnnotation``
named ``bench.<name>`` so that ``lib/xplane.py`` can say what the host was
doing in a device-idle gap. Counters are plain numbers the drivers set from
the program's own counts.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Tuple

from .xplane import HOST_PREFIX


class Spans:
    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.records: List[Tuple[str, float, float]] = []
        self.counters: Dict[str, float] = {}
        self.annotate = False       # the harness turns this on with the trace

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(HOST_PREFIX + name)
            ann.__enter__()
        t0 = self.clock()
        try:
            yield
        finally:
            self.records.append((name, t0, self.clock()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def durations(self, name: str) -> List[float]:
        return [b - a for n, a, b in self.records if n == name]

    def count(self, name: str, value: float) -> None:
        self.counters[name] = value

"""What a run leaves for the per-layer readers: spans, counters, samples
and the reduced device trace.

A reader (``bench/metrics/<name>.py``) is ``read(run) -> float | None``;
it returns ``None`` where the run holds nothing for it to read, and the
harness then leaves the metric out of the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np


class Spans:
    """Host-clock spans by name.  While the profiler runs, each span is
    also a ``TraceAnnotation``, so the device trace can say what the host
    was doing in each idle gap."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.annotate = False
        self.durations: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.enabled:
            yield
            return
        ann = None
        if self.annotate:
            from jax.profiler import TraceAnnotation
            ann = TraceAnnotation(
                name if name.startswith("bench.") else "bench." + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.durations.setdefault(name, []).append(
                time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)


@dataclasses.dataclass
class Run:
    """One run's readings, in seconds and counts."""
    config: dict
    traffic: dict
    peaks: dict
    devices: list
    spans: Spans = dataclasses.field(default_factory=Spans)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    values: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    trace: Optional[object] = None          # benchlib.trace.Reduced

    @property
    def chips(self) -> int:
        return len(self.devices)

    def pctl(self, name: str, q: float, scale: float = 1e3
             ) -> Optional[float]:
        """Percentile ``q`` of a span's durations or a sample list, in ms
        by default; None where nothing was recorded."""
        xs = self.spans.durations.get(name) or self.values.get(name)
        if not xs:
            return None
        return float(np.percentile(xs, q)) * scale

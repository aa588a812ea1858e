"""The device trace of a traced run, reduced to what the metrics read.

A traced run profiles its whole window.  The
profiler's ``.xplane.pb`` holds one plane per chip (``/device:TPU:n``)
whose ``XLA Ops`` line has an event per operation run, and the host plane
whose threads carry the benchmark's ``bench.*`` annotations on the same
clock.  From them:

- busy: the union of each chip's operation intervals, averaged over chips;
- the window: from the first ``bench.*`` span or operation to the last;
- time by operation name (the HLO instruction's name without its numeric
  suffix), each operation's own time without the operations nested in it
  (a ``while`` loop's body), averaged over chips;
- idle gaps: the stretches of the window with no operation on a chip,
  each put down to the innermost ``bench.*`` span running on the host at
  its midpoint (``host.other`` where none was).
"""
from __future__ import annotations

import dataclasses
import glob
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
SUFFIX = re.compile(r"(\.\d+)+$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    ops_s: Dict[str, float]                # name -> seconds per chip
    op_counts: Dict[str, int]              # name -> events per chip
    gaps_s: Dict[str, float]               # host span -> idle seconds

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.ops_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.gaps_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def op_seconds(self, pattern: str) -> Tuple[float, int]:
        """Seconds per chip and events per chip of the operations whose
        name matches ``pattern``."""
        rx = re.compile(pattern)
        s = sum(v for k, v in self.ops_s.items() if rx.search(k))
        n = sum(v for k, v in self.op_counts.items() if rx.search(k))
        return s, n


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``: the HLO
    instruction's name without its numeric suffix."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    return SUFFIX.sub("", name)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce_profile(pd, prefix: str = "bench.") -> Reduced:
    """``pd``: a ``jax.profiler.ProfileData``."""
    devices = [pl for pl in pd.planes if pl.name.startswith("/device:TPU:")]
    host_spans: List[Tuple[float, float, str]] = []
    for pl in pd.planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    host_spans.append((e.start_ns, e.end_ns, e.name))
    ops: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    busy = 0.0
    gaps: Dict[str, float] = {}
    lo, hi = float("inf"), float("-inf")
    per_dev = []
    for pl in devices:
        evs = sorted(((e.start_ns, -e.duration_ns, op_name(e.name))
                      for ln in pl.lines if ln.name == OPS_LINE
                      for e in ln.events))
        _self_times(evs, ops, counts)
        u = _union([(a, a - d) for a, d, _ in evs])
        per_dev.append(u)
        if u:
            lo, hi = min(lo, u[0][0]), max(hi, u[-1][1])
        busy += sum(b - a for a, b in u) * 1e-9
    # the window: from the first bench span or operation to the last, so
    # the profiler's own start and stop are not counted as idle
    w_lo = min([lo] + [s[0] for s in host_spans])
    w_hi = max([hi] + [s[1] for s in host_spans])
    win = max(w_hi - w_lo, 0.0) * 1e-9
    host_spans.sort()
    for u in per_dev:
        edges = [(w_lo, w_lo)] + u + [(w_hi, w_hi)]
        idle = [(a1, b0) for (_, a1), (b0, _) in zip(edges, edges[1:])
                if b0 > a1]
        _attribute(idle, host_spans, gaps)
    nd = max(len(devices), 1)
    return Reduced(
        window_s=win, busy_s=busy / nd, chips=len(devices),
        ops_s={k: v / nd for k, v in ops.items()},
        op_counts={k: v // nd for k, v in counts.items()},
        gaps_s={k: v / nd for k, v in gaps.items()})


def _self_times(evs, ops: Dict[str, float], counts: Dict[str, int]) -> None:
    """Seconds by operation name, each operation's own: an operation that
    runs others inside it (a ``while`` loop's body) is charged only the
    time its children do not cover.  ``evs``: (start, -duration, name),
    sorted."""
    stack: List[Tuple[float, str]] = []        # (end, name) of open ops
    for start, neg, name in evs:
        dur = -neg
        while stack and stack[-1][0] <= start:
            stack.pop()
        ops[name] = ops.get(name, 0.0) + dur * 1e-9
        counts[name] = counts.get(name, 0) + 1
        if stack:
            parent = stack[-1][1]
            ops[parent] -= dur * 1e-9
        stack.append((start + dur, name))


def _attribute(idle, host_spans, gaps: Dict[str, float]) -> None:
    """Add each idle stretch to the innermost host span running at its
    midpoint; both lists are sorted by start, so one sweep does."""
    active: List[Tuple[float, float, str]] = []
    j = 0
    for a, b in idle:
        mid = 0.5 * (a + b)
        while j < len(host_spans) and host_spans[j][0] <= mid:
            active.append(host_spans[j])
            j += 1
        active = [sp for sp in active if sp[1] >= mid]
        best = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        key = best[2] if best else "host.other"
        gaps[key] = gaps.get(key, 0.0) + (b - a) * 1e-9


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


class Profiler:
    """Profiles a whole window: started before it opens and stopped after
    it closes, so neither costs the window a stall.  The trace goes to a
    temporary directory that is removed once it is reduced."""

    def __init__(self, spans):
        self.spans = spans
        self.dir: Optional[str] = None
        self.reduced: Optional[Reduced] = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self.spans.annotate = True

    def stop(self) -> None:
        import jax
        if self.dir is None:
            return
        jax.profiler.stop_trace()
        self.spans.annotate = False
        try:
            path = glob.glob(f"{self.dir}/plugins/profile/*/*.xplane.pb")[0]
            self.reduced = reduce_file(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None

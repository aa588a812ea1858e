"""Arithmetic the per-layer readers share.  Each returns None where the
run holds nothing to read, never 0 for a share it could not measure."""
from __future__ import annotations

from typing import Optional


def idle_share(run) -> Optional[float]:
    """Percent of the traced window with no operation on the chip."""
    tr = run.trace
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def step_mfu(run) -> Optional[float]:
    """The model FLOPs the window's work needed, over the window's time
    times the chips' bf16 peak, in percent."""
    flops = run.counters.get("flops")
    secs = run.counters.get("window_s")
    peak = run.peaks.get("bf16_flops")
    if not flops or not secs or not peak:
        return None
    return 100.0 * flops / (secs * run.chips * peak)


def roofline(run, pattern: str, bytes_key: str) -> Optional[float]:
    """A memory-bound kernel's share of its roofline over the traced
    window: the least time its bytes need at the published HBM peak, over
    the time its operations took on the chip, in percent."""
    tr = run.trace
    need_b = run.counters.get(bytes_key, 0.0)
    if tr is None or not need_b or not run.peaks:
        return None
    secs, _ = tr.op_seconds(pattern)
    if secs <= 0:
        return None
    return 100.0 * need_b / run.peaks["hbm_bytes_per_s"] / secs

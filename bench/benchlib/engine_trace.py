"""The serving engine's own spans, read against the device trace.

The program's tracer (``repro.obs.trace.Tracer``, handed to the engine)
keeps each live span in its ring on the engine's clock and, under the
profiler, opens a host annotation ``repro.<span>`` around it.  From a
traced serve window this gives:

- the program's idle split: ``trace.reduce_profile(pd, prefix=PREFIX)
  .gaps_s`` puts each of the same idle stretches down to the innermost
  ``repro.*`` or ``bench.*`` annotation at its midpoint (the bench's
  spans wrap the model calls and the CF head inside the engine's);
- ``readings``: the per-layer numbers these spans and the engine's
  counters feed (``engine.*``, ``cf.host_idle_share``), each ``None``
  where the run holds no engine trace or the tracer's ring dropped events.

Nothing in ``bench/run.py`` calls this yet (see ``PERF.md``, open
questions); ``bench/tools/program_split.py`` runs a cell with it.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

import numpy as np

from benchlib import trace

PREFIX = ("repro.", "bench.")
# the model calls, the engine's spans and the bench's own around them:
# what the engine's own host time is not
MODEL_SPANS = ("repro.model.prefill", "repro.decode_step",
               "bench.model.prefill", "bench.model.decode_step")
# the CF head: the engine's spans and the bench's one around ``score``
CF_SPANS = ("repro.cf.", "bench.cf.")


def span_ms(events: Iterable[Dict], name: str) -> List[float]:
    """Durations of the live spans ``name``, in ms."""
    return [e["dur"] * 1e3 for e in events
            if e.get("ph") == "X" and e["name"] == name]


def _p50(xs: List[float]) -> Optional[float]:
    return float(np.percentile(xs, 50)) if xs else None


def readings(tracer, ticks: int, host_syncs: int,
             reduced: Optional[trace.Reduced],
             gaps: Optional[Dict[str, float]]) -> Dict[str, Optional[float]]:
    """The six numbers of the engine's own spans and counters over one
    window; ``tracer``: the window engine's (None where it had none),
    ``ticks`` / ``host_syncs``: its counters' growth over the window,
    ``reduced`` / ``gaps``: the traced window's reduction, and its idle
    split by :data:`PREFIX`.  The engine's host share is the idle time under
    its ``repro.*`` spans other than the model calls and the CF head."""
    out: Dict[str, Optional[float]] = dict.fromkeys(
        ("engine.host_idle_share", "cf.host_idle_share",
         "engine.host_syncs_per_tick", "engine.prefill_ms_p50",
         "engine.decode_step_ms_p50", "engine.cf_score_ms_p50"))
    if tracer is None or tracer.dropped > 0:
        return out
    ev = tracer.events
    out["engine.prefill_ms_p50"] = _p50(span_ms(ev, "model.prefill"))
    out["engine.decode_step_ms_p50"] = _p50(span_ms(ev, "decode_step"))
    out["engine.cf_score_ms_p50"] = _p50(span_ms(ev, "cf.lookup"))
    if ticks > 0:
        out["engine.host_syncs_per_tick"] = host_syncs / ticks
    if (reduced is not None and gaps is not None and reduced.window_s > 0
            and reduced.busy_s > 0):
        cf = sum(v for k, v in gaps.items() if k.startswith(CF_SPANS))
        host = sum(v for k, v in gaps.items()
                   if k.startswith("repro.") and not k.startswith(CF_SPANS)
                   and k not in MODEL_SPANS)
        out["cf.host_idle_share"] = 100.0 * cf / reduced.window_s
        out["engine.host_idle_share"] = 100.0 * host / reduced.window_s
    return out

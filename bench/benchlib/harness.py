"""One benchmark run: set-up, the measured window, the per-layer readers,
the correctness check, and the result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with its
limit.  The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from typing import Dict, Optional

from benchlib import device, spec
from benchlib.runlog import Run


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def limits_met(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in checks.values())


def per_layer(cell: spec.Cell, run: Run) -> Dict[str, Dict]:
    out = {}
    for m in cell.per_layer:
        v = cell.readers[m.name](run)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out


def _serve(cell, args, run, counter, t0):
    from benchlib import check, serve, trace
    c, mix = cell.config, cell.traffic
    t_cell = time.perf_counter()
    s = serve.build(c, mix, args.seed)
    t1 = time.perf_counter()
    serve.warm(s)
    setup_s = time.perf_counter() - t0
    print(f"setup: start {t_cell - t0:.2f} s, build {t1 - t_cell:.2f} s, "
          f"warm-up {t0 + setup_s - t1:.2f} s", file=sys.stderr)
    prof = trace.Profiler(run.spans) if args.trace else None
    win = serve.run_window(s, args.seconds, spans=run.spans if args.trace
                           else None, counter=counter, trace=prof)
    memory = device.memory_peak(run.devices)
    serve.fill_run(run, win)
    run.trace = prof.reduced if prof else None
    numbers = check.serve_numbers(s, win, mix["check_sample"],
                                  mix["check_group"])
    return dict(setup_s=setup_s, metrics=serve.end_to_end(win),
                attempted=win.n_window, failed=win.failed, memory=memory,
                numbers=numbers)


def _train(cell, args, run, counter, t0):
    from benchlib import train, trace
    t_cell = time.perf_counter()
    t = train.build(cell.config, cell.traffic, args.seed)
    t1 = time.perf_counter()
    readings = train.first_steps(t)
    setup_s = time.perf_counter() - t0
    print(f"setup: start {t_cell - t0:.2f} s, build {t1 - t_cell:.2f} s, "
          f"first three steps {t0 + setup_s - t1:.2f} s", file=sys.stderr)
    prof = trace.Profiler(run.spans) if args.trace else None
    win = train.run_window(t, args.seconds, spans=run.spans if args.trace
                           else None, counter=counter, trace=prof)
    memory = device.memory_peak(run.devices)
    train.fill_run(run, t, win)
    run.trace = prof.reduced if prof else None
    return dict(setup_s=setup_s,
                metrics={"train_tokens_per_s": win.tokens / win.elapsed},
                attempted=win.steps, failed=win.failed, memory=memory,
                numbers=train.check_numbers(t, readings))


KINDS = {"serve": _serve, "train": _train}


def main(argv=None, devices=None, cell: Optional[spec.Cell] = None,
         t0: Optional[float] = None) -> int:
    """``t0``: when the process started (``time.perf_counter``), so that
    ``setup_s`` counts the imports too.  ``devices``/``cell`` are for the
    tests, which run the rest of a run on the CPU at a small size; a real
    run looks for its chips itself."""
    t0 = time.perf_counter() if t0 is None else t0
    args = build_parser().parse_args(argv)
    if cell is None:
        cell = spec.load_cell(args.workload)
    devs = devices if devices is not None else device.require_tpu(cell.chips)
    if devices is None:
        device.enable_compile_cache()
    counter = device.CompileCounter()
    # the tests run on the CPU, which has no published peaks
    peaks = device.peaks(devs[0].device_kind) \
        if devs[0].platform == "tpu" else {}
    run = Run(config=cell.config, traffic=cell.traffic, peaks=peaks,
              devices=devs)
    out = KINDS[cell.traffic["kind"]](cell, args, run, counter, t0)
    checks = {k: {"value": v, "limit": cell.traffic["limits"][k]}
              for k, v in out["numbers"].items()}
    metrics = {k: {"value": float(v), "unit": _unit(cell, k)}
               for k, v in out["metrics"].items()}
    metrics["setup_s"] = {"value": out["setup_s"], "unit": "s"}
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": out["memory"]}
    result = {"correct": limits_met(checks),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if args.trace:
        result["metrics"] = per_layer(cell, run)
        tr = run.trace
        if tr is not None:
            dev["busy_s"] = tr.busy_s
            dev["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": tr.top_ops(),
                                   "idle_gaps": tr.top_gaps()}
    else:
        result["metrics"] = metrics
    result["device"] = dev
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


def _unit(cell: spec.Cell, name: str) -> str:
    for m in cell.end_to_end:
        if m.name == name:
            return m.unit
    raise KeyError(f"{name} is not an end-to-end metric of {cell.name}")

"""One traced run of a serve cell with the engine's own tracer on.

    python bench/tools/program_split.py --workload <cell> --seed <n> \
        --seconds <s> [--out DIR]

Runs exactly what ``bench/run.py --trace 1`` runs (and prints its result
line), except that the window's engine gets an enabled
``repro.obs.trace.Tracer`` sized to hold the whole window, and the
profile is reduced a second time by the engine's ``repro.*`` spans
(``benchlib.engine_trace``).  The last line of standard output is one
JSON object: the engine's readings, its counters, the idle split by
program span, and the bench's own spans' medians beside them; with
``--out`` it is also written to ``DIR/<cell>.<seed>.json``.  Needs the TPU.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(BENCH.parent / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CAPACITY = 1 << 21      # events: a 51 s serve window writes about 40k


def split(workload: str, seed: int, seconds: float, devices=None,
          cell=None, t0=None) -> dict:
    """The traced run; ``devices`` / ``cell`` as ``harness.main`` takes
    them (the tests run a small cell on the CPU)."""
    import jax

    from benchlib import engine_trace, harness, serve, trace
    from repro.obs import Tracer

    state = {"window": False, "engine": None, "gaps": None, "run": None}
    saved = (serve._engine, serve.run_window, trace.reduce_file,
             harness.per_layer)
    make_engine, run_window, reduce, per_layer = saved

    def window_engine(s, clock):
        if not state["window"]:
            return make_engine(s, clock)            # the warm-up's engine
        from repro.serving import ServingEngine
        eng = ServingEngine(s.backend, s.ecfg, clock=clock, cf_head=s.head,
                            tracer=Tracer(capacity=CAPACITY))
        state["engine"] = eng
        return eng

    def traced_window(*a, **kw):
        state["window"] = True
        try:
            return run_window(*a, **kw)
        finally:
            state["window"] = False

    def reduce_file(path):
        """The bench's reduction, and the idle split by the engine's spans
        beside it."""
        pd = jax.profiler.ProfileData.from_file(path)
        state["gaps"] = trace.reduce_profile(
            pd, prefix=engine_trace.PREFIX).gaps_s
        return reduce(path)

    def kept_per_layer(c, run):
        state["run"] = run
        return per_layer(c, run)

    serve._engine, serve.run_window = window_engine, traced_window
    trace.reduce_file, harness.per_layer = reduce_file, kept_per_layer
    try:
        harness.main(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "1"],
                     devices=devices, cell=cell, t0=t0)
    finally:
        (serve._engine, serve.run_window, trace.reduce_file,
         harness.per_layer) = saved
    eng, run = state["engine"], state["run"]
    tracer, tr = eng.tracer, run.trace
    ev = tracer.events
    return {
        "workload": workload, "seed": seed,
        "readings": engine_trace.readings(tracer, eng.ticks, eng.host_syncs,
                                          tr, state["gaps"]),
        "dropped": tracer.dropped, "events": len(ev),
        "ticks": eng.ticks, "host_syncs": eng.host_syncs,
        "decode_steps": eng.decode_steps, "prefills": eng.prefills,
        "window_s": tr.window_s if tr else None,
        "busy_s": tr.busy_s if tr else None,
        "bench_gaps": tr.top_gaps(20) if tr else [],
        "program_gaps": sorted((state["gaps"] or {}).items(),
                               key=lambda kv: -kv[1]),
        "bench_p50_ms": {k: run.pctl(k, 50) for k in
                         ("model.prefill", "model.decode_step", "cf.score")},
        "program_p50_ms": {
            k: engine_trace._p50(engine_trace.span_ms(ev, k))
            for k in sorted({e["name"] for e in ev if e.get("ph") == "X"})},
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()
    out = split(args.workload, args.seed, args.seconds, t0=T0)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(f"{args.out}/{args.workload}.{args.seed}.json", "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

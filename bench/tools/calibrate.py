"""Find what a cell's fixed numbers rest on, on the chip, in one process.

    python bench/tools/calibrate.py sweep <cell> <seed> <seconds> RATE [RATE ...]
    python bench/tools/calibrate.py readings <cell> <seconds> <n_control> SEED [SEED ...]

``sweep`` (serving cells): one set-up, then a window at each offered rate;
prints per rate the requests offered and completed per second, the TTFT,
queue-wait and TPOT tails and the tokens per second, from which the knee
(the highest rate sustained with no growing queue) is read.

``readings``: for each seed a whole set-up, a window of ``seconds`` and
the check's numbers for the program; for the first ``n_control`` seeds
also the control's (the reference at the precision below the
configuration's).  The limits in the traffic file are set from these;
serving readings give the mean of each difference beside its widest, and
training readings the faults planted in the reference and, on a cell of
several chips, the exchange between them left out of the program
(``bench/tools/faults.py``), which needs a second build of the step.
Each result is one JSON line on standard output.
"""
import json
import pathlib
import sys
import time
import traceback

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parent / "src"), str(HERE / "tools")]


def sweep(cell, seed, seconds, rates, counter):
    import numpy as np
    from benchlib import serve
    from benchlib.runlog import Spans
    t0 = time.perf_counter()
    s = serve.build(cell.config, cell.traffic, seed)
    serve.warm(s)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for r in rates:
        spans = Spans()
        counter.count = 0
        win = serve.run_window(s, seconds, spans=spans, counter=counter,
                               rate=r)
        recs = win.records.values()
        done = [x for x in recs if x.finished is not None]
        in_win = [x for x in done if x.finished <= win.elapsed]
        qw = [x.admitted - x.arrival for x in recs if x.admitted is not None]
        e2e = serve.end_to_end(win)
        print(json.dumps({
            "rate": r, "offered": win.n_window / seconds,
            "completed_in_window_per_s": len(in_win) / win.elapsed,
            "failed": win.failed, "requests": win.n_window,
            "ttft_p50_ms": float(np.percentile(
                serve.latencies(win)[0], 50)) * 1e3,
            **e2e,
            "queue_wait_p95_ms": float(np.percentile(qw, 95)) * 1e3,
            "late_p95_ms": float(np.percentile(win.late, 95)) * 1e3,
            "decode_ms_p50": float(np.median(
                spans.durations["model.decode_step"])) * 1e3,
            "prefill_ms_p50": float(np.median(
                spans.durations["model.prefill"])) * 1e3,
            "cf_ms_p50": float(np.median(spans.durations["cf.score"])) * 1e3,
            "decode_steps": win.counts["decode_steps"],
            "window_compiles": win.compiles,
            "drain_s": max(x.finished for x in done) - seconds,
        }), flush=True)


def summary(d):
    """Widest and mean of each kind of difference, and the share of
    served tokens that are the reference's best."""
    import numpy as np
    out = {f"{k}_max": float(v.max()) for k, v in d.items()}
    out.update({f"{k}_mean": float(v.mean()) for k, v in d.items()})
    out["best_share"] = float(np.mean(d["gap"] == 0))
    return out


def readings(cell, seconds, n_control, seeds, counter):
    from benchlib import check, serve, train
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        try:
            if cell.traffic["kind"] == "serve":
                s = serve.build(cell.config, cell.traffic, seed)
                serve.warm(s)
                setup = time.perf_counter() - t0
                win = serve.run_window(s, seconds, counter=counter)
                mix = cell.traffic
                prog = summary(check.serve_diffs(
                    s, win, mix["check_sample"], mix["check_group"]))
                ctrl = (summary(check.serve_diffs(
                    s, win, mix["check_sample"], mix["check_group"],
                    control=True)) if i < n_control else None)
                extra = serve.end_to_end(win)
                del s, win
            else:
                t = train.build(cell.config, cell.traffic, seed)
                prog_r = train.first_steps(t)
                setup = time.perf_counter() - t0
                win = train.run_window(t, seconds, counter=counter)
                extra = {"train_tokens_per_s": win.tokens / win.elapsed}
                prog = train.check_numbers(t, prog_r)
                ctrl = None
                if i < n_control:
                    ctrl = train.fault_numbers(cell.config, cell.traffic,
                                               seed, t.batches, t.devices)
                    if cell.chips > 1:
                        ctrl["exchange_left_out"] = planted(
                            cell, seed, "exchange_left_out")
                del t
            print(json.dumps({"seed": seed, "setup_s": setup,
                              "program": prog, "control": ctrl, **extra}),
                  flush=True)
        except Exception:
            traceback.print_exc()
            print(json.dumps({"seed": seed, "error": True}), flush=True)


def planted(cell, seed, fault):
    """The check's numbers of the program with ``fault`` planted in its
    step, built anew, through its first three steps."""
    import faults
    import pytest
    from benchlib import train
    mp = pytest.MonkeyPatch()
    try:
        faults.TRAIN[fault](mp)
        t = train.build(cell.config, cell.traffic, seed)
        return train.check_numbers(t, train.first_steps(t))
    finally:
        mp.undo()


def main(argv) -> int:
    from benchlib import device, spec
    mode, name = argv[0], argv[1]
    cell = spec.load_cell(name)
    device.require_tpu(cell.chips)
    device.enable_compile_cache()
    counter = device.CompileCounter()
    if mode == "sweep":
        sweep(cell, int(argv[2]), float(argv[3]),
              [float(r) for r in argv[4:]], counter)
    else:
        readings(cell, float(argv[2]), int(argv[3]),
                 [int(x) for x in argv[4:]], counter)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))

"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine holding the chips the cell
asks for.  Prints one JSON result as its last line; see
``bench/benchlib/harness.py``.  Without a TPU, or without the program
beside it, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
SRC = HERE.parent / "src"

if __name__ == "__main__":
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"bench: no program under {SRC}")
    sys.path[:0] = [str(HERE), str(SRC)]
    from benchlib import harness
    raise SystemExit(harness.main(t0=T0))

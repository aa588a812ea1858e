"""Tests of the benchmark harness, run on the CPU at a small size:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They import the harness as ``bench/run.py`` does, with ``bench/`` and the
program's ``src/`` on the path."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

"""Whole runs of the four-chip training cell on four virtual CPU devices
at a small size: the harness's look for a chip is skipped, everything
after it runs, once sound and once with each fault that the cell can
have planted in its step (``bench/tools/faults.py``).  JAX fixes the
number of devices when it starts, so this runs in a process of its own:

    python bench/tests/multichip.py [SEED]

Prints one JSON line per run: the fault (``null`` when sound) and the
result line's ``correct`` and ``checks``."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"     # virtual devices, never a chip

import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(BENCH / "tests"),
                str(BENCH / "tools")]

import faults  # noqa: E402
import jax  # noqa: E402
import pytest  # noqa: E402
import tiny  # noqa: E402
from benchlib import harness  # noqa: E402

FAULTS = [None, "exchange_left_out", "half_batch", "state_unchanged"]


def run(seed: int, fault) -> dict:
    cell = tiny.cell("dp2tp2")
    out = io.StringIO()
    mp = pytest.MonkeyPatch()
    try:
        if fault:
            faults.TRAIN[fault](mp)
        with contextlib.redirect_stdout(out):
            harness.main(["--workload", cell.name, "--seed", str(seed),
                          "--seconds", "1", "--trace", "0"],
                         devices=jax.devices()[:cell.chips], cell=cell)
    finally:
        mp.undo()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    return {"fault": fault, "correct": res["correct"],
            "count": res["device"]["count"], "checks": res["checks"]}


if __name__ == "__main__":
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2 ** 33 + 17
    for f in FAULTS:
        print(json.dumps(run(seed, f)), flush=True)

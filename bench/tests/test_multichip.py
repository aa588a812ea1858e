"""The four-chip training cell, run whole on four virtual CPU devices in a
process of its own (``bench/tests/multichip.py``): sound, it is correct;
with each fault that the cell can have planted in its step (a state left
unchanged, half of the batch left out, the exchange between the chips
left out), it is not."""
import json
import pathlib
import subprocess
import sys

import pytest

SCRIPT = pathlib.Path(__file__).parent / "multichip.py"
FAULTS = [None, "exchange_left_out", "half_batch", "state_unchanged"]


@pytest.fixture(scope="module")
def four_chip_runs():
    out = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    runs = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    return {r["fault"]: r for r in runs}


@pytest.mark.parametrize("fault", FAULTS)
def test_four_chip_cell_is_correct_only_when_sound(four_chip_runs, fault):
    r = four_chip_runs[fault]
    assert r["count"] == 4
    assert r["correct"] is (fault is None), r["checks"]

"""The control comes out not correct: the reference one precision below
the configuration's (int8 matmuls for bfloat16: weights per output
channel, activations per row), put in the program's place, against the
cell's own limits, at a size a test run holds.  The program itself, at
the same size, comes out correct."""
import jax
import pytest

import tiny
from benchlib import check, gen, serve, train

SEED = 9_000_000_011


def _limits_met(numbers, limits):
    return all(v <= limits[k] for k, v in numbers.items())


@pytest.fixture(scope="module")
def served():
    cell = tiny.cell("serve")
    s = serve.build(cell.config, cell.traffic, SEED)
    serve.warm(s)
    win = serve.run_window(s, 2.0)
    return cell, s, win


def test_serve_program_is_correct(served):
    cell, s, win = served
    mix = cell.traffic
    nums = check.serve_numbers(s, win, mix["check_sample"],
                               mix["check_group"])
    assert _limits_met(nums, mix["limits"]), nums


def test_serve_control_is_not(served):
    cell, s, win = served
    mix = cell.traffic
    nums = check.serve_numbers(s, win, mix["check_sample"],
                               mix["check_group"], control=True)
    assert not _limits_met(nums, mix["limits"]), nums


def test_train_control_is_not():
    cell = tiny.cell("train")
    c, job = cell.config, cell.traffic
    feed = gen.train_rows(job, SEED, c["vocab_size"], job["batch"])
    batches = [next(feed) for _ in range(3)]
    nums = train.fault_numbers(c, job, SEED, batches)["control"]
    assert not _limits_met(nums, job["limits"]), nums

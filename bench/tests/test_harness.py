"""A whole run of each kind of cell on the CPU at a small size: the
harness's look for a chip is skipped, everything after it runs."""
import json
from collections import Counter

import jax
import numpy as np
import pytest

import tiny
from benchlib import harness

SEED = 2 ** 31 + 12345      # larger than 32 signed bits hold


def run(kind, trace, capsys, seconds="2", patch=None):
    cell = tiny.cell(kind)
    if patch:
        patch(cell)
    harness.main(["--workload", cell.name, "--seed", str(SEED),
                  "--seconds", seconds, "--trace", str(trace)],
                 devices=jax.devices(), cell=cell)
    out, err = capsys.readouterr()
    return cell, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_timed_run(kind, capsys):
    cell, res, err = run(kind, 0, capsys)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m.name for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # each number compared, with its limit, ends standard error
    lines = err.strip().splitlines()[-len(res["checks"]):]
    assert [ln.split()[1] for ln in lines] == list(res["checks"])


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_traced_run(kind, capsys):
    cell, res, _ = run(kind, 1, capsys)
    assert res["correct"] is True, res["checks"]
    # the CPU has no device plane and no published peaks: the readers of
    # the trace and of shares of a peak report nothing
    host = {m.name for m in cell.per_layer
            if m.source != "device_trace" and "mfu" not in m.name}
    assert host <= set(res["metrics"]), set(res["metrics"])
    assert set(res["metrics"]) <= {m.name for m in cell.per_layer}
    if kind == "serve":
        assert res["metrics"]["serve.window_compiles"]["value"] == 0
    assert "busy_s" in res["device"] and "window_s" in res["device"]


def test_same_seed_same_work():
    from benchlib import gen
    mix = tiny.serve_mix()
    a = gen.serve_requests(mix, SEED, 5.0, 256, 256)
    b = gen.serve_requests(mix, SEED, 5.0, 256, 256)
    c = gen.serve_requests(mix, SEED + 1, 5.0, 256, 256)
    assert a == b and a != c
    # every seed: the same sizes and gaps, in another order
    key = lambda rs: sorted((len(r.prompt), r.max_new_tokens)  # noqa: E731
                            for r in rs)
    assert key(a) == key(c)
    gaps = Counter(np.round(gen.serve_shapes(mix, len(a))[4], 9))
    for rs in (a, c):
        due = np.array([r.due for r in rs])
        assert not Counter(np.round(np.diff(due), 9)) - gaps


def test_train_rows_all_differ():
    from benchlib import gen
    job = tiny.train_job()
    feed = gen.train_rows(job, SEED, 256, job["batch"])
    rows = [tuple(r) for _ in range(3) for r in next(feed)["tokens"]]
    assert len(set(rows)) == len(rows)


def test_train_window_runs_ahead_and_counts_all_it_sent():
    """The host waits for a loss only once ``ahead_steps`` later steps are
    sent; at the close it waits for every step sent, and each counts."""
    import itertools
    from benchlib import train

    job = {**tiny.train_job(), "ahead_steps": 3}
    sent, read = [], []

    class Loss:
        def __init__(self, i):
            self.i = i

        def __float__(self):
            read.append((self.i, len(sent)))
            return 0.0

    def fn(params, opt, batch):
        sent.append(batch)
        return params, opt, {"loss": Loss(len(sent))}

    t = train.Train(cfg=None, c={}, job=job, seed=SEED, fn=fn, params=None,
                    opt=None, feed=itertools.repeat({}), place=lambda b: b,
                    psh=None)
    win = train.run_window(t, 0.05)
    assert win.steps == len(sent) > job["ahead_steps"] + 1
    assert win.tokens == len(sent) * job["batch"] * job["seq"]
    assert [i for i, _ in read] == list(range(1, len(sent) + 1))
    # each loss read while the three sent after it were in flight, until
    # the close, after which nothing more was sent
    body = [(i, n) for i, n in read if i + 3 <= len(sent)]
    assert all(n == i + 3 for i, n in body)
    assert all(n == len(sent) for i, n in read[len(body):])

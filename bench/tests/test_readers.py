"""Per-layer readers: nothing to read gives nothing, never a zero share;
with readings they compute the stated ratios."""
import pytest

from benchlib import device, spec, trace
from benchlib.runlog import Run

PEAKS = device.peaks("TPU v5 lite")


def reader(name):
    return spec.load_reader(spec.BENCH_DIR / "metrics" / f"{name}.py")


def run_with(**kw):
    r = Run(config={}, traffic={}, peaks=kw.pop("peaks", PEAKS),
            devices=[object()])
    for k, v in kw.items():
        getattr(r, k).update(v) if isinstance(v, dict) else setattr(r, k, v)
    return r


ALL = sorted(p.stem for p in (spec.BENCH_DIR / "metrics").glob("*.py"))


@pytest.mark.parametrize("name", ALL)
def test_nothing_to_read_gives_nothing(name):
    assert reader(name)(run_with()) is None


def test_decode_step_mfu():
    r = run_with(counters={"decode_flops": 197e12 * 0.5})
    r.spans.durations["model.decode_step"] = [0.25, 0.25, 0.5, 1.0]
    assert reader("decode.step_mfu")(r) == pytest.approx(25.0)


def test_window_mfu():
    """The FLOPs over the window are those done by its close; the drain's
    work after it is not divided by the window."""
    from benchlib import serve
    win = serve.Window(reqs=[], n_window=0, records={}, outputs={}, cf={},
                       late=[], tokens_in_window=0, elapsed=30.0,
                       compiles=0, failed=0, hits=0, misses=0,
                       counts={"flops": 197e12 * 3.3,
                               "window_flops": 197e12 * 3.0})
    r = run_with()
    serve.fill_run(r, win)
    assert reader("device.step_mfu.serve")(r) == pytest.approx(10.0)


def test_flash_decode_roofline_and_idle():
    tr = trace.Reduced(window_s=10.0, busy_s=6.0, chips=1,
                       ops_s={"_decode_attention_jit": 2.0, "copy": 1.0},
                       op_counts={"_decode_attention_jit": 32, "copy": 4},
                       gaps_s={})
    r = run_with(counters={"traced_decode_attn_bytes": 819e9 * 0.5},
                 trace=tr)
    assert reader("flash_decode_roofline")(r) == pytest.approx(25.0)
    assert reader("device.idle_share.serve")(r) == pytest.approx(40.0)


def test_percentiles_in_ms():
    r = run_with(values={"gen.late_s": [0.001 * i for i in range(101)]})
    assert reader("gen.late_p95_ms")(r) == pytest.approx(95.0)
    r = run_with(counters={"cf.hits": 3, "cf.misses": 1})
    assert reader("cf.hit_rate")(r) == pytest.approx(75.0)


def _collective_profile():
    """Two chips and the host.  Each chip's async all-gather is issued,
    computed behind (not counted) and waited for in its -done (counted);
    a synchronous all-reduce and an all-gather inside a loop count whole."""
    from test_program_spans import Ev, Line, Plane, Profile
    chip0 = [Ev("fusion.1", 0, 100), Ev("async-collective-start.2", 100, 105),
             Ev("fusion.3", 105, 300), Ev("async-collective-done.2", 300, 340),
             Ev("all-reduce.4", 400, 460), Ev("fusion.5", 460, 500),
             Ev("while.6", 500, 600), Ev("all-gather.7", 520, 540),
             Ev("fusion.8", 540, 600)]
    chip1 = [Ev("fusion.1", 0, 120), Ev("async-collective-start.2", 120, 125),
             Ev("fusion.3", 125, 310), Ev("async-collective-done.2", 310, 320),
             Ev("all-reduce.4", 400, 460), Ev("fusion.5", 460, 600)]
    return Profile([Plane("/device:TPU:0", [Line("XLA Ops", chip0)]),
                    Plane("/device:TPU:1", [Line("XLA Ops", chip1)]),
                    Plane("/host:CPU", [Line("python",
                                             [Ev("bench.feed", 0, 600)])])])


def test_collective_exposed_share_counts_the_waits():
    tr = trace.reduce_profile(_collective_profile())
    r = run_with(trace=tr)
    # chip 0 waits 40 + 60 + 20 ns, chip 1 10 + 60, in a 600 ns window
    assert reader("train.collective_exposed_share")(r) == pytest.approx(
        100.0 * (120 + 70) / 2 / 600)


def test_collective_exposed_share_needs_two_chips():
    tr = trace.Reduced(window_s=1.0, busy_s=0.5, chips=1,
                       ops_s={"all-reduce": 0.1}, op_counts={"all-reduce": 1},
                       gaps_s={})
    assert reader("train.collective_exposed_share")(run_with(trace=tr)) \
        is None

"""The trace reduction, on a small trace recorded on a TPU v5e
(``bench/tools/record_trace.py``: a 1024^2 bf16 matmul and the paged
flash-decode kernel, three times each under ``bench.*`` annotations), and
on one recorded on four (``bench/tools/record_collective_trace.py``: a
step whose partial sums are all-reduced and whose rows are all-gathered,
three times)."""
import pathlib

import pytest

from benchlib import trace

DATA = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
COLLECTIVES = DATA.parent / "collectives.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_file(str(DATA))


def test_busy_and_idle_fill_the_window(reduced):
    assert reduced.chips == 1
    assert 0 < reduced.busy_s < reduced.window_s
    idle = sum(reduced.gaps_s.values())
    assert idle + reduced.busy_s == pytest.approx(reduced.window_s, rel=1e-9)


def test_operations_by_instruction_name(reduced):
    names = dict(reduced.top_ops())
    assert set(names) >= {"_decode_attention_jit", "fusion", "copy-done"}
    assert all("%" not in n and "=" not in n for n in reduced.ops_s)
    secs, n = reduced.op_seconds(r"decode_attention")
    assert n == 3 and 0 < secs < reduced.busy_s


def test_idle_gaps_go_to_the_host_spans(reduced):
    gaps = dict(reduced.top_gaps())
    assert set(gaps) <= {"bench.matmul", "bench.decode", "host.other"}
    assert gaps["bench.matmul"] > 0 and gaps["bench.decode"] > 0


@pytest.mark.parametrize("raw,name", [
    ("%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8,128] %p), kind=kLoop",
     "fusion"),
    ("%copy-start.3 = (bf16[4]) copy-start(bf16[4] %a)", "copy-start"),
    ("_decode_attention_jit.1", "_decode_attention_jit"),
    ("convolution_bitcast_fusion", "convolution_bitcast_fusion"),
])
def test_op_name(raw, name):
    assert trace.op_name(raw) == name


def test_union_merges_overlaps():
    assert trace._union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_gap_goes_to_innermost_span():
    gaps = {}
    spans = [(0, 100, "bench.tick"), (10, 20, "bench.cf_score"),
             (50, 90, "bench.decode")]
    trace._attribute([(12, 18), (30, 40), (60, 70), (95, 99)], spans, gaps)
    assert gaps == pytest.approx({"bench.cf_score": 6e-9,
                                  "bench.tick": 14e-9,
                                  "bench.decode": 10e-9})


def test_nested_operations_charge_their_own_time():
    ops, counts = {}, {}
    evs = sorted([(0, -100, "while"), (10, -30, "fusion"), (50, -20, "copy"),
                  (55, -5, "add"), (200, -10, "fusion")])
    trace._self_times(evs, ops, counts)
    assert ops == pytest.approx({"while": 50e-9, "fusion": 40e-9,
                                 "copy": 15e-9, "add": 5e-9})
    assert counts == {"while": 1, "fusion": 2, "copy": 1, "add": 1}


def test_collectives_on_four_chips():
    from benchlib import spec
    from benchlib.runlog import Run
    tr = trace.reduce_file(str(COLLECTIVES))
    assert tr.chips == 4
    assert tr.op_counts["all-reduce"] == tr.op_counts["all-gather"] == 3
    run = Run(config={}, traffic={}, peaks={}, devices=[None] * 4)
    run.trace = tr
    share = spec.load_reader(spec.BENCH_DIR / "metrics"
                             / "train.collective_exposed_share.py")(run)
    # each chip waits on its all-reduce and all-gather, ~0.19 of 1.6 ms a
    # step; the matmul, tanh and copies are not exchanges
    waits = tr.ops_s["all-reduce"] + tr.ops_s["all-gather"]
    assert share == pytest.approx(100.0 * waits / tr.window_s, rel=1e-12)
    assert 0 < share < 100.0 * tr.busy_s / tr.window_s

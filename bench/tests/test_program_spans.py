"""The engine's own spans against the device trace: the bench's
reduction with the engine's ``repro.*`` annotations among its spans
(``benchlib.engine_trace``), on a synthetic
profile and on a trace recorded on a TPU v5e
(``bench/tools/record_serve_trace.py``: a tiny paged flash-decode engine
with a CF head, a few ticks under ``bench.tick``), and the run of a serve
cell with the engine's tracer on (``bench/tools/program_split.py``)."""
import dataclasses
import importlib.util
import pathlib
from typing import List

import jax
import pytest

import tiny
from benchlib import engine_trace, trace
from repro.obs import ManualClock, Tracer

DATA = pathlib.Path(__file__).parent / "data" / "serve_spans.xplane.pb"
TOOLS = pathlib.Path(__file__).resolve().parents[1] / "tools"


# -- a ProfileData stand-in ---------------------------------------------------

@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Ev]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclasses.dataclass
class Profile:
    planes: List[Plane]


OPS = [Ev("fusion.1", 0, 100), Ev("copy.2", 300, 400),
       Ev("flash_decode_paged.3", 600, 900)]
# the bench's own spans: the tick, and its span around the CF head
BENCH = [Ev("bench.submit", 0, 5), Ev("bench.tick", 10, 1000),
         Ev("bench.cf.score", 125, 475)]
# one tick: a prefill with CF scoring, then a decode step and its retire
REPRO = [Ev("repro.engine.tick", 20, 990), Ev("repro.sched.refill", 25, 485),
         Ev("repro.model.prefill", 30, 120), Ev("repro.cf.lookup", 120, 480),
         Ev("repro.cf.items", 130, 260), Ev("repro.cf.gather", 140, 250),
         Ev("repro.cf.logits_row", 260, 470),
         Ev("repro.engine.decode", 505, 985),
         Ev("repro.decode_step", 510, 905),
         Ev("repro.engine.retire", 905, 985)]


def profile(host):
    return Profile([Plane("/device:TPU:0", [Line("XLA Ops", list(OPS))]),
                    Plane("/host:CPU", [Line("python", list(host))])])


def test_engine_spans_move_no_bench_number():
    """The bench's own reduction reads the same window, busy time and idle
    split with the engine's annotations in the trace as without them."""
    plain = trace.reduce_profile(profile(BENCH))
    both = trace.reduce_profile(profile(BENCH + REPRO))
    assert both == plain
    assert plain.gaps_s == pytest.approx({"bench.cf.score": 200e-9,
                                          "bench.tick": 300e-9})


def program_gaps(pd):
    return trace.reduce_profile(pd, prefix=engine_trace.PREFIX).gaps_s


def test_program_gaps_split_the_same_idle_time():
    pd = profile(BENCH + REPRO)
    gaps = program_gaps(pd)
    red = trace.reduce_profile(pd)
    assert sum(gaps.values()) == pytest.approx(sum(red.gaps_s.values()))
    assert red.busy_s + sum(gaps.values()) == pytest.approx(red.window_s)
    # idle 100-300 (mid 200: cf.gather, inside the bench's cf.score too),
    # 400-600 (mid 500: between the refill and the decode, engine.tick),
    # 900-1000 (mid 950: retire)
    assert gaps == pytest.approx({"repro.cf.gather": 200e-9,
                                  "repro.engine.tick": 200e-9,
                                  "repro.engine.retire": 100e-9})
    # where no engine span runs, the bench's span takes the stretch
    pd = profile(BENCH + [e for e in REPRO if e.name != "repro.engine.tick"])
    assert program_gaps(pd)["bench.tick"] == pytest.approx(
        200e-9)


def test_readings():
    clk = ManualClock()
    tr = Tracer(clock=clk)
    for name, ms in (("model.prefill", 6), ("model.prefill", 8),
                     ("decode_step", 59), ("cf.lookup", 4)):
        with tr.span(name, track="engine"):
            clk.advance(ms * 1e-3)
    red = trace.Reduced(window_s=10.0, busy_s=6.0, chips=1, ops_s={},
                        op_counts={}, gaps_s={"bench.tick": 4.0})
    # the bench's spans around the model calls and the CF head count
    # with them; the bench's tick, outside every engine span, with neither
    gaps = {"repro.engine.retire": 1.0, "repro.sample.tokens": 0.5,
            "repro.cf.gather": 1.0, "repro.cf.logits_row": 0.5,
            "bench.cf.score": 0.5, "repro.decode_step": 0.25,
            "bench.model.decode_step": 0.25, "repro.model.prefill": 0.25,
            "bench.tick": 0.5}
    r = engine_trace.readings(tr, ticks=10, host_syncs=45, reduced=red,
                              gaps=gaps)
    assert r == pytest.approx({
        "engine.host_idle_share": 15.0, "cf.host_idle_share": 20.0,
        "engine.host_syncs_per_tick": 4.5, "engine.prefill_ms_p50": 7.0,
        "engine.decode_step_ms_p50": 59.0, "engine.cf_score_ms_p50": 4.0})
    # no device plane: the shares are not measured, the spans still are
    r = engine_trace.readings(tr, 10, 45, dataclasses.replace(red, busy_s=0),
                              gaps)
    assert r["cf.host_idle_share"] is None
    assert r["engine.decode_step_ms_p50"] == pytest.approx(59.0)


def test_no_tracer_or_a_truncated_one_reads_nothing():
    red = trace.Reduced(window_s=10.0, busy_s=6.0, chips=1, ops_s={},
                        op_counts={}, gaps_s={})
    r = engine_trace.readings(None, 10, 45, red, {})
    assert set(r) and all(v is None for v in r.values())
    tr = Tracer(capacity=2, clock=ManualClock())
    for _ in range(3):
        with tr.span("decode_step"):
            pass
    assert tr.dropped == 1
    r = engine_trace.readings(tr, 10, 45, red, {})
    assert all(v is None for v in r.values())


# -- the trace recorded on the chip -------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    pd = jax.profiler.ProfileData.from_file(str(DATA))
    return trace.reduce_profile(pd), program_gaps(pd)


def test_recorded_idle_goes_to_engine_and_cf_spans(recorded):
    red, gaps = recorded
    assert red.chips == 1 and 0 < red.busy_s < red.window_s
    assert sum(gaps.values()) == pytest.approx(sum(red.gaps_s.values()),
                                               rel=1e-9)
    assert any(k.startswith("repro.engine.") for k in gaps), gaps
    assert any(k.startswith("repro.cf.") for k in gaps), gaps
    assert set(red.gaps_s) <= {"bench.tick", "host.other"}


def test_recorded_kernel_carries_its_name(recorded):
    red, _ = recorded
    names = set(red.ops_s)
    assert "flash_decode_paged" in names, sorted(names)
    assert not any("_decode_attention_jit" in n for n in names)
    # the flash_decode_roofline reader's pattern finds it
    secs, n = red.op_seconds(r"decode_attention|flash_decode|_decode_kernel")
    assert n > 0 and 0 < secs < red.busy_s


# -- a serve cell with the engine's tracer on --------------------------------

def _tool(name):
    path = TOOLS / f"{name}.py"
    sp = importlib.util.spec_from_file_location(f"bench_tool_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def test_program_split_on_a_small_cell(capsys):
    from benchlib import serve
    cell = tiny.cell("serve")
    before = serve._engine, serve.run_window, trace.reduce_file
    out = _tool("program_split").split(cell.name, 2 ** 31 + 7, 2.0,
                                       devices=jax.devices(), cell=cell)
    assert (serve._engine, serve.run_window, trace.reduce_file) == before
    assert out["dropped"] == 0 and out["events"] > 0
    assert out["ticks"] > 0 and out["prefills"] > 0
    r = out["readings"]
    for k in ("engine.prefill_ms_p50", "engine.decode_step_ms_p50",
              "engine.cf_score_ms_p50", "engine.host_syncs_per_tick"):
        assert r[k] > 0, k
    # the CPU has no device plane: the idle shares are not measured
    assert r["engine.host_idle_share"] is None
    # the engine's decode step holds the bench's span of the same call
    assert (out["program_p50_ms"]["decode_step"]
            >= out["bench_p50_ms"]["model.decode_step"])
    capsys.readouterr()

"""Observability subsystem: tracer ring semantics, disabled fast path,
Perfetto export schema, exact percentile delegation, engine span/TTFT
reconciliation, trace-fed stage rebalancing, and the kernel dispatch
recorder.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.obs import (DEFAULT_BOUNDS, NULL_TRACER, ManualClock,
                       MetricsRegistry, Tracer, chrome_trace, or_null,
                       percentile, stage_tick_times,
                       synthesize_pipeline_ticks, write_trace)
from repro.obs.metrics import Histogram
from repro.obs.trace import _NOOP_SPAN


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    clk = ManualClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", track="t"):
        clk.advance(1.0)
        with tr.span("inner", track="t", step=3):
            clk.advance(0.5)
        clk.advance(0.25)
    ev = tr.events
    # children exit (and therefore land) before their parent
    assert [e["name"] for e in ev] == ["inner", "outer"]
    inner, outer = ev
    assert outer["depth"] == 0 and inner["depth"] == 1
    assert outer["ts"] == 0.0 and outer["dur"] == pytest.approx(1.75)
    assert inner["ts"] == 1.0 and inner["dur"] == pytest.approx(0.5)
    assert inner["args"] == {"step": 3}
    # depth bookkeeping unwinds: a sibling span is back at depth 0
    with tr.span("sibling", track="t"):
        pass
    assert tr.events[-1]["depth"] == 0


def test_instant_and_complete():
    clk = ManualClock(5.0)
    tr = Tracer(clock=clk)
    tr.instant("sched.admit", track="sched", rid=7)
    tr.complete("req.prefill", 1.0, 3.5, track="slot0", rid=7)
    inst, comp = tr.events
    assert inst["ph"] == "i" and inst["ts"] == 5.0
    assert inst["args"]["rid"] == 7
    assert comp["ph"] == "X" and comp["ts"] == 1.0 and comp["dur"] == 2.5


def test_ring_wraparound_keeps_newest():
    tr = Tracer(capacity=4, clock=ManualClock())
    for i in range(10):
        tr.instant("e", i=i)
    assert tr.capacity == 4
    assert [e["args"]["i"] for e in tr.events] == [6, 7, 8, 9]


def test_disabled_path_allocates_nothing():
    tr = Tracer(enabled=False)
    # every span() call returns the one shared no-op singleton
    assert tr.span("a") is _NOOP_SPAN
    assert tr.span("b", track="x", big_arg=list(range(100))) is _NOOP_SPAN
    with tr.span("c"):
        pass
    tr.instant("d")
    tr.complete("e", 0.0, 1.0)
    tr.extend([{"ph": "i", "name": "f", "track": "m", "ts": 0, "args": {}}])
    assert tr.events == []
    assert or_null(None) is NULL_TRACER
    assert or_null(tr) is tr


def test_extend_merges_probe_tracer():
    probe = Tracer(clock=ManualClock())
    with probe.span("stage_tick", track="stage0", stage=0):
        pass
    main = Tracer(clock=ManualClock())
    main.extend(probe.events)
    assert main.span_names() == {"stage_tick": 1}


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""
    log: list = []

    def __init__(self, name):
        self.name = name
        self.log.append(("new", name))

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_live_span_is_a_profiler_annotation(monkeypatch):
    from repro.obs import trace
    monkeypatch.setattr(trace, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.log = []
    tr = Tracer(clock=ManualClock())
    with tr.span("engine.tick", track="engine"):
        with tr.span("decode_step", track="engine"):
            pass
    assert _FakeAnnotation.log == [
        ("new", "repro.engine.tick"), ("enter", "repro.engine.tick"),
        ("new", "repro.decode_step"), ("enter", "repro.decode_step"),
        ("exit", "repro.decode_step"), ("exit", "repro.engine.tick")]
    # retroactive spans and instants are not annotated; a disabled tracer
    # opens no annotation at all
    tr.complete("req.prefill", 0.0, 1.0)
    tr.instant("sched.admit")
    off = Tracer(enabled=False)
    with off.span("engine.tick"):
        pass
    assert len(_FakeAnnotation.log) == 6


def test_live_spans_reach_the_profiler(tmp_path):
    """Under a real profiler session the spans land on a host plane of the
    trace, nested as they ran."""
    import glob

    import jax
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("engine.tick", track="engine"):
            with tr.span("decode_step", track="engine"):
                jax.block_until_ready(jax.numpy.ones(8) * 2)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")[0]
    pd = jax.profiler.ProfileData.from_file(path)
    evs = {e.name: (e.start_ns, e.end_ns)
           for pl in pd.planes if pl.name.startswith("/host:")
           for ln in pl.lines for e in ln.events
           if e.name.startswith("repro.")}
    assert set(evs) == {"repro.engine.tick", "repro.decode_step"}
    (t0, t1), (s0, s1) = evs["repro.engine.tick"], evs["repro.decode_step"]
    assert t0 <= s0 <= s1 <= t1


def test_span_args_known_at_exit():
    tr = Tracer(clock=ManualClock())
    with tr.span("cf.lookup", track="engine", rid=4) as sp:
        sp.set(hits=3, misses=1)
    assert tr.events[-1]["args"] == {"rid": 4, "hits": 3, "misses": 1}
    off = Tracer(enabled=False)
    with off.span("cf.lookup", rid=4) as sp:
        assert sp is _NOOP_SPAN
        sp.set(hits=3, misses=1)
    assert off.events == []


def test_ring_overflow_counts_dropped():
    tr = Tracer(capacity=4, clock=ManualClock())
    for i in range(3):
        tr.instant("e", i=i)
    assert tr.dropped == 0
    with tr.span("s"):
        pass
    tr.complete("c", 0.0, 1.0)
    tr.extend([{"ph": "i", "name": "x", "track": "m", "ts": 0, "args": {}}
               for _ in range(3)])
    assert tr.dropped == 4 and len(tr.events) == 4
    tr.clear()
    assert tr.dropped == 0 and tr.events == []


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _sample_tracer_registry():
    clk = ManualClock()
    tr = Tracer(clock=clk)
    reg = MetricsRegistry(clock=clk)
    with tr.span("decode_step", track="engine", step=0):
        clk.advance(2e-3)
    tr.instant("sched.admit", track="sched", rid=0)
    reg.gauge("pool.used_blocks").set(3)
    clk.advance(1e-3)
    reg.gauge("pool.used_blocks").set(5)
    return tr, reg


def test_chrome_trace_schema_valid():
    tr, reg = _sample_tracer_registry()
    obj = json.loads(json.dumps(chrome_trace(tr, reg)))   # JSON round-trip
    ev = obj["traceEvents"]
    assert ev and obj["displayTimeUnit"] == "ms"
    for e in ev:
        for key in ("ph", "ts", "pid", "tid"):
            assert key in e, (key, e)
    by_ph = {}
    for e in ev:
        by_ph.setdefault(e["ph"], []).append(e)
    assert all("dur" in e for e in by_ph["X"])
    assert all(e["s"] == "t" for e in by_ph["i"])
    # one thread_name metadata row per track, plus the process_name row
    meta = {e["args"]["name"] for e in by_ph["M"] if
            e["name"] == "thread_name"}
    assert {"engine", "sched", "counter:pool.used_blocks"} <= meta
    # gauge series became counter events in microseconds on the same clock
    cts = [(e["ts"], e["args"]["value"]) for e in by_ph["C"]]
    assert cts == [(2e-3 * 1e6, 3.0), (3e-3 * 1e6, 5.0)]
    # span timestamps are microseconds
    assert by_ph["X"][0]["dur"] == pytest.approx(2e3)


def test_write_trace_suffix_dispatch(tmp_path):
    tr, reg = _sample_tracer_registry()
    jpath = tmp_path / "t.json"
    n = write_trace(str(jpath), tr, reg)
    obj = json.loads(jpath.read_text())
    assert len(obj["traceEvents"]) == n
    lpath = tmp_path / "t.jsonl"
    n = write_trace(str(lpath), tr, reg)
    lines = [json.loads(x) for x in lpath.read_text().splitlines()]
    assert len(lines) == n
    assert lines[0]["ph"] == "X" and lines[0]["ts"] == 0.0   # seconds
    assert "metrics" in lines[-1]
    assert lines[-1]["metrics"]["gauges"]["pool.used_blocks"]["peak"] == 5.0


# ---------------------------------------------------------------------------
# metrics: exact percentiles, serving-metrics delegation
# ---------------------------------------------------------------------------

def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 100):
        xs = rng.exponential(0.01, n).tolist()
        for q in (0, 25, 50, 95, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), rel=0, abs=0)


def test_histogram_exact_window_then_bucket_fallback():
    h = Histogram(DEFAULT_BOUNDS, max_samples=8)
    rng = np.random.default_rng(1)
    xs = rng.exponential(0.01, 8).tolist()
    for x in xs:
        h.observe(x)
    assert h.exact
    assert h.percentile(95) == float(np.percentile(xs, 95))
    assert h.summary()["mean"] == sum(xs) / len(xs)
    for x in rng.exponential(0.01, 8):
        h.observe(float(x))          # ages the window out: 16 > max_samples
    assert not h.exact and h.count == 16
    p50 = h.percentile(50)
    assert h.min <= p50 <= h.max     # bucket interpolation stays bounded


def test_serving_dist_delegates_to_obs():
    from repro.serving import metrics as sm
    assert sm.percentile is percentile
    rng = np.random.default_rng(2)
    xs = rng.exponential(0.005, 37).tolist()
    d = sm._dist(xs)
    assert d["mean"] == sum(xs) / len(xs)
    for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
        assert d[key] == float(np.percentile(xs, q))


def test_registry_snapshot():
    clk = ManualClock()
    reg = MetricsRegistry(clock=clk)
    reg.counter("c").inc()
    reg.counter("c").inc(2.0)
    reg.gauge("g").set(4)
    reg.gauge("g").set(1)
    reg.histogram("h").observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == {"value": 1.0, "peak": 4.0, "points": 2}
    assert snap["histograms"]["h"]["count"] == 1
    assert reg.counter("c") is reg.counter("c")      # get-or-create


# ---------------------------------------------------------------------------
# serving engine: spans reconcile with TTFT/TPOT on the simulated clock
# ---------------------------------------------------------------------------

def _engine_run_with_trace():
    import jax

    from repro.cache_layout import CacheLayout
    from repro.config import get_arch, reduced
    from repro.models import transformer as tf
    from repro.serving import engine as eng
    from repro.serving import traffic

    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(6):
        reqs.append(traffic.Request(
            rid=i, user_id=i,
            prompt=tuple(int(t) for t in
                         rng.integers(3, cfg.vocab_size,
                                      int(rng.integers(4, 12)))),
            max_new_tokens=int(rng.integers(3, 8)),
            arrival=0.002 * i))
    layout = CacheLayout(kind="paged", block_size=8)
    backend = eng.make_backend(cfg, params, layout=layout)
    ecfg = eng.EngineConfig(n_slots=2, max_len=64, layout=layout)
    clock = traffic.Clock(fixed_decode_s=1e-3, fixed_prefill_s=5e-3)
    tracer = Tracer()
    registry = MetricsRegistry()
    engine = eng.ServingEngine(backend, ecfg, clock=clock, tracer=tracer,
                               metrics=registry)
    outputs, records, summary = engine.run(reqs)
    return records, summary, tracer, registry


def test_engine_spans_reconcile_with_ttft_tpot():
    records, summary, tracer, registry = _engine_run_with_trace()
    spans = {}
    for e in tracer.events:
        if e["ph"] == "X" and e["name"].startswith("req."):
            spans.setdefault(e["args"]["rid"], {})[e["name"]] = e
    finished = [r for r in records if r.finished is not None]
    assert finished, "no requests finished"
    for r in finished:
        sp = spans[r.rid]
        assert set(sp) == {"req.queue_wait", "req.prefill", "req.decode"}
        # TTFT = queue_wait + prefill span durations, exactly (same
        # RequestRecord timestamps, same simulated clock domain)
        ttft = sp["req.queue_wait"]["dur"] + sp["req.prefill"]["dur"]
        assert ttft == pytest.approx(r.ttft, abs=1e-12)
        if r.tpot is not None:
            tpot = sp["req.decode"]["dur"] / (r.tokens_out - 1)
            assert tpot == pytest.approx(r.tpot, abs=1e-12)
        assert sp["req.decode"]["args"]["tokens_out"] == r.tokens_out
        # all three phases share the request's slot track
        assert len({e["track"] for e in sp.values()}) == 1
    # scheduler instants: one admission per finished request
    admits = [e for e in tracer.events
              if e["ph"] == "i" and e["name"] == "sched.admit"]
    assert len(admits) >= len(finished)
    # decode_step spans ride the engine track; each carries the rows it
    # decoded, one token per active slot past each request's first
    steps = [e for e in tracer.events if e["name"] == "decode_step"]
    assert len(steps) == summary["decode_steps"]
    assert steps[0]["track"] == "engine"
    assert all(1 <= e["args"]["rows"] <= 2 for e in steps)
    assert sum(e["args"]["rows"] for e in steps) == \
        sum(r.tokens_out - 1 for r in finished)
    # summary carries the obs section; pool metrics landed in the registry
    assert summary["obs"]["span_counts"]["decode_step"] == len(steps)
    snap = registry.snapshot()
    assert snap["gauges"]["pool.used_blocks"]["peak"] > 0
    assert "pool.shared_hits" in snap["counters"]
    assert "pool.cow_events" in snap["counters"]
    assert snap["gauges"]["engine.active_slots"]["peak"] == \
        summary["max_concurrent_slots"]
    # and the whole thing exports schema-valid
    obj = chrome_trace(tracer, registry)
    for e in obj["traceEvents"]:
        for key in ("ph", "ts", "pid", "tid"):
            assert key in e


def test_untraced_engine_summary_has_no_obs():
    import jax

    from repro.config import get_arch, reduced
    from repro.models import transformer as tf
    from repro.serving import engine as eng
    from repro.serving import traffic

    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    reqs = [traffic.Request(rid=0, user_id=0, prompt=(5, 6, 7),
                            max_new_tokens=3, arrival=0.0)]
    backend = eng.make_backend(cfg, params)
    engine = eng.ServingEngine(backend, eng.EngineConfig(n_slots=1,
                                                         max_len=32))
    _, _, summary = engine.run(reqs)
    assert "obs" not in summary
    assert not engine.tracer.enabled


# ---------------------------------------------------------------------------
# serving engine: live phase spans and counters, on a tiny paged engine
# with a CF head
# ---------------------------------------------------------------------------

class _WallClock:
    """The engine's clock read from the host, as a deployment runs it."""

    fixed_decode_s = fixed_prefill_s = fixed_handoff_s = fixed_cf_s = None

    def __init__(self):
        import time
        self._t0 = time.perf_counter()

    @property
    def now(self) -> float:
        import time
        return time.perf_counter() - self._t0

    def advance(self, dt: float) -> None:
        pass


def _tiny_cf_engine(tracer=None, n_requests: int = 6):
    import jax

    from repro.cache_layout import CacheLayout
    from repro.config import get_arch, reduced
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.serving import CFHead
    from repro.serving import engine as eng
    from repro.serving import traffic

    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    reqs = [traffic.Request(
        rid=i, user_id=i % 3,
        prompt=tuple(int(t) for t in rng.integers(3, cfg.vocab_size,
                                                  int(rng.integers(4, 20)))),
        max_new_tokens=int(rng.integers(2, 6)), arrival=0.0,
        candidates=tuple(int(c) for c in rng.choice(cfg.vocab_size, 6,
                                                    replace=False)))
        for i in range(n_requests)]
    layout = CacheLayout(kind="paged", block_size=8)
    head = CFHead.build(n_users=3, n_items=cfg.vocab_size, cf_dim=8,
                        plan="row", cache_rows=4, mesh=make_host_mesh())
    engine = eng.ServingEngine(
        eng.make_backend(cfg, params, layout=layout),
        eng.EngineConfig(n_slots=2, max_len=64, layout=layout),
        clock=_WallClock(), tracer=tracer, cf_head=head)
    return engine, reqs


# the phase tree of one tick: each live span's parent
_PARENT = {
    "sched.refill": {"engine.tick"}, "engine.decode": {"engine.tick"},
    "pool.admit": {"sched.refill"}, "model.prefill": {"sched.refill"},
    "cf.lookup": {"sched.refill"}, "sample.first": {"sched.refill"},
    "cf.user": {"cf.lookup"}, "cf.items": {"cf.lookup"},
    "cf.logits_row": {"cf.lookup"}, "cf.fuse_rank": {"cf.lookup"},
    "cf.cache.plan": {"cf.user", "cf.items"},
    "cf.gather": {"cf.user", "cf.items"},
    "pool.ensure_writable": {"engine.decode"},
    "pool.sync_tables": {"engine.decode"},
    "decode_step": {"engine.decode"}, "sample.tokens": {"engine.decode"},
    "engine.retire": {"engine.decode"},
}


def _inside(child, parent) -> bool:
    return (parent["ts"] <= child["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-12)


def _traced_ticks():
    tracer = Tracer()
    engine, reqs = _tiny_cf_engine(tracer)
    for r in reqs:
        engine.submit(r)
    decoded = []
    while engine.has_work:
        steps = engine.decode_steps
        engine.tick()
        decoded.append(engine.decode_steps > steps)
    live = [e for e in tracer.events
            if e["ph"] == "X" and e["track"] == "engine"]
    return engine, tracer, decoded, live


def test_each_decoding_tick_has_one_tick_span_around_its_decode():
    engine, tracer, decoded, live = _traced_ticks()
    assert engine.ticks == len(decoded) and any(decoded)
    # children exit first: a tick's events are those up to its own
    groups, cur = [], []
    for e in live:
        cur.append(e)
        if e["name"] == "engine.tick":
            groups.append(cur)
            cur = []
    assert cur == [] and len(groups) == len(decoded)
    for group, did in zip(groups, decoded):
        tick = group[-1]
        names = [e["name"] for e in group]
        assert tick["depth"] == 0 and names.count("engine.tick") == 1
        assert names.count("engine.decode") == names.count("decode_step") \
            == int(did)
        if did:
            dec = group[names.index("engine.decode")]
            step = group[names.index("decode_step")]
            assert (dec["depth"], step["depth"]) == (1, 2)
            assert _inside(step, dec) and _inside(dec, tick)
    assert tracer.dropped == 0


def test_admission_span_does_not_count_as_the_admission_instant():
    """The block-table admission span has a name of its own: the
    ``sched.admit`` count stays one scheduler instant per admission."""
    engine, tracer, decoded, live = _traced_ticks()
    counts = tracer.span_names()
    assert counts["sched.admit"] == engine.prefills > 0
    assert counts["pool.admit"] == engine.prefills
    assert all(e["ph"] == "i" for e in tracer.events
               if e["name"] == "sched.admit")


def test_children_never_outlast_their_parent():
    engine, tracer, decoded, live = _traced_ticks()
    assert {e["name"] for e in live} == set(_PARENT) | {"engine.tick"}
    for i, e in enumerate(live):
        if e["depth"] == 0:
            assert e["name"] == "engine.tick"
            continue
        # the parent is the next span one level up to close
        parent = next(p for p in live[i + 1:] if p["depth"] == e["depth"] - 1)
        assert parent["name"] in _PARENT[e["name"]], (e, parent)
        assert _inside(e, parent), (e, parent)


def test_host_syncs_count_every_wait_on_the_device(monkeypatch):
    """Every wait on the device that a counting probe sees inside the
    engine's ticks (``block_until_ready`` on device arrays, and each read
    of one to the host) is one ``host_syncs``; untraced, as deployed."""
    import jax
    from jax._src import array

    engine, reqs = _tiny_cf_engine(None)
    for r in reqs:
        engine.submit(r)
    seen = [0]
    real_wait, real_asarray = jax.block_until_ready, np.asarray

    def wait(x):
        if any(isinstance(v, jax.Array) for v in jax.tree.leaves(x)):
            seen[0] += 1
        return real_wait(x)

    def asarray(x, *a, **k):
        if isinstance(x, jax.Array):
            seen[0] += 1
        return real_asarray(x, *a, **k)

    def counted(real):
        def read(self, *a, **k):
            seen[0] += 1
            return real(self, *a, **k)
        return read

    with monkeypatch.context() as m:
        m.setattr(jax, "block_until_ready", wait)
        m.setattr(np, "asarray", asarray)
        for name in ("__int__", "__float__", "__bool__", "__index__",
                     "item", "tolist"):
            m.setattr(array.ArrayImpl, name,
                      counted(getattr(array.ArrayImpl, name)))
        while engine.has_work:
            engine.tick()
    assert engine.prefills == len(reqs) and engine.cf_scored == len(reqs)
    assert engine.host_syncs == seen[0] > 0
    assert engine.ticks > 0 and engine.tracer is NULL_TRACER


# ---------------------------------------------------------------------------
# straggler harness on the registry
# ---------------------------------------------------------------------------

def test_straggler_metrics_registry_equivalence():
    from repro.runtime import straggler

    sim = straggler.StragglerSim(n_workers=4, seed=3)
    base = straggler.run_policy(sim, 256, 20, "adaptive")
    reg, clk = MetricsRegistry(), ManualClock()
    out = straggler.run_policy(sim, 256, 20, "adaptive",
                               metrics=reg, clock=clk)
    assert out == base                       # same math, caller-held registry
    hist = reg.histogram("straggler.step_time_s")
    assert hist.count == 20
    # the simulated clock ends at the total simulated duration
    assert clk.now == pytest.approx(hist.total)
    assert reg.gauge("straggler.slowest_worker_t").peak > 0
    assert len(reg.gauge("straggler.slowest_worker_t").series) == 20


# ---------------------------------------------------------------------------
# trace-fed pipeline rebalancing
# ---------------------------------------------------------------------------

def _pp_setup():
    import jax

    from repro.config import get_arch, reduced
    from repro.models import transformer as tf

    cfg = dataclasses.replace(
        reduced(get_arch("olmo-1b"), layers=8), dtype="float32",
        d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    bounds = [0, 1, 8]                       # skewed: stage 1 has 7 layers
    pp = tf.pp_partition_params(cfg, params, bounds)
    return cfg, pp, bounds


def test_stage_tick_spans_feed_rebalance():
    from repro.core import load_balance
    from repro.runtime import trainer

    cfg, pp, bounds = _pp_setup()
    tr = Tracer()
    times = trainer.probe_stage_times(cfg, pp, bounds, iters=3, tracer=tr)
    ticks = [e for e in tr.events if e["name"] == "stage_tick"]
    assert len(ticks) == 3 * (len(bounds) - 1)
    assert {e["track"] for e in ticks} == {"stage0", "stage1"}
    # the trace recovers the probe's own medians exactly (same samples,
    # same sort-then-middle reduction)
    assert stage_tick_times(tr.events, len(bounds) - 1) == list(times)
    # ... so trace-fed rebalancing lands on the same bounds
    assert load_balance.rebalance_from_trace(tr.events, bounds) == \
        load_balance.rebalance_stages(times, bounds)


def test_synthesized_pipeline_timeline():
    for sched in ("1f1b", "gpipe"):
        tr = Tracer()
        end = synthesize_pipeline_ticks(tr, sched, n_stages=4, n_micro=8,
                                        stage_times=[1e-3] * 4)
        ev = tr.events
        fwd = [e for e in ev if e["name"] == "pp.fwd"]
        bwd = [e for e in ev if e["name"] == "pp.bwd"]
        assert len(fwd) == len(bwd) == 4 * 8
        assert {e["track"] for e in ev} == {f"stage{s}" for s in range(4)}
        # bwd ticks cost bwd_cost_ratio x fwd
        assert fwd[0]["dur"] == pytest.approx(1e-3)
        assert bwd[0]["dur"] == pytest.approx(2e-3)
        assert end >= 8 * 3e-3               # makespan >= useful work
        # no span crosses the end, every stage's micros appear once
        for s in range(4):
            micros = sorted(e["args"]["micro"] for e in fwd
                            if e["args"]["stage"] == s)
            assert micros == list(range(8))
        assert max(e["ts"] + e["dur"] for e in ev) == pytest.approx(end)


# ---------------------------------------------------------------------------
# kernel dispatch recorder
# ---------------------------------------------------------------------------

def test_ops_dispatch_recorder():
    import jax
    import jax.numpy as jnp

    from repro.cache_layout import CacheLayout
    from repro.kernels import ops

    records = []
    prev = ops.set_dispatch_recorder(records.append)
    try:
        B, S, Hk, H, D = 2, 16, 2, 4, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        cache = {"k": jax.random.normal(ks[1], (B, S, Hk, D)),
                 "v": jax.random.normal(ks[2], (B, S, Hk, D))}
        lengths = jnp.asarray([5, 9], jnp.int32)
        out = ops.decode_attention(q, cache, lengths,
                                   layout=CacheLayout(impl="dense"))
        assert out.shape == (B, 1, H, D)
        assert len(records) == 1
        r = records[0]
        assert r["op"] == "decode_attention" and r["impl"] == "dense"
        assert r["batch"] == B and r["heads"] == H and r["head_dim"] == D
        assert r["s_max"] == S
        assert r["kv_resident_bytes"] == 2 * B * S * Hk * D * 4  # float32
        assert r["modeled_flops"] == 4.0 * B * H * D * S
    finally:
        ops.set_dispatch_recorder(prev)
    # recorder removed: further dispatches record nothing
    ops.decode_attention(q, cache, lengths,
                         layout=CacheLayout(impl="dense"))
    assert len(records) == 1

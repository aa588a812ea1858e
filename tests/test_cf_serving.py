"""CF head serving: hot-row cache exactness at every sharding plan,
rows-touched refresh semantics, traffic candidate streams, engine
integration, and the cf_lookup_bytes comms model."""
import numpy as np
import pytest

from repro.embeddings import (CacheConfig, CachedLookup, EmbedSpec,
                              FreqTracker, HotRowCache, init_table,
                              make_plan)
from repro.obs import MetricsRegistry, Tracer
from repro.serving import (CFHead, Clock, EngineConfig, ServingEngine,
                           TrafficConfig, cf_lookup_bytes, generate)
from repro.launch.mesh import make_host_mesh

import jax

PLAN_KINDS = ["replicated", "row", "col", "row_col"]


@pytest.fixture(scope="module")
def mesh():
    # trivial 1x1 mesh: exercises every plan's shard_map code path
    # in-process without multi-device requirements.
    return make_host_mesh()


@pytest.fixture(scope="module")
def table():
    spec = EmbedSpec("cf_item", rows=96, dim=16)
    return spec, np.asarray(init_table(jax.random.PRNGKey(0), spec))


def _zipf_ids(n, rows, seed=0, a=1.3):
    rng = np.random.default_rng(seed)
    return np.clip(rng.zipf(a, size=n), 1, rows) - 1


# ---------------------------------------------------------------------------
# FreqTracker / HotRowCache mechanics
# ---------------------------------------------------------------------------

def test_freq_tracker_decayed_counts_rank_hot_rows_first():
    tr = FreqTracker(16, decay=0.5)
    tr.observe(np.array([3, 3, 3, 7]))
    top = tr.top_k(2)
    assert top[0] == 3 and set(top) == {3, 7}
    # decay: old mass fades, fresh traffic takes over
    for _ in range(12):
        tr.observe(np.array([9]))
    assert tr.top_k(1)[0] == 9
    # top_k never returns never-seen rows, even with spare capacity
    assert set(tr.top_k(16)) <= {3, 7, 9}


def test_hot_row_cache_refresh_is_incremental(table):
    spec, host = table
    cache = HotRowCache(spec.rows, capacity=4)
    cache.tracker.observe(np.array([1, 2, 3]))
    cache.refresh(host)
    stale = host.copy()
    stale[2] += 1.0                      # host moves on; cache holds old bytes
    cache.tracker.observe(np.array([2, 3, 5]))
    cache.refresh(stale)                 # 1,2,3 kept; 5 newly elected
    hit, slots = cache.plan_lookup(np.array([2, 5]))
    assert hit.all()
    np.testing.assert_array_equal(cache.rows[slots[0]], host[2])   # stale kept
    np.testing.assert_array_equal(cache.rows[slots[1]], stale[5])  # fresh read
    cache.refresh_touched(np.array([2]), stale)
    hit, slots = cache.plan_lookup(np.array([2]))
    np.testing.assert_array_equal(cache.rows[slots[0]], stale[2])


# ---------------------------------------------------------------------------
# CachedLookup: cached == uncached bit-for-bit at every plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", PLAN_KINDS)
def test_cached_lookup_exact_at_every_plan(mesh, table, kind):
    spec, host = table
    plan = make_plan(kind)
    ids = _zipf_ids(256, spec.rows)
    cached = CachedLookup(spec, plan, host, mesh=mesh,
                          cache=CacheConfig(rows=24))
    uncached = CachedLookup(spec, plan, host, mesh=mesh)
    for lo in range(0, len(ids), 32):
        chunk = ids[lo:lo + 32]
        rows_c, _ = cached(chunk)
        rows_u, _ = uncached(chunk)
        np.testing.assert_array_equal(rows_c, rows_u)
        np.testing.assert_array_equal(rows_u, host[chunk])
    assert cached.hits > 0 and cached.hit_rate > 0.5
    assert uncached.hits == 0


@pytest.mark.parametrize("kind", PLAN_KINDS)
def test_update_rows_touched_refresh_restores_parity(mesh, table, kind):
    spec, host = table
    lk = CachedLookup(spec, make_plan(kind), host, mesh=mesh,
                      cache=CacheConfig(rows=24))
    ids = _zipf_ids(128, spec.rows, seed=3)
    lk(ids)                                       # warm the cache
    hot = np.asarray(lk.cache.ids)
    assert hot.size > 0
    new_rows = np.full((hot.size, spec.dim), 7.5, np.float32)

    # refresh=False: the replica serves stale bytes for cached rows —
    # staleness persists across lookups/elections (election is incremental)
    stale = CachedLookup(spec, make_plan(kind), host, mesh=mesh,
                         cache=CacheConfig(rows=24))
    stale(ids)
    stale.update_rows(hot, new_rows, refresh=False)
    got, _ = stale(hot)
    assert not np.array_equal(got, new_rows)
    # rows-touched refresh restores exactness
    stale.refresh_touched(hot)
    got, _ = stale(hot)
    np.testing.assert_array_equal(got, new_rows)

    # refresh=True (the default) is exact immediately
    touched = lk.update_rows(hot, new_rows)
    assert set(np.asarray(touched).tolist()) == set(hot.tolist())
    got, _ = lk(hot)
    np.testing.assert_array_equal(got, new_rows)


# ---------------------------------------------------------------------------
# traffic: candidate sets
# ---------------------------------------------------------------------------

def test_candidates_leave_base_workload_unperturbed():
    base_cfg = TrafficConfig(n_requests=32, vocab_size=64, seed=5)
    with_cand = generate(TrafficConfig(n_requests=32, vocab_size=64, seed=5,
                                       candidates=8))
    base = generate(base_cfg)
    assert all(r.candidates is None for r in base)
    for b, c in zip(base, with_cand):
        assert len(c.candidates) == 8
        assert all(0 <= i < 64 for i in c.candidates)
        assert (b.prompt, b.user_id, b.arrival, b.max_new_tokens, b.slo,
                b.temperature) == (c.prompt, c.user_id, c.arrival,
                                   c.max_new_tokens, c.slo, c.temperature)
    # deterministic under the seed
    again = generate(TrafficConfig(n_requests=32, vocab_size=64, seed=5,
                                   candidates=8))
    assert [r.candidates for r in again] == [r.candidates for r in with_cand]


def test_candidate_sets_are_head_heavy():
    reqs = generate(TrafficConfig(n_requests=64, vocab_size=256,
                                  candidates=16, zipf_items=1.3))
    ids = np.concatenate([np.asarray(r.candidates) for r in reqs])
    head = (ids < 26).mean()              # top 10% of the item vocab
    assert head > 0.5, head


# ---------------------------------------------------------------------------
# engine integration: scores + tokens identical cached vs uncached
# ---------------------------------------------------------------------------

class _ToyBackend:
    """Deterministic toy: next token = (last token + 1) mod V."""
    V = 64

    def init_cache(self, n_slots, max_len):
        return {"len": np.zeros(n_slots, np.int64)}

    def prefill(self, cache, tokens, true_len, slot):
        logits = np.zeros(self.V, np.float32)
        logits[(int(tokens[0, true_len - 1]) + 1) % self.V] = 1.0
        return logits, cache

    def decode(self, cache, tokens):
        B = tokens.shape[0]
        logits = np.zeros((B, 1, self.V), np.float32)
        for b in range(B):
            logits[b, 0, (int(tokens[b, 0]) + 1) % self.V] = 1.0
        return logits, cache


def _run(reqs, cf_head, tracer=None, metrics=None):
    engine = ServingEngine(_ToyBackend(), EngineConfig(n_slots=4, max_len=64),
                           Clock(0.01, 0.05, None, 0.002),
                           tracer=tracer, metrics=metrics, cf_head=cf_head)
    outputs, recs, summary = engine.run(reqs)
    return engine, outputs, recs, summary


@pytest.mark.parametrize("kind", PLAN_KINDS)
def test_engine_cf_scores_exact_cached_vs_uncached(mesh, kind):
    reqs = generate(TrafficConfig(n_requests=16, rate=200.0, vocab_size=64,
                                  n_users=100, candidates=12, prompt_max=16))
    heads = {rows: CFHead.build(n_users=100, n_items=64, cf_dim=8, plan=kind,
                                cache_rows=rows, mesh=mesh)
             for rows in (0, 32)}
    runs = {rows: _run(reqs, head) for rows, head in heads.items()}
    eng_c, out_c, _, s_c = runs[32]
    eng_u, out_u, _, s_u = runs[0]
    assert out_c == out_u                     # token streams untouched
    assert s_c["cf"]["requests_scored"] == len(reqs)
    assert s_c["cf"]["hit_rate"] > 0.5
    assert s_u["cf"]["hits"] == 0
    for rid in eng_u.cf_results:
        rc, ru = eng_c.cf_results[rid], eng_u.cf_results[rid]
        np.testing.assert_array_equal(rc["cf"], ru["cf"])
        np.testing.assert_array_equal(rc["fused"], ru["fused"])
        np.testing.assert_array_equal(rc["ranking"], ru["ranking"])
        assert set(rc["ranking"]) == set(reqs[rid].candidates)


def test_engine_cf_obs_spans_and_counters(mesh):
    reqs = generate(TrafficConfig(n_requests=12, rate=200.0, vocab_size=64,
                                  n_users=100, candidates=8, prompt_max=16))
    tracer, metrics = Tracer(), MetricsRegistry()
    head = CFHead.build(n_users=100, n_items=64, cf_dim=8, plan="row",
                        cache_rows=24, mesh=mesh)
    _, _, recs, summary = _run(reqs, head, tracer=tracer, metrics=metrics)

    counters = metrics.snapshot()["counters"]
    assert counters["cf_cache.hits"] + counters["cf_cache.misses"] \
        == head.hits + head.misses
    assert "cf.lookup" in tracer.span_names()

    # cf time lands inside req.prefill, so ttft_reconciled stays green
    spans = {}
    for e in tracer.events:
        if e.get("ph") == "X" and "rid" in e.get("args", {}):
            spans.setdefault(e["args"]["rid"], {})[e["name"]] = e
    for r in recs:
        if r.finished is None:
            continue
        sp = spans[r.rid]
        cf, pf = sp["cf.lookup"], sp["req.prefill"]
        assert pf["ts"] <= cf["ts"]
        assert cf["ts"] + cf["dur"] <= pf["ts"] + pf["dur"] + 1e-9
        ttft = sp["req.queue_wait"]["dur"] + pf["dur"]
        assert ttft == pytest.approx(r.ttft, abs=1e-9)


def test_engine_without_candidates_skips_cf(mesh):
    reqs = generate(TrafficConfig(n_requests=6, rate=200.0, vocab_size=64,
                                  prompt_max=16))
    head = CFHead.build(n_users=100, n_items=64, cf_dim=8, mesh=mesh)
    engine, _, _, summary = _run(reqs, head)
    assert engine.cf_results == {}
    assert summary["cf"]["requests_scored"] == 0


# ---------------------------------------------------------------------------
# roofline: cf_lookup_bytes comms model
# ---------------------------------------------------------------------------

def test_cf_lookup_bytes_model():
    spec = EmbedSpec("cf_item", rows=1024, dim=32)
    mesh_shape = {"data": 2, "model": 4}
    for kind in ("row", "col", "row_col"):
        m = cf_lookup_bytes(spec, make_plan(kind), mesh_shape, batch=17,
                            hit_rate=0.6)
        assert m["uncached_bytes"] > 0
        assert m["cached_bytes"] == pytest.approx(0.4 * m["uncached_bytes"])
        assert m["saved_frac"] == pytest.approx(0.6)
        z = cf_lookup_bytes(spec, make_plan(kind), mesh_shape, batch=17)
        assert z["cached_bytes"] == z["uncached_bytes"]
    rep = cf_lookup_bytes(spec, make_plan("replicated"), mesh_shape,
                          batch=17, hit_rate=0.6)
    assert rep["uncached_bytes"] == 0 and rep["cached_bytes"] == 0
    # row+col plan exchanges at least as much as either single-axis plan
    row = cf_lookup_bytes(spec, make_plan("row"), mesh_shape, 17)
    both = cf_lookup_bytes(spec, make_plan("row_col"), mesh_shape, 17)
    assert both["uncached_bytes"] > 0 and row["uncached_bytes"] > 0
    with pytest.raises(ValueError):
        cf_lookup_bytes(spec, make_plan("row"), mesh_shape, 17, hit_rate=1.5)

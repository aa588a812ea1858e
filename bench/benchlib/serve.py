"""Serving cells: the engine under open-loop recommendation traffic.

Set-up builds the bench's weights and CF tables from the seed, the
program's paged flash-decode backend and its CF head, and warms every
program the window will run: each prefill bucket the mix can draw, the
decode step, copy-on-write, and each CF miss bucket.  The window then
drives ``ServingEngine.submit`` / ``tick`` on the host's wall clock:
every request is submitted when it is due (or as soon after as the loop
gets to it), and its TTFT runs from when it was due.  Requests due in the
window are measured to their end; arrivals go on while the last of them
finish, so they see the same load as the rest.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchlib import gen, weights
from benchlib.program import check_layout, program_config
from benchlib.runlog import Run, Spans


class WallClock:
    """The engine's clock, read from the host: a model call takes the time
    it takes and an idle wait is a real wait, so nothing is simulated."""

    fixed_decode_s = fixed_prefill_s = fixed_handoff_s = fixed_cf_s = None

    def __init__(self):
        self.t0 = time.perf_counter()

    @property
    def now(self) -> float:
        return time.perf_counter() - self.t0

    def advance(self, dt: float) -> None:
        """Time passes by itself."""


def cf_tables(c: dict, mix: dict, seed: int):
    """User and item factor tables, float32, entries normal with variance
    1/sqrt(dim) so that a CF score has unit scale next to the logits."""
    rng = gen._rng(seed, 0xCF)
    dim = mix["cf_dim"]
    s = dim ** -0.25
    users = (rng.standard_normal((mix["n_users"], dim)) * s)
    items = (rng.standard_normal((weights.padded_vocab(c), dim)) * s)
    return users.astype(np.float32), items.astype(np.float32)


@dataclasses.dataclass
class Setup:
    cfg: object
    c: dict
    mix: dict
    seed: int
    params: dict
    backend: object
    head: object
    ecfg: object
    users: np.ndarray
    items: np.ndarray
    gate: float


def build(c: dict, mix: dict, seed: int) -> Setup:
    import jax
    from repro.cache_layout import CacheLayout
    from repro.launch.mesh import make_host_mesh
    from repro.serving import CFHead, EngineConfig
    from repro.serving.cf_head import CFConfig
    from repro.serving.engine import make_backend

    cfg = program_config(c)
    params = weights.make_params(c, seed)
    check_layout(cfg, params)
    d = mix["deployment"]
    layout = CacheLayout(kind="paged", impl=d["decode_impl"],
                         block_size=d["block_size"])
    backend = make_backend(cfg, params, layout=layout)
    ecfg = EngineConfig(n_slots=d["slots"], max_len=d["max_len"],
                        queue_capacity=d["queue_capacity"],
                        prompt_quantum=d["prompt_quantum"],
                        sample_seed=seed & 0x7FFFFFFF, layout=layout)
    users, items = cf_tables(c, mix, seed)
    gate = float(mix["fusion_gate"])
    head = CFHead(users, items, fusion_gate=gate,
                  cfg=CFConfig(plan=d["cf_plan"],
                               cache_rows=d["cf_cache_rows"]),
                  mesh=make_host_mesh())
    jax.block_until_ready(params)
    return Setup(cfg, c, mix, seed, params, backend, head, ecfg, users,
                 items, gate)


def prefill_buckets(mix: dict) -> List[int]:
    d = mix["deployment"]
    q, cap = d["prompt_quantum"], d["max_len"]
    lo = -(-mix["prompt_min"] // q) * q
    hi = min(cap, -(-mix["prompt_max"] // q) * q)
    return list(range(lo, hi + 1, q))


def _engine(s: Setup, clock):
    from repro.serving import ServingEngine
    return ServingEngine(s.backend, s.ecfg, clock=clock, cf_head=s.head)


def _request(r: gen.ServeRequest):
    from repro.serving.traffic import BATCH_TIER, INTERACTIVE_TIER, Request
    return Request(rid=r.rid, user_id=r.user_id, prompt=r.prompt,
                   max_new_tokens=r.max_new_tokens, arrival=r.due,
                   slo=INTERACTIVE_TIER if r.interactive else BATCH_TIER,
                   temperature=gen.GREEDY, candidates=r.candidates)


def warm(s: Setup) -> None:
    """Run every program the window can: one request per prefill bucket
    (fresh users and tokens, so nothing is shared with the window's
    traffic), a repeated prompt whose shared tail block forces
    copy-on-write, and CF lookups that miss in each padded bucket."""
    rng = gen._rng(s.seed, 0xA17)
    vocab = s.c["vocab_size"]
    n_users = s.mix["n_users"]
    reqs = []
    buckets = prefill_buckets(s.mix)
    for i, b in enumerate(buckets):
        prompt = tuple(int(t) for t in rng.integers(3, vocab, size=b))
        reqs.append(gen.ServeRequest(i, i % n_users, prompt, 3, 0.0, True,
                                     None))
    eng = _engine(s, WallClock())
    for r in reqs:
        eng.submit(_request(r))
        while eng.has_work:
            eng.tick()
    # two requests in flight at once with one prompt that ends inside a
    # block: the second shares the first's blocks and copies on write
    cow = tuple(int(t) for t in rng.integers(3, vocab, size=buckets[0] - 3))
    for j in range(2):
        eng.submit(_request(gen.ServeRequest(len(reqs) + j, 0, cow, 3, 0.0,
                                             True, None)))
    cows = eng.pool.cow_events
    while eng.has_work:
        eng.tick()
    if eng.pool.cow_events == cows:
        raise RuntimeError("warm-up ran no copy-on-write")
    del eng
    gc.collect()
    # CF: fresh ids from the top of the item range miss the hot rows; one
    # call per padded miss bucket and a full candidate set through score
    n_items = s.items.shape[0]
    top = n_items - 1
    for n in _miss_sizes(s.mix):
        s.head.lookups["cf_item"](np.arange(top - n, top))
        top -= n
    s.head.lookups["cf_user"](np.asarray([n_users - 1]))
    lm = np.zeros(n_items, np.float32)
    s.head.score(n_users - 2, list(range(top - s.mix["candidates"], top)),
                 lm_logits_row=lm)


def _miss_sizes(mix: dict) -> List[int]:
    """One miss count in each bucket the CF lookup pads to (powers of two
    times its quantum of 8), up to a whole candidate set."""
    out, b = [], 8
    while True:
        out.append(min(b, mix["candidates"]))
        if b >= mix["candidates"]:
            return out
        b *= 2


def instrument(s: Setup, eng, spans: Spans, counts: Dict) -> None:
    """Host spans around the calls into each layer, ended by
    ``block_until_ready`` (the engine blocks right after anyway), plus the
    FLOPs and flash-decode bytes of each call from its shapes."""
    import jax
    from benchlib import costs
    be, head = s.backend, s.head
    prefill, decode, score = be.prefill, be.decode, head.score

    def timed_prefill(cache, tokens, true_len, slot, **kw):
        with spans.span("model.prefill"):
            out = prefill(cache, tokens, true_len, slot, **kw)
            jax.block_until_ready(out)
        counts["flops"] += costs.prefill_flops(s.c, int(true_len))
        return out

    def timed_decode(cache, tokens, *a):
        rows = [rec.prompt_len + rec.tokens_out for rec in eng.records
                if rec.first_token is not None and rec.finished is None]
        with spans.span("model.decode_step"):
            out = decode(cache, tokens, *a)
            jax.block_until_ready(out)
        flops = costs.decode_flops(s.c, rows)
        counts["flops"] += flops
        counts["decode_flops"] += flops
        counts["decode_steps"] += 1
        if spans.annotate:          # inside the profiled stretch
            counts["traced_decode_attn_bytes"] += costs.decode_attn_bytes(
                s.c, rows)
        return out

    def timed_score(*a, **kw):
        with spans.span("cf.score"):
            return score(*a, **kw)

    be.prefill, be.decode, head.score = (timed_prefill, timed_decode,
                                         timed_score)


def uninstrument(s: Setup) -> None:
    for obj, names in ((s.backend, ("prefill", "decode")),
                       (s.head, ("score",))):
        for n in names:
            obj.__dict__.pop(n, None)


@dataclasses.dataclass
class Window:
    reqs: List[gen.ServeRequest]
    n_window: int
    records: Dict[int, object]
    outputs: Dict[int, List[int]]
    cf: Dict[int, Dict]
    late: List[float]
    tokens_in_window: int
    elapsed: float
    compiles: int
    failed: int
    hits: int
    misses: int
    counts: Dict[str, float]


def run_window(s: Setup, seconds: float, spans: Optional[Spans] = None,
               counter=None, rate: Optional[float] = None,
               trace=None, drain_s: float = 60.0) -> Window:
    """Drive the engine for ``seconds`` of arrivals at the mix's rate (or
    ``rate``), then until every request due in the window has finished
    (at most ``drain_s`` more), with later arrivals still coming."""
    mix = s.mix
    vocab, n_items = s.c["vocab_size"], s.items.shape[0]
    reqs = gen.serve_requests(mix, s.seed, seconds, vocab, n_items,
                              rate=rate)
    n_win = len(reqs)
    more = gen.serve_requests(mix, s.seed + 1, drain_s, vocab, n_items,
                              rate=rate, first_rid=n_win, start=seconds)
    allreqs = reqs + more
    pending = [_request(r) for r in allreqs]
    clock = WallClock()
    eng = _engine(s, clock)
    counts = {"flops": 0.0, "decode_flops": 0.0, "decode_steps": 0,
              "traced_decode_attn_bytes": 0.0}
    if spans is not None:
        instrument(s, eng, spans, counts)
    hits0, miss0 = s.head.hits, s.head.misses
    late = []
    i = 0
    tokens_at_close = None
    elapsed = None
    longest = 0.0
    pool_used = []
    gc.collect()
    gc.freeze()     # set-up's objects: no collection in the window scans them
    gc0 = gc.get_stats()[2]["collections"]
    if counter is not None:
        counter.on = True
    if trace is not None:
        trace.start()
    clock.t0 = time.perf_counter()
    sp = spans if spans is not None else Spans(enabled=False)
    while True:
        now = clock.now
        with sp.span("bench.submit"):
            while i < len(pending) and pending[i].arrival <= now:
                eng.submit(pending[i])
                if i < n_win:
                    late.append(clock.now - pending[i].arrival)
                i += 1
        if tokens_at_close is None and now >= seconds:
            tokens_at_close = sum(len(v) for v in eng.outputs.values())
            counts["window_flops"] = counts["flops"]
            elapsed = now
        if now >= seconds and _window_done(eng, n_win):
            break
        if now >= seconds + drain_s:
            break
        if eng.has_work:
            t_tick = time.perf_counter()
            with sp.span("bench.tick"):
                eng.tick()
            longest = max(longest, time.perf_counter() - t_tick)
            if now < seconds:
                pool_used.append(eng.pool.used_blocks)
        elif i < len(pending):
            with sp.span("bench.idle"):
                time.sleep(max(0.0, pending[i].arrival - clock.now))
    if counter is not None:
        counter.on = False
    if trace is not None:
        trace.stop()
    gc.unfreeze()
    blocks = eng.pool.num_blocks - 1        # block 0 is the null sink
    pool_pct = (100.0 * max(pool_used, default=0) / blocks,
                100.0 * float(np.mean(pool_used or [0])) / blocks)
    print(f"window: {counter.count if counter else 0} programs compiled, "
          f"{gc.get_stats()[2]['collections'] - gc0} full collections, "
          f"longest tick {longest * 1e3:.1f} ms, KV blocks in use "
          f"peak {pool_pct[0]:.1f}% mean {pool_pct[1]:.1f}% of {blocks}",
          file=sys.stderr)
    recs = {r.rid: r for r in eng.records if r.rid < n_win}
    failed = sum(1 for rid in range(n_win)
                 if rid not in recs or recs[rid].rejected
                 or recs[rid].finished is None)
    win = Window(reqs=reqs, n_window=n_win, records=recs,
                 outputs={k: list(v) for k, v in eng.outputs.items()
                          if k < n_win},
                 cf={k: v for k, v in eng.cf_results.items() if k < n_win},
                 late=late, tokens_in_window=tokens_at_close,
                 elapsed=elapsed, compiles=counter.count if counter else 0,
                 failed=failed, hits=s.head.hits - hits0,
                 misses=s.head.misses - miss0, counts=counts)
    if spans is not None:
        uninstrument(s)
    del eng
    gc.collect()
    return win


def _window_done(eng, n_win: int) -> bool:
    done = 0
    for r in eng.records:
        if r.rid < n_win and (r.finished is not None or r.rejected):
            done += 1
    return done >= n_win


def latencies(win: Window):
    """Each finished request's TTFT (first token minus due time) and TPOT
    (mean gap between its output tokens), from the engine's stamps on the
    wall clock."""
    due = {r.rid: r.due for r in win.reqs}
    ttft, tpot = [], []
    for rid, rec in win.records.items():
        if rec.first_token is not None:
            ttft.append(rec.first_token - due[rid])
        n = len(win.outputs.get(rid, ()))
        if rec.finished is not None and n > 1:
            tpot.append((rec.finished - rec.first_token) / (n - 1))
    return ttft, tpot


def end_to_end(win: Window) -> Dict[str, float]:
    ttft, tpot = latencies(win)
    return {
        "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
        "tpot_p95_ms": float(np.percentile(tpot, 95)) * 1e3,
        "serve_tokens_per_s": win.tokens_in_window / win.elapsed,
    }


def fill_run(run: Run, win: Window) -> None:
    """What the per-layer readers read."""
    run.values["gen.late_s"] = list(win.late)
    run.values["sched.queue_wait_s"] = [
        r.admitted - r.arrival for r in win.records.values()
        if r.admitted is not None]
    run.counters["serve.window_compiles"] = win.compiles
    run.counters["cf.hits"] = win.hits
    run.counters["cf.misses"] = win.misses
    run.counters.update(win.counts)
    # the FLOPs of the work done by the window's close, over the window:
    # the drain after it is not in the divisor
    run.counters["flops"] = win.counts.get("window_flops", 0.0)
    run.counters["window_s"] = win.elapsed

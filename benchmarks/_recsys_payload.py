"""Subprocess payload for the ``serve`` artifact's recsys section: the CF
scoring head inside the engine on an N-device host mesh, cached vs
uncached hot-row replica, per sharding plan.

Run:  python -m benchmarks._recsys_payload --mesh 2,4 --candidates 16
Prints one line ``BENCH_JSON:{...}``.

Each request is a full retrieval->rank call: LM prefill + sharded
cf_user/cf_item factor lookups + gated fusion + candidate ranking.  Per
plan the same workload runs twice — hot-row cache off, then on — and the
payload records the measured hit rate, the ids that actually took the
cross-shard exchange, the ring-modeled lookup bytes at the measured hit
rate, and the exactness flags the CI gate checks (fused scores, rankings
and token streams must be bit-identical with the cache on or off).
"""
import argparse
import json
import os

ap = argparse.ArgumentParser()
ap.add_argument("--mesh", default="2,4", help="data,model extents")
ap.add_argument("--requests", type=int, default=20)
ap.add_argument("--candidates", type=int, default=16)
ap.add_argument("--cache-rows", type=int, default=128)
ap.add_argument("--n-users", type=int, default=10_000)
ap.add_argument("--cf-dim", type=int, default=16)
ap.add_argument("--seed", type=int, default=0)
args = ap.parse_args()

_DP, _MP = (int(x) for x in args.mesh.split(","))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_DP * _MP}")
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual host devices, never a chip

import dataclasses  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import get_arch, reduced  # noqa: E402
from repro.embeddings import EmbedSpec, make_plan  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.serving import (CFHead, EngineConfig, ServingEngine,  # noqa: E402
                           TrafficConfig, cf_lookup_bytes, generate)
from repro.serving.engine import make_backend  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402

cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), dtype="float32")
params = tf.init_params(jax.random.PRNGKey(0), cfg)
mesh = make_host_mesh(data=_DP, model=_MP)
mesh_shape = dict(mesh.shape)

reqs = generate(TrafficConfig(
    n_requests=args.requests, rate=500.0, prompt_max=12, new_tokens_max=8,
    vocab_size=cfg.vocab_size, n_users=args.n_users, seed=args.seed,
    candidates=args.candidates))
# item table rows must divide by the row-axis extent; round the vocab up
n_items = -(-cfg.vocab_size // (8 * _MP)) * (8 * _MP)
backend = make_backend(cfg, params)
ecfg = EngineConfig(n_slots=4, max_len=64)


def head_for(plan, cache_rows):
    return CFHead.build(n_users=args.n_users, n_items=n_items,
                        cf_dim=args.cf_dim, seed=args.seed, plan=plan,
                        cache_rows=cache_rows, mesh=mesh)


def run(plan, cache_rows):
    # warm (compiles the LM buckets + this plan's shard_map lookups),
    # then a fresh head for clean hit/exchange counters
    ServingEngine(backend, ecfg,
                  cf_head=head_for(plan, cache_rows)).run(reqs)
    head = head_for(plan, cache_rows)
    engine = ServingEngine(backend, ecfg, cf_head=head)
    outputs, _, summary = engine.run(reqs)
    scores = {rid: (r["cf"].tolist(), r["fused"].tolist(),
                    r["ranking"].tolist())
              for rid, r in engine.cf_results.items()}
    exchanged = sum(lk.exchanged_ids for lk in head.lookups.values())
    return outputs, scores, summary, head, exchanged


item_spec = EmbedSpec("cf_item", rows=n_items, dim=args.cf_dim)
out = {"mesh": mesh_shape, "devices": mesh.size,
       "requests": args.requests, "candidates": args.candidates,
       "cache_rows": args.cache_rows, "n_users": args.n_users,
       "n_items": n_items, "plans": {}}
for plan in ("replicated", "row", "col", "row_col"):
    uo, us, usum, _, u_ex = run(plan, 0)
    co, cs, csum, chead, c_ex = run(plan, args.cache_rows)
    hr = chead.hit_rate
    # modeled wire bytes of one request's lookups (user + candidates)
    # at the measured hit rate, on the training-side ring cost model
    modeled = cf_lookup_bytes(item_spec, make_plan(plan), mesh_shape,
                              batch=args.candidates + 1, hit_rate=hr)
    out["plans"][plan] = {
        "hit_rate": hr,
        "cache_rows_live": chead.cache_rows_live,
        "requests_scored": csum["cf"]["requests_scored"],
        "tok_s_cached": csum["throughput_tok_s"],
        "tok_s_uncached": usum["throughput_tok_s"],
        "exchanged_ids_cached": c_ex,
        "exchanged_ids_uncached": u_ex,
        "modeled": modeled,
        "scores_exact": bool(cs == us),
        "tokens_exact": bool(co == uo),
    }
print("BENCH_JSON:" + json.dumps(out))

"""Subprocess payload for the ``embed`` artifact: one embedding sharding
plan on N host devices — measured host step time, exchanged bytes from the
compiled HLO, per-device table memory, and roofline-modeled TPU terms.

Run:  python -m benchmarks._embed_payload --plan row --mesh 2,4 ...
Prints one line ``BENCH_JSON:{...}``.

The train step is one embedding-lookup step distilled from the recsys
model: Zipfian ids -> sharded lookup -> MSE against a target -> table-
gradient sync -> SGD row update, all inside shard_map so every exchange is
an explicit collective the cost analyzer can count.  ``--grad-sync
sparse`` swaps the dense DP all-reduce for the rows-touched all-gather.
"""
import argparse
import json
import os

ap = argparse.ArgumentParser()
ap.add_argument("--plan", required=True,
                choices=("replicated", "row", "col", "row_col"))
ap.add_argument("--mesh", default="2,4", help="data,model extents")
ap.add_argument("--grad-sync", default="dense", choices=("dense", "sparse"))
ap.add_argument("--rows", type=int, default=16384)
ap.add_argument("--dim", type=int, default=64)
ap.add_argument("--batch", type=int, default=1024, help="global ids/step")
ap.add_argument("--steps", type=int, default=5)
ap.add_argument("--zipf", type=float, default=1.3)
args = ap.parse_args()

_DP, _MP = (int(x) for x in args.mesh.split(","))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_DP * _MP}")
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual host devices, never a chip

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402

from repro.analysis import hlo_cost  # noqa: E402
from repro.config import (DCI_BW_PER_LINK, HBM_BW, ICI_BW_PER_LINK,  # noqa: E402
                          PEAK_FLOPS_BF16)
from repro.embeddings import (EmbedSpec, make_plan, named_sharding,  # noqa: E402
                              plan_summary, pspec, shard_bytes,
                              sharded_lookup_body, sparse_row_sync)
from repro.launch.mesh import make_host_mesh  # noqa: E402

mesh = make_host_mesh(data=_DP, model=_MP)
spec = EmbedSpec("bench", rows=args.rows, dim=args.dim)
plan = make_plan(args.plan)
mesh_shape = dict(mesh.shape)

rng = np.random.default_rng(0)
# Zipfian ids (recsys popularity skew) — what makes dedup worthwhile
ids_np = np.minimum(rng.zipf(args.zipf, size=(args.steps + 2, args.batch))
                    - 1, args.rows - 1).astype(np.int32)
tgt_np = rng.normal(size=(args.batch, args.dim)).astype(np.float32)
table0 = (rng.normal(size=(args.rows, args.dim)) * 0.02).astype(np.float32)

LR = 0.1


def body(tshard, ids_loc, tgt_loc):
    def loss_fn(ts):
        out = sharded_lookup_body(ts, ids_loc, plan)
        return 0.5 * jnp.mean((out - tgt_loc) ** 2)

    loss, g = jax.value_and_grad(loss_fn)(tshard)
    loss = jax.lax.pmean(loss, ("data", "model"))
    if plan.col_axis is None:
        # shard replicated across DP ranks -> gradients need the DP sync
        if args.grad_sync == "sparse":
            vr = tshard.shape[0]
            if plan.row_axis:
                lo = jax.lax.axis_index(plan.row_axis) * vr
                local = ids_loc - lo
                local = jnp.where((local >= 0) & (local < vr), local, vr)
            else:
                local = ids_loc
            g = sparse_row_sync(g, local, ("data",))
        else:
            g = jax.lax.pmean(g, "data")
    # col plans: each DP rank owns distinct columns — no table sync at all
    return tshard - LR * g, loss


tspec = pspec(plan)
step = jax.jit(
    shard_map(body, mesh=mesh,
              in_specs=(tspec, P("data"), P("data")),
              out_specs=(tspec, P()),
              check_vma=False),
    donate_argnums=(0,))

table = jax.device_put(jnp.asarray(table0), named_sharding(mesh, plan))
tgt = jax.device_put(jnp.asarray(tgt_np), NamedSharding(mesh, P("data")))
put_ids = lambda a: jax.device_put(  # noqa: E731
    jnp.asarray(a), NamedSharding(mesh, P("data")))

# AOT-compile once: the optimized HLO text is what the analyzer costs
# (the tables are f32 throughout, so the post-optimization byte sizes the
# analyzer sees match the lowering-time ones)
compiled = step.lower(table, put_ids(ids_np[0]), tgt).compile()
hlo_text = compiled.as_text()

table, loss = step(table, put_ids(ids_np[0]), tgt)       # compile + warm
jax.block_until_ready(loss)
t0 = time.perf_counter()
losses = []
for s in range(1, args.steps + 1):
    table, loss = step(table, put_ids(ids_np[s]), tgt)
    losses.append(float(loss))
dt = (time.perf_counter() - t0) / args.steps

costs = hlo_cost.analyze(hlo_text, mesh.size)
t_compute = costs.flops / PEAK_FLOPS_BF16
t_memory = costs.bytes / HBM_BW
t_coll = (costs.coll_intra / ICI_BW_PER_LINK
          + costs.coll_cross / DCI_BW_PER_LINK)

# per-device table memory at this mesh, and the ~1/N scaling curve
tb = shard_bytes(spec, plan, mesh_shape)
scaling = {}
for n in (1, 2, 4, 8):
    ms = {"data": max(n // _MP, 1) if _MP > 1 else n,
          "model": min(n, _MP)}
    try:
        scaling[n] = shard_bytes(spec, plan, ms)
    except ValueError:
        pass

out = {
    "plan": args.plan, "grad_sync": args.grad_sync,
    "mesh": mesh_shape, "devices": mesh.size,
    "rows": args.rows, "dim": args.dim, "batch": args.batch,
    "host_step_ms": dt * 1e3,
    "losses": losses[:5],
    "coll_bytes_per_dev": costs.coll_total,
    "coll_by_op": {k: v for k, v in costs.coll_bytes.items() if v},
    "bytes_per_dev": costs.bytes,
    "flops_per_dev": costs.flops,
    "table_bytes_per_dev": tb,
    "table_bytes_scaling": scaling,
    "t_compute_ms": t_compute * 1e3,
    "t_memory_ms": t_memory * 1e3,
    "t_collective_ms": t_coll * 1e3,
    "modeled": plan_summary(spec, plan, mesh_shape,
                            args.batch // mesh_shape["data"]),
}
print("BENCH_JSON:" + json.dumps(out))

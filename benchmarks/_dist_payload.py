"""Subprocess payload for distributed benchmarks: builds one parallelism
scheme on N host devices, measures real step wall-time, and derives the
roofline/communication profile from the compiled HLO.

Run:  python -m benchmarks._dist_payload --scheme hybrid --devices 8 ...
Prints one line ``BENCH_JSON:{...}``.
"""
import argparse
import json
import os
import sys
import tempfile

ap = argparse.ArgumentParser()
ap.add_argument("--scheme", required=True,
                choices=("baseline", "dp", "mp", "hybrid", "hybrid_auto"))
ap.add_argument("--devices", type=int, default=8)
ap.add_argument("--steps", type=int, default=8)
ap.add_argument("--batch", type=int, default=32)
ap.add_argument("--seq", type=int, default=64)
ap.add_argument("--layers", type=int, default=4)
ap.add_argument("--d-model", type=int, default=128)
ap.add_argument("--sync", default="flat",
                choices=("flat", "hierarchical", "onebit", "topk"))
args = ap.parse_args()

_DUMP = tempfile.mkdtemp(prefix="bench_dump_")
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={args.devices}"
    f" --xla_dump_to={_DUMP}"
    " --xla_dump_hlo_pass_re=all-reduce-promotion"
    " --xla_dump_large_constants=false")
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual host devices, never a chip

import dataclasses  # noqa: E402
import glob  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis import hlo_cost  # noqa: E402
from repro.config import (PEAK_FLOPS_BF16, HBM_BW, ICI_BW_PER_LINK,  # noqa: E402
                          DCI_BW_PER_LINK, TrainConfig, ParallelConfig,
                          ShapeConfig, get_arch, reduced)
from repro.core.hybrid import auto_plan  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.optimizer import adamw  # noqa: E402
from repro.runtime import trainer  # noqa: E402
from repro.data import pipeline  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402


def make_mesh(scheme, n):
    if scheme == "baseline":
        return make_host_mesh()
    if scheme == "dp":
        return make_host_mesh(data=n, model=1)
    if scheme == "mp":
        return make_host_mesh(data=1, model=n)
    return make_host_mesh(data=n // 2, model=2)


cfg = dataclasses.replace(
    reduced(get_arch("recllm-base")),
    num_layers=args.layers, d_model=args.d_model,
    num_heads=8, num_kv_heads=8, head_dim=args.d_model // 8,
    d_ff=args.d_model * 4, vocab_size=8192, vocab_pad_to=256,
    dtype="float32")
mesh = make_mesh(args.scheme, args.devices)
shape = ShapeConfig("bench", args.seq, args.batch, "train")
plan = auto_plan(cfg, mesh, shape, ParallelConfig())
tcfg = TrainConfig(steps=args.steps, checkpoint_every=0)

step, jitted, shardings_for = trainer.make_hybrid_train_step(cfg, plan, tcfg)
params = tf.init_params(jax.random.PRNGKey(0), cfg)
opt = adamw.init_opt_state(params)
data = list(pipeline.synthetic_lm_batches(cfg.vocab_size, args.batch,
                                          args.seq, args.steps + 3))
fn = jitted(jax.eval_shape(lambda: params), data[0])

losses = []
if args.devices <= 16:
    # measured wall time (host CPU — relative only; modeled numbers below)
    params, opt, m = fn(params, opt, data[0])
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for b in data[1:args.steps + 1]:
        params, opt, m = fn(params, opt, b)
        losses.append(float(m["loss"]))
    dt = (time.perf_counter() - t0) / args.steps
else:
    # >16 virtual devices on one core aborts XLA:CPU thunk execution;
    # compile-only (the roofline numbers come from the dump anyway)
    fn.lower(jax.eval_shape(lambda: params),
             jax.eval_shape(lambda: opt), data[0]).compile()
    dt = float("nan")

# roofline from the dump
files = sorted(glob.glob(os.path.join(_DUMP, "*jit_step*"
                                      "before_all-reduce-promotion.txt")))
costs = hlo_cost.analyze(open(files[-1]).read() if files else "",
                         mesh.size)
t_compute = costs.flops / PEAK_FLOPS_BF16
t_memory = costs.bytes / HBM_BW
t_coll = (costs.coll_intra / ICI_BW_PER_LINK
          + costs.coll_cross / DCI_BW_PER_LINK)
t_bound = max(t_compute, t_memory, t_coll, 1e-12)

out = {
    "scheme": args.scheme, "devices": mesh.size,
    "host_step_ms": dt * 1e3,
    "losses": losses[:5],
    "flops_per_dev": costs.flops,
    "bytes_per_dev": costs.bytes,
    "coll_bytes_per_dev": costs.coll_total,
    "t_compute_ms": t_compute * 1e3,
    "t_memory_ms": t_memory * 1e3,
    "t_collective_ms": t_coll * 1e3,
    "modeled_throughput": args.batch / t_bound,
    "comm_fraction": t_coll / (t_coll + max(t_compute, t_memory)),
}
print("BENCH_JSON:" + json.dumps(out))

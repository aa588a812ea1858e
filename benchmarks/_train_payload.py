"""Subprocess payload for the ``train-parallel`` artifact: run ONE
parallelism scheme of the unified training path end-to-end on N host
devices and report measured host step time + losses.

Schemes (8 devices): ``dp`` = shard_map DP-8 (flat sync), ``tp`` = GSPMD
TP-8, ``pp`` = pipeline-only 1x1x8, ``hybrid`` = DP2 x TP2 x PP2 through
``make_pp_train_step``.  Prints one line ``BENCH_JSON:{...}``.
"""
import argparse
import json
import os

ap = argparse.ArgumentParser()
ap.add_argument("--scheme", required=True,
                choices=("dp", "tp", "pp", "hybrid"))
ap.add_argument("--devices", type=int, default=8)
ap.add_argument("--steps", type=int, default=4)
ap.add_argument("--batch", type=int, default=16)
ap.add_argument("--seq", type=int, default=32)
ap.add_argument("--layers", type=int, default=8)
ap.add_argument("--pp-micro", type=int, default=4)
ap.add_argument("--schedule", default="1f1b", choices=("1f1b", "gpipe"))
args = ap.parse_args()

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={args.devices}")
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual host devices, never a chip

import dataclasses  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import (ParallelConfig, ShapeConfig, TrainConfig,  # noqa: E402
                          get_arch, reduced)
from repro.core.hybrid import auto_plan  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import layers as L, transformer as tf  # noqa: E402
from repro.optimizer import adamw  # noqa: E402
from repro.runtime import trainer  # noqa: E402

cfg = dataclasses.replace(reduced(get_arch("olmo-1b")),
                          num_layers=args.layers, dtype="float32")
ctx = tf.ModelCtx(attn_chunk=8)
tcfg = TrainConfig(steps=args.steps, checkpoint_every=0)
params = tf.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
batches = [{"tokens": jnp.asarray(rng.integers(3, cfg.vocab_size,
                                               (args.batch, args.seq)),
                                  jnp.int32),
            "targets": jnp.asarray(rng.integers(3, cfg.vocab_size,
                                                (args.batch, args.seq)),
                                   jnp.int32),
            "mask": jnp.ones((args.batch, args.seq), jnp.float32)}
           for _ in range(args.steps + 1)]


def ref_loss(p, b):
    logits, _, _ = tf.forward(cfg, p, b, ctx)
    nll = L._nll(logits, b["targets"])
    return jnp.sum(nll * b["mask"]) / jnp.sum(b["mask"])


losses = []
if args.scheme == "dp":
    mesh = make_host_mesh(data=args.devices)
    scfg = trainer.DPSyncConfig(mode="flat")
    opt = adamw.init_opt_state(params)
    resid = jnp.zeros((args.devices, trainer.residual_size(params, scfg)))
    step = trainer.make_dp_train_step(ref_loss, mesh, tcfg, scfg)

    def run(p, o, r, b):
        p, o, r, loss = step(p, o, r, b)
        return p, o, r, loss

    state = (params, opt, resid)
elif args.scheme == "tp":
    mesh = make_host_mesh(data=1, model=args.devices)
    shape = ShapeConfig("bench", args.seq, args.batch, "train")
    plan = auto_plan(cfg, mesh, shape, ParallelConfig())
    step, jitted, _ = trainer.make_hybrid_train_step(cfg, plan, tcfg)
    opt = adamw.init_opt_state(params)
    fn = jitted(jax.eval_shape(lambda: params), batches[0])

    def run(p, o, r, b):
        p, o, m = fn(p, o, b)
        return p, o, r, m["loss"]

    state = (params, opt, None)
else:
    if args.scheme == "pp":
        dp, tp, pp = 1, 1, args.devices
    else:
        dp, tp, pp = 2, 2, 2
    mesh = make_host_mesh(data=dp, model=tp, stage=pp)
    shape = ShapeConfig("bench", args.seq, args.batch, "train")
    plan = auto_plan(cfg, mesh, shape,
                     ParallelConfig(dp=dp, tp=tp, pp=pp,
                                    microbatches=args.pp_micro,
                                    pp_schedule=args.schedule))
    bounds = list(plan.stage_bounds)
    scfg = trainer.DPSyncConfig(mode="flat")
    pp_params = tf.pp_partition_params(cfg, params, bounds)
    pp_shape = jax.eval_shape(lambda: pp_params)
    opt = adamw.init_opt_state(
        trainer.pp_trainable(pp_params, cfg.tie_embeddings))
    resid = jnp.zeros((dp, tp, pp,
                       trainer.pp_residual_size(cfg, pp_shape, mesh, scfg)))
    step = trainer.make_pp_train_step(cfg, mesh, tcfg, bounds, pp_shape,
                                      n_micro=args.pp_micro,
                                      pp_schedule=args.schedule, scfg=scfg,
                                      ctx=ctx)

    def run(p, o, r, b):
        return step(p, o, r, b)

    state = (pp_params, opt, resid)

p, o, r = state
p, o, r, loss = run(p, o, r, batches[0])            # compile + warm
jax.block_until_ready(loss)
t0 = time.perf_counter()
for b in batches[1:]:
    p, o, r, loss = run(p, o, r, b)
    losses.append(float(loss))
dt = (time.perf_counter() - t0) / args.steps

print("BENCH_JSON:" + json.dumps({
    "scheme": args.scheme, "devices": args.devices,
    "schedule": args.schedule if args.scheme in ("pp", "hybrid") else None,
    "host_step_ms": dt * 1e3,
    "losses": losses[:6],
}))

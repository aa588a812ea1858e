"""Training launcher: any --arch at any scale on the available devices.

On a TPU host this is the per-host entrypoint (jax.distributed handles
multi-host); on a CPU it runs reduced configs end-to-end with the full
runtime (hybrid sharding plan, ZeRO-1/2, remat, checkpoints, prefetch,
straggler-aware data allocation).  The train state is built sharded by the
plan from the start: no device holds a whole copy of it.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --steps 50 --batch 16 --seq 64

``--pp-stages N`` switches to the pipelined DP x TP x stage path: the
planner's balanced layer bounds slice the transformer into stages, the
1F1B (or GPipe, ``--pp-schedule``) schedule drives them over ``--pp-micro``
micro-batches, and DP gradient sync composes across the ``data`` axis:

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --host-devices 8 --data 2 --model 2 --pp-stages 2 --pp-micro 4 \
      --steps 10 --batch 16 --seq 32
"""
import argparse
import dataclasses
import os


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size config (CPU-friendly)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to its first N layers, widths "
                         "unchanged (0 = the published depth)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the random initial weights")
    ap.add_argument("--data", type=int, default=1, help="dp mesh size")
    ap.add_argument("--model", type=int, default=1, help="tp mesh size")
    ap.add_argument("--pp-stages", type=int, default=1,
                    help="pipeline stages (>1 enables the pipelined path)")
    ap.add_argument("--pp-micro", type=int, default=4,
                    help="pipeline micro-batches per step")
    ap.add_argument("--pp-schedule", default="1f1b",
                    choices=("1f1b", "gpipe"))
    ap.add_argument("--pp-rebalance-every", type=int, default=0,
                    help="every K steps, re-carve the layer->stage bounds "
                         "from measured per-stage times and live-remap "
                         "params/optimizer (0 = off)")
    ap.add_argument("--grad-sync", default="flat",
                    choices=("flat", "hierarchical", "onebit", "topk"),
                    help="DP gradient sync mode on the pipelined path")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N virtual host devices on the CPU platform "
                         "(set before jax initializes; needed for "
                         "--pp-stages without N chips)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--trace-out", default="",
                    help="write the training span timeline here (train_step "
                         "/ rebalance.probe / checkpoint spans, plus "
                         "per-stage stage_tick spans from rebalance probes "
                         "on the pipelined path): .jsonl for raw events, "
                         "anything else for Chrome-trace/Perfetto JSON")
    return ap


def run(args):
    """Build the mesh, the plan and the sharded train state, then run the
    loop.  Returns the :class:`repro.runtime.trainer.TrainResult`.

    The state is initialized by a jit whose ``out_shardings`` come from
    the plan, so no device ever holds a whole copy of it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.config import (ParallelConfig, ShapeConfig, TrainConfig,
                              get_arch, list_archs, reduced)
    from repro.core.hybrid import auto_plan
    from repro.data import pipeline
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.obs import Tracer, write_trace
    from repro.optimizer import adamw
    from repro.runtime import trainer

    if args.arch not in list_archs():
        raise SystemExit(f"unknown arch {args.arch}; have {list_archs()}")
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(reduced(cfg), dtype="float32")
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    pp = max(args.pp_stages, 1)
    mesh = make_host_mesh(data=args.data, model=args.model,
                          stage=pp if pp > 1 else 0)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    pcfg = ParallelConfig(dp=args.data, tp=args.model, pp=pp,
                          microbatches=args.pp_micro,
                          pp_schedule=args.pp_schedule)
    plan = auto_plan(cfg, mesh, shape, pcfg)
    tcfg = TrainConfig(steps=args.steps, learning_rate=args.lr,
                       warmup_steps=max(args.steps // 20, 2),
                       checkpoint_dir=args.ckpt_dir,
                       checkpoint_every=max(args.steps // 4, 10))
    key = jax.random.PRNGKey(args.seed)

    def named(specs):
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                            is_leaf=lambda x: isinstance(
                                x, jax.sharding.PartitionSpec))

    tracer = Tracer() if args.trace_out else None
    params_shape = jax.eval_shape(lambda: tf.init_params(key, cfg))
    n = sum(x.size for x in jax.tree.leaves(params_shape))
    print(f"{cfg.name}: {n/1e6:.1f}M params on mesh "
          f"data={args.data} model={args.model} stage={pp}; "
          f"plan notes: {plan.notes}")

    def gen(start):
        for b in pipeline.synthetic_lm_batches(
                cfg.vocab_size, args.batch, args.seq,
                args.steps - start, seed=start):
            b = {k: jnp.asarray(v) for k, v in b.items()}
            if cfg.encoder_layers:
                b["frames"] = jnp.zeros(
                    (args.batch, cfg.encoder_frames, cfg.d_model),
                    jnp.dtype(cfg.dtype))
            if cfg.pos_type == "mrope":
                s_img = int(cfg.image_prefix_frac * args.seq)
                b["patch_embeds"] = jnp.zeros(
                    (args.batch, s_img, cfg.d_model), jnp.dtype(cfg.dtype))
                b["positions"] = jnp.broadcast_to(
                    jnp.arange(args.seq)[None, :, None],
                    (args.batch, args.seq, 3)).astype(jnp.int32)
            yield b

    if pp > 1:
        # --- pipelined DP x TP x stage path ------------------------------
        bounds = list(plan.stage_bounds)
        scfg = trainer.DPSyncConfig(mode=args.grad_sync)

        def init_pp():
            pp_params = tf.pp_partition_params(
                cfg, tf.init_params(key, cfg), bounds)
            return {"params": pp_params,
                    "opt": adamw.init_opt_state(trainer.pp_trainable(
                        pp_params, cfg.tie_embeddings))}

        pp_shape = jax.eval_shape(init_pp)["params"]
        res_shape = (args.data, args.model, pp,
                     trainer.pp_residual_size(cfg, pp_shape, mesh, scfg))
        pspec, ospec, rspec = trainer.pp_state_specs(cfg, mesh, pp_shape,
                                                     scfg)
        state = jax.jit(lambda: {**init_pp(),
                                 "residual": jnp.zeros(res_shape)},
                        out_shardings=named({"params": pspec,
                                             "opt": ospec,
                                             "residual": rspec}))()
        state["stage_bounds"] = jnp.asarray(bounds, jnp.int32)
        start = 0
        if args.resume:
            start, state = trainer.resume_or_init(state, tcfg)
            # checkpoints restore by key (shapes come from disk): a run
            # rebalanced mid-flight restores its moved carve points, and
            # the step must be rebuilt at THOSE bounds, not the planner's
            bounds = [int(b) for b in state["stage_bounds"]]
            pp_shape = jax.eval_shape(lambda: state["params"])
        step_fn = trainer.make_pp_train_step(
            cfg, mesh, tcfg, bounds, pp_shape, n_micro=args.pp_micro,
            pp_schedule=args.pp_schedule, scfg=scfg)
        rebal = None
        if args.pp_rebalance_every:
            rebal = trainer.PPRebalancer(
                cfg, mesh, tcfg, bounds, n_micro=args.pp_micro,
                pp_schedule=args.pp_schedule, scfg=scfg, tracer=tracer)
        res_run = trainer.train_loop(
            state, gen(start), step_fn, tcfg, start_step=start,
            samples_per_batch=args.batch, verbose=True,
            rebalance_every=args.pp_rebalance_every, rebalance_fn=rebal,
            log_every=max(args.steps // 10, 1), tracer=tracer)
        if rebal is not None and len(rebal.history) > 1:
            print(f"stage bounds rebalanced {len(rebal.history) - 1}x: "
                  f"{rebal.history[0]} -> {rebal.history[-1]}")
    else:
        # --- GSPMD hybrid path (TP x DP) ---------------------------------
        step, jitted, shardings_for = trainer.make_hybrid_train_step(
            cfg, plan, tcfg)
        batch0 = next(iter(gen(0)))
        psh, osh, _ = shardings_for(params_shape, batch0)

        def init_hybrid():
            params = tf.init_params(key, cfg)
            return {"params": params, "opt": adamw.init_opt_state(params)}

        state = jax.jit(init_hybrid,
                        out_shardings={"params": psh, "opt": osh})()
        start = 0
        if args.resume:
            start, state = trainer.resume_or_init(state, tcfg)
        fn = jitted(params_shape, batch0)
        res_run = trainer.train_loop(
            state, gen(start), fn, tcfg, start_step=start,
            samples_per_batch=args.batch, verbose=True,
            log_every=max(args.steps // 10, 1), tracer=tracer)
    if args.trace_out:
        nev = write_trace(args.trace_out, tracer)
        print(f"trace: {nev} events -> {args.trace_out} "
              f"(open at https://ui.perfetto.dev)")
    return res_run


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.host_devices:
        # virtual devices exist only on the host platform: pin it, so a
        # machine with a chip never hands this simulation the accelerator
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.host_devices}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    res_run = run(args)
    print(f"done: {res_run.steps_run} steps, wall-clock throughput "
          f"{res_run.throughput:.1f} samples/s, final loss "
          f"{res_run.losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

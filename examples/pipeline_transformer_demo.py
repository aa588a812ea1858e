"""Pipeline parallelism (paper C2) over REAL transformer layers: an
olmo-family reduced model split into 4 balanced stages on a 'stage' mesh,
GPipe micro-batching via shard_map + ppermute, end-to-end gradient training.

Verifies pipelined loss == serial loss, then trains a few steps.

  PYTHONPATH=src python examples/pipeline_transformer_demo.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual host devices, never a chip

import dataclasses  # noqa: E402

import jax  # noqa: E402
from jax.sharding import AxisType  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import get_arch, reduced  # noqa: E402
from repro.core import load_balance, pipeline  # noqa: E402
from repro.core.hybrid import layer_flops  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.transformer import ModelCtx  # noqa: E402

N_STAGES, N_MICRO, B, S = 4, 8, 16, 32


def main():
    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), num_layers=16,
                              dtype="float32")
    ctx = ModelCtx(attn_chunk=16)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)

    # --- stage balancing (paper C4): contiguous layer partition ----------
    costs = [layer_flops(cfg, "attn", i, S) for i in range(cfg.num_layers)]
    bounds = load_balance.balance_stages(costs, N_STAGES)
    print(f"stage bounds {bounds} "
          f"(per-stage cost ratio "
          f"{load_balance.stage_costs(costs, bounds).max() / np.mean(load_balance.stage_costs(costs, bounds)):.3f})")
    per_stage = bounds[1] - bounds[0]
    assert all(bounds[i + 1] - bounds[i] == per_stage
               for i in range(N_STAGES)), "uniform layers -> equal split"

    # reshape stacked layer params (L, ...) -> (stages, layers/stage, ...)
    stage_params = jax.tree.map(
        lambda a: a.reshape((N_STAGES, per_stage) + a.shape[1:]),
        params["blocks"])

    positions = jnp.broadcast_to(jnp.arange(S)[None], (B // N_MICRO, S))

    def stage_fn(blocks, x):
        def body(h, blk):
            a, _ = tf.attn_apply(cfg, blk["attn"], h, positions, ctx)
            h = h + a
            f, _ = tf.ffn_apply(cfg, blk["ffn"], h, ctx)
            return h + f, None
        x, _ = jax.lax.scan(body, x, blocks)
        return x

    def last_fn(lp, y, tgt):
        h = L.apply_norm(cfg, lp["final_norm"], y)
        logits = L.lm_logits(cfg, {**lp, "embed": lp["embed"]}, h)
        return L.cross_entropy_loss(logits, tgt)

    mesh = jax.make_mesh((N_STAGES,), ("stage",), axis_types=(AxisType.Auto,))
    loss_fn = pipeline.make_pipeline_loss(stage_fn, last_fn, mesh,
                                          N_STAGES, N_MICRO)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, (B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(3, cfg.vocab_size, (B, S)), jnp.int32)
    x = pipeline.microbatch(L.embed_tokens(params["embed"], tokens), N_MICRO)
    tgt = pipeline.microbatch(targets, N_MICRO)
    last_params = {"final_norm": params["final_norm"],
                   "embed": params["embed"]}

    # --- parity: pipelined == serial --------------------------------------
    loss_pipe = loss_fn(stage_params, last_params, x, tgt)
    h = L.embed_tokens(params["embed"], tokens)

    def serial_body(h, blk):
        a, _ = tf.attn_apply(cfg, blk["attn"], h,
                             jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
                             ctx)
        h = h + a
        f, _ = tf.ffn_apply(cfg, blk["ffn"], h, ctx)
        return h + f, None

    h, _ = jax.lax.scan(serial_body, h, params["blocks"])
    h = L.apply_norm(cfg, params["final_norm"], h)
    loss_serial = L.cross_entropy_loss(L.lm_logits(cfg, params, h), targets)
    print(f"pipelined loss {float(loss_pipe):.6f}  "
          f"serial loss {float(loss_serial):.6f}")
    np.testing.assert_allclose(float(loss_pipe), float(loss_serial),
                               rtol=2e-4)

    # --- train through the pipeline (GPipe backward via autodiff) ---------
    valgrad = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
    sp, lp = stage_params, last_params
    for step in range(5):
        loss, (gs, gl) = valgrad(sp, lp, x, tgt)
        sp = jax.tree.map(lambda p, g: p - 0.5 * g, sp, gs)
        lp = jax.tree.map(lambda p, g: p - 0.5 * g, lp, gl)
        print(f"pipeline train step {step}: loss {float(loss):.4f}")
    assert float(loss) < float(loss_pipe)
    print("pipeline training converges ✓")

    # --- the unified path: stage-sliced transformer + both schedules ------
    pp = tf.pp_partition_params(cfg, params, bounds)
    st_fn = tf.make_stage_fn(cfg, ctx)
    la_fn = tf.make_last_fn(cfg, ctx)
    mask = pipeline.microbatch(jnp.ones((B, S)), N_MICRO)
    print("\nschedule       loss        bubble  stash(micros)")
    for sched in ("gpipe", "1f1b"):
        vag = jax.jit(pipeline.make_pipeline_value_and_grad(
            st_fn, la_fn, mesh, N_STAGES, N_MICRO, schedule=sched))
        l_s, _ = vag(pp["stage"], pp["last"], x, tgt, mask)
        c = pipeline.schedule_cost(sched, N_STAGES, N_MICRO)
        print(f"{sched:12s} {float(l_s):10.6f}  {c['bubble_frac']:6.2f} "
              f"{c['stash_micros']:8d}")
        np.testing.assert_allclose(float(l_s), float(loss_serial), rtol=2e-4)
    print("1F1B == GPipe == serial, at a quarter of the activation stash ✓")


if __name__ == "__main__":
    main()

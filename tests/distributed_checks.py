"""Multi-device checks, run in a subprocess with 8 host devices (jax locks
the device count at first init, so the main pytest process — which must see
1 device — cannot run these inline).  Prints one JSON dict of results;
``test_distributed.py`` asserts each entry.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual host devices, never a chip

import json  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import (AxisType, Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P)
from jax import shard_map  # noqa: E402

from repro.launch.mesh import make_host_mesh  # noqa: E402


RESULTS = {}


def check(name):
    def deco(fn):
        try:
            fn()
            RESULTS[name] = {"ok": True}
        except Exception as e:  # noqa: BLE001
            RESULTS[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}",
                             "tb": traceback.format_exc(limit=6)}
        return fn
    return deco


def pod_mesh():
    return jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)


def data_mesh():
    return jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))


# ---------------------------------------------------------------------------
@check("hierarchical_allreduce_equals_flat")
def _():
    from repro.core import hierarchical
    mesh = pod_mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 33))

    def flat(xs):
        return hierarchical.flat_allreduce_mean(xs, ("pod", "data"))

    def hier(xs):
        return hierarchical.hierarchical_allreduce_mean(xs, "data", "pod")

    spec = P(("pod", "data"))
    f = shard_map(flat, mesh=mesh, in_specs=spec, out_specs=spec,
                  check_vma=False)
    h = shard_map(hier, mesh=mesh, in_specs=spec, out_specs=spec,
                  check_vma=False)
    # summation order differs (RS+AR+AG vs single ring): ~1e-6 rel noise
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(h(x)),
                               rtol=1e-5, atol=1e-7)
    # and both equal the true mean broadcast
    want = np.broadcast_to(np.asarray(x).mean(0, keepdims=True), x.shape)
    np.testing.assert_allclose(np.asarray(h(x)), want, rtol=1e-5)


# ---------------------------------------------------------------------------
@check("onebit_sync_matches_manual")
def _():
    from repro.core import compression
    mesh = data_mesh()
    P_ = 8
    N = 8 * 512
    g = jax.random.normal(jax.random.PRNGKey(1), (P_, N))
    resid = jnp.zeros((P_, N))

    def inner(gs, rs):
        out, new_r = compression.onebit_sync({"w": gs[0]}, rs[0],
                                             axis="data", block=512)
        return out["w"][None], new_r[None]

    f = shard_map(inner, mesh=mesh, in_specs=(P("data"), P("data")),
                  out_specs=(P("data"), P("data")), check_vma=False)
    synced, new_resid = f(g, resid)
    # every rank holds the same mean of dequantized peers
    from repro.kernels import ops
    deq = []
    for p in range(P_):
        pk, sc = ops.onebit_quantize(g[p], 512)
        deq.append(np.asarray(ops.onebit_dequantize(pk, sc, 512)))
    want = np.mean(deq, axis=0)
    for p in range(P_):
        np.testing.assert_allclose(np.asarray(synced[p]), want, atol=1e-5)
    # error feedback: residual + dequant == original
    np.testing.assert_allclose(np.asarray(new_resid[0] + deq[0]),
                               np.asarray(g[0]), atol=1e-5)


# ---------------------------------------------------------------------------
@check("topk_sync_matches_manual")
def _():
    from repro.core import compression
    mesh = data_mesh()
    N = 4096
    g = jax.random.normal(jax.random.PRNGKey(2), (8, N))
    resid = jnp.zeros((8, N))

    def inner(gs, rs):
        out, new_r = compression.topk_sync({"w": gs[0]}, rs[0],
                                           axis="data", block=1024, k=16)
        return out["w"][None], new_r[None]

    f = shard_map(inner, mesh=mesh, in_specs=(P("data"), P("data")),
                  out_specs=(P("data"), P("data")), check_vma=False)
    synced, new_resid = f(g, resid)
    g_np = np.asarray(g)
    kept = np.zeros_like(g_np)
    for p in range(8):
        for b in range(N // 1024):
            blk = g_np[p, b * 1024:(b + 1) * 1024]
            idx = np.argsort(-np.abs(blk))[:16]
            kept[p, b * 1024 + idx] = blk[idx]
    want = kept.mean(0)
    np.testing.assert_allclose(np.asarray(synced[0]), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_resid), g_np - kept,
                               atol=1e-5)


# ---------------------------------------------------------------------------
@check("gpipe_matches_serial")
def _():
    from repro.core import pipeline
    mesh = jax.make_mesh((8,), ("stage",), axis_types=(AxisType.Auto,))
    S, M, mb, d = 8, 16, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(3), S)
    Ws = jnp.stack([jax.random.normal(k, (d, d)) * (d ** -0.5) for k in ks])

    def stage_fn(p, x):
        return jnp.tanh(x @ p["W"])

    pipe = pipeline.gpipe(stage_fn, mesh, S, M)
    x = jax.random.normal(jax.random.PRNGKey(4), (M, mb, d))
    y = pipe({"W": Ws}, x)

    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ Ws[s])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    # gradient parity (GPipe backward via autodiff)
    tgt = jax.random.normal(jax.random.PRNGKey(5), (M, mb, d))

    def loss_pipe(W):
        return jnp.mean((pipe({"W": W}, x) - tgt) ** 2)

    def loss_ref(W):
        h = x
        for s in range(S):
            h = jnp.tanh(h @ W[s])
        return jnp.mean((h - tgt) ** 2)

    g1 = jax.grad(loss_pipe)(Ws)
    g2 = jax.grad(loss_ref)(Ws)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


# ---------------------------------------------------------------------------
@check("pipeline_1f1b_matches_gpipe_and_serial")
def _():
    """The manual 1F1B executor's loss AND gradients match the autodiff
    GPipe reference and the unpipelined model, on a real small transformer
    with deliberately uneven (padded) stages."""
    import dataclasses
    from repro.config import get_arch, reduced
    from repro.core import pipeline
    from repro.models import layers as L, transformer as tf
    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), num_layers=6,
                              dtype="float32")
    ctx = tf.ModelCtx(attn_chunk=16)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    bounds = [0, 2, 3, 5, 6]
    pp = tf.pp_partition_params(cfg, params, bounds)
    stage_fn = tf.make_stage_fn(cfg, ctx)
    last_fn = tf.make_last_fn(cfg, ctx)
    B, Sq, M = 8, 16, 4
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, (B, Sq)), jnp.int32)
    targets = jnp.asarray(rng.integers(3, cfg.vocab_size, (B, Sq)),
                          jnp.int32)
    h = L.embed_tokens(params["embed"], tokens)
    x_m = pipeline.microbatch(h, M)
    t_m = pipeline.microbatch(targets, M)
    m_m = pipeline.microbatch(jnp.ones((B, Sq)), M)

    # unpipelined reference: same chain, differentiated directly
    def ref_loss(sp, lp, xm):
        hh = xm.reshape((B, Sq, cfg.d_model))
        for s in range(4):
            hh = stage_fn(jax.tree.map(lambda a, s=s: a[s], sp), hh)
        return last_fn(lp, hh, targets, jnp.ones((B, Sq))) / (B * Sq)

    l0, (g_sp0, g_lp0, g_x0) = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        pp["stage"], pp["last"], x_m.reshape((B, Sq, cfg.d_model)))
    g_x0 = g_x0.reshape(x_m.shape)

    mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
    outs = {}
    # parity oracle #2: autodiff straight through the gpipe tick scan
    ad = jax.jit(pipeline.gpipe_value_and_grad(stage_fn, last_fn, mesh, 4,
                                               M))
    cases = [("gpipe", None), ("1f1b", None), ("gpipe_autodiff", ad)]
    for sched, vag in cases:
        if vag is None:
            vag = jax.jit(pipeline.make_pipeline_value_and_grad(
                stage_fn, last_fn, mesh, 4, M, schedule=sched))
        l1, (g_sp, g_lp, g_x) = vag(pp["stage"], pp["last"], x_m, t_m, m_m)
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5,
                                   err_msg=sched)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=sched),
            g_sp["blocks"], g_sp0["blocks"])
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=sched),
            g_lp, g_lp0)
        np.testing.assert_allclose(np.asarray(g_x), np.asarray(g_x0),
                                   atol=2e-4, err_msg=sched)
        outs[sched] = float(l1)
    RESULTS.setdefault("pipeline_losses", outs)


# ---------------------------------------------------------------------------
@check("pp_hybrid_train_step_matches_dp")
def _():
    """The full DP x TP x stage pipelined train step (both schedules, 2x2x2
    mesh) follows the plain DP-8 trajectory exactly, including a remainder
    batch that does not divide into the micro-batches."""
    import dataclasses
    from repro.config import TrainConfig, get_arch, reduced
    from repro.launch.mesh import make_host_mesh
    from repro.models import layers as L, transformer as tf
    from repro.optimizer import adamw
    from repro.runtime import trainer
    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), num_layers=4,
                              dtype="float32")
    ctx = tf.ModelCtx(attn_chunk=8)
    tcfg = TrainConfig(steps=8, learning_rate=1e-3, warmup_steps=2,
                       checkpoint_every=0)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    bounds = [0, 2, 4]
    rng = np.random.default_rng(0)
    B, Sq = 8, 16
    batches = [{"tokens": jnp.asarray(rng.integers(3, 200, (B, Sq)),
                                      jnp.int32),
                "targets": jnp.asarray(rng.integers(3, 200, (B, Sq)),
                                       jnp.int32),
                "mask": jnp.ones((B, Sq), jnp.float32)}
               for _ in range(4)]

    def ref_loss(p, b):
        logits, _, _ = tf.forward(cfg, p, b, ctx)
        nll = L._nll(logits, b["targets"])
        return jnp.sum(nll * b["mask"]) / jnp.sum(b["mask"])

    scfg = trainer.DPSyncConfig(mode="flat")
    p_ref = jax.tree.map(jnp.copy, params)
    opt_ref = adamw.init_opt_state(p_ref)
    resid = jnp.zeros((8, trainer.residual_size(p_ref, scfg)))
    step_ref = trainer.make_dp_train_step(ref_loss, make_host_mesh(data=8),
                                          tcfg, scfg)
    ref_losses = []
    for b in batches:
        p_ref, opt_ref, resid, l = step_ref(p_ref, opt_ref, resid, b)
        ref_losses.append(float(l))

    for sched in ("1f1b", "gpipe"):
        mesh = make_host_mesh(data=2, model=2, stage=2)
        pp = tf.pp_partition_params(cfg, jax.tree.map(jnp.copy, params),
                                    bounds)
        pp_shape = jax.eval_shape(lambda: pp)
        opt = adamw.init_opt_state(
            trainer.pp_trainable(pp, cfg.tie_embeddings))
        res = jnp.zeros((2, 2, 2,
                         trainer.pp_residual_size(cfg, pp_shape, mesh,
                                                  scfg)))
        step = trainer.make_pp_train_step(cfg, mesh, tcfg, bounds, pp_shape,
                                          n_micro=2, pp_schedule=sched,
                                          scfg=scfg, ctx=ctx)
        losses = []
        for b in batches:
            pp, opt, res, l = step(pp, opt, res, b)
            losses.append(float(l))
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-4,
                                   atol=1e-5, err_msg=sched)
        RESULTS.setdefault("pp_losses", {})[sched] = losses
        if sched == "1f1b":
            # microbatch remainder: B=6 does not divide n_micro=4 — the
            # step pads and masks, and the loss equals the unpipelined
            # loss on the 6 real rows
            step6 = trainer.make_pp_train_step(
                cfg, mesh, tcfg, bounds, pp_shape, n_micro=4,
                pp_schedule=sched, scfg=scfg, ctx=ctx)
            b6 = {k: v[:6] for k, v in batches[0].items()}
            pp6 = tf.pp_partition_params(
                cfg, jax.tree.map(jnp.copy, params), bounds)
            opt6 = adamw.init_opt_state(
                trainer.pp_trainable(pp6, cfg.tie_embeddings))
            res6 = jnp.zeros_like(res)
            _, _, _, l6 = step6(pp6, opt6, res6, b6)
            np.testing.assert_allclose(float(l6), float(ref_loss(
                params, b6)), rtol=2e-4)


# ---------------------------------------------------------------------------
@check("pp_train_step_compressed_embed_sync_converges")
def _():
    """The pipelined step composes the compressed (top-k) DP sync and the
    rows-touched sparse embedding sync on an untied arch."""
    import dataclasses
    from repro.config import TrainConfig, get_arch, reduced
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.optimizer import adamw
    from repro.runtime import trainer
    cfg = dataclasses.replace(reduced(get_arch("deepseek-7b")),
                              num_layers=4, dtype="float32")
    assert not cfg.tie_embeddings
    tcfg = TrainConfig(steps=10, learning_rate=3e-3, warmup_steps=2,
                       checkpoint_every=0)
    mesh = make_host_mesh(data=2, model=2, stage=2)
    bounds = [0, 2, 4]
    scfg = trainer.DPSyncConfig(mode="topk", topk_block=256, k=64)
    esync = trainer.EmbedSyncConfig(
        id_fns={"embed": lambda b: b["tokens"]})
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    pp = tf.pp_partition_params(cfg, params, bounds)
    pp_shape = jax.eval_shape(lambda: pp)
    opt = adamw.init_opt_state(trainer.pp_trainable(pp, False))
    res = jnp.zeros((2, 2, 2, trainer.pp_residual_size(
        cfg, pp_shape, mesh, scfg, embed_sync=esync)))
    step = trainer.make_pp_train_step(cfg, mesh, tcfg, bounds, pp_shape,
                                      n_micro=2, scfg=scfg,
                                      embed_sync=esync)
    rng = np.random.default_rng(1)
    losses = []
    for i in range(10):
        b = {"tokens": jnp.asarray(rng.integers(3, 200, (8, 16)),
                                   jnp.int32),
             "targets": jnp.asarray(rng.integers(3, 16, (8, 16)),
                                    jnp.int32)}
        pp, opt, res, l = step(pp, opt, res, b)
        losses.append(float(l))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    RESULTS.setdefault("pp_compressed_losses", losses)


# ---------------------------------------------------------------------------
@check("pp_rebalance_in_loop")
def _():
    """Rebalance-in-the-loop: training from a deliberately skewed
    layer->stage split, the in-loop probe->rebalance->remap hook converges
    the bounds to the balanced partition, and the loss trajectory matches
    an unrebalanced run (the remap is model-function invariant)."""
    import dataclasses
    from repro.config import TrainConfig, get_arch, reduced
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.optimizer import adamw
    from repro.runtime import trainer
    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), num_layers=6,
                              dtype="float32")
    ctx = tf.ModelCtx(attn_chunk=8)
    tcfg = TrainConfig(steps=6, learning_rate=1e-3, warmup_steps=2,
                       checkpoint_every=0)
    skew = [0, 1, 6]                           # stage 0: 1 layer, stage 1: 5
    rng = np.random.default_rng(2)
    batches = [{"tokens": jnp.asarray(rng.integers(3, 200, (8, 16)),
                                      jnp.int32),
                "targets": jnp.asarray(rng.integers(3, 200, (8, 16)),
                                       jnp.int32)}
               for _ in range(6)]
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    scfg = trainer.DPSyncConfig(mode="flat")

    def run(rebalance_every):
        mesh = make_host_mesh(data=2, model=2, stage=2)
        pp = tf.pp_partition_params(cfg, jax.tree.map(jnp.copy, params),
                                    skew)
        pp_shape = jax.eval_shape(lambda: pp)
        opt = adamw.init_opt_state(trainer.pp_trainable(pp,
                                                        cfg.tie_embeddings))
        res = jnp.zeros((2, 2, 2, trainer.pp_residual_size(
            cfg, pp_shape, mesh, scfg)))
        step = trainer.make_pp_train_step(cfg, mesh, tcfg, skew, pp_shape,
                                          n_micro=2, scfg=scfg, ctx=ctx)
        rebal = trainer.PPRebalancer(cfg, mesh, tcfg, skew, n_micro=2,
                                     scfg=scfg, ctx=ctx, probe_batch=4,
                                     probe_seq=32)
        state = {"params": pp, "opt": opt, "residual": res}
        out = trainer.train_loop(
            state, iter(batches), step, tcfg,
            rebalance_every=rebalance_every,
            rebalance_fn=rebal if rebalance_every else None)
        return out.losses, rebal
    base_losses, _ = run(0)
    losses, rebal = run(2)
    assert len(rebal.history) > 1, "rebalance never fired"
    final = rebal.history[-1]
    sizes = [final[s + 1] - final[s] for s in range(2)]
    assert max(sizes) <= 4, (rebal.history, rebal.last_stage_times)
    # the remap preserves the model function: same trajectory either way
    np.testing.assert_allclose(losses, base_losses, rtol=5e-3, atol=1e-4)
    RESULTS.setdefault("pp_rebalance_history", rebal.history)

    # checkpoint/resume leg: the moved carve points ride in the checkpoint,
    # and restore rebuilds a working step at THOSE bounds (not the skewed
    # template's) — a resumed rebalanced run must not scramble its layers
    import shutil
    import tempfile
    ckpt_dir = tempfile.mkdtemp(prefix="pp_rebal_ckpt_")
    try:
        tcfg_ck = dataclasses.replace(tcfg, checkpoint_every=2,
                                      checkpoint_dir=ckpt_dir)
        mesh = make_host_mesh(data=2, model=2, stage=2)

        def fresh_state():
            pp = tf.pp_partition_params(cfg,
                                        jax.tree.map(jnp.copy, params),
                                        skew)
            opt = adamw.init_opt_state(
                trainer.pp_trainable(pp, cfg.tie_embeddings))
            res = jnp.zeros((2, 2, 2, trainer.pp_residual_size(
                cfg, jax.eval_shape(lambda: pp), mesh, scfg)))
            return {"params": pp, "opt": opt, "residual": res,
                    "stage_bounds": jnp.asarray(skew, jnp.int32)}

        state = fresh_state()
        step = trainer.make_pp_train_step(
            cfg, mesh, tcfg_ck, skew, jax.eval_shape(lambda: state["params"]),
            n_micro=2, scfg=scfg, ctx=ctx)
        rebal2 = trainer.PPRebalancer(cfg, mesh, tcfg_ck, skew, n_micro=2,
                                      scfg=scfg, ctx=ctx, probe_batch=4,
                                      probe_seq=32)
        trainer.train_loop(state, iter(batches[:4]), step, tcfg_ck,
                           rebalance_every=2, rebalance_fn=rebal2)
        assert len(rebal2.history) > 1
        start, restored = trainer.resume_or_init(fresh_state(), tcfg_ck)
        assert start == 4
        rb = [int(b) for b in restored["stage_bounds"]]
        assert rb == rebal2.bounds, (rb, rebal2.bounds)
        step_r = trainer.make_pp_train_step(
            cfg, mesh, tcfg_ck, rb,
            jax.eval_shape(lambda: restored["params"]), n_micro=2,
            scfg=scfg, ctx=ctx)
        _, _, _, l = step_r(restored["params"], restored["opt"],
                            restored["residual"], batches[4])
        assert np.isfinite(float(l)), float(l)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
@check("pp_launch_train_e2e")
def _():
    """launch/train.py drives the pipelined hybrid path end-to-end on the
    8-device mesh (the acceptance-criterion entrypoint)."""
    from repro.launch import train as launch_train
    # run() is main() minus the persistent compile cache, which tests
    # leave off
    res = launch_train.run(launch_train.build_parser().parse_args([
        "--arch", "olmo-1b", "--reduced", "--data", "2", "--model", "2",
        "--pp-stages", "2", "--pp-micro", "2", "--steps", "3",
        "--batch", "8", "--seq", "16",
        "--ckpt-dir", "/tmp/repro_ppcheck_ckpt"]))
    assert res.steps_run == 3 and all(np.isfinite(res.losses))


# ---------------------------------------------------------------------------
@check("embed_zero_opt_state_matches_replicated")
def _():
    """Row-wise-sharded optimizer state for embedding tables (ZeRO over
    the vocab dim, composing with the sparse rows-touched sync): the
    trajectory is identical to the replicated optimizer, while the AdamW
    moments physically shard 1/8 per device."""
    from repro.config import TrainConfig
    from repro.optimizer import adamw
    from repro.runtime import trainer
    mesh = data_mesh()
    rng = np.random.default_rng(2)
    n_users, dim = 64, 8
    Wt = jnp.asarray(rng.normal(size=(n_users, dim)), jnp.float32)

    def loss_fn(params, batch):
        emb = params["emb"][batch["user"]]
        return jnp.mean((emb @ params["W"] - batch["y"]) ** 2)

    tcfg = TrainConfig(steps=40, learning_rate=1e-2, warmup_steps=4,
                       weight_decay=0.0, grad_clip=1.0, checkpoint_every=0)
    W0 = (rng.standard_normal((dim, 4)) * 0.1).astype(np.float32)
    trajs, finals = {}, {}
    for name, zero in (("replicated", False), ("zero", True)):
        esync = trainer.EmbedSyncConfig(
            id_fns={"emb": lambda b: b["user"]}, zero_opt=zero)
        scfg = trainer.DPSyncConfig(mode="flat")
        params = {"emb": jnp.zeros((n_users, dim)), "W": jnp.asarray(W0)}
        pshape = jax.eval_shape(lambda: params)
        rng2 = np.random.default_rng(7)
        opt = adamw.init_opt_state(params)
        resid = jnp.zeros((8, trainer.residual_size(
            params, scfg, exclude=esync.exclude)))
        step = trainer.make_dp_train_step(loss_fn, mesh, tcfg, scfg,
                                          embed_sync=esync,
                                          params_shape=pshape)
        losses = []
        for _ in range(40):
            users = jnp.asarray(rng2.integers(0, n_users, 64), jnp.int32)
            y = Wt[users] @ np.ones((dim, 4), np.float32) * 0.1
            params, opt, resid, loss = step(
                params, opt, resid, {"user": users, "y": jnp.asarray(y)})
            losses.append(float(loss))
        trajs[name] = losses
        finals[name] = np.asarray(params["emb"])
        if zero:
            shard = opt["m"]["emb"].sharding.shard_shape(
                opt["m"]["emb"].shape)
            assert shard == (n_users // 8, dim), shard
    np.testing.assert_allclose(trajs["zero"], trajs["replicated"],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(finals["zero"], finals["replicated"],
                               rtol=1e-4, atol=1e-6)
    RESULTS.setdefault("embed_zero_losses", trajs)


# ---------------------------------------------------------------------------
@check("dp_train_step_hier_and_compressed_converge")
def _():
    from repro.config import TrainConfig
    from repro.optimizer import adamw
    from repro.runtime import trainer
    mesh = pod_mesh()
    rng = np.random.default_rng(0)
    Wt = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)

    def loss_fn(params, batch):
        pred = batch["x"] @ params["W"]
        return jnp.mean((pred - batch["y"]) ** 2)

    tcfg = TrainConfig(steps=60, learning_rate=3e-2, warmup_steps=5,
                       weight_decay=0.0, grad_clip=0, checkpoint_every=0)
    for mode, inter in (("flat", None), ("hierarchical", "pod"),
                        ("onebit", None), ("topk", None)):
        scfg = trainer.DPSyncConfig(mode=mode, inter_axis=inter, block=512,
                                    topk_block=64, k=16)
        params = {"W": jnp.zeros((16, 4))}
        opt = adamw.init_opt_state(params)
        n = trainer.residual_size(params, scfg)
        resid = jnp.zeros((8, n))
        step = trainer.make_dp_train_step(loss_fn, mesh, tcfg, scfg)
        losses = []
        for i in range(60):
            x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
            y = x @ Wt + 0.01 * jnp.asarray(rng.normal(size=(64, 4)),
                                            jnp.float32)
            params, opt, resid, loss = step(params, opt, resid,
                                            {"x": x, "y": y})
            losses.append(float(loss))
        # top-k (25% density) legitimately converges slower (paper trade-off)
        bar = 0.3 if mode == "topk" else 0.15
        assert losses[-1] < bar * losses[0], (mode, losses[0], losses[-1])
        RESULTS.setdefault("dp_losses", {})[mode] = (losses[0], losses[-1])


# ---------------------------------------------------------------------------
@check("hybrid_gspmd_train_step_runs")
def _():
    import dataclasses
    from repro.config import get_arch, reduced, TrainConfig, ParallelConfig, \
        SHAPES
    from repro.core.hybrid import auto_plan
    from repro.models import transformer as tf, model_zoo
    from repro.optimizer import adamw
    from repro.runtime import trainer
    mesh = make_host_mesh(data=4, model=2)
    cfg = dataclasses.replace(reduced(get_arch("qwen3-moe-30b-a3b")),
                              dtype="float32", num_heads=2, num_kv_heads=2)
    plan = auto_plan(cfg, mesh, SHAPES["train_4k"], ParallelConfig())
    tcfg = TrainConfig(steps=5, checkpoint_every=0)
    step, jitted, shardings_for = trainer.make_hybrid_train_step(
        cfg, plan, tcfg)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init_opt_state(params)
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(rng.integers(3, 200, (8, 16)), jnp.int32),
             "targets": jnp.asarray(rng.integers(3, 200, (8, 16)), jnp.int32),
             "mask": jnp.ones((8, 16), jnp.float32)}
    fn = jitted(jax.eval_shape(lambda: params), batch)
    losses = []
    for _ in range(5):
        params, opt, metrics = fn(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    RESULTS.setdefault("hybrid_losses", losses)


# ---------------------------------------------------------------------------
@check("elastic_reshard_roundtrip")
def _():
    from repro.runtime import elastic
    mesh8 = data_mesh()
    x = jnp.arange(64.0).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh8, P("data")))
    # shrink to 4 survivors
    mesh4 = elastic.make_mesh_for(4)
    ys = elastic.reshard({"x": xs},
                         {"x": NamedSharding(mesh4, P("data"))})
    np.testing.assert_array_equal(np.asarray(ys["x"]), np.asarray(x))
    assert len(ys["x"].sharding.device_set) == 4


# ---------------------------------------------------------------------------
@check("embed_sharded_lookup_matches_replicated")
def _():
    """Every sharding plan's lookup — and its gradient — matches the
    replicated-dense reference on the 8-device mesh."""
    from repro import embeddings
    mesh = make_host_mesh(data=2, model=4)
    spec = embeddings.EmbedSpec("t", rows=96, dim=16)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(96, 16)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 96, size=48), jnp.int32)
    tgt = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
    want = np.asarray(table)[np.asarray(ids)]
    g_want = np.asarray(jax.grad(
        lambda t: 0.5 * jnp.mean((t[ids] - tgt) ** 2))(table))
    for kind in embeddings.PLANS:
        plan = embeddings.make_plan(kind)
        lk = embeddings.make_sharded_lookup(mesh, spec, plan)
        t_sh = jax.device_put(table, embeddings.named_sharding(mesh, plan))
        i_sh = jax.device_put(ids, NamedSharding(mesh, P("data")))
        np.testing.assert_allclose(np.asarray(lk(t_sh, i_sh)), want,
                                   atol=1e-6, err_msg=kind)
        g = jax.grad(lambda t: 0.5 * jnp.mean((lk(t, i_sh) - tgt) ** 2))(
            t_sh)
        np.testing.assert_allclose(np.asarray(g), g_want, atol=1e-6,
                                   err_msg=f"{kind} grad")


# ---------------------------------------------------------------------------
@check("embed_sparse_row_sync_matches_dense_pmean")
def _():
    """Rows-touched sparse gradient sync == dense pmean over dp ranks."""
    from repro.embeddings import sparse_row_sync
    mesh = data_mesh()
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 64, size=(8, 12)).astype(np.int32)
    g = np.zeros((8, 64, 8), np.float32)
    for p in range(8):                  # gradient mass only on touched rows
        for j in ids[p]:
            g[p, j] += rng.normal(size=8)

    def body(g_loc, ids_loc):
        return sparse_row_sync(g_loc[0], ids_loc[0], ("data",))[None]

    f = shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                  out_specs=P("data"), check_vma=False)
    out = np.asarray(f(jnp.asarray(g), jnp.asarray(ids[:, None])))
    want = g.mean(0)
    for p in range(8):
        np.testing.assert_allclose(out[p], want, atol=1e-6)


# ---------------------------------------------------------------------------
@check("dp_train_step_sparse_embed_matches_dense")
def _():
    """The DP train step with EmbedSyncConfig (rows-touched exchange)
    follows the dense-flat-sync trajectory."""
    from repro.config import TrainConfig
    from repro.optimizer import adamw
    from repro.runtime import trainer
    mesh = data_mesh()
    rng = np.random.default_rng(2)
    n_users, dim = 64, 8
    Wt = jnp.asarray(rng.normal(size=(n_users, dim)), jnp.float32)

    def loss_fn(params, batch):
        emb = params["emb"][batch["user"]]            # (B, dim)
        return jnp.mean((emb @ params["W"] - batch["y"]) ** 2)

    tcfg = TrainConfig(steps=40, learning_rate=1e-2, warmup_steps=4,
                       weight_decay=0.0, grad_clip=0, checkpoint_every=0)
    esync = trainer.EmbedSyncConfig(id_fns={"emb": lambda b: b["user"]})
    W0 = (rng.standard_normal((dim, 4)) * 0.1).astype(np.float32)
    trajs = {}
    cases = (("dense", "flat", None), ("sparse", "flat", esync),
             # embed grads ride the sparse path even when the rest of the
             # tree goes through compressed sync (residual excludes them)
             ("sparse_topk", "topk", esync))
    for name, mode, es in cases:
        scfg = trainer.DPSyncConfig(mode=mode, topk_block=32, k=16)
        # fresh arrays per run: the jitted step donates its inputs
        params = {"emb": jnp.zeros((n_users, dim)), "W": jnp.asarray(W0)}
        rng2 = np.random.default_rng(7)               # same batches per run
        opt = adamw.init_opt_state(params)
        exclude = es.exclude if es is not None else ()
        resid = jnp.zeros((8, trainer.residual_size(params, scfg,
                                                    exclude=exclude)))
        step = trainer.make_dp_train_step(loss_fn, mesh, tcfg, scfg,
                                          embed_sync=es)
        losses = []
        for _ in range(40):
            users = jnp.asarray(rng2.integers(0, n_users, 64), jnp.int32)
            y = Wt[users] @ np.ones((dim, 4), np.float32) * 0.1
            params, opt, resid, loss = step(
                params, opt, resid,
                {"user": users, "y": jnp.asarray(y)})
            losses.append(float(loss))
        trajs[name] = losses
    np.testing.assert_allclose(trajs["sparse"], trajs["dense"],
                               rtol=1e-4, atol=1e-6)
    # compressed non-embed sync still converges with sparse embed grads
    assert trajs["sparse_topk"][-1] < 0.5 * trajs["sparse_topk"][0]
    RESULTS.setdefault("embed_losses", trajs)


# ---------------------------------------------------------------------------
@check("hybrid_recllm_embed_plan_matches_replicated")
def _():
    """The hybrid GSPMD train step with the recsys CF tables routed through
    EmbedPlan placement (row-sharded over ``model``) places the tables
    sharded AND follows the replicated-placement loss trajectory exactly
    (placement must not change the math)."""
    import dataclasses
    from repro.config import get_arch, reduced, TrainConfig, ParallelConfig, \
        SHAPES
    from repro.core.hybrid import auto_plan
    from repro.models import transformer as tf
    from repro.optimizer import adamw
    from repro.recsys import model as recsys_model
    from repro.runtime import trainer
    mesh = make_host_mesh(data=4, model=2)
    cfg = dataclasses.replace(reduced(get_arch("recllm-base")),
                              dtype="float32")
    n_users = 64
    tcfg = TrainConfig(steps=4, checkpoint_every=0)
    ctx = tf.ModelCtx(attn_chunk=8)
    loss_fn = lambda p, b: recsys_model.recllm_loss(cfg, p, b, ctx)  # noqa: E731
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray(rng.integers(3, 200, (8, 16)), jnp.int32),
             "targets": jnp.asarray(rng.integers(3, 200, (8, 16)),
                                    jnp.int32),
             "user": jnp.asarray(rng.integers(0, n_users, (8,)), jnp.int32)}
    trajs = {}
    for name, eplans in (("replicated", None),
                         ("embed_plan", recsys_model.embed_plans("row"))):
        plan = auto_plan(cfg, mesh, SHAPES["train_4k"], ParallelConfig(),
                         embed_plans=eplans)
        step, jitted, shardings_for = trainer.make_hybrid_train_step(
            cfg, plan, tcfg, loss_fn=loss_fn)
        params = recsys_model.init_recllm(jax.random.PRNGKey(0), cfg,
                                          n_users)
        pspecs = plan.sharding.param_specs(
            cfg, jax.eval_shape(lambda: params))
        want = P("model", None) if eplans else P(None, None)
        assert pspecs["cf_user"] == want, pspecs["cf_user"]
        assert pspecs["cf_item"] == want, pspecs["cf_item"]
        opt = adamw.init_opt_state(params)
        fn = jitted(jax.eval_shape(lambda: params), batch)
        losses = []
        for _ in range(4):
            params, opt, metrics = fn(params, opt, batch)
            losses.append(float(metrics["loss"]))
        if eplans:
            # the table shards actually land row-sharded over `model`
            assert params["cf_user"].sharding.spec == P("model", None), \
                params["cf_user"].sharding
        assert np.isfinite(losses).all()
        trajs[name] = losses
    np.testing.assert_allclose(trajs["embed_plan"], trajs["replicated"],
                               rtol=1e-4, atol=1e-6)
    RESULTS.setdefault("recllm_embed_losses", trajs)


# ---------------------------------------------------------------------------
@check("cf_hot_row_cache_matches_sharded")
def _():
    """The serving hot-row cache is bit-exact against the raw table at
    every sharding plan on the 8-device mesh, with real cache hits, and
    the rows-touched refresh restores exactness after a table update."""
    from repro import embeddings
    from repro.embeddings.serving import CacheConfig, CachedLookup
    mesh = make_host_mesh(data=2, model=4)
    spec = embeddings.EmbedSpec("cf_item", rows=96, dim=16)
    rng = np.random.default_rng(7)
    table = rng.normal(size=(96, 16)).astype(np.float32)
    ids = np.clip(rng.zipf(1.3, size=160), 1, 96) - 1   # head-heavy
    rates = {}
    for kind in embeddings.PLANS:
        plan = embeddings.make_plan(kind)
        lk = CachedLookup(spec, plan, table, mesh=mesh,
                          cache=CacheConfig(rows=24))
        for lo in range(0, len(ids), 32):
            rows, _ = lk(ids[lo:lo + 32])
            np.testing.assert_array_equal(
                rows, table[ids[lo:lo + 32]], err_msg=kind)
        assert lk.hits > 0, kind
        # trainer update + rows-touched refresh keeps the replica exact
        hot = np.asarray(lk.cache.ids[:8])
        lk.update_rows(hot, np.full((len(hot), 16), 2.5, np.float32))
        rows, _ = lk(hot)
        np.testing.assert_array_equal(
            rows, np.full((len(hot), 16), 2.5, np.float32),
            err_msg=f"{kind} post-update")
        rates[kind] = lk.hit_rate
    RESULTS.setdefault("cf_cache_hit_rates", rates)


# ---------------------------------------------------------------------------
@check("dryrun_cell_on_host_mesh")
def _():
    """A miniature dry-run: the full build_cell path on an 8-device mesh."""
    import dataclasses
    from repro.config import get_arch, reduced, SHAPES, ParallelConfig
    import repro.config as rc
    from repro.launch import dryrun_lib
    mesh = make_host_mesh(data=4, model=2)
    cfg = reduced(get_arch("olmo-1b"))
    shape = dataclasses.replace(SHAPES["decode_32k"], seq_len=64,
                                global_batch=8)
    lower_fn, plan = dryrun_lib.build_cell(cfg, shape, mesh)
    compiled = lower_fn().compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


if __name__ == "__main__":
    print("RESULTS_JSON:" + json.dumps(RESULTS))

"""Training runtime: hybrid-parallel (GSPMD) and DP-shard_map train steps,
checkpoint/restart fault tolerance, and the training loop.

Two step builders:

* ``make_hybrid_train_step`` — the production path: jit with in/out shardings
  from the ``ShardingPlan`` (TP over ``model``, DP over ``data``/``pod``,
  ZeRO-1 optimizer state, optional remat + Megatron-SP).  Gradient sync is
  GSPMD-emitted (hierarchical across pods by construction of the mesh).
* ``make_dp_train_step`` — the paper's explicit DP path (its 8-GPU setup):
  the whole step runs inside shard_map over the dp axes with *manual*
  gradient sync: flat ring all-reduce (Eq. 8), hierarchical all-reduce (C5),
  or compressed all-gather with error feedback (C6, Eq. 10–11).
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.checkpoint import manager as ckpt
from repro.config import ArchConfig, ParallelConfig, TrainConfig
from repro.core import compression, hierarchical
from repro.core import pipeline as pipe_lib
from repro.core import sharding as sharding_lib
from repro.core.hybrid import Plan
from repro.embeddings import update as embed_update
from repro.models import layers, transformer as tf
from repro.models.transformer import ModelCtx
from repro.obs import timeline as obs_timeline
from repro.obs.trace import Tracer, or_null
from repro.optimizer import adamw, schedule


# ---------------------------------------------------------------------------
# Hybrid (GSPMD) train step — production path
# ---------------------------------------------------------------------------

def make_hybrid_train_step(cfg: ArchConfig, plan: Plan, tcfg: TrainConfig,
                           loss_fn: Optional[Callable] = None,
                           donate: bool = True):
    """Returns (step_fn, shardings) — step_fn(params, opt, batch) ->
    (params, opt, metrics)."""
    sh = plan.sharding
    tp_n = sh.mesh.shape.get("model", 1)
    ctx = ModelCtx(remat=plan.remat, constrain=sh.constrain,
                   flash_vjp=sh.dp_heavy or tp_n == 1)
    if loss_fn is None:
        loss_fn = lambda p, b: tf.loss_fn(cfg, p, b, ctx)  # noqa: E731

    accum = max(plan.pcfg.microbatches, 1)

    def _grads(params, batch):
        if accum == 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        # gradient accumulation: scan over microbatches (batch dim 0 split),
        # grads accumulated in f32 — memory ~1/accum of the monolithic step
        mb = jax.tree.map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            batch)

        def one(carry, b):
            g_acc, l_acc = carry
            (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, b)
            g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32),
                                 g_acc, g)
            return (g_acc, l_acc + loss), aux

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g, loss), auxs = jax.lax.scan(one, (g0, jnp.zeros((), jnp.float32)),
                                       mb)
        g = jax.tree.map(lambda x: x / accum, g)
        aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), auxs)
        return (loss / accum, aux), g

    def step(params, opt, batch):
        lr = schedule.warmup_cosine(opt["step"], tcfg.learning_rate,
                                    tcfg.warmup_steps, tcfg.steps)
        (loss, aux), grads = _grads(params, batch)
        # ZeRO-2: reduce-scatter gradients onto the optimizer-state sharding
        # (dp axes added) so full model-sharded-only gradients never
        # materialize — each dp rank only holds the shard it will update.
        gspecs = sh.opt_specs(cfg, jax.tree.map(
            lambda g: jax.ShapeDtypeStruct(g.shape, g.dtype), grads))
        grads = jax.tree.map(
            lambda g, sp: jax.lax.with_sharding_constraint(g, sh.named(sp)),
            grads, gspecs)
        new_params, new_opt = adamw.adamw_apply(params, grads, opt, lr, tcfg)
        metrics = {"loss": loss, "lr": lr,
                   "grad_norm": adamw.global_norm(grads)}
        return new_params, new_opt, metrics

    def shardings_for(params_shape, batch_shape):
        pspec = sh.param_specs(cfg, params_shape)
        ospec = {"m": sh.opt_specs(cfg, params_shape),
                 "v": sh.opt_specs(cfg, params_shape),
                 "master": sh.opt_specs(cfg, params_shape),
                 "step": P()}
        bspec = sh.batch_specs(batch_shape)
        to_named = lambda t: jax.tree.map(sh.named, t,  # noqa: E731
                                          is_leaf=lambda x: isinstance(x, P))
        return to_named(pspec), to_named(ospec), to_named(bspec)

    def jitted(params_shape, batch_shape):
        psh, osh, bsh = shardings_for(params_shape, batch_shape)
        return jax.jit(
            step,
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, None),
            donate_argnums=(0, 1) if donate else (),
        )

    return step, jitted, shardings_for


# ---------------------------------------------------------------------------
# DP shard_map train step — the paper's explicit path
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DPSyncConfig:
    mode: str = "flat"              # flat | hierarchical | onebit | topk
    intra_axis: str = "data"
    inter_axis: Optional[str] = None
    block: int = 512
    topk_block: int = 2048
    k: int = 32
    use_kernel: bool = True


@dataclasses.dataclass(frozen=True)
class EmbedSyncConfig:
    """Rows-touched sparse sync for embedding-table gradients.

    ``id_fns`` maps top-level param keys (the embedding tables) to
    ``batch -> ids`` extractors; those tables' gradients skip the dense
    all-reduce (and the compressed flatten path) and are exchanged as
    (unique ids, gradient rows) all-gathers instead — wire bytes scale
    with the batch, not the vocab.  ``compress="topk"`` additionally
    sparsifies each exchanged row via the Pallas top-k kernel.
    """

    id_fns: Dict[str, Callable[[Dict], jnp.ndarray]]
    # unique-id cap (default: len(ids)).  Must be >= the max unique ids a
    # rank's batch can touch: an undersized cap silently truncates the
    # exchanged row set and the dropped rows get ZERO gradient.
    cap: Optional[int] = None
    compress: Optional[str] = None  # None | "topk"
    k: int = 8
    use_kernel: bool = True
    # ZeRO over the vocab dim: the named tables' AdamW moments + master
    # rows live only on the owning dp shard (composes with the row plan —
    # per-device optimizer bytes drop 1/P).  Each rank updates its row
    # slice of the synced gradient and the fresh rows are all-gathered
    # back into the replicated table.  Requires rows % dp_world == 0 and
    # ``params_shape`` at step-build time (the opt specs become per-leaf).
    zero_opt: bool = False

    @property
    def exclude(self) -> Tuple[str, ...]:
        """Param keys outside the dense/compressed sync path — pass to
        ``residual_size(params, scfg, exclude=...)`` when compressing."""
        return tuple(self.id_fns)


def residual_size(params, scfg: DPSyncConfig,
                  exclude: Tuple[str, ...] = ()) -> int:
    """Flat padded size of the compression error-feedback state.  Params
    under top-level keys in ``exclude`` (sparse-synced embedding tables)
    carry no residual — their sync is outside the compressed path."""
    if exclude:
        params = {k: v for k, v in params.items() if k not in exclude}
    n = sum(l.size for l in jax.tree.leaves(params))
    mult = 8 * scfg.block if scfg.mode == "onebit" else scfg.topk_block
    return n + ((-n) % mult)


def make_dp_train_step(loss_fn: Callable, mesh: Mesh, tcfg: TrainConfig,
                       scfg: DPSyncConfig = DPSyncConfig(),
                       embed_sync: Optional[EmbedSyncConfig] = None,
                       params_shape=None):
    """step(params, opt, residual, batch) -> (params, opt, residual, loss).

    params/opt replicated over dp axes; batch sharded on dim 0; residual is
    per-rank error-feedback state (leading device dim, dp-sharded).  With
    ``embed_sync``, params must be a dict and the named tables' gradients
    are synced sparsely (rows touched only) instead of densely; when also
    compressing (mode onebit/topk), size the residual with
    ``residual_size(params, scfg, exclude=embed_sync.exclude)`` — the
    embedding tables never enter the flattened compressed payload.

    ``embed_sync.zero_opt`` row-shards the tables' AdamW state over the dp
    axes (ZeRO over the vocab dim): the opt in/out specs split dim 0, each
    rank updates only its row slice of the synced gradient, and the
    updated rows all-gather back into the replicated table — trajectory-
    identical to the replicated optimizer (AdamW is elementwise), at 1/P
    the optimizer bytes per device.  Needs ``params_shape`` (an
    ``eval_shape`` of params) to emit the per-leaf opt specs.
    """
    axes = (scfg.intra_axis,) + ((scfg.inter_axis,) if scfg.inter_axis
                                 else ())
    zero_opt = embed_sync is not None and embed_sync.zero_opt
    if zero_opt and params_shape is None:
        raise ValueError("embed_sync.zero_opt needs params_shape")
    compressed = scfg.mode in ("onebit", "topk")
    if compressed:
        csync = compression.make_compressed_sync(
            scfg.mode, axis=scfg.intra_axis,
            block=scfg.block if scfg.mode == "onebit" else scfg.topk_block,
            k=scfg.k, use_kernel=scfg.use_kernel)
    else:
        gsync = hierarchical.make_sync_fn(scfg.mode, scfg.intra_axis,
                                          scfg.inter_axis)
    row_compress = None
    if embed_sync is not None and embed_sync.compress:
        row_compress = embed_update.make_row_compressor(
            embed_sync.compress, embed_sync.k, embed_sync.use_kernel)

    def sync_embed_grads(grads, batch):
        """Pop embedding-table grads; sync rows-touched over all dp axes."""
        emb = {}
        for key, id_fn in embed_sync.id_fns.items():
            emb[key] = embed_update.sparse_row_sync(
                grads[key], id_fn(batch), axes, cap=embed_sync.cap,
                compress=row_compress)
        rest = {k: v for k, v in grads.items()
                if k not in embed_sync.id_fns}
        return emb, rest

    world = math.prod(mesh.shape[a] for a in axes)
    tables = tuple(embed_sync.id_fns) if embed_sync else ()
    if zero_opt:
        for key in tables:
            rows = jax.tree.leaves(params_shape[key])[0].shape[0]
            if rows % world:
                raise ValueError(
                    f"zero_opt table {key!r}: {rows} rows do not divide "
                    f"over {world} dp ranks")

    def _flat_rank():
        r = jnp.zeros((), jnp.int32)
        for ax in axes:
            r = r * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        return r

    def inner(params, opt, residual, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axes)
        if embed_sync is not None:
            emb_grads, grads = sync_embed_grads(grads, batch)
        if compressed:
            grads, new_res = csync(grads, residual[0])
            if scfg.inter_axis:                     # hierarchy: pods too
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, scfg.inter_axis), grads)
            new_res = new_res[None]
        else:
            grads = gsync(grads)
            new_res = residual
        if embed_sync is not None:
            grads = {**grads, **emb_grads}
        lr = schedule.warmup_cosine(opt["step"], tcfg.learning_rate,
                                    tcfg.warmup_steps, tcfg.steps)
        if not zero_opt:
            new_params, new_opt = adamw.adamw_apply(params, grads, opt, lr,
                                                    tcfg)
            return new_params, new_opt, new_res, loss
        # ZeRO over the vocab dim: this rank updates only its row slice of
        # each table; everything else is replicated as before
        r = _flat_rank()
        for key in tables:
            g = grads[key]
            rows = g.shape[0] // world
            grads = {**grads,
                     key: jax.lax.dynamic_slice_in_dim(g, r * rows, rows, 0)}
        tcfg_eff = tcfg
        if tcfg.grad_clip > 0:
            # global norm with shard-aware accounting (table rows are
            # disjoint per rank; the rest is replicated) so every rank
            # clips by the same scale
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for k, g in grads.items() if k not in tables)
            sq = sq + jax.lax.psum(
                sum(jnp.sum(jnp.square(grads[k].astype(jnp.float32)))
                    for k in tables), axes)
            scale = jnp.minimum(1.0, tcfg.grad_clip
                                / jnp.maximum(jnp.sqrt(sq), 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
            tcfg_eff = dataclasses.replace(tcfg, grad_clip=0.0)
        new_params, new_opt = adamw.adamw_apply(params, grads, opt, lr,
                                                tcfg_eff)
        # fresh rows all-gather back into the replicated tables (reversed
        # axes order => first listed axis ends up major, matching r)
        for key in tables:
            full = new_params[key]
            for ax in reversed(axes):
                full = jax.lax.all_gather(full, ax, axis=0, tiled=True)
            new_params = {**new_params, key: full}
        return new_params, new_opt, new_res, loss

    dp_spec = P(axes if len(axes) > 1 else axes[0])
    if zero_opt:
        ax_spec = axes if len(axes) > 1 else axes[0]

        def opt_rule(path, leaf):
            top = str(getattr(path[0], "key", ""))
            if top in tables:
                return P(ax_spec, *([None] * (len(leaf.shape) - 1)))
            return P()

        one = jax.tree_util.tree_map_with_path(opt_rule, params_shape)
        opt_specs = {"m": one, "v": one, "master": one, "step": P()}
    else:
        opt_specs = P()
    inner_sm = shard_map(
        inner, mesh=mesh,
        in_specs=(P(), opt_specs, dp_spec, dp_spec),
        out_specs=(P(), opt_specs, dp_spec, P()),
        check_vma=False)
    return jax.jit(inner_sm, donate_argnums=(0, 1, 2))


# ---------------------------------------------------------------------------
# Pipelined DP x TP x stage train step (the unified training-parallelism
# path: planner stage bounds -> 1F1B/GPipe schedule -> manual Megatron TP ->
# composed DP gradient sync)
# ---------------------------------------------------------------------------


def pp_trainable(pp_params, tied: bool):
    """The optimizer's view of the pipeline param tree (drops the pad
    mask, which is layout metadata, not a weight)."""
    t = {"stage": {"blocks": pp_params["stage"]["blocks"]},
         "last": pp_params["last"]}
    if not tied:
        t["embed"] = pp_params["embed"]
    return t


def pp_residual_size(cfg: ArchConfig, pp_params_shape, mesh,
                     scfg: DPSyncConfig,
                     embed_sync: Optional[EmbedSyncConfig] = None) -> int:
    """Flat padded size of one device's compression residual under the
    pipelined step: stage blocks count their LOCAL shard (1/S stages,
    1/tp of each TP-sliced dim), replicated extras count in full, and
    sparse-synced embedding tables are excluded (as in
    :func:`residual_size`)."""
    S = mesh.shape["stage"]
    tp = mesh.shape.get("model", 1)
    specs = sharding_lib.pp_stage_specs(
        cfg, pp_params_shape["stage"], mesh)["blocks"]
    is_p = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    spec_leaves = jax.tree.leaves(specs, is_leaf=is_p)
    blk_leaves = jax.tree.leaves(pp_params_shape["stage"]["blocks"])
    n = 0
    for leaf, sp in zip(blk_leaves, spec_leaves):
        n += leaf.size // S // (tp if sharding_lib.spec_has_axis(sp, "model")
                                else 1)
    exclude = tuple(embed_sync.id_fns) if embed_sync else ()
    for key in ("last", "embed"):
        if key in pp_params_shape and key not in exclude:
            n += sum(l.size for l in jax.tree.leaves(pp_params_shape[key]))
    mult = 8 * scfg.block if scfg.mode == "onebit" else scfg.topk_block
    return n + ((-n) % mult)


def pp_state_specs(cfg: ArchConfig, mesh: Mesh, pp_params_shape,
                   scfg: DPSyncConfig = DPSyncConfig()):
    """(param, optimizer, residual) PartitionSpecs of the pipelined step's
    state: stage blocks over ``stage`` (+ Megatron dims over ``model``),
    the head / embedding replicated, the compression residual one row per
    (data, model, stage) rank.  Jit the state's init with these as
    ``out_shardings`` to build it sharded from the start."""
    stage_specs = sharding_lib.pp_stage_specs(cfg, pp_params_shape["stage"],
                                              mesh)
    tied = cfg.tie_embeddings
    param_specs = {"stage": stage_specs,
                   "last": jax.tree.map(lambda _: P(),
                                        pp_params_shape["last"])}
    tr_specs = {"stage": {"blocks": stage_specs["blocks"]},
                "last": param_specs["last"]}
    if not tied:
        param_specs["embed"] = P()
        tr_specs["embed"] = P()
    opt_specs = {"m": tr_specs, "v": tr_specs, "master": tr_specs,
                 "step": P()}
    return param_specs, opt_specs, P(scfg.intra_axis, "model", "stage", None)


def make_pp_train_step(cfg: ArchConfig, mesh: Mesh, tcfg: TrainConfig,
                       bounds, pp_params_shape, n_micro: int = 4,
                       pp_schedule: str = "1f1b",
                       scfg: DPSyncConfig = DPSyncConfig(),
                       embed_sync: Optional[EmbedSyncConfig] = None,
                       ctx: Optional[ModelCtx] = None):
    """The full DP x TP x stage pipelined train step, one shard_map.

    step(pp_params, opt, residual, batch) -> (pp_params, opt, residual,
    loss); ``pp_params`` from :func:`transformer.pp_partition_params` at
    the planner's ``bounds``, ``opt`` = ``adamw.init_opt_state`` over the
    trainable view (everything but the pad mask), ``residual`` shaped
    (dp, tp, S, :func:`pp_residual_size`).

    Inside the body: the token embedding runs replicated (its gradient
    arrives through the pipeline's input cotangent), micro-batches pad a
    remainder batch with masked rows, the 1F1B/GPipe executor
    (:func:`repro.core.pipeline.make_pipeline_vag_body`) drives the stage
    axis with Megatron-TP stage bodies over ``model``, TP-partial gradients
    (the replicated norm leaves) are psum'd over ``model``, and the
    existing DP sync stack — flat / hierarchical / onebit / topk plus the
    rows-touched :class:`EmbedSyncConfig` path — runs across ``data``
    exactly as in :func:`make_dp_train_step`.
    """
    S = mesh.shape["stage"]
    tp = mesh.shape.get("model", 1)
    if len(bounds) - 1 != S:
        raise ValueError(f"bounds {bounds} vs stage axis {S}")
    if tp > 1 and cfg.num_heads % tp:
        raise ValueError(f"num_heads {cfg.num_heads} must divide tp {tp}")
    if tp > 1 and cfg.num_kv_heads % tp and \
            (cfg.num_heads // tp) % cfg.num_kv_heads:
        # kv falls back to replication when it doesn't divide; the GQA
        # grouping then needs local q heads divisible by the FULL kv count
        raise ValueError(
            f"tp {tp} leaves {cfg.num_heads // tp} local q heads over "
            f"{cfg.num_kv_heads} replicated kv heads — GQA grouping is "
            f"unexpressible; pick tp with num_kv_heads % tp == 0 or "
            f"(num_heads/tp) % num_kv_heads == 0")
    tied = cfg.tie_embeddings
    if embed_sync is not None and tied:
        raise NotImplementedError(
            "sparse embed sync under pp needs an untied embedding (the "
            "tied table also carries the dense lm-head gradient)")
    ctx = ctx if ctx is not None else ModelCtx(attn_chunk=8)
    stage_fn = tf.make_stage_fn_tp(cfg, ctx)
    last_fn = tf.make_last_fn(cfg, ctx)
    vag_body = pipe_lib.make_pipeline_vag_body(stage_fn, last_fn, S,
                                               n_micro, pp_schedule)

    stage_specs = sharding_lib.pp_stage_specs(cfg, pp_params_shape["stage"],
                                              mesh)
    is_p = lambda x: isinstance(x, P)  # noqa: E731
    has_model = jax.tree.map(
        lambda sp: sharding_lib.spec_has_axis(sp, "model"),
        stage_specs["blocks"], is_leaf=is_p)

    compressed = scfg.mode in ("onebit", "topk")
    if compressed:
        csync = compression.make_compressed_sync(
            scfg.mode, axis=scfg.intra_axis,
            block=scfg.block if scfg.mode == "onebit" else scfg.topk_block,
            k=scfg.k, use_kernel=scfg.use_kernel)
    else:
        gsync = hierarchical.make_sync_fn(scfg.mode, scfg.intra_axis,
                                          scfg.inter_axis)
    row_compress = None
    if embed_sync is not None and embed_sync.compress:
        row_compress = embed_update.make_row_compressor(
            embed_sync.compress, embed_sync.k, embed_sync.use_kernel)
    tcfg_noclip = dataclasses.replace(tcfg, grad_clip=0.0)

    def clip_scale(g):
        """Global-norm clip scale with shard-aware accounting: stage
        blocks psum disjoint shards over (model, stage) — replicated
        leaves (post-psum over model) weighted 1/tp first — while the
        everywhere-replicated extras count once locally."""
        sq = jnp.zeros((), jnp.float32)
        for leaf, hm in zip(jax.tree.leaves(g["stage"]["blocks"]),
                            jax.tree.leaves(has_model)):
            sq = sq + jnp.sum(jnp.square(leaf)) / (1.0 if hm else tp)
        sq = jax.lax.psum(sq, ("model", "stage"))
        for key in ("last", "embed"):
            if key in g:
                sq = sq + sum(jnp.sum(jnp.square(l))
                              for l in jax.tree.leaves(g[key]))
        norm = jnp.sqrt(sq)
        if tcfg.grad_clip <= 0:
            return jnp.ones((), jnp.float32), norm
        return jnp.minimum(1.0, tcfg.grad_clip / jnp.maximum(norm, 1e-9)), \
            norm

    def inner(params, opt, residual, batch):
        tokens, targets = batch["tokens"], batch["targets"]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(tokens.shape, jnp.float32)
        emb_tab = params["last"]["embed"] if tied else params["embed"]
        h, emb_vjp = jax.vjp(
            lambda e: layers.embed_tokens(e, tokens), emb_tab)
        x_mic = pipe_lib.microbatch(h, n_micro, pad=True)
        t_mic = pipe_lib.microbatch(targets, n_micro, pad=True)
        m_mic = pipe_lib.microbatch(mask, n_micro, pad=True)
        loss, g_stage, g_last, g_x = vag_body(
            params["stage"], params["last"], x_mic, t_mic, m_mic)
        loss = jax.lax.pmean(loss, scfg.intra_axis)
        # TP: replicated-leaf grads are per-rank partials -> reduce once
        g_blocks = jax.tree.map(
            lambda gl, hm: gl if hm else jax.lax.psum(gl, "model"),
            g_stage["blocks"], has_model)
        # embed grad via the pipeline's input cotangent (pad rows sliced)
        B_loc = tokens.shape[0]
        g_h = g_x.reshape((-1,) + g_x.shape[2:])[:B_loc].astype(h.dtype)
        (g_emb,) = emb_vjp(g_h)
        grads = {"stage": {"blocks": g_blocks}, "last": dict(g_last)}
        if tied:
            grads["last"]["embed"] = grads["last"]["embed"] \
                + g_emb.astype(jnp.float32)
        else:
            grads["embed"] = g_emb.astype(jnp.float32)
        # DP sync across `data`: sparse rows-touched tables first, then
        # the dense/compressed path over the rest
        emb_grads = {}
        if embed_sync is not None:
            for key, id_fn in embed_sync.id_fns.items():
                emb_grads[key] = embed_update.sparse_row_sync(
                    grads[key], id_fn(batch), (scfg.intra_axis,),
                    cap=embed_sync.cap, compress=row_compress)
            grads = {k: v for k, v in grads.items() if k not in emb_grads}
        if compressed:
            grads, new_res = csync(grads, residual[0, 0, 0])
            if scfg.inter_axis:
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, scfg.inter_axis), grads)
            new_res = new_res[None, None, None]
        else:
            grads = gsync(grads)
            new_res = residual
        grads = {**grads, **emb_grads}
        scale, _ = clip_scale(grads)
        grads = jax.tree.map(lambda g: g * scale, grads)
        lr = schedule.warmup_cosine(opt["step"], tcfg.learning_rate,
                                    tcfg.warmup_steps, tcfg.steps)
        trainable = pp_trainable(params, tied)
        new_tr, new_opt = adamw.adamw_apply(trainable, grads, opt, lr,
                                            tcfg_noclip)
        new_params = {"stage": {"blocks": new_tr["stage"]["blocks"],
                                "mask": params["stage"]["mask"]},
                      "last": new_tr["last"]}
        if not tied:
            new_params["embed"] = new_tr["embed"]
        return new_params, new_opt, new_res, loss

    param_specs, opt_specs, res_spec = pp_state_specs(cfg, mesh,
                                                      pp_params_shape, scfg)
    inner_sm = shard_map(
        inner, mesh=mesh,
        in_specs=(param_specs, opt_specs, res_spec, P(scfg.intra_axis)),
        out_specs=(param_specs, opt_specs, res_spec, P()),
        check_vma=False)
    return jax.jit(inner_sm, donate_argnums=(0, 1, 2))


def probe_stage_times(cfg: ArchConfig, pp_params, bounds, ctx=None,
                      batch: int = 2, seq: int = 16, iters: int = 3,
                      jit_cache: Optional[Dict] = None,
                      tracer: Optional[Tracer] = None):
    """Host-measured per-stage forward times over each stage's REAL
    (unpadded) layers — the observe half of the observe->rebalance loop.

    The padded executor runs every stage at the widest stage's layer count
    (masked identity slots), so its own tick times cannot see imbalance;
    the probe instead times each stage's true layer slice, which is what a
    production (unpadded) pipeline — and the analytic bubble model — pays.
    Returns per-stage median seconds over ``iters`` timed calls.

    ``jit_cache`` (a dict the caller keeps alive, e.g.
    :class:`PPRebalancer`'s): reuses one jitted stage program across
    probes, so repeated probing only compiles when a stage's layer count
    first appears — a converged partition probes compile-free.

    ``tracer``: every timed call lands as one ``stage_tick`` span on track
    ``stage{s}`` (args ``stage``/``phase``/``iter``), with the *exact*
    measured duration the returned medians reduce over — so
    :func:`repro.obs.timeline.stage_tick_times` (and
    :func:`repro.core.load_balance.rebalance_from_trace`) recover the
    same per-stage times from the timeline.
    """
    tracer = or_null(tracer)
    ctx = ctx if ctx is not None else ModelCtx(attn_chunk=8)
    bounds = list(bounds)
    blocks = tf.unstack_stage_params(pp_params["stage"], bounds)
    if jit_cache is not None and "fn" in jit_cache:
        fn = jit_cache["fn"]
    else:
        fn = jax.jit(tf.make_stage_fn(cfg, ctx))
        if jit_cache is not None:
            jit_cache["fn"] = fn
    x = jnp.zeros((batch, seq, cfg.d_model),
                  jax.tree.leaves(blocks)[0].dtype)
    times = []
    for s in range(len(bounds) - 1):
        n = bounds[s + 1] - bounds[s]
        sl = jax.tree.map(lambda a: a[bounds[s]:bounds[s + 1]], blocks)
        p = {"blocks": sl, "mask": jnp.ones((n,), jnp.float32)}
        jax.block_until_ready(fn(p, x))                      # compile+warm
        samples = []
        for it in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(p, x))
            t1 = time.perf_counter()
            samples.append(t1 - t0)
            tracer.complete("stage_tick", t0, t1, track=f"stage{s}",
                            stage=s, phase="fwd", iter=it)
        samples.sort()
        times.append(samples[len(samples) // 2])
    return times


class PPRebalancer:
    """Rebalance-in-the-loop for the pipelined train step.

    Every invocation (``train_loop`` calls it every ``rebalance_every``
    steps): probe per-stage times at the current bounds, re-carve the
    layer->stage partition with :func:`repro.core.load_balance.
    rebalance_stages`, and — when the carve points move — live-remap the
    stage params *and* their AdamW moments with
    :func:`repro.models.transformer.remap_stage_params` semantics, then
    rebuild the jitted step for the new bounds.  The model function is
    invariant under the remap (layer order never changes); only the stage
    assignment, pad width, and per-stage cost change.  A compressed-sync
    residual whose flat size changes with the pad width is re-zeroed
    (error feedback restarts warm).
    """

    def __init__(self, cfg: ArchConfig, mesh: Mesh, tcfg: TrainConfig,
                 bounds, n_micro: int = 4, pp_schedule: str = "1f1b",
                 scfg: DPSyncConfig = DPSyncConfig(), ctx=None,
                 probe_batch: int = 2, probe_seq: int = 16,
                 tracer: Optional[Tracer] = None):
        self.cfg, self.mesh, self.tcfg = cfg, mesh, tcfg
        self.bounds = list(bounds)
        self.n_micro, self.pp_schedule, self.scfg = n_micro, pp_schedule, scfg
        self.ctx = ctx
        self.probe_batch, self.probe_seq = probe_batch, probe_seq
        self.history = [list(bounds)]
        self.last_stage_times = None
        self._probe_jit: Dict = {}      # shared stage program across probes
        self.tracer = or_null(tracer)

    def _remap_blocks(self, blocks_tree, new_bounds):
        return tf.remap_stage_params({"blocks": blocks_tree}, self.bounds,
                                     new_bounds)["blocks"]

    def __call__(self, state, step_fn):
        from repro.core import load_balance
        n_stages = len(self.bounds) - 1
        if self.tracer.enabled:
            # with a tracer the rebalancer is a *timeline consumer*: the
            # probe emits stage_tick spans into a probe-local tracer (its
            # own clock domain), the session trace absorbs them, and the
            # stage times come back OUT of the trace — the rebalance
            # decision and the visualized timeline cannot disagree
            probe_tr = Tracer(capacity=4096)
            probe_stage_times(self.cfg, state["params"], self.bounds,
                              self.ctx, self.probe_batch, self.probe_seq,
                              jit_cache=self._probe_jit, tracer=probe_tr)
            self.tracer.extend(probe_tr.events)
            times = obs_timeline.stage_tick_times(probe_tr.events, n_stages)
        else:
            times = probe_stage_times(self.cfg, state["params"], self.bounds,
                                      self.ctx, self.probe_batch,
                                      self.probe_seq,
                                      jit_cache=self._probe_jit)
        self.last_stage_times = times
        new_bounds = load_balance.rebalance_stages(times, self.bounds)
        self.tracer.instant(
            "rebalance.decision", track="train",
            old_bounds=list(self.bounds), new_bounds=list(new_bounds),
            stage_times=[float(t) for t in times],
            changed=new_bounds != self.bounds)
        if new_bounds == self.bounds:
            return None
        params = dict(state["params"])
        params["stage"] = tf.remap_stage_params(params["stage"],
                                                self.bounds, new_bounds)
        opt = dict(state["opt"])
        for key in ("m", "v", "master"):
            if key in opt and "stage" in opt[key]:
                moment = dict(opt[key])
                moment["stage"] = {"blocks": self._remap_blocks(
                    opt[key]["stage"]["blocks"], new_bounds)}
                opt[key] = moment
        new_state = {**state, "params": params, "opt": opt,
                     "stage_bounds": jnp.asarray(new_bounds, jnp.int32)}
        pp_shape = jax.eval_shape(lambda: params)
        if "residual" in state:
            # always restart error feedback: even at an unchanged flat
            # size, moving the carve point re-aligns residual entries to
            # different layers' gradients
            n_res = pp_residual_size(self.cfg, pp_shape, self.mesh,
                                     self.scfg)
            new_state["residual"] = jnp.zeros(
                state["residual"].shape[:-1] + (n_res,),
                state["residual"].dtype)
        new_step = make_pp_train_step(
            self.cfg, self.mesh, self.tcfg, new_bounds, pp_shape,
            n_micro=self.n_micro, pp_schedule=self.pp_schedule,
            scfg=self.scfg, ctx=self.ctx)
        self.bounds = new_bounds
        self.history.append(list(new_bounds))
        return new_state, new_step


def make_update_rule(tcfg: TrainConfig):
    """The trainer's shared optimizer plumbing (AdamW + warmup-cosine LR),
    packaged so other training simulators — :mod:`repro.core.async_dp`'s
    sync/async parameter-server models — step parameters through exactly
    the update rule the real train steps use.

    Returns (init, apply): ``init(params) -> opt``;
    ``apply(params, opt, grads, lr_scale=1.0) -> (params, opt)`` where
    ``lr_scale`` is the per-update multiplier hooks like delay
    compensation (Eq. 12's 1/(1+tau)) plug into.
    """

    def init(params):
        return adamw.init_opt_state(params)

    def apply(params, opt, grads, lr_scale=1.0):
        lr = schedule.warmup_cosine(opt["step"], tcfg.learning_rate,
                                    tcfg.warmup_steps, tcfg.steps)
        return adamw.adamw_apply(params, grads, opt, lr * lr_scale, tcfg)

    return init, apply


# ---------------------------------------------------------------------------
# Training loop with checkpoint/restart
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainResult:
    steps_run: int
    final_step: int
    losses: list
    throughput: float               # samples/sec (host wall clock)
    # host wall seconds of each step, loss fetched (the first one includes
    # compilation)
    step_seconds: list = dataclasses.field(default_factory=list)


def train_loop(state: Dict[str, Any], batches: Iterator, step_fn: Callable,
               tcfg: TrainConfig, *, start_step: int = 0,
               tokens_per_batch: int = 0, samples_per_batch: int = 0,
               fail_at: Optional[int] = None,
               rebalance_every: int = 0,
               rebalance_fn: Optional[Callable] = None,
               log_every: int = 10, verbose: bool = False,
               tracer: Optional[Tracer] = None) -> TrainResult:
    """Generic loop: state = {'params', 'opt', ['residual']}.

    ``fail_at``: inject a simulated node failure (raises RuntimeError) after
    that step commits — the fault-tolerance tests restart from checkpoint.

    ``rebalance_every`` / ``rebalance_fn``: close the observe->rebalance
    loop in-training.  Every K committed steps the loop calls
    ``rebalance_fn(state, step_fn)``; a ``None`` return keeps the current
    partition, otherwise the returned ``(state, step_fn)`` — e.g. from
    :class:`PPRebalancer`, which re-carves the pipeline's layer->stage
    bounds from measured per-stage times — replaces both for the steps
    that follow.

    ``tracer``: per-step ``train_step`` spans (host wall clock, args
    ``step``/``loss``), ``rebalance.probe`` spans around each rebalance
    hook, and ``checkpoint`` spans — the training half of the unified
    timeline (``launch/train.py --trace-out``).
    """
    tr = or_null(tracer)
    losses, step_seconds = [], []
    t0 = time.perf_counter()
    step = start_step
    n = 0
    for batch in batches:
        if rebalance_every and rebalance_fn is not None and n > 0 \
                and n % rebalance_every == 0:
            with tr.span("rebalance.probe", track="train", step=step):
                new = rebalance_fn(state, step_fn)
            if new is not None:
                state, step_fn = new
                if verbose:
                    print(f"step {step}: rebalanced "
                          f"(bounds {getattr(rebalance_fn, 'bounds', '?')})")
        t_step = time.perf_counter()
        with tr.span("train_step", track="train", step=step) as sp:
            if "residual" in state:
                state["params"], state["opt"], state["residual"], loss = \
                    step_fn(state["params"], state["opt"],
                            state["residual"], batch)
                metrics = {"loss": loss}
            else:
                state["params"], state["opt"], metrics = step_fn(
                    state["params"], state["opt"], batch)
            losses.append(float(metrics["loss"]))
            step_seconds.append(time.perf_counter() - t_step)
            if tr.enabled:
                sp.args["loss"] = losses[-1]
        step += 1
        n += 1
        if verbose and step % log_every == 0:
            print(f"step {step}: loss {losses[-1]:.4f}")
        if tcfg.checkpoint_every and step % tcfg.checkpoint_every == 0:
            with tr.span("checkpoint", track="train", step=step):
                ckpt.save(tcfg.checkpoint_dir, step,
                          {"params": state["params"], "opt": state["opt"],
                           **({"residual": state["residual"]}
                              if "residual" in state else {}),
                           # a rebalanced pipeline's carve points must ride
                           # along: restore rebuilds the step at THESE
                           # bounds
                           **({"stage_bounds": state["stage_bounds"]}
                              if "stage_bounds" in state else {})},
                          keep=tcfg.keep_checkpoints)
        if fail_at is not None and step >= fail_at:
            raise RuntimeError(f"injected failure at step {step}")
    dt = time.perf_counter() - t0
    tput = samples_per_batch * n / dt if dt > 0 else 0.0
    return TrainResult(steps_run=n, final_step=step, losses=losses,
                       throughput=tput, step_seconds=step_seconds)


def resume_or_init(init_state: Dict[str, Any], tcfg: TrainConfig,
                   shardings=None) -> Tuple[int, Dict[str, Any]]:
    """Restore the latest valid checkpoint (fault tolerance) or start fresh."""
    step, tree = ckpt.restore_latest(tcfg.checkpoint_dir, init_state,
                                     shardings)
    if step is None:
        return 0, init_state
    return step, tree

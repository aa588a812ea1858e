"""Pipeline parallelism (paper C2): schedule-polymorphic micro-batched
pipelining over a ``stage`` mesh axis via shard_map + lax.ppermute.

Two schedules share one stage contract — ``stage_fn(params_slice, x) -> y``
with shape-uniform inter-stage activations:

* ``gpipe`` — the reference: full-forward / full-backward, backward falls
  out of autodiff through the tick scan (ppermute's transpose is the
  reverse permute).  Activation stash grows with ``n_micro`` (every
  in-flight micro-batch's boundary input is held until the backward
  phase); the published GPipe recovers O(1) activations by rematerializing
  each stage's internals in the backward — recompute the cost model below
  charges for.
* ``1f1b`` — PipeDream-flush: each stage interleaves one forward with one
  backward once warmed up, so at most ``n_stages - s`` micro-batches are
  ever in flight at stage ``s``.  The backward is *manual* (per-tick
  ``jax.vjp`` against a bounded input stash of depth ``n_stages`` instead
  of ``n_micro``) and is gradient-parity-tested against both ``gpipe``
  and the unpipelined model.

The tick schedules are built on the host (`schedule_tables`) as static
(T, n_stages) micro-index tables consumed by a ``lax.scan``; activations
move stage-to-stage through tagged ppermute messages landing in per-stage
ring inboxes whose no-overwrite property is implied by the 1F1B in-flight
bound (and re-checked by the builder).

Stage balancing (bubbles from uneven stages, §V.A) is handled upstream by
``load_balance.balance_stages`` / ``rebalance_stages``.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

SCHEDULES = ("gpipe", "1f1b")


def gpipe(stage_fn: Callable, mesh: Mesh, n_stages: int, n_micro: int,
          stage_axis: str = "stage"):
    """Build a pipelined apply: (stage_params, x_micro) -> y_micro.

    stage_fn(params_slice, x) -> y : one stage's computation, same x/y shape
    (inter-stage activations must be shape-uniform).
    stage_params: pytree with leading dim n_stages (sharded over the axis).
    x_micro: (n_micro, mb, ...) microbatched input, consumed by stage 0.
    Returns (n_micro, mb, ...) outputs produced by the last stage.
    """
    T = n_micro + n_stages - 1                      # GPipe ticks

    def inner(params, x_micro):
        # params leaves: (1, ...) local stage slice; x_micro: (n_micro, ...)
        p_local = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(stage_axis)
        buf0 = jnp.zeros_like(x_micro[0])
        ysink0 = jnp.zeros_like(x_micro)

        def tick(carry, t):
            buf, ysink = carry
            # stage 0 injects microbatch t (clipped index; masked later)
            x_in = jax.lax.dynamic_index_in_dim(
                x_micro, jnp.clip(t, 0, n_micro - 1), keepdims=False)
            inp = jnp.where(sid == 0, x_in, buf)
            y = stage_fn(p_local, inp)
            # last stage banks its output at micro index t-(n_stages-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            bank = (sid == n_stages - 1) & (t >= n_stages - 1)
            ysink = jax.lax.cond(
                bank,
                lambda s: jax.lax.dynamic_update_index_in_dim(
                    s, y, out_idx, axis=0),
                lambda s: s, ysink)
            # send activations downstream (wraps around; wrap is ignored)
            nxt = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, ysink), None

        (_, ysink), _ = jax.lax.scan(tick, (buf0, ysink0), jnp.arange(T))
        # every stage holds a ysink; only the last stage's is real.
        ysink = jax.lax.psum(
            jnp.where(sid == n_stages - 1, ysink, jnp.zeros_like(ysink)),
            stage_axis)
        return ysink

    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)


def make_pipeline_loss(stage_fn: Callable, last_fn: Callable, mesh: Mesh,
                       n_stages: int, n_micro: int,
                       stage_axis: str = "stage"):
    """Pipelined loss: stages 0..S-1 run stage_fn; ``last_fn(y, target)``
    maps final activations to per-microbatch scalar loss (e.g. logits + CE).

    Returns loss_fn(stage_params, last_params, x_micro, tgt_micro) -> scalar.
    Differentiable end-to-end (GPipe backward via autodiff).
    """
    pipe = gpipe(stage_fn, mesh, n_stages, n_micro, stage_axis)

    def loss(stage_params, last_params, x_micro, tgt_micro):
        y = pipe(stage_params, x_micro)             # (n_micro, mb, ...)
        per = jax.vmap(lambda yy, tt: last_fn(last_params, yy, tt))(
            y, tgt_micro)
        return jnp.mean(per)

    return loss


def microbatch(x: jnp.ndarray, n_micro: int, pad: bool = False
               ) -> jnp.ndarray:
    """(B, ...) -> (n_micro, ceil(B/n_micro), ...).

    ``pad=True`` right-pads a remainder batch with zero rows (callers mask
    the pad rows out of the loss — see ``pad_batch``); otherwise B must
    divide evenly.
    """
    B = x.shape[0]
    if B % n_micro:
        if not pad:
            raise ValueError(
                f"batch {B} does not divide into {n_micro} micro-batches; "
                f"pass pad=True (and mask the pad rows) or pick a divisor")
        x = pad_batch(x, n_micro)
        B = x.shape[0]
    return x.reshape((n_micro, B // n_micro) + x.shape[1:])


def pad_batch(x: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    """Zero-pad dim 0 up to the next multiple of ``n_micro``."""
    B = x.shape[0]
    r = (-B) % n_micro
    if r == 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((r,) + x.shape[1:], x.dtype)], axis=0)


# ---------------------------------------------------------------------------
# 1F1B (PipeDream-flush) schedule
# ---------------------------------------------------------------------------

def schedule_tables(schedule: str, n_stages: int, n_micro: int
                    ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Host-built tick tables for either schedule.

    Returns (fwd, bwd, depth): fwd/bwd are (T, n_stages) int32 — the
    micro-batch index the stage's forward/backward unit processes that
    tick (-1 = idle) — and ``depth`` is the activation-stash ring size the
    schedule needs (``n_stages`` for 1F1B, ``n_micro`` for GPipe: the
    memory difference that motivates 1F1B).

    One compute unit per stage per tick.  Under ``1f1b`` a stage prefers a
    ready backward (the PipeDream-flush rule) and may only start forward
    ``m`` while fewer than ``n_stages - s`` micro-batches are in flight;
    under ``gpipe`` forwards run unthrottled and backwards drain after.
    """
    S, M = n_stages, n_micro
    one_f_one_b = schedule == "1f1b"
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r} (have {SCHEDULES})")
    f_t = np.full((S, M), -1, np.int64)
    b_t = np.full((S, M), -1, np.int64)
    nf = [0] * S
    nb = [0] * S
    t = 0
    while min(nb) < M:
        for s in range(S):
            m = nb[s]
            can_b = (m < M and 0 <= f_t[s, m] < t
                     and (s == S - 1 or 0 <= b_t[s + 1, m] < t))
            mf = nf[s]
            cap = (S - s) if one_f_one_b else M
            can_f = (mf < M
                     and (s == 0 or 0 <= f_t[s - 1, mf] < t)
                     and nf[s] - nb[s] < cap)
            if can_b and (one_f_one_b or not can_f):
                b_t[s, m] = t
                nb[s] += 1
            elif can_f:
                f_t[s, mf] = t
                nf[s] += 1
        t += 1
        if t > 4 * (M + S) + 8:
            raise RuntimeError(
                f"{schedule} schedule did not converge ({S=}, {M=})")
    T = t
    fwd = np.full((T, S), -1, np.int32)
    bwd = np.full((T, S), -1, np.int32)
    for s in range(S):
        for m in range(M):
            fwd[f_t[s, m], s] = m
            bwd[b_t[s, m], s] = m
    depth = min(S, M) if one_f_one_b else M
    _validate_schedule(f_t, b_t, S, M, depth)
    return fwd, bwd, depth


def _validate_schedule(f_t: np.ndarray, b_t: np.ndarray, S: int, M: int,
                       D: int) -> None:
    """No-overwrite invariants for the depth-D ring buffers.

    Slot ``m % D`` of each per-stage buffer must not be rewritten by micro
    ``m + D`` before micro ``m`` is consumed.  These follow from the
    schedule's in-flight bound; re-checked here (as real raises, immune to
    ``python -O``) so a schedule bug fails loudly at build time instead of
    as silent gradient corruption.
    """

    def need(ok, what, s, m):
        if not ok:
            raise ValueError(
                f"invalid schedule: {what} violated at stage {s}, "
                f"micro {m} (S={S}, M={M}, depth={D})")

    for s in range(S):
        for m in range(M - D):
            # input stash: fwd m+D writes the slot bwd m reads
            need(f_t[s, m + D] > b_t[s, m], "stash reuse", s, m)
            if s >= 1:      # fwd inbox: arrival of m+D vs consumption of m
                need(f_t[s - 1, m + D] + 1 > f_t[s, m], "fwd inbox", s, m)
            if s <= S - 2:  # bwd inbox
                need(b_t[s + 1, m + D] + 1 > b_t[s, m], "bwd inbox", s, m)
    # dependency sanity
    for s in range(S):
        for m in range(M):
            need(b_t[s, m] > f_t[s, m] >= 0, "fwd-before-bwd", s, m)
            if s >= 1:
                need(f_t[s, m] > f_t[s - 1, m], "fwd dependency", s, m)
            if s <= S - 2:
                need(b_t[s, m] > b_t[s + 1, m], "bwd dependency", s, m)


def schedule_cost(schedule: str, n_stages: int, n_micro: int,
                  t_fwd: float = 1.0, t_bwd: float = 2.0) -> Dict[str, float]:
    """Per-step schedule cost model (the bubble column of the
    ``train-parallel`` benchmark).

    This prices the schedules as a TPU deployment would run them:
    ``gpipe`` runs a full forward phase then a full backward phase;
    holding every micro-batch's activations to avoid recompute would cost
    O(n_micro) stash, so the published schedule rematerializes each
    stage's forward inside the backward phase — the backward tick costs
    ``t_fwd + t_bwd``.  ``1f1b`` keeps at most ``n_stages`` boundary
    inputs stashed and need not recompute: every tick costs its nominal
    unit.  Bubble fraction is 1 - useful/span; 1F1B's is strictly below
    GPipe's for n_stages > 1.

    Note the HOST-SIMULATION executor (:func:`make_pipeline_vag_body`)
    recomputes the stage forward inside ``jax.vjp`` on every backward
    tick under BOTH schedules (and computes masked idle ticks), so
    measured host step times will NOT show this model's gpipe-vs-1f1b
    compute gap — on the simulator the schedules differ in stash depth
    and tick count only.  The benchmark's measured and modeled columns
    are therefore reported (and gated) separately.
    """
    S, M = n_stages, n_micro
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r} (have {SCHEDULES})")
    useful = M * (t_fwd + t_bwd)
    if schedule == "gpipe":
        span = (M + S - 1) * t_fwd + (M + S - 1) * (t_fwd + t_bwd)
        stash = M
    else:
        span = (M + S - 1) * (t_fwd + t_bwd)
        stash = min(S, M)
    return {"schedule": schedule, "n_stages": S, "n_micro": M,
            "span": span, "useful": useful,
            "bubble_frac": 1.0 - useful / span,
            "stash_micros": stash}


# ---------------------------------------------------------------------------
# Pipelined value-and-grad (both schedules, one signature)
# ---------------------------------------------------------------------------
#
# Contract shared by gpipe and 1f1b:
#   stage_fn(stage_params_slice, x) -> y           shape-uniform activations
#   last_fn(last_params, y, tgt, mask) -> loss_sum masked NLL *sum* (the
#       pipeline divides by the global mask weight, so remainder-padded
#       micro-batches weight correctly)
#   vag(stage_params, last_params, x_micro, tgt_micro, mask_micro)
#     -> (loss, (g_stage, g_last, g_x))
# with g_x the cotangent of x_micro — the hook the trainer uses to reach
# the (replicated) token-embedding parameters that produced x.


def make_pipeline_value_and_grad(stage_fn: Callable, last_fn: Callable,
                                 mesh: Mesh, n_stages: int, n_micro: int,
                                 schedule: str = "1f1b",
                                 stage_axis: str = "stage"):
    """Standalone shard_map wrapper over the manual schedule executor
    (:func:`make_pipeline_vag_body`) — both schedules, one signature."""
    body = make_pipeline_vag_body(stage_fn, last_fn, n_stages, n_micro,
                                  schedule, stage_axis)
    sm = shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis), P(), P(), P(), P()),
        out_specs=(P(), P(stage_axis), P(), P()),
        check_vma=False)

    def vag(stage_params, last_params, x_micro, tgt_micro, mask_micro):
        loss, g_stage, g_last, g_x = sm(stage_params, last_params, x_micro,
                                        tgt_micro, mask_micro)
        return loss, (g_stage, g_last, g_x)

    return vag


def gpipe_value_and_grad(stage_fn, last_fn, mesh, n_stages, n_micro,
                         stage_axis: str = "stage"):
    """Autodiff reference: value-and-grad straight through the gpipe tick
    scan (ppermute transposes handled by jax).  Same signature as
    :func:`make_pipeline_value_and_grad` — the parity oracle the manual
    schedule executor is tested against."""
    pipe = gpipe(stage_fn, mesh, n_stages, n_micro, stage_axis)

    def loss(stage_params, last_params, x_micro, tgt_micro, mask_micro):
        y = pipe(stage_params, x_micro)             # (n_micro, mb, ...)
        sums = jax.vmap(
            lambda yy, tt, mm: last_fn(last_params, yy, tt, mm))(
            y, tgt_micro, mask_micro)
        W = jnp.maximum(jnp.sum(mask_micro), 1.0)
        return jnp.sum(sums) / W

    return jax.value_and_grad(loss, argnums=(0, 1, 2))


def make_pipeline_vag_body(stage_fn: Callable, last_fn: Callable,
                           n_stages: int, n_micro: int,
                           schedule: str = "1f1b",
                           stage_axis: str = "stage"):
    """Per-device pipelined value-and-grad body — the manual schedule
    executor, built for embedding inside a larger shard_map (the trainer's
    DP x TP x stage step maps it over ``stage`` alongside its data/model
    axes; :func:`make_pipeline_value_and_grad` wraps it standalone).

    The tick scan walks the host-built :func:`schedule_tables`; each tick a
    stage runs at most one forward (stashing its boundary input in a
    depth-``depth`` ring — ``n_stages`` under 1F1B, ``n_micro`` under
    GPipe) and one ready backward (``jax.vjp`` against the stashed input;
    the last stage's backward folds ``last_fn`` in and seeds itself,
    emitting the per-micro loss as a side product).  Cotangents flow
    upstage through the reverse ppermute.

    body(stage_params, last_params, x_micro, tgt_micro, mask_micro) ->
    (loss, g_stage, g_last, g_x); stage_params leaves carry a leading
    local dim of 1 (the stage shard); loss/g_last/g_x return replicated
    (psum over the stage axis), g_stage local.
    """
    S, M = n_stages, n_micro
    fwd_np, bwd_np, depth = schedule_tables(schedule, S, M)
    down = [(i, (i + 1) % S) for i in range(S)]
    up = [(i, (i - 1) % S) for i in range(S)]

    def inner(stage_params, last_params, x_micro, tgt_micro, mask_micro):
        p_local = jax.tree.map(lambda a: a[0], stage_params)
        sid = jax.lax.axis_index(stage_axis)
        is_last = sid == S - 1
        W = jnp.maximum(jnp.sum(mask_micro), 1.0)
        act0 = jnp.zeros((depth,) + x_micro.shape[1:], x_micro.dtype)
        f32 = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jnp.zeros(a.shape, jnp.float32), t)

        carry0 = {
            "inbox_f": act0, "inbox_b": act0, "stash": act0,
            "g_stage": f32(p_local), "g_last": f32(last_params),
            "g_x": jnp.zeros(x_micro.shape, jnp.float32),
            "loss": jnp.zeros((), jnp.float32),
        }

        def tick(carry, sched):
            fm_row, bm_row = sched
            m_f = fm_row[sid]
            m_b = bm_row[sid]
            # ---- forward unit ------------------------------------------
            act_f = m_f >= 0
            mf = jnp.clip(m_f, 0, M - 1)
            x_in = jnp.where(sid == 0, x_micro[mf],
                             carry["inbox_f"][mf % depth])
            y = stage_fn(p_local, x_in)
            stash = jnp.where(
                act_f, carry["stash"].at[mf % depth].set(x_in),
                carry["stash"])
            send_f = jnp.where(act_f & ~is_last, y, jnp.zeros_like(y))
            tag_f = jnp.where(act_f & ~is_last, m_f, -1)
            # ---- backward unit -----------------------------------------
            act_b = m_b >= 0
            mb = jnp.clip(m_b, 0, M - 1)
            x_s = stash[mb % depth]

            def last_branch(_):
                def f(p, lp, x):
                    return last_fn(lp, stage_fn(p, x), tgt_micro[mb],
                                   mask_micro[mb])
                ls, vjp = jax.vjp(f, p_local, last_params, x_s)
                gp, glp, gx = vjp(jnp.ones((), ls.dtype))
                return ls.astype(jnp.float32), gp, glp, gx

            def mid_branch(_):
                ct = carry["inbox_b"][mb % depth].astype(x_s.dtype)
                _, vjp = jax.vjp(stage_fn, p_local, x_s)
                gp, gx = vjp(ct)
                return jnp.zeros((), jnp.float32), gp, \
                    jax.tree.map(jnp.zeros_like, last_params), gx

            ls, gp, glp, gx = jax.lax.cond(is_last, last_branch, mid_branch,
                                           None)
            acc = lambda a, g: a + jnp.where(  # noqa: E731
                act_b, g.astype(jnp.float32) / W, 0.0)
            g_stage = jax.tree.map(acc, carry["g_stage"], gp)
            g_last = jax.tree.map(acc, carry["g_last"], glp)
            g_x = jnp.where(
                act_b & (sid == 0),
                carry["g_x"].at[mb].set(gx.astype(jnp.float32) / W),
                carry["g_x"])
            loss = carry["loss"] + jnp.where(act_b & is_last, ls, 0.0)
            send_b = jnp.where(act_b & (sid > 0), gx,
                               jnp.zeros_like(x_s)).astype(x_micro.dtype)
            tag_b = jnp.where(act_b & (sid > 0), m_b, -1)
            # ---- message passing (unconditional collectives) ----------
            recv_y, recv_tf = jax.lax.ppermute((send_f, tag_f), stage_axis,
                                               down)
            recv_ct, recv_tb = jax.lax.ppermute((send_b, tag_b), stage_axis,
                                                up)
            inbox_f = jnp.where(
                recv_tf >= 0,
                carry["inbox_f"].at[jnp.clip(recv_tf, 0) % depth].set(recv_y),
                carry["inbox_f"])
            inbox_b = jnp.where(
                recv_tb >= 0,
                carry["inbox_b"].at[jnp.clip(recv_tb, 0) % depth].set(
                    recv_ct),
                carry["inbox_b"])
            return {"inbox_f": inbox_f, "inbox_b": inbox_b, "stash": stash,
                    "g_stage": g_stage, "g_last": g_last, "g_x": g_x,
                    "loss": loss}, None

        carry, _ = jax.lax.scan(
            tick, carry0, (jnp.asarray(fwd_np), jnp.asarray(bwd_np)))

        # the loss / last-params grads / input cotangents live on one stage
        # each — psum replicates them (zeros elsewhere)
        loss = jax.lax.psum(
            jnp.where(is_last, carry["loss"], 0.0), stage_axis) / W
        g_last = jax.tree.map(
            lambda g: jax.lax.psum(jnp.where(is_last, g, 0.0), stage_axis),
            carry["g_last"])
        g_x = jax.lax.psum(
            jnp.where(sid == 0, carry["g_x"], 0.0), stage_axis)
        g_stage = jax.tree.map(lambda g: g[None], carry["g_stage"])
        return loss, g_stage, g_last, g_x

    return inner

"""Pallas TPU kernel: chunked WKV6 (rwkv6 time-mix recurrence).

The §Perf H1 hillclimb showed the WKV state scan is the SSM family's
hot-spot; this kernel keeps the (hs, hs) state in VMEM across the chunk loop
and builds the (C, C) intra-chunk mixing matrix one source column at a time
— HBM traffic is just the r/k/v/w streams and one output write.  All decay exponents are <= 0 (exact,
no overflow; see models/ssm._wkv6_chunked for the math).

Grid: (B, H, T/C) with the chunk axis "arbitrary" (sequential) carrying the
state in VMEM scratch.  Tiles: (C, hs) streams, C=32..128, hs=64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cumsum_rows(x):
    """Inclusive prefix sum down axis 0 in log2(rows) roll-and-add steps
    (Mosaic has no cumsum)."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < x.shape[0]:
        x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, chunk: int,
            hs: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)              # (C, hs)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                 # (1, hs)

    lw = jnp.log(jnp.maximum(w, 1e-30))
    cum = _cumsum_rows(lw)                           # (C, hs), <= 0
    cum_prev = cum - lw
    # intra-chunk mixing m[t, s] (strictly causal, s < t), one source column
    # per step: the decay stays a difference of cumulative logs (<= 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.zeros((chunk, chunk), jnp.float32)
    for s in range(chunk - 1):
        d = jnp.exp(jnp.minimum(cum_prev - cum[s:s + 1], 0.0))   # (C, hs)
        ms = jnp.sum(r * k[s:s + 1] * d, axis=-1, keepdims=True)  # (C, 1)
        m = m + jnp.where((col == s) & (row > s), ms, 0.0)
    o = jax.lax.dot_general(m, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # cross-chunk state contribution
    o += jax.lax.dot_general(r * jnp.exp(cum_prev), s_scr[...],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # bonus (current token)
    o += jnp.sum(r * k * u, axis=-1, keepdims=True) * v
    # state update: S' = diag(exp(cum_C)) S + (k * exp(cum_C - cum))^T v
    cum_c = cum[chunk - 1:chunk]                     # (1, hs)
    k2 = k * jnp.exp(cum_c - cum)
    # decay_rows[i, j] = exp(cum_c[i]): the row scaling diag(exp(cum_c)) as
    # lw^T @ ones, so no (1, hs) -> (hs, 1) relayout is needed
    decay_rows = jnp.exp(jax.lax.dot_general(
        lw, jnp.ones((chunk, hs), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    s_scr[...] = (decay_rows * s_scr[...]
                  + jax.lax.dot_general(k2, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    o_ref[0, 0] = o.astype(o_ref.dtype)


def wkv6_chunked(r, k, v, w, u, *, chunk: int = 32, interpret=False):
    """r,k,v,w: (B, H, T, hs); w decay in (0,1); u: (H, hs) -> (B, H, T, hs).

    Zero initial state (prefill/train); T % chunk == 0.
    """
    B, H, T, hs = r.shape
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    grid = (B, H, nc)
    kernel = functools.partial(_kernel, chunk=chunk, hs=hs, n_chunks=nc)

    def spec():
        return pl.BlockSpec((1, 1, chunk, hs), lambda b, h, c: (b, h, c, 0))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec(), spec(), spec(), spec(),
                  pl.BlockSpec((1, 1, hs), lambda b, h, c: (h, 0, 0))],
        out_specs=spec(),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hs), r.dtype),
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, hs))
    return out

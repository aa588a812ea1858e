"""Pallas TPU flash attention: blocked online-softmax with causal /
sliding-window masking and GQA (grouped KV heads indexed in the BlockSpec
index maps — repeated KV is never materialized).

Layout: q (B, H, Sq, D); k, v (B, Hk, Sk, D).  Grid is
(B, H, Sq/bq, Sk/bk) with the KV axis as the innermost "arbitrary"
dimension; running max / sum / accumulator live in VMEM scratch across KV
iterations.  Tiles: bq x D and bk x D (D = head_dim, 64..256 — MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, kv_len: int,
            block_q: int, block_k: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    pos_q = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    pos_k = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = pos_k < kv_len
    if causal:
        mask &= pos_k <= pos_q
    if window > 0:
        mask &= pos_k > pos_q - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0]                                   # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, 0] * corr + jnp.sum(p, axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)                    # (bk, D)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv
    m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ki == n_kv - 1)
    def _done():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, softmax_scale=None,
                    block_q=128, block_k=128, interpret=False):
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    G = H // Hk
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    kv_len = Sk

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k
    n_q, n_kv = Sq_p // block_q, Sk_p // block_k

    grid = (B, H, n_q, n_kv)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, kv_len=kv_len,
        block_q=block_q, block_k=block_k, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :Sq]
    return out

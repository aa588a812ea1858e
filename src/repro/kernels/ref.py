"""Pure-jnp oracles for every Pallas kernel.  Tests assert_allclose the
kernel (interpret=True on CPU) against these.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# flash attention (layout: q (B,H,Sq,D); k,v (B,Hk,Sk,D))
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=0, softmax_scale=None,
                    kv_len=None):
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    G = H // Hk
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    kr = jnp.repeat(k, G, axis=1)
    vr = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    pos_q = jnp.arange(Sq)[:, None]
    pos_k = jnp.arange(Sk)[None, :]
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= pos_k <= pos_q
    if window > 0:
        m &= pos_k > pos_q - window
    if kv_len is not None:
        m &= pos_k < kv_len
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# flash-decode attention (layout: q (B,Sq,H,D); caches (B,S,Hk,D)) — the
# length-skipping oracle: per-slot live prefixes, sliding-window band or
# gemma ring wraparound masking, int8 per-(position, head) scales.  Empty
# slots (len == 0) are defined to produce exactly-zero outputs.
#
# Speculative decode generalizes Sq from 1 to k draft rows: ``lengths``
# keeps its single-step meaning (row 0's attendable length = cache_len + 1,
# the row's own freshly written position included), and draft row ``j``
# attends with effective length ``lengths + j`` — cache plus draft rows
# ``< j`` plus itself, the causal intra-draft mask.  ``q_lens`` (B,) caps
# the live rows per slot; rows ``>= q_lens`` are defined to produce
# exactly-zero outputs (they are padding in a ragged speculative batch).
# ---------------------------------------------------------------------------

def _decode_mask(lengths, S: int, window: int, ring: bool):
    """(B, S) bool: which cache rows a slot's single query may attend."""
    pos = jnp.arange(S)[None, :]
    lengths = lengths[:, None]
    if ring and window > 0:
        valid = pos < jnp.minimum(lengths, S)
        valid &= jnp.mod(lengths - 1 - pos, S) < window
    else:
        valid = pos < lengths
        if window > 0:
            valid &= pos > lengths - 1 - window
    return valid


def _decode_mask_rows(lengths, q_lens, Sq: int, S: int, window: int,
                      ring: bool):
    """(B, Sq, S) bool: rows draft row ``j`` of each slot may attend.

    Row ``j``'s effective length is ``lengths + j``; rows ``>= q_lens``
    (speculation padding) attend nothing."""
    pos = jnp.arange(S)[None, None, :]
    eff = (lengths[:, None] + jnp.arange(Sq)[None, :])[:, :, None]
    if ring and window > 0:
        valid = pos < jnp.minimum(eff, S)
        valid &= jnp.mod(eff - 1 - pos, S) < window
    else:
        valid = pos < eff
        if window > 0:
            valid &= pos > eff - 1 - window
    valid &= (jnp.arange(Sq)[None, :] < q_lens[:, None])[:, :, None]
    return valid


def decode_attention(q, k, v, lengths, *, window=0, ring=False,
                     softmax_scale=None, q_lens=None):
    B, Sq, H, D = q.shape
    _, S, Hk, _ = k.shape
    G = H // Hk
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if q_lens is None:
        q_lens = jnp.full((B,), Sq, jnp.int32)
    qg = q.reshape(B, Sq, Hk, G, D).astype(jnp.float32)
    s = jnp.einsum("bjhgd,bkhd->bhjgk", qg, k.astype(jnp.float32)) * scale
    valid = _decode_mask_rows(lengths, q_lens, Sq, S, window, ring)
    s = jnp.where(valid[:, None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, :, None, :], p, 0.0)        # len==0 -> 0
    out = jnp.einsum("bhjgk,bkhd->bjhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention_quant(q, k_q, k_s, v_q, v_s, lengths, *,
                           softmax_scale=None, q_lens=None):
    B, Sq, H, D = q.shape
    _, S, Hk, _ = k_q.shape
    G = H // Hk
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if q_lens is None:
        q_lens = jnp.full((B,), Sq, jnp.int32)
    qg = q.reshape(B, Sq, Hk, G, D).astype(jnp.float32)
    s = jnp.einsum("bjhgd,bkhd->bhjgk", qg, k_q.astype(jnp.float32))
    s = s * k_s.transpose(0, 2, 1)[:, :, None, None, :] * scale
    valid = _decode_mask_rows(lengths, q_lens, Sq, S, 0, False)
    s = jnp.where(valid[:, None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, :, None, :], p, 0.0)
    pv = jnp.einsum("bhjgk,bkhd->bjhgd",
                    p * v_s.transpose(0, 2, 1)[:, :, None, None, :],
                    v_q.astype(jnp.float32))
    return pv.reshape(B, Sq, H, D).astype(q.dtype)


def paged_gather(pool, table):
    """Gather a slot-contiguous view out of a shared block pool.

    pool (N, bs, ...) + table (B, nb) int32 -> (B, nb*bs, ...): the dense-
    layout cache the paged layout virtualizes (dead entries gather the null
    block's rows, which every consumer masks by length)."""
    B, nb = table.shape
    bs = pool.shape[1]
    return pool[table.reshape(-1)].reshape((B, nb * bs) + pool.shape[2:])


def pool_layer(pool, layer):
    """Layer ``layer`` of a layer-stacked pool (L, N, bs, ...); a pool
    without the layer axis (N, bs, ...) is the ``L = 1`` case."""
    return pool if pool.ndim == 4 else pool[layer]


def decode_attention_paged(q, k_pool, v_pool, block_tables, lengths, *,
                           window=0, ring=False, softmax_scale=None,
                           q_lens=None):
    """Paged oracle: gather pool blocks into the dense layout, then attend."""
    return decode_attention(q, paged_gather(k_pool, block_tables),
                            paged_gather(v_pool, block_tables), lengths,
                            window=window, ring=ring,
                            softmax_scale=softmax_scale, q_lens=q_lens)


def decode_attention_paged_quant(q, k_q_pool, k_s_pool, v_q_pool, v_s_pool,
                                 block_tables, lengths, *,
                                 softmax_scale=None, q_lens=None):
    return decode_attention_quant(
        q, paged_gather(k_q_pool, block_tables),
        paged_gather(k_s_pool, block_tables),
        paged_gather(v_q_pool, block_tables),
        paged_gather(v_s_pool, block_tables), lengths,
        softmax_scale=softmax_scale, q_lens=q_lens)


# ---------------------------------------------------------------------------
# MoE router: softmax + top-k (first-occurrence argmax tie-break)
# ---------------------------------------------------------------------------

def moe_router(logits, k: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    T, E = probs.shape
    tmp = probs
    gates, idxs = [], []
    iota = jnp.arange(E)
    for _ in range(k):
        m = jnp.max(tmp, axis=-1)
        is_max = tmp == m[:, None]
        idx = jnp.min(jnp.where(is_max, iota, E), axis=-1)
        gates.append(m)
        idxs.append(idx)
        tmp = jnp.where(iota[None] == idx[:, None], -jnp.inf, tmp)
    gates = jnp.stack(gates, -1)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, jnp.stack(idxs, -1).astype(jnp.int32), probs


# ---------------------------------------------------------------------------
# 1-bit gradient compression (paper Eq. 10): sign pack + per-block L1 scale
# layout: g viewed as (8, N/8); packed (N/8,) uint8; one scale per block col-
# chunk of size ``block`` (so scales has N/8/block entries).
# ---------------------------------------------------------------------------

def onebit_quantize(g2d, block: int):
    """g2d: (8, M) f32 -> (packed (M,) uint8, scales (M/block,) f32)."""
    _, M = g2d.shape
    assert M % block == 0
    bits = (g2d >= 0).astype(jnp.int32)                      # (8, M)
    weights = (2 ** jnp.arange(8, dtype=jnp.int32))[:, None]
    packed = jnp.sum(bits * weights, axis=0).astype(jnp.uint8)
    scales = jnp.mean(jnp.abs(g2d.reshape(8, M // block, block)),
                      axis=(0, 2)).astype(jnp.float32)
    return packed, scales


def onebit_dequantize(packed, scales, block: int):
    """packed (M,) uint8, scales (M/block,) -> (8, M) f32 approx gradient."""
    M = packed.shape[0]
    j = jnp.arange(8, dtype=jnp.int32)[:, None]
    bits = (packed.astype(jnp.int32)[None, :] >> j) & 1      # (8, M)
    signs = 2.0 * bits.astype(jnp.float32) - 1.0
    s = jnp.repeat(scales, block)[None, :]
    return signs * s


# ---------------------------------------------------------------------------
# block-local top-k sparsification (paper Eq. 11 semantics: keep |x| >= t,
# t = k-th largest |x| in the block, ties included; residual = x - kept)
# ---------------------------------------------------------------------------

def topk_sparsify(x2d, k: int):
    """x2d: (nb, block) -> (kept, residual), same shapes."""
    a = jnp.abs(x2d)
    t = jnp.sort(a, axis=-1)[:, -k][:, None]
    kept = jnp.where(a >= t, x2d, 0.0)
    return kept, x2d - kept


# ---------------------------------------------------------------------------
# embedding gather / segment-sum scatter-add (the dedup-lookup pair)
# ---------------------------------------------------------------------------

def gather_rows(table, ids):
    """table (V, D), ids (n,) -> (n, D) = table[ids]."""
    return table[ids]


def scatter_add_rows(x, idx, n_rows: int):
    """x (n, D), idx (n,) -> (n_rows, D) with out[idx[i]] += x[i]."""
    return jnp.zeros((n_rows, x.shape[-1]), x.dtype).at[idx].add(x)


# ---------------------------------------------------------------------------
# fused AdamW update
# ---------------------------------------------------------------------------

def adamw_update(p, g, m, v, *, lr, b1, b2, eps, wd, bc1, bc2):
    """bc1/bc2 are bias corrections 1-b^t (precomputed)."""
    m1 = b1 * m + (1 - b1) * g
    v1 = b2 * v + (1 - b2) * jnp.square(g)
    mh = m1 / bc1
    vh = v1 / bc2
    p1 = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    return p1, m1, v1


# ---------------------------------------------------------------------------
# chunked WKV6 oracle: sequential recurrence (layout (B, H, T, hs))
# ---------------------------------------------------------------------------

def wkv6_chunked(r, k, v, w, u):
    """S_t = diag(w_t) S + k_t v_t^T ; o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)."""
    B, H, T, hs = r.shape

    def step(S, inp):
        rt, kt, vt, wt = inp                         # (B,H,hs)
        kv = kt[..., :, None] * vt[..., None, :]
        o = jnp.einsum("bhk,bhkv->bhv", rt, S + u[..., None] * kv)
        S = wt[..., None] * S + kv
        return S, o

    S0 = jnp.zeros((B, H, hs, hs), jnp.float32)
    xs = tuple(t.transpose(2, 0, 1, 3).astype(jnp.float32)
               for t in (r, k, v, w))
    _, out = jax.lax.scan(step, S0, xs)
    return out.transpose(1, 2, 0, 3).astype(r.dtype)

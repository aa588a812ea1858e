"""Composable transformer stacks for every assigned architecture family.

Families and their parameter layouts:

* ``uniform``  — dense / MoE decoder-only (internlm2, olmo, deepseek, moonshot,
  qwen3-moe, qwen2-vl, recllm): one ``lax.scan`` over L stacked layers.
* ``rwkv6``    — attention-free stack (token-shift time-mix + channel-mix).
* ``jamba``    — periods of [attn, mamba x7] with MoE every other FFN; scan
  over periods, unrolled inside.
* ``gemma``    — 5 local : 1 global attention; 26 small layers, fully unrolled
  (heterogeneous ring-buffer vs full KV caches).
* ``whisper``  — encoder-decoder; conv frontend stubbed (precomputed frames).

All functions are pure; distribution enters only through ``ModelCtx.constrain``
(activation sharding hooks installed by ``core.sharding``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ArchConfig
from repro.models import attention as attn_lib
from repro.models import layers, moe, ssm


@dataclasses.dataclass(frozen=True)
class ModelCtx:
    """Runtime knobs threaded through the stack (not part of params)."""
    attn_impl: str = "chunked"       # naive | chunked | pallas
    attn_chunk: int = 1024
    decode_impl: str = "dense"       # dense | flash (Pallas flash-decode)
    decode_block_k: int = 128        # flash-decode KV block (skip quantum)
    mamba_chunk: int = 512
    remat: bool = False
    use_kernels: bool = False
    moe_group: int = 256
    moe_capacity_factor: float = 1.25
    flash_vjp: bool = False          # custom flash backward (dp_heavy/no-TP)
    constrain: Callable[[jnp.ndarray, str], jnp.ndarray] = \
        staticmethod(lambda x, name: x)


# ---------------------------------------------------------------------------
# Attention block
# ---------------------------------------------------------------------------

def init_attn_block(key, cfg: ArchConfig, cross: bool = False) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    d = cfg.d_model
    p = {
        "norm": layers.init_norm(cfg),
        "wq": layers.init_dense(ks[0], d, cfg.q_dim, dtype),
        "wk": layers.init_dense(ks[1], d, cfg.kv_dim, dtype),
        "wv": layers.init_dense(ks[2], d, cfg.kv_dim, dtype),
        "wo": layers.init_dense(ks[3], cfg.q_dim, d, dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), jnp.float32)
    return p


def _qkv(cfg: ArchConfig, p: Dict, h, positions, ctx: ModelCtx,
         rope: bool = True):
    B, S, _ = h.shape
    q = (h @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm and "q_norm" in p:
        q = layers.rms_norm_simple(q, p["q_norm"])
        k = layers.rms_norm_simple(k, p["k_norm"])
    if rope and cfg.pos_type in ("rope", "mrope"):
        q = layers.position_embedding(cfg, q, positions)
        k = layers.position_embedding(cfg, k, positions)
    q = ctx.constrain(q, "heads")
    k = ctx.constrain(k, "kv_heads")
    v = ctx.constrain(v, "kv_heads")
    return q, k, v


def attn_apply(cfg: ArchConfig, p: Dict, x, positions, ctx: ModelCtx,
               *, window: int = 0, return_kv: bool = False):
    """Full-sequence (train/prefill) self-attention residual branch."""
    h = layers.apply_norm(cfg, p["norm"], x)
    q, k, v = _qkv(cfg, p, h, positions, ctx)
    o = attn_lib.attention(q, k, v, causal=True, window=window,
                           impl=ctx.attn_impl, chunk=ctx.attn_chunk,
                           flash_vjp=ctx.flash_vjp)
    out = ctx.constrain(o.reshape(x.shape[0], x.shape[1], cfg.q_dim)
                        @ p["wo"], "residual")
    if return_kv:
        return out, (k, v)
    return out, None


def attn_decode(cfg: ArchConfig, p: Dict, x, position, ctx: ModelCtx,
                k_cache, v_cache, cache_len, *, window: int = 0):
    """One-token decode.  x:(B,1,d); caches (B,S,Hk,D); cache_len (B,).

    Returns (out, k_cache, v_cache).  For ``window>0`` the cache is a ring
    buffer of size W (softmax is permutation-invariant over keys; RoPE is
    applied with absolute positions before insertion)."""
    B = x.shape[0]
    S = k_cache.shape[1]
    h = layers.apply_norm(cfg, p["norm"], x)
    q, k, v = _qkv(cfg, p, h, position[:, None] if position.ndim == 1 else position,
                   ctx)
    slot = cache_len % S if window > 0 else cache_len
    k_cache = k_cache.at[jnp.arange(B), slot].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[jnp.arange(B), slot].set(v[:, 0].astype(v_cache.dtype))
    if window > 0:
        # ring-buffer cache: unclamped lengths + wraparound band masking
        # (ring rows hold permuted absolute positions; with window == S the
        # band covers every written row, reducing to the length clamp)
        o = attn_lib.decode_attention(q, k_cache, v_cache, cache_len + 1,
                                      window=window, ring=True,
                                      impl=ctx.decode_impl,
                                      block_k=ctx.decode_block_k)
    else:
        valid = jnp.minimum(cache_len + 1, S)
        o = attn_lib.decode_attention(q, k_cache, v_cache, valid,
                                      impl=ctx.decode_impl,
                                      block_k=ctx.decode_block_k)
    out = o.reshape(B, 1, cfg.q_dim) @ p["wo"]
    return out, k_cache, v_cache


def attn_decode_paged(cfg: ArchConfig, p: Dict, x, position, ctx: ModelCtx,
                      k_pool, v_pool, layer, read_table, write_table,
                      cache_len):
    """One-token decode of layer ``layer`` against a paged cache.  x
    (B,1,d); pools (L, N, bs, Hk, D) stacked over layers and shared across
    slots; tables (B, nb) int32; cache_len (B,).

    The new K/V row lands at ``[layer, write_table[b, len//bs], len % bs]``
    — the *write* table, so slots that do not own their frontier block
    (shared prefix tails awaiting copy-on-write, or retired slots with
    zeroed tables) scatter into the reserved null block 0 instead of
    corrupting a neighbour.  The engine guarantees every *active* slot's
    frontier is exclusively owned (read == write) before the step, so live
    tokens always land in readable rows.  Attention then reads layer
    ``layer`` through the *read* table via the unified layout dispatch.
    The whole pools go in and come out: the write is an in-place row
    scatter, never a copy of a layer."""
    from repro.cache_layout import CacheLayout
    from repro.kernels import ops
    B = x.shape[0]
    bs = k_pool.shape[2]
    S = read_table.shape[1] * bs                 # virtual position space
    h = layers.apply_norm(cfg, p["norm"], x)
    q, k, v = _qkv(cfg, p, h,
                   position[:, None] if position.ndim == 1 else position,
                   ctx)
    blk = cache_len // bs
    off = cache_len % bs
    phys = write_table[jnp.arange(B), blk]
    k_pool = k_pool.at[layer, phys, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[layer, phys, off].set(v[:, 0].astype(v_pool.dtype))
    layout = CacheLayout(kind="paged", impl=ctx.decode_impl, block_size=bs)
    valid = jnp.minimum(cache_len + 1, S)
    o = ops.decode_attention(q, {"k": k_pool, "v": v_pool,
                                 "block_table": read_table}, valid,
                             layout=layout, layer=layer)
    out = o.reshape(B, 1, cfg.q_dim) @ p["wo"]
    return out, k_pool, v_pool


def attn_decode_spec(cfg: ArchConfig, p: Dict, x, position, ctx: ModelCtx,
                     k_cache, v_cache, cache_len, q_lens, *,
                     window: int = 0, snapshot: bool = False):
    """Speculative k-row decode.  x: (B, k, d); position (B, k) (or
    (B, k, 3) mrope); caches (B, S, Hk, D); cache_len (B,) committed rows;
    q_lens (B,) in [1, k] live rows per slot.

    All k rows' K/V land at positions ``cache_len + j`` *before* the
    attention; the k-row decode kernels give draft row ``j`` the effective
    length ``cache_len + 1 + j`` (causal intra-draft: cache plus rows
    ``<= j``) and zero out rows ``>= q_lens``.  Dead/rejected rows leave
    garbage only at positions beyond the committed length — masked until
    linear appends overwrite them — so linear caches need no rollback.

    Ring caches (``window > 0``): rows land at ``(cache_len + j) % S``.
    Exactness against row-by-row decode needs ``S >= window + k - 1``
    (:func:`init_cache` ``spec_margin``): then a slot written by row
    ``j' > j`` is outside row ``j``'s window band — exactly as the old
    position it overwrote would have been.  ``snapshot=True`` also returns
    the k overwritten (k, v) row pairs so the caller can restore rejected
    rows post-verification (:func:`_restore_ring_rows`)."""
    B, Sq = x.shape[:2]
    S = k_cache.shape[1]
    h = layers.apply_norm(cfg, p["norm"], x)
    q, k, v = _qkv(cfg, p, h, position, ctx)
    b_idx = jnp.arange(B)[:, None]
    pos = cache_len[:, None] + jnp.arange(Sq)[None]
    snaps = None
    if window > 0:
        slots = pos % S
        if snapshot:
            snaps = (k_cache[b_idx, slots], v_cache[b_idx, slots])
        k_cache = k_cache.at[b_idx, slots].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[b_idx, slots].set(v.astype(v_cache.dtype))
        o = attn_lib.decode_attention(q, k_cache, v_cache, cache_len + 1,
                                      window=window, ring=True,
                                      impl=ctx.decode_impl,
                                      block_k=ctx.decode_block_k,
                                      q_lens=q_lens)
    else:
        # dead rows spilling past the cache end are dropped, not clamped —
        # a clamp would race them against row S-1's live write
        k_cache = k_cache.at[b_idx, pos].set(k.astype(k_cache.dtype),
                                             mode="drop")
        v_cache = v_cache.at[b_idx, pos].set(v.astype(v_cache.dtype),
                                             mode="drop")
        o = attn_lib.decode_attention(q, k_cache, v_cache,
                                      jnp.minimum(cache_len + 1, S),
                                      impl=ctx.decode_impl,
                                      block_k=ctx.decode_block_k,
                                      q_lens=q_lens)
    out = o.reshape(B, Sq, cfg.q_dim) @ p["wo"]
    return out, k_cache, v_cache, snaps


def attn_decode_paged_spec(cfg: ArchConfig, p: Dict, x, position,
                           ctx: ModelCtx, k_pool, v_pool, layer, read_table,
                           write_table, cache_len, q_lens):
    """Speculative k-row twin of :func:`attn_decode_paged` (layer
    ``layer`` of the stacked pools): the k-token span scatters through the
    write table (row ``j`` at physical block ``write_table[b, (len + j) //
    bs]``, offset ``(len + j) % bs``); rows overflowing the virtual space
    land in the null block 0.  The engine pre-owns every block the live
    span touches
    (:meth:`~repro.serving.block_pool.SlotTables.ensure_writable_span`),
    so accepted rows always land in readable blocks; rejected rows leave
    garbage at dead positions only."""
    from repro.cache_layout import CacheLayout
    from repro.kernels import ops
    B, Sq = x.shape[:2]
    bs = k_pool.shape[2]
    nb = read_table.shape[1]
    S = nb * bs
    h = layers.apply_norm(cfg, p["norm"], x)
    q, k, v = _qkv(cfg, p, h, position, ctx)
    pos = cache_len[:, None] + jnp.arange(Sq)[None]
    blk = jnp.minimum(pos // bs, nb - 1)
    phys = write_table[jnp.arange(B)[:, None], blk]
    phys = jnp.where(pos < S, phys, 0)
    off = pos % bs
    k_pool = k_pool.at[layer, phys, off].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[layer, phys, off].set(v.astype(v_pool.dtype))
    layout = CacheLayout(kind="paged", impl=ctx.decode_impl, block_size=bs)
    o = ops.decode_attention(q, {"k": k_pool, "v": v_pool,
                                 "block_table": read_table},
                             jnp.minimum(cache_len + 1, S), layout=layout,
                             q_lens=q_lens, layer=layer)
    out = o.reshape(B, Sq, cfg.q_dim) @ p["wo"]
    return out, k_pool, v_pool


def _restore_ring_rows(k_cache, v_cache, snaps, cache_len, accepts, Sq: int):
    """Put back the pre-step (k, v) ring rows for rejected draft rows
    (``j >= accepts``) — the rollback half of gemma ring speculation.
    ``snaps``: the (B, Sq, Hk, D) row pairs :func:`attn_decode_spec`
    captured before writing."""
    S = k_cache.shape[1]
    B = k_cache.shape[0]
    b_idx = jnp.arange(B)[:, None]
    slots = (cache_len[:, None] + jnp.arange(Sq)[None]) % S
    keep = (jnp.arange(Sq)[None] < accepts[:, None])[..., None, None]
    snap_k, snap_v = snaps
    k_cache = k_cache.at[b_idx, slots].set(
        jnp.where(keep, k_cache[b_idx, slots], snap_k))
    v_cache = v_cache.at[b_idx, slots].set(
        jnp.where(keep, v_cache[b_idx, slots], snap_v))
    return k_cache, v_cache


def init_cross_attn(key, cfg: ArchConfig) -> Dict:
    return init_attn_block(key, cfg, cross=True)


def cross_attn_apply(cfg: ArchConfig, p: Dict, x, enc_kv, ctx: ModelCtx):
    """enc_kv: precomputed (k, v) from encoder output, (B,F,Hk,D)."""
    B, S, _ = x.shape
    h = layers.apply_norm(cfg, p["norm"], x)
    q = (h @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k, v = enc_kv
    o = attn_lib.attention(q, k, v, causal=False, impl="naive"
                           if S == 1 else ctx.attn_impl, chunk=ctx.attn_chunk)
    return o.reshape(B, S, cfg.q_dim) @ p["wo"]


def enc_kv(cfg: ArchConfig, p: Dict, enc_out):
    B, F, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, F, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, F, cfg.num_kv_heads, cfg.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# FFN block (dense MLP or MoE)
# ---------------------------------------------------------------------------

def init_ffn(key, cfg: ArchConfig, is_moe: bool) -> Dict:
    p = {"norm": layers.init_norm(cfg)}
    if is_moe:
        p["moe"] = moe.init_moe(key, cfg)
    else:
        p["mlp"] = layers.init_mlp(key, cfg)
    return p


def ffn_apply(cfg: ArchConfig, p: Dict, x, ctx: ModelCtx, live=None):
    """``live`` (optional (B, S) mask, serving prefill): positions masked
    out are excluded from MoE routing/capacity — see :func:`moe.moe_ffn`.
    Dense MLPs are per-token, so the mask is irrelevant there."""
    h = layers.apply_norm(cfg, p["norm"], x)
    if "moe" in p:
        out, aux = moe.moe_ffn(cfg, p["moe"], h, group_size=ctx.moe_group,
                               capacity_factor=ctx.moe_capacity_factor,
                               use_kernel=ctx.use_kernels,
                               constrain=ctx.constrain, live=live)
    else:
        out, aux = layers.apply_mlp(cfg, p["mlp"], h), None
    return ctx.constrain(out, "residual"), aux


def zero_aux(cfg: ArchConfig) -> Dict:
    a = {"lb_loss": jnp.zeros((), jnp.float32),
         "z_loss": jnp.zeros((), jnp.float32)}
    if cfg.is_moe:
        a["expert_load"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    return a


def _aux_of(aux, cfg: ArchConfig) -> Dict:
    if aux is None:
        return zero_aux(cfg)
    a = {"lb_loss": jnp.asarray(aux["lb_loss"], jnp.float32),
         "z_loss": jnp.asarray(aux["z_loss"], jnp.float32)}
    if cfg.is_moe:
        a["expert_load"] = jnp.asarray(aux["expert_load"], jnp.float32)
    return a


def _sum_aux(a, b):
    return {k: a[k] + b[k] for k in a}


# ---------------------------------------------------------------------------
# Family: uniform decoder-only (dense / full-MoE / vlm)
# ---------------------------------------------------------------------------

def _init_uniform_layer(key, cfg: ArchConfig) -> Dict:
    k1, k2 = jax.random.split(key)
    return {"attn": init_attn_block(k1, cfg),
            "ffn": init_ffn(k2, cfg, cfg.is_moe)}


def _stack_init(key, n: int, init_one) -> Dict:
    ks = jax.random.split(key, n)
    per = [init_one(k) for k in ks]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


def init_params(key, cfg: ArchConfig) -> Dict:
    """Entry point: params for any family."""
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": layers.init_embedding(ks[0], cfg),
                              "final_norm": layers.init_norm(cfg)}
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.init_dense(
            ks[1], cfg.d_model, cfg.padded_vocab, jnp.dtype(cfg.dtype))

    fam = family(cfg)
    if fam == "uniform":
        params["blocks"] = _stack_init(
            ks[2], cfg.num_layers, lambda k: _init_uniform_layer(k, cfg))
    elif fam == "rwkv6":
        def one(k):
            k1, k2 = jax.random.split(k)
            return {"tmix": ssm.init_rwkv6(k1, cfg),
                    "cmix": ssm.init_rwkv_cmix(k2, cfg),
                    "norm1": layers.init_norm(cfg),
                    "norm2": layers.init_norm(cfg)}
        params["blocks"] = _stack_init(ks[2], cfg.num_layers, one)
    elif fam == "jamba":
        n_periods = cfg.num_layers // cfg.attn_period
        def one_period(k):
            kk = jax.random.split(k, 4)
            per = cfg.attn_period
            n_moe = per // 2
            return {
                "attn": init_attn_block(kk[0], cfg),
                "mamba": _stack_init(kk[1], per - 1,
                                     lambda k2: {"norm": layers.init_norm(cfg),
                                                 "m": ssm.init_mamba(k2, cfg)}),
                "ffn_dense": _stack_init(
                    kk[2], per - n_moe, lambda k2: init_ffn(k2, cfg, False)),
                "ffn_moe": _stack_init(
                    kk[3], n_moe, lambda k2: init_ffn(k2, cfg, True)),
            }
        params["blocks"] = _stack_init(ks[2], n_periods, one_period)
    elif fam == "gemma":
        params["blocks"] = tuple(
            _init_uniform_layer(k, cfg) for k in jax.random.split(
                ks[2], cfg.num_layers))
    elif fam == "whisper":
        def dec_layer(k):
            kk = jax.random.split(k, 3)
            return {"attn": init_attn_block(kk[0], cfg),
                    "cross": init_cross_attn(kk[1], cfg),
                    "ffn": init_ffn(kk[2], cfg, False)}
        params["blocks"] = _stack_init(ks[2], cfg.num_layers, dec_layer)
        params["enc_blocks"] = _stack_init(
            ks[3], cfg.encoder_layers, lambda k: _init_uniform_layer(k, cfg))
        params["enc_final_norm"] = layers.init_norm(cfg)
        params["dec_pos"] = (jax.random.normal(
            ks[4], (32768, cfg.d_model), jnp.float32) * 0.01
        ).astype(jnp.dtype(cfg.dtype))
    else:
        raise ValueError(fam)
    return params


def family(cfg: ArchConfig) -> str:
    if cfg.ssm_type == "rwkv6":
        return "rwkv6"
    if cfg.ssm_type == "mamba":
        return "jamba"
    if cfg.local_global_pattern > 0:
        return "gemma"
    if cfg.encoder_layers > 0:
        return "whisper"
    return "uniform"


def _maybe_remat(fn, ctx: ModelCtx):
    return jax.checkpoint(fn) if ctx.remat else fn


# --- uniform forward --------------------------------------------------------

def _uniform_forward(cfg, params, h, positions, ctx, collect_kv: bool,
                     live=None):
    def body(carry, blk):
        x, aux = carry
        a_out, kv = attn_apply(cfg, blk["attn"], x, positions, ctx,
                               return_kv=collect_kv)
        x = x + a_out
        f_out, f_aux = ffn_apply(cfg, blk["ffn"], x, ctx, live=live)
        x = x + f_out
        return (x, _sum_aux(aux, _aux_of(f_aux, cfg))), kv

    body = _maybe_remat(body, ctx)
    (h, aux), kvs = jax.lax.scan(body, (h, zero_aux(cfg)), params["blocks"])
    return h, aux, kvs


def _uniform_decode(cfg, params, h, position, ctx, cache):
    def body(carry, inp):
        x = carry
        blk, kc, vc = inp
        a_out, kc, vc = attn_decode(cfg, blk["attn"], x, position, ctx,
                                    kc, vc, cache["len"])
        x = x + a_out
        f_out, _ = ffn_apply(cfg, blk["ffn"], x, ctx)
        x = x + f_out
        return x, (kc, vc)

    h, (kcs, vcs) = jax.lax.scan(body, h, (params["blocks"],
                                           cache["k"], cache["v"]))
    return h, {"k": kcs, "v": vcs, "len": cache["len"] + 1}


def _paged_layer_scan(cfg, params, h, ctx, cache, attend):
    """The paged decode's layer scan: the stacked pools ride in the carry
    with the residual stream, and ``attend(p, x, k_pool, v_pool, layer)``
    writes layer ``layer``'s rows into them in place and attends that
    layer.  The pools are never scanned over as ``xs`` / ``ys``, which
    would slice each layer out and stack it back (whole-pool copies every
    step)."""
    def body(carry, inp):
        x, kp, vp = carry
        blk, layer = inp
        a_out, kp, vp = attend(blk["attn"], x, kp, vp, layer)
        x = x + a_out
        f_out, _ = ffn_apply(cfg, blk["ffn"], x, ctx)
        return (x + f_out, kp, vp), None

    (h, kp, vp), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    return h, {**cache, "k": kp, "v": vp}


def _uniform_decode_paged(cfg, params, h, position, ctx, cache):
    def attend(p, x, kp, vp, layer):
        return attn_decode_paged(cfg, p, x, position, ctx, kp, vp, layer,
                                 cache["block_table"], cache["write_table"],
                                 cache["len"])

    h, cache = _paged_layer_scan(cfg, params, h, ctx, cache, attend)
    return h, {**cache, "len": cache["len"] + 1}


def _uniform_decode_spec(cfg, params, h, position, ctx, cache, q_lens):
    def body(x, inp):
        blk, kc, vc = inp
        a_out, kc, vc, _ = attn_decode_spec(cfg, blk["attn"], x, position,
                                            ctx, kc, vc, cache["len"], q_lens)
        x = x + a_out
        f_out, _ = ffn_apply(cfg, blk["ffn"], x, ctx)
        x = x + f_out
        return x, (kc, vc)

    h, (kcs, vcs) = jax.lax.scan(body, h, (params["blocks"],
                                           cache["k"], cache["v"]))
    return h, {"k": kcs, "v": vcs, "len": cache["len"]}


def _uniform_decode_paged_spec(cfg, params, h, position, ctx, cache, q_lens):
    def attend(p, x, kp, vp, layer):
        return attn_decode_paged_spec(
            cfg, p, x, position, ctx, kp, vp, layer, cache["block_table"],
            cache["write_table"], cache["len"], q_lens)

    return _paged_layer_scan(cfg, params, h, ctx, cache, attend)


# --- rwkv forward ------------------------------------------------------------

def _rwkv_forward(cfg, params, h, ctx):
    def body(x, blk):
        t_out, _ = ssm.rwkv6_forward(cfg, blk["tmix"],
                                     layers.apply_norm(cfg, blk["norm1"], x))
        x = x + t_out
        c_out, _ = ssm.rwkv_cmix_forward(cfg, blk["cmix"],
                                         layers.apply_norm(cfg, blk["norm2"], x))
        x = ctx.constrain(x + c_out, "residual")
        return x, None

    body = _maybe_remat(body, ctx)
    h, _ = jax.lax.scan(body, h, params["blocks"])
    return h


def _rwkv_decode(cfg, params, h, ctx, cache):
    def body(x, inp):
        blk, st = inp
        xn = layers.apply_norm(cfg, blk["norm1"], x)
        t_out, tstate = ssm.rwkv6_forward(
            cfg, blk["tmix"], xn, state={"last": st["tmix_last"],
                                         "wkv": st["wkv"]})
        x = x + t_out
        xn2 = layers.apply_norm(cfg, blk["norm2"], x)
        c_out, clast = ssm.rwkv_cmix_forward(cfg, blk["cmix"], xn2,
                                             state=st["cmix_last"])
        x = x + c_out
        new_st = {"tmix_last": xn[:, -1], "wkv": tstate["wkv"],
                  "cmix_last": xn2[:, -1]}
        return x, new_st

    h, states = jax.lax.scan(body, h, (params["blocks"], cache["states"]))
    return h, {"states": states, "len": cache["len"] + 1}


# --- jamba forward -----------------------------------------------------------

def _jamba_ffn_idx(j: int) -> Tuple[str, int]:
    # global layer index within period: j odd -> MoE slot j//2, else dense j//2
    return ("ffn_moe", j // 2) if j % 2 == 1 else ("ffn_dense", j // 2)


def _jamba_forward(cfg, params, h, positions, ctx, collect_kv: bool,
                   live=None):
    per = cfg.attn_period

    # nested remat: each sublayer is its own checkpoint so the period
    # backward holds one sublayer's recomputed internals at a time (the
    # period body is 8 layers — period-level remat alone peaks at 8x).
    def attn_sub(blk, x):
        a_out, kvs = attn_apply(cfg, blk["attn"], x, positions, ctx,
                                return_kv=collect_kv)
        return x + a_out, kvs

    def mamba_sub(mblk, x):
        m_out, _ = ssm.mamba_forward(
            cfg, mblk["m"], layers.apply_norm(cfg, mblk["norm"], x),
            chunk=ctx.mamba_chunk)
        return x + ctx.constrain(m_out, "residual")

    def ffn_sub(fblk, x):
        f_out, f_aux = ffn_apply(cfg, fblk, x, ctx, live=live)
        return x + f_out, _aux_of(f_aux, cfg)

    if ctx.remat:
        attn_sub = jax.checkpoint(attn_sub)
        mamba_sub = jax.checkpoint(mamba_sub)
        ffn_sub = jax.checkpoint(ffn_sub)

    def body(carry, blk):
        x, aux = carry
        kvs = None
        for j in range(per):
            if j == 0:
                x, kvs = attn_sub(blk, x)
            else:
                mblk = jax.tree.map(lambda a: a[j - 1], blk["mamba"])
                x = mamba_sub(mblk, x)
            name, idx = _jamba_ffn_idx(j)
            fblk = jax.tree.map(lambda a: a[idx], blk[name])
            x, f_aux = ffn_sub(fblk, x)
            aux = _sum_aux(aux, f_aux)
        return (x, aux), kvs

    body = _maybe_remat(body, ctx)
    (h, aux), kvs = jax.lax.scan(body, (h, zero_aux(cfg)), params["blocks"])
    return h, aux, kvs


def _jamba_decode(cfg, params, h, position, ctx, cache):
    per = cfg.attn_period

    def body(x, inp):
        blk, kc, vc, mstates = inp
        new_m = []
        for j in range(per):
            if j == 0:
                a_out, kc, vc = attn_decode(cfg, blk["attn"], x, position, ctx,
                                            kc, vc, cache["len"])
                x = x + a_out
            else:
                mblk = jax.tree.map(lambda a: a[j - 1], blk["mamba"])
                mst = jax.tree.map(lambda a: a[j - 1], mstates)
                m_out, mst = ssm.mamba_decode_step(
                    cfg, mblk["m"], layers.apply_norm(cfg, mblk["norm"], x), mst)
                new_m.append(mst)
                x = x + m_out
            name, idx = _jamba_ffn_idx(j)
            fblk = jax.tree.map(lambda a: a[idx], blk[name])
            f_out, _ = ffn_apply(cfg, fblk, x, ctx)
            x = x + f_out
        new_m = jax.tree.map(lambda *xs: jnp.stack(xs), *new_m)
        return x, (kc, vc, new_m)

    h, (kcs, vcs, ms) = jax.lax.scan(
        body, h, (params["blocks"], cache["k"], cache["v"], cache["mamba"]))
    return h, {"k": kcs, "v": vcs, "mamba": ms, "len": cache["len"] + 1}


# --- gemma forward (unrolled heterogeneous local/global) ---------------------

def _gemma_forward(cfg, params, h, positions, ctx, collect_kv: bool,
                   live=None):
    kinds = cfg.layer_kinds()
    kvs = []
    aux = zero_aux(cfg)

    def layer(x, blk, window):
        a_out, kv = attn_apply(cfg, blk["attn"], x, positions, ctx,
                               window=window, return_kv=collect_kv)
        x = x + a_out
        f_out, f_aux = ffn_apply(cfg, blk["ffn"], x, ctx, live=live)
        return x + f_out, kv, f_aux

    for blk, kind in zip(params["blocks"], kinds):
        window = cfg.sliding_window if kind == "local_attn" else 0
        fn = _maybe_remat(partial(layer, window=window), ctx)
        h, kv, f_aux = fn(h, blk)
        aux = _sum_aux(aux, _aux_of(f_aux, cfg))
        kvs.append(kv)
    return h, aux, kvs


def _gemma_decode(cfg, params, h, position, ctx, cache):
    kinds = cfg.layer_kinds()
    new_k, new_v = [], []
    for i, (blk, kind) in enumerate(zip(params["blocks"], kinds)):
        window = cfg.sliding_window if kind == "local_attn" else 0
        a_out, kc, vc = attn_decode(cfg, blk["attn"], h, position, ctx,
                                    cache["k"][i], cache["v"][i], cache["len"],
                                    window=window)
        h = h + a_out
        f_out, _ = ffn_apply(cfg, blk["ffn"], h, ctx)
        h = h + f_out
        new_k.append(kc)
        new_v.append(vc)
    return h, {"k": tuple(new_k), "v": tuple(new_v), "len": cache["len"] + 1}


def _gemma_decode_spec(cfg, params, h, position, ctx, cache, q_lens):
    """k-row gemma decode: global layers are linear (no rollback needed);
    local ring layers snapshot the k rows they overwrite so
    :func:`decode_spec` can restore the rejected ones post-verification.
    Returns (h, cache, snaps) with ``snaps[i]`` None for global layers."""
    kinds = cfg.layer_kinds()
    new_k, new_v, snaps = [], [], []
    for i, (blk, kind) in enumerate(zip(params["blocks"], kinds)):
        window = cfg.sliding_window if kind == "local_attn" else 0
        a_out, kc, vc, snap = attn_decode_spec(
            cfg, blk["attn"], h, position, ctx, cache["k"][i], cache["v"][i],
            cache["len"], q_lens, window=window, snapshot=window > 0)
        h = h + a_out
        f_out, _ = ffn_apply(cfg, blk["ffn"], h, ctx)
        h = h + f_out
        new_k.append(kc)
        new_v.append(vc)
        snaps.append(snap)
    return h, {"k": tuple(new_k), "v": tuple(new_v),
               "len": cache["len"]}, snaps


# --- whisper (enc-dec) --------------------------------------------------------

def _sinusoid(F: int, d: int):
    pos = jnp.arange(F)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def whisper_encode(cfg, params, frames, ctx):
    """frames: (B, F, d) precomputed by the (stubbed) conv frontend."""
    h = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)

    def body(x, blk):
        hn = layers.apply_norm(cfg, blk["attn"]["norm"], x)
        B, F, _ = hn.shape
        q = (hn @ blk["attn"]["wq"]).reshape(B, F, cfg.num_heads, cfg.head_dim)
        k = (hn @ blk["attn"]["wk"]).reshape(B, F, cfg.num_kv_heads, cfg.head_dim)
        v = (hn @ blk["attn"]["wv"]).reshape(B, F, cfg.num_kv_heads, cfg.head_dim)
        o = attn_lib.attention(q, k, v, causal=False, impl=ctx.attn_impl,
                               chunk=ctx.attn_chunk)
        x = x + o.reshape(B, F, cfg.q_dim) @ blk["attn"]["wo"]
        f_out, _ = ffn_apply(cfg, blk["ffn"], x, ctx)
        return x + f_out, None

    body = _maybe_remat(body, ctx)
    h, _ = jax.lax.scan(body, h, params["enc_blocks"])
    return layers.apply_norm(cfg, params["enc_final_norm"], h)


def _whisper_dec_forward(cfg, params, h, positions, enc_out, ctx,
                         collect_kv: bool):
    def body(carry, blk):
        x = carry
        a_out, kv = attn_apply(cfg, blk["attn"], x, positions, ctx,
                               return_kv=collect_kv)
        x = x + a_out
        ekv = enc_kv(cfg, blk["cross"], enc_out)
        x = x + cross_attn_apply(cfg, blk["cross"], x, ekv, ctx)
        f_out, _ = ffn_apply(cfg, blk["ffn"], x, ctx)
        x = x + f_out
        return x, (kv, ekv) if collect_kv else None

    body = _maybe_remat(body, ctx)
    h, kvs = jax.lax.scan(body, h, params["blocks"])
    return h, zero_aux(cfg), kvs


def whisper_prefill_cross(cfg, params, frames, ctx: ModelCtx = ModelCtx()):
    """Run the encoder and precompute per-layer cross-attention K/V for the
    decode cache: returns (cross_k, cross_v) stacked (L, B, F, Hk, D)."""
    enc_out = whisper_encode(cfg, params, frames, ctx)

    def one(blk):
        return enc_kv(cfg, blk["cross"], enc_out)

    ks, vs = jax.vmap(one, in_axes=(0,))(params["blocks"])
    return ks, vs


def _whisper_decode(cfg, params, h, position, ctx, cache):
    def body(x, inp):
        blk, kc, vc, ck, cv = inp
        a_out, kc, vc = attn_decode(cfg, blk["attn"], x, position, ctx,
                                    kc, vc, cache["len"])
        x = x + a_out
        x = x + cross_attn_apply(cfg, blk["cross"], x, (ck, cv), ctx)
        f_out, _ = ffn_apply(cfg, blk["ffn"], x, ctx)
        x = x + f_out
        return x, (kc, vc)

    h, (kcs, vcs) = jax.lax.scan(
        body, h, (params["blocks"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    return h, {"k": kcs, "v": vcs, "cross_k": cache["cross_k"],
               "cross_v": cache["cross_v"], "len": cache["len"] + 1}


def _whisper_decode_spec(cfg, params, h, position, ctx, cache, q_lens):
    # cross-attention is non-causal over a fixed frame count — every draft
    # row attends all frames, so k rows are safe; force the naive impl so
    # the k-row scores reduce bit-identically to the single-row decode path
    cross_ctx = dataclasses.replace(ctx, attn_impl="naive")

    def body(x, inp):
        blk, kc, vc, ck, cv = inp
        a_out, kc, vc, _ = attn_decode_spec(cfg, blk["attn"], x, position,
                                            ctx, kc, vc, cache["len"],
                                            q_lens)
        x = x + a_out
        x = x + cross_attn_apply(cfg, blk["cross"], x, (ck, cv), cross_ctx)
        f_out, _ = ffn_apply(cfg, blk["ffn"], x, ctx)
        x = x + f_out
        return x, (kc, vc)

    h, (kcs, vcs) = jax.lax.scan(
        body, h, (params["blocks"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    return h, {"k": kcs, "v": vcs, "cross_k": cache["cross_k"],
               "cross_v": cache["cross_v"], "len": cache["len"]}


# ---------------------------------------------------------------------------
# Pipeline-parallel stage slicing (uniform family)
# ---------------------------------------------------------------------------
#
# The stacked-layer (L, ...) scan params split at ``balance_stages`` bounds
# into per-stage blocks with shape-uniform inter-stage activations.  Stages
# may hold different layer counts, so every stage is padded to the widest
# stage and carries a per-slot ``mask`` — a masked slot is the identity
# (``x + 0 * sublayer(x)``), with the pad slots holding copies of a real
# layer's params so no degenerate-weight numerics ever run.  Embed and
# final-norm/head ride outside the stage stack as first/last-stage extras
# (``pp_partition_params`` -> {"stage", "last", ["embed"]}).


def stage_slice_params(cfg: ArchConfig, blocks, bounds) -> Dict:
    """Split stacked (L, ...) uniform blocks into {"blocks": (S, L_max, ...),
    "mask": (S, L_max)} at ``bounds`` (len S+1, from balance_stages)."""
    S = len(bounds) - 1
    sizes = [bounds[s + 1] - bounds[s] for s in range(S)]
    if min(sizes) < 1:
        raise ValueError(f"empty stage in bounds {bounds}")
    L_max = max(sizes)

    def slice_one(a):
        outs = []
        for s in range(S):
            sl = a[bounds[s]:bounds[s + 1]]
            if sizes[s] < L_max:                  # pad with a real layer
                pad = jnp.broadcast_to(sl[-1:],
                                       (L_max - sizes[s],) + sl.shape[1:])
                sl = jnp.concatenate([sl, pad], axis=0)
            outs.append(sl)
        return jnp.stack(outs)

    mask = jnp.asarray([[1.0] * n + [0.0] * (L_max - n) for n in sizes],
                       jnp.float32)
    return {"blocks": jax.tree.map(slice_one, blocks), "mask": mask}


def unstack_stage_params(stage_params: Dict, bounds) -> Any:
    """Inverse of :func:`stage_slice_params`: back to stacked (L, ...)."""
    S = len(bounds) - 1
    sizes = [bounds[s + 1] - bounds[s] for s in range(S)]

    def join(a):
        return jnp.concatenate([a[s, :sizes[s]] for s in range(S)], axis=0)

    return jax.tree.map(join, stage_params["blocks"])


def remap_stage_params(stage_params: Dict, old_bounds, new_bounds) -> Dict:
    """Live stage remap: re-carve a padded stage stack under new layer
    bounds (the observe->rebalance loop).  The model function is invariant
    — layer order is preserved, only the stage assignment (and pad width)
    changes."""
    blocks = unstack_stage_params(stage_params, old_bounds)
    return stage_slice_params(None, blocks, new_bounds)


def pp_partition_params(cfg: ArchConfig, params: Dict, bounds) -> Dict:
    """Full-model params -> the pipeline-parallel partition.

    Returns {"stage": stage-stacked blocks+mask, "last": final-norm + head
    (the tied-embedding table lives here when ``cfg.tie_embeddings``),
    "embed": input table (untied only)}."""
    if family(cfg) != "uniform":
        raise NotImplementedError(
            f"pipeline stage slicing covers the uniform family; "
            f"{cfg.name} is {family(cfg)}")
    if cfg.is_moe:
        raise NotImplementedError(
            "pipelined training drops MoE aux losses; dense uniform only")
    if cfg.pos_type == "mrope":
        raise NotImplementedError(
            "the pipelined path runs plain rope positions and a bare "
            "token embedding; mrope archs (patch_embeds mixing, "
            "3-component positions) are not stage-sliceable yet")
    out = {"stage": stage_slice_params(cfg, params["blocks"], bounds),
           "last": {"final_norm": params["final_norm"]}}
    if cfg.tie_embeddings:
        out["last"]["embed"] = params["embed"]
    else:
        out["last"]["lm_head"] = params["lm_head"]
        out["embed"] = params["embed"]
    return out


def pp_merge_params(cfg: ArchConfig, pp_params: Dict, bounds) -> Dict:
    """Inverse of :func:`pp_partition_params` (checkpoint/export)."""
    params = {"blocks": unstack_stage_params(pp_params["stage"], bounds),
              "final_norm": pp_params["last"]["final_norm"]}
    if cfg.tie_embeddings:
        params["embed"] = pp_params["last"]["embed"]
    else:
        params["lm_head"] = pp_params["last"]["lm_head"]
        params["embed"] = pp_params["embed"]
    return params


def make_stage_fn(cfg: ArchConfig, ctx: ModelCtx = ModelCtx(),
                  tp_axis: Optional[str] = None):
    """stage_fn(stage_slice, x) for the pipeline schedules: a masked scan
    over the stage's (padded) layers.  x: (mb, S, d) residual stream.

    With ``tp_axis`` set this is the manual Megatron-TP body, for use
    inside a shard_map whose mesh carries that axis alongside the stage
    axis (the trainer's full DP x TP x stage step): per-device block
    params hold head / d_ff column slices (see ``pp_stage_specs``); each
    residual branch enters through the Megatron ``f`` collective
    (identity forward / psum backward) and exits through ``g`` (psum
    forward / identity backward) — the conjugate pair is load-bearing: a
    bare ``lax.psum`` transposes to another psum, so cotangents crossing
    k branch boundaries would be scaled tp^k.  Gradients of TP-sliced
    weights come out exact and local; gradients of the *replicated*
    leaves inside a branch (the norms) are per-rank partials the trainer
    psums over ``tp_axis`` at sync time.  Local head counts are inferred
    from the sliced param shapes, so one builder serves any tp degree.
    """
    if tp_axis is not None:
        f_in, g_out = _tp_f_g(tp_axis)
    else:
        f_in = g_out = lambda x: x

    def stage_fn(p, x):
        qd = p["blocks"]["attn"]["wq"].shape[-1]
        kvd = p["blocks"]["attn"]["wk"].shape[-1]
        cfg_l = dataclasses.replace(cfg, num_heads=qd // cfg.head_dim,
                                    num_kv_heads=kvd // cfg.head_dim)
        B, S_seq, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S_seq)[None], (B, S_seq))

        def body(h, inp):
            blk, m = inp
            m = jax.lax.stop_gradient(m).astype(h.dtype)  # pad mask: no param
            a_out, _ = attn_apply(cfg_l, blk["attn"], f_in(h), positions,
                                  ctx)
            h = h + m * g_out(a_out)
            # dense FFN spelled out (pp_partition_params rejects MoE):
            # norm -> mlp -> residual constrain, = ffn_apply's dense path
            # (the constrain sees the full, post-collective branch output)
            hn = layers.apply_norm(cfg_l, blk["ffn"]["norm"], f_in(h))
            f_out = layers.apply_mlp(cfg_l, blk["ffn"]["mlp"], hn)
            h = h + m * ctx.constrain(g_out(f_out), "residual")
            return h, None

        body = _maybe_remat(body, ctx)
        h, _ = jax.lax.scan(body, x, (p["blocks"], p["mask"]))
        return h

    return stage_fn


def make_stage_fn_tp(cfg: ArchConfig, ctx: ModelCtx = ModelCtx(),
                     tp_axis: str = "model"):
    """The Megatron-TP configuration of :func:`make_stage_fn`."""
    return make_stage_fn(cfg, ctx, tp_axis=tp_axis)


def _tp_f_g(axis: str):
    """Megatron's conjugate TP collectives for shard_map bodies.

    ``f``: identity forward, psum backward — wraps a replicated activation
    entering a tensor-sliced branch, so the branch's input cotangent is
    reduced exactly once.  ``g``: psum forward, identity backward — merges
    the branch's partial outputs without re-reducing the (already
    replicated) cotangent on the way back.
    """

    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None),
             lambda _, ct: (jax.lax.psum(ct, axis),))

    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis)

    g.defvjp(lambda x: (jax.lax.psum(x, axis), None),
             lambda _, ct: (ct,))
    return f, g


def make_last_fn(cfg: ArchConfig, ctx: ModelCtx = ModelCtx()):
    """last_fn(last_params, y, tgt, mask) -> masked NLL *sum* over one
    micro-batch (the pipeline divides by the global mask weight)."""

    def last_fn(lp, y, tgt, mask):
        h = layers.apply_norm(cfg, lp["final_norm"], y)
        logits = ctx.constrain(layers.lm_logits(cfg, lp, h), "logits")
        nll = layers._nll(logits, tgt)
        return jnp.sum(nll * mask)

    return last_fn


# ---------------------------------------------------------------------------
# mrope decode positions (qwen2-vl serving)
# ---------------------------------------------------------------------------

def mrope_prompt_positions(cfg: ArchConfig, seq_len: int,
                           grid: Optional[Tuple[int, int]] = None):
    """(1, seq_len, 3) multimodal-RoPE positions for a prompt laid out as
    [grid_h x grid_w image patches][text...].

    Patch token p sits at (t=0, h=p//gw, w=p%gw); the first text token
    starts at ``max(gh, gw)`` — one past the largest patch index — and text
    advances all three components together (the qwen2-vl rule).  ``grid``
    None means a pure-text prompt (positions = arange on every component).
    Pad positions past the true prompt length are harmless: causal
    attention never lets a live query see them.

    ``seq_len`` here is the (possibly padded) buffer length, so the check
    below only catches grids larger than the whole buffer; the caller
    must guard ``gh*gw < true_len`` against the REAL prompt length (the
    serving engine rejects such requests at admission, and
    :func:`mrope_next_position` raises) — patches spilling into pad
    positions would silently mis-position every generated token.
    """
    idx = jnp.arange(seq_len)
    if grid is None:
        pos = jnp.stack([idx, idx, idx], axis=-1)
        return pos[None].astype(jnp.int32)
    gh, gw = grid
    n_patch = gh * gw
    if n_patch > seq_len:
        raise ValueError(f"patch grid {grid} exceeds prompt length {seq_len}")
    base = max(gh, gw)
    text = base + idx - n_patch
    t = jnp.where(idx < n_patch, 0, text)
    h = jnp.where(idx < n_patch, idx // max(gw, 1), text)
    w = jnp.where(idx < n_patch, idx % max(gw, 1), text)
    return jnp.stack([t, h, w], axis=-1)[None].astype(jnp.int32)


def mrope_next_position(true_len: int,
                        grid: Optional[Tuple[int, int]] = None) -> int:
    """Scalar position (shared by all three components) of the NEXT token
    after a ``true_len``-token prompt with the given patch layout — the
    value the serving engine advances per generated token."""
    if grid is None:
        return int(true_len)
    gh, gw = grid
    if gh * gw >= true_len:
        raise ValueError(
            f"patch grid {grid} needs {gh * gw} tokens but the prompt has "
            f"only {true_len}; a prompt must carry at least one text token "
            f"after its patches")
    return int(max(gh, gw) + true_len - gh * gw)


# ---------------------------------------------------------------------------
# Public API: forward / loss / cache / decode
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, params, batch, ctx):
    tokens = batch["tokens"]
    h = layers.embed_tokens(params["embed"], tokens, ctx.constrain)
    if cfg.pos_type == "mrope" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(h.dtype)
        h = jnp.concatenate([pe, h[:, pe.shape[1]:]], axis=1)
    if cfg.pos_type == "learned":
        S = tokens.shape[1]
        h = h + params["dec_pos"][:S][None]
    return ctx.constrain(h, "residual")


def _positions(cfg, batch):
    if cfg.pos_type == "mrope":
        return batch["positions"]                        # (B,S,3)
    B, S = batch["tokens"].shape
    return jnp.broadcast_to(jnp.arange(S)[None], (B, S))


def forward_hidden(cfg: ArchConfig, params: Dict, batch: Dict,
                   ctx: ModelCtx = ModelCtx(), collect_kv: bool = False,
                   true_len=None):
    """Full-sequence forward up to the final norm: (hidden, aux, kvs).

    ``true_len`` (serving prefill): positions >= true_len are right-padding
    — they are masked out of MoE routing so pad garbage never consumes
    expert capacity (every other sublayer is causal or per-token, so pads
    cannot touch real positions there)."""
    fam = family(cfg)
    h = _embed_inputs(cfg, params, batch, ctx)
    positions = _positions(cfg, batch)
    live = None
    if true_len is not None:
        B, S = batch["tokens"].shape
        live = jnp.broadcast_to((jnp.arange(S) < true_len)[None], (B, S))
    if fam == "uniform":
        h, aux, kvs = _uniform_forward(cfg, params, h, positions, ctx,
                                       collect_kv, live)
    elif fam == "rwkv6":
        h, aux, kvs = _rwkv_forward(cfg, params, h, ctx), zero_aux(cfg), None
    elif fam == "jamba":
        h, aux, kvs = _jamba_forward(cfg, params, h, positions, ctx,
                                     collect_kv, live)
    elif fam == "gemma":
        h, aux, kvs = _gemma_forward(cfg, params, h, positions, ctx,
                                     collect_kv, live)
    elif fam == "whisper":
        enc_out = whisper_encode(cfg, params, batch["frames"], ctx)
        h, aux, kvs = _whisper_dec_forward(cfg, params, h, positions, enc_out,
                                           ctx, collect_kv)
    else:
        raise ValueError(fam)
    return layers.apply_norm(cfg, params["final_norm"], h), aux, kvs


def forward(cfg: ArchConfig, params: Dict, batch: Dict,
            ctx: ModelCtx = ModelCtx(), collect_kv: bool = False,
            true_len=None):
    """Full-sequence forward.  Returns (logits, aux, kvs)."""
    h, aux, kvs = forward_hidden(cfg, params, batch, ctx, collect_kv,
                                 true_len=true_len)
    logits = ctx.constrain(layers.lm_logits(cfg, params, h), "logits")
    return logits, aux, kvs


def chunked_ce(cfg: ArchConfig, params: Dict, hidden, targets, mask,
               ctx: ModelCtx, chunk: int = 512):
    """LM-head + CE evaluated in sequence chunks with per-chunk remat.

    The (B, S, V) logits tensor — the single largest activation for 150k+
    vocabularies — only ever exists one chunk at a time; the backward
    recomputes each chunk's logits (head matmul) instead of stashing three
    full copies (fwd logits, softmax, d_logits)."""
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    if S % chunk:
        import math
        chunk = math.gcd(chunk, S)
    nh = S // chunk
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    hs = hidden.reshape(B, nh, chunk, d).swapaxes(0, 1)
    ts = targets.reshape(B, nh, chunk).swapaxes(0, 1)
    ms = mask.reshape(B, nh, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(carry, args):
        hc, tc, mc = args
        logits = ctx.constrain(layers.lm_logits(cfg, params, hc), "logits")
        nll = layers._nll(logits, tc)
        s, n = carry
        return (s + jnp.sum(nll * mc), n + jnp.sum(mc)), None

    (s, n), _ = jax.lax.scan(one, (jnp.zeros((), jnp.float32),
                                   jnp.zeros((), jnp.float32)),
                             (hs, ts, ms))
    return s / jnp.maximum(n, 1.0)


def loss_fn(cfg: ArchConfig, params: Dict, batch: Dict,
            ctx: ModelCtx = ModelCtx(),
            lb_weight: float = 0.01, z_weight: float = 1e-3):
    hidden, aux, _ = forward_hidden(cfg, params, batch, ctx)
    loss = chunked_ce(cfg, params, hidden, batch["targets"],
                      batch.get("mask"), ctx)
    total = loss + lb_weight * aux["lb_loss"] + z_weight * aux["z_loss"]
    return total, {"ce": loss, **aux}


# --- caches -------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               spec_margin: int = 0) -> Dict:
    """Decode cache pytree (all-zeros; lengths supplied separately).

    ``spec_margin`` (speculative decode, ``k - 1`` for draft width k):
    extra rows on gemma's sliding-window ring buffers.  A k-row
    speculative step writes k consecutive ring slots before attending, so
    exactness against row-by-row decode needs the slots written by rows
    ``> j`` to sit *outside* row ``j``'s window band — true iff the ring
    holds ``window + k - 1`` rows (the overwritten positions were outside
    the band too, so the attended sets match).  Linear caches need no
    margin: rejected rows land at dead positions beyond the committed
    length."""
    fam = family(cfg)
    dtype = jnp.dtype(cfg.dtype)
    Hk, D = cfg.num_kv_heads, cfg.head_dim
    L = cfg.num_layers

    def kv(n, s):
        return jnp.zeros((n, batch, s, Hk, D), dtype)

    if fam == "uniform":
        return {"k": kv(L, max_len), "v": kv(L, max_len),
                "len": jnp.zeros((batch,), jnp.int32)}
    if fam == "rwkv6":
        st = {"tmix_last": jnp.zeros((L, batch, cfg.d_model), dtype),
              "wkv": jnp.zeros((L, batch, cfg.d_model // cfg.rwkv_head_size,
                                cfg.rwkv_head_size, cfg.rwkv_head_size),
                               jnp.float32),
              "cmix_last": jnp.zeros((L, batch, cfg.d_model), dtype)}
        return {"states": st, "len": jnp.zeros((batch,), jnp.int32)}
    if fam == "jamba":
        n_per = cfg.num_layers // cfg.attn_period
        d_in = cfg.ssm_expand * cfg.d_model
        m = {"conv": jnp.zeros((n_per, cfg.attn_period - 1, batch,
                                cfg.ssm_d_conv - 1, d_in), dtype),
             "ssm": jnp.zeros((n_per, cfg.attn_period - 1, batch, d_in,
                               cfg.ssm_d_state), jnp.float32)}
        return {"k": kv(n_per, max_len), "v": kv(n_per, max_len), "mamba": m,
                "len": jnp.zeros((batch,), jnp.int32)}
    if fam == "gemma":
        kinds = cfg.layer_kinds()
        ks, vs = [], []
        for kind in kinds:
            s = (cfg.sliding_window + spec_margin
                 if kind == "local_attn" else max_len)
            ks.append(jnp.zeros((batch, s, Hk, D), dtype))
            vs.append(jnp.zeros((batch, s, Hk, D), dtype))
        return {"k": tuple(ks), "v": tuple(vs),
                "len": jnp.zeros((batch,), jnp.int32)}
    if fam == "whisper":
        F = cfg.encoder_frames
        return {"k": kv(L, max_len), "v": kv(L, max_len),
                "cross_k": kv(L, F), "cross_v": kv(L, F),
                "len": jnp.zeros((batch,), jnp.int32)}
    raise ValueError(fam)


def prefill_into_cache(cfg: ArchConfig, params: Dict, batch: Dict,
                       cache: Dict, ctx: ModelCtx = ModelCtx()):
    """Batched all-rows prefill: one full-sequence forward whose per-layer
    K/V land in the decode cache (every row shares one prompt length).

    Supported for the uniform and whisper families (stacked (L,B,S,Hk,D)
    caches).  The serving engine uses the family-polymorphic
    :func:`prefill_into_slot` instead, which covers every family — ring
    buffers, recurrent states, cross-KV — one slot row at a time.
    Returns (last_logits (B, V), cache)."""
    fam = family(cfg)
    if fam not in ("uniform", "whisper"):
        raise NotImplementedError(f"batched prefill for family {fam}")
    B, S_p = batch["tokens"].shape
    logits, aux, kvs = forward(cfg, params, batch, ctx, collect_kv=True)
    if fam == "whisper":
        kvs, ekvs = kvs
        cache["cross_k"], cache["cross_v"] = ekvs
    k, v = kvs                                  # (L, B, S_p, Hk, D)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    cache["len"] = jnp.full((B,), S_p, jnp.int32)
    return logits[:, -1], cache


# --- per-slot serving state (the family-polymorphic DecodeState protocol) ---
#
# Every family exposes the same three operations to the serving engine:
#   init_slots(cfg, n_slots, max_len)            -> slot-indexed state
#   prefill_into_slot(cfg, params, state, ...)   -> scatter one request
#   decode_step(cfg, params, state, tokens)      -> one token for all slots
# The state layout is family-owned (stacked KV rows, ring buffers, mamba /
# wkv recurrent rows, whisper cross-KV); the engine never looks inside it.


def init_slots(cfg: ArchConfig, n_slots: int, max_len: int,
               spec_margin: int = 0) -> Dict:
    """Slot-indexed decode state for ``n_slots`` concurrent requests (the
    serving alias of :func:`init_cache`: one cache row == one slot).
    ``spec_margin``: gemma ring headroom for speculative decode — see
    :func:`init_cache`."""
    return init_cache(cfg, n_slots, max_len, spec_margin=spec_margin)


def init_paged_slots(cfg: ArchConfig, n_slots: int, max_len: int, *,
                     num_blocks: int, block_size: int) -> Dict:
    """Paged decode state for the uniform family: per-layer KV lives in one
    shared pool ``(L, num_blocks, block_size, Hk, D)`` instead of per-slot
    padded rows; slots hold only block tables.  ``block_table`` is what
    attention *reads* through, ``write_table`` is where appends land
    (entries the slot does not own point at the null block 0).  Both start
    all-null: the serving engine's :class:`~repro.serving.block_pool`
    machinery populates them at admission.  Other families page through the
    generic pooled-leaf composition in :mod:`repro.serving.engine`."""
    if family(cfg) != "uniform":
        raise ValueError("init_paged_slots is the uniform-family native "
                         f"path, not {family(cfg)!r}")
    if max_len % block_size:
        raise ValueError(f"max_len={max_len} not a multiple of "
                         f"block_size={block_size}")
    dtype = jnp.dtype(cfg.dtype)
    Hk, D = cfg.num_kv_heads, cfg.head_dim
    L = cfg.num_layers
    nb = max_len // block_size
    # two tables, two buffers: a donated step must not see one buffer twice
    return {"k": jnp.zeros((L, num_blocks, block_size, Hk, D), dtype),
            "v": jnp.zeros((L, num_blocks, block_size, Hk, D), dtype),
            "block_table": jnp.zeros((n_slots, nb), jnp.int32),
            "write_table": jnp.zeros((n_slots, nb), jnp.int32),
            "len": jnp.zeros((n_slots,), jnp.int32)}


def _ring_rows(x, true_len, window: int):
    """Gather a prompt's K or V rows (x: (S, Hk, D), absolute positions)
    into ring-buffer layout: row ``r`` holds the *latest* position
    ``p < true_len`` with ``p % window == r`` — the layout decode's
    ``slot = len % window`` insertion continues from, wraparound-correct
    for prompts longer than the window.  Rows with no valid position
    (true_len < window) hold clamped garbage; decode masks them via the
    per-slot length."""
    S = x.shape[0]
    r = jnp.arange(window)
    p = true_len - 1 - jnp.mod(true_len - 1 - r, window)
    return x[jnp.clip(p, 0, S - 1)]


def _scatter_kv(cache: Dict, name: str, rows, slot):
    """Scatter (L, 1, S, Hk, D) prompt K/V into slot ``slot`` of a stacked
    (L, n_slots, max_len, Hk, D) cache entry."""
    return jax.lax.dynamic_update_slice(
        cache[name], rows.astype(cache[name].dtype), (0, slot, 0, 0, 0))


def _uniform_prefill_slot(cfg, params, cache, tokens, true_len, slot, ctx,
                          grid=None):
    batch = {"tokens": tokens}
    if cfg.pos_type == "mrope":
        # positions from the request's text+patch layout (qwen2-vl); the
        # patch ids embed through the token table — position handling is
        # what decode correctness needs (see mrope_prompt_positions)
        batch["positions"] = mrope_prompt_positions(cfg, tokens.shape[1],
                                                    grid)
    logits, _, (k, v) = forward(cfg, params, batch, ctx,
                                collect_kv=True, true_len=true_len)
    cache = dict(cache)
    cache["k"] = _scatter_kv(cache, "k", k, slot)
    cache["v"] = _scatter_kv(cache, "v", v, slot)
    cache["len"] = cache["len"].at[slot].set(true_len)
    return logits[0, true_len - 1], cache


def _uniform_prefill_slot_paged(cfg, params, cache, tokens, true_len, slot,
                                ctx, grid=None):
    """Paged twin of :func:`_uniform_prefill_slot`: the same whole-prompt
    forward, with the per-layer K/V rows scattered block-by-block through
    the slot's *write* table.  Virtual blocks the slot does not own (shared
    sealed prefix blocks, or table entries past the mapped span) have write
    entry 0, so their recomputed rows land in the null block — storage is
    deduplicated while prefill compute stays a pure function of the
    request.  Pad rows inside owned blocks are dead by the slot length and
    are overwritten in place by decode appends before the length reaches
    them (the same argument as the dense layout's bucket padding)."""
    batch = {"tokens": tokens}
    if cfg.pos_type == "mrope":
        batch["positions"] = mrope_prompt_positions(cfg, tokens.shape[1],
                                                    grid)
    logits, _, (k, v) = forward(cfg, params, batch, ctx,
                                collect_kv=True, true_len=true_len)
    L, _, S_p, Hk, D = k.shape
    bs = cache["k"].shape[2]
    pad = (-S_p) % bs
    if pad:
        grow = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
        k, v = jnp.pad(k, grow), jnp.pad(v, grow)
    nbp = (S_p + pad) // bs
    wt = cache["write_table"][slot][:nbp]                    # (nbp,)
    cache = dict(cache)
    cache["k"] = cache["k"].at[:, wt].set(
        k[:, 0].reshape(L, nbp, bs, Hk, D).astype(cache["k"].dtype))
    cache["v"] = cache["v"].at[:, wt].set(
        v[:, 0].reshape(L, nbp, bs, Hk, D).astype(cache["v"].dtype))
    cache["len"] = cache["len"].at[slot].set(true_len)
    return logits[0, true_len - 1], cache


def _uniform_prefill_slot_chunked(cfg, params, cache, tokens, true_len,
                                  slot, ctx, chunk: int):
    """Streaming prefill: the prompt runs through the stack in fixed
    ``chunk``-token pieces that reuse the decode cache-append path — each
    chunk's per-layer K/V lands in the slot's cache rows and the next chunk
    attends the accumulated prefix (``q_offset`` causal masking).  A long
    prompt therefore never compiles or pads a monolithic ``(1, S_pad)``
    forward: the traced unit is one chunk, scanned ``S_pad/chunk`` times.

    Parity with the whole-prompt path is exact for dense uniform archs
    (per-position math is identical; only the attention accumulation order
    differs).  MoE layers route each chunk as its own capacity group, so a
    capacity-dropping MoE can differ from the bucket-length grouping of the
    monolithic forward — streams stay a pure function of request + chunk
    size.  mrope archs take the whole-prompt path (their patch/text
    position layout is not chunk-decomposable here)."""
    B, S_in = tokens.shape
    pad = (-S_in) % chunk
    if pad:
        tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    S_pad = S_in + pad
    n_chunks = S_pad // chunk
    L = cfg.num_layers
    S_max = cache["k"].shape[2]
    Hk, D = cfg.num_kv_heads, cfg.head_dim
    k_rows = jax.lax.dynamic_slice(cache["k"], (0, slot, 0, 0, 0),
                                   (L, 1, S_max, Hk, D))
    v_rows = jax.lax.dynamic_slice(cache["v"], (0, slot, 0, 0, 0),
                                   (L, 1, S_max, Hk, D))
    if S_pad > S_max:
        # chunk padding may overhang the cache (bucket == S_max with a
        # non-dividing chunk): give the working rows that headroom so the
        # tail chunk's dynamic_update_slice never clamps into live rows —
        # the overhang holds pad-token K/V only and is dropped at
        # write-back (positions >= true_len are dead by the slot length)
        grow = ((0, 0), (0, 0), (0, S_pad - S_max), (0, 0), (0, 0))
        k_rows = jnp.pad(k_rows, grow)
        v_rows = jnp.pad(v_rows, grow)

    def per_chunk(carry, ci):
        k_rows, v_rows = carry
        c0 = ci * chunk
        toks = jax.lax.dynamic_slice(tokens, (0, c0), (1, chunk))
        x = layers.embed_tokens(params["embed"], toks)
        positions = c0 + jnp.arange(chunk)[None]             # (1, chunk)
        live = positions < true_len

        def body(h, inp):
            blk, kc, vc = inp                                # kc (1,S,Hk,D)
            hn = layers.apply_norm(cfg, blk["attn"]["norm"], h)
            q, k, v = _qkv(cfg, blk["attn"], hn, positions, ctx)
            kc = jax.lax.dynamic_update_slice(
                kc, k.astype(kc.dtype), (0, c0, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v.astype(vc.dtype), (0, c0, 0, 0))
            o = attn_lib.attention(q, kc, vc, causal=True, q_offset=c0,
                                   impl="chunked", chunk=ctx.attn_chunk)
            h = h + o.reshape(1, chunk, cfg.q_dim) @ blk["attn"]["wo"]
            f_out, _ = ffn_apply(cfg, blk["ffn"], h, ctx, live=live)
            return h + f_out, (kc, vc)

        x, (k_rows, v_rows) = jax.lax.scan(
            body, x, (params["blocks"], k_rows, v_rows))
        return (k_rows, v_rows), x                           # x (1,chunk,d)

    (k_rows, v_rows), hs = jax.lax.scan(
        per_chunk, (k_rows, v_rows), jnp.arange(n_chunks))
    hidden = hs.transpose(1, 0, 2, 3).reshape(1, S_pad, cfg.d_model)
    row = jax.lax.dynamic_slice(hidden, (0, true_len - 1, 0),
                                (1, 1, cfg.d_model))
    row = layers.apply_norm(cfg, params["final_norm"], row)
    logits = layers.lm_logits(cfg, params, row)              # (1, 1, V)
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k_rows[:, :, :S_max], (0, slot, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v_rows[:, :, :S_max], (0, slot, 0, 0, 0))
    cache["len"] = cache["len"].at[slot].set(true_len)
    return logits[0, 0], cache


def _gemma_prefill_slot(cfg, params, cache, tokens, true_len, slot, ctx):
    logits, _, kvs = forward(cfg, params, {"tokens": tokens}, ctx,
                             collect_kv=True, true_len=true_len)
    cache = dict(cache)
    new_k, new_v = [], []
    for (k, v), kind, kc, vc in zip(kvs, cfg.layer_kinds(),
                                    cache["k"], cache["v"]):
        if kind == "local_attn":                 # ring-buffer rows
            ring = kc.shape[1]       # window + spec margin (see init_cache)
            k_row = _ring_rows(k[0], true_len, ring)
            v_row = _ring_rows(v[0], true_len, ring)
        else:                                    # full rows from position 0
            k_row, v_row = k[0], v[0]
        new_k.append(jax.lax.dynamic_update_slice(
            kc, k_row[None].astype(kc.dtype), (slot, 0, 0, 0)))
        new_v.append(jax.lax.dynamic_update_slice(
            vc, v_row[None].astype(vc.dtype), (slot, 0, 0, 0)))
    cache["k"], cache["v"] = tuple(new_k), tuple(new_v)
    cache["len"] = cache["len"].at[slot].set(true_len)
    return logits[0, true_len - 1], cache


def _jamba_prefill_slot(cfg, params, cache, tokens, true_len, slot, ctx):
    per = cfg.attn_period
    batch = {"tokens": tokens}
    h = _embed_inputs(cfg, params, batch, ctx)
    positions = _positions(cfg, batch)
    B, S = tokens.shape
    live = jnp.broadcast_to((jnp.arange(S) < true_len)[None], (B, S))

    def body(x, blk):
        kv, new_m = None, []
        for j in range(per):
            if j == 0:
                a_out, kv = attn_apply(cfg, blk["attn"], x, positions, ctx,
                                       return_kv=True)
                x = x + a_out
            else:
                mblk = jax.tree.map(lambda a: a[j - 1], blk["mamba"])
                m_out, mst = ssm.mamba_forward(
                    cfg, mblk["m"], layers.apply_norm(cfg, mblk["norm"], x),
                    chunk=ctx.mamba_chunk, true_len=true_len)
                new_m.append(mst)
                x = x + m_out
            name, idx = _jamba_ffn_idx(j)
            fblk = jax.tree.map(lambda a: a[idx], blk[name])
            f_out, _ = ffn_apply(cfg, fblk, x, ctx, live=live)
            x = x + f_out
        new_m = jax.tree.map(lambda *xs: jnp.stack(xs), *new_m)
        return x, (kv, new_m)

    h, (kvs, ms) = jax.lax.scan(body, h, params["blocks"])
    h = layers.apply_norm(cfg, params["final_norm"], h)
    logits = layers.lm_logits(cfg, params, h)
    cache = dict(cache)
    k, v = kvs                                   # (n_per, 1, S, Hk, D)
    cache["k"] = _scatter_kv(cache, "k", k, slot)
    cache["v"] = _scatter_kv(cache, "v", v, slot)
    # mamba rows: (n_per, per-1, B, ...) — batch axis 2
    cache["mamba"] = ssm.scatter_slot_state(cache["mamba"], ms, slot,
                                            batch_axis=2)
    cache["len"] = cache["len"].at[slot].set(true_len)
    return logits[0, true_len - 1], cache


def _rwkv_prefill_slot(cfg, params, cache, tokens, true_len, slot, ctx):
    h = _embed_inputs(cfg, params, {"tokens": tokens}, ctx)

    def body(x, blk):
        xn = layers.apply_norm(cfg, blk["norm1"], x)
        t_out, tstate = ssm.rwkv6_forward(cfg, blk["tmix"], xn,
                                          true_len=true_len)
        x = x + t_out
        xn2 = layers.apply_norm(cfg, blk["norm2"], x)
        c_out, clast = ssm.rwkv_cmix_forward(cfg, blk["cmix"], xn2,
                                             true_len=true_len)
        x = x + c_out
        st = {"tmix_last": tstate["last"], "wkv": tstate["wkv"],
              "cmix_last": clast}
        return x, st

    h, states = jax.lax.scan(body, h, params["blocks"])
    h = layers.apply_norm(cfg, params["final_norm"], h)
    logits = layers.lm_logits(cfg, params, h)
    cache = dict(cache)
    cache["states"] = ssm.scatter_slot_state(cache["states"], states, slot,
                                             batch_axis=1)
    cache["len"] = cache["len"].at[slot].set(true_len)
    return logits[0, true_len - 1], cache


def _whisper_prefill_slot(cfg, params, cache, tokens, true_len, slot, ctx,
                          frames):
    logits, _, (kvs, ekvs) = forward(
        cfg, params, {"tokens": tokens, "frames": frames}, ctx,
        collect_kv=True, true_len=true_len)
    cache = dict(cache)
    cache["k"] = _scatter_kv(cache, "k", kvs[0], slot)
    cache["v"] = _scatter_kv(cache, "v", kvs[1], slot)
    cache["cross_k"] = _scatter_kv(cache, "cross_k", ekvs[0], slot)
    cache["cross_v"] = _scatter_kv(cache, "cross_v", ekvs[1], slot)
    cache["len"] = cache["len"].at[slot].set(true_len)
    return logits[0, true_len - 1], cache


def prefill_into_slot(cfg: ArchConfig, params: Dict, cache: Dict, tokens,
                      true_len, slot, ctx: ModelCtx = ModelCtx(),
                      frames=None, grid=None, chunk: int = 0):
    """Scatter one request's prompt state into slot ``slot`` of a decode
    state built by :func:`init_slots`; returns (last-position logits (V,),
    new state).  This is the family-polymorphic half of the serving
    DecodeState protocol — every architecture family implements it over
    its own state layout:

    * ``uniform``  — per-layer K/V rows scattered at positions [0, true_len).
    * ``gemma``    — global layers as uniform; local layers land in
      sliding-window **ring-buffer** rows (``position % window``),
      wraparound-correct for prompts longer than the window.
    * ``jamba``    — per-period K/V rows + mamba conv/ssm recurrent rows.
    * ``rwkv6``    — wkv ``S``-state plus time-mix/channel-mix shift states.
    * ``whisper``  — decoder self-KV plus per-slot cross-KV computed once
      here from the request's encoder ``frames`` (1, F, d_model).

    ``tokens`` (1, S_pad) may be right-padded to a static prefill bucket;
    ``true_len`` marks the real prompt end.  KV families mask padding via
    the per-slot length; recurrent families neutralize pad steps inside
    the scan (identity transitions — see :mod:`repro.models.ssm`); MoE
    layers drop pad positions from routing so they never consume expert
    capacity.  The scattered state is the state after ``true_len`` tokens
    — exactly, except that a capacity-dropping MoE evaluates its group
    capacity at the bucket length (streams stay a pure function of the
    request + bucket, never of pad contents).

    ``chunk > 0`` (uniform family): streaming prefill — the prompt runs in
    fixed ``chunk``-token pieces through the decode cache-append path, so
    long prompts never trace a monolithic ``(1, S_pad)`` forward (see
    :func:`_uniform_prefill_slot_chunked`)."""
    fam = family(cfg)
    if fam == "uniform":
        if "block_table" in cache:
            if chunk > 0:
                raise ValueError("streaming (chunked) prefill is not "
                                 "supported on the native paged path; use "
                                 "the pooled-leaf composition backend")
            return _uniform_prefill_slot_paged(cfg, params, cache, tokens,
                                               true_len, slot, ctx,
                                               grid=grid)
        if chunk > 0 and cfg.pos_type != "mrope":
            # streaming prefill: fixed chunks through the decode
            # cache-append path (mrope prompts keep the monolithic
            # forward — their position layout is not chunk-decomposable)
            return _uniform_prefill_slot_chunked(
                cfg, params, cache, tokens, true_len, slot, ctx, chunk)
        return _uniform_prefill_slot(cfg, params, cache, tokens, true_len,
                                     slot, ctx, grid=grid)
    if fam == "gemma":
        return _gemma_prefill_slot(cfg, params, cache, tokens, true_len,
                                   slot, ctx)
    if fam == "jamba":
        return _jamba_prefill_slot(cfg, params, cache, tokens, true_len,
                                   slot, ctx)
    if fam == "rwkv6":
        return _rwkv_prefill_slot(cfg, params, cache, tokens, true_len,
                                  slot, ctx)
    if fam == "whisper":
        if frames is None:
            raise ValueError("whisper prefill_into_slot needs the request's "
                             "encoder frames (1, F, d_model)")
        return _whisper_prefill_slot(cfg, params, cache, tokens, true_len,
                                     slot, ctx, frames)
    raise ValueError(fam)


def decode_step(cfg: ArchConfig, params: Dict, cache: Dict, tokens,
                ctx: ModelCtx = ModelCtx(), positions=None):
    """One decode step.  tokens (B,1) -> (logits (B,1,V), new_cache)."""
    fam = family(cfg)
    batch = {"tokens": tokens}
    if positions is not None:
        batch["positions"] = positions
    h = layers.embed_tokens(params["embed"], tokens)
    if cfg.pos_type == "learned":
        h = h + jnp.take(params["dec_pos"], cache["len"], axis=0)[:, None]
    pos = positions if positions is not None else cache["len"]
    if fam == "uniform":
        if "block_table" in cache:
            h, cache = _uniform_decode_paged(cfg, params, h, pos, ctx, cache)
        else:
            h, cache = _uniform_decode(cfg, params, h, pos, ctx, cache)
    elif fam == "rwkv6":
        h, cache = _rwkv_decode(cfg, params, h, ctx, cache)
    elif fam == "jamba":
        h, cache = _jamba_decode(cfg, params, h, pos, ctx, cache)
    elif fam == "gemma":
        h, cache = _gemma_decode(cfg, params, h, pos, ctx, cache)
    elif fam == "whisper":
        h, cache = _whisper_decode(cfg, params, h, pos, ctx, cache)
    else:
        raise ValueError(fam)
    h = layers.apply_norm(cfg, params["final_norm"], h)
    logits = layers.lm_logits(cfg, params, h)
    return logits, cache


# Families whose decode state is a pure KV cache: rejected draft rows can
# be abandoned (linear caches) or restored (gemma rings).  jamba / rwkv6
# carry recurrent per-token state that cannot cheaply rewind.
SPEC_FAMILIES = ("uniform", "gemma", "whisper")


def verify_greedy(tokens, logits, q_lens):
    """Greedy draft verification.  ``tokens`` (B, k) are the step inputs
    (row 0 = last committed token, rows 1.. = drafts), ``logits`` (B, k, V)
    from :func:`decode_spec`, ``q_lens`` (B,) live rows.  Returns
    ``accepts`` (B,) in ``[1, q_lens]``: row ``j``'s greedy emission
    ``argmax(logits[:, j])`` counts iff every earlier draft row matched the
    emission before it — by induction the accepted prefix is exactly what
    row-by-row greedy decode would have produced."""
    B, k = tokens.shape
    g = jnp.argmax(logits, axis=-1)
    ok = (tokens[:, 1:] == g[:, :-1]) & \
        (jnp.arange(k - 1)[None] < q_lens[:, None] - 1)
    return (1 + jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                        axis=1)).astype(jnp.int32)


def decode_spec(cfg: ArchConfig, params: Dict, cache: Dict, tokens,
                ctx: ModelCtx = ModelCtx(), q_lens=None, positions=None):
    """Speculative k-row decode + greedy verification + commit.

    ``tokens`` (B, k): row 0 is the last committed token (whose KV is not
    yet in the cache — the same contract as :func:`decode_step`), rows
    ``1..k-1`` the self-drafted continuation.  ``q_lens`` (B,) in
    ``[1, k]``: live rows per slot (1 = plain single-step for that slot;
    default all-k).  ``positions`` (B, k) or (B, k, 3): explicit decode
    positions (mrope).

    Returns ``(logits (B, k, V), accepts (B,), cache)``: the emitted
    tokens are ``argmax(logits, -1)[:, :accepts]`` per slot, and the cache
    is *committed* — ``len += accepts``, with gemma ring rows written by
    rejected drafts restored from pre-step snapshots.  Rejected rows on
    linear caches (uniform dense/paged, whisper, gemma global layers)
    leave garbage only at positions beyond the committed length, which the
    per-slot length masks until later appends overwrite it.

    Recurrent-state families raise: their per-token state cannot cheaply
    roll back a rejected draft."""
    fam = family(cfg)
    if fam not in SPEC_FAMILIES:
        raise ValueError(
            f"speculative decode needs a rollback-free KV cache; family "
            f"{fam!r} carries recurrent per-token state that cannot rewind "
            f"rejected draft rows (supported: {SPEC_FAMILIES})")
    B, k = tokens.shape
    if q_lens is None:
        q_lens = jnp.full((B,), k, jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    if fam == "gemma":
        for kc, kind in zip(cache["k"], cfg.layer_kinds()):
            if kind == "local_attn" and \
                    kc.shape[1] < cfg.sliding_window + k - 1:
                raise ValueError(
                    f"gemma speculative decode with k={k} needs ring "
                    f"buffers of >= window + k - 1 = "
                    f"{cfg.sliding_window + k - 1} rows (have "
                    f"{kc.shape[1]}); build the state with "
                    f"init_cache(..., spec_margin=k - 1)")
    h = layers.embed_tokens(params["embed"], tokens)
    if cfg.pos_type == "learned":
        h = h + jnp.take(params["dec_pos"],
                         cache["len"][:, None] + jnp.arange(k), axis=0)
    pos = positions if positions is not None \
        else cache["len"][:, None] + jnp.arange(k)[None]
    snaps = None
    if fam == "uniform":
        if "block_table" in cache:
            h, cache = _uniform_decode_paged_spec(cfg, params, h, pos, ctx,
                                                  cache, q_lens)
        else:
            h, cache = _uniform_decode_spec(cfg, params, h, pos, ctx, cache,
                                            q_lens)
    elif fam == "gemma":
        h, cache, snaps = _gemma_decode_spec(cfg, params, h, pos, ctx,
                                             cache, q_lens)
    else:
        h, cache = _whisper_decode_spec(cfg, params, h, pos, ctx, cache,
                                        q_lens)
    h = layers.apply_norm(cfg, params["final_norm"], h)
    logits = layers.lm_logits(cfg, params, h)
    accepts = verify_greedy(tokens, logits, q_lens)
    cache = dict(cache)
    if snaps is not None:
        new_k, new_v = list(cache["k"]), list(cache["v"])
        for i, snap in enumerate(snaps):
            if snap is None:
                continue
            new_k[i], new_v[i] = _restore_ring_rows(
                new_k[i], new_v[i], snap, cache["len"], accepts, k)
        cache["k"], cache["v"] = tuple(new_k), tuple(new_v)
    cache["len"] = cache["len"] + accepts
    return logits, accepts, cache

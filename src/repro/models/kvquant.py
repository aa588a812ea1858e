"""Int8 KV-cache quantization (serving memory optimization).

The §Roofline decode cells are bandwidth-bound streaming the KV cache
(e.g. deepseek-7b decode_32k: 8 GB/dev of cache, the whole memory term).
Per-(position, head) symmetric int8 quantization halves cache bytes vs
bf16 — and the roofline memory term with it — at <0.5% attention-output
error (validated in tests/test_kvquant.py).

Layout: values int8 (B, S, Hk, D); scales f32 (B, S, Hk) — amax over the
head dim, the standard KV-quant granularity.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(..., D) float -> (int8 values, f32 scales (...,))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantize_kv_tree(tree):
    """Quantize every leaf of a KV pytree (e.g. gemma's per-layer ring
    buffers, whisper's cross-KV): returns (int8-values tree, scales tree)
    with the input treedef.  Requantizing a dequantized leaf is exact —
    the max-|x| element of each (…, D) row always lands on ±127, pinning
    the scale — so round-tripping untouched cache rows every decode step
    does not drift (the property the serving int8 composition relies on)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    qs, ss = [], []
    for leaf in leaves:
        q, s = quantize_kv(leaf)
        qs.append(q)
        ss.append(s)
    return treedef.unflatten(qs), treedef.unflatten(ss)


def dequantize_kv_tree(q_tree, s_tree, dtype=jnp.bfloat16):
    """Inverse of :func:`quantize_kv_tree`."""
    return jax.tree.map(lambda q, s: dequantize_kv(q, s, dtype),
                        q_tree, s_tree)


def init_quant_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                     layers: int) -> Dict:
    """Stacked per-layer quantized K/V cache."""
    return {
        "k_q": jnp.zeros((layers, batch, max_len, n_kv, head_dim), jnp.int8),
        "k_s": jnp.zeros((layers, batch, max_len, n_kv), jnp.float32),
        "v_q": jnp.zeros((layers, batch, max_len, n_kv, head_dim), jnp.int8),
        "v_s": jnp.zeros((layers, batch, max_len, n_kv), jnp.float32),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def cache_insert(cache_q, cache_s, pos, k_new):
    """Insert one token's K or V (B, Hk, D) at per-sequence positions."""
    B = k_new.shape[0]
    q, s = quantize_kv(k_new)
    cache_q = cache_q.at[jnp.arange(B), pos].set(q)
    cache_s = cache_s.at[jnp.arange(B), pos].set(s)
    return cache_q, cache_s


def cache_insert_paged(pool_q, pool_s, phys, off, k_new):
    """Paged twin of :func:`cache_insert`: pools (N, bs, Hk, D) / (N, bs,
    Hk); ``phys``/``off`` (B,) physical block and in-block row per slot
    (write-table resolved — unowned slots target the null block 0)."""
    q, s = quantize_kv(k_new)
    pool_q = pool_q.at[phys, off].set(q)
    pool_s = pool_s.at[phys, off].set(s)
    return pool_q, pool_s


def init_model_quant_cache(cfg, batch: int, max_len: int) -> Dict:
    """Quantized decode cache shaped for an ArchConfig (uniform family:
    stacked per-layer K/V, the layout serving's Int8KVBackend scatters
    into)."""
    from repro.models import transformer as tf
    if tf.family(cfg) != "uniform":
        raise NotImplementedError(
            f"int8 KV cache supports the uniform family, not {tf.family(cfg)}")
    return init_quant_cache(batch, max_len, cfg.num_kv_heads, cfg.head_dim,
                            cfg.num_layers)


def init_paged_quant_cache(cfg, n_slots: int, max_len: int, *,
                           num_blocks: int, block_size: int) -> Dict:
    """Paged int8 decode cache (uniform family): pooled quantized values
    ``(L, num_blocks, block_size, Hk, D)`` int8 + pooled scales
    ``(L, num_blocks, block_size, Hk)`` f32, with the same read/write block
    tables as :func:`transformer.init_paged_slots`."""
    from repro.models import transformer as tf
    if tf.family(cfg) != "uniform":
        raise NotImplementedError(
            f"int8 KV cache supports the uniform family, not {tf.family(cfg)}")
    if max_len % block_size:
        raise ValueError(f"max_len={max_len} not a multiple of "
                         f"block_size={block_size}")
    L, Hk, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    nb = max_len // block_size
    return {
        "k_q": jnp.zeros((L, num_blocks, block_size, Hk, D), jnp.int8),
        "k_s": jnp.zeros((L, num_blocks, block_size, Hk), jnp.float32),
        "v_q": jnp.zeros((L, num_blocks, block_size, Hk, D), jnp.int8),
        "v_s": jnp.zeros((L, num_blocks, block_size, Hk), jnp.float32),
        "block_table": jnp.zeros((n_slots, nb), jnp.int32),
        "write_table": jnp.zeros((n_slots, nb), jnp.int32),
        "len": jnp.zeros((n_slots,), jnp.int32),
    }


def quant_decode_step(cfg, params, cache: Dict, tokens, ctx=None):
    """One decode step against the int8 cache — the quantized twin of
    ``transformer.decode_step`` for the uniform family.

    tokens (B, 1) -> (logits (B, 1, V), new_cache).  Per-layer K/V for the
    incoming token are quantized on insert; attention runs via
    :func:`decode_attention_quant` so the cache is never dequantized in
    full.  A paged cache (``"block_table"`` present — built by
    :func:`init_paged_quant_cache`) inserts through the write table and
    attends through the read table via the unified layout dispatch."""
    from repro.models import layers
    from repro.models import transformer as tf
    if tf.family(cfg) != "uniform":
        raise NotImplementedError("quant_decode_step: uniform family only")
    if ctx is None:
        ctx = tf.ModelCtx()
    B = tokens.shape[0]
    pos = cache["len"]                              # (B,) per-row lengths
    h = layers.embed_tokens(params["embed"], tokens)
    paged = "block_table" in cache
    if paged:
        from repro.cache_layout import CacheLayout
        from repro.kernels import ops
        bs = cache["k_q"].shape[2]
        S = cache["block_table"].shape[1] * bs
        phys = cache["write_table"][jnp.arange(B), pos // bs]
        off = pos % bs
        layout = CacheLayout(kind="paged", kv_bits=8, impl=ctx.decode_impl,
                             block_size=bs)

    def body(x, inp):
        blk, k_q, k_s, v_q, v_s = inp
        hn = layers.apply_norm(cfg, blk["attn"]["norm"], x)
        q, k, v = tf._qkv(cfg, blk["attn"], hn, pos[:, None], ctx)
        if paged:
            k_q, k_s = cache_insert_paged(k_q, k_s, phys, off, k[:, 0])
            v_q, v_s = cache_insert_paged(v_q, v_s, phys, off, v[:, 0])
            o = ops.decode_attention(
                q, {"k_q": k_q, "k_s": k_s, "v_q": v_q, "v_s": v_s,
                    "block_table": cache["block_table"]},
                jnp.minimum(pos + 1, S), layout=layout)
        else:
            k_q, k_s = cache_insert(k_q, k_s, pos, k[:, 0])
            v_q, v_s = cache_insert(v_q, v_s, pos, v[:, 0])
            o = decode_attention_quant(q, k_q, k_s, v_q, v_s, pos + 1,
                                       impl=ctx.decode_impl,
                                       block_k=ctx.decode_block_k)
        x = x + o.reshape(B, 1, cfg.q_dim) @ blk["attn"]["wo"]
        f_out, _ = tf.ffn_apply(cfg, blk["ffn"], x, ctx)
        x = x + f_out
        return x, (k_q, k_s, v_q, v_s)

    h, (kqs, kss, vqs, vss) = jax.lax.scan(
        body, h, (params["blocks"], cache["k_q"], cache["k_s"],
                  cache["v_q"], cache["v_s"]))
    h = layers.apply_norm(cfg, params["final_norm"], h)
    logits = layers.lm_logits(cfg, params, h)
    out = {"k_q": kqs, "k_s": kss, "v_q": vqs, "v_s": vss,
           "len": cache["len"] + 1}
    if paged:
        out["block_table"] = cache["block_table"]
        out["write_table"] = cache["write_table"]
    return logits, out


def quant_decode_spec(cfg, params, cache: Dict, tokens, ctx=None,
                      q_lens=None):
    """Speculative k-row twin of :func:`quant_decode_step` (uniform family,
    dense or paged int8 cache).

    tokens (B, k) -> (logits (B, k, V), accepts (B,), committed cache with
    ``len += accepts``).  The k rows' K/V quantize and land at positions
    ``len + j`` before attention; :func:`decode_attention_quant` (or the
    paged layout dispatch) gives draft row ``j`` effective length
    ``len + 1 + j`` and ``q_lens`` caps live rows.  Rejected rows leave
    int8 garbage at dead positions only (>= the committed length) — the
    same no-rollback argument as the bf16 linear caches."""
    from repro.models import layers
    from repro.models import transformer as tf
    if tf.family(cfg) != "uniform":
        raise NotImplementedError("quant_decode_spec: uniform family only")
    if ctx is None:
        ctx = tf.ModelCtx()
    B, Sq = tokens.shape
    if q_lens is None:
        q_lens = jnp.full((B,), Sq, jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    lens = cache["len"]
    pos = lens[:, None] + jnp.arange(Sq)[None]          # (B, k) absolute
    b_idx = jnp.arange(B)[:, None]
    h = layers.embed_tokens(params["embed"], tokens)
    paged = "block_table" in cache
    if paged:
        from repro.cache_layout import CacheLayout
        from repro.kernels import ops
        bs = cache["k_q"].shape[2]
        nb = cache["block_table"].shape[1]
        S = nb * bs
        blk = jnp.minimum(pos // bs, nb - 1)
        phys = cache["write_table"][b_idx, blk]
        phys = jnp.where(pos < S, phys, 0)    # overflow rows -> null block
        off = pos % bs
        layout = CacheLayout(kind="paged", kv_bits=8, impl=ctx.decode_impl,
                             block_size=bs)
    else:
        S = cache["k_q"].shape[2]

    def body(x, inp):
        blk_p, k_q, k_s, v_q, v_s = inp
        hn = layers.apply_norm(cfg, blk_p["attn"]["norm"], x)
        q, k, v = tf._qkv(cfg, blk_p["attn"], hn, pos, ctx)
        kq_new, ks_new = quantize_kv(k)
        vq_new, vs_new = quantize_kv(v)
        if paged:
            k_q = k_q.at[phys, off].set(kq_new)
            k_s = k_s.at[phys, off].set(ks_new)
            v_q = v_q.at[phys, off].set(vq_new)
            v_s = v_s.at[phys, off].set(vs_new)
            o = ops.decode_attention(
                q, {"k_q": k_q, "k_s": k_s, "v_q": v_q, "v_s": v_s,
                    "block_table": cache["block_table"]},
                jnp.minimum(lens + 1, S), layout=layout, q_lens=q_lens)
        else:
            k_q = k_q.at[b_idx, pos].set(kq_new, mode="drop")
            k_s = k_s.at[b_idx, pos].set(ks_new, mode="drop")
            v_q = v_q.at[b_idx, pos].set(vq_new, mode="drop")
            v_s = v_s.at[b_idx, pos].set(vs_new, mode="drop")
            o = decode_attention_quant(q, k_q, k_s, v_q, v_s, lens + 1,
                                       impl=ctx.decode_impl,
                                       block_k=ctx.decode_block_k,
                                       q_lens=q_lens)
        x = x + o.reshape(B, Sq, cfg.q_dim) @ blk_p["attn"]["wo"]
        f_out, _ = tf.ffn_apply(cfg, blk_p["ffn"], x, ctx)
        x = x + f_out
        return x, (k_q, k_s, v_q, v_s)

    h, (kqs, kss, vqs, vss) = jax.lax.scan(
        body, h, (params["blocks"], cache["k_q"], cache["k_s"],
                  cache["v_q"], cache["v_s"]))
    h = layers.apply_norm(cfg, params["final_norm"], h)
    logits = layers.lm_logits(cfg, params, h)
    accepts = tf.verify_greedy(tokens, logits, q_lens)
    out = {"k_q": kqs, "k_s": kss, "v_q": vqs, "v_s": vss,
           "len": cache["len"] + accepts}
    if paged:
        out["block_table"] = cache["block_table"]
        out["write_table"] = cache["write_table"]
    return logits, accepts, out


def quant_prefill_kv(cfg, params, batch: Dict, ctx=None):
    """Full-sequence prefill forward returning quantized per-layer K/V.

    Returns (logits (B, S, V), (k_q, k_s, v_q, v_s)) with the K/V stacked
    (L, B, S, Hk, D) / scales (L, B, S, Hk), ready to scatter into an
    :func:`init_model_quant_cache` slot."""
    from repro.models import transformer as tf
    if tf.family(cfg) != "uniform":
        raise NotImplementedError("quant prefill: uniform family only")
    if ctx is None:
        ctx = tf.ModelCtx()
    logits, _, kvs = tf.forward(cfg, params, batch, ctx, collect_kv=True)
    k, v = kvs
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    return logits, (k_q, k_s, v_q, v_s)


def decode_attention_quant(q, k_q, k_s, v_q, v_s, lengths,
                           softmax_scale=None, impl="dense", block_k=128,
                           q_lens=None):
    """Decode against an int8 cache.

    q: (B, Sq, H, D); k_q/v_q: (B, S, Hk, D) int8; k_s/v_s: (B, S, Hk).
    Sq > 1 is speculative k-row verification: draft row ``j`` attends with
    effective length ``lengths + j`` and ``q_lens`` (B,) caps live rows.
    The score matmul runs int8 x bf16 -> f32 with the scale folded in
    afterwards (on TPU this is an int8 MXU pass — cache bytes halve AND
    the matmul rate doubles).  ``impl="flash"`` routes through the fused
    Pallas flash-decode kernel (in-kernel tile dequantization, per-slot
    KV-block skipping) so the quantized cache is attended without ever
    materializing a bf16 copy — and without streaming dead positions.
    Empty slots (``len == 0``) produce exactly-zero outputs on both paths.
    """
    if impl == "flash":
        from repro.kernels import ops
        return ops.flash_decode_quant(q, k_q, k_s, v_q, v_s, lengths,
                                      softmax_scale=softmax_scale,
                                      block_k=block_k, q_lens=q_lens)
    if impl != "dense":
        raise ValueError(f"decode impl {impl!r} (want dense|flash)")
    B, Sq, H, D = q.shape
    _, S, Hk, _ = k_q.shape
    G = H // Hk
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if q_lens is None:
        q_lens = jnp.full((B,), Sq, jnp.int32)
    qg = q.reshape(B, Sq, Hk, G, D)
    s = jnp.einsum("bjhgd,bkhd->bhjgk", qg.astype(jnp.float32),
                   k_q.astype(jnp.float32))
    s = s * k_s.transpose(0, 2, 1)[:, :, None, None, :] * scale
    pos_k = jnp.arange(S)[None, None, :]
    eff = (lengths[:, None] + jnp.arange(Sq)[None, :])[:, :, None]
    valid = pos_k < eff
    valid &= (jnp.arange(Sq)[None, :] < q_lens[:, None])[:, :, None]
    s = jnp.where(valid[:, None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, :, None, :], p, 0.0)        # len==0 -> 0
    pv = jnp.einsum("bhjgk,bkhd->bjhgd",
                    (p * v_s.transpose(0, 2, 1)[:, :, None, None, :]),
                    v_q.astype(jnp.float32))
    return pv.reshape(B, Sq, H, D).astype(q.dtype)

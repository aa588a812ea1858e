"""Public jit'd wrappers around the Pallas kernels.

On a CPU backend kernels run in ``interpret=True`` mode so they are
validated end-to-end; on a TPU they compile natively (every kernel here is
compiled for a v5e at real widths by ``tests/test_tpu_compile.py``, and
``chip_smoke.py`` runs the decode kernels on the chip).  ``impl`` can
force ``"ref"`` (pure-jnp oracle) — the default for *lowering* paths where a
clean HLO matters (dry-run roofline) is chosen by the caller.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _decode
from repro.kernels import embedding_ops as _embed
from repro.kernels import fused_adamw as _adamw
from repro.kernels import wkv6 as _wkv6
from repro.kernels import flash_attention as _flash
from repro.kernels import grad_compress as _gc
from repro.kernels import moe_router as _router
from repro.kernels import ref
from repro.kernels import topk_sparsify as _topk


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# -- flash attention ---------------------------------------------------------

@partial(jax.jit, static_argnames=("causal", "window", "softmax_scale",
                                   "block_q", "block_k", "impl"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=0,
                         softmax_scale=None, block_q=128, block_k=128,
                         impl="kernel"):
    """Layout (B, H, S, D)."""
    if impl == "ref":
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   softmax_scale=softmax_scale)
    return _flash.flash_attention(q, k, v, causal=causal, window=window,
                                  softmax_scale=softmax_scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


def flash_attention(q, k, v, *, causal=True, window=0, softmax_scale=None,
                    block_q=128, block_k=128, impl="kernel"):
    """Layout (B, S, H, D) — the model-stack layout."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                             softmax_scale=softmax_scale, block_q=block_q,
                             block_k=block_k, impl=impl)
    return o.transpose(0, 2, 1, 3)


# -- flash-decode attention ---------------------------------------------------

# Optional observability hook: a callable fed one record dict per
# decode_attention *dispatch* with the kernel route and roofline-modeled
# bytes/FLOPs from the argument shapes.  The body of the jitted entry point
# only runs at trace time (the engine calls it from inside jitted model
# code), so this fires per trace/compile — the honest granularity for a
# dispatch-level hook; per-step utilization is stamped on the engine's
# ``decode_step`` spans from the live lengths instead.
_dispatch_recorder = None


def set_dispatch_recorder(fn):
    """Install (or clear, fn=None) the dispatch recorder; returns the
    previous one so callers can restore it."""
    global _dispatch_recorder
    prev = _dispatch_recorder
    _dispatch_recorder = fn
    return prev


def _nbytes(x) -> int:
    return int(x.size) * jnp.dtype(x.dtype).itemsize


def _record_decode_dispatch(q, cache, layout) -> None:
    if _dispatch_recorder is None:
        return
    kv_keys = [k for k in ("k", "v", "k_q", "k_s", "v_q", "v_s")
               if k in cache]
    kv_bytes = sum(_nbytes(cache[k]) for k in kv_keys)
    B, _, H, D = q.shape
    # cache positions per slot: pool blocks * block_size when paged, else
    # the padded row length
    if layout.paged:
        if "k" in cache and cache["k"].ndim == 5:
            kv_bytes //= cache["k"].shape[0]     # one layer of the stack
        S = int(cache["block_table"].shape[1]) * layout.block_size
    else:
        S = int(cache["k" if "k" in cache else "k_q"].shape[-3])
    _dispatch_recorder({
        "op": "decode_attention", "impl": layout.impl,
        "kind": layout.kind, "kv_bits": layout.kv_bits,
        "batch": int(B), "heads": int(H), "head_dim": int(D),
        "s_max": S,
        "kv_resident_bytes": kv_bytes,
        # qk^T + attn@v over the padded span (upper bound; the
        # length-aware kernel streams less — see serving.roofline)
        "modeled_flops": 4.0 * B * H * D * S,
    })


def decode_attention(q, cache, lengths, *, layout, softmax_scale=None,
                     q_lens=None, layer=0):
    """Dispatch-recording wrapper over :func:`_decode_attention_jit` —
    the public entry point every model/backend calls."""
    _record_decode_dispatch(q, cache, layout)
    return _decode_attention_jit(q, cache, lengths, layout=layout,
                                 softmax_scale=softmax_scale, q_lens=q_lens,
                                 layer=layer)


@partial(jax.jit, static_argnames=("layout", "softmax_scale"))
def _decode_attention_jit(q, cache, lengths, *, layout, softmax_scale=None,
                          q_lens=None, layer=0):
    """THE decode-attention entry point, keyed off one
    :class:`repro.cache_layout.CacheLayout` instead of four separate
    wrappers.  ``cache`` is a dict whose keys the layout determines:

    * dense bf16 — ``{"k", "v"}`` with (B, S, Hk, D) per-slot rows;
    * dense int8 — ``{"k_q", "k_s", "v_q", "v_s"}`` (scales (B, S, Hk));
    * paged — the same value keys holding *pool* arrays (N, bs, Hk, D)
      (scales (N, bs, Hk)), plus ``"block_table"`` (B, nb) int32; bf16
      pools may be stacked over layers, (L, N, bs, Hk, D), attended at
      ``layer`` (traced; the flash kernel prefetches it, the ref and
      dense paths index it before their gather).

    ``layout.impl`` selects ref oracle / dense XLA einsum / Pallas flash
    kernel; ``layout.window`` / ``layout.ring`` the masking variant (int8
    supports full-cache masking only, matching the fused kernels).
    ``q_lens`` (B,) carries live draft rows for speculative k-row
    verification (q (B, Sq, H, D)); None keeps the single-step semantics.
    The legacy ``flash_decode`` / ``flash_decode_quant`` wrappers below
    remain as thin shims over the same kernels."""
    if layout.quantized and (layout.window or layout.ring):
        raise ValueError("int8 decode supports full-cache masking only")
    interp = _interpret()
    if layout.paged:
        table = cache["block_table"]
        if layout.quantized:
            args = (cache["k_q"], cache["k_s"], cache["v_q"], cache["v_s"])
            if layout.impl == "ref":
                return ref.decode_attention_paged_quant(
                    q, *args, table, lengths, softmax_scale=softmax_scale,
                    q_lens=q_lens)
            if layout.impl == "dense":
                from repro.models import kvquant
                return kvquant.decode_attention_quant(
                    q, *(ref.paged_gather(a, table) for a in args), lengths,
                    softmax_scale=softmax_scale, impl="dense",
                    q_lens=q_lens)
            return _decode.flash_decode_attention_paged_quant(
                q, *args, table, lengths, softmax_scale=softmax_scale,
                interpret=interp, q_lens=q_lens)
        if layout.impl == "flash":
            return _decode.flash_decode_attention_paged(
                q, cache["k"], cache["v"], table, lengths, layer=layer,
                window=layout.window, ring=layout.ring,
                softmax_scale=softmax_scale, interpret=interp,
                q_lens=q_lens)
        k_pool, v_pool = (ref.pool_layer(cache[n], layer) for n in "kv")
        if layout.impl == "ref":
            return ref.decode_attention_paged(
                q, k_pool, v_pool, table, lengths,
                window=layout.window, ring=layout.ring,
                softmax_scale=softmax_scale, q_lens=q_lens)
        from repro.models import attention
        return attention.decode_attention(
            q, ref.paged_gather(k_pool, table),
            ref.paged_gather(v_pool, table), lengths,
            window=layout.window, ring=layout.ring,
            softmax_scale=softmax_scale, impl="dense", q_lens=q_lens)
    if layout.quantized:
        args = (cache["k_q"], cache["k_s"], cache["v_q"], cache["v_s"])
        if layout.impl == "ref":
            return ref.decode_attention_quant(q, *args, lengths,
                                              softmax_scale=softmax_scale,
                                              q_lens=q_lens)
        if layout.impl == "dense":
            from repro.models import kvquant
            return kvquant.decode_attention_quant(
                q, *args, lengths, softmax_scale=softmax_scale, impl="dense",
                q_lens=q_lens)
        return _decode.flash_decode_attention_quant(
            q, *args, lengths, softmax_scale=softmax_scale,
            block_k=layout.block_k, interpret=interp, q_lens=q_lens)
    if layout.impl == "ref":
        return ref.decode_attention(q, cache["k"], cache["v"], lengths,
                                    window=layout.window, ring=layout.ring,
                                    softmax_scale=softmax_scale,
                                    q_lens=q_lens)
    if layout.impl == "dense":
        from repro.models import attention
        return attention.decode_attention(
            q, cache["k"], cache["v"], lengths, window=layout.window,
            ring=layout.ring, softmax_scale=softmax_scale, impl="dense",
            q_lens=q_lens)
    return _decode.flash_decode_attention(
        q, cache["k"], cache["v"], lengths, window=layout.window,
        ring=layout.ring, softmax_scale=softmax_scale,
        block_k=layout.block_k, interpret=interp, q_lens=q_lens)


@partial(jax.jit, static_argnames=("window", "ring", "softmax_scale",
                                   "block_k", "impl"))
def flash_decode(q, k_cache, v_cache, lengths, *, window=0, ring=False,
                 softmax_scale=None, block_k=128, impl="kernel",
                 q_lens=None):
    """Decode over per-slot live cache prefixes.  q (B, Sq, H, D); caches
    (B, S, Hk, D); lengths (B,); q_lens (B,) live draft rows when Sq > 1
    (speculative verification).  Layouts match the model stack's decode
    caches — no transposes on the hot path."""
    if impl == "ref":
        return ref.decode_attention(q, k_cache, v_cache, lengths,
                                    window=window, ring=ring,
                                    softmax_scale=softmax_scale,
                                    q_lens=q_lens)
    return _decode.flash_decode_attention(
        q, k_cache, v_cache, lengths, window=window, ring=ring,
        softmax_scale=softmax_scale, block_k=block_k,
        interpret=_interpret(), q_lens=q_lens)


@partial(jax.jit, static_argnames=("softmax_scale", "block_k", "impl"))
def flash_decode_quant(q, k_q, k_s, v_q, v_s, lengths, *, softmax_scale=None,
                       block_k=128, impl="kernel", q_lens=None):
    """Int8 fused decode: in-kernel tile dequantization of the quantized
    cache (values (B, S, Hk, D) int8, per-(position, head) f32 scales)."""
    if impl == "ref":
        return ref.decode_attention_quant(q, k_q, k_s, v_q, v_s, lengths,
                                          softmax_scale=softmax_scale,
                                          q_lens=q_lens)
    return _decode.flash_decode_attention_quant(
        q, k_q, k_s, v_q, v_s, lengths, softmax_scale=softmax_scale,
        block_k=block_k, interpret=_interpret(), q_lens=q_lens)


# -- MoE router ---------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "impl"))
def moe_router(logits, k: int, impl="kernel"):
    if impl == "ref":
        return ref.moe_router(logits, k)
    return _router.moe_router(logits, k, interpret=_interpret())


# -- 1-bit compression ---------------------------------------------------------

@partial(jax.jit, static_argnames=("block", "impl"))
def onebit_quantize(g: jnp.ndarray, block: int = 512, impl="kernel"):
    """Flat (N,) f32, N % (8*block) == 0 -> (packed (N/8,) u8, scales)."""
    g2d = g.reshape(8, g.shape[0] // 8)
    if impl == "ref":
        return ref.onebit_quantize(g2d, block)
    return _gc.onebit_quantize(g2d, block, interpret=_interpret())


@partial(jax.jit, static_argnames=("block", "impl"))
def onebit_dequantize(packed, scales, block: int = 512, impl="kernel"):
    if impl == "ref":
        g2d = ref.onebit_dequantize(packed, scales, block)
    else:
        g2d = _gc.onebit_dequantize(packed, scales, block,
                                    interpret=_interpret())
    return g2d.reshape(-1)


# -- top-k sparsification -------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "block", "impl"))
def topk_sparsify(g: jnp.ndarray, k: int, block: int = 2048, impl="kernel"):
    """Flat (N,) f32 -> (kept (N,), residual (N,)); block-local top-k."""
    N = g.shape[0]
    assert N % block == 0, (N, block)
    x2d = g.reshape(N // block, block)
    if impl == "ref":
        kept, resid = ref.topk_sparsify(x2d, k)
    else:
        kept, resid = _topk.topk_sparsify(x2d, k, interpret=_interpret())
    return kept.reshape(N), resid.reshape(N)


# -- embedding gather / scatter-add ---------------------------------------------

@partial(jax.jit, static_argnames=("impl",))
def embedding_gather(table, ids, impl="kernel"):
    """table (V, D), ids (n,) -> (n, D) = table[ids] (fused DMA gather)."""
    if impl == "ref":
        return ref.gather_rows(table, ids)
    return _embed.gather_rows(table, ids, interpret=_interpret())


@partial(jax.jit, static_argnames=("n_rows", "impl"))
def embedding_scatter_add(x, idx, n_rows: int, impl="kernel"):
    """x (n, D), idx (n,) -> (n_rows, D) segment-sum (exact duplicates)."""
    if impl == "ref":
        return ref.scatter_add_rows(x, idx, n_rows)
    return _embed.scatter_add_rows(x, idx, n_rows, interpret=_interpret())


# -- fused AdamW -----------------------------------------------------------------

@partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "impl"))
def adamw_update(p, g, m, v, lr, bc1, bc2, *, b1=0.9, b2=0.95, eps=1e-8,
                 wd=0.1, impl="kernel"):
    if impl == "ref":
        return ref.adamw_update(p, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps,
                                wd=wd, bc1=bc1, bc2=bc2)
    return _adamw.adamw_update(p, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps,
                               wd=wd, bc1=bc1, bc2=bc2,
                               interpret=_interpret())


# -- chunked WKV6 ---------------------------------------------------------------

@partial(jax.jit, static_argnames=("chunk", "impl"))
def wkv6_chunked(r, k, v, w, u, chunk: int = 32, impl="kernel"):
    """r,k,v,w: (B, H, T, hs) -> (B, H, T, hs); zero initial state."""
    if impl == "ref":
        return ref.wkv6_chunked(r, k, v, w, u)
    return _wkv6.wkv6_chunked(r, k, v, w, u, chunk=chunk,
                              interpret=_interpret())

"""Pallas TPU flash-decode attention: the serving engine's per-step hot path.

One decode step attends a single query token per sequence against that
sequence's resident KV cache.  The dense XLA path streams the **entire
padded** cache ``(B, S_max, Hk, D)`` every step; this kernel streams only
the live prefix.  Grid is ``(B, S/block_k)`` with the KV axis innermost
("arbitrary"); the per-slot ``lengths`` vector is **scalar-prefetched** so

* the KV BlockSpec index maps clamp every out-of-range block index onto the
  last live block — consecutive grid steps that map to the same block are
  not re-fetched, so the HBM traffic for a slot is ``ceil(len/block_k)``
  blocks instead of ``S_max/block_k`` (the O(B*S_max) -> O(B*len) claim);
* a ``pl.when`` guard skips the online-softmax update for dead blocks, so
  the clamped (re-visited) block is never double-counted.

A KV tile is ``(1, block_k, Hk, D)`` — every KV head of one block, since
the TPU's tiling takes a head dim only whole — and each grid cell loops
over the heads, reading head ``h``'s ``(block_k, D)`` rows from the tile.
GQA: q is reshaped to ``(B, Hk, G, D)`` and head ``h`` computes its G query
heads against its rows — repeated KV heads are never materialized.
Running max / sum / accumulator live in VMEM scratch, one ``(rows, .)``
plane per KV head, across KV iterations (same online-softmax recurrence as
the prefill flash kernel in :mod:`repro.kernels.flash_attention`).

Three fused variants share the one kernel body:

* **full** (``window=0``) — mask ``pos < len``; blocks past the length are
  skipped.
* **sliding window** (``window>0, ring=False``) — linear cache, band mask
  ``len-window <= pos < len``; blocks are skipped from *both* ends.
* **ring** (``window>0, ring=True``) — gemma's sliding-window ring buffer:
  row ``r`` holds the latest absolute position ``p < len`` with
  ``p % S == r``, so the valid band *wraps*: a row is attendable iff
  ``r < min(len, S)`` and ``(len-1-r) mod S < window``.  With
  ``window == S`` (the layout :func:`repro.models.transformer.init_cache`
  builds) the wrap band covers every written row and the mask reduces to
  the length clamp — but the kernel handles ``window < S`` exactly.

* **int8** (:func:`flash_decode_attention_quant`) — the cache is int8
  values + per-(position, head) f32 scales; tiles are dequantized *inside*
  the kernel (scores fold ``k_s`` after the matmul, ``v_s`` folds into the
  probabilities before the PV matmul), so the quantized path attends
  without ever materializing a bf16 cache.

**Paged** variants (:func:`flash_decode_attention_paged`,
:func:`flash_decode_attention_paged_quant`) read the same kernel body
against a *shared block pool* ``(num_blocks, block_size, Hk, D)`` (the
bf16 one: a layer-stacked ``(L, num_blocks, ...)`` pool, the layer's
offset scalar-prefetched) plus a per-slot block table
``(B, blocks_per_slot)``: the block table is scalar-prefetched alongside
``lengths`` and the KV
BlockSpec index map becomes a table lookup — grid step ``ki`` of slot
``b`` fetches physical block ``tables[b, ki]`` instead of contiguous
row-block ``ki``.  Virtual positions are still ``ki * block_size + iota``,
so the full / window / ring masks and the length-skipping clamp are
identical to the dense-layout kernel; only *where a block's rows live*
changes.  Dead table entries point at the reserved null block 0 and are
never touched (the clamp keeps ``ki`` inside the live range).

**Speculative multi-token verification** generalizes every variant from one
query row to ``Sq = k`` draft rows per slot, folded into the kernel's row
axis: q ``(B, Sq, H, D)`` becomes ``(B, Hk, Sq*G_pad, D)`` so draft row
``j`` of KV head ``h`` occupies kernel rows ``[j*G_pad, (j+1)*G_pad)`` and
one grid cell still computes every row of every KV head against one KV
block.  A second scalar-prefetched vector ``q_lens`` (B,) carries the live
draft length per slot — speculation is ragged under continuous batching —
and the in-kernel masks become per-row: row ``j`` attends with *effective
length* ``lengths + j`` (the committed cache, draft rows ``< j``, and its
own freshly written position — the causal intra-draft mask), while rows
``>= q_lens`` attend nothing and produce exactly-zero outputs.  The live-
block clamp extends to ``lengths + q_lens - 1``, so a slot still fetches
only ``ceil((len+k)/block_k)`` blocks.  With ``Sq == 1`` the row index is
identically zero and every variant reduces bit-for-bit to the single-step
kernel above.

Empty slots (``len == 0``) produce exactly-zero outputs in every variant —
the semantics the pure-jnp oracle in :mod:`repro.kernels.ref` pins and the
dense paths in :mod:`repro.models.attention` / :mod:`repro.models.kvquant`
share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _sublanes(dtype) -> int:
    return 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8


def _live_block_bounds(length, block_k: int, S: int, window: int,
                       ring: bool, q_len=None):
    """(lo, hi) inclusive block-index range holding live KV positions.

    With ``q_len`` draft rows the last live position is row ``q_len-1``'s
    effective length ``length + q_len - 1``; ``q_len=None`` is the
    single-row decode (identical to ``q_len == 1``).  Degenerate slots
    (no attendable position) return (0, 0): block 0 is the one block that
    gets (re-)mapped — fetched at most once — and compute is skipped.
    """
    last = length if q_len is None else length + q_len - 1
    eff = jnp.minimum(last, S) if ring else last
    hi = jnp.maximum(pl.cdiv(eff, block_k) - 1, 0)
    if window > 0 and not ring:
        # row 0's band starts lowest: pos > length - 1 - window
        lo = jnp.clip(length - window, 0, None) // block_k
        lo = jnp.minimum(lo, hi)
    else:
        lo = jnp.zeros_like(hi)
    return lo, hi


def _decode_kernel(lens_ref, qlens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int,
                   ring: bool, block_k: int, n_kv: int, S: int, g_pad: int,
                   quant: bool = False, ks_ref=None, vs_ref=None):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = lens_ref[b]
    q_len = qlens_ref[b]
    n_heads, rows = q_ref.shape[1], q_ref.shape[2]          # rows: Sq*g_pad

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lo, hi = _live_block_bounds(length, block_k, S, window, ring, q_len)
    # single-step (q_len == 1) this is the old ``length > 0`` guard; with
    # drafts, row j > 0 can attend even from an empty cache (eff = j > 0)
    live = (ki >= lo) & (ki <= hi) & (length + q_len > 1)

    @pl.when(live)
    def _compute():
        row_j = jax.lax.broadcasted_iota(                    # draft index
            jnp.int32, (rows, block_k), 0) // g_pad
        pos_k = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        eff = length + row_j                         # causal intra-draft
        if ring and window > 0:
            mask = pos_k < jnp.minimum(eff, S)
            mask &= jnp.mod(eff - 1 - pos_k, S) < window
        else:
            mask = pos_k < eff
            if window > 0:
                mask &= pos_k > eff - 1 - window
        mask &= row_j < q_len                        # ragged draft padding

        # the KV tile holds every head of block ``ki``: one strided read
        # per head, the same online-softmax update per head
        for h in range(n_heads):
            q = q_ref[0, h].astype(jnp.float32)              # (rows, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)        # (block_k, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quant:                                        # fold k scales
                s = s * ks_ref[0, pl.ds(h, 1), :]
            s = jnp.where(mask, s * scale, NEG_INF)          # (rows, bk)

            m_prev = m_scr[h][:, :1]                         # (rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_scr[h][:, :1] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            if quant:                                        # fold v scales
                p = p * vs_ref[0, pl.ds(h, 1), :]
            v = v_ref[0, :, h, :].astype(jnp.float32)        # (block_k, D)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_scr[h] = acc_scr[h] * corr + pv
            m_scr[h] = jnp.broadcast_to(m_new, (rows, LANES))
            l_scr[h] = jnp.broadcast_to(l_new, (rows, LANES))

    @pl.when(ki == n_kv - 1)
    def _done():
        l = jnp.maximum(l_scr[...][:, :, :1], 1e-30)         # dead rows -> 0/1
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _prep_q(q, Hk: int):
    """(B, Sq, H, D) -> padded (B, Hk, Sq*G_pad, D); returns
    (qg, Sq, G, G_pad).  Draft row ``j`` lands on kernel rows
    ``[j*G_pad, (j+1)*G_pad)`` — the row axis folds drafts and query-head
    groups so one head's pass computes every draft row of that KV head."""
    B, Sq, H, D = q.shape
    G = H // Hk
    qg = q.reshape(B, Sq, Hk, G, D).transpose(0, 2, 1, 3, 4)
    sub = _sublanes(q.dtype)
    G_pad = max(sub, ((G + sub - 1) // sub) * sub)
    if G_pad != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, G_pad - G), (0, 0)))
    return qg.reshape(B, Hk, Sq * G_pad, D), Sq, G, G_pad


def _unprep_out(out, B: int, Sq: int, H: int, D: int, G: int, G_pad: int,
                Hk: int):
    """(B, Hk, Sq*G_pad, D) kernel output -> (B, Sq, H, D)."""
    out = out.reshape(B, Hk, Sq, G_pad, D)[:, :, :, :G]
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, D)


def _q_lens_or_full(q_lens, B: int, Sq: int):
    if q_lens is None:
        return jnp.full((B,), Sq, jnp.int32)
    return q_lens.astype(jnp.int32)


def _pad_kv_len(x, block_k: int):
    pad = (-x.shape[1]) % block_k
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x


def _run(kernel, prefetch, qg, kv_args, kv_specs, *, n_kv: int,
         interpret: bool, name: str):
    """One ``pallas_call`` over grid ``(B, n_kv)``: every KV head of one
    slot's block ``ki`` per grid step.  KV tiles are ``(1, bk, Hk, D)`` —
    the head and feature dims whole, as the TPU's (8, 128) tiling
    requires — and q / out / scratch carry all ``Hk`` heads of the slot.

    ``name`` (``flash_decode...``) scopes the call, so the kernel's device
    operation carries it in a profile, whatever jit wraps it."""
    B, Hk, rows, D = qg.shape
    n_pf = len(prefetch)

    def slot_map(b, ki, *_):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pf,
        grid=(B, n_kv),
        in_specs=[pl.BlockSpec((1, Hk, rows, D), slot_map)] + kv_specs,
        out_specs=pl.BlockSpec((1, Hk, rows, D), slot_map),
        scratch_shapes=[
            pltpu.VMEM((Hk, rows, LANES), jnp.float32),
            pltpu.VMEM((Hk, rows, LANES), jnp.float32),
            pltpu.VMEM((Hk, rows, D), jnp.float32),
        ],
    )
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hk, rows, D), qg.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(*prefetch, qg, *kv_args)


def flash_decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                           ring: bool = False, softmax_scale=None,
                           block_k: int = 128, interpret: bool = False,
                           q_lens=None):
    """q (B, Sq, H, D); k/v (B, S, Hk, D); lengths (B,) int32 live prefix
    for row 0; q_lens (B,) int32 live draft rows (None = all Sq rows).

    Returns (B, Sq, H, D) in q.dtype.  ``window``/``ring`` select the
    masking variant; draft row ``j`` attends with effective length
    ``lengths + j`` (see module docstring)."""
    B, Sq, H, D = q.shape
    S, Hk = k_cache.shape[1], k_cache.shape[2]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    block_k = min(block_k, S)
    qg, Sq, G, G_pad = _prep_q(q, Hk)
    k_cache = _pad_kv_len(k_cache, block_k)
    v_cache = _pad_kv_len(v_cache, block_k)
    n_kv = k_cache.shape[1] // block_k

    def kv_map(b, ki, lens, qlens):
        lo, hi = _live_block_bounds(lens[b], block_k, S, window, ring,
                                    qlens[b])
        return (b, jnp.clip(ki, lo, hi), 0, 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, ring=ring,
        block_k=block_k, n_kv=n_kv, S=S, g_pad=G_pad)
    kv_spec = pl.BlockSpec((1, block_k, Hk, D), kv_map)
    out = _run(kernel, (lengths.astype(jnp.int32), _q_lens_or_full(
        q_lens, B, Sq)), qg, (k_cache, v_cache), [kv_spec, kv_spec],
        n_kv=n_kv, interpret=interpret, name="flash_decode")
    return _unprep_out(out, B, Sq, H, D, G, G_pad, Hk)


def flash_decode_attention_quant(q, k_q, k_s, v_q, v_s, lengths, *,
                                 softmax_scale=None, block_k: int = 128,
                                 interpret: bool = False, q_lens=None):
    """Int8 fused variant: k_q/v_q (B, S, Hk, D) int8; k_s/v_s (B, S, Hk)
    f32 per-(position, head) scales; attends the quantized cache directly
    (tile dequantization inside the kernel, full-cache masking only).
    ``q_lens`` enables k-row speculative verification as in
    :func:`flash_decode_attention`."""
    B, Sq, H, D = q.shape
    S, Hk = k_q.shape[1], k_q.shape[2]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    block_k = min(block_k, S)
    qg, Sq, G, G_pad = _prep_q(q, Hk)
    k_q = _pad_kv_len(k_q, block_k)
    v_q = _pad_kv_len(v_q, block_k)
    # scales travel as (B, Hk, S): lane-major along the blocked axis
    k_s = _pad_kv_len(k_s, block_k).transpose(0, 2, 1)
    v_s = _pad_kv_len(v_s, block_k).transpose(0, 2, 1)
    n_kv = k_q.shape[1] // block_k

    def kv_map(b, ki, lens, qlens):
        lo, hi = _live_block_bounds(lens[b], block_k, S, 0, False, qlens[b])
        return (b, jnp.clip(ki, lo, hi), 0, 0)

    def scale_map(b, ki, lens, qlens):
        return (b, 0, kv_map(b, ki, lens, qlens)[1])

    def kernel(lens_ref, qlens_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
               o_ref, m_scr, l_scr, acc_scr):
        _decode_kernel(lens_ref, qlens_ref, q_ref, kq_ref, vq_ref, o_ref,
                       m_scr, l_scr, acc_scr, scale=scale, window=0,
                       ring=False, block_k=block_k, n_kv=n_kv, S=S,
                       g_pad=G_pad, quant=True, ks_ref=ks_ref,
                       vs_ref=vs_ref)

    kv_spec = pl.BlockSpec((1, block_k, Hk, D), kv_map)
    # (1, Hk, bk) scale tiles: Hk is the whole dim; bk a lane multiple or
    # the whole (padded) length
    s_spec = pl.BlockSpec((1, Hk, block_k), scale_map)
    out = _run(kernel, (lengths.astype(jnp.int32), _q_lens_or_full(
        q_lens, B, Sq)), qg, (k_q, k_s, v_q, v_s),
        [kv_spec, s_spec, kv_spec, s_spec], n_kv=n_kv, interpret=interpret,
        name="flash_decode_int8")
    return _unprep_out(out, B, Sq, H, D, G, G_pad, Hk)


def flash_decode_attention_paged(q, k_pool, v_pool, block_tables, lengths, *,
                                 layer=0, window: int = 0, ring: bool = False,
                                 softmax_scale=None,
                                 interpret: bool = False, q_lens=None):
    """Paged flash decode: q (B, Sq, H, D); k/v pools (L, N, bs, Hk, D)
    stacked over layers and shared across slots, attended at layer
    ``layer`` (a scalar, traced or not); block_tables (B, nb) int32
    physical block ids; lengths (B,) live virtual prefix.  A pool without
    the layer axis, (N, bs, Hk, D), is the ``L = 1`` case.

    The kernel reads its blocks straight out of the stacked pool, so a
    caller that keeps every layer's pool in one buffer (the decode step's
    layer scan) never slices a layer out of it: the pool is viewed as
    ``(L*N, bs, Hk, D)`` (merging the two leading dims is a bitcast), the
    layer's first row ``layer * N`` is scalar-prefetched with the tables,
    and the KV index map returns ``layer * N + tables[b, ki]``.  The KV
    tile is one pool block (``block_k == block_size``).  ``q_lens``
    enables k-row speculative verification — the live-block clamp covers
    the draft span, so a draft crossing a block boundary fetches both
    touched blocks."""
    if k_pool.ndim == 4:                     # one layer: L = 1
        k_pool, v_pool = k_pool[None], v_pool[None]
    B, Sq, H, D = q.shape
    L, N, bs, Hk, _ = k_pool.shape
    nb = block_tables.shape[1]
    S = nb * bs                              # virtual position space
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    qg, Sq, G, G_pad = _prep_q(q, Hk)

    def kv_map(b, ki, lens, qlens, tables, base):
        lo, hi = _live_block_bounds(lens[b], bs, S, window, ring, qlens[b])
        return (base[0] + tables[b, jnp.clip(ki, lo, hi)], 0, 0, 0)

    kernel_body = functools.partial(
        _decode_kernel, scale=scale, window=window, ring=ring,
        block_k=bs, n_kv=nb, S=S, g_pad=G_pad)

    def kernel(lens_ref, qlens_ref, tables_ref, base_ref, q_ref, k_ref,
               v_ref, o_ref, m_scr, l_scr, acc_scr):
        kernel_body(lens_ref, qlens_ref, q_ref, k_ref, v_ref, o_ref,
                    m_scr, l_scr, acc_scr)

    kv_spec = pl.BlockSpec((1, bs, Hk, D), kv_map)
    base = (jnp.asarray(layer, jnp.int32) * N).reshape(1)
    out = _run(kernel, (lengths.astype(jnp.int32),
                        _q_lens_or_full(q_lens, B, Sq),
                        block_tables.astype(jnp.int32), base),
               qg, (k_pool.reshape(L * N, bs, Hk, D),
                    v_pool.reshape(L * N, bs, Hk, D)),
               [kv_spec, kv_spec], n_kv=nb, interpret=interpret,
               name="flash_decode_paged")
    return _unprep_out(out, B, Sq, H, D, G, G_pad, Hk)


def flash_decode_attention_paged_quant(q, k_q_pool, k_s_pool, v_q_pool,
                                       v_s_pool, block_tables, lengths, *,
                                       softmax_scale=None,
                                       interpret: bool = False,
                                       q_lens=None):
    """Paged int8 fused variant: value pools (N, bs, Hk, D) int8, scale
    pools (N, bs, Hk) f32; in-kernel tile dequant exactly as the dense-
    layout quant kernel, with the block-table index map of the paged one.
    ``q_lens`` enables k-row speculative verification."""
    B, Sq, H, D = q.shape
    N, bs, Hk, _ = k_q_pool.shape
    nb = block_tables.shape[1]
    S = nb * bs
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    qg, Sq, G, G_pad = _prep_q(q, Hk)
    # scales travel as (N, Hk, bs): a (1, Hk, bs) tile is two whole dims
    k_s_pool = k_s_pool.transpose(0, 2, 1)
    v_s_pool = v_s_pool.transpose(0, 2, 1)

    def kv_map(b, ki, lens, qlens, tables):
        lo, hi = _live_block_bounds(lens[b], bs, S, 0, False, qlens[b])
        return (tables[b, jnp.clip(ki, lo, hi)], 0, 0, 0)

    def scale_map(b, ki, lens, qlens, tables):
        return kv_map(b, ki, lens, qlens, tables)[:3]

    def kernel(lens_ref, qlens_ref, tables_ref, q_ref, kq_ref, ks_ref,
               vq_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr):
        _decode_kernel(lens_ref, qlens_ref, q_ref, kq_ref, vq_ref, o_ref,
                       m_scr, l_scr, acc_scr, scale=scale, window=0,
                       ring=False, block_k=bs, n_kv=nb, S=S, g_pad=G_pad,
                       quant=True, ks_ref=ks_ref, vs_ref=vs_ref)

    kv_spec = pl.BlockSpec((1, bs, Hk, D), kv_map)
    s_spec = pl.BlockSpec((1, Hk, bs), scale_map)
    out = _run(kernel, (lengths.astype(jnp.int32),
                        _q_lens_or_full(q_lens, B, Sq),
                        block_tables.astype(jnp.int32)),
               qg, (k_q_pool, k_s_pool, v_q_pool, v_s_pool),
               [kv_spec, s_spec, kv_spec, s_spec], n_kv=nb,
               interpret=interpret, name="flash_decode_paged_int8")
    return _unprep_out(out, B, Sq, H, D, G, G_pad, Hk)

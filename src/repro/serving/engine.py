"""Fixed-slot continuous-batching serving engine.

The TPU-idiomatic version of vLLM-style batching: the decode batch has a
*static* shape of ``n_slots`` cache rows, each slot holds one request, and
per-slot lengths (``cache["len"]``) track where each row's KV frontier is.
Arriving requests wait in a bounded admission queue; a free slot is filled
by a batched prefill of the prompt scattered into that slot's cache row
(prefill-on-arrival), after which every engine step decodes one token for
all occupied slots.  Finished slots (max-new-tokens reached or early EOS)
are refilled immediately (``refill="continuous"``) or only once the whole
batch drains (``refill="static"`` — the classical static-batching baseline
the benchmark compares against).

Admission is SLO-aware: the bounded queue is a two-level priority queue
(``interactive`` before ``batch``), and at saturation an interactive
arrival sheds the newest batch-tier entry rather than being dropped.
Decoding honors per-request sampling params (``temperature`` / ``top_k``
on :class:`~repro.serving.traffic.Request`): each slot carries a
per-request RNG key folded with the token index, so sampled streams are
reproducible regardless of slot placement or batch composition
(temperature 0 = greedy, the default).

The scheduler is **state-layout agnostic**: it only ever calls a backend's
``init_slots`` / ``prefill`` / ``decode`` and treats the slot state as an
opaque pytree.  Backends come from a *family registry*
(:func:`make_backend` dispatches on ``transformer.family(cfg)``), built on
the family-polymorphic DecodeState protocol in
:mod:`repro.models.transformer` — so every architecture family serves
through the same engine: uniform decoders (stacked KV rows), gemma
(sliding-window ring-buffer rows), jamba (per-period KV + mamba recurrent
rows), rwkv6 (wkv state rows), whisper (self-KV + per-slot cross-KV from
each request's encoder frames).

The cache layout is one explicit spec — :class:`repro.cache_layout
.CacheLayout` on :class:`EngineConfig` — consumed by :func:`make_backend`,
the kernels, and the launch flags alike.  Precision (``kv_bits=8``: fused
int8 attention for uniform via ``models.kvquant``, the generic
:class:`Int8KVSlots` composition elsewhere) and placement (``kind="paged"``:
a shared block pool + per-slot block tables instead of per-slot padded
rows) compose orthogonally.  The layout IS the spec — the pre-layout
``kv=`` / ``decode_impl=`` kwargs were removed after their one-release
deprecation window and now raise ``TypeError``.

The engine is instrumented (see :mod:`repro.obs` and README
"Observability"): hand :class:`ServingEngine` a ``tracer`` and/or
``metrics`` registry and it pins both to its clock.  Live spans on the
``engine`` track time each phase of a tick where the work happens, and
under a profiler session they are host annotations (``repro.<name>``) on
the device trace's clock::

    engine.tick
      sched.refill
        pool.admit         block-table admission + table upload
        model.prefill      the prefill call, dispatch to wait
        cf.lookup          the CF head (cf.user, cf.items, cf.logits_row,
                           cf.fuse_rank; lookups: cf.cache.plan, cf.gather)
        sample.first
      engine.decode
        pool.ensure_writable   copy-on-write
        pool.sync_tables
        decode_step        the decode call, dispatch to wait (``rows``)
        sample.tokens      sampling and the tokens' read to the host
        engine.retire      per-slot commit, finish, release

Per-request phase spans (``req.queue_wait`` / ``req.prefill`` /
``req.decode`` on one track per slot) are retroactive, built from the
*same* :class:`~repro.serving.metrics.RequestRecord` timestamps the
TTFT/TPOT report reads; scheduler instants (``sched.admit`` /
``sched.reject`` / ``sched.shed`` / ``sched.pushback``) and live
block-pool gauges/counters complete the picture.  Two plain counters need
no tracer: ``ticks`` and ``host_syncs`` (each blocking wait of the engine
or its CF head on the device, and each read of a device array to the
host).

Paged serving adds three scheduler-side pieces (see
:mod:`repro.serving.block_pool`): admission maps a request's virtual
blocks onto pooled physical blocks — adopting hash-matched *sealed* prefix
blocks from earlier identical prompts instead of allocating; decode
guarantees every active slot's frontier block is exclusively owned before
the step (**copy-on-write** at the first divergent token of a shared
tail); retirement releases refcounts, returning blocks to the free list.
Pool exhaustion degrades to queueing: a request that cannot map its span
goes back to the head of the admission queue and waits for retirements.
All five families page: attention KV rows move into the pool (uniform and
jamba stacked rows, whisper self-KV, gemma global layers), while per-slot
recurrent and ring state (mamba, wkv, gemma sliding-window rings, whisper
cross-KV) stays slot-resident — it is already live-bounded, which is the
entire point of paging the linearly growing rows.

Time is kept on a :class:`~repro.serving.traffic.Clock`: each model call
advances it by measured wall time (or a pinned per-call cost in tests), and
idle waits jump straight to the next arrival, so simulated Poisson load
plays out faithfully without real sleeping.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache_layout import (CacheLayout, blocks_per_slot,
                                resolved_num_blocks)
from repro.models import kvquant
from repro.models import transformer as tf
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, or_null
from repro.serving import metrics as metrics_lib
from repro.serving.block_pool import BlockPool, SlotTables, prefix_keys
from repro.serving.traffic import Clock, Request


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4
    max_len: int = 128
    queue_capacity: int = 64
    refill: str = "continuous"          # continuous | static
    prompt_quantum: int = 8             # prompts pad to multiples (bounds
                                        # the number of prefill recompiles)
    pad_id: int = 0
    sample_seed: int = 0                # base of the per-request RNG keys
    layout: CacheLayout = CacheLayout()  # cache layout spec (kind/bits/impl)
    prefill_chunk: int = 0              # uniform streaming prefill chunk
    spec_k: int = 1                     # speculative decode: rows verified
                                        # per step (1 = classic one-token)
    spec_draft: str = "ngram"           # self-speculative draft source


def _bucket(n: int, quantum: int, cap: int) -> int:
    return min(cap, ((n + quantum - 1) // quantum) * quantum)


def sample_token(logits_row, temperature: float, top_k: int, key) -> int:
    """One token from a (V,) logits row: greedy when ``temperature <= 0``,
    else softmax(logits/T) restricted to the top-k logits (0 = no cap)."""
    if temperature <= 0.0:
        return int(jnp.argmax(logits_row))
    lg = jnp.asarray(logits_row, jnp.float32)
    if top_k > 0:
        kth = jax.lax.top_k(lg, min(top_k, lg.shape[-1]))[0][-1]
        lg = jnp.where(lg >= kth, lg, -jnp.inf)
    return int(jax.random.categorical(key, lg / temperature))


def sample_tokens(logits, temperatures, top_ks, keys):
    """Batched :func:`sample_token`: one token per (V,) row of ``logits``
    in a single traced computation — per-row temperature / top-k / RNG key,
    greedy rows (``temperature <= 0``) take the argmax.  Bit-identical to
    calling ``sample_token`` row by row (the kth-largest cut value equals
    ``lax.top_k``'s, and vmapping ``categorical`` over keys preserves each
    key's stream)."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    lg = logits.astype(jnp.float32)
    kth = jnp.take_along_axis(
        -jnp.sort(-lg, axis=-1),
        (jnp.clip(top_ks, 1, V) - 1).astype(jnp.int32)[:, None], axis=-1)
    lg = jnp.where((top_ks[:, None] > 0) & (lg < kth), -jnp.inf, lg)
    safe_t = jnp.where(temperatures > 0.0, temperatures, 1.0)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, lg / safe_t)
    return jnp.where(temperatures > 0.0, sampled, greedy)


# module-level jits: every ServingEngine instance (the bench builds dozens)
# shares one compile per (n_slots, V) shape
@jax.jit
def _greedy_tokens(logits):
    return jnp.argmax(logits, axis=-1)


@jax.jit
def _fold_and_sample(logits, temperatures, top_ks, keys, counts):
    keys = jax.vmap(jax.random.fold_in)(keys, counts)
    return sample_tokens(logits, temperatures, top_ks, keys)


def ngram_draft(history, need: int, lookback: int = 64) -> List[int]:
    """Self-speculative n-gram draft (prompt-lookup style): propose up to
    ``need`` continuation tokens by matching the tail of ``history``
    (prompt + generated so far) against its own recent past — bigram match
    first, unigram fallback, empty when nothing recurs.  No second model:
    the k-row verification step prices wrong drafts at zero extra
    cache-read bytes, so even a weak drafter only ever helps.  ``lookback``
    bounds the backward scan so drafting stays O(1) per step."""
    if need <= 0 or len(history) < 2:
        return []

    def match_once(h, want):
        for width in (2, 1):
            if len(h) <= width:
                continue
            pat = h[-width:]
            start = max(0, len(h) - 1 - lookback)
            for i in range(len(h) - 1 - width, start - 1, -1):
                if h[i:i + width] == pat:
                    cont = h[i + width:i + width + want]
                    if cont:
                        return [int(t) for t in cont]
        return []

    # Autoregressive extension: a match near the tail (e.g. a repeated run
    # "... x x x") yields a continuation truncated by the end of history.
    # Re-matching against history + draft-so-far fills the budget, so runs
    # and short cycles draft the full k-1 instead of one token.
    h, out = list(history), []
    while len(out) < need:
        step = match_once(h, need - len(out))
        if not step:
            break
        out.extend(step)
        h.extend(step)
    return out


class AdmissionQueue:
    """Two-level SLO-priority admission queue (interactive > batch).

    FIFO within a tier; ``popleft`` serves the interactive tier first, and
    ``shed_batch`` evicts the *newest* batch-tier entry to make room for an
    interactive arrival when the bounded queue saturates (shedding the
    request that would have waited longest anyway).
    """

    def __init__(self):
        self._tiers: Dict[bool, Deque] = {True: deque(), False: deque()}

    @staticmethod
    def _interactive(req: Request) -> bool:
        return req.slo.name == "interactive"

    def __len__(self) -> int:
        return len(self._tiers[True]) + len(self._tiers[False])

    def append(self, item) -> None:
        self._tiers[self._interactive(item[0])].append(item)

    def popleft(self):
        for tier in (True, False):
            if self._tiers[tier]:
                return self._tiers[tier].popleft()
        raise IndexError("pop from an empty AdmissionQueue")

    def shed_batch(self):
        """Evict and return the newest batch-tier entry (None if none)."""
        return self._tiers[False].pop() if self._tiers[False] else None

    def pushback(self, item) -> None:
        """Return an item to the *head* of its tier — used when paged
        admission fails on pool exhaustion: the request keeps its place in
        line and retries after retirements free blocks."""
        self._tiers[self._interactive(item[0])].appendleft(item)


# Which slot-state entries hold scatterable KV rows, per family (the int8
# composition quantizes exactly these; rwkv6 carries no KV at all).
KV_KEYS: Dict[str, tuple] = {
    "uniform": ("k", "v"),
    "gemma": ("k", "v"),
    "jamba": ("k", "v"),
    "whisper": ("k", "v", "cross_k", "cross_v"),
    "rwkv6": (),
}

# family -> backend class; filled by @register_family below.
FAMILY_BACKENDS: Dict[str, type] = {}


def register_family(*families):
    """Class decorator: register a SlotBackend for the given families."""
    def deco(cls):
        for fam in families:
            FAMILY_BACKENDS[fam] = cls
        cls.families = families
        return cls
    return deco


class SlotBackend:
    """Jit wiring over the family-polymorphic DecodeState protocol.

    Subclasses supply ``init_slots`` (slot-indexed state pytree),
    ``_prefill_impl`` (traced: scatter one request's prompt state into one
    slot row, return that slot's last-position logits), and
    ``_decode_impl`` (traced one-token decode for every slot)."""

    families = None                     # set by @register_family (None: any)
    # speculative decode rows per step.  The engine stamps the resolved
    # value BEFORE init_slots so state that depends on it (gemma local
    # rings, sized window + spec_k - 1 for mid-draft wraparound exactness)
    # is built to match; composition backends forward it to their inner.
    spec_k = 1

    def __init__(self, cfg, params, ctx: Optional[tf.ModelCtx] = None,
                 decode_impl: Optional[str] = None):
        fam = tf.family(cfg)
        if self.families is not None and fam not in self.families:
            raise NotImplementedError(
                f"{type(self).__name__} supports families {self.families}; "
                f"{cfg.name} is {fam}")
        self.cfg, self.params, self.family = cfg, params, fam
        # mrope archs (qwen2-vl) need explicit decode positions: they
        # advance per generated token from the request's text+patch layout
        # rather than equalling the KV frontier
        self.needs_positions = cfg.pos_type == "mrope"
        self.ctx = ctx if ctx is not None else tf.ModelCtx(attn_chunk=8)
        if decode_impl is not None:
            self.ctx = dataclasses.replace(self.ctx, decode_impl=decode_impl)
        # the slot state is consumed and replaced every call: donating it
        # lets XLA update the KV cache in place instead of allocating a
        # fresh multi-MB copy per decode step.  Every backend honours it,
        # so a read of a donated state fails the same way on the CPU
        donate = (1,)
        self._decode = jax.jit(self._decode_impl, donate_argnums=donate)
        # the patch grid is layout (shapes the traced position tensor):
        # static arg, one compile per distinct grid — like prompt buckets
        self._prefill = jax.jit(self._prefill_impl, static_argnames="grid",
                                donate_argnums=donate)
        # the layout this backend realizes (paged backends overwrite it
        # with the full spec; make_backend stamps the resolved one)
        if not hasattr(self, "layout"):
            self.layout = CacheLayout(impl=self.ctx.decode_impl)
        if hasattr(self, "_copy_impl"):
            self._copy = jax.jit(self._copy_impl)
        if hasattr(self, "_decode_spec_impl"):
            self._decode_spec = jax.jit(self._decode_spec_impl,
                                        donate_argnums=donate)
            self._decode_spec_packed = jax.jit(self._packed_spec_impl,
                                               donate_argnums=donate)

    def kv_keys(self) -> tuple:
        return KV_KEYS[self.family]

    def init_slots(self, n_slots: int, max_len: int) -> Dict:
        raise NotImplementedError

    # back-compat alias (PR 1/2 name)
    def init_cache(self, n_slots: int, max_len: int) -> Dict:
        return self.init_slots(n_slots, max_len)

    def prefill(self, cache: Dict, tokens: np.ndarray, true_len: int,
                slot: int, frames=None, grid=None):
        """tokens (1, S_pad) -> (last-position logits (V,), cache).
        ``frames`` (F, d) or (1, F, d): encoder input for enc-dec families
        (zeros when omitted — every slot then shares one silent context).
        ``grid`` (gh, gw): vlm prompts' leading patch-token grid (mrope
        position layout)."""
        if self.cfg.encoder_layers:
            if frames is None:
                frames = np.zeros(
                    (1, self.cfg.encoder_frames, self.cfg.d_model),
                    np.float32)
            frames = jnp.asarray(frames, jnp.dtype(self.cfg.dtype))
            if frames.ndim == 2:
                frames = frames[None]
        else:
            frames = None
        return self._prefill(self.params, cache,
                             jnp.asarray(tokens, jnp.int32),
                             jnp.int32(true_len), jnp.int32(slot), frames,
                             grid=grid)

    def decode(self, cache: Dict, tokens, positions=None):
        """tokens (n_slots, 1) -> (logits (n_slots, 1, V), cache).
        ``positions`` (n_slots, 1, 3): per-slot mrope positions (vlm)."""
        if positions is None:
            return self._decode(self.params, cache, tokens)
        return self._decode(self.params, cache, tokens, positions)

    def decode_spec(self, cache: Dict, tokens, q_lens, positions=None):
        """Speculative k-row step: tokens (n_slots, k) — row 0 the last
        committed token, rows 1.. self-drafted — verified greedily in one
        fused pass.  Returns (logits (n_slots, k, V), accepts (n_slots,),
        committed cache).  ``positions`` (n_slots, k, 3): mrope."""
        if not hasattr(self, "_decode_spec"):
            raise NotImplementedError(
                f"{type(self).__name__} has no speculative decode path")
        if positions is None:
            return self._decode_spec(self.params, cache, tokens, q_lens)
        return self._decode_spec(self.params, cache, tokens, q_lens,
                                 positions)

    def _packed_spec_impl(self, params, cache, packed, positions=None):
        tokens, q_lens = packed[:, :-1], packed[:, -1]
        if positions is None:
            return self._decode_spec_impl(params, cache, tokens, q_lens)
        return self._decode_spec_impl(params, cache, tokens, q_lens,
                                      positions)

    def decode_spec_packed(self, cache: Dict, packed, positions=None):
        """:meth:`decode_spec` minus one host->device put: ``packed``
        (n_slots, k + 1) int32 carries the draft rows with ``q_lens`` in
        the last column, uploaded as a single array and split inside the
        jitted step.  On CPU-sized models the second upload is a
        measurable share of a decode step, so the engine hot loop prefers
        this entry point."""
        if not hasattr(self, "_decode_spec_packed"):
            raise NotImplementedError(
                f"{type(self).__name__} has no speculative decode path")
        packed = jnp.asarray(packed, jnp.int32)
        if positions is None:
            return self._decode_spec_packed(self.params, cache, packed)
        return self._decode_spec_packed(self.params, cache, packed,
                                        positions)


@register_family("uniform", "gemma", "jamba", "rwkv6", "whisper")
class NativeBackend(SlotBackend):
    """Model-dtype slot state via the transformer DecodeState protocol
    (``init_slots`` / ``prefill_into_slot`` / ``decode_step``).

    ``prefill_chunk > 0`` streams uniform-family prompts through the
    decode cache-append path in fixed chunks instead of one monolithic
    padded forward (see :func:`transformer.prefill_into_slot`)."""

    def __init__(self, cfg, params, ctx: Optional[tf.ModelCtx] = None,
                 decode_impl: Optional[str] = None, prefill_chunk: int = 0):
        self.prefill_chunk = int(prefill_chunk)
        super().__init__(cfg, params, ctx, decode_impl)

    def init_slots(self, n_slots: int, max_len: int) -> Dict:
        return tf.init_slots(self.cfg, n_slots, max_len,
                             spec_margin=self.spec_k - 1)

    def _decode_impl(self, params, cache, tokens, positions=None):
        return tf.decode_step(self.cfg, params, cache, tokens, self.ctx,
                              positions=positions)

    def _decode_spec_impl(self, params, cache, tokens, q_lens,
                          positions=None):
        return tf.decode_spec(self.cfg, params, cache, tokens, self.ctx,
                              q_lens=q_lens, positions=positions)

    def _prefill_impl(self, params, cache, tokens, true_len, slot,
                      frames=None, grid=None):
        return tf.prefill_into_slot(self.cfg, params, cache, tokens,
                                    true_len, slot, self.ctx, frames=frames,
                                    grid=grid, chunk=self.prefill_chunk)


class Int8KVBackend(SlotBackend):
    """Fused int8-KV path for the uniform family (kvquant): the cache is
    int8 values + per-(position, head) scales and the decode score matmul
    runs against the int8 values directly — half the cache bytes per slot
    AND no dequantized copy is ever materialized."""

    families = ("uniform",)

    def __init__(self, cfg, params, ctx: Optional[tf.ModelCtx] = None,
                 decode_impl: Optional[str] = None):
        super().__init__(cfg, params, ctx, decode_impl)
        self.layout = self.layout.replace(kv_bits=8)

    def init_slots(self, n_slots: int, max_len: int) -> Dict:
        return kvquant.init_model_quant_cache(self.cfg, n_slots, max_len)

    def _decode_impl(self, params, cache, tokens, positions=None):
        if positions is not None:
            raise NotImplementedError(
                "fused int8 decode has no mrope positions path; "
                "make_backend routes mrope archs through Int8KVSlots")
        return kvquant.quant_decode_step(self.cfg, params, cache, tokens,
                                         self.ctx)

    def _decode_spec_impl(self, params, cache, tokens, q_lens,
                          positions=None):
        if positions is not None:
            raise NotImplementedError(
                "fused int8 decode has no mrope positions path; "
                "make_backend routes mrope archs through Int8KVSlots")
        return kvquant.quant_decode_spec(self.cfg, params, cache, tokens,
                                         self.ctx, q_lens=q_lens)

    def _prefill_impl(self, params, cache, tokens, true_len, slot,
                      frames=None, grid=None):
        logits, (k_q, k_s, v_q, v_s) = kvquant.quant_prefill_kv(
            self.cfg, params, {"tokens": tokens}, self.ctx)
        cache = dict(cache)
        for name, upd in (("k_q", k_q), ("k_s", k_s),
                          ("v_q", v_q), ("v_s", v_s)):
            start = (0, slot) + (0,) * (upd.ndim - 2)
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], upd.astype(cache[name].dtype), start)
        cache["len"] = cache["len"].at[slot].set(true_len)
        return logits[0, true_len - 1], cache


class Int8KVSlots(SlotBackend):
    """Generic int8-KV composition over any KV-bearing family backend.

    The inner family's slot state keeps its layout, but every KV entry
    (``KV_KEYS`` — stacked rows, gemma ring buffers, whisper cross-KV) is
    *stored* as int8 values + per-(position, head) f32 scales; recurrent
    states (mamba rows, wkv) stay full precision (they are O(1) per slot).
    Each step dequantizes for the family's native decode and requantizes
    the updated state.  Requantizing untouched rows is exact (see
    :func:`repro.models.kvquant.quantize_kv_tree`), so only the newly
    written position actually changes — repeated steps do not drift.  On
    a real accelerator the dequantized working copy is a per-step
    activation; the *resident* per-slot state is the halved int8 form that
    the decode roofline's memory term prices."""

    def __init__(self, inner: SlotBackend):
        self.inner = inner
        super().__init__(inner.cfg, inner.params, inner.ctx)
        self.layout = self.layout.replace(kv_bits=8)

    def kv_keys(self) -> tuple:
        return self.inner.kv_keys()

    def _quant(self, cache: Dict) -> Dict:
        keys = self.inner.kv_keys()
        q, s = kvquant.quantize_kv_tree({k: cache[k] for k in keys})
        rest = {k: v for k, v in cache.items() if k not in keys}
        return {"kv_q": q, "kv_s": s, "rest": rest}

    def _dequant(self, qcache: Dict) -> Dict:
        kv = kvquant.dequantize_kv_tree(qcache["kv_q"], qcache["kv_s"],
                                        jnp.dtype(self.cfg.dtype))
        return {**qcache["rest"], **kv}

    def init_slots(self, n_slots: int, max_len: int) -> Dict:
        self.inner.spec_k = self.spec_k     # sizes gemma rings in the inner
        return self._quant(self.inner.init_slots(n_slots, max_len))

    def _decode_impl(self, params, qcache, tokens, positions=None):
        logits, cache = self.inner._decode_impl(params,
                                                self._dequant(qcache),
                                                tokens, positions)
        return logits, self._quant(cache)

    def _decode_spec_impl(self, params, qcache, tokens, q_lens,
                          positions=None):
        # requantizing untouched rows is exact (the max element pins the
        # scale), so dequant -> inner k-row verify -> requant preserves
        # the inner path's token-exactness guarantee
        logits, accepts, cache = self.inner._decode_spec_impl(
            params, self._dequant(qcache), tokens, q_lens, positions)
        return logits, accepts, self._quant(cache)

    def _prefill_impl(self, params, qcache, tokens, true_len, slot,
                      frames=None, grid=None):
        logits, cache = self.inner._prefill_impl(
            params, self._dequant(qcache), tokens, true_len, slot, frames,
            grid=grid)
        return logits, self._quant(cache)


_TABLE_KEYS = ("block_table", "write_table")


class _PagedBackendMixin:
    """Shared device-side plumbing of the paged backends.

    ``supports_prefix_sharing`` marks backends whose prompt block content
    is a pure function of (prompt, engine constants) — the precondition
    for the hash index being sound.  ``set_tables`` uploads the host
    read/write tables; ``copy_block`` is the device half of copy-on-write
    (duplicate one physical block's rows across every pooled leaf).

    The ``gather_block_values`` / ``scatter_block_values`` /
    ``export_slot_state`` / ``import_slot_state`` quartet is the device
    half of prefill→decode handoff: snapshot the pooled rows of an
    exported block chain (plus the slot's non-pooled per-slot state) out
    of one engine's cache, and land them in another engine's cache at
    freshly mapped physical blocks.  Pure data movement — bit-exact — so
    a handed-off request decodes token-identically to one that never
    moved.  ``_pool_leaves`` names the pooled leaf arrays (block axis 1)
    for the fused uniform-family backends; :class:`PagedSlots` overrides
    the quartet to walk its generic leaf specs instead."""

    supports_prefix_sharing = True
    _pool_leaves: tuple = ()

    def set_tables(self, cache: Dict, read: np.ndarray,
                   write: np.ndarray) -> Dict:
        cache = dict(cache)
        cache["block_table"] = jnp.asarray(read, jnp.int32)
        cache["write_table"] = jnp.asarray(write, jnp.int32)
        return cache

    def copy_block(self, cache: Dict, src: int, dst: int) -> Dict:
        return self._copy(cache, jnp.int32(src), jnp.int32(dst))

    def gather_block_values(self, cache: Dict,
                            blocks: Sequence[int]) -> Dict:
        """Snapshot the pooled rows of ``blocks`` (physical ids, in
        virtual order) — the payload of a cross-pool handoff."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        return {n: cache[n][:, idx] for n in self._pool_leaves}

    def scatter_block_values(self, cache: Dict, blocks: Sequence[int],
                             values: Dict,
                             rows: Optional[Sequence[int]] = None) -> Dict:
        """Write a gathered snapshot into ``blocks`` of this cache;
        ``rows`` selects which rows of the snapshot to use (virtual block
        indices the import actually copied — dedupe-adopted blocks are
        skipped)."""
        cache = dict(cache)
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        sel = (None if rows is None
               else jnp.asarray(np.asarray(rows, np.int32)))
        for n in self._pool_leaves:
            v = values[n]
            if sel is not None:
                v = v[:, sel]
            cache[n] = cache[n].at[:, idx].set(v.astype(cache[n].dtype))
        return cache

    def export_slot_state(self, cache: Dict, slot: int) -> Dict:
        """Non-pooled per-slot state riding along with a handoff (for the
        fused uniform backends that's just the KV frontier length)."""
        return {"len": cache["len"][slot]}

    def import_slot_state(self, cache: Dict, slot: int,
                          state: Dict) -> Dict:
        cache = dict(cache)
        cache["len"] = cache["len"].at[slot].set(state["len"])
        return cache


class PagedNativeBackend(_PagedBackendMixin, SlotBackend):
    """Native paged path for the uniform family: stacked per-layer KV in a
    shared pool ``(L, N, bs, Hk, D)``; decode appends through the write
    table and attends through the read table with the paged flash-decode
    kernel (or its dense-gather twin) — see
    :func:`transformer.init_paged_slots` / :func:`attn_decode_paged`."""

    families = ("uniform",)
    _pool_leaves = ("k", "v")

    def __init__(self, cfg, params, ctx: Optional[tf.ModelCtx] = None,
                 layout: CacheLayout = CacheLayout(kind="paged")):
        self.layout = layout
        super().__init__(cfg, params, ctx, layout.impl)

    def init_slots(self, n_slots: int, max_len: int) -> Dict:
        return tf.init_paged_slots(
            self.cfg, n_slots, max_len,
            num_blocks=resolved_num_blocks(self.layout, n_slots, max_len),
            block_size=self.layout.block_size)

    def _decode_impl(self, params, cache, tokens, positions=None):
        return tf.decode_step(self.cfg, params, cache, tokens, self.ctx,
                              positions=positions)

    def _decode_spec_impl(self, params, cache, tokens, q_lens,
                          positions=None):
        return tf.decode_spec(self.cfg, params, cache, tokens, self.ctx,
                              q_lens=q_lens, positions=positions)

    def _prefill_impl(self, params, cache, tokens, true_len, slot,
                      frames=None, grid=None):
        return tf.prefill_into_slot(self.cfg, params, cache, tokens,
                                    true_len, slot, self.ctx, frames=frames,
                                    grid=grid)

    def _copy_impl(self, cache, src, dst):
        cache = dict(cache)
        for name in ("k", "v"):
            cache[name] = cache[name].at[:, dst].set(cache[name][:, src])
        return cache


class PagedInt8Backend(_PagedBackendMixin, SlotBackend):
    """Fused paged int8 path (uniform family): pooled int8 values + pooled
    per-(position, head) scales, in-kernel tile dequantization through the
    block-table index map (``models.kvquant`` paged twins)."""

    families = ("uniform",)
    _pool_leaves = ("k_q", "k_s", "v_q", "v_s")

    def __init__(self, cfg, params, ctx: Optional[tf.ModelCtx] = None,
                 layout: CacheLayout = CacheLayout(kind="paged", kv_bits=8)):
        self.layout = layout
        super().__init__(cfg, params, ctx, layout.impl)

    def init_slots(self, n_slots: int, max_len: int) -> Dict:
        return kvquant.init_paged_quant_cache(
            self.cfg, n_slots, max_len,
            num_blocks=resolved_num_blocks(self.layout, n_slots, max_len),
            block_size=self.layout.block_size)

    def _decode_impl(self, params, cache, tokens, positions=None):
        if positions is not None:
            raise NotImplementedError(
                "fused int8 decode has no mrope positions path; "
                "make_backend routes mrope archs through the composition")
        return kvquant.quant_decode_step(self.cfg, params, cache, tokens,
                                         self.ctx)

    def _decode_spec_impl(self, params, cache, tokens, q_lens,
                          positions=None):
        if positions is not None:
            raise NotImplementedError(
                "fused int8 decode has no mrope positions path; "
                "make_backend routes mrope archs through the composition")
        return kvquant.quant_decode_spec(self.cfg, params, cache, tokens,
                                         self.ctx, q_lens=q_lens)

    def _prefill_impl(self, params, cache, tokens, true_len, slot,
                      frames=None, grid=None):
        logits, (k_q, k_s, v_q, v_s) = kvquant.quant_prefill_kv(
            self.cfg, params, {"tokens": tokens}, self.ctx)
        bs = self.layout.block_size
        S_p = tokens.shape[1]
        pad = (-S_p) % bs
        nbp = (S_p + pad) // bs
        wt = cache["write_table"][slot][:nbp]
        cache = dict(cache)
        for name, upd in (("k_q", k_q), ("k_s", k_s),
                          ("v_q", v_q), ("v_s", v_s)):
            if pad:
                upd = jnp.pad(upd, ((0, 0), (0, 0), (0, pad))
                              + ((0, 0),) * (upd.ndim - 3))
            vals = upd[:, 0].reshape((upd.shape[0], nbp, bs)
                                     + upd.shape[3:])
            cache[name] = cache[name].at[:, wt].set(
                vals.astype(cache[name].dtype))
        cache["len"] = cache["len"].at[slot].set(true_len)
        return logits[0, true_len - 1], cache

    def _copy_impl(self, cache, src, dst):
        cache = dict(cache)
        for name in ("k_q", "k_s", "v_q", "v_s"):
            cache[name] = cache[name].at[:, dst].set(cache[name][:, src])
        return cache


class PagedSlots(_PagedBackendMixin, SlotBackend):
    """Generic paged composition over ANY family backend — how gemma,
    jamba, rwkv6, whisper (and compositions like int8-over-native) page
    without family-specific pool code.

    At ``init_slots`` the inner backend's dense slot state is used as a
    *template*: every array leaf under a self-attention KV key ("k"/"v",
    including gemma's per-layer tuple elements and the int8 composition's
    ``kv_q``/``kv_s`` subtrees) whose per-slot length dimension equals
    ``max_len`` is replaced by a shared pool ``(..., N, bs, ...)``.
    Everything else — mamba conv/ssm rows, wkv state, gemma sliding-window
    rings shorter than the serving window, whisper cross-KV — stays
    slot-resident: that state is already live-bounded (O(1) or
    O(window)), so paging it would add indirection without reclaiming
    memory.  rwkv6 pages zero leaves and degenerates to the identity
    composition (block tables exist but no pool), which keeps the five
    families behind one code path.

    Each traced step *gathers* pooled leaves into the inner backend's
    dense layout through the read table, runs the inner family step
    unchanged, and *scatters* updated rows back through the write table
    (rows of shared or unmapped blocks land in the null block 0).  The
    gather/scatter round trip is pure data movement — bit-exact — so
    paged serving is token-exact against the dense backend by
    construction; for the int8 composition, exact requantization of
    untouched rows (:func:`kvquant.quantize_kv_tree`) preserves the same
    guarantee.  On an accelerator the gathered working set is a per-step
    activation; the *resident* state is the pool, which is what the
    admission model prices."""

    def __init__(self, inner: SlotBackend, layout: CacheLayout):
        self.inner = inner
        self.layout = layout
        self._specs = None
        self._state_axes = None
        super().__init__(inner.cfg, inner.params, inner.ctx)

    def kv_keys(self) -> tuple:
        return self.inner.kv_keys()

    def init_slots(self, n_slots: int, max_len: int) -> Dict:
        # forward spec_k before building the template: margined gemma
        # rings (window + spec_k - 1 != max_len) stay slot-resident
        self.inner.spec_k = self.spec_k
        template = self.inner.init_slots(n_slots, max_len)
        bs = self.layout.block_size
        nb = blocks_per_slot(self.layout, max_len)
        num_blocks = resolved_num_blocks(self.layout, n_slots, max_len)
        paths, leaves = zip(*jax.tree_util.tree_flatten_with_path(
            template)[0])
        # slot axis of each slot-resident leaf (handoff transfers that
        # row): probe a phantom (n_slots + 1)-slot template through
        # eval_shape — zero allocation — and take the axis whose size
        # moved.  Exact for every family layout (mamba rows keep the
        # slot on axis 2), unlike any shape-matching heuristic.
        probe = jax.eval_shape(
            lambda: self.inner.init_slots(n_slots + 1, max_len))
        probe_leaves = jax.tree_util.tree_leaves(probe)
        specs, pooled, state_axes = [], [], []
        for path, leaf, pleaf in zip(paths, leaves, probe_leaves):
            ax = self._slot_axis(path, leaf, n_slots, max_len)
            specs.append(ax)
            if ax is None:
                pooled.append(leaf)
                diff = [i for i, (a, b) in enumerate(
                    zip(leaf.shape, pleaf.shape)) if a != b]
                state_axes.append(diff[0] if diff else None)
            else:
                shape = list(leaf.shape)
                shape[ax], shape[ax + 1] = num_blocks, bs
                pooled.append(jnp.zeros(tuple(shape), leaf.dtype))
                state_axes.append(None)
        self._specs = tuple(specs)
        self._state_axes = tuple(state_axes)
        state = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), pooled)
        state = dict(state)
        # distinct buffers: the donated decode step must not alias them
        state["block_table"] = jnp.zeros((n_slots, nb), jnp.int32)
        state["write_table"] = jnp.zeros((n_slots, nb), jnp.int32)
        return state

    @staticmethod
    def _slot_axis(path, leaf, n_slots: int, max_len: int):
        """Slot axis of a pageable leaf, or None.  Pageable = an array
        under a "k"/"v" path key (self-attention KV; excludes cross_k/v,
        mamba, wkv) whose length dim is exactly ``max_len`` — linear
        append-at-``len`` semantics.  Shorter ring buffers stay resident.
        Slot axis is 0 for per-layer tuple elements (n, S, ...) and 1 for
        stacked (L, n, S, ...) entries."""
        keyed = any(getattr(p, "key", None) in ("k", "v") for p in path)
        if not keyed or not hasattr(leaf, "ndim"):
            return None
        if leaf.ndim >= 2 and leaf.shape[0] == n_slots \
                and leaf.shape[1] == max_len:
            return 0
        if leaf.ndim >= 3 and leaf.shape[1] == n_slots \
                and leaf.shape[2] == max_len:
            return 1
        return None

    def _split(self, cache: Dict):
        inner = {k: v for k, v in cache.items() if k not in _TABLE_KEYS}
        flat, treedef = jax.tree_util.tree_flatten(inner)
        return flat, treedef

    def _gather(self, cache: Dict) -> Dict:
        """Pooled state -> the inner backend's dense slot layout."""
        rt = cache["block_table"]
        n, nb = rt.shape
        bs = self.layout.block_size
        flat, treedef = self._split(cache)
        idx = rt.reshape(-1)
        out = []
        for leaf, ax in zip(flat, self._specs):
            if ax is None:
                out.append(leaf)
            elif ax == 0:
                g = leaf[idx].reshape((n, nb * bs) + leaf.shape[2:])
                out.append(g)
            else:
                g = leaf[:, idx].reshape(
                    (leaf.shape[0], n, nb * bs) + leaf.shape[3:])
                out.append(g)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _repool(self, cache: Dict, dense: Dict) -> Dict:
        """Scatter an updated dense state back into the pools (write
        table: shared/unmapped rows -> null block), keep non-paged leaves
        from the inner result, carry the tables through."""
        wt = cache["write_table"]
        n, nb = wt.shape
        bs = self.layout.block_size
        pooled_flat, _ = self._split(cache)
        dense_flat, treedef = jax.tree_util.tree_flatten(
            {k: v for k, v in dense.items() if k not in _TABLE_KEYS})
        idx = wt.reshape(-1)
        out = []
        for pool, leaf, ax in zip(pooled_flat, dense_flat, self._specs):
            if ax is None:
                out.append(leaf)
            elif ax == 0:
                vals = leaf.reshape((n * nb, bs) + leaf.shape[2:])
                out.append(pool.at[idx].set(vals.astype(pool.dtype)))
            else:
                vals = leaf.reshape((leaf.shape[0], n * nb, bs)
                                    + leaf.shape[3:])
                out.append(pool.at[:, idx].set(vals.astype(pool.dtype)))
        state = dict(jax.tree_util.tree_unflatten(treedef, out))
        state["block_table"] = cache["block_table"]
        state["write_table"] = cache["write_table"]
        return state

    def _decode_impl(self, params, cache, tokens, positions=None):
        logits, dense = self.inner._decode_impl(params, self._gather(cache),
                                                tokens, positions)
        return logits, self._repool(cache, dense)

    def _decode_spec_impl(self, params, cache, tokens, q_lens,
                          positions=None):
        # gather -> inner k-row verify -> repool is pure data movement:
        # rejected rows land as garbage at dead positions of exclusively
        # owned blocks (the engine COWs the whole span first)
        logits, accepts, dense = self.inner._decode_spec_impl(
            params, self._gather(cache), tokens, q_lens, positions)
        return logits, accepts, self._repool(cache, dense)

    def _prefill_impl(self, params, cache, tokens, true_len, slot,
                      frames=None, grid=None):
        logits, dense = self.inner._prefill_impl(
            params, self._gather(cache), tokens, true_len, slot, frames,
            grid=grid)
        return logits, self._repool(cache, dense)

    def _copy_impl(self, cache, src, dst):
        flat, treedef = self._split(cache)
        out = []
        for leaf, ax in zip(flat, self._specs):
            if ax is None:
                out.append(leaf)
            elif ax == 0:
                out.append(leaf.at[dst].set(leaf[src]))
            else:
                out.append(leaf.at[:, dst].set(leaf[:, src]))
        state = dict(jax.tree_util.tree_unflatten(treedef, out))
        state["block_table"] = cache["block_table"]
        state["write_table"] = cache["write_table"]
        return state

    # -- handoff (block-value + slot-state transfer) -----------------------

    def gather_block_values(self, cache: Dict,
                            blocks: Sequence[int]) -> Dict:
        """Pooled-leaf rows of ``blocks``, keyed by flat leaf index.
        rwkv6 pages zero leaves and returns {} — its whole live state
        rides :meth:`export_slot_state` instead."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        flat, _ = self._split(cache)
        vals = {}
        for j, (leaf, ax) in enumerate(zip(flat, self._specs)):
            if ax is None:
                continue
            vals[j] = leaf[idx] if ax == 0 else leaf[:, idx]
        return vals

    def scatter_block_values(self, cache: Dict, blocks: Sequence[int],
                             values: Dict,
                             rows: Optional[Sequence[int]] = None) -> Dict:
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        sel = (None if rows is None
               else jnp.asarray(np.asarray(rows, np.int32)))
        flat, treedef = self._split(cache)
        out = list(flat)
        for j, v in values.items():
            ax = self._specs[j]
            if sel is not None:
                v = v[sel] if ax == 0 else v[:, sel]
            if ax == 0:
                out[j] = flat[j].at[idx].set(v.astype(flat[j].dtype))
            else:
                out[j] = flat[j].at[:, idx].set(v.astype(flat[j].dtype))
        state = dict(jax.tree_util.tree_unflatten(treedef, out))
        state["block_table"] = cache["block_table"]
        state["write_table"] = cache["write_table"]
        return state

    def export_slot_state(self, cache: Dict, slot: int) -> Dict:
        """Every slot-resident (non-pooled) leaf's row for ``slot``: the
        KV frontier length plus whatever the family keeps outside the
        pool — mamba conv/ssm rows, wkv state, gemma short rings, whisper
        cross-KV."""
        flat, _ = self._split(cache)
        st = {}
        for j, (leaf, ax, sax) in enumerate(
                zip(flat, self._specs, self._state_axes)):
            if ax is not None or sax is None:
                continue
            st[j] = leaf[(slice(None),) * sax + (slot,)]
        return st

    def import_slot_state(self, cache: Dict, slot: int,
                          state: Dict) -> Dict:
        flat, treedef = self._split(cache)
        out = list(flat)
        for j, v in state.items():
            sel = (slice(None),) * self._state_axes[j] + (slot,)
            out[j] = flat[j].at[sel].set(v.astype(flat[j].dtype))
        st = dict(jax.tree_util.tree_unflatten(treedef, out))
        st["block_table"] = cache["block_table"]
        st["write_table"] = cache["write_table"]
        return st


def make_backend(cfg, params, ctx: Optional[tf.ModelCtx] = None,
                 prefill_chunk: int = 0, *,
                 layout: Optional[CacheLayout] = None):
    """Family-registry dispatch keyed off one :class:`CacheLayout`.

    The layout picks the whole backend matrix: dense/bf16 ->
    :class:`NativeBackend`; dense/int8 -> fused :class:`Int8KVBackend`
    (uniform, whole-prompt prefill) or the :class:`Int8KVSlots`
    composition; paged/bf16 -> native :class:`PagedNativeBackend`
    (uniform) or the generic :class:`PagedSlots` composition; paged/int8
    -> fused :class:`PagedInt8Backend` (uniform) or
    ``PagedSlots(Int8KVSlots(native))``.  ``layout.impl`` overrides the
    decode-attention hot path on the backend's ModelCtx when it differs
    from the default.  ``prefill_chunk > 0`` enables streaming prefill for
    uniform-family prompts (which forces composition backends — the fused
    paths need the whole-prompt forward).

    The pre-layout ``kv=`` / ``decode_impl=`` kwargs were removed (PR-6
    deprecation window closed); passing them raises ``TypeError`` — use
    ``layout=CacheLayout(kv_bits=8, impl="flash")``."""
    explicit = layout is not None
    if layout is None:
        layout = CacheLayout()
    fam = tf.family(cfg)
    if fam not in FAMILY_BACKENDS:
        raise NotImplementedError(
            f"no serving backend registered for family {fam!r} "
            f"(have {sorted(FAMILY_BACKENDS)})")
    if layout.quantized and not KV_KEYS[fam]:
        raise ValueError(
            f"family {fam!r} carries no KV cache; int8 KV does not "
            f"apply (its recurrent state is O(1) per slot already)")
    # only override a caller-supplied ModelCtx's decode impl when the
    # layout (or legacy kwarg) explicitly asked for one
    impl = layout.impl if explicit else None
    if not layout.paged:
        if not layout.quantized:
            return FAMILY_BACKENDS[fam](cfg, params, ctx, impl,
                                        prefill_chunk)
        if fam == "uniform" and cfg.pos_type != "mrope" and not prefill_chunk:
            # fused int8 path (whole-prompt quantized prefill).  mrope
            # archs need explicit decode positions and chunked prefill
            # needs the native cache-append path: both take the generic
            # composition below
            backend = Int8KVBackend(cfg, params, ctx, impl)
        else:
            backend = Int8KVSlots(FAMILY_BACKENDS[fam](
                cfg, params, ctx, impl, prefill_chunk))
        backend.layout = layout.replace(kv_bits=8)
        return backend
    if fam == "uniform" and not prefill_chunk:
        if layout.quantized:
            if cfg.pos_type != "mrope":
                return PagedInt8Backend(cfg, params, ctx, layout)
        else:
            return PagedNativeBackend(cfg, params, ctx, layout)
    if layout.quantized:
        inner = Int8KVSlots(FAMILY_BACKENDS[fam](cfg, params, ctx, impl,
                                                 prefill_chunk))
    else:
        inner = FAMILY_BACKENDS[fam](cfg, params, ctx, impl, prefill_chunk)
    return PagedSlots(inner, layout)


@dataclasses.dataclass
class Handoff:
    """A prefilled request in flight from a prefill-tier engine to a
    decode-tier engine.

    Self-contained: the exported block chain (physical ids valid in the
    *source* pool, sealed content keys for dedupe), the gathered pooled
    block values (device snapshots — immutable, so the source slot can be
    released immediately), the non-pooled slot state, and the scheduler
    fields the decode engine needs to continue the stream exactly where
    prefill left it (last emitted token, remaining budget, sampling key,
    mrope position).  ``ready_at`` models the transfer latency
    (``Clock.fixed_handoff_s``); the record and output list are shared
    objects, so TTFT/TPOT and the token stream accumulate across tiers
    without any merge step."""

    req: Request
    rec: metrics_lib.RequestRecord
    last_token: int
    budget: int                     # generation budget incl. the first token
    key: np.ndarray                 # per-request sampling PRNG key
    live_tokens: int                # KV rows filled (= prompt length)
    blocks: List[int]               # exported chain (source-pool physical)
    keys: List[Optional[int]]       # sealed content key per block (or None)
    values: Dict                    # gathered pooled rows of blocks[:n_live]
    slot_state: Dict                # non-pooled per-slot rows
    src_pool: Optional[BlockPool]   # identity only (shared-pool detection)
    src: str                        # source engine name
    exported_at: float
    ready_at: float
    out: List[int]                  # the request's (shared) output list
    pos: int = 0                    # mrope: next input token's position


class ServingEngine:
    """Slot scheduler over any backend exposing init_slots/prefill/decode.

    The scheduler never looks inside the slot state — family layout
    (stacked KV, ring buffers, recurrent rows, cross-KV) is entirely the
    backend's business.  With a paged backend the engine additionally owns
    the host-side block accounting: a :class:`BlockPool` +
    :class:`SlotTables` pair whose read/write tables it uploads to the
    cache whenever they change, prefix-sharing admission keyed by
    :func:`prefix_keys`, and the per-step copy-on-write walk
    (:meth:`SlotTables.ensure_writable` -> ``backend.copy_block``)."""

    def __init__(self, backend, ecfg: EngineConfig = EngineConfig(),
                 clock: Optional[Clock] = None,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None, *,
                 name: str = "engine", role: str = "both",
                 cf_head=None):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r} "
                             "(both | prefill | decode)")
        self.backend, self.ecfg = backend, ecfg
        self.name = name
        self.role = role
        self.clock = clock if clock is not None else Clock()
        # observability: spans/instants + pool gauges, both pinned to the
        # engine's (simulated) clock so per-request span durations reconcile
        # with the TTFT/TPOT report by construction
        self.tracer = or_null(tracer)
        if tracer is not None:
            self.tracer.clock = lambda: self.clock.now
        self.metrics = metrics
        if metrics is not None:
            metrics.clock = lambda: self.clock.now
        n = ecfg.n_slots
        self.layout = getattr(backend, "layout", None) or ecfg.layout
        self.pool: Optional[BlockPool] = None
        self.tables: Optional[SlotTables] = None
        self.prefix_sharing = False
        if self.layout.paged and hasattr(backend, "set_tables"):
            self.pool = BlockPool(
                resolved_num_blocks(self.layout, n, ecfg.max_len),
                self.layout.block_size)
            self.tables = SlotTables(
                self.pool, n, blocks_per_slot(self.layout, ecfg.max_len))
            self.prefix_sharing = (
                self.layout.prefix_sharing
                and getattr(backend, "supports_prefix_sharing", False))
            if metrics is not None:
                self.pool.attach_metrics(
                    metrics,
                    prefix="pool" if name == "engine" else f"{name}.pool",
                    clock=lambda: self.clock.now)
        if role != "both" and self.tables is None:
            raise ValueError(
                f"engine role {role!r} needs a paged layout — prefill/"
                "decode handoff rides the block pool (layout=CacheLayout("
                "kind='paged'))")
        # disaggregated serving: handoffs exported by a prefill-tier
        # engine (drained by the DisaggServer driver) and the inbox of
        # handoffs awaiting a free slot on a decode-tier engine
        self.pending_handoffs: Deque[Handoff] = deque()
        self.handoff_inbox: Deque[Handoff] = deque()
        self.handoffs_out = 0
        self.handoffs_in = 0
        # sliding-window TTFT/TPOT percentiles (router routing signal)
        self.win = (metrics_lib.WindowedLatency(metrics, name)
                    if metrics is not None else None)
        # speculative decode: k rows verified per scheduler step
        self.spec_k = max(1, int(ecfg.spec_k))
        if self.spec_k > 1:
            if ecfg.spec_draft != "ngram":
                raise ValueError(
                    f"unknown spec_draft {ecfg.spec_draft!r}; the engine "
                    "is self-speculative (draft='ngram', no second model)")
            fam = getattr(backend, "family", None)
            if fam is not None and fam not in tf.SPEC_FAMILIES:
                raise ValueError(
                    f"speculative decode (spec_k={self.spec_k}) needs a "
                    f"pure-KV cache family {tf.SPEC_FAMILIES}; {fam!r} "
                    "carries recurrent per-token state that cannot rewind "
                    "a rejected draft — serve it with spec_k=1")
            has_spec = (hasattr(backend, "_decode_spec")
                        or hasattr(backend, "_decode_spec_impl")
                        or (not isinstance(backend, SlotBackend)
                            and hasattr(backend, "decode_spec")))
            if not has_spec:
                raise ValueError(
                    f"{type(backend).__name__} has no speculative decode "
                    "path; serve it with spec_k=1")
            # stamp BEFORE init_slots: gemma local rings must be sized
            # window + spec_k - 1 for mid-draft wraparound exactness.
            # max() keeps a shared backend's rings large enough for every
            # engine using it (single-step on a margined ring is exact)
            backend.spec_k = max(getattr(backend, "spec_k", 1), self.spec_k)
        init = getattr(backend, "init_slots", None) or backend.init_cache
        self.cache = init(n, ecfg.max_len)
        self.queue = AdmissionQueue()
        self.slot_req: List[Optional[Request]] = [None] * n
        self.slot_rec: List[Optional[metrics_lib.RequestRecord]] = [None] * n
        self.slot_remaining = np.zeros(n, np.int64)
        self.slot_tokens = np.zeros((n, 1), np.int32)
        # device twin of slot_tokens: on pure decode steps the next tokens
        # are already on device (the sampler's output), so nothing is
        # re-uploaded; only host-side slot writes (prefill) mark it dirty
        self._tokens_dev = None
        self._tokens_dirty = True
        self.slot_key: List = [None] * n    # per-slot sampling RNG keys
        # mrope: the position of each slot's NEXT input token, advanced
        # per generated token from the request's prefill text+patch layout
        self.slot_pos = np.zeros(n, np.int64)
        self.outputs: Dict[int, List[int]] = {}
        self.records: List[metrics_lib.RequestRecord] = []
        self.decode_steps = 0
        self.prefills = 0
        # KV frontier per slot (= rows filled: prompt + generated so far);
        # the paged write path makes position _slot_len[s] writable before
        # each decode step lands a token there
        self._slot_len = np.zeros(n, np.int64)
        # serve-artifact metrics: peak batch occupancy and resident KV
        # bytes integrated over decode steps (modeled via roofline)
        self.max_concurrent = 0
        self._kv_bytes_sum = 0.0
        # speculative accounting: tokens emitted by decode steps (not
        # scheduler steps) over live slot-steps, so accepted_tokens/step
        # is per slot (classic single-step decode == exactly 1.0)
        self.spec_tokens = 0
        self.spec_slot_steps = 0
        self.spec_rows = 0      # verify rows run (drafting intensity)
        # recsys serving: CF head (sharded cf_user/cf_item scoring with
        # the hot-row replica) — requests carrying a candidate set are
        # scored at prefill, inside the req.prefill span
        self.cf_head = cf_head
        self.cf_results: Dict[int, Dict] = {}
        self.cf_scored = 0
        # the phase spans' track; the CF head's spans nest on it too
        self._etrack = self._track("engine")
        if cf_head is not None:
            cf_head.set_tracer(self.tracer, self._etrack)
        self.ticks = 0
        self.host_syncs = 0     # blocking waits on, and reads from, the device

    # -- bookkeeping helpers -------------------------------------------------

    def _sync_tables(self) -> None:
        if self.tables is not None and self.tables.dirty:
            self.cache = self.backend.set_tables(
                self.cache, self.tables.read, self.tables.write)
            self.tables.dirty = False

    def _share_seed(self, req: Request):
        """Cache-namespace seed for prefix hashing: everything besides the
        prompt tokens that shapes a prompt's KV rows (model + backend +
        numerics config; encoder frames and the vlm patch grid for the
        families whose self-KV depends on them)."""
        parts: List = [getattr(self.backend.cfg, "name", ""),
                       self.layout.kv_bits,
                       type(self.backend).__name__,
                       type(getattr(self.backend, "inner", None)).__name__,
                       repr(getattr(self.backend, "ctx", None)),
                       self.ecfg.prefill_chunk]
        if req.frames is not None:
            fb = np.ascontiguousarray(np.asarray(req.frames, np.float32))
            parts.append(hashlib.blake2b(fb.tobytes(),
                                         digest_size=8).hexdigest())
        if req.grid is not None:
            parts.append(tuple(req.grid))
        return tuple(parts)

    def _resident_kv_bytes(self) -> float:
        """Modeled resident decode-state bytes right now (paged: pool
        occupancy; dense: every slot pinned at max_len)."""
        cfg = getattr(self.backend, "cfg", None)
        if cfg is None or not hasattr(cfg, "layer_kinds"):
            return 0.0
        from repro.serving import roofline
        if self.pool is not None:
            return roofline.resident_kv_bytes(
                cfg, self.ecfg.n_slots, self.ecfg.max_len, self.layout,
                used_blocks=self.pool.used_blocks)
        return self.ecfg.n_slots * roofline.decode_state_bytes(
            cfg, self.ecfg.max_len, kv_bits=self.layout.kv_bits)

    def _track(self, base: str) -> str:
        """Trace track name: bare for the default single engine (keeps
        existing traces/tests byte-identical), ``{name}.{base}`` when this
        engine is a named replica sharing a timeline with others."""
        return base if self.name == "engine" else f"{self.name}.{base}"

    def _trace_request(self, rec: metrics_lib.RequestRecord,
                       slot: int) -> None:
        """Retroactive per-request phase spans on track ``slot{N}``, built
        from the exact RequestRecord timestamps the metrics report reads:
        ``ttft == queue_wait.dur + prefill.dur`` and
        ``tpot == decode.dur / (tokens_out - 1)`` hold identically."""
        tr = self.tracer
        if not tr.enabled or rec.finished is None:
            return
        track = self._track(f"slot{slot}")
        tr.complete("req.queue_wait", rec.arrival, rec.admitted, track=track,
                    rid=rec.rid, slo=rec.slo_name)
        tr.complete("req.prefill", rec.admitted, rec.first_token, track=track,
                    rid=rec.rid, prompt_len=rec.prompt_len)
        tr.complete("req.decode", rec.first_token, rec.finished, track=track,
                    rid=rec.rid, tokens_out=rec.tokens_out)

    @property
    def n_active(self) -> int:
        return sum(1 for r in self.slot_req if r is not None)

    def _timed(self, fixed_s: Optional[float], fn, wait: bool = True):
        """Run ``fn`` and advance the clock by its time; ``wait``: its
        output is on the device and the host blocks until it is ready."""
        t0 = time.perf_counter()
        out = fn()
        if wait:
            jax.block_until_ready(out)
            self.host_syncs += 1
        self.clock.advance(fixed_s if fixed_s is not None
                           else time.perf_counter() - t0)
        return out

    def _read(self, x, dtype=None) -> np.ndarray:
        """A device array read to the host: one blocking sync."""
        self.host_syncs += 1
        return np.asarray(x, dtype)

    # -- scheduler ops -------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Enqueue; False (and a rejected record) when the bounded admission
        queue is full or the prompt cannot fit the serving window.  At
        saturation an interactive arrival sheds the newest batch-tier entry
        instead of being dropped (SLO-aware admission)."""
        rec = metrics_lib.RequestRecord(
            rid=req.rid, user_id=req.user_id, prompt_len=len(req.prompt),
            slo_name=req.slo.name, ttft_slo_s=req.slo.ttft_ms / 1e3,
            tpot_slo_s=req.slo.tpot_ms / 1e3, arrival=req.arrival)
        self.records.append(rec)
        if len(req.prompt) >= self.ecfg.max_len:
            rec.rejected = True
            self.tracer.instant("sched.reject", track=self._track("sched"),
                                rid=req.rid, reason="prompt_too_long")
            return False
        if req.grid is not None and \
                req.grid[0] * req.grid[1] >= len(req.prompt):
            # a patch grid must leave at least one text token: patches
            # spilling into pad positions would silently corrupt the
            # request's mrope layout (see mrope_prompt_positions)
            rec.rejected = True
            self.tracer.instant("sched.reject", track=self._track("sched"),
                                rid=req.rid, reason="grid_overflow")
            return False
        if len(self.queue) >= self.ecfg.queue_capacity:
            shed = (self.queue.shed_batch()
                    if req.slo.name == "interactive" else None)
            if shed is None:
                rec.rejected = True
                self.tracer.instant("sched.reject", track=self._track("sched"),
                                    rid=req.rid, reason="queue_full")
                return False
            shed[1].rejected = True         # the batch-tier request it evicts
            self.tracer.instant("sched.shed", track=self._track("sched"),
                                rid=shed[0].rid, for_rid=req.rid)
        self.queue.append((req, rec))
        self._note_load()
        return True

    def _request_key(self, req: Request):
        """Per-request sampling key: reproducible across runs/slots."""
        return jax.random.fold_in(
            jax.random.PRNGKey(self.ecfg.sample_seed), req.rid)

    def _start(self, slot: int, req: Request,
               rec: metrics_lib.RequestRecord) -> bool:
        """Prefill-on-arrival into one slot; the first generated token falls
        out of the prefill logits.  Returns False — request untouched — when
        the block pool cannot map the request yet (paged admission): the
        caller requeues it behind the blocks that retiring slots free."""
        tr, trk = self.tracer, self._etrack
        prompt = np.asarray(req.prompt, np.int32)
        if self.tables is not None:
            with tr.span("pool.admit", track=trk):
                bs = self.layout.block_size
                if self.role == "prefill":
                    # tier advantage: a prefill engine maps only the
                    # prompt's blocks — the decode budget is reserved by
                    # the decode tier at import (pad-row writes past the
                    # prompt sink into the null block)
                    span = -(-len(prompt) // bs)
                else:
                    span = -(-min(len(prompt) + req.max_new_tokens,
                                  self.ecfg.max_len) // bs)
                if self.prefix_sharing:
                    keys, tail = prefix_keys(req.prompt, bs,
                                             self._share_seed(req))
                else:
                    keys, tail = [], None
                if not self.tables.admit(slot, keys, tail, span):
                    return False
                self._sync_tables()
        rec.admitted = self.clock.now
        self.tracer.instant("sched.admit", track=self._track("sched"),
                            rid=req.rid, slot=slot,
                            queue_wait=rec.admitted - rec.arrival)
        s_pad = _bucket(len(prompt), self.ecfg.prompt_quantum,
                        self.ecfg.max_len)
        padded = np.full((1, s_pad), self.ecfg.pad_id, np.int32)
        padded[0, :len(prompt)] = prompt
        kwargs = {}
        if req.frames is not None:       # enc-dec: cross-KV at admission
            kwargs["frames"] = np.asarray(req.frames, np.float32)
        if getattr(self.backend, "needs_positions", False):
            kwargs["grid"] = req.grid    # text+patch mrope layout
        with tr.span("model.prefill", track=trk, rid=req.rid, tokens=s_pad):
            logits_row, self.cache = self._timed(
                self.clock.fixed_prefill_s,
                lambda: self.backend.prefill(self.cache, padded,
                                             len(prompt), slot, **kwargs))
        self.prefills += 1
        self._slot_len[slot] = len(prompt)
        if self.tables is not None:
            # publish this prompt's self-computed blocks for later sharers
            self.tables.seal_prompt(slot)
        if self.cf_head is not None and req.candidates:
            # retrieval->rank: score the candidate set through the sharded
            # CF tables and fuse with the prompt's last-position logits.
            # Runs between prefill and the first-token stamp, so the CF
            # time lands inside the req.prefill span and the TTFT/span
            # reconciliation holds unchanged.
            # the head returns host arrays: its own reads are its syncs
            syncs = self.cf_head.host_syncs
            with tr.span("cf.lookup", track=trk, rid=req.rid,
                         candidates=len(req.candidates)) as sp:
                res = self._timed(
                    getattr(self.clock, "fixed_cf_s", None),
                    lambda: self.cf_head.score(req.user_id, req.candidates,
                                               lm_logits_row=logits_row),
                    wait=False)
                sp.set(hits=res["hits"], misses=res["misses"])
            self.host_syncs += self.cf_head.host_syncs - syncs
            self.cf_results[req.rid] = res
            self.cf_scored += 1
            if self.metrics is not None:
                self.metrics.counter("cf_cache.hits").inc(res["hits"])
                self.metrics.counter("cf_cache.misses").inc(res["misses"])
                self.metrics.gauge("cf_cache.hit_rate").set(
                    self.cf_head.hit_rate)
                self.metrics.gauge("cf_cache.rows").set(
                    self.cf_head.cache_rows_live)
        with tr.span("sample.first", track=trk):
            key = self._request_key(req)
            first = sample_token(logits_row, req.temperature, req.top_k,
                                 jax.random.fold_in(key, 0))
            self.host_syncs += 1            # the token read to the host
        rec.first_token = self.clock.now
        rec.tokens_out = 1
        if self.win is not None:
            self.win.observe_ttft(rec.first_token - rec.arrival)
        self.outputs[req.rid] = [first]
        budget = min(req.max_new_tokens, self.ecfg.max_len - len(prompt))
        if first == req.eos_id or budget <= 1:
            rec.finished = self.clock.now       # slot never occupied
            if self.tables is not None:
                self.tables.release(slot)
            self._trace_request(rec, slot)
            self._note_finish(rec)
            return True
        if self.role == "prefill":
            # hand the sealed prompt blocks + slot state to the decode
            # tier; this slot frees immediately, so the next queued
            # prompt prefills back-to-back (the tier's whole point)
            self._export_request(slot, req, rec, first, self._read(key),
                                 budget)
            return True
        self.slot_req[slot] = req
        self.slot_rec[slot] = rec
        self.slot_remaining[slot] = budget - 1
        self.slot_tokens[slot, 0] = first
        self._tokens_dirty = True           # host wrote a slot: re-upload
        self.slot_key[slot] = self._read(key)    # host copy: stacked later
        if getattr(self.backend, "needs_positions", False):
            # the first generated token's mrope position, one past the
            # prompt's layout (text continues all three components)
            self.slot_pos[slot] = tf.mrope_next_position(len(prompt),
                                                         req.grid)
        return True

    # -- disaggregated handoff ----------------------------------------------

    def _export_request(self, slot: int, req: Request,
                        rec: metrics_lib.RequestRecord, first: int,
                        key: np.ndarray, budget: int) -> None:
        """Package the just-prefilled request for the decode tier: snapshot
        the slot's block chain (values + sealed keys) and slot state, then
        release the slot.  The snapshot arrays are immutable, so the blocks
        can be reused here before the decode tier lands the import."""
        bs = self.layout.block_size
        live = len(req.prompt)
        blocks, keys = self.tables.export_slot(slot)
        n_live = -(-live // bs)
        values = self.backend.gather_block_values(self.cache,
                                                  blocks[:n_live])
        state = self.backend.export_slot_state(self.cache, slot)
        pos = 0
        if getattr(self.backend, "needs_positions", False):
            pos = int(tf.mrope_next_position(live, req.grid))
        now = self.clock.now
        h = Handoff(
            req=req, rec=rec, last_token=first, budget=budget, key=key,
            live_tokens=live, blocks=blocks[:n_live], keys=keys[:n_live],
            values=values, slot_state=state, src_pool=self.pool,
            src=self.name, exported_at=now,
            ready_at=now + (self.clock.fixed_handoff_s or 0.0),
            out=self.outputs[req.rid], pos=pos)
        self.tables.release(slot)
        self.pending_handoffs.append(h)
        self.handoffs_out += 1
        self.tracer.instant("pool.handoff", track=self._track("pool"),
                            rid=req.rid, dir="out", blocks=n_live,
                            live_tokens=live)
        if self.metrics is not None:
            self.metrics.counter(f"{self.name}.handoffs_out").inc()
        self._note_load()

    def import_handoff(self, h: Handoff) -> bool:
        """Land a handoff in a free slot: map the exported chain into this
        pool (dedupe via sealed keys / re-refcount when pools are shared),
        scatter the copied block values, restore slot state, and resume
        the request mid-stream.  False when no slot or not enough blocks
        are free yet — the caller retries after retirements."""
        slot = next((s for s in range(self.ecfg.n_slots)
                     if self.slot_req[s] is None), None)
        if slot is None:
            return False
        bs = self.layout.block_size
        span = -(-min(h.live_tokens + h.budget, self.ecfg.max_len) // bs)
        copies = self.tables.import_slot(
            slot, h.blocks, h.keys, h.live_tokens,
            src_pool=h.src_pool, span_blocks=span)
        if copies is None:
            if self.pool.used_blocks == 0:
                raise RuntimeError(
                    f"decode tier pool too small for handoff rid="
                    f"{h.req.rid} ({span} blocks needed, "
                    f"{self.pool.num_blocks} in pool)")
            return False
        if copies:
            self.cache = self.backend.scatter_block_values(
                self.cache, [d for _, d in copies], h.values,
                rows=[i for i, _ in copies])
        self.cache = self.backend.import_slot_state(self.cache, slot,
                                                    h.slot_state)
        self._sync_tables()
        req, rec = h.req, h.rec
        self.outputs[req.rid] = h.out
        self.slot_req[slot] = req
        self.slot_rec[slot] = rec
        self.slot_remaining[slot] = h.budget - 1
        self.slot_tokens[slot, 0] = h.last_token
        self._tokens_dirty = True
        self.slot_key[slot] = h.key
        self._slot_len[slot] = h.live_tokens
        if getattr(self.backend, "needs_positions", False):
            self.slot_pos[slot] = h.pos
        self.handoffs_in += 1
        self.tracer.instant("pool.handoff", track=self._track("pool"),
                            rid=req.rid, dir="in", slot=slot,
                            copied=len(copies), adopted=len(h.blocks) -
                            len(copies))
        # the handoff span sits inside req.decode on the destination slot
        # track: TTFT closed at prefill (first token came from the prefill
        # tier); the transfer is decode-side latency the TPOT report pays
        self.tracer.complete("req.handoff", h.exported_at, self.clock.now,
                             track=self._track(f"slot{slot}"), rid=req.rid,
                             src=h.src, blocks=len(h.blocks))
        if self.metrics is not None:
            self.metrics.counter(f"{self.name}.handoffs_in").inc()
        self._note_load()
        self._note_occupancy()
        return True

    def _drain_inbox(self) -> bool:
        progressed = False
        while self.handoff_inbox:
            if not self.import_handoff(self.handoff_inbox[0]):
                break
            self.handoff_inbox.popleft()
            progressed = True
        if progressed:
            self._note_load()
        return progressed

    @property
    def has_work(self) -> bool:
        return bool(self.n_active or self.queue or self.handoff_inbox)

    def tick(self) -> bool:
        """One non-blocking scheduler step for the multi-engine driver:
        land ready handoffs, refill free slots from the queue, decode once
        if anything is active.  Returns False when nothing moved (the
        engine is blocked waiting on blocks or deliveries)."""
        tr, trk = self.tracer, self._etrack
        with tr.span("engine.tick", track=trk):
            self.ticks += 1
            before = (self.prefills, self.decode_steps, self.handoffs_in,
                      len(self.queue), len(self.handoff_inbox))
            self._drain_inbox()
            with tr.span("sched.refill", track=trk):
                self._refill()
            active = self.n_active
            if active:
                self._decode_once(active)
            after = (self.prefills, self.decode_steps, self.handoffs_in,
                     len(self.queue), len(self.handoff_inbox))
        return after != before

    # -- refill -------------------------------------------------------------

    def _refill(self) -> None:
        free = [s for s in range(self.ecfg.n_slots)
                if self.slot_req[s] is None]
        if self.ecfg.refill == "static" and len(free) < self.ecfg.n_slots:
            return                              # classical batch barrier
        for s in free:
            while self.queue and self.slot_req[s] is None:
                req, rec = self.queue.popleft()
                if self._start(s, req, rec):    # may finish instantly (EOS)
                    continue
                # paged admission failed: not enough free blocks.  An empty
                # pool that still can't cover the request never will —
                # reject; otherwise park it at the queue head until
                # retiring slots return their blocks (graceful queueing,
                # never corruption)
                if self.pool is not None and self.pool.used_blocks == 0:
                    rec.rejected = True
                    self.tracer.instant("sched.reject",
                                        track=self._track("sched"),
                                        rid=req.rid, reason="pool_too_small")
                    continue
                self.queue.pushback((req, rec))
                self.tracer.instant("sched.pushback",
                                    track=self._track("sched"), rid=req.rid,
                                    free_blocks=self.pool.free_blocks
                                    if self.pool is not None else 0)
                self._note_occupancy()
                return
        self._note_occupancy()

    def _note_occupancy(self) -> None:
        active = self.n_active
        self.max_concurrent = max(self.max_concurrent, active)
        if self.metrics is not None:
            self.metrics.gauge(f"{self.name}.active_slots").set(
                active, t=self.clock.now)

    def _note_load(self) -> None:
        """Per-replica load gauges the router scores on: queued work
        (admission queue + handoff inbox) and the decode tokens still owed
        by active slots.  Stamped with this engine's clock explicitly, so
        N engines sharing one registry keep coherent series."""
        if self.metrics is None:
            return
        t = self.clock.now
        self.metrics.gauge(f"{self.name}.queue_depth").set(
            len(self.queue) + len(self.handoff_inbox), t=t)
        inflight = int(sum(int(self.slot_remaining[s])
                           for s in range(self.ecfg.n_slots)
                           if self.slot_req[s] is not None))
        self.metrics.gauge(f"{self.name}.in_flight_tokens").set(
            inflight, t=t)

    def _note_finish(self, rec: metrics_lib.RequestRecord) -> None:
        if self.win is not None and rec.tpot is not None:
            self.win.observe_tpot(rec.tpot)

    def _decode_once(self, rows: Optional[int] = None) -> None:
        """One decode step; ``rows``: the active slots, which ``tick``
        has counted already."""
        if rows is None:
            rows = self.n_active
        with self.tracer.span("engine.decode", track=self._etrack):
            if self.spec_k > 1:
                self._spec_decode_once()
            else:
                self._single_decode_once(rows)

    def _single_decode_once(self, rows: int) -> None:
        tr, trk = self.tracer, self._etrack
        if self.tables is not None:
            # make every active slot's KV frontier exclusively owned before
            # the step writes there: COW off shared tails, claim sole-owner
            # sealed blocks, then upload the changed tables once
            with tr.span("pool.ensure_writable", track=trk):
                for s in range(self.ecfg.n_slots):
                    if self.slot_req[s] is None:
                        continue
                    cow = self.tables.ensure_writable(s,
                                                      int(self._slot_len[s]))
                    if cow is not None:
                        self.cache = self.backend.copy_block(self.cache, *cow)
                        tr.instant("pool.cow", track=self._track("pool"),
                                   slot=s, src=cow[0], dst=cow[1])
            with tr.span("pool.sync_tables", track=trk):
                self._sync_tables()
        positions = None
        if getattr(self.backend, "needs_positions", False):
            # (n, 1, 3): text decode advances t/h/w together per token
            positions = jnp.asarray(
                np.broadcast_to(self.slot_pos[:, None, None],
                                (self.ecfg.n_slots, 1, 3)), jnp.int32)
        if self._tokens_dirty or self._tokens_dev is None:
            self._tokens_dev = jnp.asarray(self.slot_tokens)
            self._tokens_dirty = False
        tokens = self._tokens_dev
        if positions is None:       # toy/test backends take (cache, tokens)
            call = lambda: self.backend.decode(  # noqa: E731
                self.cache, tokens)
        else:
            call = lambda: self.backend.decode(  # noqa: E731
                self.cache, tokens, positions)
        with tr.span("decode_step", track=trk, step=self.decode_steps,
                     rows=rows):
            logits, self.cache = self._timed(self.clock.fixed_decode_s, call)
        self.decode_steps += 1
        self.slot_pos += 1
        n = self.ecfg.n_slots
        with tr.span("sample.tokens", track=trk):
            any_sampled = any(r is not None and r.temperature > 0.0
                              for r in self.slot_req)
            if not any_sampled:
                nxt_dev = _greedy_tokens(logits[:, 0, :])
            else:
                # batched temperature/top-k/categorical over all slots: one
                # device call, one host sync.  Per-slot keys fold with the
                # token index inside the jit, so slot placement and batch
                # composition never change a request's sampled stream (the
                # semantics the scalar sample_token path established).
                temps = np.zeros(n, np.float32)
                topks = np.zeros(n, np.int32)
                counts = np.zeros(n, np.int32)
                keys = np.zeros((n, 2), np.uint32)
                for s in range(n):
                    if self.slot_req[s] is None:
                        continue
                    temps[s] = self.slot_req[s].temperature
                    topks[s] = self.slot_req[s].top_k
                    counts[s] = self.slot_rec[s].tokens_out
                    keys[s] = self.slot_key[s]
                nxt_dev = _fold_and_sample(logits[:, 0, :], temps, topks,
                                           keys, counts)
            nxt = self._read(nxt_dev, np.int32)
            # the sampled tokens are the next step's inputs and are already
            # on device — keep them there instead of re-uploading from host
            self._tokens_dev = nxt_dev[:, None].astype(jnp.int32)
        with tr.span("engine.retire", track=trk):
            self._kv_bytes_sum += self._resident_kv_bytes()
            for s in range(n):
                req, rec = self.slot_req[s], self.slot_rec[s]
                if req is None:
                    continue
                tok = int(nxt[s])
                self.outputs[req.rid].append(tok)
                rec.tokens_out += 1
                self.slot_remaining[s] -= 1
                self.slot_tokens[s, 0] = tok
                self._slot_len[s] += 1          # this step's token landed
                if tok == req.eos_id or self.slot_remaining[s] <= 0:
                    rec.finished = self.clock.now
                    self.slot_req[s] = None
                    self.slot_rec[s] = None
                    self.slot_key[s] = None
                    if self.tables is not None:
                        self.tables.release(s)  # refcounts back to the pool
                    self._trace_request(rec, s)
                    self._note_finish(rec)
            self._note_load()

    def _spec_decode_once(self) -> None:
        """One speculative scheduler step: self-draft up to ``spec_k - 1``
        continuation tokens per greedy slot, verify all rows in one fused
        k-row decode, commit per-slot accepted prefixes.  Token streams are
        identical to single-step decode by construction (greedy
        verification accepts exactly the prefix row-by-row decode would
        have emitted); sampled slots fall back to one token per step."""
        n, k = self.ecfg.n_slots, self.spec_k
        rows = np.full((n, k), self.ecfg.pad_id, np.int32)
        rows[:, 0] = self.slot_tokens[:, 0]
        q_lens = np.ones(n, np.int64)
        for s in range(n):
            req = self.slot_req[s]
            if req is None:
                continue
            # draft cap: the step writes q_len KV rows at len..len+q_len-1
            # (must fit max_len) and can emit at most the slot's remaining
            # token budget; sampled streams verify nothing — draft 0
            cap = min(k - 1, int(self.slot_remaining[s]) - 1,
                      self.ecfg.max_len - 1 - int(self._slot_len[s]))
            if req.temperature > 0.0:
                cap = 0
            if cap > 0:
                draft = ngram_draft(
                    list(req.prompt) + self.outputs[req.rid], cap)
                rows[s, 1:1 + len(draft)] = draft
                q_lens[s] = 1 + len(draft)
        # shape-bucketed verify: run this step at the smallest power-of-two
        # row count covering the longest draft (1, 2, ... up to spec_k), so
        # short-draft steps pay near single-row cost instead of the full
        # k-row shape.  Each bucket jit-compiles once and is then cached.
        k_step = 1
        while k_step < int(q_lens.max()):
            k_step *= 2
        k_step = min(k_step, k)
        rows = rows[:, :k_step]
        tr, trk = self.tracer, self._etrack
        if self.tables is not None:
            # own the whole write span up front: one pass per touched
            # block regardless of k (batched COW)
            with tr.span("pool.ensure_writable", track=trk):
                for s in range(n):
                    if self.slot_req[s] is None:
                        continue
                    for src, dst in self.tables.ensure_writable_span(
                            s, int(self._slot_len[s]), int(q_lens[s])):
                        self.cache = self.backend.copy_block(self.cache,
                                                             src, dst)
                        tr.instant("pool.cow", track=self._track("pool"),
                                   slot=s, src=src, dst=dst)
            with tr.span("pool.sync_tables", track=trk):
                self._sync_tables()
        positions = None
        if getattr(self.backend, "needs_positions", False):
            # (n, k_step, 3): text decode advances t/h/w together per row
            pos = self.slot_pos[:, None] + np.arange(k_step)[None, :]
            positions = jnp.asarray(
                np.broadcast_to(pos[:, :, None], (n, k_step, 3)), jnp.int32)
        if hasattr(self.backend, "_decode_spec_packed"):
            # one upload for rows + q_lens (last column), done before the
            # timed call — like the classic path's device-resident tokens,
            # the clock prices the model step, not the host handoff
            packed = jnp.asarray(np.concatenate(
                [rows, q_lens[:, None].astype(np.int32)], axis=1))
            call = lambda: self.backend.decode_spec_packed(  # noqa: E731
                self.cache, packed, positions)
        else:
            tokens = jnp.asarray(rows)
            q_dev = jnp.asarray(q_lens, jnp.int32)
            call = lambda: self.backend.decode_spec(  # noqa: E731
                self.cache, tokens, q_dev, positions)
        live = [s for s in range(n) if self.slot_req[s] is not None]
        live_rows = int(q_lens[live].sum())
        with tr.span("decode_step", track=trk, step=self.decode_steps,
                     rows=len(live), q_rows=live_rows):
            logits, accepts_dev, self.cache = self._timed(
                self.clock.fixed_decode_s, call)
        self.decode_steps += 1
        with tr.span("sample.tokens", track=trk):
            emitted_np = self._read(_greedy_tokens(logits), np.int64)
            accepts = self._read(accepts_dev, np.int64)
            sampled = None
            if any(r is not None and r.temperature > 0.0
                   for r in self.slot_req):
                temps = np.zeros(n, np.float32)
                topks = np.zeros(n, np.int32)
                counts = np.zeros(n, np.int32)
                keys = np.zeros((n, 2), np.uint32)
                for s in range(n):
                    if self.slot_req[s] is None:
                        continue
                    temps[s] = self.slot_req[s].temperature
                    topks[s] = self.slot_req[s].top_k
                    counts[s] = self.slot_rec[s].tokens_out
                    keys[s] = self.slot_key[s]
                sampled = self._read(_fold_and_sample(
                    logits[:, 0, :], temps, topks, keys, counts), np.int32)
        self._tokens_dirty = True       # host builds next step's draft rows
        step_emitted = 0
        self.spec_slot_steps += len(live)
        self.spec_rows += live_rows
        with tr.span("engine.retire", track=trk):
            self._kv_bytes_sum += self._resident_kv_bytes()
            for s in live:
                req, rec = self.slot_req[s], self.slot_rec[s]
                a = int(accepts[s])
                if req.temperature > 0.0:
                    toks = [int(sampled[s])]       # a == 1 (q_len was 1)
                else:
                    toks = [int(t) for t in emitted_np[s, :a]]
                # stop at the first EOS (the device cache over-commits the
                # rows behind it, but a finishing slot's state is discarded)
                eos_at = next((j for j, t in enumerate(toks)
                               if t == req.eos_id), None)
                if eos_at is not None:
                    toks = toks[:eos_at + 1]
                self.outputs[req.rid].extend(toks)
                rec.tokens_out += len(toks)
                step_emitted += len(toks)
                self.slot_remaining[s] -= len(toks)
                self._slot_len[s] += a          # device KV frontier: accepts
                self.slot_pos[s] += a
                self.slot_tokens[s, 0] = toks[-1]
                if eos_at is not None or self.slot_remaining[s] <= 0:
                    rec.finished = self.clock.now
                    self.slot_req[s] = None
                    self.slot_rec[s] = None
                    self.slot_key[s] = None
                    if self.tables is not None:
                        self.tables.release(s)
                    self._trace_request(rec, s)
                    self._note_finish(rec)
            self._note_load()
        self.spec_tokens += step_emitted
        if self.metrics is not None:
            self.metrics.counter("engine.spec_tokens").inc(step_emitted)

    # -- driver --------------------------------------------------------------

    def run(self, requests: Sequence[Request]):
        """Serve a workload to completion.

        Returns (outputs {rid: [token, ...]}, records, summary-dict)."""
        reqs = sorted(requests, key=lambda r: r.arrival)
        i = 0
        while True:
            while i < len(reqs) and reqs[i].arrival <= self.clock.now:
                self.submit(reqs[i])
                i += 1
            if self.has_work:
                steps = self.decode_steps
                self.tick()
                if self.decode_steps != steps:
                    continue
                if self.queue:
                    # every slot free + non-empty queue should have refilled
                    raise RuntimeError("scheduler stalled with queued work")
            if i < len(reqs):
                self.clock.advance(reqs[i].arrival - self.clock.now)
                continue
            break
        summary = metrics_lib.summarize(self.records, self.clock.now)
        summary["decode_steps"] = self.decode_steps
        summary["prefills"] = self.prefills
        summary["max_concurrent_slots"] = self.max_concurrent
        summary["kv_bytes_per_step"] = (
            self._kv_bytes_sum / max(self.decode_steps, 1))
        if self.spec_k > 1:
            summary["spec"] = {
                "k": self.spec_k,
                "draft": self.ecfg.spec_draft,
                "spec_tokens": self.spec_tokens,
                # per live slot-step: classic decode == 1.0 by definition,
                # so anything above 1 is pure multi-token win
                "accepted_tokens_per_step": (
                    self.spec_tokens / max(self.spec_slot_steps, 1)),
                # verify rows run per live slot-step (1 + mean draft len):
                # the compute-side price the accepts above were bought at
                "verify_rows_per_step": (
                    self.spec_rows / max(self.spec_slot_steps, 1)),
            }
        if self.cf_head is not None:
            summary["cf"] = self.cf_head.summary()
            summary["cf"]["requests_scored_here"] = self.cf_scored
        if self.pool is not None:
            summary["paged"] = {
                "num_blocks": self.pool.num_blocks,
                "block_size": self.pool.block_size,
                "peak_used_blocks": self.pool.peak_used,
                "shared_hits": self.pool.shared_hits,
                "cow_events": self.pool.cow_events,
                "seal_count": self.pool.seal_count,
            }
        if self.tracer.enabled or self.metrics is not None:
            obs: Dict = {}
            if self.tracer.enabled:
                obs["span_counts"] = self.tracer.span_names()
                obs["trace_events"] = len(self.tracer.events)
            if self.metrics is not None:
                obs["metrics"] = self.metrics.snapshot()
            summary["obs"] = obs
        return self.outputs, self.records, summary


def serve(cfg, params, requests: Sequence[Request],
          ecfg: EngineConfig = EngineConfig(),
          ctx: Optional[tf.ModelCtx] = None,
          clock: Optional[Clock] = None,
          tracer: Optional[Tracer] = None,
          metrics: Optional[MetricsRegistry] = None):
    """One-call convenience wrapper: build backend + engine, run, report.

    The cache layout comes from ``ecfg.layout`` (dense/paged, bf16/int8,
    decode impl); ``ecfg.prefill_chunk`` selects streaming prefill.
    ``tracer`` / ``metrics`` flow through to :class:`ServingEngine`.  The
    legacy ``kv=`` kwarg was removed with the PR-6 deprecation shims —
    set ``EngineConfig.layout=CacheLayout(kv_bits=8)``."""
    layout = ecfg.layout
    # only hand make_backend an explicit layout when one was actually
    # chosen — a default layout must not override a caller ctx's decode_impl
    explicit = layout != CacheLayout()
    backend = make_backend(cfg, params, ctx,
                           layout=layout if explicit else None,
                           prefill_chunk=ecfg.prefill_chunk)
    engine = ServingEngine(backend, ecfg, clock, tracer=tracer,
                           metrics=metrics)
    return engine.run(requests)

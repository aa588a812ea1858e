"""Compressed gradient synchronization with error feedback (paper C6,
Eq. 10–11), running inside a shard_map'd DP train step.

* 1-bit (EF-signSGD): each rank packs sign bits 8-per-uint8 with per-block L1
  scales (Pallas kernel), all-gathers the uint8 payload + scales over the dp
  axis (wire bytes = N/8 + 4N/block vs 4N for fp32), locally dequantizes and
  averages.  The quantization error accumulates into a per-rank residual
  (error feedback) that is added to the next step's gradient — Eq. 11.
* top-k: each rank keeps the per-block top-k magnitudes, all-gathers (values,
  indices) = 8k bytes per block of ``block`` elements, scatter-adds locally.

Both return (synced_mean_gradient, new_residual).  Residuals are per-rank
state stored in the optimizer state with a leading dp-sharded device dim.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _flatten(tree):
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    return flat, (treedef, shapes, sizes)


def _unflatten(flat, meta):
    treedef, shapes, sizes = meta
    out, off = [], 0
    for shape, size in zip(shapes, sizes):
        out.append(flat[off:off + size].reshape(shape))
        off += size
    return jax.tree.unflatten(treedef, out)


def flat_size(tree, mult: int) -> int:
    n = sum(l.size for l in jax.tree.leaves(tree))
    return n + ((-n) % mult)


def onebit_sync(grads, residual: jnp.ndarray, *, axis: str = "data",
                block: int = 512, use_kernel: bool = True
                ) -> Tuple[object, jnp.ndarray]:
    """EF-signSGD sync inside shard_map.  residual: flat (N_pad,) f32."""
    flat, meta = _flatten(grads)
    npad = residual.shape[0] - flat.shape[0]
    flat = jnp.pad(flat, (0, npad)) + residual
    impl = "kernel" if use_kernel else "ref"
    packed, scales = ops.onebit_quantize(flat, block, impl=impl)
    local_hat = ops.onebit_dequantize(packed, scales, block, impl=impl)
    new_residual = flat - local_hat
    # exchange compressed payloads (uint8 + per-block scales on the wire)
    packed_all = jax.lax.all_gather(packed, axis)            # (P, N/8) u8
    scales_all = jax.lax.all_gather(scales, axis)            # (P, nb) f32
    # accumulate one dequantized peer at a time: a (P, N) f32 stack of
    # them would cost P dense gradients of device memory
    g_hat = ops.onebit_dequantize(packed_all[0], scales_all[0], block,
                                  impl=impl)
    for p in range(1, packed_all.shape[0]):
        g_hat = g_hat + ops.onebit_dequantize(packed_all[p], scales_all[p],
                                              block, impl=impl)
    g_hat = g_hat / packed_all.shape[0]
    n = flat.shape[0] - npad
    return _unflatten(g_hat[:n], meta), new_residual


def topk_sync(grads, residual: jnp.ndarray, *, axis: str = "data",
              block: int = 2048, k: int = 32, use_kernel: bool = True
              ) -> Tuple[object, jnp.ndarray]:
    """Top-k sparsified sync (Eq. 11) inside shard_map."""
    flat, meta = _flatten(grads)
    npad = residual.shape[0] - flat.shape[0]
    flat = jnp.pad(flat, (0, npad)) + residual
    impl = "kernel" if use_kernel else "ref"
    kept, _ = ops.topk_sparsify(flat, k, block, impl=impl)
    # extract exactly-k (values, indices) per block -> the wire payload
    # (ties beyond k fall back into the residual: error feedback keeps them)
    nb = flat.shape[0] // block
    kept2d = kept.reshape(nb, block)
    _, idx = jax.lax.top_k(jnp.abs(kept2d), k)               # (nb, k)
    vals = jnp.take_along_axis(kept2d, idx, axis=-1)         # signed values

    def scatter(v, i):
        rows = jnp.broadcast_to(
            jnp.arange(nb)[(None,) * (i.ndim - 2) + (slice(None), None)],
            i.shape)
        return jnp.zeros((nb, block), jnp.float32).at[rows, i].add(v)

    new_residual = flat - scatter(vals, idx).reshape(-1)
    vals_all = jax.lax.all_gather(vals, axis)                # (P, nb, k)
    idx_all = jax.lax.all_gather(idx, axis)
    # every peer's (values, indices) land in ONE dense buffer: a vmapped
    # scatter would hold P dense gradients at once
    g_hat = (scatter(vals_all, idx_all) / vals_all.shape[0]).reshape(-1)
    n = flat.shape[0] - npad
    return _unflatten(g_hat[:n], meta), new_residual


def make_compressed_sync(mode: str, *, axis: str = "data", block: int = 512,
                         k: int = 32, use_kernel: bool = True):
    """Returns sync(grads, residual) -> (mean_grads, new_residual)."""
    if mode == "onebit":
        return partial(onebit_sync, axis=axis, block=block,
                       use_kernel=use_kernel)
    if mode == "topk":
        return partial(topk_sync, axis=axis, block=block, k=k,
                       use_kernel=use_kernel)
    raise ValueError(mode)

"""Sparse-gradient updates: sync only the rows a step touched.

The backward of an embedding lookup is zero everywhere except the rows the
batch hit, yet replicated-dense DP all-reduces the full (V, D) gradient
every step.  This module replaces that with a rows-touched exchange inside
the shard_map'd DP step:

    u    = unique(local ids)                # (U,) + sentinel padding
    rows = dense_grad[u]                    # (U, D) — all the mass there is
    all-gather (u, rows) over the dp axes   # wire: P * U * (D*4 + 4) bytes
    scatter-add into (V, D), divide by P    # == pmean(dense_grad) exactly

Wire bytes scale with the batch's unique-id count instead of the vocab:
for the recsys tables (V ~ 1e5..1e7, U ~ batch) that is orders of
magnitude.  The payload can additionally ride the existing compression
kernels — ``make_row_compressor("topk", k)`` keeps the top-k magnitudes
per row via ``kernels/topk_sparsify.py`` before the gather (lossy; the
dropped mass is bounded by the per-row tail, and unlike dense top-k DP
sync no error-feedback residual is needed because untouched rows carry no
gradient to remember).

``sparse_row_sync`` is numerically the mean of the per-rank dense
gradients: every touched row appears in its rank's unique set, untouched
rows are zero on every rank.  On a 1-device mesh it is bit-for-bit equal.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


def rows_touched(ids: jnp.ndarray, n_rows: int,
                 cap: Optional[int] = None) -> jnp.ndarray:
    """Unique ids padded with the out-of-range sentinel ``n_rows``."""
    flat = ids.reshape(-1)
    return jnp.unique(flat, size=cap or flat.shape[0], fill_value=n_rows)


def gather_grad_rows(dense_grad: jnp.ndarray, u: jnp.ndarray
                     ) -> jnp.ndarray:
    """(U, D) gradient rows for unique ids; sentinel entries read as 0."""
    v = dense_grad.shape[0]
    valid = u < v
    rows = dense_grad[jnp.clip(u, 0, v - 1)]
    return jnp.where(valid[:, None], rows, jnp.zeros((), dense_grad.dtype))


def scatter_rows(u: jnp.ndarray, rows: jnp.ndarray, n_rows: int,
                 use_kernel: bool = False) -> jnp.ndarray:
    """(V, D) dense gradient from (ids, rows); sentinel ids drop onto a
    dump row that is sliced off."""
    idx = jnp.minimum(u, n_rows)
    if use_kernel:
        return ops.embedding_scatter_add(rows, idx, n_rows + 1)[:n_rows]
    return (jnp.zeros((n_rows + 1, rows.shape[-1]), rows.dtype)
            .at[idx].add(rows)[:n_rows])


def make_row_compressor(mode: str, k: int = 8, use_kernel: bool = True
                        ) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Per-row payload compressor for the exchanged gradient rows.

    ``topk`` keeps the k largest-magnitude entries of each row (block size
    = the embedding dim) through the existing Pallas sparsifier.
    """
    if mode != "topk":
        raise ValueError(f"unknown row compressor {mode!r}")

    def compress(rows: jnp.ndarray) -> jnp.ndarray:
        u, d = rows.shape
        kept, _ = ops.topk_sparsify(rows.reshape(-1), min(k, d), block=d,
                                    impl="kernel" if use_kernel else "ref")
        return kept.reshape(u, d)

    return compress


def sparse_row_sync(dense_grad: jnp.ndarray, ids: jnp.ndarray,
                    axes: Sequence[str], *, cap: Optional[int] = None,
                    compress: Optional[Callable] = None) -> jnp.ndarray:
    """Mean DP gradient via rows-touched all-gather (inside shard_map).

    dense_grad: this rank's (V, D) gradient; ids: the local batch's ids
    (any shape).  Returns the (V, D) mean over the dp ``axes`` — what
    ``pmean(dense_grad, axes)`` computes, at U-row wire cost.

    ``cap`` bounds the exchanged row count; it must cover the batch's
    unique-id count (``cap >= unique(ids)``, trivially true for the
    default ``cap = len(ids)``): ``jnp.unique(size=cap)`` truncates
    silently, and a truncated row is dropped from the sync entirely —
    zero gradient, not even the local contribution.
    """
    v = dense_grad.shape[0]
    u = rows_touched(ids, v, cap)
    rows = gather_grad_rows(dense_grad, u)
    if compress is not None:
        rows = compress(rows)
    n_ranks = 1
    for ax in axes:
        u = jax.lax.all_gather(u, ax, axis=0, tiled=True)
        rows = jax.lax.all_gather(rows, ax, axis=0, tiled=True)
        n_ranks *= jax.lax.axis_size(ax)
    return scatter_rows(u, rows, v) / n_ranks


def sparse_grad_from_lookup(dout: jnp.ndarray, ids: jnp.ndarray,
                            n_rows: int, cap: Optional[int] = None,
                            use_kernel: bool = False
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(unique ids, per-unique-row gradient) from the lookup cotangent —
    the segment-sum form, for optimizers that update touched rows only.

    dout: (..., D) cotangent of ``table[ids]``; returns (u (U,),
    grad_rows (U, D)) with ``scatter_rows(u, grad_rows, n_rows)`` equal to
    the dense gradient.
    """
    flat = ids.reshape(-1)
    d = dout.shape[-1]
    g2d = dout.reshape(-1, d)
    size = cap or flat.shape[0]
    u, inv = jnp.unique(flat, return_inverse=True, size=size)
    inv = inv.reshape(-1)
    if use_kernel:
        rows = ops.embedding_scatter_add(g2d, inv, size)
    else:
        rows = jnp.zeros((size, d), g2d.dtype).at[inv].add(g2d)
    # sentinel-padded tail repeats u[...]=fill; only the first occurrence
    # accumulated anything (inv never points at padding), so rows there
    # are zero and scattering them back is harmless.
    return u, rows

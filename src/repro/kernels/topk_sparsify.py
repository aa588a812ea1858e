"""Pallas TPU kernel for block-local top-k gradient sparsification with
error-feedback residual (paper Eq. 11).

Semantics (shared with ``ref.topk_sparsify``): within each block keep every
element with |x| >= t where t is the k-th largest magnitude (ties included);
residual = x - kept.  The k-th magnitude is found by k iterations of
max-and-mask on the VPU — k is small (<= 64) in practice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ROWS = 8


def _kernel(x_ref, kept_ref, resid_ref, *, k: int):
    x = x_ref[...]                                       # (rows, block)
    a = jnp.abs(x)

    def body(_, carry):
        tmp, thr = carry
        m = jnp.max(tmp, axis=-1, keepdims=True)             # per row
        tmp = jnp.where(tmp >= m, -1.0, tmp)
        return tmp, m

    _, t = jax.lax.fori_loop(0, k, body,
                             (a, jnp.full((x.shape[0], 1), jnp.inf)))
    kept = jnp.where(a >= t, x, 0.0)
    kept_ref[...] = kept
    resid_ref[...] = x - kept


def topk_sparsify(x2d: jnp.ndarray, k: int, interpret=False):
    """x2d: (nb, block) f32 -> (kept, residual) same shape.  Each grid step
    takes ``_ROWS`` blocks (one sublane tile; zero rows pad the tail)."""
    nb, block = x2d.shape
    n_pad = nb + (-nb) % _ROWS
    x2d = jnp.pad(x2d, ((0, n_pad - nb), (0, 0)))
    spec = pl.BlockSpec((_ROWS, block), lambda i: (i, 0))
    kept, resid = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(n_pad // _ROWS,),
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n_pad, block), jnp.float32),
                   jax.ShapeDtypeStruct((n_pad, block), jnp.float32)],
        interpret=interpret,
    )(x2d)
    return kept[:nb], resid[:nb]

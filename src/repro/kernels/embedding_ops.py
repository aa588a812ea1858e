"""Pallas TPU kernels for the sparse-embedding subsystem.

* ``gather_rows`` — fused embedding gather.  The row ids are a
  scalar-prefetch operand (:class:`pltpu.PrefetchScalarGridSpec`); grid
  step ``(i, r)`` fetches the aligned 8-row tile that holds id
  ``i*8 + r`` (the smallest row block the TPU's tiling lets a pipeline
  move) and copies its row into output tile ``i``.  The table is never
  materialized in VMEM: bytes moved = ``8 * n_ids * D * itemsize``,
  independent of the table size.
* ``scatter_add_rows`` — segment-sum scatter-add, the transpose of the
  gather: accumulates input rows into ``out[idx[i]] += x[i]``.  The grid
  tiles the output rows (``_SCATTER_TILE`` per step) and streams the input
  in ``_SCATTER_IN``-row blocks; each step adds the inputs whose id falls in
  its tile with a sequential loop — duplicate ids are exact, no atomics
  needed, and VMEM holds one output tile whatever ``n_rows`` is.

Both are validated in interpret mode against ``kernels/ref.py`` oracles
(tests/test_embeddings.py) and compiled for a v5e chip at recllm-base
widths (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_GATHER_ROWS = 8          # one sublane tile: rows per fetch and per output
_SCATTER_TILE = 512       # output rows resident in VMEM per grid step
_SCATTER_IN = 512         # input rows per grid step


def _gather_kernel(ids_ref, tbl_ref, out_ref, tile_scr, rows_scr):
    i, r = pl.program_id(0), pl.program_id(1)
    off = ids_ref[i * _GATHER_ROWS + r] % _GATHER_ROWS
    # single-row dynamic slices go through f32 scratch: packed (16-bit)
    # tiles refuse a row index not provably tile-aligned
    tile_scr[...] = tbl_ref[...].astype(jnp.float32)
    rows_scr[pl.ds(r, 1), :] = tile_scr[pl.ds(off, 1), :]

    @pl.when(r == _GATHER_ROWS - 1)
    def _emit():
        out_ref[...] = rows_scr[...].astype(out_ref.dtype)


def gather_rows(table: jnp.ndarray, ids: jnp.ndarray,
                interpret: bool = False) -> jnp.ndarray:
    """table (V, D), ids (n,) int32 -> (n, D) = table[ids]."""
    n = ids.shape[0]
    _, D = table.shape
    rows = _GATHER_ROWS
    n_pad = n + (-n) % rows
    ids = jnp.pad(ids.astype(jnp.int32), (0, n_pad - n))   # pad: row 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // rows, rows),
        in_specs=[pl.BlockSpec(
            (rows, D), lambda i, r, ids: (ids[i * rows + r] // rows, 0))],
        out_specs=pl.BlockSpec((rows, D), lambda i, r, ids: (i, 0)),
        scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32),
                        pltpu.VMEM((rows, D), jnp.float32)],
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, D), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, table)
    return out[:n]


def _scatter_add_kernel(idx_ref, x_ref, out_ref, *, n: int):
    tile, n_in = out_ref.shape[0], x_ref.shape[0]
    lo = pl.program_id(0) * tile
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, carry):
        r = idx_ref[j * n_in + i] - lo

        @pl.when((r >= 0) & (r < tile))
        def _add():
            out_ref[pl.ds(r, 1), :] += x_ref[pl.ds(i, 1), :]
        return carry

    jax.lax.fori_loop(0, jnp.minimum(n_in, n - j * n_in), body, 0)


def scatter_add_rows(x: jnp.ndarray, idx: jnp.ndarray, n_rows: int,
                     interpret: bool = False) -> jnp.ndarray:
    """x (n, D), idx (n,) int32 -> (n_rows, D) with out[idx[i]] += x[i].

    Exact for duplicate ids (sequential accumulation).  Out-of-range ids
    must be pre-clamped by the caller (the dedup path maps its sentinel to
    a dump row it slices off).
    """
    n, D = x.shape
    tile = min(_SCATTER_TILE, n_rows + (-n_rows) % 8)
    n_in = min(_SCATTER_IN, n + (-n) % 8)
    n_pad = n + (-n) % n_in
    x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    idx = jnp.pad(idx.astype(jnp.int32), (0, n_pad - n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(n_rows, tile), n_pad // n_in),
        in_specs=[pl.BlockSpec((n_in, D), lambda t, j, idx: (j, 0))],
        out_specs=pl.BlockSpec((tile, D), lambda t, j, idx: (t, 0)),
    )
    return pl.pallas_call(
        functools.partial(_scatter_add_kernel, n=n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idx, x)

"""Embedding lookups: dedup path and the sharded shard_map exchanges.

Dedup (unique -> gather -> inverse-scatter) exploits the Zipfian id
distribution of recsys batches: a batch of B ids hits U <= B unique rows,
so the gather moves U rows and — under the row-sharded plans — the psum
exchanges U-row payloads instead of B-row ones.  ``jnp.unique(size=...)``
keeps everything statically shaped (sentinel-padded) for jit.

The sharded lookups run the whole (gather + exchange) inside ``shard_map``
so the collectives appear explicitly in the compiled HLO and
``analysis/hlo_cost.py`` can count their bytes:

* ``row``      — each device owns a vocab slice; masked local gather, then
                 ``psum`` of the (U, D) partials over the row axis.
* ``col``      — DLRM-style: features sharded over the DP ranks; ids are
                 all-gathered over the col axis, each rank computes its
                 column slice for the whole global batch, and an
                 ``all_to_all`` swaps batch-slices for column-slices.
* ``row_col``  — both: masked gather, psum over rows, all_to_all over cols.

Gradients flow through the transposed collectives automatically (psum's
transpose is free, all_to_all's is all_to_all), so a table shard's gradient
lands on its owner without any dense full-table exchange.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.embeddings.table import EmbedPlan, EmbedSpec, pspec
from repro.kernels import ops


def dedup_ids(ids: jnp.ndarray, cap: Optional[int] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(unique ids (cap,), inverse (n,)) with sentinel padding (repeats of
    the smallest id) — ``u[inv]`` reconstructs ``ids`` exactly."""
    flat = ids.reshape(-1)
    u, inv = jnp.unique(flat, return_inverse=True,
                        size=cap or flat.shape[0])
    return u, inv.reshape(-1)


def dedup_lookup(table: jnp.ndarray, ids: jnp.ndarray,
                 use_kernel: bool = False) -> jnp.ndarray:
    """``table[ids]`` via unique -> gather -> inverse-scatter.

    Bit-identical to the direct gather; moves U <= n rows.  With
    ``use_kernel`` the gather is the Pallas scalar-prefetch DMA kernel
    (``kernels/embedding_ops.py``); default is the jnp gather, which keeps
    lowering-path HLO clean for the cost analyzer.
    """
    u, inv = dedup_ids(ids)
    rows = ops.embedding_gather(table, u) if use_kernel else table[u]
    return rows[inv].reshape(ids.shape + (table.shape[-1],))


# ---------------------------------------------------------------------------
# sharded lookups
# ---------------------------------------------------------------------------

def _local_gather(tshard, u, plan: EmbedPlan):
    """Gather the shard's slice of rows ``u`` (global ids), masking rows
    another shard owns; psum over the row axis completes them."""
    if plan.row_axis is None:
        return tshard[u]
    vr = tshard.shape[0]
    lo = jax.lax.axis_index(plan.row_axis) * vr
    local = u - lo
    own = (local >= 0) & (local < vr)
    rows = jnp.where(own[:, None],
                     tshard[jnp.clip(local, 0, vr - 1)],
                     jnp.zeros((), tshard.dtype))
    return jax.lax.psum(rows, plan.row_axis)


def sharded_lookup_body(tshard: jnp.ndarray, ids_loc: jnp.ndarray,
                        plan: EmbedPlan) -> jnp.ndarray:
    """The per-device lookup, for use *inside* shard_map: local table
    shard + local ids -> (B_loc, D) complete embeddings.  Composable into
    larger shard_map'd steps (the DP trainer, the benchmark payload)."""
    q = (jax.lax.all_gather(ids_loc, plan.col_axis, axis=0, tiled=True)
         if plan.col_axis else ids_loc)
    if plan.dedup:
        u, inv = dedup_ids(q)
    else:
        u, inv = q, jnp.arange(q.shape[0])
    rows = _local_gather(tshard, u, plan)              # (U, Dc)
    out = rows[inv]                                    # (Bq, Dc)
    if plan.col_axis:
        # (B_glob, D/nc): swap batch-slices for column-slices
        out = jax.lax.all_to_all(out, plan.col_axis, split_axis=0,
                                 concat_axis=1, tiled=True)
    return out                                         # (B_loc, D)


def make_sharded_lookup(mesh: Mesh, spec: EmbedSpec, plan: EmbedPlan,
                        dp_axis: str = "data"):
    """Returns jitted ``lookup(table, ids) -> (B, D)``.

    ``table`` is the global (rows, dim) array (placed by ``in_shardings``
    from the plan's PartitionSpec); ``ids`` is the global (B,) id vector,
    sharded over ``dp_axis``.  The result is (B, D), batch-sharded over
    ``dp_axis`` and replicated over the table axes.
    """
    if plan.col_axis is not None and plan.col_axis != dp_axis:
        raise ValueError(
            f"col sharding must use the DP axis (got col_axis="
            f"{plan.col_axis!r}, dp_axis={dp_axis!r}): the all-to-all "
            f"swaps batch slices for column slices across DP ranks")
    del spec                            # shapes come from the shards

    fn = shard_map(partial(sharded_lookup_body, plan=plan), mesh=mesh,
                   in_specs=(pspec(plan), P(dp_axis)),
                   out_specs=P(dp_axis, None),
                   check_vma=False)
    return jax.jit(fn)


def replicated_lookup(table: jnp.ndarray, ids: jnp.ndarray,
                      dedup: bool = True) -> jnp.ndarray:
    """The baseline every plan is checked against: plain (optionally
    deduped) gather on a replicated table."""
    return dedup_lookup(table, ids) if dedup else table[ids]

"""Hierarchical All-Reduce (paper C5, §III.B) via shard_map.

The paper's rack hierarchy maps to TPU pod locality: gradients are
reduce-scattered over the fast intra-pod ``data`` axis, all-reduced over the
slow cross-pod ``pod`` axis on the 1/P-sized shard, then all-gathered back
intra-pod.  Versus a flat all-reduce over (pod x data), the cross-pod link —
the bandwidth bottleneck — carries 1/16th of the bytes.

These functions run *inside* ``shard_map`` over the dp axes (the DP-pure
training path, mirroring the paper's 8-GPU setup), or standalone through
``dp_gradient_sync`` which wraps a gradient pytree.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map



def _pad_to(x: jnp.ndarray, mult: int) -> Tuple[jnp.ndarray, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.pad(x, (0, pad))
    return x, pad


def flat_allreduce_mean(g: jnp.ndarray, axes) -> jnp.ndarray:
    """Baseline: single all-reduce over all dp axes (ring over the flat
    communicator — the paper's 'synchronous DP' Eq. 8)."""
    return jax.lax.pmean(g, axes)


def hierarchical_allreduce_mean(g: jnp.ndarray, intra_axis: str = "data",
                                inter_axis: Optional[str] = "pod"):
    """reduce-scatter(intra) -> all-reduce(inter) -> all-gather(intra)."""
    shape = g.shape
    flat = g.reshape(-1)
    n_intra = jax.lax.axis_size(intra_axis)
    flat, pad = _pad_to(flat, n_intra)
    shard = jax.lax.psum_scatter(flat, intra_axis, scatter_dimension=0,
                                 tiled=True)
    total = n_intra
    if inter_axis is not None:
        shard = jax.lax.psum(shard, inter_axis)
        total *= jax.lax.axis_size(inter_axis)
    out = jax.lax.all_gather(shard, intra_axis, axis=0, tiled=True)
    if pad:
        out = out[:-pad]
    return out.reshape(shape) / total


def make_sync_fn(mode: str, intra_axis: str = "data",
                 inter_axis: Optional[str] = None):
    """Leaf-wise gradient synchronizer for use *inside* a shard_map'd train
    step.  mode: 'flat' (Eq. 8) | 'hierarchical' (C5)."""
    axes = (intra_axis,) + ((inter_axis,) if inter_axis else ())

    def sync(g):
        if mode == "flat":
            return flat_allreduce_mean(g, axes)
        if mode == "hierarchical":
            return hierarchical_allreduce_mean(g, intra_axis, inter_axis)
        raise ValueError(mode)

    return lambda grads: jax.tree.map(sync, grads)

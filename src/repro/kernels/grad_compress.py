"""Pallas TPU kernels for 1-bit (EF-signSGD) gradient compression — paper
Eq. 10.  Bit packing is expressed as an 8-sublane weighted reduction so it
vectorizes on the VPU (the TPU analogue of a CUDA warp-ballot pack).

Layout contract (matches ``ref.onebit_quantize``): the flat gradient of size
N (N % 8 == 0) is viewed as (8, M) with M = N // 8; ``packed[j]`` holds the 8
sign bits of column j; one f32 L1 scale per ``block`` columns.  Inside the
kernels a scale travels as one lane-broadcast ``(1, 1, 128)`` tile, the
smallest block the TPU's tiling accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128


def _quant_kernel(g_ref, packed_ref, scale_ref, *, block: int):
    g = g_ref[...]                                         # (8, block) f32
    bits = (g >= 0).astype(jnp.int32)
    w = jax.lax.broadcasted_iota(jnp.int32, (8, block), 0)
    weights = jnp.left_shift(jnp.ones_like(w), w)          # 2^row
    packed = jnp.sum(bits * weights, axis=0)               # (block,) int32
    packed_ref[...] = packed[None, :].astype(jnp.uint8)
    scale_ref[...] = jnp.full(scale_ref.shape, jnp.mean(jnp.abs(g)))


def _dequant_kernel(packed_ref, scale_ref, g_ref, *, block: int):
    packed = packed_ref[...].astype(jnp.int32)             # (1, block)
    j = jax.lax.broadcasted_iota(jnp.int32, (8, block), 0)
    bits = jnp.right_shift(jnp.broadcast_to(packed, (8, block)), j) & 1
    signs = 2.0 * bits.astype(jnp.float32) - 1.0
    g_ref[...] = signs * scale_ref[0][:, :1]


def onebit_quantize(g2d: jnp.ndarray, block: int = 512, interpret=False):
    """g2d: (8, M) f32 -> (packed (M,) uint8, scales (M/block,) f32)."""
    _, M = g2d.shape
    assert M % block == 0, (M, block)
    nb = M // block
    packed, scales = pl.pallas_call(
        functools.partial(_quant_kernel, block=block),
        grid=(nb,),
        in_specs=[pl.BlockSpec((8, block), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, M), jnp.uint8),
            jax.ShapeDtypeStruct((nb, 1, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(g2d)
    return packed[0], scales[:, 0, 0]


def onebit_dequantize(packed: jnp.ndarray, scales: jnp.ndarray,
                      block: int = 512, interpret=False):
    """packed (M,) uint8, scales (M/block,) -> (8, M) f32."""
    M = packed.shape[0]
    nb = M // block
    g = pl.pallas_call(
        functools.partial(_dequant_kernel, block=block),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((8, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, M), jnp.float32),
        interpret=interpret,
    )(packed[None, :], jnp.broadcast_to(scales[:, None, None],
                                        (nb, 1, _LANES)))
    return g

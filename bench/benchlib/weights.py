"""Weights from the seed, made by the benchmark in the program's layout.

The configuration's own module (``spec.load_reference``) says what the
leaves are and how each is drawn (``init_params(c, key)``); here is only
what every layout shares: the key from the seed, and one jitted call that
builds every leaf on the device, in the type it is served in, placed by
``out_shardings``.  The reference reads the same arrays; the program is
handed them and never makes its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchlib.spec import load_reference


def padded_vocab(c: dict) -> int:
    p = c["vocab_pad_to"]
    return (c["vocab_size"] + p - 1) // p * p


def seed_key(lo, hi):
    """A key from both 32-bit halves of a seed of up to 64 bits."""
    k = jax.random.fold_in(jax.random.key(0), lo)
    return jax.random.fold_in(k, hi)


def seed_halves(seed: int):
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def make_params(c: dict, seed: int, out_shardings=None):
    """The configuration's weights for ``seed``, on the device."""
    init = load_reference(c).init_params
    fn = jax.jit(lambda lo, hi: init(c, seed_key(lo, hi)),
                 out_shardings=out_shardings)
    return fn(*seed_halves(seed))


def shapes(c: dict):
    """The weights' tree of ``jax.ShapeDtypeStruct``, nothing computed."""
    init = load_reference(c).init_params
    return jax.eval_shape(lambda: init(c, jax.random.key(0)))

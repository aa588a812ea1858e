"""Weights from the seed, made by the benchmark in the program's layout.

One jitted call builds every leaf on the device, in the type it is
served in: normal(0, 1/sqrt(fan_in)) projections, a normal(0, 0.02)
embedding whose padding rows past ``vocab_size`` are zero (rows no token
ever trains), and ``(1 + scale)`` norm weights with scale normal(0, 0.1)
where the norm has one.  The reference reads the same arrays; the
program is handed them and never makes its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def padded_vocab(c: dict) -> int:
    p = c["vocab_pad_to"]
    return (c["vocab_size"] + p - 1) // p * p


def _norm(key, c, d):
    if c["norm"] == "nonparam_layernorm":
        return {}
    return {"scale": 0.1 * jax.random.normal(key, (d,), jnp.float32)}


def _dense(key, shape, dtype):
    w = jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(shape[-2])
    return w.astype(dtype)


def _init(c: dict, key):
    dt = jnp.dtype(c["dtype"])
    L, d = c["num_hidden_layers"], c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    f = c["intermediate_size"]
    V = padded_vocab(c)
    ks = iter(jax.random.split(key, 16))
    emb = 0.02 * jax.random.normal(next(ks), (V, d), jnp.float32)
    emb = jnp.where(jnp.arange(V)[:, None] < c["vocab_size"], emb, 0.0)
    stack = lambda k, n: jax.vmap(lambda kk: _norm(kk, c, n))(  # noqa: E731
        jax.random.split(k, L))
    return {
        "embed": emb.astype(dt),
        "final_norm": _norm(next(ks), c, d),
        "blocks": {
            "attn": {"norm": stack(next(ks), d),
                     "wq": _dense(next(ks), (L, d, q), dt),
                     "wk": _dense(next(ks), (L, d, kv), dt),
                     "wv": _dense(next(ks), (L, d, kv), dt),
                     "wo": _dense(next(ks), (L, q, d), dt)},
            "ffn": {"norm": stack(next(ks), d),
                    "mlp": {"wi_gate": _dense(next(ks), (L, d, f), dt),
                            "wi_up": _dense(next(ks), (L, d, f), dt),
                            "wo": _dense(next(ks), (L, f, d), dt)}},
        },
    }


def seed_key(lo, hi):
    """A key from both 32-bit halves of a seed of up to 64 bits."""
    k = jax.random.fold_in(jax.random.key(0), lo)
    return jax.random.fold_in(k, hi)


def seed_halves(seed: int):
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def make_params(c: dict, seed: int, out_shardings=None):
    """The configuration's weights for ``seed``, on the device."""
    fn = jax.jit(lambda lo, hi: _init(c, seed_key(lo, hi)),
                 out_shardings=out_shardings)
    return fn(*seed_halves(seed))

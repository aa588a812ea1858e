"""The dense decoders (olmo-1b, recllm-base): their plain reference, and
what follows from their layer layout (the benchmark's weights in the
program's layout, the program's fields, and the operations and bytes of
each call).

Pre-norm decoder: token embedding, then per layer
``x += Wo attn(rope(norm(x) Wq), rope(norm(x) Wk), norm(x) Wv)`` with a
causal softmax over ``q.k / sqrt(head_dim)``, and
``x += W2 (silu(norm(x) W1) * norm(x) W3)``; a final norm and logits
against the tied embedding.  Rotary embeddings rotate the two halves of
each head (``rotate_half``), as OLMo's published code does.  ``norm`` is
the configuration's: ``nonparam_layernorm`` (OLMo: no scale, no bias) or
``rmsnorm`` with a ``(1 + scale)`` weight.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``, no kernels, no
cache, no batching tricks; it imports nothing of the program under test.
Weights are the benchmark's own, in the layout ``weights.make_params``
builds (the embedding table may carry padding rows past ``vocab_size``;
they take part in the softmax as any row does).

``dtype`` and ``quant`` exist for the control only: the same mathematics
with matmul inputs rounded to ``dtype``, and with ``quant`` both operands
of every weight matmul rounded to int8 (weights per output channel,
activations per row), as an int8 matmul path would.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from benchlib.weights import padded_vocab

HIGHEST = jax.lax.Precision.HIGHEST
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "norm",
        "norm_eps", "rope_theta")


def as_run(c: dict) -> dict:
    """The configuration as the program runs it: the published values, with
    each departure the file states under ``departures`` in its place."""
    return {**c, **c.get("departures", {})}


def frozen(c: dict) -> tuple:
    """The configuration's keys this reference reads, hashable."""
    c = as_run(c)
    return tuple((k, c[k]) for k in KEYS)


def _q8(w, axis: int):
    """int8 round trip with one scale per slice along ``axis``; gradients
    pass straight through the rounding, as in int8 training."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.round(w / s).clip(-127, 127) * s
    return w + jax.lax.stop_gradient(q - w)


class Math:
    """Matmul and norm at one precision."""

    def __init__(self, c: dict, dtype=jnp.float32, quant: bool = False):
        self.c, self.dtype, self.quant = as_run(c), dtype, quant
        self.prec = HIGHEST if dtype == jnp.float32 else None

    def mm(self, x, w):
        w = w.astype(jnp.float32)
        if self.quant:
            x, w = _q8(x, -1), _q8(w, -2)
        return jnp.matmul(x.astype(self.dtype), w.astype(self.dtype),
                          precision=self.prec,
                          preferred_element_type=jnp.float32)

    def norm(self, p, x):
        eps = self.c["norm_eps"]
        if self.c["norm"] == "nonparam_layernorm":
            mu = jnp.mean(x, -1, keepdims=True)
            var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
            return (x - mu) / jnp.sqrt(var + eps)
        if self.c["norm"] == "rmsnorm":
            ms = jnp.mean(jnp.square(x), -1, keepdims=True)
            return x / jnp.sqrt(ms + eps) * (1.0 + p["scale"])
        raise ValueError(self.c["norm"])


def rope(x, pos, theta: float):
    """x (B, S, H, D); pos (B, S)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m: Math, x, blk, pos):
    c = m.c
    B, S, _ = x.shape
    H, Hk, D = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    a = blk["attn"]
    h = m.norm(a["norm"], x)
    q = m.mm(h, a["wq"]).reshape(B, S, H, D)
    k = m.mm(h, a["wk"]).reshape(B, S, Hk, D)
    v = m.mm(h, a["wv"]).reshape(B, S, Hk, D)
    q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    k = jnp.repeat(k, H // Hk, axis=2)
    v = jnp.repeat(v, H // Hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(m.dtype), k.astype(m.dtype),
                   precision=m.prec, preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(m.dtype), v.astype(m.dtype),
                   precision=m.prec, preferred_element_type=jnp.float32)
    x = x + m.mm(o.reshape(B, S, H * D), a["wo"])
    f = blk["ffn"]
    h = m.norm(f["norm"], x)
    mlp = f["mlp"]
    g = jax.nn.silu(m.mm(h, mlp["wi_gate"])) * m.mm(h, mlp["wi_up"])
    return x + m.mm(g, mlp["wo"])


def hidden(m: Math, w: dict, tokens):
    """tokens (B, S) -> final-normed hidden states (B, S, d), float32."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = w["embed"].astype(jnp.float32)[tokens]

    def body(x, blk):
        return _layer(m, x, blk, pos), None

    x, _ = jax.lax.scan(body, x, w["blocks"])
    return m.norm(w["final_norm"], x)


def logits(m: Math, w: dict, h):
    """h (..., d) -> logits over every row of the (tied) embedding."""
    return m.mm(h, w["embed"].T)


@functools.partial(jax.jit, static_argnames=("c", "dtype", "quant"))
def logits_at(c, w, tokens, positions, dtype=jnp.float32, quant=False):
    """Logits (B, K, V) at ``positions`` (B, K) of each row of tokens;
    ``c`` is :func:`frozen` of the configuration."""
    m = Math(dict(c), dtype, quant)
    h = hidden(m, w, tokens)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    return logits(m, w, h)


def loss(c: dict, w: dict, batch: dict, dtype=jnp.float32, quant=False):
    """Mean next-token cross entropy over the masked positions."""
    m = Math(c, dtype, quant)
    h = hidden(m, w, batch["tokens"])
    lg = logits(m, w, h)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, batch["targets"][..., None], -1)[..., 0]
    mask = batch["mask"]
    return jnp.sum((lse - gold) * mask), jnp.sum(mask)


# -- the layout: weights, the program's fields, costs ------------------------

def _norm(key, c, d):
    if c["norm"] == "nonparam_layernorm":
        return {}
    return {"scale": 0.1 * jax.random.normal(key, (d,), jnp.float32)}


def _dense(key, shape, dtype):
    w = jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(shape[-2])
    return w.astype(dtype)


def init_params(c: dict, key):
    """The weights in the program's layout, every leaf from ``key``:
    normal(0, 1/sqrt(fan_in)) projections, a normal(0, 0.02) embedding
    whose padding rows past ``vocab_size`` are zero (rows no token ever
    trains), and ``(1 + scale)`` norm weights with scale normal(0, 0.1)
    where the norm has one; bf16 where the configuration serves bf16."""
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


def program_fields(c: dict) -> dict:
    """The attributes the program's registered architecture must have."""
    return {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
            "tie_embeddings": c["tie_word_embeddings"], "dtype": c["dtype"],
            "vocab_pad_to": c["vocab_pad_to"],
            "norm_type": {"nonparam_layernorm": "nonparam_ln",
                          "rmsnorm": "rmsnorm"}[c["norm"]]}


def layer_matmul_params(c: dict) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return c["num_hidden_layers"] * (2 * d * q + 2 * d * kv + 3 * d * f)


def head_params(c: dict) -> int:
    return padded_vocab(c) * c["hidden_size"]


def _qdim(c: dict) -> int:
    return c["num_attention_heads"] * c["head_dim"]


def prefill_flops(c: dict, n: int) -> float:
    """A prompt of ``n`` tokens: every layer at every position, causal
    attention over n(n+1)/2 pairs, the LM head at the last position."""
    L = c["num_hidden_layers"]
    return (2.0 * layer_matmul_params(c) * n
            + 2.0 * L * _qdim(c) * n * (n + 1)
            + 2.0 * head_params(c))


def decode_flops(c: dict, rows: Sequence[int]) -> float:
    """One decode step; ``rows[i]`` is the context slot i attends,
    its new token included."""
    L = c["num_hidden_layers"]
    per_tok = 2.0 * (layer_matmul_params(c) + head_params(c))
    return per_tok * len(rows) + 4.0 * L * _qdim(c) * float(sum(rows))


def decode_attn_bytes(c: dict, rows: Sequence[int],
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step's attention must move over all layers: the
    K and V rows of each live prefix, each query read and output written."""
    L = c["num_hidden_layers"]
    Hk, D = c["num_key_value_heads"], c["head_dim"]
    kv = 2.0 * Hk * D * kv_bytes * float(sum(rows))
    qo = 2.0 * _qdim(c) * 2 * len(rows)
    return L * (kv + qo)


def train_flops(c: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step (3x the forward), recomputation
    not counted: matmuls, the LM head at every position, causal
    attention."""
    L = c["num_hidden_layers"]
    fwd = (2.0 * (layer_matmul_params(c) + head_params(c))
           * batch * seq
           + 2.0 * L * _qdim(c) * seq * (seq + 1) * batch)
    return 3.0 * fwd

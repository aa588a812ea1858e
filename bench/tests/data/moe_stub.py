"""A stub configuration module with a layer layout other than the dense
decoder's: the program's sparse-expert decoder (``moe-stub.json``), each
layer's feed-forward a router over E experts whose weights are
``(E, d, f)``, and an LM head apart from the embedding.  It is in no cell
and has no reference forward pass: it shows that a layout comes to the
benchmark as files alone (weights, the program's fields, the costs)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchlib.weights import padded_vocab


def _dense(key, shape, dtype):
    w = jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(shape[-2])
    return w.astype(dtype)


def init_params(c: dict, key):
    """Normal(0, 1/sqrt(fan_in)) projections, a normal(0, 0.02) embedding,
    RMSNorm scales at zero (the program's ``(1 + scale)``)."""
    dt = jnp.dtype(c["dtype"])
    L, d = c["num_hidden_layers"], c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    E, f = c["n_routed_experts"], c["moe_intermediate_size"]
    V = padded_vocab(c)
    ks = iter(jax.random.split(key, 16))
    norm = lambda *s: {"scale": jnp.zeros(s + (d,), jnp.float32)}  # noqa: E731
    return {
        "embed": (0.02 * jax.random.normal(next(ks), (V, d))).astype(dt),
        "lm_head": _dense(next(ks), (d, V), dt),
        "final_norm": norm(),
        "blocks": {
            "attn": {"norm": norm(L),
                     "wq": _dense(next(ks), (L, d, q), dt),
                     "wk": _dense(next(ks), (L, d, kv), dt),
                     "wv": _dense(next(ks), (L, d, kv), dt),
                     "wo": _dense(next(ks), (L, q, d), dt)},
            "ffn": {"norm": norm(L),
                    "moe": {"router": _dense(next(ks), (L, d, E),
                                             jnp.float32),
                            "wi_gate": _dense(next(ks), (L, E, d, f), dt),
                            "wi_up": _dense(next(ks), (L, E, d, f), dt),
                            "wo": _dense(next(ks), (L, E, f, d), dt)}},
        },
    }


def program_fields(c: dict) -> dict:
    return {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["moe_intermediate_size"],
            "num_experts": c["n_routed_experts"],
            "experts_per_token": c["num_experts_per_tok"],
            "vocab_size": c["vocab_size"], "vocab_pad_to": c["vocab_pad_to"],
            "tie_embeddings": c["tie_word_embeddings"],
            "norm_type": c["norm"], "rope_theta": c["rope_theta"],
            "dtype": c["dtype"]}


def train_flops(c: dict, batch: int, seq: int) -> float:
    """3x the forward: each token through the attention projections, the
    router and its top-k experts only, the LM head; causal attention."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    E, f = c["n_routed_experts"], c["moe_intermediate_size"]
    k = c["num_experts_per_tok"]
    per_tok = (L * (2 * d * q + 2 * d * kv + d * E + k * 3 * d * f)
               + padded_vocab(c) * d)
    fwd = (2.0 * per_tok * batch * seq
           + 2.0 * L * q * seq * (seq + 1) * batch)
    return 3.0 * fwd

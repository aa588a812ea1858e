"""Operations and bytes a call needs, worked out from its shapes.

These count what the mathematics requires, not what an implementation
happens to do: an LM head only where a logit is used, causal attention
over the pairs it attends, the KV rows of the live prefix only.  So a
roofline or utilization share built on them cannot pass 100% unless the
time leaves out part of the work.
"""
from __future__ import annotations

from typing import Sequence


def layer_matmul_params(c: dict) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return c["num_hidden_layers"] * (2 * d * q + 2 * d * kv + 3 * d * f)


def head_params(c: dict, padded_vocab: int) -> int:
    return padded_vocab * c["hidden_size"]


def _qdim(c: dict) -> int:
    return c["num_attention_heads"] * c["head_dim"]


def _vocab(c: dict) -> int:
    p = c["vocab_pad_to"]
    return (c["vocab_size"] + p - 1) // p * p


def prefill_flops(c: dict, n: int) -> float:
    """A prompt of ``n`` tokens: every layer at every position, causal
    attention over n(n+1)/2 pairs, the LM head at the last position."""
    L = c["num_hidden_layers"]
    return (2.0 * layer_matmul_params(c) * n
            + 2.0 * L * _qdim(c) * n * (n + 1)
            + 2.0 * head_params(c, _vocab(c)))


def decode_flops(c: dict, rows: Sequence[int]) -> float:
    """One decode step; ``rows[i]`` is the context slot i attends,
    its new token included."""
    L = c["num_hidden_layers"]
    per_tok = 2.0 * (layer_matmul_params(c) + head_params(c, _vocab(c)))
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
    fwd = (2.0 * (layer_matmul_params(c) + head_params(c, _vocab(c)))
           * batch * seq
           + 2.0 * L * _qdim(c) * seq * (seq + 1) * batch)
    return 3.0 * fwd

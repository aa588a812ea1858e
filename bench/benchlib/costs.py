"""Operations and bytes a call needs, worked out from its shapes.

These count what the mathematics requires, not what an implementation
happens to do: an LM head only where a logit is used, causal attention
over the pairs it attends, the KV rows of the live prefix only.  So a
roofline or utilization share built on them cannot pass 100% unless the
time leaves out part of the work.

The counts follow the layer layout, so each configuration's own module
(``spec.load_reference``) defines them; these are the calls the harness
makes, with the same names and arguments.
"""
from __future__ import annotations

from typing import Sequence

from benchlib.spec import load_reference


def prefill_flops(c: dict, n: int) -> float:
    """A prompt of ``n`` tokens, its logits at the last position only."""
    return load_reference(c).prefill_flops(c, n)


def decode_flops(c: dict, rows: Sequence[int]) -> float:
    """One decode step; ``rows[i]`` is the context slot i attends,
    its new token included."""
    return load_reference(c).decode_flops(c, rows)


def decode_attn_bytes(c: dict, rows: Sequence[int],
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step's attention must move over all layers."""
    return load_reference(c).decode_attn_bytes(c, rows, kv_bytes)


def train_flops(c: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step, recomputation not counted."""
    return load_reference(c).train_flops(c, batch, seq)
